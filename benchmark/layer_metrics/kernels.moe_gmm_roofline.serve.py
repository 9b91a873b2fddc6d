"""Share of their roofline the expert layers' grouped matmuls reach in
the traced steps: the least time the chip could take for the traced
calls (``lib/xing4_kernels.py``: the larger of the touched experts'
weights plus the live pairs' rows over the HBM peak, and the live pairs'
FLOPs over the bf16 peak) over their summed device time. Two calls
(gate-and-up, down) an expert layer; the counts are the means of the
traced steps' ``serving.ragged_step`` spans. A program whose span lacks
``moe_pairs`` or whose trace has no such kernel gives nothing."""
from lib import xing4_kernels as xk


def read(record, cell):
    steps = xk.steps_with(record, cell, xk.MOE_ATTRS)
    if not record.get("trace") or not cell.peaks or not steps:
        return None
    calls, spent = xk.moe_gmm_calls(record["trace"], cell.config)
    if not spent:
        return None
    least = sum(xk.moe_gmm_least_s(s["args"], cell.config, cell.peaks)
                for s in steps) / len(steps)
    layers = calls / 2.0
    cell.log("moe_gmm_roofline: %d calls (%.1f steps of %d expert "
             "layers); least %.6f s a layer, %.6f s spent a layer"
             % (calls, layers / steps[0]["args"]["moe_layers"],
                steps[0]["args"]["moe_layers"], least, spent / layers))
    return 100.0 * least * layers / spent
