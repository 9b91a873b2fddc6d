"""Share of its roofline the latent ragged-attention kernel reaches in
the traced steps: the least time the chip could take for the traced
calls (``lib/xing4_kernels.py``: the larger of the live latent pages'
bytes, each read once, over the HBM peak, and the live (query, key)
pairs' FLOPs in the cheaper, expanded, form over the bf16 peak) over
their summed device time. The counts are the means of the traced steps'
``serving.ragged_step`` spans, a call a cache layer; the calls are
counted in the trace. A program whose span lacks ``attn_pairs`` or whose
trace has no such kernel gives nothing to read."""
from lib import xing4_kernels as xk


def read(record, cell):
    steps = xk.steps_with(record, cell, xk.ATTN_ATTRS)
    if not record.get("trace") or not cell.peaks or not steps:
        return None
    calls, spent = xk.latent_attn_calls(record["trace"], cell.config)
    if not spent:
        return None
    least = sum(xk.latent_attn_least_s(s["args"], cell.config, cell.peaks)
                for s in steps) / len(steps)
    cell.log("latent_attn_roofline: %d calls (%.1f steps of %d cache "
             "layers); least %.6f s a call, %.6f s spent a call"
             % (calls, calls / steps[0]["args"]["cache_layers"],
                steps[0]["args"]["cache_layers"], least, spent / calls))
    return 100.0 * least * calls / spent
