"""Share of its roofline the windowed attention layers' kernel reaches in
the traced steps: the least time the chip could take for the traced
steps' windowed calls (``lib/window_kernels.py``: the larger of the pages
inside the rows' windows, queries and outputs over the HBM peak, and the
(query, key seen) pairs' FLOPs over the bf16 peak) over the device
seconds of phase ``attn.window`` (``lib/phases.py``). The counts are the
means of the traced steps' ``serving.ragged_step`` spans, times the steps
the phase table counted. A program whose span lacks the window
attributes, or whose trace has no ``attn.window`` phase, gives nothing to
read."""
from lib import phases, window_kernels as wk


def read(record, cell):
    steps = wk.window_steps(record, cell)
    if not record.get("trace") or not cell.peaks or not steps:
        return None
    spent = wk.phase_seconds(record, cell, wk.PHASES)
    traced = phases.table(record, cell, "serve")["steps"] if spent else 0
    if not spent or not traced:
        return None
    least = sum(wk.window_least_s(s["args"], cell.config, cell.peaks)
                for s in steps) / len(steps)
    cell.log("window_attn_roofline: %d steps in the phase table; least "
             "%.6f s a step, %.6f s spent a step"
             % (traced, least, spent / traced))
    return 100.0 * least * traced / spent
