"""Share of its roofline the state-space layers' scan reaches in the
traced steps: the least time the chip could take for the traced steps'
scans (``lib/ssm_kernels.py``: the larger of the live rows' states read
and written once over the HBM peak, and the recurrence's FLOPs over the
bf16 peak) over the device seconds of phase ``ssm.scan`` (``lib/
phases.py``). The counts are the means of the traced steps'
``serving.ragged_step`` spans, times the steps the phase table counted.
A program whose span lacks the state attributes, or whose trace has no
``ssm.scan`` phase, gives nothing to read."""
from lib import phases, ssm_kernels as sk


def read(record, cell):
    steps = sk.scan_steps(record, cell)
    if not record.get("trace") or not cell.peaks or not steps:
        return None
    spent = sk.phase_seconds(record, cell, ("ssm.scan",))
    traced = phases.table(record, cell, "serve")["steps"] if spent else 0
    if not spent or not traced:
        return None
    least = sum(sk.scan_least_s(s["args"], cell.config, cell.peaks)
                for s in steps) / len(steps)
    cell.log("ssm_scan_roofline: %d steps in the phase table; least "
             "%.6f s a step, %.6f s spent a step"
             % (traced, least, spent / traced))
    return 100.0 * least * traced / spent
