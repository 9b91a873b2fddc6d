"""Time device 0's ``XLA Ops`` line spends in all-reduce, all-gather,
reduce-scatter, collective-permute and all-to-all operations (their
-start/-done included) over the traced window. That line holds what the
core executes, so this is the exposed part of the collectives."""
from lib import xplane


def read(record, cell):
    trace = record.get("trace")
    if not trace or not trace["chips"]:
        return None
    chip = trace["chips"][sorted(trace["chips"])[0]]
    spent = sum(op["seconds"] for op in chip["ops"]
                if xplane.is_collective(op))
    return 100.0 * spent / chip["window_s"] if chip["window_s"] else None
