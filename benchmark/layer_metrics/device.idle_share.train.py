"""1 minus the union of ``XLA Ops`` intervals over the traced window, on
the chip where that is largest."""
from lib import xplane


def read(record, cell):
    trace = record.get("trace")
    return xplane.worst_idle_share(trace) if trace else None
