"""The expert layers' grouped matmuls' summed device time over device
busy time, all chips together (``lib/xing4_kernels.py`` tells them by
their rank-3 stack of expert weights). A trace without them gives
nothing."""
from lib import xing4_kernels as xk


def read(record, cell):
    trace = record.get("trace")
    if not trace:
        return None
    busy = sum(c["busy_s"] for c in trace["chips"].values())
    calls, spent = xk.moe_gmm_calls(trace, cell.config)
    return 100.0 * spent / busy if busy and calls else None
