"""The latent ragged-attention kernel's summed device time over device
busy time, all chips together (``lib/xing4_kernels.py`` tells the kernel
by its one latent pool). A trace without the kernel gives nothing."""
from lib import xing4_kernels as xk


def read(record, cell):
    trace = record.get("trace")
    if not trace:
        return None
    busy = sum(c["busy_s"] for c in trace["chips"].values())
    calls, spent = xk.latent_attn_calls(trace, cell.config)
    return 100.0 * spent / busy if busy and calls else None
