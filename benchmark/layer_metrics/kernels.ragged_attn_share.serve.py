"""The ragged paged-attention kernel's summed device time over device
busy time, all chips together."""
from lib import xplane


def read(record, cell):
    trace = record.get("trace")
    if not trace:
        return None
    busy = sum(c["busy_s"] for c in trace["chips"].values())
    spent = sum(op["seconds"] for c in trace["chips"].values()
                for op in c["ops"]
                if (xplane.classify_kernel(op) or ("",))[0]
                == "ragged_attn")
    return 100.0 * spent / busy if busy else None
