"""Share of their roofline the flash-attention forward and fused-backward
calls reach: the least time the chip could take for the traced calls
(operations and bytes from their shapes, ``lib/flops.py``, against
``lib/peaks.py``) over their summed device time, all chips together. At
head size 128 and sequence 2048 both calls are bound by compute
(512 FLOP a byte against the v5e's 240); the reader logs the bound."""
from lib import flops, xplane


def read(record, cell):
    trace = record.get("trace")
    if not trace or not cell.peaks:
        return None
    least = spent = 0.0
    bounds = set()
    for chip in trace["chips"].values():
        for op in chip["ops"]:
            kc = xplane.classify_kernel(op)
            if not kc or kc[0] not in ("flash_fwd", "flash_bwd"):
                continue
            cost = flops.flash_call_cost(kc[0], operands=op["operands"],
                                         results=op["results"], **kc[1])
            roof = flops.least_seconds(cost, cell.peaks)
            bounds.add(roof["bound"])
            least += roof["seconds"] * op["count"]
            spent += op["seconds"]
    if not spent:
        return None
    cell.log("flash_roofline: bound by %s; least %.6f s of %.6f s spent"
             % ("/".join(sorted(bounds)), least, spent))
    return 100.0 * least / spent
