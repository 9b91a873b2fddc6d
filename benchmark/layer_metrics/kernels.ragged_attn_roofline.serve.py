"""Share of its HBM roofline the ragged paged-attention kernel reaches in
the traced steps: the least time the chip could take to read the KV
pages the traced calls had to read, over their summed device time.

Bytes (``lib/serve_bytes.py``): a call reads the live pages of its cache
layer, keys and values, all KV heads a page; the live pages of a step are
the program's count on its ``serving.ragged_step`` span (the mean over
the steps of the traced part of the window), the page's shape is the
kernel's own pool operand in the trace, and the calls are counted in the
trace. The kernel is bound by memory at these shapes (a decode visit
does 2 x 2 x 128 FLOP a KV element of 2 bytes, far under the v5e's 240
FLOP a byte); the chunk's visits do more, and still less than the bound.
A program whose span lacks ``live_pages`` or ``cache_layers`` gives
nothing to read."""
from lib import serve_bytes


def read(record, cell):
    trace = record.get("trace")
    steps = serve_bytes.traced_steps(record, cell)
    if not trace or not cell.peaks or not steps:
        return None
    calls, spent, page = serve_bytes.ragged_kernel_calls(trace)
    if not spent:
        return None
    live = sum(s["args"]["live_pages"] for s in steps) / len(steps)
    least = serve_bytes.ragged_attn_bytes(live, calls, page) \
        / cell.peaks["hbm_bytes_per_s"]
    cell.log("ragged_attn_roofline: %d calls (%.1f steps of %d cache "
             "layers), %.2f live pages a call of %d bytes; least %.6f s "
             "of %.6f s spent"
             % (calls, calls / steps[0]["args"]["cache_layers"],
                steps[0]["args"]["cache_layers"], live, page, least,
                spent))
    return 100.0 * least / spent
