"""Share of the 128-row blocks of the expert layers' grouped matmuls that
the kernel skipped because they held no (token, expert) pair: 100 x (1 -
sum ``moe_blocks_live`` / sum ``moe_blocks``) over the window's
``serving.device_wait`` spans. ``moe_blocks`` is static (the blocks of
``moe_rows``: every pair the token budget could route, and a block of
slack a held expert); ``moe_blocks_live`` is counted by the step itself
from its dispatch's layout, summed over its expert layers, and read with
the step's tokens. For a skipped block no weight tile is fetched, nothing
is multiplied and nothing written. A program whose spans lack the
attributes (one that computes every block) gives nothing to read."""


def read(record, cell):
    live = blocks = 0
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.device_wait" and a.get("moe_blocks"):
            live += a["moe_blocks_live"]
            blocks += a["moe_blocks"]
    return 100.0 * (1.0 - live / blocks) if blocks else None
