"""Share of the serving step's device time in the expert layers' glue:
phases ``moe.route``, ``moe.dispatch`` (the sort and the gather into
rows), ``moe.act`` (``silu(g) * u`` between the grouped matmuls) and
``moe.combine`` (``lib/phases.py``). The grouped matmuls themselves are
``kernels.moe_gmm_share.serve``."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "serve", (
        "moe.route", "moe.dispatch", "moe.act", "moe.combine"))
