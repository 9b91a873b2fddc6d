"""Share of the serving step's device time in phases ``embed``, ``attn.proj``
and ``ffn``: the norms, projections and dense MLPs, which run over every
row of the token budget whatever is live (``lib/phases.py``)."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "serve", ("embed", "attn.proj", "ffn"))
