"""Share of the train dispatch's device time in phases ``head`` (the final
norm) and ``loss`` (the chunked LM-head cross entropy), forward and
backward (``lib/phases.py``)."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "train", ("head", "loss"))
