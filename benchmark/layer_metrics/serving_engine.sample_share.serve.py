"""Share of the serving step's device time in phases ``sample`` (the
sampler) and ``carry`` (the tokens taken from the step before on the
device, the counts appended to the result) (``lib/phases.py``)."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "serve", ("sample", "carry"))
