"""Share of the train dispatch's device time after the backward pass: phases
``grad_norm`` (the whole-model gradient norm), ``clip`` and ``optimizer``
(the update) (``lib/phases.py``)."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "train", (
        "grad_norm", "clip", "optimizer"))
