"""Share of the serving step's device time in phase ``head``: the final norm,
the LM head over every token of the budget, and the gather of each row's
last position (``lib/phases.py``)."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "serve", ("head",))
