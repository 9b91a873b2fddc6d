"""Share of the train dispatch's device time (the first chip's self seconds
in the traced window) whose instruction belongs to a phase of the program
(``lib/phases.py``), forward and backward together."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "train")
