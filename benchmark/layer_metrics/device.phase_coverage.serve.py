"""Share of the serving step's device time (the first chip's self seconds in
the traced window) whose instruction belongs to a phase of the program
(``lib/phases.py``): what the phase table accounts for. The rest are
instructions the compiler made without metadata and the window's other
programs."""
from lib import phases


def read(record, cell):
    return phases.share(record, cell, "serve")
