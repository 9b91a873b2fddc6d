"""Share of the serving step's device time in the windowed attention
layers' kernel calls (phase ``attn.window``, ``lib/phases.py``; a full
layer's call is ``attn.kernel``). A program without that phase gives
nothing to read."""
from lib import phases, window_kernels as wk


def read(record, cell):
    if wk.phase_seconds(record, cell, wk.PHASES) is None:
        return None
    return phases.share(record, cell, "serve", wk.PHASES)
