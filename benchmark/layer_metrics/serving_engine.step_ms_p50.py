"""Median duration of the program's ``serving.step`` spans inside the
window: one scheduler round under the engine's lock, from admission to
the tokens' emission, the wait for the device (``np.asarray(nxt)``)
included. (``serving.ragged_step`` closes before that wait: on the chip
it reads 6 ms of a 90 ms step, so it is not the step's time.) Telemetry
is on in the traced run only."""
from lib import stats


def read(record, cell):
    xs = [s["dur"] / 1e3 for s in record.get("spans", ())
          if s["name"] == "serving.step"]
    return stats.median(xs) if xs else None
