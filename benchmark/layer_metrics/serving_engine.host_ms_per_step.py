"""The host's part of a serving step: median, over the program's
``serving.step`` spans of the window that ran a batch, of the step's
duration less the ``serving.device_wait`` span inside it (the host
blocked on ``np.asarray(nxt)``). What is left is scheduling, packing,
the transfers, the enqueue and the emission of tokens, all under the
engine's lock. The spans carry no ids, so a child is matched to its
step by time: ``step.ts <= ts < step.ts + step.dur``. A program without
``serving.device_wait`` gives nothing to read."""
from lib import stats


def read(record, cell):
    spans = record.get("spans", ())
    waits = sorted((s["ts"], s["dur"]) for s in spans
                   if s["name"] == "serving.device_wait")
    steps = sorted((s["ts"], s["dur"]) for s in spans
                   if s["name"] == "serving.step")
    host, k = [], 0
    for ts, dur in steps:
        while k < len(waits) and waits[k][0] < ts:
            k += 1
        if k < len(waits) and waits[k][0] < ts + dur:
            host.append((dur - waits[k][1]) / 1e3)
    return stats.median(host) if host else None
