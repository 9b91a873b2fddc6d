"""Did the chip wait for the host? Of the window's rounds that collected a
step, the share whose ``serving.device_wait`` (the host blocked on the
step's tokens) lasted under ``STARVED_US``: the step had ENDED before the
host came to read it, so the chip may have stood idle before the next
launch reached it. An upper bound on the rounds in which the device can
have idled (a step that ended early with the next one already queued cost
nothing), from the rounds' own clock over the whole window, where
``device.idle_share.serve`` sees only the profiler's 2 s and what the
profiler itself does to the host. A program without the span gives
nothing to read.

``STARVED_US``: on the v5e's host a read of tokens that are READY takes
0.33-0.46 ms (the least ``device_wait`` of 10,512 rounds in the five
serving cells is 0.325 ms; none is shorter), and a round whose step is
still running waits for the rest of it, milliseconds (medians 3.8-38.8
ms; PERF.md section 5 has the distributions this file logs). 0.5 ms is the ready read and a
little: a wait under it means the step had ended when the host arrived.

The same pass logs how many of the starved rounds fall inside the
profiler's part of the window (the traced run profiles ``traced_s`` from
the window's middle: there the host is two to three times slower), and
``bench: longest rounds:``: the window's longest ``serving.step`` spans
with their children's durations, those near the profiler's start and stop
marked, so that a stalled round is placed in a phase of the round (a
child is matched to its round by time, the spans carry no ids)."""
import json

from lib import stats

STARVED_US = 500.0
CUTS_US = (250, 350, 400, 450, 500, 600, 750, 1000, 2000, 5000)
CHILDREN = tuple("serving." + n for n in (
    "schedule", "build_batch", "transfer", "ragged_step", "device_wait",
    "emit"))
LONGEST = 5


def read(record, cell):
    spans = record.get("spans", ())
    waits = [s for s in spans if s["name"] == "serving.device_wait"]
    if not waits:
        return None
    # the kind starts its profiler at the window's middle; its start and
    # stop stall a round each
    t0 = min(s["ts"] for s in spans)
    lo = t0 + cell.seconds / 2 * 1e6 - 0.3e6
    hi = lo + (float(cell.traffic.get("traced_s", 2.0)) + 1.0) * 1e6
    starved = [s for s in waits if s["dur"] < STARVED_US]
    share = 100.0 * len(starved) / len(waits)
    cell.log("device_wait_ms: %s; rounds under each cut (us): %s; starved "
             "(< %g us): %.3f %% of %d rounds, %d of them in or next to the "
             "profiler's part"
             % (stats.summary([s["dur"] / 1e3 for s in waits]),
                {c: sum(s["dur"] < c for s in waits) for c in CUTS_US},
                STARVED_US, share, len(waits),
                sum(lo <= s["ts"] < hi for s in starved)))
    rounds = sorted((s for s in spans if s["name"] == "serving.step"),
                    key=lambda s: -s["dur"])[:LONGEST]
    cell.log("longest rounds: %s" % json.dumps([
        {"ms": round(r["dur"] / 1e3, 3),
         "at_s": round((r["ts"] - t0) / 1e6, 3),
         "profiler": lo <= r["ts"] < hi,
         "tokens": (r.get("args") or {}).get("tokens"),
         "children_ms": [
             [s["name"].split(".", 1)[1], round(s["dur"] / 1e3, 3)]
             for s in sorted(spans, key=lambda s: s["ts"])
             if s["name"] in CHILDREN
             and r["ts"] <= s["ts"] < r["ts"] + r["dur"]]}
        for r in rounds]))
    return share
