"""Share of the engine's slots that hold a request, read from inside:
mean over the window's ``serving.step`` spans of
``(running + prefilling) / slots_max``, which the engine writes on the
span at each step's end from the scheduler's own state (no call waits
for the lock). A program whose step spans lack these gives nothing to
read."""


def read(record, cell):
    xs = []
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.step" and a.get("slots_max"):
            xs.append((a["running"] + a["prefilling"]) / a["slots_max"])
    return 100.0 * sum(xs) / len(xs) if xs else None
