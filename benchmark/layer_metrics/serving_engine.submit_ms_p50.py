"""Median duration of the clients' ``ServingEngine.submit()`` calls made
inside the window: the benchmark's own span around the call into the
engine. ``submit()`` takes the engine's lock, which the step loop holds
for the whole step and re-takes at once."""
from lib import stats


def read(record, cell):
    xs = record.get("host", {}).get("submit_ms")
    return stats.median(xs) if xs else None
