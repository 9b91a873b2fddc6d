"""Share of the KV pool's pages that requests hold, read from inside:
mean over the window's ``serving.step`` spans of
``pages_in_use / pages_max``, which the engine writes on the span at
each step's end from the block manager's own count (pages neither free
nor evictable). ``serving_engine.kv_pool_occupancy`` works the same
quantity out from the clients' records. A program whose step spans lack
these gives nothing to read."""


def read(record, cell):
    xs = []
    for s in record.get("spans", ()):
        a = s.get("args") or {}
        if s["name"] == "serving.step" and a.get("pages_max"):
            xs.append(a["pages_in_use"] / a["pages_max"])
    return 100.0 * sum(xs) / len(xs) if xs else None
