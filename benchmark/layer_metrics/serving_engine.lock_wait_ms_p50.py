"""How long a client waits for the engine's lock to get one request in:
per request whose ``submit()`` began in the window, the sum of the
program's ``serving.lock_wait`` spans of that request at the sites
``submit`` and ``events`` (the same client waits for the same lock
twice, once to queue the request and once to find its stream); the
median over those requests. The step loop holds that lock for a whole
step and re-takes it at once. A program without ``serving.lock_wait``
gives nothing to read."""
from lib import stats


def read(record, cell):
    per_rid, asked = {}, set()
    for s in record.get("spans", ()):
        if s["name"] != "serving.lock_wait":
            continue
        a = s.get("args") or {}
        if a.get("site") not in ("submit", "events") or "rid" not in a:
            continue
        per_rid[a["rid"]] = per_rid.get(a["rid"], 0.0) + s["dur"] / 1e3
        if a["site"] == "submit":
            asked.add(a["rid"])
    xs = [per_rid[r] for r in asked]
    return stats.median(xs) if xs else None
