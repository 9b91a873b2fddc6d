"""Median ``queue_s`` of the access log's ``rt.request`` spans of the
window: from the request's arrival in the waiting queue (stamped under
the engine's lock, so after the wait for it, which is the record's
``lock_wait_s`` and ``serving_engine.lock_wait_ms_p50``) to its
admission into a slot. Requests the benchmark cancels at the window's
end count with what they had."""
from lib import stats


def read(record, cell):
    xs = [1e3 * s["args"]["queue_s"] for s in record.get("spans", ())
          if s["name"] == "rt.request"
          and "queue_s" in (s.get("args") or {})]
    return stats.median(xs) if xs else None
