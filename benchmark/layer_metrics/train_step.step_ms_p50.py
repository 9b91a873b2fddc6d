"""Median time of one optimizer step: the benchmark's host clock around
each dispatch, over the dispatch's steps."""
from lib import stats


def read(record, cell):
    xs = record.get("host", {}).get("step_ms")
    return stats.median(xs) if xs else None
