"""Median, over the requests submitted inside the window, of the time
from the client's ``submit()`` call to its first token (a request still
unanswered at the window's end counts as slower than any). Host clock
of the benchmark's clients. An end-to-end quantity by nature, kept here
because it spreads by 46 % between runs (PERF.md, PR 24)."""
from lib import stats


def read(record, cell):
    xs = record.get("host", {}).get("ttft_ms")
    return stats.median(xs) if xs else None
