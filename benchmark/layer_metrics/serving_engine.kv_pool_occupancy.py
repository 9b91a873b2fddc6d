"""Share of the KV pool's pages that requests in flight hold, averaged
over the window. The pages held are worked out from the clients' records
(``kinds/closed_loop.py``: prompt and tokens so far, in pages, of every
stream between ``submit()`` and its end), so nothing waits for the
engine's lock; the pool's size is the engine's ``config.num_blocks``. It
says how much of the reserved pool the traffic fills: the step copies
the rest all the same."""


def read(record, cell):
    pool = record.get("pool")
    if not pool or not pool["held"] or not pool["pages"]:
        return None
    return 100.0 * sum(pool["held"]) / len(pool["held"]) / pool["pages"]
