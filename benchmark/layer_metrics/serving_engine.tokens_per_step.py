"""Tokens a ragged step carries: the program's counters
(serving.decode_tokens + serving.prefill_tokens) / serving.ragged_steps,
each as its increase over the window."""


def read(record, cell):
    c = record.get("counters") or {}
    steps = c.get("serving.ragged_steps")
    if not steps:
        return None
    return (c.get("serving.decode_tokens", 0.0)
            + c.get("serving.prefill_tokens", 0.0)) / steps
