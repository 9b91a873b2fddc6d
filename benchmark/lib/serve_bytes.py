"""Bytes a serving step has to move, from its shapes and from what the
program's ``serving.ragged_step`` span says of it.

The ragged paged-attention kernel reads, in each cache layer, the live
pages of every row: keys and values, all KV heads a page. A decode-heavy
step is bound by HBM: besides those pages it streams the layer weights
once a pass over the stack, and the output head's weights once. Queries,
outputs, activations and the tokens' own new keys and values are left
out: at 6 to 304 tokens a step they are under a thousandth of the rest,
so the count is a lower bound on the bytes, and the shares built on it
cannot be flattered by it.
"""
from __future__ import annotations

from . import xplane
from .flops import DTYPE_BYTES, shape_bytes

STEP_ATTRS = ("live_pages", "cache_layers", "passes", "weight_bytes")


def kv_page_bytes(pool) -> int:
    """Keys and values of one page in one cache layer; ``pool`` is the
    kernel's pool operand ``(dtype, (n_kv, pages, block, head_dim))``."""
    dtype, (n_kv, pages, block, head_dim) = pool
    return 2 * shape_bytes((dtype, (n_kv, block, head_dim)))


def config_page_bytes(config: dict) -> int:
    """Keys and values of one page in one cache layer, from the
    configuration file alone: KV heads x the engine's ``block_size`` x
    the head's size, in the served dtype."""
    heads = int(config["num_heads"])
    n_kv = int(config.get("model_kwargs", {}).get("num_kv_heads") or heads)
    head_dim = int(config["hidden_size"]) // heads
    return 2 * n_kv * int(config["engine"]["block_size"]) * head_dim \
        * _itemsize(config)


def ragged_kernel_calls(trace):
    """The ragged kernel in a reduced trace: -> (calls, their summed
    device seconds, ``kv_page_bytes`` of their pool), all chips; ``(0,
    0.0, None)`` where the trace has none."""
    calls, spent, page = 0, 0.0, None
    for chip in (trace or {"chips": {}})["chips"].values():
        for op in chip["ops"]:
            kc = xplane.classify_kernel(op)
            if kc and kc[0] == "ragged_attn":
                page = kv_page_bytes(next(s for s in reversed(op["operands"])
                                          if s[1] == kc[1]["pool"]))
                calls += op["count"]
                spent += op["seconds"]
    return calls, spent, page


def ragged_attn_bytes(live_pages: float, cache_layers: int,
                      page_bytes: int) -> float:
    """KV bytes the kernel's calls of one step read: ``live_pages`` (the
    span's count, pages over all rows in ONE cache layer) in each of
    ``cache_layers`` pools."""
    return float(live_pages) * cache_layers * page_bytes


def head_bytes(config: dict) -> int:
    """The output head's weights [hidden, vocab] in the served dtype."""
    return int(config["hidden_size"]) * int(config["vocab_size"]) \
        * _itemsize(config)


def _itemsize(config: dict) -> int:
    return DTYPE_BYTES[{"bfloat16": "bf16", "float16": "f16",
                        "float32": "f32"}[config["dtype"]]]


def step_least_bytes(args: dict, page_bytes: int, config: dict) -> float:
    """Least bytes one step moves, from its ``serving.ragged_step`` span's
    ``passes``, ``weight_bytes``, ``cache_layers`` and ``live_pages``."""
    return float(args["passes"]) * float(args["weight_bytes"]) \
        + ragged_attn_bytes(args["live_pages"], args["cache_layers"],
                            page_bytes) \
        + head_bytes(config)


def step_spans(record):
    """The window's ``serving.ragged_step`` spans that carry the looped
    step's attributes, in time order: a program without them gives
    nothing."""
    return sorted((s for s in record.get("spans", ())
                   if s["name"] == "serving.ragged_step"
                   and all(k in s["args"] for k in STEP_ATTRS)),
                  key=lambda s: s["ts"])


def traced_steps(record, cell):
    """The ``serving.ragged_step`` spans of the part of the window the
    profiler covered (it starts at the window's middle and lasts the
    traffic's ``traced_s``; spans are stamped on the epoch clock, the
    trace on its own, so the part is found from the window's first span),
    all of the window's where that finds none."""
    steps = step_spans(record)
    if not steps:
        return []
    t0 = min(s["ts"] for s in record["spans"]) + cell.seconds / 2 * 1e6
    t1 = t0 + float(cell.traffic.get("traced_s", 2.0)) * 1e6
    return [s for s in steps if t0 <= s["ts"] < t1] or steps
