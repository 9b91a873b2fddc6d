"""The windowed attention layers of a traced serving run: the least time
their kernel calls could take, and which phase is theirs. What the
``kernels.window_attn_share.serve`` and ``kernels.window_attn_roofline.
serve`` readers share.

The windowed layers' kernel is the per-head ragged kernel, whose operands
the full layers' calls share, so it is told by its PHASE (``attn.window``,
``lib/phases.py``'s join of the device trace with the program's own
instruction-to-phase map), not by its operands. Its work per step and per
windowed layer, from the ``serving.ragged_step`` span's attributes (the
program's, counted on the host from the arrays it builds):

* bytes = ``window_live_pages`` x a page's K and V (KV heads x
  ``block_size`` x head dim x 2 B, twice): every page inside a row's
  window read once, all KV heads; + ``tokens`` x heads x head dim x 2 B
  twice: the queries in and the output out;
* FLOPs = ``window_pairs`` x 4 x heads x head dim: a (query, key seen)
  pair's score and its part of p @ v, the keys a query sees being the
  last ``window`` of its causal prefix.

Times ``window_layers``. Masked keys, pages read again for another head
block and the softmax are left out, so the count is under what any
implementation must do and no implementation can read over 100 %.
"""
from __future__ import annotations

from . import serve_bytes
from .ssm_kernels import phase_seconds

STEP_ATTRS = ("window_layers", "window_live_pages", "window_pairs",
              "tokens")
PHASES = ("attn.window",)
ITEM = 2                       # bytes of a served bfloat16

__all__ = ["STEP_ATTRS", "PHASES", "window_steps", "window_least_s",
           "phase_seconds"]


def window_steps(record, cell):
    """The traced part's ``serving.ragged_step`` spans that carry the
    window attributes: a program without them gives nothing."""
    return [s for s in serve_bytes.traced_steps(record, cell)
            if all(k in s["args"] for k in STEP_ATTRS)]


def window_least_s(args, config, peaks) -> float:
    """Least seconds of ONE step's windowed calls, all windowed layers."""
    heads, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    page = 2 * int(config["num_key_value_heads"]) \
        * int(config["engine"]["block_size"]) * hd * ITEM
    bytes_ = args["window_live_pages"] * page \
        + 2 * args["tokens"] * heads * hd * ITEM
    flops = args["window_pairs"] * 4.0 * heads * hd
    return args["window_layers"] * max(bytes_ / peaks["hbm_bytes_per_s"],
                                       flops / peaks["bf16_flops"])
