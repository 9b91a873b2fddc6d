"""The Xing4.0 cells' own kernels in a reduced device trace, and the
operations and bytes each has to do: what the ``kernels.latent_attn_*``,
``kernels.moe_gmm_*`` and ``serving_engine.moe_pad_share`` readers share.

A Pallas call is told by its operands (its name is the wrapping
transform's), from the configuration's own sizes:

* the LATENT RAGGED ATTENTION kernel takes exactly one rank-4 pool
  ``[1, pages, page, dp]``, ``dp`` the latent (``kv_lora_rank +
  qk_rope_head_dim``) in whole 128-lane tiles, beside a rank-2 ``s32``
  block table, and returns no pool (the in-place latent write takes the
  same pool, no rank-2 table, and returns the pool);
* the GROUPED MATMULS of the expert layers take a rank-3 weight stack
  ``[n_routed_experts, in, out]``.

Counts, per step, from the ``serving.ragged_step`` span's attributes
(the program's, counted on the host from the arrays it builds):

* latent attention, one cache layer: bytes = ``live_pages`` x page x
  ``latent_dim`` x 2 B, every live page read ONCE for keys and values and
  counted at the 576 values a token leaves, not at the padded row;
  FLOPs = ``attn_pairs`` x 2 x heads x (qk_nope + qk_rope + v): a (query
  token, key) pair in the EXPANDED form, without the context's
  re-expansion. The absorbed form does 2 x heads x (latent_dim +
  kv_lora_rank) a pair, 3.4 times as much; the smaller count is under
  what either form can do, so no implementation flatters itself;
* grouped matmuls, one expert layer: FLOPs = live pairs x 3 x 2 x hidden
  x moe_intermediate; bytes = the weights of the experts the live tokens
  touch, (1 - (1 - k / E) ^ tokens) x E under uniform routing (every one
  of them from 60 tokens up; random weights route near uniformly), plus
  each live pair's rows in and out of both matmuls.
"""
from __future__ import annotations

from . import serve_bytes

ATTN_ATTRS = ("live_pages", "attn_pairs", "cache_layers", "latent_dim")
MOE_ATTRS = ("moe_pairs", "moe_rows", "moe_layers", "experts",
             "experts_per_token", "tokens")
ITEM = 2                       # bytes of a served bfloat16


def _pallas_ops(trace):
    for chip in (trace or {"chips": {}})["chips"].values():
        for op in chip["ops"]:
            if op["target"] == "tpu_custom_call":
                yield op


def _sum(ops):
    ops = list(ops)
    return sum(o["count"] for o in ops), sum(o["seconds"] for o in ops)


def latent_pool_dim(config) -> int:
    d = int(config["kv_lora_rank"]) + int(config["qk_rope_head_dim"])
    return -(-d // 128) * 128


def is_latent_attn(op, config) -> bool:
    pools = [s for s in op["operands"]
             if len(s[1]) == 4 and s[0] != "s32"]
    return len(pools) == 1 and pools[0][1][0] == 1 \
        and pools[0][1][3] == latent_pool_dim(config) \
        and any(s[0] == "s32" and len(s[1]) == 2 for s in op["operands"]) \
        and all(len(s[1]) != 4 for s in op["results"])


def is_moe_gmm(op, config) -> bool:
    e = int(config["n_routed_experts"])
    return any(len(s[1]) == 3 and s[1][0] == e and s[0] != "s32"
               for s in op["operands"])


def latent_attn_calls(trace, config):
    """-> (calls, their summed device seconds), all chips."""
    return _sum(o for o in _pallas_ops(trace) if is_latent_attn(o, config))


def moe_gmm_calls(trace, config):
    return _sum(o for o in _pallas_ops(trace) if is_moe_gmm(o, config))


def steps_with(record, cell, attrs):
    """The traced part's ``serving.ragged_step`` spans that carry
    ``attrs``: a program without them gives nothing."""
    return [s for s in serve_bytes.traced_steps(record, cell)
            if all(k in s["args"] for k in attrs)]


def latent_attn_least_s(args, config, peaks) -> float:
    """Least seconds of ONE call (one cache layer) of a step."""
    page = int(config["engine"]["block_size"])
    bytes_ = args["live_pages"] * page * args["latent_dim"] * ITEM
    flops = args["attn_pairs"] * 2.0 * int(config["num_attention_heads"]) \
        * (int(config["qk_nope_head_dim"]) + int(config["qk_rope_head_dim"])
           + int(config["v_head_dim"]))
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               flops / peaks["bf16_flops"])


def moe_gmm_least_s(args, config, peaks) -> float:
    """Least seconds of the two grouped matmuls of ONE expert layer."""
    c, i = int(config["hidden_size"]), int(config["moe_intermediate_size"])
    e, k = args["experts"], args["experts_per_token"]
    pairs = args["moe_pairs"] / args["moe_layers"]
    touched = e * (1.0 - (1.0 - k / e) ** (pairs / k))
    bytes_ = touched * 3 * c * i * ITEM + pairs * (2 * c + 3 * i) * ITEM
    return max(bytes_ / peaks["hbm_bytes_per_s"],
               pairs * 6.0 * c * i / peaks["bf16_flops"])
