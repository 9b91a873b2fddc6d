"""Fused decode path: whole-generation compiled autoregressive decoding
(reference: the serving fusion tier paddle/phi/kernels/fusion/gpu/ —
fused_multi_transformer_kernel.cu, masked_multihead_attention_kernel.cu —
and PaddleNLP's generate loop; beam search reconstructs sequences with
gather_tree exactly like the reference's gather_tree op).

TPU-native design: instead of per-op fused CUDA kernels driven by a host
loop, the ENTIRE decode runs as one XLA program — prefill fills a
fixed-size KV cache, then ``lax.scan`` iterates single-token steps with
``dynamic_update_slice`` cache writes and masked single-query attention.
Zero host round-trips per token (a per-token dispatch would otherwise
rival the decode math it launches); XLA fuses ln/rope/proj into the
matmuls the way fused_multi_transformer does by hand.

The engine is MODEL-GENERIC: each CausalLM exposes ``decode_adapter()``
returning a DecodeAdapter (weight extraction + pure-array embed / layers
/ logits; the base class runs them over a dense cache, prefill or step,
and over the serving engine's paged pools), and this module drives
sampling (greedy / temperature / top-p) and beam search over any adapter.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import observability as _obs
from ..core import random as _rng
from ..core.tensor import Tensor
from ..observability import scopes as _scopes

__all__ = ["generate", "beam_search", "speculative_generate",
           "GPTDecodeAdapter", "LlamaDecodeAdapter", "OuroDecodeAdapter",
           "LatentDecodeAdapter", "Xing4DecodeAdapter",
           "SarvamDecodeAdapter"]


def _ln(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = x32.var(-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32)).astype(x.dtype)


def _rms(x, w, eps, dtype=None):
    x32 = x.astype(jnp.float32)
    nrm = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (nrm * w.astype(jnp.float32)).astype(
        x.dtype if dtype is None else dtype)


def _linear(x, w, b=None):
    if isinstance(w, dict) and "q4" in w:
        # weight-only int4, group-wise scales (reference:
        # nn/quant/quantized_linear.py weight_only_linear
        # weight_dtype='int4'): w is {"q4": [G, gs, out] int4,
        # "s4": [G, out]}. The int4->bf16 convert fuses into the
        # grouped-dot operand read; the per-group scale contraction is
        # a [*, G, out] x [G, out] reduce — tiny next to the weight
        # stream, which drops to a QUARTER of bf16.
        G, gs, out_dim = w["q4"].shape
        xg = x.reshape(x.shape[:-1] + (G, gs))
        z = jnp.einsum("...gi,gio->...go", xg,
                       w["q4"].astype(x.dtype))
        y = jnp.einsum("...go,go->...o", z, w["s4"].astype(x.dtype))
    elif isinstance(w, dict) and "q8" in w:
        # weight-only int8: XLA fuses the int8->bf16 convert into the
        # matmul operand read, so HBM traffic halves vs bf16 weights —
        # decode is weight-bandwidth-bound, so this is ~2x tokens/s
        y = (x @ w["q8"].astype(x.dtype)) * w["s"].astype(x.dtype)
    else:
        y = x @ w
    return y if b is None else y + b


def _quantize_w(w):
    """Per-output-channel symmetric int8 for a [in, out] matmul weight."""
    s = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=0,
                keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / s), -127, 127) \
        .astype(jnp.int8)
    return {"q8": q, "s": s}


def _quantize_w4(w, group=128):
    """Group-wise symmetric int4 for a [in, out] matmul weight: scales
    per (input-group, out-channel), the standard weight-only-int4 recipe
    (reference: nn/quant/quantized_linear.py weight_only_linear,
    group_size arg). The nibbles are STORED as int8 ("q4i8") and
    converted to jnp.int4 on device inside the compiled program
    (_activate_q4): int4 arrays cannot cross the jit boundary on every
    platform plugin, but a convert placed inside the program
    materializes the packed copy once per dispatch, and the decode scan
    then streams the QUARTER-width weights from HBM every step."""
    din, dout = w.shape
    if din % group != 0:
        return _quantize_w(w)       # ragged in-dim: fall back to int8
    wg = w.astype(jnp.float32).reshape(din // group, group, dout)
    s = jnp.max(jnp.abs(wg), axis=1) / 7.0           # [G, out]
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(wg / s[:, None, :]), -7, 7).astype(jnp.int8)
    return {"q4i8": q, "s4": s.astype(jnp.bfloat16)}


def _activate_q4(w):
    """Inside-jit tree walk converting stored q4i8 nibbles to jnp.int4
    (values already in [-7, 7], so the convert is exact)."""
    if isinstance(w, dict):
        if "q4i8" in w:
            return {"q4": w["q4i8"].astype(jnp.int4), "s4": w["s4"]}
        return {k: _activate_q4(v) for k, v in w.items()}
    if isinstance(w, list):
        return [_activate_q4(v) for v in w]
    return w


_QUANT_SKIP = {"wte", "wpe"}  # embedding gathers stay full precision


def _quantized_weights(model, w_now, bits=8):
    """Per-model cached quantized weight tree (shared by generate /
    speculative target / speculative draft). Re-quantize after a weight
    update by clearing ``model._gen_quant_w`` / ``_gen_quant_w4``."""
    attr = "_gen_quant_w" if bits == 8 else "_gen_quant_w4"
    qw = getattr(model, attr, None)
    if qw is None:
        if w_now.get("lm_head") is None:
            w_now = dict(w_now)
            w_now["lm_head"] = w_now["wte"].T
        qw = _quantize_tree(w_now, bits=bits)
        setattr(model, attr, qw)
    return qw


def _resolve_weight_quant(model, w_now, weight_quant):
    if weight_quant is None:
        return w_now
    if weight_quant == "int8":
        return _quantized_weights(model, w_now, bits=8)
    if weight_quant == "int4":
        return _quantized_weights(model, w_now, bits=4)
    raise ValueError("weight_quant must be None, 'int8' or 'int4'")


def _quantize_tree(w, min_dim=256, bits=8):
    """Walk an adapter weight pytree, replacing big 2D matmul weights with
    int8 (or group-wise int4) quant dicts (reference analog:
    weight_only_linear / llm.int8 serving paths,
    phi/kernels/fusion/gpu/fused_weight_only_*). In int4 mode the
    lm_head stays int8: the argmax over the vocab is the single most
    quantization-sensitive matmul in the decode."""
    if isinstance(w, dict):
        out = {}
        for k, v in w.items():
            if k in _QUANT_SKIP:
                out[k] = v
            elif isinstance(v, (dict, list)):
                out[k] = _quantize_tree(v, min_dim, bits)
            elif (hasattr(v, "ndim") and v is not None and v.ndim == 2
                    and min(v.shape) >= min_dim):
                if bits == 4 and k != "lm_head":
                    out[k] = _quantize_w4(v)
                else:
                    out[k] = _quantize_w(v)
            else:
                out[k] = v
        return out
    if isinstance(w, list):
        return [_quantize_tree(v, min_dim, bits) for v in w]
    return w


def _quantize_kv(k):
    """Per-(position, head) symmetric int8 for a [..., nh, hd] K or V
    slab (reference analog: the cache_k_quant_scales /
    cache_v_quant_scales surface of
    python/paddle/incubate/nn/functional/masked_multihead_attention.py —
    there the scales are host-computed calibration inputs; here they are
    computed on the fly per written row, which is exact for the
    read side because each row's scale rides with it)."""
    s = jnp.max(jnp.abs(k.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)                        # [..., nh]
    q = jnp.clip(jnp.round(k.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return {"q8": q, "s": s}


# Cache layout is HEAD-MAJOR [b, nh, T, hd] (scales [b, nh, T]): the
# per-step attention then lowers to batched matmuls over (b, h) with a
# contiguous [T, hd] panel per head — the MXU-friendly orientation —
# instead of strided gathers over a [b, T, nh, hd] slab.

def _kv_prefill_store(k, b, total, plen, dt, quant):
    """Build a [b, nh, total, hd] cache from prefill rows
    k [b, plen, nh, hd]."""
    nh, hd = k.shape[-2], k.shape[-1]
    k = jnp.swapaxes(k, 1, 2)                       # [b, nh, plen, hd]
    if not quant:
        return jnp.zeros((b, nh, total, hd), dt).at[:, :, :plen].set(k)
    qk = _quantize_kv(k)
    return {"q8": jnp.zeros((b, nh, total, hd), jnp.int8)
            .at[:, :, :plen].set(qk["q8"]),
            "s": jnp.zeros((b, nh, total), jnp.float32)
            .at[:, :, :plen].set(qk["s"])}


def _kv_write(cache, k, pos):
    """Write one decode row k [b, nh, hd] at position pos."""
    if not isinstance(cache, dict):
        return jax.lax.dynamic_update_slice(cache, k[:, :, None],
                                            (0, 0, pos, 0))
    qk = _quantize_kv(k)
    return {"q8": jax.lax.dynamic_update_slice(
                cache["q8"], qk["q8"][:, :, None], (0, 0, pos, 0)),
            "s": jax.lax.dynamic_update_slice(
                cache["s"], qk["s"][:, :, None], (0, 0, pos))}


def _kv_write_rows(cache, k, pos):
    """Write g rows k [b, g, nh, hd] at per-row positions pos [b, g]
    (speculative verify writes land at different offsets per sequence).
    Out-of-window positions (finished rows still looping) are dropped.
    Advanced indices on axes 0 and 2 around the head slice produce
    [b, g, nh, hd] update slots — matching k's natural layout."""
    bidx = jnp.arange(k.shape[0])[:, None]
    if not isinstance(cache, dict):
        return cache.at[bidx, :, pos].set(k.astype(cache.dtype),
                                          mode="drop")
    qk = _quantize_kv(k)
    return {"q8": cache["q8"].at[bidx, :, pos].set(qk["q8"],
                                                   mode="drop"),
            "s": cache["s"].at[bidx, :, pos].set(qk["s"], mode="drop")}


def _kv_repeat(cache, rep):
    """GQA head replication for either cache representation."""
    if rep <= 1:
        return cache
    if not isinstance(cache, dict):
        return jnp.repeat(cache, rep, axis=1)
    return {"q8": jnp.repeat(cache["q8"], rep, axis=1),
            "s": jnp.repeat(cache["s"], rep, axis=1)}


def _rope(x, pos, base):
    """Rotate [..., nh, hd] by absolute positions pos (int array
    broadcastable to x.shape[:-2])."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[..., None, None] * freqs  # [..., 1, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def _lm_head(w, x):
    """The vocabulary projection: the model's head, or its tied
    embedding."""
    head = w["lm_head"]
    if head is None:
        return x @ w["wte"].T
    return _linear(x, head)


class DecodeAdapter:
    """Per-model weight extraction + pure-array decode callbacks.

    A model's adapter supplies its attributes and three pure methods;
    this class supplies the cache forms over them.

    Attributes: kv_layout ("kv": per-head keys and values, what this
    class's cache forms carry; "latent": LatentDecodeAdapter's),
    num_layers (WEIGHT layers), passes (how many times the
    stack of them runs over a token: 1 but for a looped model),
    cache_layers (the K/V caches a token writes, one a (pass, layer):
    ``passes * num_layers``; every cache argument, dense or paged, is a
    tuple of that many entries, and the serving engine builds that many
    pools), num_heads, num_kv_heads, head_dim, dtype, vocab_size,
    max_positions, weights (flat pytree of jax arrays; ``["layers"]`` is
    the list of weight layers).
    The model's methods (pure over arrays, jit-safe):
      embed(w, toks [...], pos [...]) -> x [..., h]
      layers(w, x [..., h], pos [...], attend) -> x [..., h]: the model's
          arithmetic, written once. ``attend(i, q, k, v)`` takes q
          [..., nh, hd] and k, v [..., kvh, hd], stores k and v in cache
          ``i`` and returns the attention of q over that cache,
          [..., nh * hd]. Cache ``i`` of pass r, layer l is
          ``r * len(w["layers"]) + l``.
      logits(w, x [..., h]) -> [..., V]
    ``layers`` wraps each sublayer in its phase (``observability/scopes``:
    ``attn.proj``, ``ffn``, ...) and ``ragged_chunk``, the serving step's
    form, wraps ``embed``, ``attend``'s write and kernel, and ``logits``.
    The cache forms (each ``embed`` -> ``layers`` with one ``attend`` ->
    ``logits``):
      prefill(w, ids, total) -> (x [b, plen, h], ck, cv: cache_layers x
                                 [b, kvh, total, hd])
      step(w, tok [b], pos, ck, cv, t_mask) -> (logits [b, V], ck, cv)
      chunk_step(w, toks [b, g], pos [b, g], ck, cv)
          -> (logits [b, g, V], ck, cv)
      ragged_chunk(w, ..., kpages, vpages, block_tables)
          -> (logits [T, V], kpages, vpages) over cache_layers paged
          pools, each [n_kv, pages, page, hd]; a page id names the same
          token span in all of them.
    """

    passes = 1
    kv_layout = "kv"

    @property
    def cache_layers(self) -> int:
        return self.passes * self.num_layers

    def prefill(self, w, ids, total, kv_quant=False):
        """The whole prompt ids [b, plen] into fresh dense caches of
        ``total`` positions. Returns the hidden states, not the logits:
        callers want the last position's alone."""
        b, plen = ids.shape
        dt, rep = self.dtype, self.num_heads // self.num_kv_heads
        causal = jnp.tril(jnp.ones((plen, plen), bool))
        n = self.passes * len(w["layers"])
        ck, cv = [None] * n, [None] * n

        def attend(i, q, k, v):
            ck[i] = _kv_prefill_store(k, b, total, plen, dt, kv_quant)
            cv[i] = _kv_prefill_store(v, b, total, plen, dt, kv_quant)
            kf = jnp.repeat(k, rep, axis=2) if rep > 1 else k
            vf = jnp.repeat(v, rep, axis=2) if rep > 1 else v
            return _causal_prefill_attn(q, kf, vf, causal, self.head_dim,
                                        dt)

        pos = jnp.arange(plen)[None, :]
        x = self.layers(w, self.embed(w, ids, pos), pos, attend)
        return x, tuple(ck), tuple(cv)

    def step(self, w, tok, pos, ck, cv, t_mask):
        """One token a sequence, all at position ``pos`` (a scalar)."""
        b = tok.shape[0]
        rep = self.num_heads // self.num_kv_heads
        ck, cv = list(ck), list(cv)

        def attend(i, q, k, v):
            ck[i] = _kv_write(ck[i], k, pos)
            cv[i] = _kv_write(cv[i], v, pos)
            att = _masked_sdpa(q, _kv_repeat(ck[i], rep),
                               _kv_repeat(cv[i], rep), t_mask,
                               self.head_dim)
            return att.reshape(b, -1)

        pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
        x = self.layers(w, self.embed(w, tok, pos_b), pos_b, attend)
        return self.logits(w, x), tuple(ck), tuple(cv)

    def chunk_step(self, w, toks, pos, ck, cv):
        """g tokens at per-row positions in one pass (speculative-decode
        draft/verify; the draft_model surface of the reference's
        fused_speculate_* serving ops). toks, pos [b, g]; returns
        logits [b, g, V] where slot j reflects the prefix through
        toks[:, j]."""
        b, g = toks.shape
        rep = self.num_heads // self.num_kv_heads
        ck, cv = list(ck), list(cv)

        def attend(i, q, k, v):
            ck[i] = _kv_write_rows(ck[i], k, pos)
            cv[i] = _kv_write_rows(cv[i], v, pos)
            att = _chunk_sdpa(q, _kv_repeat(ck[i], rep),
                              _kv_repeat(cv[i], rep), pos, self.head_dim)
            return att.reshape(b, g, -1)

        x = self.layers(w, self.embed(w, toks, pos), pos, attend)
        return self.logits(w, x), tuple(ck), tuple(cv)

    def ragged_chunk(self, w, toks, pos, row_of, q_starts, query_lens,
                     context_lens, kpages, vpages, block_tables,
                     tally=None):
        """ONE ragged mixed prefill+decode step over paged pools (the
        single-dispatch serving step). Flat token axis [T] packed
        row-major: row r owns tokens q_starts[r] ..
        q_starts[r]+query_lens[r], row_of [T] maps each token to its
        row (-1 = padding). pos [T] is each token's absolute position
        (< 0 = padding: write dropped, output ignored); block_tables
        [n_rows, P] is per ROW; context_lens[r] counts the row's KV
        INCLUDING this step's tokens. kpages/vpages: ``cache_layers``
        pools of [n_kv, pages, page, d] (bf16 or int8 dicts), one page
        index space for all of them. ``tally``: a dict for what only the
        step can count (nothing here; LatentDecodeAdapter's expert
        layers). Returns (logits [T, V], kpages, vpages)."""
        from ..incubate.nn.pallas.paged_attention import \
            paged_kv_write_chunk

        T = toks.shape[0]
        n_rows = block_tables.shape[0]
        with _scopes.phase("attn.kv_write"):
            bt_tok = jnp.take(block_tables,
                              jnp.clip(row_of, 0, n_rows - 1), axis=0)
        kp, vp = list(kpages), list(vpages)

        def attend(i, q, k, v):
            with _scopes.phase("attn.kv_write"):
                kp[i], vp[i] = paged_kv_write_chunk(
                    kp[i], vp[i], k[:, None], v[:, None], bt_tok,
                    pos[:, None])
            with _scopes.phase("attn.kernel"):
                att = _ragged_attn(q, kp[i], vp[i], block_tables,
                                   context_lens, query_lens, q_starts,
                                   row_of, self.head_dim)
                return att.reshape(T, -1)

        safe_pos = jnp.maximum(pos, 0)
        with _scopes.phase("embed"):
            x = self.embed(w, toks, safe_pos)
        x = self.layers(w, x, safe_pos, attend)
        with _scopes.phase("head"):
            return self.logits(w, x), tuple(kp), tuple(vp)


class GPTDecodeAdapter(DecodeAdapter):
    """Learned-position GPT decoder (gpt.py GPTForCausalLM)."""

    def __init__(self, model):
        cfg = model.config
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.layer_norm_eps
        self.vocab_size = cfg.vocab_size
        self.max_positions = getattr(cfg, "max_position_embeddings", None)
        g = model.gpt
        layers = []
        for blk in g.h:
            layers.append({
                "ln1_w": blk.ln_1.weight._data, "ln1_b": blk.ln_1.bias._data,
                "qkv_w": blk.attn.qkv_proj.weight._data,
                "qkv_b": (blk.attn.qkv_proj.bias._data
                          if blk.attn.qkv_proj.bias is not None else None),
                "out_w": blk.attn.out_proj.weight._data,
                "out_b": (blk.attn.out_proj.bias._data
                          if blk.attn.out_proj.bias is not None else None),
                "ln2_w": blk.ln_2.weight._data, "ln2_b": blk.ln_2.bias._data,
                "fc1_w": blk.mlp.fc1.weight._data,
                "fc1_b": (blk.mlp.fc1.bias._data
                          if blk.mlp.fc1.bias is not None else None),
                "fc2_w": blk.mlp.fc2.weight._data,
                "fc2_b": (blk.mlp.fc2.bias._data
                          if blk.mlp.fc2.bias is not None else None),
            })
        head = None if model.lm_head is None else model.lm_head.weight._data
        self.weights = {
            "wte": g.wte.weight._data, "wpe": g.wpe.weight._data,
            "lnf_w": g.ln_f.weight._data, "lnf_b": g.ln_f.bias._data,
            "layers": layers, "lm_head": head,
        }
        self.dtype = self.weights["wte"].dtype

    def embed(self, w, toks, pos):
        return (w["wte"][toks] + w["wpe"][pos]).astype(self.dtype)

    def layers(self, w, x, pos, attend):
        nh, hd = self.num_heads, self.head_dim
        lead = x.shape[:-1]
        for i, W in enumerate(w["layers"]):
            with _scopes.phase("attn.proj"):
                h1 = _ln(x, W["ln1_w"], W["ln1_b"], self.eps)
                qkv = _linear(h1, W["qkv_w"], W["qkv_b"]) \
                    .reshape(lead + (3, nh, hd))
                att = attend(i, qkv[..., 0, :, :], qkv[..., 1, :, :],
                             qkv[..., 2, :, :])
                x = x + _linear(att, W["out_w"], W["out_b"])
            with _scopes.phase("ffn"):
                h2 = _ln(x, W["ln2_w"], W["ln2_b"], self.eps)
                m = jax.nn.gelu(_linear(h2, W["fc1_w"], W["fc1_b"]),
                                approximate=True)
                x = x + _linear(m, W["fc2_w"], W["fc2_b"])
        return x

    def logits(self, w, x):
        return _lm_head(w, _ln(x, w["lnf_w"], w["lnf_b"], self.eps))


class LlamaDecodeAdapter(DecodeAdapter):
    """RMSNorm + rope + GQA + SwiGLU decoder (llama.py LlamaForCausalLM)."""

    def __init__(self, model):
        cfg = model.config
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        self.rope_base = cfg.rope_base
        self.vocab_size = cfg.vocab_size
        self.max_positions = getattr(cfg, "max_position_embeddings", None)
        mdl = model.llama
        layers = []
        for blk in mdl.layers:
            layers.append({
                "in_ln": blk.input_layernorm.weight._data,
                "q_w": blk.self_attn.q_proj.weight._data,
                "k_w": blk.self_attn.k_proj.weight._data,
                "v_w": blk.self_attn.v_proj.weight._data,
                "o_w": blk.self_attn.o_proj.weight._data,
                "post_ln": blk.post_attention_layernorm.weight._data,
                "gate_w": blk.mlp.gate_proj.weight._data,
                "up_w": blk.mlp.up_proj.weight._data,
                "down_w": blk.mlp.down_proj.weight._data,
            })
        head = None if model.lm_head is None else model.lm_head.weight._data
        self.weights = {
            "wte": mdl.embed_tokens.weight._data,
            "norm": mdl.norm.weight._data,
            "layers": layers, "lm_head": head,
        }
        self.dtype = self.weights["wte"].dtype

    def embed(self, w, toks, pos):
        return w["wte"][toks].astype(self.dtype)

    def layers(self, w, x, pos, attend):
        nh, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        lead = x.shape[:-1]
        for i, W in enumerate(w["layers"]):
            with _scopes.phase("attn.proj"):
                h1 = _rms(x, W["in_ln"], self.eps)
                q = _linear(h1, W["q_w"]).reshape(lead + (nh, hd))
                k = _linear(h1, W["k_w"]).reshape(lead + (kvh, hd))
                v = _linear(h1, W["v_w"]).reshape(lead + (kvh, hd))
                att = attend(i, _rope(q, pos, self.rope_base),
                             _rope(k, pos, self.rope_base), v)
                x = x + _linear(att, W["o_w"])
            with _scopes.phase("ffn"):
                h2 = _rms(x, W["post_ln"], self.eps)
                m = jax.nn.silu(_linear(h2, W["gate_w"])) \
                    * _linear(h2, W["up_w"])
                x = x + _linear(m, W["down_w"])
        return x

    def logits(self, w, x):
        return _lm_head(w, _rms(x, w["norm"], self.eps))


class OuroDecodeAdapter(DecodeAdapter):
    """Looped sandwich-norm decoder (ouro.py OuroForCausalLM): the L
    weight layers run ``passes`` times, pass r reading and writing caches
    ``r * L .. (r + 1) * L - 1`` (dense or paged alike). The layer's
    arithmetic is LlamaDecodeAdapter's (``_rms``, ``_rope``, ``_linear``,
    SwiGLU) with the sublayer outputs normed; the final norm closes every
    pass, so ``logits`` is the head alone, applied to the hidden state of
    each token's exit pass."""

    def __init__(self, model):
        cfg = model.config
        self.num_layers = cfg.num_layers
        self.passes = cfg.total_ut_steps
        self.exit_threshold = float(cfg.early_exit_threshold)
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.eps = cfg.rms_norm_eps
        self.rope_base = cfg.rope_base
        self.vocab_size = cfg.vocab_size
        self.max_positions = getattr(cfg, "max_position_embeddings", None)
        mdl = model.ouro
        layers = []
        for blk in mdl.layers:
            layers.append({
                "in_ln": blk.input_layernorm.weight._data,
                "q_w": blk.self_attn.q_proj.weight._data,
                "k_w": blk.self_attn.k_proj.weight._data,
                "v_w": blk.self_attn.v_proj.weight._data,
                "o_w": blk.self_attn.o_proj.weight._data,
                "in_ln2": blk.input_layernorm_2.weight._data,
                "post_ln": blk.post_attention_layernorm.weight._data,
                "gate_w": blk.mlp.gate_proj.weight._data,
                "up_w": blk.mlp.up_proj.weight._data,
                "down_w": blk.mlp.down_proj.weight._data,
                "post_ln2": blk.post_attention_layernorm_2.weight._data,
            })
        head = None if model.lm_head is None else model.lm_head.weight._data
        self.weights = {
            "wte": mdl.embed_tokens.weight._data,
            "norm": mdl.norm.weight._data,
            "exit_w": mdl.early_exit_gate.weight._data,
            "exit_b": mdl.early_exit_gate.bias._data,
            "layers": layers, "lm_head": head,
        }
        self.dtype = self.weights["wte"].dtype

    def embed(self, w, toks, pos):
        return w["wte"][toks].astype(self.dtype)

    def layers(self, w, x, pos, attend):
        """The R passes over the L layers -> the hidden state of each
        token's exit pass.

        The residual stream is float32 whatever the weights' dtype. It
        grows to an RMS of sqrt(2 L) through a pass (every sublayer adds
        a unit-RMS output), where a bf16 stream rounds each addition by
        a fiftieth of what it adds, and every further pass doubles the
        error it inherits (measured on the float32 reference at
        Ouro-2.6B's size with random weights: 0.6 % of the hidden state
        after one pass, 7 % after four; PERF.md). The stream is
        [tokens, hidden]: in float32 it costs nothing beside the
        weights. The matmuls, keys and values stay in the weights'
        dtype."""
        from .ouro import exit_hidden, exit_pass

        nh, kvh, hd = self.num_heads, self.num_kv_heads, self.head_dim
        lead, dt, f32, eps = x.shape[:-1], self.dtype, jnp.float32, self.eps
        L = len(w["layers"])
        x = x.astype(f32)
        hs, lambdas = [], []
        for r in range(self.passes):
            for l, W in enumerate(w["layers"]):
                with _scopes.phase("attn.proj"):
                    u = _rms(x, W["in_ln"], eps, dt)
                    q = _linear(u, W["q_w"]).reshape(lead + (nh, hd))
                    k = _linear(u, W["k_w"]).reshape(lead + (kvh, hd))
                    v = _linear(u, W["v_w"]).reshape(lead + (kvh, hd))
                    a = attend(r * L + l, _rope(q, pos, self.rope_base),
                               _rope(k, pos, self.rope_base), v)
                    x = x + _rms(_linear(a, W["o_w"]), W["in_ln2"], eps,
                                 f32)
                with _scopes.phase("ffn"):
                    u = _rms(x, W["post_ln"], eps, dt)
                    m = jax.nn.silu(_linear(u, W["gate_w"])) \
                        * _linear(u, W["up_w"])
                    x = x + _rms(_linear(m, W["down_w"]), W["post_ln2"],
                                 eps, f32)
            with _scopes.phase("head"):     # the final norm closes a pass
                x = _rms(x, w["norm"], eps)
            hs.append(x)
            with _scopes.phase("mix"):
                lambdas.append(jax.nn.sigmoid(
                    x @ w["exit_w"].astype(f32)[:, 0]
                    + w["exit_b"].astype(f32)[0]))
        with _scopes.phase("mix"):
            ex = exit_pass(lambdas, self.exit_threshold)
            return exit_hidden(hs, ex).astype(dt)

    def logits(self, w, x):
        return _lm_head(w, x)


class LatentDecodeAdapter(DecodeAdapter):
    """The LATENT form of the cache contract: a token leaves ONE vector
    of ``latent_dim`` values in each cache layer, shared by every query
    head, which is its key whole and, in its first ``latent_value_dim``
    values, its value (latent attention read in the absorbed form: the
    per-head key expansion folded into the query, the value expansion
    applied to what comes back).

    Further attributes: ``kv_layout`` (``"latent"``; ``"kv"`` on every
    other adapter), ``latent_dim``, ``latent_value_dim``, ``attn_scale``
    (the score scale, which is not ``latent_dim ** -0.5``).
    ``num_kv_heads`` is 1 and ``head_dim`` is ``latent_dim``.
    ``layers``' ``attend(i, q, c)`` takes q [..., nh, latent_dim] and the
    tokens' latents c [..., latent_dim], stores c in cache ``i`` and
    returns the attended latents [..., nh, latent_value_dim].
    The four cache forms keep their signatures; the K argument carries
    the latent caches (dense [b, total, latent_dim]; paged pools of
    [1, pages, page, latent_pool_dim(latent_dim)], rows zero-padded to
    whole lane tiles) and the V argument is an empty tuple.

    The family's two sublayers are written here once, for every adapter
    of it to call from its ``layers`` (:meth:`latent_attention`,
    :meth:`moe`), with ``embed``, ``logits`` and what they read of a
    config (:meth:`_setup`). An adapter with expert layers also says
    ``experts`` (the routed experts HELD here: the stacks the grouped
    matmuls read), ``experts_routed`` (the router's width),
    ``expert_first`` (held: ``[expert_first, expert_first + experts)``),
    ``experts_per_token`` and ``moe_layers``. ``layers`` takes a fifth
    argument ``count`` (``ragged_chunk`` gives it: None, or what counts
    a step's held pairs and live row blocks) and hands it to
    :meth:`moe`."""

    kv_layout = "latent"
    num_kv_heads = 1
    expert_first = 0

    @property
    def head_dim(self) -> int:
        return self.latent_dim

    def _setup(self, cfg):
        """What the shared bodies read of a config of the family
        (``Xing4Config``, ``SarvamMLAConfig``: the same names)."""
        from .xing4 import yarn_inv_freq, yarn_mscale

        self.cfg = cfg
        self.num_layers = cfg.num_layers
        self.num_heads = cfg.num_heads
        self.latent_dim = cfg.latent_dim
        self.latent_value_dim = cfg.kv_lora_rank
        self.experts_per_token = cfg.num_experts_per_tok
        self.moe_layers = cfg.num_layers - cfg.first_k_dense_replace
        self.vocab_size = cfg.vocab_size
        self.max_positions = cfg.max_position_embeddings
        self.inv_freq = yarn_inv_freq(cfg)
        m_all = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        self.rope_cos_scale = yarn_mscale(cfg.rope_factor,
                                          cfg.rope_mscale) / m_all
        self.attn_scale = cfg.qk_head_dim ** -0.5 * m_all * m_all

    @staticmethod
    def _mlp_weights(m):
        return {"gate_w": m.gate_proj.weight._data,
                "up_w": m.up_proj.weight._data,
                "down_w": m.down_proj.weight._data}

    def _block_weights(self, blk):
        """What every block of the family holds: its two norms, the
        latent's projections, the output projection, and its ``mlp`` (the
        dense SwiGLU's weights, or the router's, the held experts' stacks
        and the shared expert's). The adapter adds its query's."""
        at, mlp = blk.self_attn, blk.mlp
        W = {"in_ln": blk.input_layernorm.weight._data,
             "kva_w": at.kv_a_proj_with_mqa.weight._data,
             "kv_ln": at.kv_a_layernorm.weight._data,
             "kvb_w": at.kv_b_proj.weight._data,
             "o_w": at.o_proj.weight._data,
             "post_ln": blk.post_attention_layernorm.weight._data}
        if hasattr(mlp, "experts_gate_up"):
            W.update(router_w=mlp.gate_weight._data,
                     router_b=mlp.e_score_correction_bias._data,
                     gate_up=mlp.experts_gate_up._data,
                     down=mlp.experts_down._data,
                     shared=self._mlp_weights(mlp.shared_experts))
        else:
            W["dense"] = self._mlp_weights(mlp)
        return W

    def _set_weights(self, model, layers):
        head = None if model.lm_head is None else model.lm_head.weight._data
        self.weights = {"wte": model.model.embed_tokens.weight._data,
                        "norm": model.model.norm.weight._data,
                        "layers": layers, "lm_head": head}
        self.dtype = self.weights["wte"].dtype

    def embed(self, w, toks, pos):
        return w["wte"][toks].astype(self.dtype)

    def logits(self, w, x):
        return _lm_head(self._control({"lm_head": w["lm_head"],
                                       "wte": w["wte"]}),
                        _rms(x, w["norm"], self.cfg.rms_norm_eps))

    def latent_attention(self, i, W, h, pos, attend):
        """The attention sublayer of layer ``i`` over its normed input h
        [..., C] -> [..., C]: the query (through a low-rank bottleneck
        where the layer has ``qa_w``, else straight from h; a norm over
        each head's values where it has ``q_head_ln``), the token's
        latent (normed) and its rope key, the key expansion folded into
        the query, ``attend``, the value expansion, the output
        projection."""
        cfg = self.cfg
        nh, eps = cfg.num_heads, cfg.rms_norm_eps
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        rank, lead = cfg.kv_lora_rank, h.shape[:-1]

        def rope(t):
            return _rope_freqs(t, pos, self.inv_freq, self.rope_cos_scale)

        with _scopes.phase("attn.proj"):
            if "qa_w" in W:
                q = _linear(_rms(_linear(h, W["qa_w"]), W["q_ln"], eps),
                            W["qb_w"])
            else:
                q = _linear(h, W["q_w"])
            q = q.reshape(lead + (nh, dn + dr))
            if "q_head_ln" in W:
                q = _rms(q, W["q_head_ln"], eps)
            kv = _linear(h, W["kva_w"])
            c = _rms(kv[..., :rank], W["kv_ln"], eps)
            k_rope = rope(kv[..., None, rank:])[..., 0, :]
            kvb = W["kvb_w"].reshape(rank, nh, dn + dv)
            q_abs = jnp.einsum("...hd,chd->...hc", q[..., :dn],
                               kvb[..., :dn])
            o = attend(i, jnp.concatenate([q_abs, rope(q[..., dn:])], -1),
                       jnp.concatenate([c, k_rope], -1))
            v = jnp.einsum("...hc,chd->...hd", o, kvb[..., dn:])
            return _linear(v.reshape(lead + (nh * dv,)), W["o_w"])

    def _swiglu(self, W, h):
        """A dense SwiGLU over every row: a leading layer's, or an expert
        layer's shared expert."""
        with _scopes.phase("ffn"):
            return _linear(jax.nn.silu(_linear(h, W["gate_w"]))
                           * _linear(h, W["up_w"]), W["down_w"])

    def route(self, W, h32):
        """-> (scores [S, E] float32, the scores the choice is made by)."""
        with _scopes.phase("moe.route"):
            s = jax.nn.sigmoid(jnp.dot(
                h32, W["router_w"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST))
            return s, s + W["router_b"].astype(jnp.float32)

    def moe(self, W, h32, count=None):
        """The expert layer over normed tokens h32 [S, C] (float32) ->
        [S, C] float32: the router over all ``experts_routed``, every
        (token, chosen expert) pair whose expert is held here, none of
        them dropped, sorted by expert and run as two grouped matmuls
        over the held stacks, beside the shared expert. The weights are
        normalised over all the chosen; an absent expert's term is left
        out. The grouped matmuls leave the rows of the row blocks that
        hold no pair unwritten; the combine reads held pairs' rows and,
        for an absent pair, row 0 at weight 0, which row block 0, always
        computed, has zeros for where no pair at all is held. ``count``,
        where given, is called with [S, k] bool, which of the tokens'
        pairs are held, and the layout's count of live row blocks."""
        from ..incubate.nn.pallas.moe_dispatch import (grouped_matmul,
                                                       sort_dispatch)

        cfg, f32 = self.cfg, jnp.float32
        k = cfg.num_experts_per_tok
        s, sel = self.route(W, h32)
        with _scopes.phase("moe.dispatch"):
            h = h32.astype(self.dtype)
            d = sort_dispatch(h, s, k, normalize=cfg.norm_topk_prob,
                              select=sel, first=self.expert_first,
                              held=self.experts)
            gid, live = d["block_gid"], d["live_blocks"]
            if count is not None:
                count(d["here"], live)
        with _scopes.phase("moe.experts"):
            gu = grouped_matmul(d["xp"], W["gate_up"], gid, live)
        with _scopes.phase("moe.act"):
            g, u = jnp.split(gu, 2, axis=-1)
            a = jax.nn.silu(g) * u
        with _scopes.phase("moe.experts"):
            y = grouped_matmul(a, W["down"], gid, live)
        with _scopes.phase("moe.combine"):
            routed = (y[d["dest"]].astype(f32)
                      * (d["weight"] * cfg.routed_scaling_factor)[:, None]) \
                .reshape(h.shape[0], k, -1).sum(1)
        shared = self._swiglu(W["shared"], h)
        with _scopes.phase("moe.combine"):
            return routed + shared.astype(f32)

    def _control(self, tree):
        """``control_operand_dtype`` set (no cell's): the projections' and
        experts' weights rounded to it as they are read."""
        ctl = self.cfg.control_operand_dtype
        if ctl is None:
            return tree
        keep = ("phi", "bias", "alpha", "router_w", "router_b")

        def rounded(path, a):
            name = getattr(path[-1], "key", None)
            if a is None or a.ndim < 2 or name in keep:
                return a
            return a.astype(ctl).astype(a.dtype)

        return jax.tree_util.tree_map_with_path(rounded, tree)

    def _attend_dense(self, q, cache, mask):
        """q [b, g, nh, D] over cache [b, T, D] where ``mask``
        [b, 1, g, T] (or broadcastable) allows."""
        sc = jnp.einsum("bghd,btd->bhgt", q, cache,
                        preferred_element_type=jnp.float32) \
            * self.attn_scale
        w = jax.nn.softmax(jnp.where(mask, sc, -1e30), axis=-1)
        return jnp.einsum("bhgt,btv->bghv", w.astype(cache.dtype),
                          cache[..., :self.latent_value_dim])

    def prefill(self, w, ids, total, kv_quant=False):
        if kv_quant:
            raise ValueError("a latent cache has no int8 form")
        b, plen = ids.shape
        causal = jnp.tril(jnp.ones((plen, plen), bool))
        cc = [None] * (self.passes * len(w["layers"]))

        def attend(i, q, c):
            cc[i] = jnp.zeros((b, total, c.shape[-1]), self.dtype) \
                .at[:, :plen].set(c)
            return self._attend_dense(q, c, causal)

        pos = jnp.arange(plen)[None, :]
        x = self.layers(w, self.embed(w, ids, pos), pos, attend)
        return x, tuple(cc), ()

    def step(self, w, tok, pos, ck, cv, t_mask):
        b = tok.shape[0]
        cc = list(ck)

        def attend(i, q, c):
            cc[i] = jax.lax.dynamic_update_slice(cc[i], c[:, None],
                                                 (0, pos, 0))
            return self._attend_dense(q[:, None], cc[i], t_mask)[:, 0]

        pos_b = jnp.broadcast_to(jnp.asarray(pos), (b,))
        x = self.layers(w, self.embed(w, tok, pos_b), pos_b, attend)
        return self.logits(w, x), tuple(cc), ()

    def chunk_step(self, w, toks, pos, ck, cv):
        cc = list(ck)
        bidx = jnp.arange(toks.shape[0])[:, None]

        def attend(i, q, c):
            cc[i] = cc[i].at[bidx, pos].set(c.astype(cc[i].dtype),
                                            mode="drop")
            mask = (jnp.arange(cc[i].shape[1])[None, None, :]
                    <= pos[:, :, None])[:, None]
            return self._attend_dense(q, cc[i], mask)

        x = self.layers(w, self.embed(w, toks, pos), pos, attend)
        return self.logits(w, x), tuple(cc), ()

    def ragged_chunk(self, w, toks, pos, row_of, q_starts, query_lens,
                     context_lens, kpages, vpages, block_tables,
                     tally=None):
        """``tally``: a dict, where the caller wants what only the step
        can count. Summed over the expert layers (int32 scalars, none
        where there is no expert layer) it gets ``moe_pairs_held``, the
        live tokens' (token, expert) pairs dispatched to experts held
        here, and ``moe_blocks_live``, the row blocks of the grouped
        matmuls' layouts that hold a pair: those computed."""
        from ..incubate.nn.pallas.paged_attention import (
            latent_visits, paged_latent_write_chunk,
            ragged_latent_attention)

        n_rows = block_tables.shape[0]
        with _scopes.phase("attn.kv_write"):
            bt_tok = jnp.take(block_tables,
                              jnp.clip(row_of, 0, n_rows - 1), axis=0)
        pools = list(kpages)
        # the kernel's work list reads nothing of a layer: made once
        with _scopes.phase("attn.kernel"):
            visits = latent_visits(toks.shape[0], pools[0].shape[2],
                                   block_tables, context_lens, query_lens,
                                   q_starts)

        def attend(i, q, c):
            with _scopes.phase("attn.kv_write"):
                pools[i] = paged_latent_write_chunk(pools[i], c, bt_tok,
                                                    pos)
            with _scopes.phase("attn.kernel"):
                return ragged_latent_attention(
                    q, pools[i], block_tables, context_lens, query_lens,
                    q_starts=q_starts, row_of=row_of,
                    value_dim=self.latent_value_dim, scale=self.attn_scale,
                    visits=visits)

        held, live = [], []

        def count(here, live_blocks):
            held.append(jnp.sum(here & (pos >= 0)[:, None],
                                dtype=jnp.int32))
            live.append(live_blocks)

        safe_pos = jnp.maximum(pos, 0)
        with _scopes.phase("embed"):
            x = self.embed(w, toks, safe_pos)
        x = self.layers(w, x, safe_pos, attend,
                        None if tally is None else count)
        if held:
            with _scopes.phase("carry"):
                tally["moe_pairs_held"] = sum(held)
                tally["moe_blocks_live"] = sum(live)
        with _scopes.phase("head"):
            return self.logits(w, x), tuple(pools), ()


def _rope_freqs(x, pos, inv_freq, cos_scale=1.0):
    """:func:`_rope` with the inverse frequencies given (YaRN's)."""
    half = x.shape[-1] // 2
    ang = pos.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(ang) * cos_scale, jnp.sin(ang) * cos_scale
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


class Xing4DecodeAdapter(LatentDecodeAdapter):
    """mHC streams around the family's two sublayers (xing4.py
    Xing4ForCausalLM): latent attention with a low-rank query, and
    sigmoid-routed experts, all of them held. The streams, the three mHC
    maps and the router are float32 (the last two at full matmul
    precision); the projections, the latent and the experts run in the
    weights' dtype."""

    def __init__(self, model):
        cfg = model.config
        self._setup(cfg)
        self.experts = self.experts_routed = cfg.n_routed_experts
        self.hc_streams = cfg.hc_mult

        def hc(m):
            return {"phi": m.phi._data, "bias": m.bias._data,
                    "alpha": m.alpha._data}

        layers = []
        for blk in model.model.layers:
            at = blk.self_attn
            layers.append(dict(
                self._block_weights(blk),
                hc_attn=hc(blk.hc_attn), hc_mlp=hc(blk.hc_mlp),
                qa_w=at.q_a_proj.weight._data,
                q_ln=at.q_a_layernorm.weight._data,
                qb_w=at.q_b_proj.weight._data))
        self._set_weights(model, layers)

    def hc_maps(self, H, X):
        """The three mHC maps of streams X [..., n, C] (float32):
        -> (pre [..., n], post [..., n], res [..., n, n]), res doubly
        stochastic by Sinkhorn's iterations."""
        cfg, f32 = self.cfg, jnp.float32
        n, eps = cfg.hc_mult, cfg.hc_eps
        v = X.reshape(X.shape[:-2] + (-1,))
        u = v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True) + eps)
        h = jnp.dot(u, H["phi"].astype(f32),
                    precision=jax.lax.Precision.HIGHEST)
        a, b = H["alpha"].astype(f32), H["bias"].astype(f32)
        pre = jax.nn.sigmoid(a[0] * h[..., :n] + b[:n])
        post = 2.0 * jax.nn.sigmoid(a[1] * h[..., n:2 * n] + b[n:2 * n])
        m = jnp.exp(jnp.clip(a[2] * h[..., 2 * n:] + b[2 * n:],
                             cfg.mhc_h_res_clamp_min,
                             cfg.mhc_h_res_clamp_max))
        m = m.reshape(m.shape[:-1] + (n, n))
        for _ in range(cfg.hc_sinkhorn_iters):
            m = m / (m.sum(-2, keepdims=True) + eps)        # columns
            m = m / (m.sum(-1, keepdims=True) + eps)        # rows
        return pre, post, m

    def layers(self, w, x, pos, attend, count=None):
        cfg, dt, f32 = self.cfg, self.dtype, jnp.float32
        eps, lead = cfg.rms_norm_eps, x.shape[:-1]

        def sublayer(H, X, fn):
            # the mixes are sums of n products an element, written as such:
            # a float32 einsum would go to the MXU in one bf16 pass and
            # round the whole stream at every sublayer
            with _scopes.phase("mix"):
                pre, post, res = self.hc_maps(H, X)
                z = (pre[..., None] * X).sum(-2)
            y = fn(z)
            with _scopes.phase("mix"):
                mixed = (res[..., None] * X[..., None, :, :]).sum(-2)
                return mixed + post[..., None] * y.astype(f32)[..., None, :]

        with _scopes.phase("mix"):
            X = jnp.broadcast_to(x.astype(f32)[..., None, :],
                                 lead + (cfg.hc_mult, x.shape[-1]))
        for i, W in enumerate(self._control(w["layers"])):
            def attention(z, i=i, W=W):
                with _scopes.phase("attn.proj"):
                    h = _rms(z, W["in_ln"], eps, dt)
                return self.latent_attention(i, W, h, pos, attend)

            def ffn(z, W=W):
                with _scopes.phase("ffn"):
                    if "dense" in W:
                        return self._swiglu(W["dense"],
                                            _rms(z, W["post_ln"], eps, dt))
                    h32 = _rms(z, W["post_ln"], eps, f32)
                return self.moe(W, h32.reshape(-1, h32.shape[-1]), count) \
                    .reshape(h32.shape)

            X = sublayer(W["hc_attn"], X, attention)
            X = sublayer(W["hc_mlp"], X, ffn)
        with _scopes.phase("mix"):
            return X.sum(-2).astype(dt)


class SarvamDecodeAdapter(LatentDecodeAdapter):
    """One residual stream around the family's two sublayers (sarvam.py
    SarvamForCausalLM): latent attention with a full-rank, per-head
    normed query, and sigmoid-routed experts of which this model may hold
    a share (``experts`` of ``experts_routed``, from ``expert_first``).
    The stream and the router are float32 (the router at full matmul
    precision); the projections, the latent and the experts run in the
    weights' dtype."""

    def __init__(self, model):
        cfg = model.config
        self._setup(cfg)
        self.experts = cfg.num_experts_held
        self.experts_routed = cfg.num_experts
        self.expert_first = cfg.expert_first
        layers = []
        for blk in model.model.layers:
            W = dict(self._block_weights(blk),
                     q_w=blk.self_attn.q_proj.weight._data)
            if cfg.use_qk_norm:
                W["q_head_ln"] = blk.self_attn.q_norm.weight._data
            layers.append(W)
        self._set_weights(model, layers)

    def layers(self, w, x, pos, attend, count=None):
        cfg, dt, f32 = self.cfg, self.dtype, jnp.float32
        eps = cfg.rms_norm_eps
        x = x.astype(f32)
        for i, W in enumerate(self._control(w["layers"])):
            with _scopes.phase("attn.proj"):
                h = _rms(x, W["in_ln"], eps, dt)
                x = x + self.latent_attention(i, W, h, pos,
                                              attend).astype(f32)
            if "dense" in W:
                with _scopes.phase("ffn"):
                    x = x + self._swiglu(
                        W["dense"],
                        _rms(x, W["post_ln"], eps, dt)).astype(f32)
            else:
                with _scopes.phase("ffn"):
                    h32 = _rms(x, W["post_ln"], eps, f32)
                y = self.moe(W, h32.reshape(-1, h32.shape[-1]), count)
                with _scopes.phase("moe.combine"):
                    x = x + y.reshape(h32.shape)
        return x.astype(dt)


def _ragged_attn(q, kpages, vpages, block_tables, context_lens,
                 query_lens, q_starts, row_of, hd):
    """Ragged mixed prefill+decode attention over PAGED pools for the
    serving engine: q [T, nh, hd] flat token axis, per-row spans as in
    ragged_paged_attention, which picks the Pallas kernel or the XLA
    composition from the backend and the pool shapes
    (``paged_attention.ragged_impl``)."""
    from ..incubate.nn.pallas.paged_attention import ragged_paged_attention

    return ragged_paged_attention(
        q, kpages, vpages, block_tables, context_lens, query_lens,
        q_starts=q_starts, row_of=row_of, scale=hd ** -0.5)


def _chunk_sdpa(q, ck, cv, pos, hd):
    """Chunked causal attention over the cache for speculative verify:
    q [b, g, nh, hd] at per-row positions pos [b, g] attends to every
    cache slot t <= pos[b, g] (the chunk's own k/v were written before
    this call, so within-chunk causality falls out of the position
    mask). Handles bf16 and int8 cache representations like
    _masked_sdpa."""
    T = ck["q8"].shape[2] if isinstance(ck, dict) else ck.shape[2]
    mask = (jnp.arange(T)[None, None, :] <= pos[:, :, None])[:, None]
    if isinstance(ck, dict):
        sc = jnp.einsum("bghd,bhtd->bhgt", q, ck["q8"].astype(q.dtype),
                        preferred_element_type=jnp.float32)
        sc = sc * ck["s"][:, :, None, :] * (hd ** -0.5)
        sc = jnp.where(mask, sc, -1e30)
        w = jax.nn.softmax(sc, axis=-1)
        wv = (w * cv["s"][:, :, None, :]).astype(q.dtype)
        return jnp.einsum("bhgt,bhtd->bghd", wv, cv["q8"].astype(q.dtype))
    sc = jnp.einsum("bghd,bhtd->bhgt", q, ck,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    sc = jnp.where(mask, sc, -1e30)
    w = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgt,bhtd->bghd", w, cv)


def _causal_prefill_attn(q, k, v, causal, hd, dt):
    """Full-prompt causal attention shared by the adapters' prefill."""
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    preferred_element_type=jnp.float32) * (hd ** -0.5)
    sc = jnp.where(causal, sc, -1e30)
    wts = jax.nn.softmax(sc, axis=-1).astype(dt)
    att = jnp.einsum("bhqk,bkhd->bqhd", wts, v)
    b, plen = q.shape[0], q.shape[1]
    return att.reshape(b, plen, -1)


def _masked_sdpa(q, ck, cv, t_mask, hd):
    """Masked single-query attention over the cache — the
    masked_multihead_attention analog. q [b, nh, hd] is attended against
    the full cache [b, nh, T, hd] with invalid positions masked.

    int8 caches arrive as {"q8": [b,nh,T,hd] int8, "s": [b,nh,T] f32}.
    The dequant NEVER materializes a bf16 cache in HBM: the int8->bf16
    convert fuses into the dot operand read (same trick as the int8
    weight path), and the per-row scales — constant over the head dim —
    are applied on the score side (exact: scores_bht = s_bht * <q, q8>)
    and folded into the softmax weights for the V contraction."""
    if isinstance(ck, dict):
        scores = jnp.einsum("bhd,bhtd->bht", q, ck["q8"].astype(q.dtype),
                            preferred_element_type=jnp.float32)
        scores = scores * ck["s"] * (hd ** -0.5)
        scores = jnp.where(t_mask[None, None, :], scores, -1e30)
        w = jax.nn.softmax(scores, axis=-1)
        wv = (w * cv["s"]).astype(q.dtype)
        return jnp.einsum("bht,bhtd->bhd", wv, cv["q8"].astype(q.dtype))
    scores = jnp.einsum("bhd,bhtd->bht", q, ck,
                        preferred_element_type=jnp.float32) * (hd ** -0.5)
    scores = jnp.where(t_mask[None, None, :], scores, -1e30)
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bht,bhtd->bhd", w, cv)


def _draw(logits, key, t, top_p):
    """One sampled token id a row of [b, V] logits at temperature ``t``
    (a positive scalar, or [b, 1]) inside the nucleus ``top_p`` (None:
    the whole distribution; a scalar, or [b])."""
    lg = logits.astype(jnp.float32) / t
    if top_p is None:
        return jax.random.categorical(key, lg, axis=-1).astype(jnp.int32)
    probs = jax.nn.softmax(lg, axis=-1)
    # one sort, both results kept: the sorted values come back with the
    # permutation (-(-p) is exact), so nothing is gathered back by index
    iota = jax.lax.broadcasted_iota(jnp.int32, probs.shape, probs.ndim - 1)
    neg, sort_idx = jax.lax.sort((-probs, iota), dimension=-1, num_keys=1,
                                 is_stable=True)
    sorted_p = -neg
    cum = jnp.cumsum(sorted_p, axis=-1)
    if not isinstance(top_p, (int, float)):
        top_p = jnp.asarray(top_p, jnp.float32)[..., None]
    keep = (cum - sorted_p) < top_p
    filt = jnp.where(keep, sorted_p, 0.0)
    draw = jax.random.categorical(
        key, jnp.log(jnp.maximum(filt, 1e-30)), axis=-1)
    return jnp.take_along_axis(sort_idx, draw[..., None],
                               axis=-1)[..., 0].astype(jnp.int32)


def _sample(logits, key, temperature, top_p):
    """Greedy / temperature / nucleus sampling over [b, V] logits.

    ``temperature`` and ``top_p`` accept Python scalars (whole-batch —
    the original path, kept bit-identical) OR per-row arrays [b] for
    mixed-request serving batches (serving/engine.py): each row scales
    by its own temperature, filters by its own nucleus (``top_p >= 1``
    keeps the full distribution), and rows with ``temperature == 0``
    take the greedy lane through a ``where`` select. The per-row path
    decides on the device whether any row has a temperature: when none
    has, a ``cond`` skips the sampling lane (softmax, sort, cumsum,
    draw) and the step pays for the ``argmax`` alone; the tokens are the
    same either way, and it stays one program.
    """
    if isinstance(temperature, (int, float)):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return _draw(logits, key, max(temperature, 1e-6), top_p)
    temperature = jnp.asarray(temperature, jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def lane():
        t = jnp.maximum(temperature, 1e-6)[..., None]
        return jnp.where(temperature == 0.0, greedy,
                         _draw(logits, key, t, top_p))

    return jax.lax.cond(jnp.any(temperature > 0.0), lane, lambda: greedy)


def _check_window(ad, plen, max_new_tokens):
    total = plen + max_new_tokens
    if ad.max_positions is not None and total > ad.max_positions:
        raise ValueError(
            f"prompt length {plen} + max_new_tokens {max_new_tokens} = "
            f"{total} exceeds max_position_embeddings {ad.max_positions}; "
            "XLA would silently clamp position gathers past the window")
    return total


def _as_ids(input_ids):
    ids = input_ids._data if isinstance(input_ids, Tensor) else \
        jnp.asarray(np.asarray(input_ids), jnp.int32)
    return ids.astype(jnp.int32)


def _gen_cache(model):
    cache = getattr(model, "_gen_cache", None)
    if cache is None:
        cache = model._gen_cache = {}
    return cache


def _count_cache_lookup(miss: bool):
    """decode fn-cache hit/miss telemetry (generate / spec / beam share
    the counters — a miss is a fresh trace + XLA compile)."""
    if _obs.enabled():
        _obs.registry.counter(
            "decode.cache_miss" if miss else "decode.cache_hit").inc()
        if miss:
            _obs.flight_recorder.record("jit.cache_miss", site="decode")


def generate(model, input_ids, max_new_tokens: int = 32,
             temperature: float = 0.0, top_p: Optional[float] = None,
             eos_token_id: Optional[int] = None, weight_quant=None,
             kv_cache_quant=None, name=None):
    """Greedy / temperature / nucleus decoding, fully compiled, for any
    model exposing ``decode_adapter()``.

    Returns the generated token ids [batch, max_new_tokens] (prompt not
    included). ``temperature=0`` = greedy. Tokens after ``eos_token_id``
    are clamped to eos. ``weight_quant="int8"`` serves per-channel int8
    weights (half the HBM reads of the weight-bandwidth-bound decode);
    ``"int4"`` serves group-wise int4 blocks with an int8 lm_head
    (quarter-width weight stream — reference surface:
    nn/quant/quantized_linear.py weight_only_linear). Quantized copies
    are cached on the model — re-quantize by clearing
    ``model._gen_quant_w`` / ``_gen_quant_w4`` after a weight update.
    ``kv_cache_quant="int8"`` stores the KV cache as int8 with
    per-(position, head) scales computed at write time; the dequant is
    fused into the attention read (reference surface:
    masked_multihead_attention's cache_k/v_quant_scales args).
    """
    if kv_cache_quant not in (None, "int8"):
        raise ValueError("kv_cache_quant must be None or 'int8'")
    ad = model.decode_adapter()
    ids = _as_ids(input_ids)
    b, plen = ids.shape
    total = _check_window(ad, plen, max_new_tokens)
    # detach the weights from the adapter: the jitted fn's closure keeps
    # the adapter alive in _gen_cache, and pinning a stale copy of every
    # parameter array there would hold ~model-size HBM after updates
    w_now, ad.weights = ad.weights, None
    w_now = _resolve_weight_quant(model, w_now, weight_quant)

    kv_quant = kv_cache_quant == "int8"
    telemetry = _obs.enabled()

    def make_prefill():
        def run_prefill(weights, ids, key):
            weights = _activate_q4(weights)
            x, ck, cv = ad.prefill(weights, ids, total,
                                   kv_quant=kv_quant)
            lg0 = ad.logits(weights, x[:, -1])
            key, k0 = jax.random.split(key)
            tok0 = _sample(lg0, k0, temperature, top_p)
            alive = jnp.ones((b,), bool)
            if eos_token_id is not None:
                alive = alive & (tok0 != eos_token_id)
            return tok0, ck, cv, key, alive
        return run_prefill

    def make_decode():
        def run_decode(weights, tok0, ck, cv, key, alive):
            weights = _activate_q4(weights)

            def step(carry, _):
                tok, pos, ck, cv, key, alive = carry
                key, sk = jax.random.split(key)
                t_mask = jnp.arange(total) <= pos
                lg, ck, cv = ad.step(weights, tok, pos, ck, cv, t_mask)
                nxt = _sample(lg, sk, temperature, top_p)
                if eos_token_id is not None:
                    nxt = jnp.where(alive, nxt, eos_token_id)
                    alive = alive & (nxt != eos_token_id)
                return (nxt, pos + 1, ck, cv, key, alive), nxt

            carry = (tok0, jnp.int32(plen), ck, cv, key, alive)
            if max_new_tokens > 1:
                _, rest = jax.lax.scan(step, carry, None,
                                       length=max_new_tokens - 1)
                toks = jnp.concatenate([tok0[None], rest], axis=0)
            else:
                toks = tok0[None]
            return jnp.swapaxes(toks, 0, 1)   # [b, max_new]
        return run_decode

    cache = _gen_cache(model)
    # the telemetry flag is part of the key: the split two-dispatch path
    # and the fused one-dispatch path are distinct programs
    key_cache = ("sample", b, plen, max_new_tokens, temperature, top_p,
                 eos_token_id, weight_quant, kv_cache_quant, telemetry)
    entry = cache.get(key_cache)
    _count_cache_lookup(miss=entry is None)

    if not telemetry:
        # fused path: the WHOLE generation is one compiled dispatch
        if entry is None:
            run_prefill, run_decode = make_prefill(), make_decode()

            def run(weights, ids, key):
                tok0, ck, cv, key, alive = run_prefill(weights, ids, key)
                return run_decode(weights, tok0, ck, cv, key, alive)

            entry = cache[key_cache] = jax.jit(run)
        return Tensor(entry(w_now, ids, _rng.next_key()))

    # telemetry path: prefill and decode compile as SEPARATE dispatches
    # so the prefill/decode time split is an honest device-time split
    # (one extra host round-trip per generate call — accepted while
    # telemetry is on). AOT lower().compile() doubles as the
    # cost_analysis() source without compiling anything twice.
    key = _rng.next_key()
    with _obs.span("decode.generate", cat="decode",
                   args={"batch": b, "prompt": plen,
                         "max_new": max_new_tokens}):
        if entry is None:
            with _obs.span("jit.compile", cat="jit",
                           args={"site": "decode.prefill"}):
                pf = jax.jit(make_prefill()).lower(
                    w_now, ids, key).compile()
            _obs.record_cost_analysis("decode.prefill", pf)
        else:
            pf = entry[0]
        t0 = time.perf_counter()
        with _obs.span("decode.prefill", cat="decode",
                       args={"tokens": b * plen}):
            res = jax.block_until_ready(pf(w_now, ids, key))
        t_prefill = time.perf_counter() - t0
        if entry is None:
            with _obs.span("jit.compile", cat="jit",
                           args={"site": "decode.decode"}):
                df = jax.jit(make_decode()).lower(w_now, *res).compile()
            _obs.record_cost_analysis("decode.steps", df)
            cache[key_cache] = (pf, df)
        else:
            df = entry[1]
        t0 = time.perf_counter()
        with _obs.span("decode.decode", cat="decode",
                       args={"tokens": b * max_new_tokens}):
            out = jax.block_until_ready(df(w_now, *res))
        t_decode = time.perf_counter() - t0

    reg = _obs.registry
    reg.histogram("decode.prefill_time").observe(t_prefill)
    reg.histogram("decode.decode_time").observe(t_decode)
    reg.histogram("decode.token_latency").observe(
        t_decode / max_new_tokens)
    reg.counter("decode.prefill_tokens").inc(b * plen)
    reg.counter("decode.decode_tokens").inc(b * max_new_tokens)
    _obs.sample_device_memory()
    return Tensor(out)


def speculative_generate(model, input_ids, max_new_tokens: int = 32,
                         gamma: int = 4, draft_model=None,
                         draft_layers: Optional[int] = None,
                         eos_token_id: Optional[int] = None,
                         weight_quant=None, kv_cache_quant=None,
                         return_stats: bool = False):
    """Speculative greedy decoding, fully compiled (reference analog:
    the speculative serving tier — PaddleNLP's speculate_decoding and
    the fused_speculate_* ops feeding masked_multihead_attention with
    draft token chunks).

    A cheap draft proposes ``gamma`` tokens autoregressively; the target
    verifies all of them in ONE chunked forward pass (one weight read
    for up to gamma+1 emitted tokens — the weight-bandwidth win).
    Greedy acceptance makes the output IDENTICAL to ``generate(...,
    temperature=0)``: a proposal is accepted iff it equals the target's
    argmax given the accepted prefix, and the first mismatch is replaced
    by the target's own token. Acceptance is tracked PER ROW — batch
    rows advance at their own rate via per-row cache/output pointers.

    Draft choices: ``draft_model`` (a smaller CausalLM sharing the
    vocab) or ``draft_layers=k`` (self-speculative early exit: the
    target's first k blocks + its final norm/head, zero extra weights).

    TPU-native structure: the whole loop is one ``lax.while_loop`` on
    device — no host round-trip per iteration; out-of-window writes from
    finished rows are dropped by scatter mode="drop".
    """
    if (draft_model is None) == (draft_layers is None):
        raise ValueError("pass exactly one of draft_model / draft_layers")
    if gamma < 1:
        raise ValueError("gamma must be >= 1")
    kv_quant = kv_cache_quant == "int8"
    if kv_cache_quant not in (None, "int8"):
        raise ValueError("kv_cache_quant must be None or 'int8'")

    ad = model.decode_adapter()
    ids = _as_ids(input_ids)
    b, plen = ids.shape
    # window slack: verify writes run up to gamma past the last commit
    total = _check_window(ad, plen, max_new_tokens + 2 * gamma + 2)

    w_now, ad.weights = ad.weights, None
    w_now = _resolve_weight_quant(model, w_now, weight_quant)

    if draft_model is not None:
        dad = draft_model.decode_adapter()
        if dad.vocab_size != ad.vocab_size:
            raise ValueError("draft vocab must match the target's")
        # the draft decodes over the same window — a shorter draft
        # position range would silently clamp wpe/rope gathers and
        # quietly zero the acceptance rate
        _check_window(dad, plen, max_new_tokens + 2 * gamma + 2)
        dw_now, dad.weights = dad.weights, None
        dw_now = _resolve_weight_quant(draft_model, dw_now, weight_quant)
        # structural key: the cached fn closes over dad's static config,
        # so two drafts may share it ONLY if every field the traced code
        # reads is identical (weights themselves are traced args)
        draft_key = ("model", type(dad).__name__, dad.num_layers,
                     dad.num_heads, dad.num_kv_heads, dad.head_dim,
                     dad.vocab_size, getattr(dad, "eps", None),
                     getattr(dad, "rope_base", None))
    else:
        if not 0 < draft_layers < ad.num_layers:
            raise ValueError("draft_layers must be in (0, num_layers)")
        dad = ad
        dw_now = dict(w_now)
        dw_now["layers"] = list(w_now["layers"])[:draft_layers]
        draft_key = ("self", draft_layers)

    cache = _gen_cache(model)
    key_cache = ("spec", b, plen, max_new_tokens, gamma, eos_token_id,
                 weight_quant, kv_cache_quant, draft_key)
    fn = cache.get(key_cache)
    _count_cache_lookup(miss=fn is None)
    if fn is None:
        W_out = max_new_tokens + gamma + 1

        def run(weights, dweights, ids):
            weights = _activate_q4(weights)
            dweights = _activate_q4(dweights)
            x, ck, cv = ad.prefill(weights, ids, total,
                                   kv_quant=kv_quant)
            _, dck, dcv = dad.prefill(dweights, ids, total,
                                      kv_quant=kv_quant)
            cur = jnp.argmax(ad.logits(weights, x[:, -1]),
                             axis=-1).astype(jnp.int32)       # [b]
            ptr = jnp.zeros((b,), jnp.int32)     # tokens committed to out
            ln = jnp.full((b,), plen, jnp.int32)  # committed cache length
            out = jnp.zeros((b, W_out), jnp.int32)
            n_iter = jnp.int32(0)
            n_acc = jnp.int32(0)

            def cond(carry):
                return jnp.min(carry[1]) < max_new_tokens

            def body(carry):
                out, ptr, cur, ln, ck, cv, dck, dcv, n_iter, n_acc = carry

                # -- draft proposes gamma tokens (one-token chunk steps)
                def dstep(c, j):
                    tok, dck, dcv = c
                    lg, dck, dcv = dad.chunk_step(
                        dweights, tok[:, None], (ln + j)[:, None],
                        dck, dcv)
                    nxt = jnp.argmax(lg[:, 0], -1).astype(jnp.int32)
                    return (nxt, dck, dcv), nxt

                (last_d, dck, dcv), props = jax.lax.scan(
                    dstep, (cur, dck, dcv), jnp.arange(gamma))
                props = jnp.swapaxes(props, 0, 1)        # [b, gamma]
                # write the final proposal's kv so the draft cache stays
                # complete when every proposal is accepted
                _, dck, dcv = dad.chunk_step(
                    dweights, last_d[:, None], (ln + gamma)[:, None],
                    dck, dcv)

                # -- target verifies the whole chunk in one pass
                chunk = jnp.concatenate([cur[:, None], props], 1)
                pos = ln[:, None] + jnp.arange(gamma + 1)[None, :]
                lg, ck, cv = ad.chunk_step(weights, chunk, pos, ck, cv)
                tgt = jnp.argmax(lg, -1).astype(jnp.int32)  # [b, g+1]

                # longest accepted prefix: props[:, j] must equal the
                # target token after chunk[:, :j+1]
                match = (props == tgt[:, :gamma]).astype(jnp.int32)
                acc = jnp.cumprod(match, axis=1).sum(axis=1)  # [b]

                # commit [cur, accepted...] — unaccepted tail slots get
                # overwritten next iteration (ptr only advances 1+acc)
                bidx = jnp.arange(b)[:, None]
                out = out.at[bidx, ptr[:, None]
                             + jnp.arange(gamma + 1)[None, :]].set(
                    chunk, mode="drop")
                new_cur = tgt[jnp.arange(b), acc]
                # stats only count rows still producing real tokens —
                # finished rows loop on a frozen cache (writes dropped)
                # and their phantom acceptances would skew the mean
                active = (ptr < max_new_tokens).astype(jnp.int32)
                return (out, ptr + 1 + acc, new_cur, ln + 1 + acc,
                        ck, cv, dck, dcv, n_iter + active.sum(),
                        n_acc + (acc * active).sum())

            carry = (out, ptr, cur, ln, ck, cv, dck, dcv, n_iter, n_acc)
            out, ptr, _, _, _, _, _, _, n_iter, n_acc = \
                jax.lax.while_loop(cond, body, carry)
            toks = out[:, :max_new_tokens]
            if eos_token_id is not None:
                seen = jnp.cumsum(toks == eos_token_id, 1) \
                    - (toks == eos_token_id)
                toks = jnp.where(seen > 0, eos_token_id, toks)
            return toks, n_iter, n_acc

        fn = jax.jit(run)
        cache[key_cache] = fn

    toks, n_iter, n_acc = fn(w_now, dw_now, ids)
    if _obs.enabled():
        it = max(int(n_iter), 1)
        reg = _obs.registry
        reg.gauge("decode.spec_acceptance_rate").set(
            float(n_acc) / (it * gamma))
        reg.gauge("decode.spec_tokens_per_pass").set(
            1.0 + float(n_acc) / it)
        reg.counter("decode.decode_tokens").inc(b * max_new_tokens)
    if return_stats:
        # n_iter = active (row, iteration) pairs; n_acc = accepted
        # proposals summed over those pairs
        it = max(int(n_iter), 1)
        stats = {"iterations": int(n_iter),
                 "mean_accepted": float(n_acc) / it,
                 "tokens_per_target_pass": 1.0 + float(n_acc) / it}
        return Tensor(toks), stats
    return Tensor(toks)


def _kv_rows(cache, idx_or_reps, gather):
    """Beam bookkeeping on either cache representation (plain array or
    quant dict — every leaf is batch-major): batch-axis gather
    (parent-beam reorder) or repeat (beam expansion)."""
    if gather:
        return jax.tree.map(lambda a: a[idx_or_reps], cache)
    return jax.tree.map(lambda a: jnp.repeat(a, idx_or_reps, axis=0),
                        cache)


def beam_search(model, input_ids, max_new_tokens: int = 32,
                num_beams: int = 4, length_penalty: float = 0.0,
                eos_token_id: Optional[int] = None, weight_quant=None,
                kv_cache_quant=None):
    """Compiled beam search over the fused decode path (reference: the
    gather_tree op exists exactly for this — beam parent pointers are
    resolved into sequences at the end, nn/functional/extend.py
    gather_tree). Supports the same serving quant tiers as generate()
    (weight_quant int8/int4, kv_cache_quant int8).

    Returns token ids [batch, max_new_tokens] of the best beam.
    """
    if kv_cache_quant not in (None, "int8"):
        raise ValueError("kv_cache_quant must be None or 'int8'")
    kv_quant = kv_cache_quant == "int8"
    ad = model.decode_adapter()
    ids = _as_ids(input_ids)
    b, plen = ids.shape
    total = _check_window(ad, plen, max_new_tokens)
    w_now, ad.weights = ad.weights, None  # see generate()
    w_now = _resolve_weight_quant(model, w_now, weight_quant)
    K = num_beams
    V = ad.vocab_size

    cache = _gen_cache(model)
    key_cache = ("beam", b, plen, max_new_tokens, K, length_penalty,
                 eos_token_id, weight_quant, kv_cache_quant)
    fn = cache.get(key_cache)
    _count_cache_lookup(miss=fn is None)
    if fn is None:

        def run(weights, ids):
            weights = _activate_q4(weights)
            x, ck, cv = ad.prefill(weights, ids, total,
                                   kv_quant=kv_quant)
            lg0 = jax.nn.log_softmax(
                ad.logits(weights, x[:, -1]).astype(jnp.float32), axis=-1)
            # seed the beams with the prompt's top-K continuations
            scores0, tok0 = jax.lax.top_k(lg0, K)      # [b, K]
            # expand caches to one row per beam: [L, b*K, T, ...]
            ck = tuple(_kv_rows(c, K, gather=False) for c in ck)
            cv = tuple(_kv_rows(c, K, gather=False) for c in cv)
            alive0 = jnp.ones((b, K), bool)
            if eos_token_id is not None:
                alive0 = tok0 != eos_token_id
            lens0 = jnp.ones((b, K), jnp.float32)  # seed token counts

            def step(carry, _):
                tok, pos, ck, cv, scores, alive, lens = carry
                t_mask = jnp.arange(total) <= pos
                lg, ck, cv = ad.step(weights, tok.reshape(b * K), pos,
                                     ck, cv, t_mask)
                logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
                logp = logp.reshape(b, K, V)
                # finished beams only extend with EOS at zero cost
                if eos_token_id is not None:
                    eos_only = jnp.full((V,), -jnp.inf).at[
                        eos_token_id].set(0.0)
                    logp = jnp.where(alive[..., None], logp,
                                     eos_only[None, None, :])
                cand = scores[..., None] + logp        # [b, K, V]
                flat = cand.reshape(b, K * V)
                new_scores, idx = jax.lax.top_k(flat, K)   # [b, K]
                parent = (idx // V).astype(jnp.int32)
                nxt = (idx % V).astype(jnp.int32)
                # reorder caches by parent beam (per batch row)
                gidx = (jnp.arange(b)[:, None] * K + parent) \
                    .reshape(b * K)
                ck = tuple(_kv_rows(c, gidx, gather=True) for c in ck)
                cv = tuple(_kv_rows(c, gidx, gather=True) for c in cv)
                alive = jnp.take_along_axis(alive, parent, axis=1)
                lens = jnp.take_along_axis(lens, parent, axis=1)
                # a live beam grows by its new token (incl. a fresh EOS)
                lens = lens + alive.astype(jnp.float32)
                if eos_token_id is not None:
                    alive = alive & (nxt != eos_token_id)
                return (nxt, pos + 1, ck, cv, new_scores, alive, lens), \
                    (nxt, parent)

            carry = (tok0, jnp.int32(plen), ck, cv, scores0, alive0,
                     lens0)
            if max_new_tokens > 1:
                carry, (toks, parents) = jax.lax.scan(
                    step, carry, None, length=max_new_tokens - 1)
                final_scores = carry[4]
                final_lens = carry[6]
                # [T, b, K] including the seeded first token (parent = own
                # beam index by construction of the seed)
                all_toks = jnp.concatenate([tok0[None], toks], axis=0)
                all_parents = jnp.concatenate(
                    [jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32),
                                      (1, b, K)), parents], axis=0)
            else:
                final_scores = scores0
                final_lens = lens0
                all_toks = tok0[None]
                all_parents = jnp.broadcast_to(
                    jnp.arange(K, dtype=jnp.int32), (1, b, K))
            # resolve parent pointers into sequences (gather_tree)
            from ..nn.functional.extend import gather_tree

            seqs = gather_tree(Tensor(all_toks),
                               Tensor(all_parents))._data  # [T, b, K]
            if length_penalty:
                # GNMT-style: each beam normalized by ITS OWN finished
                # length (frozen at EOS), not a shared constant
                final_scores = final_scores / (
                    final_lens ** length_penalty)
            best = jnp.argmax(final_scores, axis=1)      # [b]
            out = jnp.take_along_axis(
                seqs, best[None, :, None], axis=2)[..., 0]  # [T, b]
            return jnp.swapaxes(out, 0, 1)               # [b, T]

        fn = jax.jit(run)
        cache[key_cache] = fn

    return Tensor(fn(w_now, ids))
