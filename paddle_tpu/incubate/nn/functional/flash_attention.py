"""Flash attention (reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu,
python/paddle/nn/functional/flash_attention.py).

Layout: [batch, seq, num_heads, head_dim] (paddle convention).

On TPU this dispatches to the Pallas flash-attention kernel
(:mod:`paddle_tpu.incubate.nn.pallas.flash_attn`) when the shapes tile onto
the MXU (seq % block == 0, head_dim in {64,128,256}); otherwise it falls back
to an XLA softmax composition, which XLA still fuses well.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ....core import random as _rng
from ....ops._helpers import as_tensor, run_op, unwrap

__all__ = ["flash_attention", "flash_attn_unpadded",
           "scaled_dot_product_attention", "attention_impl"]


import threading

_recompute_tls = threading.local()


def _entering_recompute():
    """Context marker set by the recompute engine: the Pallas custom-vjp
    does not compose with jax.checkpoint's re-linearization (the raw fwd
    pallas_call would be jvp'd), so attention inside a rematerialized
    block uses the XLA composition (within ~15% at the shapes where both
    apply; tools/tune_flash_attn.py)."""

    class _Ctx:
        def __enter__(self):
            _recompute_tls.depth = getattr(_recompute_tls, "depth", 0) + 1

        def __exit__(self, *a):
            _recompute_tls.depth -= 1

    return _Ctx()


def attention_impl(q_shape, kv_seq, head_dim) -> str:
    """Which implementation attention over these shapes resolves to:
    ``"pallas"`` (the flash kernel) or ``"xla"`` (the softmax
    composition). Decided by the backend, the shapes and the recompute
    scope alone — never by whether something failed to import or
    compile."""
    if getattr(_recompute_tls, "depth", 0):
        return "xla"
    if jax.default_backend() != "tpu":
        return "xla"
    seq = q_shape[1]
    # measured on v5e (tools/tune_flash_attn.py): at seq<=512 the XLA
    # softmax composition beats the Pallas kernel fwd+bwd (13ms vs 16ms
    # per 12 layers at bench shapes) because the s^2 logits still fit HBM
    # comfortably; the flash kernel's O(s) memory wins from ~1k sequence
    # where the materialized [b,h,s,s] tensor starts to dominate
    if (head_dim in (64, 128, 256) and seq % 128 == 0
            and kv_seq % 128 == 0 and seq >= 1024):
        return "pallas"
    return "xla"


def _pallas_attention(q_shape, kv_heads, causal):
    """The flash kernel as a function of (q, k, v) arrays. Under an
    active SPMD mesh it is mapped over the mesh by hand: GSPMD cannot
    partition a Mosaic kernel (lowering fails with "Mosaic kernels cannot
    be automatically partitioned"). Attention is independent per batch
    row and per head, so batch splits over "dp" and heads over "mp"
    where they divide; the sequence stays whole inside each shard (a
    seq-sharded layout is gathered at the boundary, which is what the
    "gspmd" sequence-parallel mode means)."""
    from ....distributed.auto_parallel.constraint import _active_jax_mesh
    from ..pallas.flash_attn import flash_attention as pallas_fa

    fn = functools.partial(pallas_fa, causal=causal)
    mesh = _active_jax_mesh()
    if mesh is None or mesh.size == 1:
        return fn
    from jax.sharding import PartitionSpec as P

    def axis(name, *dims):
        n = mesh.shape.get(name, 1)
        return name if n > 1 and all(d % n == 0 for d in dims) else None

    spec = P(axis("dp", q_shape[0]), None,
             axis("mp", q_shape[2], kv_heads), None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)


def _xla_attention(q, k, v, causal, scale=None):
    """Reference composition: XLA fuses this into a reasonable kernel chain.

    Stays in the paddle [b, s, h, d] layout end to end — the head/seq
    permutation is folded into the dot_general dimension numbers instead of
    materialized transposes (measured ~20% faster fwd+bwd at bench shapes
    on v5e, tools/probe_attn_paths2.py)."""
    s = scale if scale is not None else q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * s
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), dtype=bool), klen - qlen)
        logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v)


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    head_dim = q.shape[-1]

    if attention_impl(tuple(q.shape), k.shape[1], head_dim) == "pallas" \
            and not return_softmax:
        out = run_op(
            _pallas_attention(tuple(q.shape), k.shape[2], causal),
            [q, k, v], name="flash_attention",
        )
    else:
        out = run_op(
            lambda qa, ka, va: _xla_attention(qa, ka, va, causal),
            [q, k, v], name="flash_attention",
        )

    if dropout > 0.0 and training:
        key_ = _rng.next_key()
        out = run_op(
            lambda o: jnp.where(
                jax.random.bernoulli(key_, 1.0 - dropout, o.shape),
                o / (1.0 - dropout), 0.0).astype(o.dtype),
            [out], name="attn_dropout",
        )
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale=None, dropout=0.0,
                        causal=False, return_softmax=False, name=None):
    """Varlen flash attention: segment-masked single-sequence attention.

    q/k/v: [total_tokens, num_heads, head_dim]; cu_seqlens: [batch+1].
    """
    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    cq = unwrap(as_tensor(cu_seqlens_q)).astype(jnp.int32)
    ck = unwrap(as_tensor(cu_seqlens_k)).astype(jnp.int32)

    def fn(qa, ka, va):
        tq = qa.shape[0]
        tk = ka.shape[0]
        # segment id per token
        seg_q = jnp.cumsum(
            jnp.zeros(tq, jnp.int32).at[cq[1:-1]].add(1))
        seg_k = jnp.cumsum(
            jnp.zeros(tk, jnp.int32).at[ck[1:-1]].add(1))
        s = scale if scale is not None else qa.shape[-1] ** -0.5
        logits = jnp.einsum("qhd,khd->hqk", qa, ka,
                            preferred_element_type=jnp.float32) * s
        mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(tq) - jnp.take(cq, seg_q)
            pos_k = jnp.arange(tk) - jnp.take(ck, seg_k)
            mask = mask & (pos_q[:, None] >= pos_k[None, :])
        logits = jnp.where(mask[None], logits, -1e30)
        w = jax.nn.softmax(logits, axis=-1).astype(va.dtype)
        return jnp.einsum("hqk,khd->qhd", w, va)

    out = run_op(fn, [q, k, v], name="flash_attn_unpadded")
    return out, None


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None):
    from ....nn.functional.common import scaled_dot_product_attention as sdpa

    return sdpa(query, key, value, attn_mask, dropout_p, is_causal, training)
