"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference provides only the group plumbing for its ``sep`` axis and
leaves the attention-side sequence exchange to model libraries (reference:
python/paddle/distributed/fleet/base/topology.py:199-258 sep groups;
test/collective/fleet/hybrid_parallel_sep_model.py:132-148 shows the
user-side pattern; no ring/Ulysses kernel in-repo). Here both are
first-class, TPU-native:

- :func:`ring_attention` — blockwise-softmax attention where K/V chunks
  rotate around the sequence-axis ring via ``lax.ppermute`` (ICI
  neighbor exchange), with online max/denominator accumulation. O(S/P)
  memory per chip; compute overlaps the permute (XLA pipelines the
  collective-permute with the per-step einsum).
- :func:`ulysses_attention` — all-to-all head<->sequence exchange
  (DeepSpeed-Ulysses style): each chip attends over the FULL sequence
  for ``heads/P`` heads, so the local attention can use the Pallas flash
  kernel, then a second all-to-all restores sequence sharding.

Both are written to be called INSIDE ``jax.shard_map`` over a mesh with
a sequence axis; the ``*_sharded`` wrappers apply shard_map for global
arrays. Both are differentiable (ppermute/all_to_all have transpose
rules; the ring step is rematerialized so residuals stay O(chunk)).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ring_attention", "ulysses_attention",
           "ring_attention_sharded", "ulysses_attention_sharded"]

_NEG_INF = -1e30


def _chunk_attention(q, k, v, scale, pos_q, pos_k, causal):
    """One blockwise step: returns (unnormalized acc, rowmax m, denom l).

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; pos_*: global token positions.
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = pos_q[:, None] >= pos_k[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)          # [b,h,q,1]
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    acc = jnp.einsum("bhqk,bkhd->bhqd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return acc, m, l


def ring_attention(q, k, v, axis_name, causal=True, scale=None):
    """Ring attention over the ``axis_name`` mesh axis (call in shard_map).

    q/k/v: LOCAL sequence shards ``[batch, seq_local, heads, head_dim]``.
    Returns the local output shard, same shape/dtype as q.
    """
    b, sl, h, d = q.shape
    if scale is None:
        scale = float(d) ** -0.5
    axis_size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    pos_q = my * sl + jnp.arange(sl)

    @jax.checkpoint
    def step_compute(q, k_cur, v_cur, src, m_prev, l_prev, acc_prev):
        pos_k = src * sl + jnp.arange(sl)
        acc_c, m_c, l_c = _chunk_attention(q, k_cur, v_cur, scale,
                                           pos_q, pos_k, causal)
        m_new = jnp.maximum(m_prev, m_c)
        corr_prev = jnp.exp(m_prev - m_new)
        corr_c = jnp.exp(m_c - m_new)
        l_new = corr_prev * l_prev + corr_c * l_c
        acc_new = corr_prev * acc_prev + corr_c * acc_c
        return m_new, l_new, acc_new

    def body(carry, t):
        k_cur, v_cur, m_prev, l_prev, acc_prev = carry
        src = (my - t) % axis_size
        m_new, l_new, acc_new = step_compute(
            q, k_cur, v_cur, src, m_prev, l_prev, acc_prev)
        # rotate kv to the next rank (skip after the final step's compute
        # would be ideal; XLA overlaps the permute with the next compute)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sl, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sl, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, sl, d), jnp.float32)
    (k_f, v_f, m_f, l_f, acc_f), _ = jax.lax.scan(
        body, (k, v, m0, l0, acc0), jnp.arange(axis_size))
    del k_f, v_f
    l_safe = jnp.where(l_f == 0.0, 1.0, l_f)
    out = (acc_f / l_safe).astype(q.dtype)          # [b,h,s,d]
    return jnp.swapaxes(out, 1, 2)


def ulysses_attention(q, k, v, axis_name, causal=True, scale=None,
                      attention_fn=None):
    """Ulysses all-to-all attention over ``axis_name`` (call in shard_map).

    q/k/v: LOCAL sequence shards ``[batch, seq_local, heads, head_dim]``;
    ``heads`` must be divisible by the axis size. Exchanges seq<->heads so
    each rank runs full-sequence attention on heads/P heads (flash-attn
    eligible), then exchanges back.
    """
    b, sl, h, d = q.shape
    axis_size = jax.lax.psum(1, axis_name)

    def a2a_fwd(x):
        # [b, s_loc, h, d] -> [b, s_full, h/P, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def a2a_bwd(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qg, kg, vg = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    if attention_fn is None:
        def attention_fn(q_, k_, v_):
            from ..incubate.nn.functional.flash_attention import (
                _xla_attention, attention_impl)
            from ..incubate.nn.pallas.flash_attn import flash_attention

            if attention_impl(tuple(q_.shape), k_.shape[1],
                              q_.shape[-1]) == "pallas":
                return flash_attention(q_, k_, v_, causal=causal, scale=scale)
            return _xla_attention(q_, k_, v_, causal, scale)

    out = attention_fn(qg, kg, vg)
    return a2a_bwd(out)


def _sharded(fn, mesh, seq_axis, batch_axis=None):
    spec = P(batch_axis, seq_axis, None, None)
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )


def ring_attention_sharded(q, k, v, mesh: Mesh, seq_axis: str,
                           causal=True, scale=None, batch_axis=None):
    """Ring attention on GLOBAL arrays [b, s, h, d] sharded over seq_axis."""
    fn = functools.partial(ring_attention, axis_name=seq_axis, causal=causal,
                           scale=scale)
    wrapped = _sharded(lambda q, k, v: fn(q, k, v), mesh, seq_axis,
                       batch_axis)
    spec = P(batch_axis, seq_axis, None, None)
    q, k, v = (jax.device_put(x, NamedSharding(mesh, spec))
               for x in (q, k, v))
    return wrapped(q, k, v)


def ulysses_attention_sharded(q, k, v, mesh: Mesh, seq_axis: str,
                              causal=True, scale=None, batch_axis=None):
    """Ulysses attention on GLOBAL arrays [b, s, h, d] sharded over seq_axis."""
    fn = functools.partial(ulysses_attention, axis_name=seq_axis,
                           causal=causal, scale=scale)
    wrapped = _sharded(lambda q, k, v: fn(q, k, v), mesh, seq_axis,
                       batch_axis)
    spec = P(batch_axis, seq_axis, None, None)
    q, k, v = (jax.device_put(x, NamedSharding(mesh, spec))
               for x in (q, k, v))
    return wrapped(q, k, v)
