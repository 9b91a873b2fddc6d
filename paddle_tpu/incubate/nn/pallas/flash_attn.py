"""Pallas TPU flash attention, forward + backward.

TPU-native replacement for the reference's dynloaded flashattention CUDA
kernels (reference: paddle/phi/kernels/gpu/flash_attn_kernel.cu,
paddle/phi/backends/dynload/flashattn.cc). Blockwise online-softmax
attention tiled for the MXU: Q/K/V blocks stream HBM->VMEM, the score
block ``q @ k^T`` and the weighted sum ``p @ v`` hit the 128x128 systolic
array, and the running max/denominator live in VMEM scratch across the
sequential kv-block grid dimension.

Public entry: :func:`flash_attention` on paddle-layout arrays
``[batch, seq, num_heads, head_dim]`` with a custom VJP whose backward is
also two Pallas kernels (dq; dk/dv), using the saved logsumexp — O(seq)
memory, no materialized attention matrix.

On non-TPU backends the same kernels run under the Pallas interpreter so
the numerics are testable on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# pltpu imports fine without TPU hardware (interpret mode uses its
# scratch-shape constructors too)
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 1024  # measured on v5e (tools/tune_flash_attn.py):
DEFAULT_BLOCK_K = 1024  # 1024-blocks beat 128 by ~2.5x fwd+bwd
_NEG_INF = -1e30
_LANES = 128  # scratch minor dim: one full lane register row


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _fit_block(block: int, seq: int) -> int:
    """Largest block <= requested that divides seq (callers guarantee
    seq % 128 == 0, so halving from 1024 always terminates >= 128)."""
    block = min(block, seq)
    while seq % block:
        block //= 2
    return max(block, 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                num_k_blocks, offset):
    # offset = sk - sq: bottom-right-aligned causal mask (query i attends
    # keys <= i + offset), matching the XLA fallback's tril(..., sk - sq)
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[0]  # [block_q, d]
        k = k_ref[0]  # [block_k, d]
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]

        if causal:
            q_pos = i * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

        m_prev = m_scr[:, :1]                       # [bq, 1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)   # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)             # correction for old acc
        p = jnp.exp(s - m_new)                      # [bq, bk]
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    if causal:
        # skip fully-masked blocks above the (offset) diagonal
        @pl.when(j * block_k < (i + 1) * block_q + offset)
        def _run():
            _body()
    else:
        _body()

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_scr[...] / l_safe).astype(o_ref.dtype)
        m = m_scr[:, :1]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(l_safe))
        lse_ref[0] = lse  # [block_q, 1]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    """q/k/v: [bh, s, d] -> (out [bh, s, d], lse [bh, s] f32)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq, nk = sq // block_q, sk // block_k

    grid = (bh, nq, nk)
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, offset=sk - sq)

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    out, lse = pl.pallas_call(
        kernel,
        name="flash_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * sq * sk * d // (2 if causal else 1),
            bytes_accessed=int(
                (q.size + k.size + v.size + q.size) * q.dtype.itemsize),
            transcendentals=bh * sq * sk,
        ),
    )(q, k, v)
    return out, lse


# ---------------------------------------------------------------------------
# backward: dk/dv kernel (grid over k blocks, sequential over q blocks)
# ---------------------------------------------------------------------------
def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                    block_q, block_k, num_q_blocks, offset):
    j = pl.program_id(1)   # k block
    i = pl.program_id(2)   # q block (sequential)

    @pl.when(i == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _body():
        q = q_ref[0]         # [bq, d]
        k = k_ref[0]         # [bk, d]
        v = v_ref[0]
        do = do_ref[0]        # [bq, d]
        lse = lse_ref[0]      # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        p = jnp.exp(s - lse)                         # [bq, bk]
        if causal:
            q_pos = i * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            # explicit zero (not exp underflow): fully-masked rows carry
            # lse = -NEG_INF and would otherwise give exp(0) = 1
            p = jnp.where(q_pos >= k_pos, p, 0.0)

        # dv += p^T @ do
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dp = do @ v^T
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)      # [bq, bk]
        ds = p * (dp - delta) * scale                # [bq, bk]
        # dk += ds^T @ q
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when((i + 1) * block_q + offset > j * block_k)
        def _run():
            _body()
    else:
        _body()

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# backward: dq kernel (grid over q blocks, sequential over k blocks)
# ---------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scr, *, scale, causal, block_q, block_k,
                   num_k_blocks, offset):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # k block (sequential)

    @pl.when(j == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]      # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        p = jnp.exp(s - lse)
        if causal:
            q_pos = i * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        # dq += ds @ k
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k < (i + 1) * block_q + offset)
        def _run():
            _body()
    else:
        _body()

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, dq_all,
                      *, scale, causal, block_q, block_k, num_q_blocks,
                      num_k_blocks, offset):
    """Single-pass backward: dk, dv AND dq from one (j, i) sweep.

    The two-kernel split recomputes s = q k^T and dp = do v^T in both
    kernels (7 block matmuls); sharing them here does the ideal 5. dq
    accumulates across the OUTER j loop, which output windows cannot do
    on TPU (a revisited block is not re-fetched) — so dq for the whole
    sequence lives in a VMEM scratch (seq x d f32) and each (b, i)
    window is flushed at its last j visit. The scratch caps the fused
    path at moderate sequence lengths; _flash_bwd falls back to the
    two-kernel split beyond it."""
    j = pl.program_id(1)   # k block (outer)
    i = pl.program_id(2)   # q block (sequential inner)

    @pl.when(i == 0)
    def _init_kv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    @pl.when(j == 0)
    def _init_dq():
        dq_all[pl.ds(i * block_q, block_q), :] = jnp.zeros(
            (block_q, dq_all.shape[1]), jnp.float32)

    def _body():
        q = q_ref[0]          # [bq, d]
        k = k_ref[0]          # [bk, d]
        v = v_ref[0]
        do = do_ref[0]        # [bq, d]
        lse = lse_ref[0]      # [bq, 1]
        delta = delta_ref[0]  # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [bq, bk]
        p = jnp.exp(s - lse)
        if causal:
            q_pos = i * block_q + offset + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            p = jnp.where(q_pos >= k_pos, p, 0.0)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        ds = p * (dp - delta) * scale                     # [bq, bk]
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_all[pl.ds(i * block_q, block_q), :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        @pl.when((i + 1) * block_q + offset > j * block_k)
        def _run():
            _body()
    else:
        _body()

    @pl.when(i == num_q_blocks - 1)
    def _flush_kv():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(j == num_k_blocks - 1)
    def _flush_dq():
        dq_ref[0] = dq_all[pl.ds(i * block_q, block_q), :] \
            .astype(dq_ref.dtype)


# dq scratch cap for the fused backward: seq * d * 4 bytes of VMEM
_FUSED_BWD_MAX_SEQ_D = 8192 * 128


def _flash_bwd(q, k, v, out, lse, do, causal, scale, block_q, block_k,
               interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    block_q = _fit_block(block_q, sq)
    block_k = _fit_block(block_k, sk)
    nq, nk = sq // block_q, sk // block_k

    if sq == sk and sq * d <= _FUSED_BWD_MAX_SEQ_D:
        return _flash_bwd_fused(q, k, v, out, lse, do, causal, scale,
                                block_q, block_k, nq, nk, interpret)
    return _flash_bwd_split(q, k, v, out, lse, do, causal, scale,
                            block_q, block_k, nq, nk, interpret)


def _flash_bwd_fused(q, k, v, out, lse, do, causal, scale, block_q,
                     block_k, nq, nk, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, sq, 1]
    block_shapes = [
        (1, block_q, d), (1, block_k, d), (1, block_k, d),
        (1, block_q, d), (1, block_q, 1), (1, block_q, 1),
    ]
    maps = [
        lambda b, j, i: (b, i, 0),
        lambda b, j, i: (b, j, 0),
        lambda b, j, i: (b, j, 0),
        lambda b, j, i: (b, i, 0),
        lambda b, j, i: (b, i, 0),
        lambda b, j, i: (b, i, 0),
    ]
    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"))
    kernel = functools.partial(
        _bwd_fused_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_q_blocks=nq,
        num_k_blocks=nk, offset=sk - sq)
    dq, dk, dv = pl.pallas_call(
        kernel,
        name="flash_bwd_fused",
        grid=(bh, nk, nq),
        in_specs=[pl.BlockSpec(s, m)
                  for s, m in zip(block_shapes, maps)],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((sq, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


def _flash_bwd_split(q, k, v, out, lse, do, causal, scale, block_q,
                     block_k, nq, nk, interpret):
    bh, sq, d = q.shape
    sk = k.shape[1]

    # delta = rowsum(do * o): cheap XLA reduction, feeds both kernels
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)  # [bh, sq, 1]

    block_shapes = [
        (1, block_q, d),   # q
        (1, block_k, d),   # k
        (1, block_k, d),   # v
        (1, block_q, d),   # do
        (1, block_q, 1),   # lse
        (1, block_q, 1),   # delta
    ]

    def specs(maps):
        return [pl.BlockSpec(s, m) for s, m in zip(block_shapes, maps)]

    compiler_params = None
    if not interpret:
        compiler_params = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))

    # ---- dk, dv: grid (bh, nk, nq), q-dim sequential
    dkv_kernel = functools.partial(
        _bwd_dkv_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_q_blocks=nq, offset=sk - sq)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        name="flash_bwd_dkv",
        grid=(bh, nk, nq),
        in_specs=specs([
            lambda b, j, i: (b, i, 0),
            lambda b, j, i: (b, j, 0),
            lambda b, j, i: (b, j, 0),
            lambda b, j, i: (b, i, 0),
            lambda b, j, i: (b, i, 0),
            lambda b, j, i: (b, i, 0),
        ]),
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # ---- dq: grid (bh, nq, nk), k-dim sequential
    dq_kernel = functools.partial(
        _bwd_dq_kernel, scale=scale, causal=causal,
        block_q=block_q, block_k=block_k, num_k_blocks=nk, offset=sk - sq)
    dq = pl.pallas_call(
        dq_kernel,
        name="flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=specs([
            lambda b, i, j: (b, i, 0),
            lambda b, i, j: (b, j, 0),
            lambda b, i, j: (b, j, 0),
            lambda b, i, j: (b, i, 0),
            lambda b, i, j: (b, i, 0),
            lambda b, i, j: (b, i, 0),
        ]),
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=compiler_params,
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom-vjp wrapper on [bh, s, d]
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, out, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, out, lse, do, causal, scale,
                            block_q, block_k, interpret)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Flash attention on paddle-layout arrays [batch, seq, heads, head_dim].

    Supports GQA/MQA (k/v may have fewer heads; must divide q heads).
    Differentiable via Pallas backward kernels.
    """
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    sk = k.shape[1]
    if interpret is None:
        interpret = _interpret_default()
    if scale is None:
        scale = float(d) ** -0.5
    if hk != hq:
        assert hq % hk == 0, (hq, hk)
        rep = hq // hk
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)

    # [b, s, h, d] -> [b*h, s, d]
    def to_bh(x, s):
        return jnp.swapaxes(x, 1, 2).reshape(b * hq, s, x.shape[-1])

    qb = to_bh(q, sq)
    kb = to_bh(k, sk)
    vb = to_bh(v, sk)
    ob = _flash(qb, kb, vb, causal, scale, block_q, block_k, interpret)
    return jnp.swapaxes(ob.reshape(b, hq, sq, d), 1, 2)


__all__ = ["flash_attention"]
