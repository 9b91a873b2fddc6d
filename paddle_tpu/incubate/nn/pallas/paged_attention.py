"""Pallas TPU paged-attention decode kernel (block KV cache).

TPU-native analog of the reference paged/blocked-KV fused kernels
(reference: phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu and
masked_multihead_attention_kernel.cu; python surface
incubate/nn/functional/block_multihead_attention.py).

Single-token decode: each (batch, kv_head) program walks that sequence's
pages via a scalar-prefetched block table — the page indirection happens in
the BlockSpec index_map, so only the pages actually referenced are DMA'd
into VMEM (the point of paged attention). Online-softmax accumulation in
f32 VMEM scratch across the page grid dimension.

Layouts:
  q:            [batch, num_heads, head_dim]   (one decode step)
  k/v_pages:    [num_kv_heads, total_pages, page_size, head_dim]
  block_tables: [batch, pages_per_seq] int32 (page id per slot)
  context_lens: [batch] int32
Grouped-query attention: num_heads % num_kv_heads == 0; the group of query
heads sharing a kv head is processed together (one MXU matmul per page).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "ragged_paged_attention", "paged_kv_write",
           "paged_kv_write_chunk", "quantize_kv_pages", "decode_impl",
           "ragged_impl"]


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def ragged_impl(head_dim: int, page_size: int) -> str:
    """Which implementation :func:`ragged_paged_attention` resolves to
    for these pool shapes — ``"pallas"`` or ``"xla"`` — from the backend
    and the shapes alone (fp and int8 pools alike). The kernels need
    MXU-friendly tiles: a lane-wide head dim and pages that are whole
    128-row tiles. Off-TPU the kernel would run interpreted on every
    step, so the XLA composition serves there."""
    if jax.default_backend() != "tpu":
        return "xla"
    if head_dim in (64, 128, 256) and page_size % 128 == 0:
        return "pallas"
    return "xla"


def decode_impl(head_dim: int, page_size: int, quant: bool = False) -> str:
    """Same rule for the decode-only :func:`paged_attention`; int8 pools
    have no decode kernel and take the XLA dequant-fused gather."""
    return "xla" if quant else ragged_impl(head_dim, page_size)


def _dequant(q8, s, dtype=jnp.float32):
    """The ONE int8-page decode rule: ``value = q8 * s`` with the
    per-row absmax scale broadcast over the trailing head dim.  The XLA
    gather path and the engine's cross-pool handoff import decode
    through this helper; the ragged Pallas kernel applies the same
    scale on the score side (``<q, q8 * s> == <q, q8> * s``).  The write
    side is :func:`_quantize_rows`."""
    return q8.astype(dtype) * s[..., None].astype(dtype)


def _decode_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                   m_s, l_s, acc_s, *, scale, page_size):
    b = pl.program_id(0)
    p = pl.program_id(2)
    n_pages = pl.num_programs(2)

    @pl.when(p == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)         # [group, d]
    k = k_ref[0, 0].astype(jnp.float32)         # [page, d]
    v = v_ref[0, 0].astype(jnp.float32)         # [page, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # mask tokens beyond this sequence's length
    token_idx = p * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)
    s = jnp.where(token_idx < len_ref[b], s, -jnp.inf)

    m_prev = m_s[...]                           # [group, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # guard fully-masked pages (m_new = -inf): exp(-inf - -inf) -> use 0
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    pexp = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m), 0.0)

    l_s[...] = l_s[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new

    @pl.when(p == n_pages - 1)
    def _flush():
        l = l_s[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_s[...] / l).astype(o_ref.dtype)


def _xla_paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                         scale):
    """Reference composition: gather pages then masked attention.

    Handles EMPTY slots (``context_lens == 0``): freshly-joined or
    inactive continuous-batching slots carry arbitrary block-table
    entries over uninitialized pages, so their rows are forced to zero
    instead of softmax(all -inf) = NaN over garbage gathers. Pools may
    be plain arrays or int8 dicts ``{"q8": [kv, pages, page, d] int8,
    "s": [kv, pages, page] f32}`` — gathered rows decode through the
    shared :func:`_dequant` rule; the elementwise scale feeds straight
    into the einsum so XLA fuses it (no separate f32 pool copy)."""
    bsz, n_heads, d = q.shape
    quant = isinstance(k_pages, dict)
    kp = k_pages["q8"] if quant else k_pages
    n_kv, total_pages, page, _ = kp.shape
    group = n_heads // n_kv
    pages_per_seq = block_tables.shape[1]
    max_len = pages_per_seq * page
    bt = jnp.clip(block_tables, 0, total_pages - 1)

    def gather(pages):                 # [n_kv, b, pp, page, ...]
        g = jnp.take(pages, bt, axis=1)
        return jnp.moveaxis(g, 1, 0).reshape(
            (bsz, n_kv, max_len) + pages.shape[3:])

    qg = q.reshape(bsz, n_kv, group, d).astype(jnp.float32)
    if quant:
        kg = _dequant(gather(k_pages["q8"]), gather(k_pages["s"]))
    else:
        kg = gather(k_pages).astype(jnp.float32)
    s = jnp.einsum("bkgd,bktd->bkgt", qg, kg) * scale
    mask = jnp.arange(max_len)[None, None, None, :] \
        < context_lens[:, None, None, None]
    s = jnp.where(mask, s, -jnp.inf)
    # empty slot: all positions masked -> softmax would be 0/0 = NaN
    w = jnp.where(mask, jax.nn.softmax(s, axis=-1), 0.0)
    if quant:
        vg = _dequant(gather(v_pages["q8"]), gather(v_pages["s"]))
    else:
        vg = gather(v_pages).astype(jnp.float32)
    out = jnp.einsum("bkgt,bktd->bkgd", w, vg)
    return out.reshape(bsz, n_heads, d).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "use_kernel"))
def paged_attention(q, k_pages, v_pages, block_tables, context_lens,
                    scale=None, interpret=None, use_kernel=None):
    """Decode-step attention over a paged KV cache. See module docstring.

    Slots with ``context_lens == 0`` (inactive / freshly-joined
    continuous-batching slots) return ZEROS: their block-table rows may
    reference uninitialized pages, so the gather indices are clamped
    into range and the fully-masked softmax short-circuits to zero
    weight instead of NaN. int8 pools (``{"q8", "s"}`` dicts from
    :func:`quantize_kv_pages` / :func:`paged_kv_write_chunk`) take the
    XLA dequant-fused gather path. ``use_kernel=None`` picks by
    :func:`decode_impl`; tests pass it explicitly to pin a path."""
    bsz, n_heads, d = q.shape
    quant = isinstance(k_pages, dict)
    n_kv, total_pages, page, _ = (k_pages["q8"] if quant
                                  else k_pages).shape
    assert n_heads % n_kv == 0
    group = n_heads // n_kv
    pages_per_seq = block_tables.shape[1]
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    if use_kernel is None:
        use_kernel = decode_impl(d, page, quant) == "pallas"
    if quant or not use_kernel:
        return _xla_paged_attention(q, k_pages, v_pages, block_tables,
                                    context_lens, scale)

    # empty-slot safety: the scalar-prefetched index_map DMAs page
    # bt[b, p] unconditionally — garbage ids from inactive rows must be
    # clamped into the pool before they pick the DMA source
    block_tables = jnp.clip(block_tables, 0, total_pages - 1)
    qg = q.reshape(bsz, n_kv, group, d)
    grid = (bsz, n_kv, pages_per_seq)

    kernel = functools.partial(_decode_kernel, scale=scale, page_size=page)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,          # block_tables, context_lens
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, group, d),
                         lambda b, h, p, bt, cl: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b, h, p, bt, cl: (h, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda b, h, p, bt, cl: (h, bt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, group, d),
                               lambda b, h, p, bt, cl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, 1), jnp.float32),
            pltpu.VMEM((group, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, n_kv, group, d), q.dtype),
        interpret=interpret,
    )(block_tables, context_lens,
      qg.reshape(bsz, n_kv, group, d),
      k_pages.reshape(n_kv, total_pages, page, d),
      v_pages)
    return out.reshape(bsz, n_heads, d)


# ---------------------------------------------------------------------------
# Ragged paged attention: mixed prefill+decode rows in ONE launch.
#
# The serving engine used to dispatch two jitted programs per scheduler
# tick — a [1, prefill_chunk] chunked-prefill step and a [max_slots]
# decode step. The ragged kernel kills that dispatch seam: the batch is
# a FLAT token axis [T] packed row-major (row r owns tokens
# q_starts[r] .. q_starts[r]+query_lens[r]), where a decode row
# contributes query_lens == 1 token and a prefill row contributes its
# whole chunk. context_lens[r] is the total KV length of row r AFTER
# this step's tokens are written, so token j of row r (0-based within
# the row) attends causally to KV positions < context_lens[r] -
# query_lens[r] + j + 1. Rows with query_lens == 0 and padding tokens
# (not owned by any row) produce zeros.
# ---------------------------------------------------------------------------


def _ragged_accumulate(q2, k, v, start, n, ctx, p, m_s, l_s, acc_s, *,
                       scale, page_size, group, ks=None, vs=None):
    """Online-softmax update of (m, l, acc) scratch for ONE (row, page)
    visit. ``q2`` is the whole flat token batch [T*group, d] — tokens
    outside row ``b``'s [start, start+n) span and KV slots beyond the
    causal limit are masked to -inf, so foreign rows' statistics are
    untouched (alpha == 1 / pexp == 0 for them). Same guarded math as
    :func:`_decode_kernel` (fully-masked visits keep m at -inf).

    int8 pages pass ``k``/``v`` as the raw q8 values and their per-row
    scales ``ks``/``vs`` as [1, page] rows: a row's scale is constant
    over the head dim, so ``<q, q8 * s> == <q, q8> * s`` and the
    :func:`_dequant` rule is applied on the score tile, where the scale
    row broadcasts along sublanes with no relayout."""
    s = jax.lax.dot_general(q2, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if ks is not None:
        s = s * ks
    tok = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // group
    kv_pos = page_size * p + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # causal limit for token j = tok - start of row b: ctx - n + j + 1
    limit = ctx - n + (tok - start) + 1
    mask = (tok >= start) & (tok < start + n) & (kv_pos < limit)
    s = jnp.where(mask, s, -jnp.inf)

    m_prev = m_s[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - safe_m), 0.0)
    pexp = jnp.where(jnp.isfinite(s), jnp.exp(s - safe_m), 0.0)
    l_s[...] = l_s[...] * alpha + jnp.sum(pexp, axis=-1, keepdims=True)
    acc_s[...] = acc_s[...] * alpha + jax.lax.dot_general(
        pexp if vs is None else pexp * vs, v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_s[...] = m_new


def _ragged_kernel(bt_ref, cl_ref, ql_ref, qs_ref, q_ref, k_ref, v_ref,
                   o_ref, m_s, l_s, acc_s, *, scale, page_size, group):
    """Grid (n_kv_heads, rows, pages_per_seq). The output block depends
    only on the head index, so it is revisited consecutively across the
    (row, page) inner dims — scratch spans the WHOLE flat token axis
    and is reset once per head, flushed at the last (row, page) step.
    v1 masking cost: each (row, page) visit computes scores for all T
    tokens and masks the foreign ones; fine for serving-step T (tens to
    low hundreds), revisit with per-row q blocking if T grows."""
    b = pl.program_id(1)
    p = pl.program_id(2)
    last = (b == pl.num_programs(1) - 1) & (p == pl.num_programs(2) - 1)

    @pl.when((b == 0) & (p == 0))
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((ql_ref[b] > 0) & (page_size * p < cl_ref[b]))
    def _accum():
        q = q_ref[:, 0].astype(jnp.float32)       # [T, group, d]
        t, g, d = q.shape
        _ragged_accumulate(q.reshape(t * g, d),
                           k_ref[0, 0].astype(jnp.float32),
                           v_ref[0, 0].astype(jnp.float32),
                           qs_ref[b], ql_ref[b], cl_ref[b], p,
                           m_s, l_s, acc_s, scale=scale,
                           page_size=page_size, group=group)

    @pl.when(last)
    def _flush():
        l = l_s[...]
        l = jnp.where(l == 0.0, 1.0, l)
        t = q_ref.shape[0]
        d = q_ref.shape[3]
        o_ref[:, 0] = (acc_s[...] / l).reshape(t, group, d) \
            .astype(o_ref.dtype)


def _ragged_kernel_q8(bt_ref, cl_ref, ql_ref, qs_ref, q_ref, k8_ref,
                      ks_ref, v8_ref, vs_ref, o_ref, m_s, l_s, acc_s, *,
                      scale, page_size, group):
    """int8-pool variant of :func:`_ragged_kernel`: K/V page blocks
    arrive as (q8, per-row scale) pairs; the scale blocks are [1, page]
    rows applied on the score side (see :func:`_ragged_accumulate`)."""
    b = pl.program_id(1)
    p = pl.program_id(2)
    last = (b == pl.num_programs(1) - 1) & (p == pl.num_programs(2) - 1)

    @pl.when((b == 0) & (p == 0))
    def _init():
        m_s[...] = jnp.full_like(m_s, -jnp.inf)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when((ql_ref[b] > 0) & (page_size * p < cl_ref[b]))
    def _accum():
        q = q_ref[:, 0].astype(jnp.float32)       # [T, group, d]
        t, g, d = q.shape
        _ragged_accumulate(q.reshape(t * g, d),
                           k8_ref[0, 0].astype(jnp.float32),
                           v8_ref[0, 0].astype(jnp.float32),
                           qs_ref[b], ql_ref[b], cl_ref[b], p,
                           m_s, l_s, acc_s, scale=scale,
                           page_size=page_size, group=group,
                           ks=ks_ref[0, 0], vs=vs_ref[0, 0])

    @pl.when(last)
    def _flush():
        l = l_s[...]
        l = jnp.where(l == 0.0, 1.0, l)
        t = q_ref.shape[0]
        d = q_ref.shape[3]
        o_ref[:, 0] = (acc_s[...] / l).reshape(t, group, d) \
            .astype(o_ref.dtype)


def _token_rows(q_starts, query_lens, n_tokens):
    """Derive the per-token owning row [T] (-1 for padding tokens) from
    per-row spans. Used by the XLA fallback when the caller did not
    pass ``row_of`` explicitly."""
    t = jnp.arange(n_tokens)
    in_row = (t[None, :] >= q_starts[:, None]) & \
        (t[None, :] < (q_starts + query_lens)[:, None])
    return jnp.where(jnp.any(in_row, axis=0),
                     jnp.argmax(in_row, axis=0), -1)


def _xla_ragged_paged_attention(q, k_pages, v_pages, block_tables,
                                context_lens, query_lens, q_starts,
                                row_of, scale):
    """XLA-composition fallback: expand the ragged batch to per-TOKEN
    (lens, block-table) views and delegate to the existing batched
    :func:`_xla_paged_attention` (b == T, one 'sequence' per token with
    its causal prefix length). Padding tokens get lens == 0 -> zeros."""
    n_tokens = q.shape[0]
    n_rows = block_tables.shape[0]
    if row_of is None:
        row_of = _token_rows(q_starts, query_lens, n_tokens)
    r = jnp.clip(row_of, 0, n_rows - 1)
    j = jnp.arange(n_tokens) - q_starts[r]        # token idx within row
    lens = context_lens[r] - query_lens[r] + j + 1
    lens = jnp.where(row_of >= 0, jnp.maximum(lens, 0), 0)
    bt_tok = jnp.take(block_tables, r, axis=0)    # [T, pages_per_seq]
    return _xla_paged_attention(q, k_pages, v_pages, bt_tok, lens, scale)


@functools.partial(jax.jit, static_argnames=("scale", "interpret",
                                             "use_kernel"))
def ragged_paged_attention(q, k_pages, v_pages, block_tables,
                           context_lens, query_lens, q_starts=None,
                           row_of=None, scale=None, interpret=None,
                           use_kernel=None):
    """Attention for a RAGGED batch mixing prefill and decode rows over
    one paged KV pool, in one launch (arxiv 2604.15464 style).

    Layouts:
      q:            [n_tokens, num_heads, head_dim] — flat token axis,
                    rows packed contiguously (row r owns tokens
                    q_starts[r] .. q_starts[r] + query_lens[r])
      k/v_pages:    fp pool [n_kv, pages, page, d] or int8
                    ``{"q8","s"}`` dict
      block_tables: [n_rows, pages_per_seq] int32
      context_lens: [n_rows] — KV length INCLUDING this step's tokens
      query_lens:   [n_rows] — tokens this row contributes (1 for a
                    decode row, the chunk length for prefill, 0 for an
                    idle slot)
      q_starts:     [n_rows] exclusive prefix of query_lens (derived
                    when omitted)
      row_of:       [n_tokens] owning row per token, -1 for padding
                    (derived from q_starts/query_lens when omitted)

    Token j of row r attends to KV positions
    ``< context_lens[r] - query_lens[r] + j + 1`` (causal within the
    chunk, full history before it). Idle rows (query_lens == 0) and
    padding tokens return zeros. int8 pools decode by the shared
    :func:`_dequant` rule on both the kernel and XLA paths.
    ``use_kernel=None`` picks by :func:`ragged_impl`; tests pass it
    explicitly to pin a path."""
    n_tokens, n_heads, d = q.shape
    quant = isinstance(k_pages, dict)
    kp = k_pages["q8"] if quant else k_pages
    n_kv, total_pages, page, _ = kp.shape
    assert n_heads % n_kv == 0
    group = n_heads // n_kv
    n_rows, pages_per_seq = block_tables.shape
    if scale is None:
        scale = d ** -0.5
    if interpret is None:
        interpret = _interpret_default()
    if q_starts is None:
        q_starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(query_lens.astype(jnp.int32))[:-1]])
    if use_kernel is None:
        use_kernel = ragged_impl(d, page) == "pallas"
    if not use_kernel:
        return _xla_ragged_paged_attention(
            q, k_pages, v_pages, block_tables, context_lens, query_lens,
            q_starts, row_of, scale)

    block_tables = jnp.clip(block_tables, 0, total_pages - 1)
    cl = context_lens.astype(jnp.int32)
    ql = query_lens.astype(jnp.int32)
    qs = q_starts.astype(jnp.int32)
    qr = q.reshape(n_tokens, n_kv, group, d)
    grid = (n_kv, n_rows, pages_per_seq)
    scratch = [
        pltpu.VMEM((n_tokens * group, 1), jnp.float32),
        pltpu.VMEM((n_tokens * group, 1), jnp.float32),
        pltpu.VMEM((n_tokens * group, d), jnp.float32),
    ]
    out_spec = pl.BlockSpec((n_tokens, 1, group, d),
                            lambda h, b, p, *_: (0, h, 0, 0))
    q_spec = pl.BlockSpec((n_tokens, 1, group, d),
                          lambda h, b, p, *_: (0, h, 0, 0))
    if quant:
        kernel = functools.partial(_ragged_kernel_q8, scale=scale,
                                   page_size=page, group=group)
        # scale rows ride as [n_kv, pages, 1, page]: a (1, page) block
        # then spans the array's own last two dims, which Mosaic needs
        s_spec = pl.BlockSpec((1, 1, 1, page),
                              lambda h, b, p, bt, *_: (h, bt[b, p], 0, 0))
        s_shape = (n_kv, total_pages, 1, page)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,      # bt, cl, ql, qs
            grid=grid,
            in_specs=[
                q_spec,
                pl.BlockSpec((1, 1, page, d),
                             lambda h, b, p, bt, *_: (h, bt[b, p], 0, 0)),
                s_spec,
                pl.BlockSpec((1, 1, page, d),
                             lambda h, b, p, bt, *_: (h, bt[b, p], 0, 0)),
                s_spec,
            ],
            out_specs=out_spec,
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((n_tokens, n_kv, group, d),
                                           q.dtype),
            interpret=interpret,
        )(block_tables, cl, ql, qs, qr,
          k_pages["q8"], k_pages["s"].reshape(s_shape),
          v_pages["q8"], v_pages["s"].reshape(s_shape))
        return out.reshape(n_tokens, n_heads, d)

    kernel = functools.partial(_ragged_kernel, scale=scale,
                               page_size=page, group=group)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,          # bt, cl, ql, qs
        grid=grid,
        in_specs=[
            q_spec,
            pl.BlockSpec((1, 1, page, d),
                         lambda h, b, p, bt, *_: (h, bt[b, p], 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda h, b, p, bt, *_: (h, bt[b, p], 0, 0)),
        ],
        out_specs=out_spec,
        scratch_shapes=scratch,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tokens, n_kv, group, d),
                                       q.dtype),
        interpret=interpret,
    )(block_tables, cl, ql, qs, qr, k_pages, v_pages)
    return out.reshape(n_tokens, n_heads, d)


@jax.jit
def paged_kv_write(k_pages, v_pages, k_new, v_new, block_tables,
                   context_lens):
    """Append one decode step's k/v ([batch, n_kv, d]) into the paged cache
    at position ``context_lens`` (the slot the new token occupies).
    Returns (k_pages, v_pages) updated — functional, donatable under jit.
    Reference analog: the cache-write half of
    block_multi_head_attention_kernel.cu."""
    n_kv, total_pages, page, d = k_pages.shape
    bsz = k_new.shape[0]
    pages_per_seq = block_tables.shape[1]
    pos = context_lens                     # [b], slot of the new token
    # sequences whose pages are already full have no slot: no-op write
    # (otherwise the clamped index would corrupt the last page's slot 0)
    valid = pos < page * pages_per_seq
    page_slot = jnp.minimum(pos // page, pages_per_seq - 1)
    page_idx = jnp.take_along_axis(
        block_tables, page_slot[:, None], axis=1)[:, 0]       # [b]
    slot = pos % page                      # [b]

    def write(pages, new):
        # scatter [b, n_kv, d] into [n_kv, total_pages, page, d]
        def one(pages, b):
            cur = pages[:, page_idx[b], slot[b], :]
            val = jnp.where(valid[b], new[b].astype(pages.dtype), cur)
            return pages.at[:, page_idx[b], slot[b], :].set(val)

        return jax.lax.fori_loop(0, bsz, lambda b, p: one(p, b), pages)

    return write(k_pages, k_new), write(v_pages, v_new)


def _quantize_rows(x):
    """Per-(row, head) symmetric int8 for [..., n_kv, d] K/V rows (the
    paged analog of models/generation.py _quantize_kv: each written row
    carries its own scale, so the read side is exact)."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    s = jnp.maximum(s, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, s


def quantize_kv_pages(pages):
    """Quantize a bf16/f32 pool [n_kv, pages, page, d] into the int8
    pool representation ``{"q8": int8 same shape, "s": [n_kv, pages,
    page] f32}`` consumed by :func:`paged_attention` and
    :func:`paged_kv_write_chunk`."""
    q, s = _quantize_rows(pages)
    return {"q8": q, "s": s}


@jax.jit
def paged_kv_write_chunk(k_pages, v_pages, k_new, v_new, block_tables,
                         pos):
    """Scatter a CHUNK of per-row-position k/v rows into paged pools.

    k/v_new: [b, g, n_kv, d] — g tokens per row at positions
    ``pos [b, g]``; block_tables: [b, pages_per_seq]. Rows with
    ``pos < 0`` or past the block-table window are DROPPED (inactive
    continuous-batching slots / prefill-chunk padding). Pools may be
    plain arrays or int8 ``{"q8", "s"}`` dicts (rows are quantized at
    write time, per-row scales ride in ``"s"``). Functional — returns
    the updated (k_pages, v_pages).
    """
    quant = isinstance(k_pages, dict)
    kp = k_pages["q8"] if quant else k_pages
    n_kv, total_pages, page, d = kp.shape
    b, g = pos.shape
    pages_per_seq = block_tables.shape[1]
    window = page * pages_per_seq
    valid = (pos >= 0) & (pos < window)
    safe = jnp.clip(pos, 0, window - 1)
    page_id = jnp.take_along_axis(
        jnp.clip(block_tables, 0, total_pages - 1),
        safe // page, axis=1)                       # [b, g]
    flat_slot = page_id * page + safe % page
    # invalid rows get an out-of-range slot; scatter mode="drop" skips
    flat_slot = jnp.where(valid, flat_slot, total_pages * page)
    idx = flat_slot.reshape(b * g)

    def write(pages, new):
        rows = new.reshape(b * g, n_kv, -1).swapaxes(0, 1)  # [kv, M, d]
        if not quant:
            flat = pages.reshape(n_kv, total_pages * page, d)
            flat = flat.at[:, idx].set(rows.astype(flat.dtype),
                                       mode="drop")
            return flat.reshape(n_kv, total_pages, page, d)
        q8, s = _quantize_rows(rows)
        qflat = pages["q8"].reshape(n_kv, total_pages * page, d)
        sflat = pages["s"].reshape(n_kv, total_pages * page)
        qflat = qflat.at[:, idx].set(q8, mode="drop")
        sflat = sflat.at[:, idx].set(s, mode="drop")
        return {"q8": qflat.reshape(n_kv, total_pages, page, d),
                "s": sflat.reshape(n_kv, total_pages, page)}

    return write(k_pages, k_new), write(v_pages, v_new)
