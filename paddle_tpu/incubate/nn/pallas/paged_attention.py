"""Pallas TPU paged-attention kernels (block KV cache): the decode kernel
and the ragged mixed prefill+decode kernel the serving engine runs.

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

:func:`ragged_paged_attention` (further down, with its own notes) serves a
flat token axis of decode rows and prefill chunks over the same pools in one
launch. Its grid walks the batch's live (row, page) pairs, every KV head a
visit, and scores a row's own tokens only; it keeps the block table first
among its scalars and the two pools last among its inputs, which is how
``benchmark/lib/xplane.py`` finds it in a device trace; and it shares
``flash_attn.py``'s precision: MXU operands in the pool's dtype, float32
accumulation and softmax state, ``p`` rounded to the value dtype.

:func:`paged_kv_write_chunk` (at the end, with its own notes) writes a
step's keys and values into those pools: wherever the ragged kernel reads
them, by a Pallas call that takes both pools of a cache layer in the
kernel's layout and returns them aliased, so that with the pools donated
only the tile groups written move; elsewhere and for int8 pools by a
scatter.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "ragged_paged_attention", "paged_kv_write",
           "paged_kv_write_chunk", "quantize_kv_pages", "decode_impl",
           "ragged_impl", "kv_write_impl", "ragged_latent_attention",
           "paged_latent_write_chunk", "latent_visits", "latent_impl",
           "latent_pool_dim"]


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
#
# The kernel's work has the shape of the batch, not of its padding:
#
# * A VISIT is one (row, page) pair with every KV head of a head block
#   (all of them unless VMEM forbids): K and V blocks of
#   (heads, 1, page, d), one DMA each. The wrapper lists the LIVE visits
#   — page p of row r with query_lens[r] > 0 and p * page <
#   context_lens[r] — row-major on a flat axis (:func:`_live_visits`),
#   and the grid is (head blocks, rows * pages_per_seq) over that list.
#   Steps past the last live visit keep its block index, so they fetch
#   nothing (an unchanged block is not copied again) and compute nothing.
# * A visit scores the row's OWN tokens only. q and the float32 (m, l,
#   acc) scratch hold the whole token axis head-major ([heads,
#   T * group, d], resident for the call) and are addressed at the row's
#   span, widened to whole sublane tiles of ``_Q_ALIGN`` tokens, in
#   blocks of two static sizes: as many big ones as fit, then small
#   ones. A decode row is one small block, a prefill chunk mostly big
#   ones; a neighbour's tokens inside a widened block are masked.
# * Precision, shared with flash_attn.py: operands go to the MXU in the
#   pool's dtype with float32 accumulation, p is rounded to the value
#   dtype before p @ v, and m, l, acc stay float32. int8 pages are cast
#   to q's dtype (exact) and their row scales applied on the score side.
# * Operand order, which benchmark/lib/xplane.py tells the kernel by:
#   the s32 [rows, pages_per_seq] block table first among the prefetched
#   scalars, and the two pools the last rank-4 inputs (int8 scale rows
#   ride before them; q is rank 3). The KV write kernel takes the two
#   pools last too, so its prefetched tables are all rank 1: it must not
#   be counted as attention.
# ---------------------------------------------------------------------------

# tokens a q block starts and ends on: the bf16 sublane tile (a multiple
# of float32's), so every dynamic slice of q and the scratch is tile-aligned
_Q_ALIGN = 16
# rows of a big q block's score tile: one MXU pass of the page
_Q_BIG_ROWS = 128
# float32 bytes of score tile one batch of heads may hold at a time
_SCORE_TILE_BYTES = 128 * 1024
# scoped VMEM the kernel may ask for: within every TPU generation's
_VMEM_BUDGET = 32 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _largest_divisor(n: int, fits) -> int:
    """The largest divisor of ``n`` that ``fits``, else 1."""
    return next((h for h in range(n, 0, -1) if n % h == 0 and fits(h)), 1)


def _live_visits(context_lens, query_lens, page, pages_per_seq):
    """The live (row, page) visits of a ragged batch, row-major on a
    flat axis of the static length ``rows * pages_per_seq``: ``(vrow,
    vpage, n_live)``. Entries past ``n_live`` repeat the last live
    visit (row 0, page 0 when there is none)."""
    n_rows = context_lens.shape[0]
    npg = jnp.where(query_lens > 0,
                    jnp.clip(-(-context_lens // page), 0, pages_per_seq), 0)
    ends = jnp.cumsum(npg)
    n_live = ends[-1]
    i = jnp.minimum(jnp.arange(n_rows * pages_per_seq, dtype=jnp.int32),
                    jnp.maximum(n_live - 1, 0))
    vrow = jnp.minimum(
        jnp.sum((i[:, None] >= ends[None, :]).astype(jnp.int32), axis=1),
        n_rows - 1)
    vpage = i - (ends[vrow] - npg[vrow])
    return vrow, vpage, n_live.reshape(1)


def _ragged_kernel(bt_ref, cl_ref, ql_ref, qs_ref, vrow_ref, vpage_ref,
                   nlive_ref, q_ref, *refs, scale, page_size, group,
                   n_tokens, big, quant):
    """Grid (head blocks, visits). ``refs`` is ``(k, v, o, m, l, acc)``,
    with the int8 pools' scale rows ``(ks, vs)`` before it when
    ``quant``: [heads, 1, 1, page] blocks, applied on the score side
    (a row's scale is constant over the head dim, so ``<q, q8 * s> ==
    <q, q8> * s``, the :func:`_dequant` rule with no relayout).

    The scratch spans the whole token axis of the head block: reset at
    its first visit, updated at [row's span] by each live visit, turned
    into the output at its last step — tokens no visit touched (idle
    rows, padding) keep l == 0 and come out as zeros."""
    if quant:
        ks_ref, vs_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs
    else:
        k_ref, v_ref, o_ref, m_s, l_s, acc_s = refs
    n_heads = q_ref.shape[0]
    i = pl.program_id(1)

    def each_head(body):
        jax.lax.fori_loop(0, n_heads, lambda h, c: body(h), None)

    @pl.when(i == 0)
    def _init():
        def body(h):
            m_s[h] = jnp.full(m_s.shape[1:], -jnp.inf, jnp.float32)
            l_s[h] = jnp.zeros(l_s.shape[1:], jnp.float32)
            acc_s[h] = jnp.zeros(acc_s.shape[1:], jnp.float32)
        each_head(body)

    def accumulate(h0, hb, tok0, bq, p, start, n, ctx):
        """Online-softmax update of (m, l, acc) at tokens [tok0,
        tok0 + bq) and heads [h0, h0 + hb) for page ``p`` of the row
        that owns tokens [start, start + n)."""
        rows = bq * group
        heads = pl.ds(h0, hb)
        span = pl.ds(pl.multiple_of(tok0 * group, _Q_ALIGN), rows)
        q = q_ref[heads, span, :]                   # [hb, rows, d]
        k = k_ref[heads, 0]                         # [hb, page, d]
        v = v_ref[heads, 0]
        if quant:
            k, v = k.astype(q.dtype), v.astype(q.dtype)
        s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (0,))),
                                preferred_element_type=jnp.float32) * scale
        if quant:
            s = s * ks_ref[heads, 0]
        tok = tok0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0) // group
        kv_pos = page_size * p + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        # causal limit for token j = tok - start of the row: ctx - n + j + 1
        mask = (tok >= start) & (tok < start + n) \
            & (kv_pos < ctx - n + (tok - start) + 1)
        s = jnp.where(mask[None], s, -jnp.inf)

        m_prev = m_s[heads, span, :]                # [hb, rows, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # a token with nothing to see yet keeps m at -inf; exp(-inf - 0)
        # is 0 for its alpha and its p, so foreign tokens stay untouched
        safe_m = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m_prev - safe_m)
        pexp = jnp.exp(s - safe_m)
        l_s[heads, span, :] = l_s[heads, span, :] * alpha \
            + jnp.sum(pexp, axis=-1, keepdims=True)
        if quant:
            pexp = pexp * vs_ref[heads, 0]
        acc_s[heads, span, :] = acc_s[heads, span, :] * alpha \
            + jax.lax.dot_general(pexp.astype(v.dtype), v,
                                  (((2,), (1,)), ((0,), (0,))),
                                  preferred_element_type=jnp.float32)
        m_s[heads, span, :] = m_new

    def visit_blocks(first, count, bq, p, start, n, ctx):
        """``count`` q blocks of ``bq`` tokens from token ``first``, the
        heads in batches whose score tile stays small."""
        hb = _largest_divisor(
            n_heads,
            lambda h: h * bq * group * page_size * 4 <= _SCORE_TILE_BYTES)

        def block(j, c):
            tok0 = first + j * bq
            # pages wholly past the block's last token's causal limit
            seen = ctx - n + jnp.minimum(tok0 + bq, start + n) - start

            @pl.when(page_size * p < seen)
            def _():
                jax.lax.fori_loop(
                    0, n_heads // hb,
                    lambda b, c: accumulate(b * hb, hb, tok0, bq, p,
                                            start, n, ctx), None)
        jax.lax.fori_loop(0, count, block, None)

    @pl.when(i < nlive_ref[0])
    def _visit():
        r, p = vrow_ref[i], vpage_ref[i]
        start, ctx = qs_ref[r], cl_ref[r]
        n = jnp.minimum(ql_ref[r], n_tokens - start)
        first = start // _Q_ALIGN * _Q_ALIGN
        small = (start + n - first + _Q_ALIGN - 1) // _Q_ALIGN
        if big > _Q_ALIGN:
            n_big = small * _Q_ALIGN // big
            visit_blocks(first, n_big, big, p, start, n, ctx)
            first = first + n_big * big
            small = small - n_big * (big // _Q_ALIGN)
        visit_blocks(first, small, _Q_ALIGN, p, start, n, ctx)

    @pl.when(i == pl.num_programs(1) - 1)
    def _flush():
        def body(h):
            l = l_s[h]
            o_ref[h] = (acc_s[h] / jnp.where(l == 0.0, 1.0, l)) \
                .astype(o_ref.dtype)
        each_head(body)


def _token_rows(q_starts, query_lens, n_tokens):
    """Derive the per-token owning row [T] (-1 for padding tokens) from
    per-row spans. Used by the XLA fallback when the caller did not
    pass ``row_of`` explicitly."""
    t = jnp.arange(n_tokens)
    in_row = (t[None, :] >= q_starts[:, None]) & \
        (t[None, :] < (q_starts + query_lens)[:, None])
    return jnp.where(jnp.any(in_row, axis=0),
                     jnp.argmax(in_row, axis=0), -1)


def _per_token_views(n_tokens, block_tables, context_lens, query_lens,
                     q_starts, row_of):
    """A ragged batch seen a TOKEN at a time: -> (the keys each token
    sees [T], 0 for padding; its row's block table [T, pages_per_seq])."""
    n_rows = block_tables.shape[0]
    if row_of is None:
        row_of = _token_rows(q_starts, query_lens, n_tokens)
    r = jnp.clip(row_of, 0, n_rows - 1)
    j = jnp.arange(n_tokens) - q_starts[r]        # token idx within row
    lens = context_lens[r] - query_lens[r] + j + 1
    lens = jnp.where(row_of >= 0, jnp.maximum(lens, 0), 0)
    return lens, jnp.take(block_tables, r, axis=0)


def _xla_ragged_paged_attention(q, k_pages, v_pages, block_tables,
                                context_lens, query_lens, q_starts,
                                row_of, scale):
    """XLA-composition fallback: expand the ragged batch to per-TOKEN
    (lens, block-table) views and delegate to the existing batched
    :func:`_xla_paged_attention` (b == T, one 'sequence' per token with
    its causal prefix length). Padding tokens get lens == 0 -> zeros."""
    lens, bt_tok = _per_token_views(q.shape[0], block_tables, context_lens,
                                    query_lens, q_starts, row_of)
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
    explicitly to pin a path.

    The kernel (see the notes above :func:`_ragged_kernel`) makes one
    grid step per live (row, page) pair with every KV head, reads rows'
    spans from ``query_lens`` and ``q_starts`` alone — any packing order
    of non-overlapping spans inside ``[0, n_tokens)`` — and computes in
    the pool's dtype with float32 accumulation, as ``flash_attn.py``
    does. fp and int8 pools share the one kernel."""
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
    vrow, vpage, n_live = _live_visits(cl, ql, page, pages_per_seq)

    # head-major q with the token axis padded to whole q blocks:
    # [n_kv, t_pad * group, d], row = token * group + head within group
    t_pad = _round_up(n_tokens, _Q_ALIGN)
    tg = t_pad * group
    cdt = q.dtype if quant else kp.dtype
    qh = jnp.pad(q.astype(cdt), ((0, t_pad - n_tokens), (0, 0), (0, 0))) \
        .reshape(t_pad, n_kv, group, d).transpose(1, 0, 2, 3) \
        .reshape(n_kv, tg, d)
    big = max(_Q_ALIGN, _Q_BIG_ROWS // group // _Q_ALIGN * _Q_ALIGN)
    if big > t_pad:
        big = _Q_ALIGN

    def vmem_bytes(hb):
        """Double-buffered q, out, K, V (and scale rows), the float32
        scratch with m and l padded to a lane tile, and room for the
        score tiles."""
        lanes = _round_up(d, 128)
        io = 2 * hb * tg * lanes * (cdt.itemsize + q.dtype.itemsize)
        kv = 4 * hb * page * lanes * kp.dtype.itemsize
        if quant:
            kv += 4 * hb * 8 * _round_up(page, 128) * 4
        scratch = hb * tg * (2 * 128 + lanes) * 4
        return io + kv + scratch + 32 * _SCORE_TILE_BYTES

    hblk = _largest_divisor(n_kv, lambda h: vmem_bytes(h) <= _VMEM_BUDGET)

    kernel = functools.partial(
        _ragged_kernel, scale=scale, page_size=page, group=group,
        n_tokens=n_tokens, big=big, quant=quant)
    qo_spec = pl.BlockSpec((hblk, tg, d), lambda hb, i, *_: (hb, 0, 0))

    def page_map(hb, i, bt, cl, ql, qs, vrow, vpage, n_live):
        return (hb, bt[vrow[i], vpage[i]], 0, 0)

    pool_spec = pl.BlockSpec((hblk, 1, page, d), page_map)
    if quant:
        # scale rows ride as [n_kv, pages, 1, page]: a (1, page) block
        # then spans the array's own last two dims, which Mosaic needs
        s_shape = (n_kv, total_pages, 1, page)
        s_spec = pl.BlockSpec((hblk, 1, 1, page), page_map)
        in_specs = [qo_spec, s_spec, s_spec, pool_spec, pool_spec]
        operands = (k_pages["s"].reshape(s_shape),
                    v_pages["s"].reshape(s_shape),
                    k_pages["q8"], v_pages["q8"])
    else:
        in_specs = [qo_spec, pool_spec, pool_spec]
        operands = (k_pages, v_pages)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,      # bt, cl, ql, qs, vrow, vpage, n_live
            grid=(n_kv // hblk, n_rows * pages_per_seq),
            in_specs=in_specs,
            out_specs=qo_spec,
            scratch_shapes=[
                pltpu.VMEM((hblk, tg, 1), jnp.float32),
                pltpu.VMEM((hblk, tg, 1), jnp.float32),
                pltpu.VMEM((hblk, tg, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((n_kv, tg, d), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
    )(block_tables, cl, ql, qs, vrow, vpage, n_live, qh, *operands)
    return out.reshape(n_kv, t_pad, group, d).transpose(1, 0, 2, 3) \
        .reshape(t_pad, n_heads, d)[:n_tokens]


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


# ---------------------------------------------------------------------------
# The in-place KV write. The pools are head-major ([n_kv, pages, page, d],
# the layout both attention kernels read), so the slot axis is the
# second-minor one and a pool's smallest addressable piece is a TILE
# GROUP: ``G`` consecutive slots of one page with every KV head, ``G``
# the pool dtype's sublane tile (16 for bf16, 8 for float32). A scatter
# on that axis makes XLA change the layout of the whole pool before it
# and back after it; this kernel moves the tile groups written and
# nothing else:
#
# * A VISIT is one tile group that receives tokens in this call. The
#   wrapper lists them by DESTINATION (:func:`_write_visits`: two visits
#   to one tile in one call would lose an update, the second's tile being
#   fetched before the first's is stored), and the grid is the live
#   visits and no step more (a dynamic bound): a step that ran on a tile
#   already visited would store it as it was before the write.
# * K and V pools enter and leave through the same (n_kv, 1, G, d) block,
#   input aliased to output: with the pools donated to the enclosing jit
#   no other byte of them moves.
# * The new rows ride whole in VMEM, head-major in float32 (exact for
#   every pool dtype, and a 32-bit row can be picked at a dynamic
#   sublane), ``_WRITE_NEW_BYTES`` of them a call; a visit copies its
#   tokens' rows into a staging tile and stores ``where(written, staged,
#   old)`` in the pool's dtype.
# * Operands: the grid's bound, three prefetched ``s32`` tables, all of
#   rank 1, the rows, the pools. benchmark/lib/xplane.py takes a custom
#   call whose first operand is a rank-2 s32 and whose last rank-4
#   inputs are two equal pools for the ragged attention kernel (above):
#   no table of this kernel may be rank 2.
# ---------------------------------------------------------------------------

# VMEM the double-buffered float32 K and V rows of one call may take
_WRITE_NEW_BYTES = 16 * 1024 * 1024


def _write_visits(slot, n_slots, tile):
    """The tile groups a write touches, each once: ``slot`` [M] is every
    token's flat destination slot, ``>= n_slots`` for a dropped token.
    -> ``(vtile [M], vtok [M * tile], vbits [M], n_live)``: visit ``i <
    n_live`` writes tile group ``vtile[i]`` (flat over pages), slot ``j``
    of it from token ``vtok[i * tile + j]`` (-1: keep; bit ``j`` of
    ``vbits[i]`` says the same). With nothing live, entry 0 is tile 0
    with nothing to write. It reads nothing of a layer: XLA keeps one
    copy for all the cache layers of a step."""
    m = slot.shape[0]
    t = jnp.arange(m, dtype=jnp.int32)
    tl = slot // tile
    # a live token opens a visit unless an earlier one lands in its tile
    opens = (slot < n_slots) & ~jnp.any(
        (tl[:, None] == tl[None, :]) & (t[None, :] < t[:, None]), axis=1)
    ends = jnp.cumsum(opens.astype(jnp.int32))
    n_live = ends[-1]
    # visit i is opened by the first token with i + 1 visits open
    src = jnp.sum((ends[None, :] <= t[:, None]).astype(jnp.int32), axis=1)
    vtile = jnp.where(t < n_live, tl[jnp.minimum(src, m - 1)], 0)
    want = vtile[:, None] * tile + jnp.arange(tile, dtype=jnp.int32)
    vtok = jnp.max(jnp.where(slot[None, None, :] == want[:, :, None],
                             t[None, None, :], -1), axis=2)   # [M, tile]
    vbits = jnp.sum((vtok >= 0).astype(jnp.int32)
                    << jnp.arange(tile, dtype=jnp.int32), axis=1)
    return vtile, vtok.reshape(m * tile), vbits, n_live


def _kv_write_kernel(vtile_ref, vtok_ref, vbits_ref, *refs, tile, n):
    """Grid (live visits,). ``refs``: for each of the ``n`` pools written
    together (K and V of a cache layer, or a latent pool alone) its new
    rows [n_kv, M, d] float32, resident; then each pool's (n_kv, 1, tile,
    d) block at the visit, in and out; then a float32 staging tile
    each."""
    new, old, out, staged = (refs[k * n:(k + 1) * n] for k in range(4))
    i = pl.program_id(0)
    for j in range(tile):
        tok = vtok_ref[i * tile + j]

        @pl.when(tok >= 0)
        def _():
            for st, nw in zip(staged, new):
                st[:, pl.ds(j, 1), :] = nw[:, pl.ds(tok, 1), :]

    slot = jax.lax.broadcasted_iota(jnp.int32, staged[0].shape[1:], 0)
    written = (((vbits_ref[i] >> slot) & 1) == 1)[None]
    for o, st, od in zip(out, staged, old):
        o[:, 0] = jnp.where(written, st[...].astype(o.dtype), od[:, 0])


def _pallas_kv_write(pools, rows, slot, interpret):
    """Write ``rows`` (one [n_kv, M, d] float32 array a pool) at flat
    slots ``slot`` [M] (``>= pages * page``: dropped) into ``pools``
    (arrays of one shape, written at the same slots), in place.
    -> the pools, a list."""
    n = len(pools)
    n_kv, total_pages, page, d = pools[0].shape
    tile = 8 * 4 // pools[0].dtype.itemsize
    tiles_per_page = page // tile
    m = slot.shape[0]
    vtile, vtok, vbits, n_live = _write_visits(
        slot, total_pages * page, tile)

    def pool_map(i, vtile, vtok, vbits):
        return (0, vtile[i] // tiles_per_page, vtile[i] % tiles_per_page, 0)

    new_spec = pl.BlockSpec((n_kv, m, d), lambda i, *_: (0, 0, 0))
    pool_spec = pl.BlockSpec((n_kv, 1, tile, d), pool_map)
    pool_shape = jax.ShapeDtypeStruct(pools[0].shape, pools[0].dtype)
    return pl.pallas_call(
        functools.partial(_kv_write_kernel, tile=tile, n=n),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # vtile, vtok, vbits
            # the live visits and no step more; one with nothing to write
            # when there is none, so that the output block it stores is
            # the tile as it was
            grid=(jnp.maximum(n_live, 1),),
            in_specs=[new_spec] * n + [pool_spec] * n,
            out_specs=[pool_spec] * n,
            scratch_shapes=[pltpu.VMEM((n_kv, tile, d), jnp.float32)] * n),
        out_shape=[pool_shape] * n,
        input_output_aliases={3 + n + k: k for k in range(n)},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
    )(vtile, vtok, vbits, *rows, *pools)


def kv_write_impl(head_dim: int, page_size: int, quant: bool = False) -> str:
    """Which write :func:`paged_kv_write_chunk` resolves to: the in-place
    tile-group kernel (``"pallas"``) wherever the ragged attention kernel
    reads the pool (:func:`ragged_impl`, so a pool's writer and reader
    agree on its layout), the scatter (``"xla"``) elsewhere and for int8
    pools."""
    return "xla" if quant else ragged_impl(head_dim, page_size)


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def paged_kv_write_chunk(k_pages, v_pages, k_new, v_new, block_tables,
                         pos, interpret=None, use_kernel=None):
    """Scatter a CHUNK of per-row-position k/v rows into paged pools.

    k/v_new: [b, g, n_kv, d] — g tokens per row at positions
    ``pos [b, g]``; block_tables: [b, pages_per_seq]. Rows with
    ``pos < 0`` or past the block-table window are DROPPED (inactive
    continuous-batching slots / prefill-chunk padding). Pools may be
    plain arrays or int8 ``{"q8", "s"}`` dicts (rows are quantized at
    write time, per-row scales ride in ``"s"``). Functional — returns
    the updated (k_pages, v_pages); on the kernel path (``use_kernel=
    None`` picks by :func:`kv_write_impl`; tests pass it explicitly) the
    results alias the pools, so a caller that donates them has them
    written in place.
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
    if use_kernel is None:
        use_kernel = kv_write_impl(d, page, quant) == "pallas"
    if use_kernel:
        if interpret is None:
            interpret = _interpret_default()

        def rows(new):      # head-major, rounded as the pool rounds
            return new.reshape(b * g, n_kv, d).swapaxes(0, 1) \
                .astype(kp.dtype).astype(jnp.float32)

        k_rows, v_rows = rows(k_new), rows(v_new)
        # tokens a call: 4 bytes, K and V, two buffers each, a row
        step = max(8, _WRITE_NEW_BYTES
                   // (16 * n_kv * _round_up(d, 128)) // 8 * 8)
        for m0 in range(0, b * g, step):
            k_pages, v_pages = _pallas_kv_write(
                (k_pages, v_pages), (k_rows[:, m0:m0 + step],
                                     v_rows[:, m0:m0 + step]),
                idx[m0:m0 + step], interpret)
        return k_pages, v_pages

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


# ---------------------------------------------------------------------------
# Latent pages: ONE pool a cache layer, [1, pages, page, dp], a token's
# row its whole latent (the normed low-rank values, then the rope key)
# zero-padded to ``dp`` = whole lane tiles (576 -> 640: the TPU lays a
# 576-wide row out in five 128-lane tiles whatever the array says, so the
# padding costs nothing and one DMA brings a page). Every query head reads
# the same page: the row is the key, its first ``value_dim`` lanes are the
# value, and a page is fetched ONCE for both.
#
# The kernel's work list is made by :func:`latent_visits`, once a step for
# all cache layers. A query token is ``gp`` rows (its heads, padded to the
# bf16 sublane tile), so the token axis has far more query rows than the
# per-head kernel's (536 tokens x 32 heads) and is not resident: it is cut
# into Q BLOCKS of ``_LATENT_BQ`` tokens, and a VISIT is one (q block, row
# of the batch, page) triple, sorted by q block, so that a block's
# float32 (m, l, acc) live in scratch from its first visit to its last,
# which turns them into the block's output. A visit scores the row's own
# tokens of the block: all of them in one matmul where the block lies
# inside a prefill chunk, else token by token (a decode row is one token:
# ``gp`` query rows against the page). A block no row touches gets one
# null visit, which writes zeros. The grid is the visits and no step more.
# Precision is the per-head kernel's: MXU operands in the pool's dtype,
# float32 accumulation and softmax state, p rounded to the pool's dtype.
# Operands: the visit list (rank 1), the block table (rank 2), three
# rank-1 row tables, q (rank 2), ONE pool (rank 4): with a single pool it
# is not what ``benchmark/lib/xplane.py`` takes for the per-head kernel;
# ``benchmark/lib/xing4_kernels.py`` knows it by that pool.
# ---------------------------------------------------------------------------

# tokens a q block: 16 x 32 heads are 512 query rows a visit
_LATENT_BQ = 16
# query rows scored at a time inside a whole-block visit
_LATENT_SCORE_ROWS = 256
# bit fields of a visit: q block << 20 | batch row << 12 | page of the row
_VISIT_ROW_BITS, _VISIT_PAGE_BITS = 8, 12


def latent_pool_dim(latent_dim: int) -> int:
    """Row width of a latent pool: ``latent_dim`` in whole lane tiles."""
    return _round_up(latent_dim, 128)


def latent_impl(value_dim: int, page_size: int) -> str:
    """Which implementation :func:`ragged_latent_attention` and
    :func:`paged_latent_write_chunk` resolve to, ``"pallas"`` or
    ``"xla"``: the kernels on a TPU where values end on a lane tile and
    pages are whole 128-row tiles, the XLA composition and the scatter
    elsewhere."""
    if jax.default_backend() == "tpu" and value_dim % 128 == 0 \
            and page_size % 128 == 0:
        return "pallas"
    return "xla"


def latent_visits(n_tokens, page, block_tables, context_lens, query_lens,
                  q_starts):
    """The latent kernel's work list for one ragged batch: -> ``(visits
    [static bound] s32, n_visits [1] s32)``, visit ``i < n_visits`` being
    ``q block << 20 | row << 12 | page``, sorted by q block; row ==
    ``n_rows`` marks a null visit (a q block no live row touches). A
    block of row r sees pages up to its last token's causal limit."""
    n_rows, pages_per_seq = block_tables.shape
    assert n_rows < (1 << _VISIT_ROW_BITS) - 1 \
        and pages_per_seq <= (1 << _VISIT_PAGE_BITS)
    bq = _LATENT_BQ
    nb = _round_up(n_tokens, bq) // bq
    cl, ql, qs = (a.astype(jnp.int32)[None, :]
                  for a in (context_lens, query_lens, q_starts))
    ql = jnp.minimum(ql, n_tokens - qs)
    b0 = (jnp.arange(nb, dtype=jnp.int32) * bq)[:, None]
    hi = jnp.minimum(qs + ql, b0 + bq)                    # [nb, rows]
    live = hi > jnp.maximum(qs, b0)
    npg = jnp.where(live, jnp.clip(-(-(cl - ql + hi - qs) // page), 0,
                                   pages_per_seq), 0)
    null = (jnp.sum(npg, axis=1, keepdims=True) == 0).astype(jnp.int32)
    counts = jnp.concatenate([npg, null], axis=1).reshape(-1)
    ends = jnp.cumsum(counts)
    bound = (nb + n_rows) * pages_per_seq + nb
    i = jnp.arange(bound, dtype=jnp.int32)
    pair = jnp.minimum(jnp.searchsorted(ends, i, side="right"),
                       counts.shape[0] - 1).astype(jnp.int32)
    pg = jnp.clip(i - (ends[pair] - counts[pair]), 0, pages_per_seq - 1)
    visits = (pair // (n_rows + 1)) << (_VISIT_ROW_BITS + _VISIT_PAGE_BITS) \
        | (pair % (n_rows + 1)) << _VISIT_PAGE_BITS | pg
    return visits, ends[-1:]


def _latent_kernel(vis_ref, nvis_ref, bt_ref, cl_ref, ql_ref, qs_ref,
                   q_ref, kv_ref, o_ref, m_s, l_s, acc_s, *, scale,
                   page_size, gp, bq, n_rows, n_tokens, value_dim):
    """Grid (visits,). ``q_ref`` [bq * gp, dp] and ``o_ref`` [bq * gp,
    value_dim] are the visit's q block, ``kv_ref`` [1, 1, page, dp] its
    page; the float32 scratch spans the q block."""
    i = pl.program_id(0)
    n = nvis_ref[0]
    shift = _VISIT_ROW_BITS + _VISIT_PAGE_BITS
    b = vis_ref[i] >> shift
    r = (vis_ref[i] >> _VISIT_PAGE_BITS) & ((1 << _VISIT_ROW_BITS) - 1)
    p = vis_ref[i] & ((1 << _VISIT_PAGE_BITS) - 1)
    first = (i == 0) | ((vis_ref[jnp.maximum(i - 1, 0)] >> shift) != b)
    last = (i == n - 1) | ((vis_ref[jnp.minimum(i + 1, n - 1)] >> shift) != b)

    @pl.when(first)
    def _init():
        m_s[...] = jnp.full(m_s.shape, -jnp.inf, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def accumulate(row0, rows, start, nq, ctx):
        """Online-softmax update of query rows [row0, row0 + rows) of the
        block for page ``p`` of the batch row that owns tokens [start,
        start + nq)."""
        span = pl.ds(row0, rows)
        q = q_ref[span, :]
        kv = kv_ref[0, 0]                               # [page, dp]
        s = jax.lax.dot_general(q, kv, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        tok = b * bq + (row0 + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 0)) // gp
        kv_pos = page_size * p + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        mask = (tok >= start) & (tok < start + nq) \
            & (kv_pos < ctx - nq + (tok - start) + 1)
        s = jnp.where(mask, s, -jnp.inf)
        m_prev = m_s[span, :]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        safe_m = jnp.where(m_new == -jnp.inf, 0.0, m_new)
        alpha = jnp.exp(m_prev - safe_m)
        pexp = jnp.exp(s - safe_m)
        l_s[span, :] = l_s[span, :] * alpha \
            + jnp.sum(pexp, axis=-1, keepdims=True)
        acc_s[span, :] = acc_s[span, :] * alpha + jax.lax.dot_general(
            pexp.astype(kv.dtype), kv[:, :value_dim],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_s[span, :] = m_new

    @pl.when(r < n_rows)
    def _visit():
        start, ctx = qs_ref[r], cl_ref[r]
        nq = jnp.minimum(ql_ref[r], n_tokens - start)
        lo = jnp.maximum(start, b * bq) - b * bq
        hi = jnp.minimum(start + nq, b * bq + bq) - b * bq

        @pl.when(hi - lo == bq)
        def _whole():
            step = min(_LATENT_SCORE_ROWS, bq * gp)
            for row0 in range(0, bq * gp, step):
                accumulate(row0, step, start, nq, ctx)

        @pl.when(hi - lo < bq)
        def _tokens():
            jax.lax.fori_loop(
                lo, hi, lambda j, c: accumulate(
                    pl.multiple_of(j * gp, gp), gp, start, nq, ctx), None)

    @pl.when(last)
    def _flush():
        l = l_s[...]
        o_ref[...] = (acc_s[...] / jnp.where(l == 0.0, 1.0, l)) \
            .astype(o_ref.dtype)


def _xla_ragged_latent_attention(q, pool, block_tables, context_lens,
                                 query_lens, q_starts, row_of, value_dim,
                                 scale):
    """The XLA composition: every token gathers its row's pages and
    attends over its causal prefix of them."""
    n_tokens, _, d = q.shape
    _, total_pages, page, _ = pool.shape
    pages_per_seq = block_tables.shape[1]
    lens, bt_tok = _per_token_views(n_tokens, block_tables, context_lens,
                                    query_lens, q_starts, row_of)
    g = jnp.take(pool[0], jnp.clip(bt_tok, 0, total_pages - 1),
                 axis=0).reshape(
        n_tokens, pages_per_seq * page, -1).astype(jnp.float32)
    s = jnp.einsum("thd,tkd->thk", q.astype(jnp.float32), g[..., :d]) * scale
    mask = jnp.arange(pages_per_seq * page)[None, None, :] \
        < lens[:, None, None]
    w = jnp.where(mask, jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1), 0.0)
    return jnp.einsum("thk,tkv->thv", w, g[..., :value_dim]).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("value_dim", "scale",
                                             "interpret", "use_kernel"))
def ragged_latent_attention(q, pool, block_tables, context_lens,
                            query_lens, q_starts=None, row_of=None,
                            value_dim=None, scale=None, visits=None,
                            interpret=None, use_kernel=None):
    """:func:`ragged_paged_attention` over LATENT pages (notes above
    :func:`latent_visits`): q [n_tokens, num_heads, latent_dim] (absorbed
    queries), ``pool`` [1, pages, page, dp]; every head of token j of row
    r attends to the rows of r's pages before its causal limit, keys the
    rows whole, values their first ``value_dim`` lanes. -> [n_tokens,
    num_heads, value_dim]; idle rows and padding tokens give zeros.
    ``visits``: :func:`latent_visits`' result where the caller has made
    it (once for all cache layers). ``use_kernel=None`` picks by
    :func:`latent_impl`."""
    n_tokens, n_heads, d = q.shape
    _, total_pages, page, dp = pool.shape
    n_rows, pages_per_seq = block_tables.shape
    if scale is None:
        scale = d ** -0.5
    if q_starts is None:
        q_starts = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32),
             jnp.cumsum(query_lens.astype(jnp.int32))[:-1]])
    if use_kernel is None:
        use_kernel = latent_impl(value_dim, page) == "pallas"
    if not use_kernel:
        return _xla_ragged_latent_attention(
            q, pool, block_tables, context_lens, query_lens, q_starts,
            row_of, value_dim, scale)
    if interpret is None:
        interpret = _interpret_default()
    if visits is None:
        visits = latent_visits(n_tokens, page, block_tables, context_lens,
                               query_lens, q_starts)
    vis, n_vis = visits
    bq = _LATENT_BQ
    gp = _round_up(n_heads, _Q_ALIGN)
    t_pad = _round_up(n_tokens, bq)
    qp = jnp.pad(q.astype(pool.dtype),
                 ((0, t_pad - n_tokens), (0, gp - n_heads), (0, dp - d))) \
        .reshape(t_pad * gp, dp)
    row_mask = (1 << _VISIT_ROW_BITS) - 1
    page_mask = (1 << _VISIT_PAGE_BITS) - 1

    def block_map(i, vis, *_):
        return (vis[i] >> (_VISIT_ROW_BITS + _VISIT_PAGE_BITS), 0)

    def page_map(i, vis, nvis, bt, *_):
        r = jnp.minimum((vis[i] >> _VISIT_PAGE_BITS) & row_mask, n_rows - 1)
        return (0, bt[r, vis[i] & page_mask], 0, 0)

    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, page_size=page, gp=gp, bq=bq,
            n_rows=n_rows, n_tokens=n_tokens, value_dim=value_dim),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,      # visits, n_visits, bt, cl, ql, qs
            grid=(n_vis[0],),
            in_specs=[pl.BlockSpec((bq * gp, dp), block_map),
                      pl.BlockSpec((1, 1, page, dp), page_map)],
            out_specs=pl.BlockSpec((bq * gp, value_dim), block_map),
            scratch_shapes=[pltpu.VMEM((bq * gp, 1), jnp.float32),
                            pltpu.VMEM((bq * gp, 1), jnp.float32),
                            pltpu.VMEM((bq * gp, value_dim), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((t_pad * gp, value_dim), q.dtype),
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_BUDGET),
        interpret=interpret,
    )(vis, n_vis, jnp.clip(block_tables, 0, total_pages - 1),
      context_lens.astype(jnp.int32), query_lens.astype(jnp.int32),
      q_starts.astype(jnp.int32), qp, pool)
    return out.reshape(t_pad, gp, value_dim)[:n_tokens, :n_heads]


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"))
def paged_latent_write_chunk(pool, rows, block_tables, pos, interpret=None,
                             use_kernel=None):
    """Write tokens' latents ``rows`` [T, latent_dim] at positions ``pos``
    [T] of the sequences whose pages ``block_tables`` [T, pages_per_seq]
    names (a block table a TOKEN) into ``pool`` [1, pages, page, dp], the
    rows zero-padded to ``dp``. Tokens with ``pos < 0`` or past the
    window are dropped. On the kernel path (``use_kernel=None`` picks by
    :func:`latent_impl`) the result aliases the pool: the in-place
    tile-group write of :func:`paged_kv_write_chunk`, one pool wide."""
    _, total_pages, page, dp = pool.shape
    window = page * block_tables.shape[1]
    valid = (pos >= 0) & (pos < window)
    safe = jnp.clip(pos, 0, window - 1)
    page_id = jnp.take_along_axis(
        jnp.clip(block_tables, 0, total_pages - 1),
        (safe // page)[:, None], axis=1)[:, 0]
    slot = jnp.where(valid, page_id * page + safe % page,
                     total_pages * page)
    rows = jnp.pad(rows.astype(pool.dtype),
                   ((0, 0), (0, dp - rows.shape[-1])))[None]     # [1, T, dp]
    if use_kernel is None:
        use_kernel = latent_impl(dp, page) == "pallas"
    if not use_kernel:
        return pool.reshape(1, total_pages * page, dp) \
            .at[:, slot].set(rows, mode="drop").reshape(pool.shape)
    if interpret is None:
        interpret = _interpret_default()
    rows = rows.astype(jnp.float32)
    # tokens a call: 4 bytes, two buffers, a row
    step = max(8, _WRITE_NEW_BYTES // (8 * dp) // 8 * 8)
    for m0 in range(0, slot.shape[0], step):
        pool, = _pallas_kv_write((pool,), (rows[:, m0:m0 + step],),
                                 slot[m0:m0 + step], interpret)
    return pool
