"""Sort-based MoE dispatch + grouped GEMM (VERDICT r3 next #8; reference:
paddle/phi/kernels/fusion/gpu/fused_moe_kernel.cu — sort tokens by expert,
run one grouped GEMM per projection, scatter back).

TPU-idiomatic ragged dispatch (the megablocks/MaxText pattern):
  1. top-k routing -> (token, expert) pairs, grouped by expert with a
     COUNTING sort (cumsum over the one-hot — XLA's bitonic sort and
     row scatters are both slow paths on TPU; this is one VPU prefix
     pass, no capacity dropping, and the wide data movement is
     gather-only);
  2. tokens land in expert-contiguous rows, each expert's group padded
     to the 128-row MXU block so every grid block belongs to exactly
     ONE expert;
  3. grouped GEMM: a Pallas kernel whose BlockSpec index_map reads the
     per-block expert id from scalar-prefetch SMEM and pulls that
     expert's weight tile — [BM, K] x [K, BN] MXU matmuls, none of them
     on another expert's weights. The layout is the static worst case,
     so the row blocks behind the last group hold no pair: given the
     count of live blocks (scalar prefetch too) the kernel fetches no
     tile, multiplies nothing and writes nothing for a dead block, which
     costs its grid steps' fixed time and leaves its output rows
     UNWRITTEN (jax.lax.ragged_dot drives the same Mosaic path, computes
     every block, and is used off-TPU / in interpret mode);
  4. gather-only combine: dest is pair-major, so the weighted top-k
     reduction needs no scatter and no un-sort.

Measured (v5e, 8192 tokens x 2048, E=8 swiglu dff=2816, top-2):
7.7 ms/step, 74 TF/s on the grouped GEMMs, dispatch below timer
resolution — 2.6x the GShard [S,E,C] one-hot einsum path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["sort_dispatch", "grouped_matmul", "moe_ffn_sorted",
           "dispatch_rows"]

_BM = 128  # row block: one expert per block after padding


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def dispatch_rows(tokens: int, k: int, experts: int) -> int:
    """Rows the grouped matmuls run over for ``tokens`` tokens, for the
    ``experts`` held here: the static bound of :func:`sort_dispatch`'s
    padded layout (every pair, since every pair COULD be a held
    expert's, and a row block of slack a held expert)."""
    return -(-tokens * k // _BM) * _BM + experts * _BM


def sort_dispatch(x, probs, k, normalize=True, select=None, first=0,
                  held=None):
    """Route tokens to top-k experts via one sort.

    x: [S, M]; probs: [S, E] router probabilities; ``select`` [S, E]:
    the scores the k experts are chosen by where those are not the
    probabilities themselves (a selection bias added to them), the
    weights still being ``probs`` at the chosen experts.
    ``first``, ``held``: the contiguous range ``[first, first + held)``
    of the E routed experts that is held here (one chip's share of an
    expert-parallel layer), every one of them by default. The choice and
    the normalisation are over all E whatever is held; a pair whose
    expert is absent gets no row and weight 0 (its part of the sum is
    another chip's), and the layout's experts are the held ones, numbered
    from 0 as the ``[held, ...]`` weight stacks are.
    Returns dict with padded expert-contiguous rows and the metadata to
    combine back:
      xp [P, M] (P static = S*k + held*_BM, block-aligned groups),
      dest [S*k] padded row of each (token, k) pair (an absent pair's: 0),
      weight [S*k] combine weights,
      block_gid [P/_BM] held expert's number per row block,
      live_blocks int32 scalar: the row blocks that hold a group, all of
        them at the front (``sum(padded_sizes) // _BM``); the blocks
        behind them hold zero rows alone,
      group_sizes [held] true rows per expert,
      here [S, k] which pairs' experts are held.
    """
    s, m = x.shape
    e = probs.shape[-1]
    held = e if held is None else held
    share = (first, held) != (0, e)
    t = s * k
    top_p, top_e = jax.lax.top_k(probs if select is None else select, k)
    if select is not None:
        top_p = jnp.take_along_axis(probs, top_e, axis=1)
    if normalize:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    flat_e = top_e.reshape(-1)                        # [T]
    flat_p = top_p.reshape(-1)
    # counting sort via cumsum over the one-hot — XLA's bitonic sort is
    # the slow path on TPU; a [T, E] prefix-sum is one cheap VPU pass
    if share:
        here = (flat_e >= first) & (flat_e < first + held)
        # an absent pair's one-hot row is all zeros: it joins no group
        oh = jax.nn.one_hot(jnp.where(here, flat_e - first, held), held,
                            dtype=jnp.int32)          # [T, held]
        flat_p = jnp.where(here, flat_p, 0.0)
    else:
        here = jnp.ones((t,), bool)
        oh = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)   # [T, E]
    prefix = jnp.cumsum(oh, axis=0)                   # [T, E]
    counts = prefix[-1]                               # [E]
    padded = ((counts + _BM - 1) // _BM) * _BM
    group_start = jnp.cumsum(padded) - padded         # padded offsets
    p_rows = dispatch_rows(s, k, held)                # static upper bound
    if share:
        # read through the one-hot, which an absent pair has none of (and
        # which a share of no expert at all has no column of)
        dest = jnp.sum(oh * (group_start + prefix - 1), axis=1)
        to_row = jnp.where(here, dest, p_rows)        # absent: dropped
    else:
        rank = jnp.take_along_axis(prefix, flat_e[:, None],
                                   axis=1)[:, 0] - 1  # rank within expert
        to_row = dest = group_start[flat_e] + rank    # [T] padded row
    # row -> source pair: one small int32 scatter (pad rows gather the
    # appended zero row); the WIDE data movement stays gather-only
    row_pair = jnp.full((p_rows,), t, jnp.int32).at[to_row].set(
        jnp.arange(t, dtype=jnp.int32), mode="drop")
    src_tok = jnp.where(row_pair < t, row_pair // k, s)
    xz = jnp.concatenate([x, jnp.zeros((1, m), x.dtype)], 0)
    xp = xz[src_tok]
    rows = jnp.arange(p_rows)
    gid_of_row = jnp.clip(
        jnp.searchsorted(jnp.cumsum(padded), rows, side="right"),
        0, max(held - 1, 0))
    block_gid = gid_of_row[::_BM].astype(jnp.int32)
    return {"xp": xp, "dest": dest, "weight": flat_p,
            "block_gid": block_gid,
            "live_blocks": (jnp.sum(padded) // _BM).astype(jnp.int32),
            "group_sizes": counts,
            "padded_sizes": padded, "here": here.reshape(s, k)}


def _gmm_kernel(gid_ref, live_ref, x_ref, w_ref, o_ref, *, w_nk=False):
    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        if w_nk:
            o_ref[...] = jax.lax.dot_general(
                x_ref[...], w_ref[0], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
        else:
            o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                                 preferred_element_type=jnp.float32
                                 ).astype(o_ref.dtype)


def _gmm_index_maps(col_blocks):
    """The x, weight and output index maps of :func:`grouped_matmul`'s
    grid ``(row block i, column block j)``, scalar-prefetch operands
    ``gid`` [row blocks] and ``live`` [1] last. A dead step (``i >=
    live[0]``) names the blocks of the LAST live step, so the pipeline
    sees no index change after it: no fetch, and the one write-back of
    the last live output block when the grid ends. Two weight maps: of
    a [E, K, N] stack, and of a [E, N, K] one."""
    def o_map(i, j, gid, live):
        dead = i >= live[0]
        return (jnp.where(dead, live[0] - 1, i),
                jnp.where(dead, col_blocks - 1, j))

    def x_map(i, j, gid, live):
        return o_map(i, j, gid, live)[0], 0

    def w_map(i, j, gid, live):
        i, j = o_map(i, j, gid, live)
        return gid[i], 0, j

    def w_nk_map(i, j, gid, live):
        i, j = o_map(i, j, gid, live)
        return gid[i], j, 0

    return x_map, w_map, w_nk_map, o_map


def grouped_matmul(xp, w, block_gid, live_blocks=None, *, bn=None,
                   impl=None, interpret=None, w_nk=False):
    """Block-aligned grouped GEMM: row block i multiplies expert
    ``block_gid[i]``'s weight.  xp [P, K] (P % 128 == 0), w [E, K, N],
    or with ``w_nk`` [E, N, K] (each expert's weight out x in, a Linear's
    own layout: where N is no multiple of 128, as relu² experts' 1856,
    the chip keeps an [E, K, N] stack with K minor, and the kernel would
    need a copy of the whole stack a call; an [E, N, K] one it reads as
    it lies).

    ``live_blocks`` (int32 scalar, :func:`sort_dispatch`'s): only the
    first that many row blocks are computed; the rows of the others are
    left UNWRITTEN (whatever the buffer held), so nothing may read them.
    Row block 0 always counts as live: a layout with no pair at all has
    zeros there, and an absent pair's ``dest`` 0 reads zeros, not what
    the buffer held. None: every block is computed.

    impl: "pallas" (the scalar-prefetch kernel; interpret=True runs it on
    CPU), "ragged" (jax.lax.ragged_dot — same Mosaic path on TPU, every
    block computed), or None = pallas on TPU, ragged elsewhere."""
    if impl is None:
        impl = "ragged" if _interpret_default() else "pallas"
    p, kdim = xp.shape
    e, n = w.shape[0], w.shape[1 if w_nk else 2]
    def _ragged():
        # padded group sizes from the block map (nondecreasing by
        # construction, so rows are expert-contiguous as ragged_dot needs)
        sizes = jnp.bincount(block_gid, length=e) * _BM
        return jax.lax.ragged_dot(xp, jnp.swapaxes(w, 1, 2) if w_nk else w,
                                  sizes.astype(jnp.int32))

    if impl == "ragged":
        return _ragged()
    if interpret is None:
        interpret = _interpret_default()
    # a given bn must DIVIDE n
    if bn is not None:
        if n % bn:
            raise ValueError(f"bn={bn} does not divide N={n}")
    elif n <= 512:
        bn = n
    else:
        bn = next((c for c in (512, 384, 256, 128) if n % c == 0), None)
        if bn is None:
            # no MXU-aligned divisor (relu² experts of 1856): the width
            # of the fewest padded columns, the last column block hanging
            # over the array's edge, where Pallas reads padding and
            # writes nothing
            bn = min((512, 384, 256), key=lambda c: (-(-n // c) * c, -c))
    grid = (p // _BM, -(-n // bn))
    live = jnp.clip(grid[0] if live_blocks is None else live_blocks,
                    1, grid[0]).astype(jnp.int32).reshape(1)
    x_map, w_map, w_nk_map, o_map = _gmm_index_maps(grid[1])
    return pl.pallas_call(
        functools.partial(_gmm_kernel, w_nk=True) if w_nk else _gmm_kernel,
        name="moe_grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((_BM, kdim), x_map),
                pl.BlockSpec((1, bn, kdim), w_nk_map) if w_nk else
                pl.BlockSpec((1, kdim, bn), w_map),
            ],
            out_specs=pl.BlockSpec((_BM, bn), o_map),
        ),
        out_shape=jax.ShapeDtypeStruct((p, n), xp.dtype),
        interpret=interpret,
    )(block_gid, live, xp, w)


def moe_ffn_sorted(x, probs, w1, w2, k=2, *, activation="swiglu",
                   normalize=True, b1=None, b2=None, impl=None,
                   interpret=None):
    """Full sort-dispatched MoE FFN.

    x [S, M]; probs [S, E]; w1 [E, M, H] (H = 2*dff for swiglu);
    w2 [E, H'|dff, M]. Returns [S, M]."""
    d = sort_dispatch(x, probs, k, normalize=normalize)
    h = grouped_matmul(d["xp"], w1, d["block_gid"], d["live_blocks"],
                       impl=impl, interpret=interpret)
    if b1 is not None:
        h = h + b1.reshape(b1.shape[0], -1)[d["block_gid"]
                                            ].repeat(_BM, 0)[:h.shape[0]]
    if activation == "swiglu":
        g, u = jnp.split(h, 2, axis=-1)
        h = jax.nn.silu(g) * u
    elif activation == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jnp.maximum(h, 0)
    y = grouped_matmul(h, w2, d["block_gid"], d["live_blocks"], impl=impl,
                       interpret=interpret)
    if b2 is not None:
        y = y + b2.reshape(b2.shape[0], -1)[d["block_gid"]
                                            ].repeat(_BM, 0)[:y.shape[0]]
    s, m = x.shape
    # gather-only combine: dest is pair-major, so y[dest] is already in
    # (token, k) order — weighted reduce over k, no scatter, no un-sort
    pair_y = y[d["dest"]] * d["weight"][:, None].astype(y.dtype)
    return jnp.sum(pair_y.reshape(s, k, m), axis=1)
