"""Ragged chunked scan of Mamba-2's state-space layer (SSD) for the
serving step: several prefill rows and many one-token decode rows packed
on one flat token axis, each row carrying its own recurrent state.

The recurrence, per head h (``P`` values a head, state size ``N``, the
heads in ``G`` groups that share one B and one C):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T        S: [P, N]
    y_t = S_t C_t + D_h x_t

Layouts of :func:`ssd_ragged_scan`, the kernel's own (``T`` tokens of the
flat axis, ``R`` rows = the engine's slots):

  xT    [H, P, T]   the conv's output, x part, tokens last
  dtT   [H, T]      float32, after softplus
  A, D  [H]         float32, A < 0
  BT, CT [G, T, N]
  state [R, H, P, N]  float32 (or bfloat16), read and written in place
  q_starts, query_lens [R]: row r owns tokens [q_starts[r], + query_lens[r])
  reset [R] bool: the row starts from zeros (its first token is at
        position 0), whatever its slot held.

-> (yT [H, P, T] float32, state). A row with no token this step keeps its
state untouched; a token of no row has no defined y.

The Pallas kernel cuts the flat axis into blocks of ``chunk`` tokens and
walks a WORK LIST of (row, block) pairs: each row's tokens split at the
blocks' edges, rows in the order of their first token, so that a row's
items and a block's items are each consecutive. Grid (head blocks, work
items); the state block of the item's row stays in fast memory across
the row's items and is written back once, after its last; the output
block of a token block likewise. An item of ONE token (a decode row)
takes the recurrence as written; an item of more takes the chunked form
of the same sum (the state-space dual: intra-chunk C B^T weighted by the
segment decays, the carried state read out with the decay to each token,
and the new state as the decayed old one plus the chunk's weighted
outer products). Tokens outside the item are masked: dt = 0 and x = 0
there, which decays nothing and adds nothing. Tokens of different rows
never mix. Elsewhere than on a TPU the same arithmetic runs a token at a
time through ``lax.scan`` (:func:`ssd_impl` says which).

The kernel's operands are told apart from the ragged attention kernel's
and the grouped expert matmuls' in a device trace
(``benchmark/lib/xplane.py``, ``lib/xing4_kernels.py``): one rank-4
non-integer operand (the state), no rank-2 ``s32`` table, and no rank-3
operand whose leading size is an expert count (its rank-3 operands lead
with heads or groups).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_ragged_scan", "ssd_sequence", "ssd_update", "ssd_impl",
           "ssd_work_items"]

CHUNK = 128                 # tokens a block of the flat axis
_VMEM_STATE = 4 << 20       # the state block's share of fast memory
_HIGHEST = jax.lax.Precision.HIGHEST
# flags of a work item
FIRST_ROW, RESET, FIRST_BLOCK, LIVE = 1, 2, 4, 8


def ssd_impl() -> str:
    """"pallas" on a TPU, "xla" (a token at a time) elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def ssd_update(s, x, dt, A, B, C, D):
    """One token of the recurrence, any leading batch dims: s [..., H, P,
    N], x [..., H, P], dt [..., H], B, C [..., G, N] -> (s, y [..., H,
    P]), float32."""
    f32 = jnp.float32
    rep = x.shape[-2] // B.shape[-2]
    Bh = jnp.repeat(B.astype(f32), rep, axis=-2)
    Ch = jnp.repeat(C.astype(f32), rep, axis=-2)
    x, dt = x.astype(f32), dt.astype(f32)
    s = jnp.exp(dt * A)[..., None, None] * s.astype(f32) \
        + (dt[..., None] * x)[..., None] * Bh[..., None, :]
    y = jnp.sum(s * Ch[..., None, :], axis=-1) + D[:, None] * x
    return s, y


def ssd_sequence(x, dt, A, B, C, D, s0):
    """Whole sequences a token at a time: x [b, s, H, P], dt [b, s, H],
    B, C [b, s, G, N], s0 [b, H, P, N] -> (y [b, s, H, P] float32, the
    state after the last token, float32)."""
    def step(s, inp):
        s, y = ssd_update(s, *inp[:2], A, *inp[2:], D)
        return s, y

    inp = tuple(jnp.swapaxes(a, 0, 1) for a in (x, dt, B, C))
    s, y = jax.lax.scan(step, s0.astype(jnp.float32), inp)
    return jnp.swapaxes(y, 0, 1), s


def _row_of(q_starts, query_lens, n_tokens):
    t = jnp.arange(n_tokens)
    inside = (t[None, :] >= q_starts[:, None]) \
        & (t[None, :] < (q_starts + query_lens)[:, None])
    return jnp.where(jnp.any(inside, 0), jnp.argmax(inside, 0), -1)


def _xla_ragged_scan(x, dt, A, B, C, D, state, q_starts, query_lens,
                     reset):
    row_of = _row_of(q_starts, query_lens, x.shape[0])
    s_all = jnp.where(reset[:, None, None, None], 0.0,
                      state.astype(jnp.float32))

    def step(s_all, inp):
        r = inp[-1]
        rr = jnp.maximum(r, 0)
        s, y = ssd_update(s_all[rr], *inp[:2], A, *inp[2:4], D)
        s_all = s_all.at[rr].set(jnp.where(r >= 0, s, s_all[rr]))
        return s_all, jnp.where(r >= 0, y, 0.0)

    s_all, y = jax.lax.scan(step, s_all, (x, dt, B, C, row_of))
    return y, s_all.astype(state.dtype)


def ssd_work_items(q_starts, query_lens, reset, n_tokens, chunk=CHUNK):
    """The kernel's work list for a flat axis of ``n_tokens`` (a multiple
    of ``chunk``): -> (row, block, first token within the block, tokens,
    flags), each [items], ``items`` = rows + blocks - 1 (a row has at
    least one item, and each block edge splits at most one row). Items
    past the live ones repeat the last live item's row and block with no
    flag, so that they move nothing; with no live item at all, item 0
    copies row 0's state through."""
    n_rows = q_starts.shape[0]
    n_items = n_rows + n_tokens // chunk - 1
    live_row = query_lens > 0
    b0 = q_starts // chunk
    b1 = (q_starts + query_lens - 1) // chunk
    cnt = jnp.where(live_row, b1 - b0 + 1, 0)
    order = jnp.argsort(jnp.where(live_row, q_starts, n_tokens),
                        stable=True)
    cnt_o = cnt[order]
    ends = jnp.cumsum(cnt_o)
    n_live = ends[-1]
    i = jnp.arange(n_items, dtype=jnp.int32)
    ii = jnp.minimum(i, jnp.maximum(n_live - 1, 0))
    k = jnp.minimum(jnp.sum((ii[:, None] >= ends[None, :])
                            .astype(jnp.int32), axis=1), n_rows - 1)
    r = order[k]
    j = ii - (ends[k] - cnt_o[k])
    b = b0[r] + j
    lo = jnp.maximum(q_starts[r], b * chunk) - b * chunk
    hi = jnp.minimum(q_starts[r] + query_lens[r], (b + 1) * chunk) \
        - b * chunk
    live = i < n_live
    first_row = (live & (j == 0)) | ((n_live == 0) & (i == 0))
    prev = jnp.concatenate([jnp.full((1,), -1, b.dtype), b[:-1]])
    first_blk = (b != prev) & (live | (i == 0))
    flags = FIRST_ROW * first_row.astype(jnp.int32) \
        + RESET * (first_row & live & reset[r]).astype(jnp.int32) \
        + FIRST_BLOCK * first_blk.astype(jnp.int32) \
        + LIVE * live.astype(jnp.int32)
    return (r.astype(jnp.int32), b.astype(jnp.int32), lo.astype(jnp.int32),
            (hi - lo).astype(jnp.int32), flags)


def _kernel(row_ref, blk_ref, off_ref, len_ref, flag_ref, a_ref, d_ref,
            x_ref, dt_ref, b_ref, c_ref, s_ref, y_ref, so_ref, *, hpg,
            chunk):
    """Grid (head blocks, work items). Blocks: x, y [hb, P, L]; dt [hb,
    L]; B, C [hb / hpg, L, N]; the state [1, hb, P, N]. A and D are
    whole in scalar memory."""
    f32 = jnp.float32
    L = chunk
    hb, n_grp = x_ref.shape[0], b_ref.shape[0]
    h0 = pl.program_id(0) * hb
    w = pl.program_id(1)
    flag, off, n = flag_ref[w], off_ref[w], len_ref[w]

    @pl.when(flag & FIRST_BLOCK != 0)
    def _zero():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)

    @pl.when(flag & FIRST_ROW != 0)
    def _start():
        keep = (flag & RESET) == 0
        so_ref[0] = jnp.where(keep, s_ref[0].astype(f32), 0.0) \
            .astype(so_ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    mine = (lane >= off) & (lane < off + n)             # [1, L]
    sub = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)
    mine_col = (sub >= off) & (sub < off + n)           # [L, 1]
    live = (flag & LIVE) != 0

    def group_inputs(g):
        dt8 = jnp.where(mine, dt_ref[pl.ds(pl.multiple_of(g * hpg, hpg),
                                           hpg), :], 0.0)   # [hpg, L]
        bg = jnp.where(mine_col, b_ref[g].astype(f32), 0.0)  # [L, N]
        cg = jnp.where(mine_col, c_ref[g].astype(f32), 0.0)
        return dt8, bg, cg

    @pl.when(live & (n == 1))
    def _decode():
        def group(g, carry):
            dt8, bg, cg = group_inputs(g)
            bt = jnp.sum(bg, axis=0, keepdims=True)          # [1, N]
            ct = jnp.sum(cg, axis=0, keepdims=True)
            dts = jnp.sum(dt8, axis=1, keepdims=True)        # [hpg, 1]
            for j in range(hpg):
                h = g * hpg + j
                x = jnp.where(mine, x_ref[h].astype(f32), 0.0)   # [P, L]
                xt = jnp.sum(x, axis=1, keepdims=True)           # [P, 1]
                dt = dts[j:j + 1]                                # [1, 1]
                s = jnp.exp(dt * a_ref[h0 + h]) * so_ref[0, h].astype(f32) \
                    + (dt * xt) * bt                             # [P, N]
                y = jnp.sum(s * ct, axis=1, keepdims=True) \
                    + d_ref[h0 + h] * xt                         # [P, 1]
                so_ref[0, h] = s.astype(so_ref.dtype)
                y_ref[h] = jnp.where(mine, y, y_ref[h])
            return carry

        jax.lax.fori_loop(0, n_grp, group, None)

    @pl.when(live & (n > 1))
    def _chunk():
        t_i = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        s_i = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        causal, eye = s_i <= t_i, s_i == t_i
        upper = (t_i <= s_i).astype(f32)          # [s, t]: s <= t

        def group(g, carry):
            dt8, bg, cg = group_inputs(g)
            # cumulative sums of dt over the block: A is a head's constant
            cum8 = jnp.dot(dt8, upper, precision=_HIGHEST,
                           preferred_element_type=f32)      # [hpg, L]
            cb = jax.lax.dot_general(cg, bg, (((1,), (1,)), ((), ())),
                                     precision=_HIGHEST,
                                     preferred_element_type=f32)  # [t, s]
            for j in range(hpg):
                h = g * hpg + j
                a = a_ref[h0 + h]
                dt = dt8[j:j + 1]                                # [1, L]
                cs = a * cum8[j:j + 1]                           # [1, L]
                cs_col = jnp.sum(jnp.where(eye, cs, 0.0), axis=1,
                                 keepdims=True)                  # [L, 1]
                seg = jnp.exp(jnp.where(causal, cs_col - cs, -jnp.inf))
                wmat = cb * seg * dt                             # [t, s]
                x = jnp.where(mine, x_ref[h].astype(f32), 0.0)   # [P, L]
                s0 = so_ref[0, h].astype(f32)                    # [P, N]
                y = jax.lax.dot_general(
                    x, wmat, (((1,), (1,)), ((), ())), precision=_HIGHEST,
                    preferred_element_type=f32) \
                    + jnp.exp(cs) * jax.lax.dot_general(
                        s0, cg, (((1,), (1,)), ((), ())),
                        precision=_HIGHEST, preferred_element_type=f32) \
                    + d_ref[h0 + h] * x                          # [P, L]
                # a <= 0: the last live token's sum is the smallest
                cs_end = jnp.min(cs, axis=1, keepdims=True)      # [1, 1]
                wgt = jnp.exp(cs_end - cs) * dt                  # [1, L]
                s = jnp.exp(cs_end) * s0 + jnp.dot(
                    x * wgt, bg, precision=_HIGHEST,
                    preferred_element_type=f32)                  # [P, N]
                so_ref[0, h] = s.astype(so_ref.dtype)
                y_ref[h] = jnp.where(mine, y, y_ref[h])
            return carry

        jax.lax.fori_loop(0, n_grp, group, None)


def _head_block(H, P, N, hpg, itemsize):
    """Heads a grid block: whole groups, the state block within its
    share of fast memory (double-buffered, in and out)."""
    hb = hpg
    while H % (2 * hb) == 0 and 4 * 2 * hb * P * N * itemsize \
            <= _VMEM_STATE:
        hb *= 2
    return hb


def _pallas_ragged_scan(xT, dtT, A, Bg, Cg, D, state, q_starts, query_lens,
                        reset, interpret):
    H, P, T = xT.shape
    G, N = Bg.shape[0], Bg.shape[2]
    hpg = H // G
    L = CHUNK
    Tp = -(-T // L) * L
    if Tp != T:
        xT = jnp.pad(xT, ((0, 0), (0, 0), (0, Tp - T)))
        dtT = jnp.pad(dtT, ((0, 0), (0, Tp - T)))
        Bg, Cg = (jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0)))
                  for a in (Bg, Cg))
    items = ssd_work_items(q_starts, query_lens, reset, Tp, L)
    hb = _head_block(H, P, N, hpg, max(state.dtype.itemsize, 4))
    gb = hb // hpg

    def tok_map(h, w, row, blk, *_):
        return (h, 0, blk[w])

    def dt_map(h, w, row, blk, *_):
        return (h, blk[w])

    def grp_map(h, w, row, blk, *_):
        return (h, blk[w], 0)

    def st_map(h, w, row, blk, *_):
        return (row[w], h, 0, 0)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    yT, state = pl.pallas_call(
        functools.partial(_kernel, hpg=hpg, chunk=L),
        name="ssd_ragged_scan",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(H // hb, items[0].shape[0]),
            in_specs=[smem, smem,
                      pl.BlockSpec((hb, P, L), tok_map),
                      pl.BlockSpec((hb, L), dt_map),
                      pl.BlockSpec((gb, L, N), grp_map),
                      pl.BlockSpec((gb, L, N), grp_map),
                      pl.BlockSpec((1, hb, P, N), st_map)],
            out_specs=[pl.BlockSpec((hb, P, L), tok_map),
                       pl.BlockSpec((1, hb, P, N), st_map)]),
        out_shape=[jax.ShapeDtypeStruct((H, P, Tp), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the state: the last operand, after the five scalar tables
        input_output_aliases={11: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*items, A.astype(jnp.float32), D.astype(jnp.float32), xT,
      dtT.astype(jnp.float32), Bg, Cg, state)
    return yT[..., :T], state


def ssd_ragged_scan(xT, dtT, A, BT, CT, D, state, q_starts, query_lens,
                    reset, *, impl=None, interpret=None):
    """The ragged scan of one SSD layer (module docstring), in the
    kernel's layouts, tokens last for x and dt: xT [H, P, T], dtT [H, T],
    BT, CT [G, T, N] -> (yT [H, P, T] float32, state). The y of a token
    of no row is unspecified (the caller masks it)."""
    if impl is None:
        impl = ssd_impl()
    if impl == "xla":
        y, state = _xla_ragged_scan(
            xT.transpose(2, 0, 1), dtT.T, A, BT.transpose(1, 0, 2),
            CT.transpose(1, 0, 2), D, state, q_starts, query_lens, reset)
        return y.transpose(1, 2, 0), state
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _pallas_ragged_scan(xT, dtT, A, BT, CT, D, state, q_starts,
                               query_lens, reset, interpret)
