"""The ragged chunked scan of Mamba-2's state-space layer
(``incubate/nn/pallas/ssd_scan.py``): the Pallas kernel under the
interpreter, and the token-at-a-time XLA form, against a plain numpy
recurrence a row at a time, over random ragged layouts of a flat token
axis: one-token (decode) rows, prefill rows that start anywhere and
cross the 128-token blocks' edges, idle rows, padding, rows that start
from zeros. Small widths; the published ones are compiled for the chip in
``tests/test_ragged_kernel_mosaic.py``. float32 throughout: what
separates the chunked kernel from the recurrence is the order of its
sums (tolerance 1e-4 of values of order 1)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.pallas import ssd_scan as S

H, P, G, N = 8, 8, 2, 16
TOL = 1e-4


def _recurrence(x, dt, A, B, C, D, state, qs, ql, reset):
    """A row at a time, a token at a time, float64."""
    T = x.shape[0]
    rep = H // G
    y = np.zeros((T, H, P))
    st = np.array(state, np.float64)
    for r in range(len(qs)):
        if ql[r] == 0:
            continue
        s = np.zeros_like(st[r]) if reset[r] else st[r].copy()
        for t in range(qs[r], qs[r] + ql[r]):
            Bh, Ch = np.repeat(B[t], rep, 0), np.repeat(C[t], rep, 0)
            s = np.exp(dt[t] * A)[:, None, None] * s \
                + (dt[t][:, None] * x[t])[:, :, None] * Bh[:, None, :]
            y[t] = (s * Ch[:, None, :]).sum(-1) + D[:, None] * x[t]
        st[r] = s
    return y, st


def _layout(rng, T, R):
    """Random rows packed on [0, T): decode rows of one token and prefill
    rows of 2..150 tokens in a random order of slots, some slots idle,
    padding at the end; about a third of the rows restart."""
    qs, ql = np.zeros(R, np.int32), np.zeros(R, np.int32)
    cursor = 0
    for r in rng.permutation(R):
        n = 1 if rng.rand() < 0.5 else int(rng.randint(2, 151))
        if rng.rand() < 0.2 or cursor + n > T - 3:
            continue                                  # idle
        qs[r], ql[r] = cursor, n
        cursor += n
    return qs, ql, (rng.rand(R) < 0.3) & (ql > 0)


def _inputs(rng, T, R):
    x = rng.randn(T, H, P).astype(np.float32)
    dt = rng.uniform(0.001, 0.3, (T, H)).astype(np.float32)
    A = -rng.uniform(1, 16, H).astype(np.float32)
    D = rng.randn(H).astype(np.float32)
    B = (rng.randn(T, G, N) * 0.5).astype(np.float32)
    C = (rng.randn(T, G, N) * 0.5).astype(np.float32)
    state = rng.randn(R, H, P, N).astype(np.float32)
    return x, dt, A, B, C, D, state


def _run(impl, x, dt, A, B, C, D, state, qs, ql, reset):
    def f(*a):
        x, dt, A, B, C, D, st, qs, ql, rs = a
        yT, st = S.ssd_ragged_scan(
            x.transpose(1, 2, 0), dt.T, A, B.transpose(1, 0, 2),
            C.transpose(1, 0, 2), D, st, qs, ql, rs, impl=impl,
            interpret=True)
        return yT.transpose(2, 0, 1), st

    y, st = jax.jit(f)(x, dt, A, B, C, D, state, jnp.asarray(qs),
                       jnp.asarray(ql), jnp.asarray(reset))
    return np.asarray(y), np.asarray(st)


def _check(impl, args, qs, ql, reset):
    want_y, want_s = _recurrence(*args, qs, ql, reset)
    y, st = _run(impl, *args, qs, ql, reset)
    live = np.zeros(y.shape[0], bool)
    for r in range(len(qs)):
        live[qs[r]:qs[r] + ql[r]] = True
    assert np.abs(y[live] - want_y[live]).max() < TOL
    assert np.abs(st - want_s).max() < TOL
    # a row with no token keeps its state bit for bit
    idle = ql == 0
    assert (st[idle] == args[-1][idle]).all()


@pytest.mark.parametrize("impl", ["pallas", "xla"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ragged_layouts_equal_the_recurrence(impl, seed):
    rng = np.random.RandomState(seed)
    T, R = 384, 10
    qs, ql, reset = _layout(rng, T, R)
    assert (ql == 1).any() and (ql > 1).any() and (ql == 0).any()
    _check(impl, _inputs(rng, T, R), qs, ql, reset)


def test_rows_across_block_edges_beside_decode_rows():
    """The serving step's shape of work: a decode row on each side of a
    block edge, a prefill row that starts at token 3 and crosses two
    edges, one that starts one token before an edge, one of a single
    token at the last position, and a row of zero tokens."""
    rng = np.random.RandomState(5)
    T, R = 300, 7
    qs = np.array([0, 0, 3, 127, 128, 129, 299], np.int32)
    ql = np.array([1, 0, 124, 1, 1, 170, 1], np.int32)
    reset = np.array([0, 0, 1, 0, 0, 0, 1], bool)
    _check("pallas", _inputs(rng, T, R), qs, ql, reset)


def test_no_live_row_moves_no_state():
    rng = np.random.RandomState(6)
    args = _inputs(rng, 128, 4)
    zeros = np.zeros(4, np.int32)
    y, st = _run("pallas", *args, zeros, zeros, np.zeros(4, bool))
    assert (st == args[-1]).all()


def test_bfloat16_state_rounds_once_an_item():
    """A bfloat16 state is read and written in bfloat16, the arithmetic
    in float32: within bfloat16's rounding of the float32 result."""
    rng = np.random.RandomState(7)
    T, R = 256, 6
    qs, ql, reset = _layout(rng, T, R)
    args = list(_inputs(rng, T, R))
    args[-1] = args[-1].astype(jnp.bfloat16)
    want_y, want_s = _recurrence(*args[:-1],
                                 np.asarray(args[-1], np.float32),
                                 qs, ql, reset)
    y, st = _run("pallas", *args, qs, ql, reset)
    assert st.dtype == jnp.bfloat16
    scale = np.abs(want_s).max()
    assert np.abs(np.asarray(st, np.float32) - want_s).max() < 0.05 * scale


def test_work_list_splits_rows_at_block_edges():
    """Rows in the order of their first token; a row's items consecutive,
    each inside one block; the flags say where a row and a block start;
    past the live items, the last one repeated with no flag."""
    qs = jnp.asarray([200, 0, 5, 0], jnp.int32)
    ql = jnp.asarray([100, 5, 190, 0], jnp.int32)
    reset = jnp.asarray([True, False, False, False])
    row, blk, off, n, flags = (np.asarray(a) for a in S.ssd_work_items(
        qs, ql, reset, 384))
    assert len(row) == 4 + 3 - 1
    live = (flags & S.LIVE) != 0
    assert list(zip(row[live], blk[live], off[live], n[live])) == [
        (1, 0, 0, 5), (2, 0, 5, 123), (2, 1, 0, 67), (0, 1, 72, 56),
        (0, 2, 0, 44)]
    assert list((flags[live] & S.FIRST_ROW) != 0) == [1, 1, 0, 1, 0]
    assert list((flags[live] & S.FIRST_BLOCK) != 0) == [1, 0, 1, 0, 1]
    assert list((flags[live] & S.RESET) != 0) == [0, 0, 0, 1, 0]
    assert (row[~live] == 0).all() and (blk[~live] == 2).all() \
        and (flags[~live] == 0).all()


def test_sequence_form_equals_the_recurrence():
    """``ssd_sequence`` (the dense forms' prefill) and ``ssd_update``
    (their decode step) over whole sequences from zeros."""
    rng = np.random.RandomState(8)
    x, dt, A, B, C, D, _ = _inputs(rng, 40, 1)
    y, s = S.ssd_sequence(*(jnp.asarray(a)[None] for a in (x, dt)), A,
                          *(jnp.asarray(a)[None] for a in (B, C)), D,
                          jnp.zeros((1, H, P, N)))
    want_y, want_s = _recurrence(x, dt, A, B, C, D,
                                 np.zeros((1, H, P, N)), [0], [40], [True])
    assert np.abs(np.asarray(y[0]) - want_y).max() < TOL
    assert np.abs(np.asarray(s) - want_s).max() < TOL
