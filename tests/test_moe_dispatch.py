"""Sort-based MoE dispatch + grouped GEMM kernel (VERDICT r3 next #8;
reference: paddle/phi/kernels/fusion/gpu/fused_moe_kernel.cu)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.incubate.nn.pallas.moe_dispatch import (_BM, grouped_matmul,
                                                        moe_ffn_sorted,
                                                        sort_dispatch)


def _dense_ref(x, probs, w1, w2, k, normalize=True):
    top_p, top_e = jax.lax.top_k(probs, k)
    if normalize:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    S, M = x.shape
    DFF = w2.shape[1]
    ref = np.zeros((S, M), np.float32)
    pn, en = np.asarray(top_p), np.asarray(top_e)
    xn, w1n, w2n = np.asarray(x), np.asarray(w1), np.asarray(w2)
    for s in range(S):
        for j in range(k):
            e = en[s, j]
            h = xn[s] @ w1n[e]
            g, u = h[:DFF], h[DFF:]
            ref[s] += pn[s, j] * (((g / (1 + np.exp(-g))) * u) @ w2n[e])
    return ref


@pytest.fixture
def problem():
    rng = np.random.RandomState(0)
    S, M, E, K, DFF = 64, 32, 4, 2, 48
    x = jnp.asarray(rng.randn(S, M), jnp.float32)
    probs = jax.nn.softmax(jnp.asarray(rng.randn(S, E), jnp.float32), -1)
    w1 = jnp.asarray(rng.randn(E, M, 2 * DFF) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.randn(E, DFF, M) * 0.3, jnp.float32)
    return x, probs, w1, w2, K


class TestSortDispatch:
    def test_structure(self, problem):
        x, probs, w1, w2, K = problem
        d = sort_dispatch(x, probs, K)
        S, M = x.shape
        E = probs.shape[-1]
        assert d["xp"].shape[0] % _BM == 0
        # every (token, expert) pair lands in its expert's padded group
        counts = np.asarray(d["group_sizes"])
        padded = np.asarray(d["padded_sizes"])
        assert counts.sum() == S * K
        assert (padded % _BM == 0).all() and (padded >= counts).all()
        # block ids nondecreasing (expert-contiguous rows)
        gid = np.asarray(d["block_gid"])
        assert (np.diff(gid) >= 0).all()
        # dispatched rows hold the right token vectors
        dest = np.asarray(d["dest"])
        xp = np.asarray(d["xp"])
        for pair in range(0, S * K, 17):
            tok = pair // K
            np.testing.assert_allclose(xp[dest[pair]], np.asarray(x)[tok])

    def test_ffn_matches_dense(self, problem):
        x, probs, w1, w2, K = problem
        ref = _dense_ref(x, probs, w1, w2, K)
        out = moe_ffn_sorted(x, probs, w1, w2, k=K, impl="ragged")
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-3,
                                   atol=1e-4)

    def test_pallas_kernel_interpret(self, problem):
        x, probs, w1, w2, K = problem
        ref = _dense_ref(x, probs, w1, w2, K)
        out = moe_ffn_sorted(x, probs, w1, w2, k=K, impl="pallas",
                             interpret=True)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-3,
                                   atol=1e-4)

    def test_unnormalized_and_bias(self, problem):
        x, probs, w1, w2, K = problem
        E, _, M = w2.shape
        rng = np.random.RandomState(1)
        b1 = jnp.asarray(rng.randn(E, w1.shape[-1]) * 0.1, jnp.float32)
        b2 = jnp.asarray(rng.randn(E, M) * 0.1, jnp.float32)
        out = moe_ffn_sorted(x, probs, w1, w2, k=K, normalize=False,
                             b1=b1, b2=b2, impl="ragged")
        # dense reference with bias, unnormalized probs
        top_p, top_e = jax.lax.top_k(probs, K)
        S = x.shape[0]
        DFF = w2.shape[1]
        ref = np.zeros((S, M), np.float32)
        for s in range(S):
            for j in range(K):
                e = int(top_e[s, j])
                h = np.asarray(x)[s] @ np.asarray(w1)[e] + np.asarray(b1)[e]
                g, u = h[:DFF], h[DFF:]
                ref[s] += float(top_p[s, j]) * (
                    ((g / (1 + np.exp(-g))) * u) @ np.asarray(w2)[e]
                    + np.asarray(b2)[e])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-3,
                                   atol=1e-4)

    def test_jit_and_grad(self, problem):
        x, probs, w1, w2, K = problem

        @jax.jit
        def loss(xx, ww1, ww2):
            return moe_ffn_sorted(xx, probs, ww1, ww2, k=K,
                                  impl="ragged").sum()

        g = jax.grad(loss, argnums=(0, 1, 2))(x, w1, w2)
        for gi in g:
            assert np.isfinite(np.asarray(gi)).all()

    def test_extreme_imbalance(self):
        """All tokens to one expert — group padding must absorb it."""
        rng = np.random.RandomState(0)
        S, M, E, DFF = 96, 16, 4, 24
        x = jnp.asarray(rng.randn(S, M), jnp.float32)
        logits = jnp.full((S, E), -10.0).at[:, 2].set(10.0)
        probs = jax.nn.softmax(logits, -1)
        w1 = jnp.asarray(rng.randn(E, M, 2 * DFF) * 0.3, jnp.float32)
        w2 = jnp.asarray(rng.randn(E, DFF, M) * 0.3, jnp.float32)
        out = moe_ffn_sorted(x, probs, w1, w2, k=1, impl="ragged")
        ref = _dense_ref(x, probs, w1, w2, 1)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-3,
                                   atol=1e-4)


class TestGroupedMatmul:
    def test_vs_blockwise_dense(self):
        rng = np.random.RandomState(0)
        E, K_, N = 3, 16, 8
        P = 4 * _BM
        xp = jnp.asarray(rng.randn(P, K_), jnp.float32)
        w = jnp.asarray(rng.randn(E, K_, N), jnp.float32)
        gid = jnp.asarray([0, 1, 1, 2], jnp.int32)
        out = grouped_matmul(xp, w, gid, impl="ragged")
        ref = np.concatenate([
            np.asarray(xp)[i * _BM:(i + 1) * _BM] @ np.asarray(w)[g]
            for i, g in enumerate([0, 1, 1, 2])])
        np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-4,
                                   atol=1e-4)
        out_p = grouped_matmul(xp, w, gid, impl="pallas", interpret=True)
        np.testing.assert_allclose(np.asarray(out_p), ref, rtol=1e-4,
                                   atol=1e-4)

    @pytest.mark.parametrize("impl", ["ragged", "pallas"])
    @pytest.mark.parametrize("n", [8, 600])
    def test_a_stack_of_out_by_in_weights(self, impl, n):
        """``w_nk``: a [E, N, K] stack gives what its [E, K, N] transpose
        gives, also where no 128-multiple divides N (column blocks of
        384, the last hanging over the edge)."""
        rng = np.random.RandomState(n)
        E, K_ = 3, 128
        xp = jnp.asarray(rng.randn(4 * _BM, K_), jnp.float32)
        w = jnp.asarray(rng.randn(E, K_, n), jnp.float32)
        gid = jnp.asarray([0, 1, 1, 2], jnp.int32)
        kw = dict(impl=impl, interpret=True if impl == "pallas" else None)
        want = np.asarray(grouped_matmul(xp, w, gid, **kw))
        got = np.asarray(grouped_matmul(xp, jnp.swapaxes(w, 1, 2), gid,
                                        w_nk=True, **kw))
        assert got.shape == (4 * _BM, n)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)


def test_fused_moe_serving_api_uses_sorted_path():
    import paddle_tpu as paddle

    F = paddle.incubate.nn.functional
    rng = np.random.RandomState(0)
    B, S, DM, DFF, E, K = 2, 3, 8, 16, 4, 2
    x = rng.randn(B, S, DM).astype(np.float32)
    gw = rng.randn(DM, E).astype(np.float32)
    w1 = rng.randn(E, DM, 2 * DFF).astype(np.float32)
    w2 = rng.randn(E, DFF, DM).astype(np.float32)
    out = F.fused_moe(paddle.to_tensor(x), paddle.to_tensor(gw),
                      paddle.to_tensor(w1), paddle.to_tensor(w2),
                      moe_topk=K).numpy()
    probs = jax.nn.softmax(jnp.asarray(x.reshape(-1, DM) @ gw), -1)
    ref = _dense_ref(jnp.asarray(x.reshape(-1, DM)), probs,
                     jnp.asarray(w1), jnp.asarray(w2), K)
    np.testing.assert_allclose(out.reshape(-1, DM), ref, rtol=1e-3,
                               atol=1e-4)


# ------------------------------------------- a share of the routed experts
# (first, held) of 8 routed experts: the whole, the four quarters' kind, an
# uneven range, one expert, and none
RANGES = [(0, 8), (0, 2), (2, 2), (6, 2), (3, 5), (5, 1), (4, 0)]


@pytest.fixture
def routed8():
    rng = np.random.RandomState(3)
    S, M, E, K, DFF = 50, 16, 8, 3, 24
    x = jnp.asarray(rng.randn(S, M), jnp.float32)
    probs = jax.nn.sigmoid(jnp.asarray(rng.randn(S, E), jnp.float32))
    select = probs + jnp.asarray(rng.randn(E) * 0.3, jnp.float32)
    w1 = jnp.asarray(rng.randn(E, M, 2 * DFF) * 0.3, jnp.float32)
    w2 = jnp.asarray(rng.randn(E, DFF, M) * 0.3, jnp.float32)
    return x, probs, select, w1, w2, K


def _held_ffn(d, w1, w2, first, held, s, k, impl, gmm=grouped_matmul):
    """The combine of a share: the grouped matmuls over the HELD stacks,
    told the layout's live row blocks as the serving adapter tells them."""
    if held == 0:                  # no stack to run: every weight is 0
        assert not np.asarray(d["weight"]).any()
        return np.zeros((s, w2.shape[-1]), np.float32)
    kw = dict(impl=impl, interpret=True if impl == "pallas" else None)
    gid, live = d["block_gid"], d["live_blocks"]
    g, u = jnp.split(gmm(d["xp"], w1[first:first + held], gid, live, **kw),
                     2, axis=-1)
    y = gmm(jax.nn.silu(g) * u, w2[first:first + held], gid, live, **kw)
    return np.asarray((y[d["dest"]] * d["weight"][:, None])
                      .reshape(s, k, -1).sum(1))


@pytest.mark.parametrize("first, held", RANGES)
def test_held_range_lays_out_only_its_own_pairs(routed8, first, held):
    """Routing is over all 8 experts; rows exist only for pairs whose
    expert lies in [first, first + held); the weights stay normalised
    over all k chosen; the held experts are numbered from 0."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import dispatch_rows

    x, probs, select, w1, w2, K = routed8
    S = x.shape[0]
    d = sort_dispatch(x, probs, K, select=select, first=first, held=held)
    full = sort_dispatch(x, probs, K, select=select)
    top_e = np.asarray(jax.lax.top_k(select, K)[1])
    here = (top_e >= first) & (top_e < first + held)
    assert (np.asarray(d["here"]) == here).all()
    assert d["xp"].shape[0] == dispatch_rows(S, K, held) \
        == -(-S * K // _BM) * _BM + held * _BM
    counts = np.asarray(d["group_sizes"])
    assert counts.shape == (held,)
    assert (counts == [(top_e == first + e).sum() for e in range(held)]).all()
    # no row for an absent expert: the live rows are the held pairs' alone
    xp, dest = np.asarray(d["xp"]), np.asarray(d["dest"]).reshape(S, K)
    assert (np.abs(xp).sum(1) > 0).sum() == here.sum()
    assert len(np.unique(dest[here])) == here.sum()
    assert (xp[dest[here]] == np.asarray(x)[np.nonzero(here)[0]]).all()
    starts = np.cumsum(np.asarray(d["padded_sizes"])) \
        - np.asarray(d["padded_sizes"])
    for t, j in zip(*np.nonzero(here)):
        e = top_e[t, j] - first
        assert starts[e] <= dest[t, j] < starts[e] + counts[e]
        assert np.asarray(d["block_gid"])[dest[t, j] // _BM] == e
    # weights: the whole layer's where held, 0 where absent
    w, w_full = (np.asarray(a["weight"]).reshape(S, K) for a in (d, full))
    assert (w[here] == w_full[here]).all() and (w[~here] == 0).all()
    assert np.allclose(w_full.sum(1), 1.0)


@pytest.mark.parametrize("impl", ["ragged", "pallas"])
@pytest.mark.parametrize("first, held", RANGES)
def test_held_range_equals_the_per_token_loop(routed8, first, held, impl):
    x, probs, select, w1, w2, K = routed8
    S, DFF = x.shape[0], w2.shape[1]
    d = sort_dispatch(x, probs, K, select=select, first=first, held=held)
    got = _held_ffn(d, w1, w2, first, held, S, K, impl)
    top_e = np.asarray(jax.lax.top_k(select, K)[1])
    top_p = np.take_along_axis(np.asarray(probs), top_e, 1)
    top_p = top_p / top_p.sum(1, keepdims=True)      # over ALL k chosen
    xn, w1n, w2n = (np.asarray(a) for a in (x, w1, w2))
    want = np.zeros((S, x.shape[1]), np.float32)
    for s in range(S):
        for j in range(K):
            e = top_e[s, j]
            if first <= e < first + held:
                h = xn[s] @ w1n[e]
                g, u = h[:DFF], h[DFF:]
                want[s] += top_p[s, j] * ((g / (1 + np.exp(-g)) * u)
                                          @ w2n[e])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_the_shares_of_a_layer_add_up_to_the_layer(routed8):
    x, probs, select, w1, w2, K = routed8
    S = x.shape[0]
    whole = _held_ffn(sort_dispatch(x, probs, K, select=select), w1, w2,
                      0, 8, S, K, "ragged")
    parts = sum(_held_ffn(sort_dispatch(x, probs, K, select=select,
                                        first=f, held=2),
                          w1, w2, f, 2, S, K, "ragged")
                for f in (0, 2, 4, 6))
    np.testing.assert_allclose(parts, whole, rtol=1e-4, atol=1e-5)


def test_every_expert_held_is_todays_result_bit_for_bit(routed8):
    """``held`` = the routed experts is the layout and the program of a
    call without a range: the same jaxpr, so the same bits."""
    x, probs, select, w1, w2, K = routed8
    a = sort_dispatch(x, probs, K, select=select)
    b = sort_dispatch(x, probs, K, select=select, first=0, held=8)
    assert sorted(a) == sorted(b)
    for k in a:
        assert (np.asarray(a[k]) == np.asarray(b[k])).all(), k
    assert np.asarray(a["here"]).all()
    f = lambda **kw: jax.make_jaxpr(                     # noqa: E731
        lambda x, p, s: sort_dispatch(x, p, K, select=s, **kw))(
            x, probs, select)
    assert str(f()) == str(f(first=0, held=8))
    # and moe_ffn_sorted, which passes no range, still equals the dense sum
    out = moe_ffn_sorted(x, probs, w1, w2, k=K, impl="ragged")
    np.testing.assert_allclose(np.asarray(out),
                               _dense_ref(x, probs, w1, w2, K),
                               rtol=1e-3, atol=1e-4)


# ------------------------------------------- the dead row blocks are skipped
def _layout(case):
    """-> (xp, w, block_gid, live_blocks) of a block-aligned layout as
    :func:`sort_dispatch` makes them: live groups first, expert-contiguous,
    the trailing blocks' expert clipped to the last one."""
    rng = np.random.RandomState(5)
    blocks_of = {                       # live row blocks of each expert
        "full": [1, 2, 1],              # no dead block at all
        "experts_with_no_pair": [1, 0, 0, 2, 0],
        "three_blocks_of_one_expert": [1, 3, 1],
        "nothing_live": [0, 0, 0],
    }[case]
    dead = 0 if case == "full" else 3
    e, kdim, n = len(blocks_of), 24, 1024          # bn 512: 2 column blocks
    gid = np.repeat(np.arange(e), blocks_of)
    live = len(gid)
    gid = np.concatenate([gid, np.full(dead, e - 1)]).astype(np.int32)
    xp = rng.randn(len(gid) * _BM, kdim).astype(np.float32)
    xp[live * _BM:] = 0
    w = rng.randn(e, kdim, n).astype(np.float32)
    return jnp.asarray(xp), jnp.asarray(w), jnp.asarray(gid), live


@pytest.mark.parametrize("case", ["full", "experts_with_no_pair",
                                  "three_blocks_of_one_expert",
                                  "nothing_live"])
def test_live_blocks_leaves_every_live_row_as_it_was(case):
    """(a) With the count of live blocks the kernel computes the same
    bits on every live row as without it, and ``ragged`` agrees within
    its tolerance; ``live_blocks == p // 128`` is the call without it."""
    xp, w, gid, live = _layout(case)
    kw = dict(impl="pallas", interpret=True)
    whole = np.asarray(grouped_matmul(xp, w, gid, **kw))
    got = np.asarray(grouped_matmul(xp, w, gid, jnp.int32(live), **kw))
    rows = live * _BM
    assert (got[:rows] == whole[:rows]).all()
    np.testing.assert_allclose(
        got[:rows], np.asarray(grouped_matmul(xp, w, gid, jnp.int32(live),
                                              impl="ragged"))[:rows],
        rtol=1e-4, atol=1e-4)
    every = grouped_matmul(xp, w, gid, jnp.int32(len(gid)), **kw)
    assert (np.asarray(every) == whole).all()
    # row block 0 always counts as live: zeros in, zeros out
    if live == 0:
        assert (got[:_BM] == 0).all()


@pytest.mark.parametrize("first, held", [(0, 8), (2, 2), (3, 5)])
def test_live_blocks_on_a_dispatched_share(routed8, first, held):
    """(a) The same on :func:`sort_dispatch`'s own layout of a held range,
    and (d) its ``live_blocks`` is the padded groups' blocks, laid out
    first and expert by expert."""
    x, probs, select, w1, w2, K = routed8
    d = sort_dispatch(x, probs, K, select=select, first=first, held=held)
    live, padded = int(d["live_blocks"]), np.asarray(d["padded_sizes"])
    assert d["live_blocks"].dtype == jnp.int32 and d["live_blocks"].ndim == 0
    assert live == padded.sum() // _BM < d["xp"].shape[0] // _BM
    assert (np.bincount(np.asarray(d["block_gid"])[:live], minlength=held)
            == padded // _BM).all()
    assert not np.asarray(d["xp"])[live * _BM:].any()
    kw = dict(impl="pallas", interpret=True)
    w = w1[first:first + held]
    whole = np.asarray(grouped_matmul(d["xp"], w, d["block_gid"], **kw))
    got = np.asarray(grouped_matmul(d["xp"], w, d["block_gid"],
                                    d["live_blocks"], **kw))
    assert (got[:live * _BM] == whole[:live * _BM]).all()


def test_live_blocks_counts_an_expert_with_three_blocks():
    """(d) 300 tokens that all choose expert 1 of 4: three blocks of it,
    one of the second choice's, and the layout's other blocks dead."""
    s, e = 300, 4
    x = jnp.ones((s, 8), jnp.float32)
    probs = jax.nn.softmax(jnp.tile(jnp.asarray(
        [[0.0, 9.0, 1.0, -9.0]], jnp.float32), (s, 1)), -1)
    d = sort_dispatch(x, probs, 2)
    padded = np.asarray(d["padded_sizes"])
    assert list(padded // _BM) == [0, 3, 3, 0]
    assert int(d["live_blocks"]) == 6 == padded.sum() // _BM
    assert list(np.asarray(d["block_gid"])[:6]) == [1, 1, 1, 2, 2, 2]
    assert d["xp"].shape[0] // _BM == 5 + e


def _poisoned(monkeypatch):
    """Every grouped matmul given a count of live blocks gets its dead rows
    filled with NaN first: what an unwritten buffer may hold. (In interpret
    mode an unwritten output block reads as zeros, so the first matmul's
    dead rows would reach the second as zeros without this.)"""
    from paddle_tpu.incubate.nn.pallas import moe_dispatch

    real = moe_dispatch.grouped_matmul

    def poisoned(xp, w, block_gid, live_blocks=None, **kw):
        assert live_blocks is not None
        dead = jnp.arange(xp.shape[0]) >= jnp.maximum(live_blocks, 1) * _BM
        return real(jnp.where(dead[:, None], jnp.nan, xp), w, block_gid,
                    live_blocks, **kw)

    monkeypatch.setattr(moe_dispatch, "grouped_matmul", poisoned)
    return poisoned


@pytest.mark.parametrize("layer", ["held_ffn", "moe_ffn_sorted",
                                   "no_pair_held"])
def test_nothing_live_reads_a_dead_row(routed8, monkeypatch, layer):
    """(b) The dead rows of both matmuls' inputs poisoned with NaN: the
    layer's result is finite and bit for bit the unpoisoned one. Also
    where NO pair is held, so that every absent pair's ``dest`` 0 reads a
    block no group owns: row block 0 is computed all the same."""
    from paddle_tpu.incubate.nn.pallas import moe_dispatch

    x, probs, select, w1, w2, K = routed8
    S = x.shape[0]
    if layer == "no_pair_held":
        # expert 7 is nobody's choice of three
        probs = probs.at[:, 7].set(0.0)
        select, first, held = probs, 7, 1
    else:
        first, held = 2, 3

    def run(gmm):
        if layer == "moe_ffn_sorted":
            return np.asarray(moe_ffn_sorted(x, probs, w1, w2, k=K,
                                             impl="pallas", interpret=True))
        d = sort_dispatch(x, probs, K, select=select, first=first,
                          held=held)
        assert (int(d["live_blocks"]) == 0) == (layer == "no_pair_held")
        return _held_ffn(d, w1, w2, first, held, S, K, "pallas", gmm)

    want = run(moe_dispatch.grouped_matmul)
    got = run(_poisoned(monkeypatch))
    assert np.isfinite(got).all() and (got == want).all()
    if layer == "no_pair_held":
        assert not got.any()
    else:
        assert np.abs(got).max() > 0


@pytest.mark.parametrize("live, blocks, cols", [
    (32, 67, 8), (64, 81, 4), (1, 5, 2), (5, 5, 3), (3, 9, 1)])
def test_index_maps_stand_still_after_the_last_live_step(live, blocks,
                                                         cols):
    """(c) The three index maps over the whole grid, row block outermost:
    a live step names its own blocks; after the last live step every index
    is constant (no block changes, so the pipeline fetches and writes
    nothing); the weight tiles moved are the live steps' alone."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import _gmm_index_maps

    rng = np.random.RandomState(live)
    gid = np.sort(rng.randint(0, 7, blocks)).astype(np.int32)
    gid[live:] = 6                                  # clipped to the last
    x_map, w_map, w_nk_map, o_map = _gmm_index_maps(cols)
    lv = np.asarray([live], np.int32)
    steps = [(i, j) for i in range(blocks) for j in range(cols)]
    seen = [tuple(tuple(int(v) for v in m(i, j, gid, lv))
                  for m in (x_map, w_map, o_map)) for i, j in steps]
    # an [E, N, K] stack's map names the same tile, its axes swapped
    assert all(tuple(int(v) for v in w_nk_map(i, j, gid, lv))
               == (w[0], w[2], w[1]) for (i, j), (_, w, _) in zip(steps,
                                                                   seen))
    for (i, j), (xi, wi, oi) in zip(steps[:live * cols], seen):
        assert (xi, wi, oi) == ((i, 0), (gid[i], 0, j), (i, j))
    assert set(seen[live * cols:]) <= {seen[live * cols - 1]}

    def fetches(k):      # a block is moved when its index changes
        return 1 + sum(a[k] != b[k] for a, b in zip(seen, seen[1:]))

    assert fetches(0) == live                       # x: once a row block
    assert fetches(2) == live * cols                # every live output
    # the weights: a tile a live step (with one column block an expert's
    # consecutive row blocks share it), where the call without the count
    # moves one for every step of the grid
    assert fetches(1) == (live * cols if cols > 1
                          else len(set(gid[:live])))
    if cols > 1:
        every = np.asarray([blocks], np.int32)
        assert 1 + sum(w_map(*a, gid, every) != w_map(*b, gid, every)
                       for a, b in zip(steps, steps[1:])) == blocks * cols
