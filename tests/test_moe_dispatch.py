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


def _held_ffn(d, w1, w2, first, held, s, k, impl):
    """The combine of a share: the grouped matmuls over the HELD stacks."""
    if held == 0:                  # no stack to run: every weight is 0
        assert not np.asarray(d["weight"]).any()
        return np.zeros((s, w2.shape[-1]), np.float32)
    kw = dict(impl=impl, interpret=True if impl == "pallas" else None)
    g, u = jnp.split(grouped_matmul(d["xp"], w1[first:first + held],
                                    d["block_gid"], **kw), 2, axis=-1)
    y = grouped_matmul(jax.nn.silu(g) * u, w2[first:first + held],
                       d["block_gid"], **kw)
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
