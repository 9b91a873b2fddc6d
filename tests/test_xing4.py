"""Xing4.0 (models/xing4.py: latent attention, sigmoid-routed experts, mHC
streams) against its plain float32 reference
(benchmark/references/xing4.py), through every cache form of the decode
adapter, ``generate()`` and ``ServingEngine``: tiny sizes, float32, CPU.
Hidden 64, 4 heads, a latent of 32 + 8, 1 dense + 2 expert layers of 8
experts top-2 + 1 shared, 4 streams, pages of 16 tokens."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
from lib import build, runner  # noqa: E402

ref = runner.load_module("references", "xing4")
paged = importlib.import_module(
    "paddle_tpu.incubate.nn.pallas.paged_attention")

L = 3
KNOBS = dict(max_slots=2, block_size=16, num_blocks=24, prefill_chunk=16,
             max_seq_len=128)
# float32 everywhere: what separates the adapter from the reference is
# the order of the sums (absorbed against expanded attention, grouped
# against per-expert matmuls)
TOL = 2e-4


def _build(seed=3, **kw):
    """A tiny model whose norm gains are not the initial ones: a norm in
    the wrong place, or the wrong norm, has to show."""
    pt.seed(seed)
    model = pt.models.Xing4ForCausalLM(pt.models.xing4_tiny(**kw))
    model.eval()
    rng = np.random.RandomState(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            p.set_value(rng.uniform(0.5, 1.5, p.shape).astype("float32"))
    return model


def _params(model):
    return {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def model():
    return _build()


def _prompts(model, lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, model.config.vocab_size, n).tolist()
            for n in lens]


def _drain(eng):
    steps = 0
    while eng.step():
        steps += 1
        assert steps < 2000
    return steps


def _generate(model, prompt, n):
    return model.generate(pt.to_tensor(np.asarray([prompt], np.int64)),
                          max_new_tokens=n).numpy()[0].tolist()


def _ref_logits(model, ids):
    return np.asarray(ref.logits(_params(model), np.asarray(ids),
                                 model.config.published()))


def _shortfall(model, prompt, out):
    """How far under the reference's best logit the stream's tokens lie,
    teacher-forced: 0 when every token is the reference's argmax."""
    ids = np.zeros((1, 64), np.int32)    # one shape, one compile: causal
    ids[0, :len(prompt) + len(out)] = prompt + out
    rows = _ref_logits(model, ids)[0][len(prompt) - 1:
                                      len(prompt) - 1 + len(out)]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("kw", [{}, {"first_k_dense_replace": 0},
                                {"hc_mult": 2}, {"norm_topk_prob": False},
                                {"tie_word_embeddings": True}],
                         ids=["published", "all_experts", "two_streams",
                              "raw_scores", "tied"])
def test_prefill_logits_equal_the_reference(kw):
    """The adapter's prefill (absorbed attention, sorted grouped experts)
    against the reference (expanded attention, an expert at a time)."""
    m = _build(seed=7, **kw)
    ids = np.random.RandomState(1).randint(0, m.config.vocab_size, (2, 40))
    got = m(pt.to_tensor(ids)).numpy()
    assert np.abs(got - _ref_logits(m, ids)).max() < TOL


def test_step_and_chunk_step_equal_the_reference(model):
    """``prefill`` + ``step`` token by token, and ``chunk_step`` four
    tokens at a time at a row's own positions, on dense latent caches."""
    ad = model.decode_adapter()
    w = ad.weights
    ids = np.random.RandomState(2).randint(0, 512, (2, 30))
    want = _ref_logits(model, ids)
    x, ck, cv = jax.jit(lambda w, i: ad.prefill(w, i, 32))(
        w, jnp.asarray(ids[:, :20]))
    assert cv == () and len(ck) == L
    assert all(c.shape == (2, 32, ad.latent_dim) for c in ck)
    got = [np.asarray(ad.logits(w, x[:, -1]))]
    step = jax.jit(lambda w, tok, t, ck: ad.step(
        w, tok, t, ck, (), jnp.arange(32) <= t))
    for t in range(20, 24):
        lg, ck, cv = step(w, jnp.asarray(ids[:, t]), t, ck)
        got.append(np.asarray(lg))
    assert np.abs(np.stack(got, 1) - want[:, 19:24]).max() < TOL
    pos = jnp.asarray(np.arange(24, 28)[None].repeat(2, 0))
    lg, ck, cv = jax.jit(ad.chunk_step)(w, jnp.asarray(ids[:, 24:28]), pos,
                                        ck, cv)
    assert np.abs(np.asarray(lg) - want[:, 24:28]).max() < TOL


def test_absorbed_attention_equals_expanded():
    """What the latent contract rests on: scores and values read from the
    576-style latent with the key expansion folded into the query equal
    per-head keys and values expanded from it."""
    rng = np.random.RandomState(0)
    s, nh, rank, dn, dr, dv = 9, 4, 32, 16, 8, 16
    q_nope, q_rope = rng.randn(s, nh, dn), rng.randn(s, nh, dr)
    c, k_rope = rng.randn(s, rank), rng.randn(s, dr)
    kvb = rng.randn(rank, nh, dn + dv)
    causal = np.tril(np.ones((s, s), bool))

    def soft(sc):
        sc = np.where(causal, sc, -np.inf)
        e = np.exp(sc - sc.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    k = np.einsum("sc,chd->shd", c, kvb[..., :dn])
    v = np.einsum("sc,chd->shd", c, kvb[..., dn:])
    sc = np.einsum("qhd,khd->hqk", q_nope, k) \
        + np.einsum("qhd,kd->hqk", q_rope, k_rope)
    expanded = np.einsum("hqk,khd->qhd", soft(sc * 0.1), v)
    q_abs = np.einsum("qhd,chd->qhc", q_nope, kvb[..., :dn])
    lat = np.concatenate([c, k_rope], -1)
    sc = np.einsum("qhd,kd->hqk", np.concatenate([q_abs, q_rope], -1), lat)
    o = np.einsum("hqk,kc->qhc", soft(sc * 0.1), lat[:, :rank])
    absorbed = np.einsum("qhc,chd->qhd", o, kvb[..., dn:])
    assert np.abs(absorbed - expanded).max() < 1e-9


def test_sinkhorn_maps_are_doubly_stochastic_and_differ_by_token():
    model = _build(hc_sinkhorn_iters=20)          # the published count
    ad = model.decode_adapter()
    H = ad.weights["layers"][1]["hc_mlp"]
    X = jnp.asarray(np.random.RandomState(3).randn(11, 4, 64), jnp.float32)
    pre, post, res = (np.asarray(a) for a in ad.hc_maps(H, X))
    eps = model.config.hc_eps
    # rows are normalised last: 1 within hc_eps and float32's rounding;
    # columns as far as 20 iterations bring a random map
    assert np.abs(res.sum(-1) - 1).max() < 10 * eps
    assert np.abs(res.sum(-2) - 1).max() < 1e-2
    assert (res > 0).all() and (0 < pre).all() and (pre < 1).all() \
        and (0 < post).all() and (post < 2).all()
    # the maps are made from the streams: another token, another map
    for m in (pre, post, res.reshape(11, -1)):
        assert np.abs(m[0] - m[1]).max() > 1e-3
    # and equal the reference's
    hc = dict(n=4, iters=model.config.hc_sinkhorn_iters, eps=eps,
              lo=-30.0, hi=30.0)
    want = ref.hc_maps(X, H["phi"], H["bias"], H["alpha"], **hc)
    for a, b in zip((pre, post, res), want):
        assert np.abs(a - np.asarray(b)).max() < 1e-5


def test_routing_drops_no_token_and_grouped_equals_per_token(model):
    """Every (token, chosen expert) pair lands in the grouped matmuls'
    rows, however uneven the routing, and the sorted grouped path equals
    a loop over each token's chosen experts."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import (dispatch_rows,
                                                            sort_dispatch)

    ad = model.decode_adapter()
    W = ad.weights["layers"][2]
    h = jnp.asarray(np.random.RandomState(4).randn(37, 64), jnp.float32)
    got = np.asarray(ad.moe(W, h))
    s, sel = ad.route(W, h)
    _, top_e = jax.lax.top_k(sel, 2)
    wts = np.take_along_axis(np.asarray(s), np.asarray(top_e), 1)
    wts = wts / wts.sum(1, keepdims=True) \
        * model.config.routed_scaling_factor
    want = np.array(ad._swiglu(W["shared"], h))
    hn, gate_up, down = (np.asarray(a) for a in (h, W["gate_up"], W["down"]))
    for t in range(37):                  # a token at a time, no sorting
        for e, wt in zip(np.asarray(top_e)[t], wts[t]):
            g, u = np.split(hn[t] @ gate_up[e], 2)
            want[t] += wt * ((g / (1 + np.exp(-g)) * u) @ down[e])
    assert np.abs(got - want).max() < 1e-5
    top, wt = ref.route(h, {"mlp.gate_weight": W["router_w"],
                            "mlp.e_score_correction_bias": W["router_b"]},
                        model.config.published())
    assert (np.sort(np.asarray(jax.lax.top_k(sel, 2)[1]), 1)
            == np.sort(top, 1)).all()
    assert np.allclose(wt.sum(1), model.config.routed_scaling_factor)
    # uneven on purpose: every token wants expert 0 and one other
    skew = jnp.asarray(s).at[:, 0].set(0.99)
    d = sort_dispatch(h, skew, 2, select=skew)
    assert int(d["group_sizes"].sum()) == 37 * 2
    assert int(d["group_sizes"][0]) == 37
    assert d["xp"].shape[0] == dispatch_rows(37, 2, 8)
    assert len(np.unique(np.asarray(d["dest"]))) == 37 * 2
    rows = np.asarray(d["xp"])[np.asarray(d["dest"])]
    assert np.abs(rows - np.repeat(np.asarray(h), 2, 0)).max() == 0


def test_configuration_file_is_the_catalog_row():
    """``benchmark/configs/xing4-29b-a4b-l6.json`` against the published
    ``config.json`` (XingChen-AGI/Xing4.0-29B-A4B), written out here:
    every key not in ``reduced`` equal."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584,
        "intermediate_size": 9216, "kv_lora_rank": 512,
        "max_position_embeddings": 262144, "model_type": "xing4_0",
        "moe_intermediate_size": 1024, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 4, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-06,
        "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
        "q_lora_rank": 768, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "xing4-29b-a4b-l6.json")) as f:
        cfg = json.load(f)
    reduced = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
               "num_nextn_predict_layers": 0}
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published} == dict(published, **reduced)
    assert cfg["dtype"] == "bfloat16" and cfg["source"].endswith(
        "XingChen-AGI/Xing4.0-29B-A4B/blob/main/config.json")
    for k in ("depth", "mtp", "streams", "mhc", "mhc_init", "attention",
              "kv_cache", "experts", "router_init", "max_seq_len",
              "weights", "expert_init", "query_init", "control"):
        assert cfg["assumed"][k]
    # what the program's config class is given says the same
    arch = {k: cfg[k] for k in build.ARCH_KEYS if k in cfg}
    c = pt.models.Xing4Config(**dict(arch, **cfg["model_kwargs"]))
    want = dict(published, **reduced)
    for k, v in c.published().items():
        assert v == want[k] or (k == "rope_scaling" and all(
            v[j] == want[k][j] for j in v)), k
    assert c.n_shared_experts == 1 and c.intermediate_size == 9216
    assert c.latent_dim == 576
    assert c.control_operand_dtype is None    # the margin's control only
    # the cut's arithmetic: parameters by hand
    attn = 3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584
    hc = 2 * (4 * 3584 * 24)
    expert = 3 * 3584 * 1024
    n = 2 * 131072 * 3584 + (attn + hc + 3 * 3584 * 9216) \
        + 5 * (attn + hc + 65 * expert + 3584 * 64)
    assert round(n / 1e6, 1) == 4792.6
    assert cfg["engine"] == {"max_slots": 24, "block_size": 128,
                             "prefill_chunk": 512, "num_blocks":
                             cfg["engine"]["num_blocks"],
                             "max_seq_len": 16384}


def test_control_rounds_the_weights_the_program_reads():
    """``control_operand_dtype`` (the margin's lower-precision control, no
    cell's): the same parameters, read through float8, move the logits
    away from the reference's, which reads them whole."""
    ids = np.random.RandomState(1).randint(0, 512, (1, 24))
    diff = {}
    for ctl in (None, "float8_e5m2"):
        m = _build(seed=7, control_operand_dtype=ctl)
        diff[ctl] = np.abs(m(pt.to_tensor(ids)).numpy()
                           - _ref_logits(m, ids)).max()
    assert diff[None] < TOL and diff["float8_e5m2"] > 100 * TOL


# ----------------------------------------------------- generate()/engine
def test_generate_follows_the_reference(model):
    p = _prompts(model, (33,))[0]
    assert _shortfall(model, p, _generate(model, p, 8)) < 1e-3


def test_engine_streams_follow_the_reference_and_generate(model):
    """Mixed prompts over several prefill chunks and pages, fewer slots
    than requests: every streamed token is the reference's argmax, and
    the stream is ``generate()``'s token for token."""
    prompts = _prompts(model, (5, 37, 50, 20, 3))
    eng = pt.serving.ServingEngine(model, **KNOBS)
    # one latent pool a cache layer where K pools are; no V pools
    assert len(eng._kp) == L and eng._vp == ()
    assert all(p.shape == (1, KNOBS["num_blocks"], 16, 128)
               for p in eng._kp)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drain(eng)
    outs = [eng.result(r) for r in rids]
    assert eng.ragged_compiles == 1
    for p, o in zip(prompts, outs):
        assert len(o) == 12
        assert _shortfall(model, p, o) < 1e-3
    for k in (1, 4):
        assert outs[k] == _generate(model, prompts[k], 12)
    eng.shutdown()


@pytest.mark.parametrize("kernel", [False, True], ids=["xla", "pallas"])
def test_ragged_chunk_logits_equal_the_reference(kernel, model,
                                                 monkeypatch):
    """One ragged step of two prefill rows over two pages each on paged
    latent pools: logits of every token against the reference's full
    forward pass; with the Pallas kernels (interpreted) as with the XLA
    composition and the scatter."""
    if kernel:
        monkeypatch.setattr(paged, "latent_impl", lambda *a: "pallas")
    ad = model.decode_adapter()
    bs, pages = 16, 6
    assert (ad.kv_layout, ad.cache_layers, ad.num_kv_heads) == \
        ("latent", L, 1)
    assert (ad.latent_dim, ad.latent_value_dim, ad.head_dim) == (40, 32, 40)
    kp = tuple(jnp.zeros((1, pages, bs, paged.latent_pool_dim(40)))
               for _ in range(L))
    prompts = _prompts(model, (21, 30), seed=4)
    toks = np.zeros(56, np.int32)
    pos = np.full(56, -1, np.int32)
    row_of = np.full(56, -1, np.int32)
    toks[:21], toks[21:51] = prompts
    pos[:21], pos[21:51] = np.arange(21), np.arange(30)
    row_of[:21], row_of[21:51] = 0, 1
    bt = np.asarray([[4, 1, 0], [2, 5, 0]], np.int32)
    lg, kp2, vp2 = jax.jit(ad.ragged_chunk)(
        ad.weights, *(jnp.asarray(a) for a in (
            toks, pos, row_of, [0, 21], [21, 30], [21, 30])),
        kp, (), jnp.asarray(bt))
    for lo, p in zip((0, 21), prompts):
        want = _ref_logits(model, [p])[0]
        assert np.abs(np.asarray(lg[lo:lo + len(p)]) - want).max() < 1e-3
    assert len(kp2) == L and vp2 == ()
    for pool in kp2:
        pool = np.asarray(pool)
        assert np.abs(pool[0, 4, :, :40]).max() > 0
        assert np.abs(pool[0, 3]).max() == 0          # nobody's page
        assert np.abs(pool[..., 40:]).max() == 0      # the row's padding


def test_preemption_and_prefix_hit_reproduce_the_tokens(model):
    prompts = _prompts(model, (6, 6), seed=3)
    refs = [_generate(model, p, 30) for p in prompts]
    eng = pt.serving.ServingEngine(
        model, max_slots=2, block_size=16, num_blocks=4, prefill_chunk=16,
        max_seq_len=64, enable_prefix_cache=False, watermark=0.0)
    rids = [eng.submit(p, max_new_tokens=30) for p in prompts]
    _drain(eng)
    assert [eng.result(r) for r in rids] == refs
    assert eng.scheduler.preemptions >= 1 and eng.ragged_compiles == 1
    eng.shutdown()

    prompt = _prompts(model, (37,), seed=5)[0]
    want = _generate(model, prompt, 5)
    eng = pt.serving.ServingEngine(model, **KNOBS)
    r1 = eng.submit(prompt, max_new_tokens=5)
    _drain(eng)
    r2 = eng.submit(prompt, max_new_tokens=5)
    req2 = eng._requests[r2]
    _drain(eng)
    assert eng.result(r1) == want and eng.result(r2) == want
    assert req2.num_cached == 32          # two latent pages a layer
    eng.shutdown()


def test_handoff_and_prefix_transfer_carry_the_latent_pools(model):
    """Hand-off, prefix export and import move latent pages through the
    same codec: a tuple of one pool a cache layer, and no V side."""
    prompt = _prompts(model, (37,), seed=6)[0]
    want = _generate(model, prompt, 6)
    src = pt.serving.ServingEngine(model, **KNOBS)
    dst = pt.serving.ServingEngine(model, **KNOBS)
    src.submit(prompt, max_new_tokens=6, handoff=True)
    _drain(src)
    pay = src.take_handoff()
    assert len(pay.k_pages) == L and pay.v_pages == ()
    assert all(p.shape == (1, 3, 16, 128) for p in pay.k_pages)
    assert pay.nbytes() == L * 3 * 16 * 128 * 4
    rid = dst.adopt_handoff(pay)
    _drain(dst)
    assert [pay.first_token] + dst.result(rid) == want

    k, v, n = src.export_prefix(prompt)
    assert n == 2 and len(k) == L and v == () and k[0].shape[1] == 2
    third = pt.serving.ServingEngine(model, **KNOBS)
    assert third.import_prefix(prompt, n, k, v) == 32
    rid = third.submit(prompt, max_new_tokens=6)
    req = third._requests[rid]
    _drain(third)
    assert third.result(rid) == want and req.num_cached == 32
    for e in (src, dst, third):
        e.shutdown()


def test_int8_latent_pages_are_refused(model):
    with pytest.raises(ValueError, match="latent KV pool has no int8"):
        pt.serving.ServingEngine(model, kv_quant="int8", **KNOBS)
    with pytest.raises(ValueError, match="latent cache has no int8"):
        model.decode_adapter().prefill(None, jnp.zeros((1, 4), jnp.int32),
                                       8, kv_quant=True)


def test_step_span_and_counters_say_what_ran(model):
    """Launch side: what the step is made of. Collect side: the two
    counts the step returns behind its tokens, every routed pair (all 8
    experts are held) and the row blocks that held one. Telemetry off
    records none of it and emits the same tokens."""
    obs = pt.observability
    eng = pt.serving.ServingEngine(model, **KNOBS)
    assert eng._no_tokens.shape == (KNOBS["max_slots"] + 2,)
    eng.warmup()
    prompts = _prompts(model, (20, 7))
    obs.enable()
    try:
        obs.registry.reset()
        obs.tracing.reset()      # an earlier file's spans are not this run's
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        _drain(eng)
        done = obs.tracing.finished_spans()
        snap = obs.registry.snapshot()["counters"]
    finally:
        obs.disable()
    spans = [s for s in done if s.name == "serving.ragged_step"]
    waits = [s for s in done if s.name == "serving.device_wait"]
    outs = [eng.result(r) for r in rids]
    assert [len(o) for o in outs] == [5, 5]
    obs.registry.reset()
    obs.tracing.reset()
    quiet = [eng.submit(p, max_new_tokens=5) for p in prompts]
    _drain(eng)
    assert not obs.tracing.finished_spans()
    assert not obs.registry.snapshot()["counters"]
    assert outs == [eng.result(r) for r in quiet]
    assert eng.ragged_compiles == 1 and len(waits) == len(spans)
    # dispatch_rows(18, 2, 8) / 128 = 9 blocks a layer; an expert gets at
    # most a pair a row of the budget's 18, so one block
    for s, w in zip(spans, waits):
        a = w.args
        assert a["moe_pairs_held"] == a["moe_pairs_routed"] \
            == s.args["tokens"] * 2 * 2
        assert a["moe_blocks"] == s.args["moe_blocks"] == 2 * 9
        assert 2 <= a["moe_blocks_live"] <= 2 * 8
    assert snap["serving.moe_blocks_skipped"] == sum(
        w.args["moe_blocks"] - w.args["moe_blocks_live"] for w in waits)
    assert snap["serving.moe_pairs_held"] == sum(s.args["moe_pairs"]
                                                 for s in spans)
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import dispatch_rows
    for s in spans:
        a = s.args
        assert (a["kv_layout"], a["latent_dim"], a["hc_streams"]) == \
            ("latent", 40, 4)
        assert (a["experts"], a["experts_per_token"], a["moe_layers"]) == \
            (8, 2, 2)
        assert a["moe_pairs"] == a["tokens"] * 2 * 2
        assert a["moe_rows"] == 2 * dispatch_rows(18, 2, 8)
        assert (a["passes"], a["cache_layers"]) == (1, L)
    # the first step packs the budget's 18 tokens of one prompt, at
    # positions 0..17: token j sees j + 1 keys
    assert spans[0].args["attn_pairs"] == 18 * 19 // 2
    assert spans[0].args["live_pages"] == 2
    # the pairs and the latent pages read are the span's own attributes
    # (live_pages in each of cache_layers pools): no counter repeats them
    assert sum(s.args["moe_pairs"] for s in spans) \
        == 2 * 2 * int(snap["serving.decode_tokens"]
                       + snap["serving.prefill_tokens"])
    assert all(s.args["live_pages"] >= 1 and s.args["cache_layers"] == L
               for s in spans)
    assert not {"serving.moe_pairs", "serving.latent_pages_read"} & set(snap)
    eng.shutdown()


# ------------------------------------------------- the benchmark's readers
def _cell(seconds=40.0):
    class Cell:
        pass
    c = Cell()
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "xing4-29b-a4b-l6.json")) as f:
        c.config = json.load(f)
    c.traffic, c.seconds = {"traced_s": 2.0}, seconds
    c.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    c.log = lambda msg: None
    return c


NEW_METRICS = ("kernels.latent_attn_roofline.serve",
               "kernels.latent_attn_share.serve",
               "kernels.moe_gmm_roofline.serve",
               "kernels.moe_gmm_share.serve", "serving_engine.moe_pad_share")


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metric_readers_read_their_kernels_and_nothing_else(metric):
    """Each reader finds its kernel in a reduced trace by its operands and
    its counts on the step's span; a program without them (the parent, a
    per-head model) gives ``None``, never an error."""
    from lib import xplane

    read = runner.load_module("layer_metrics", metric).read
    cell = _cell()
    for empty in ({}, {"trace": None, "spans": []},
                  {"trace": {"chips": {}}, "spans": []}):
        assert read(empty, cell) is None
    # a per-head model's trace and span: its kernels are not these
    kv = xplane.parse_hlo(
        "%c.1 = bf16[16,304,128]{2,1,0} custom-call(s32[48,16]{1,0} %bt, "
        "s32[48]{0} %cl, bf16[16,304,128]{2,1,0} %q, "
        "bf16[16,240,128,128]{3,2,1,0} %k, bf16[16,240,128,128]{3,2,1,0} "
        '%v), custom_call_target="tpu_custom_call"')
    span = {"name": "serving.ragged_step", "ts": 21e6, "dur": 100.0,
            "args": {"rows": 48, "tokens": 304, "live_pages": 400,
                     "cache_layers": 24, "passes": 1, "weight_bytes": 1,
                     "kv_layout": "kv"}}
    other = {"trace": {"chips": {"/device:TPU:0": {
        "busy_s": 1.0, "window_s": 2.0,
        "ops": [dict(kv, seconds=0.5, count=24)]}}}, "spans": [span]}
    assert read(other, cell) is None
    # the Xing4.0 step: 6 attention calls, 6 writes, 10 grouped matmuls
    attn = xplane.parse_hlo(
        "%c.2 = bf16[17408,512]{1,0} custom-call(s32[7458]{0} %vis, "
        "s32[1]{0} %n, s32[24,128]{1,0} %bt, s32[24]{0} %cl, s32[24]{0} "
        "%ql, s32[24]{0} %qs, bf16[17408,640]{1,0} %q, "
        'bf16[1,2048,128,640]{3,2,1,0} %pool), '
        'custom_call_target="tpu_custom_call"')
    write = xplane.parse_hlo(
        "%c.3 = bf16[1,2048,128,640]{3,2,1,0} custom-call(s32[536]{0} "
        "%vt, s32[8576]{0} %vk, s32[536]{0} %vb, f32[1,536,640]{2,1,0} "
        "%rows, bf16[1,2048,128,640]{3,2,1,0} %pool), "
        'custom_call_target="tpu_custom_call"')
    gmm = xplane.parse_hlo(
        "%c.4 = bf16[10368,2048]{1,0} custom-call(s32[81]{0} %gid, "
        "s32[1]{0} %live, bf16[10368,3584]{1,0} %xp, bf16[64,3584,2048]{2,1,0} %w), "
        'custom_call_target="tpu_custom_call"')
    args = dict(span["args"], kv_layout="latent", latent_dim=576,
                live_pages=1400, cache_layers=6, tokens=536,
                attn_pairs=2_300_000, experts=64, experts_per_token=4,
                moe_layers=5, moe_pairs=536 * 4 * 5, moe_rows=5 * 10368,
                hc_streams=4)
    rec = {"trace": {"chips": {"/device:TPU:0": {
        "busy_s": 0.040, "window_s": 0.050, "ops": [
            dict(attn, seconds=0.018, count=6),
            dict(write, seconds=0.001, count=6),
            dict(gmm, seconds=0.011, count=10)]}}},
        "spans": [dict(span, args=args)]}
    got = read(rec, cell)
    want = {
        # 1,400 pages x 128 x 576 x 2 B / 819e9 = 0.252 ms against
        # 2.3 M pairs x 2 x 32 x 320 / 197e12 = 0.239 ms; 3 ms a call
        "kernels.latent_attn_roofline.serve": 100 * 0.0002521 / 0.003,
        "kernels.latent_attn_share.serve": 45.0,
        # 64 experts x 22 MB + 2,144 pairs' rows, 1.76 ms; 2.2 ms a layer
        "kernels.moe_gmm_roofline.serve": 100 * 0.0017559 / 0.0022,
        "kernels.moe_gmm_share.serve": 27.5,
        "serving_engine.moe_pad_share": 100 * (1 - 2144 / 10368)}[metric]
    assert abs(got - want) < 0.02 * want
    assert got < 100
