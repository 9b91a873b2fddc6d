"""sarvam-105b (models/sarvam.py: latent attention with a full-rank,
per-head normed query; sigmoid-routed experts of which a model may hold a
SHARE) against its plain float32 reference
(benchmark/references/sarvam.py), through every cache form of the decode
adapter, ``generate()`` and ``ServingEngine``: tiny sizes, float32, CPU.
Hidden 64, 4 heads, a latent of 32 + 8, 1 dense + 2 expert layers of 8
routed experts top-3 + 1 shared, pages of 16 tokens. ``SHARE`` holds
experts [2, 4) of the 8: one chip of a four-way expert-parallel layer."""
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

ref = runner.load_module("references", "sarvam")
paged = importlib.import_module(
    "paddle_tpu.incubate.nn.pallas.paged_attention")

L, K = 3, 3
SHARE = dict(num_experts_held=2, expert_first=2)
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
    model = pt.models.SarvamForCausalLM(pt.models.sarvam_tiny(**kw))
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
    """The chip's share: 2 of 8 routed experts held."""
    return _build(**SHARE)


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
@pytest.mark.parametrize("kw", [
    {}, SHARE, {"num_experts_held": 5, "expert_first": 3},
    {"first_k_dense_replace": 0, **SHARE}, {"use_qk_norm": False},
    {"norm_topk_prob": False, **SHARE}, {"tie_word_embeddings": True}],
    ids=["every_expert", "share", "uneven_share", "all_expert_layers",
         "no_qk_norm", "raw_scores", "tied"])
def test_prefill_logits_equal_the_reference(kw):
    """The adapter's prefill (absorbed attention, sorted grouped experts
    over the held stacks) against the reference (expanded attention, a
    held expert at a time)."""
    m = _build(seed=7, **kw)
    ids = np.random.RandomState(1).randint(0, m.config.vocab_size, (2, 40))
    got = m(pt.to_tensor(ids)).numpy()
    assert np.abs(got - _ref_logits(m, ids)).max() < TOL


def test_the_share_is_data_not_a_mode():
    """``num_experts_held == num_experts`` is the whole layer, and the
    default; a range outside the routed experts, or an empty one, is
    refused."""
    whole = pt.models.SarvamMLAConfig(num_experts=128, num_experts_held=128,
                                      expert_first=0)
    assert whole == pt.models.SarvamMLAConfig()
    assert whole.published()["head_dim"] == 576
    for bad in (dict(num_experts_held=0), dict(num_experts_held=4,
                                               expert_first=6),
                dict(expert_first=-1)):
        with pytest.raises(ValueError, match="held experts"):
            pt.models.sarvam_tiny(**bad)
    m = _build(**SHARE)
    blk = m.model.layers[1].mlp
    assert tuple(blk.experts_gate_up.shape) == (2, 64, 64)
    assert tuple(blk.experts_down.shape) == (2, 32, 64)
    assert tuple(blk.gate_weight.shape) == (64, 8)      # the router: whole
    ad = m.decode_adapter()
    assert (ad.experts, ad.experts_routed, ad.expert_first,
            ad.experts_per_token, ad.moe_layers) == (2, 8, 2, K, 2)


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


def test_absorbed_attention_equals_expanded_at_64_heads():
    """What the latent contract rests on, at the published head shapes
    (64 heads of 128 + 64 query dims, values of 128, a latent of 512):
    scores and values read from the 576-wide latent with the key
    expansion folded into the query equal per-head keys and values
    expanded from it."""
    rng = np.random.RandomState(0)
    s, nh, rank, dn, dr, dv = 7, 64, 512, 128, 64, 128
    q_nope, q_rope = rng.randn(s, nh, dn), rng.randn(s, nh, dr)
    c, k_rope = rng.randn(s, rank), rng.randn(s, dr)
    kvb = rng.randn(rank, nh, dn + dv) * rank ** -0.5
    causal = np.tril(np.ones((s, s), bool))
    scale = (dn + dr) ** -0.5

    def soft(sc):
        sc = np.where(causal, sc, -np.inf)
        e = np.exp(sc - sc.max(-1, keepdims=True))
        return e / e.sum(-1, keepdims=True)

    k = np.einsum("sc,chd->shd", c, kvb[..., :dn])
    v = np.einsum("sc,chd->shd", c, kvb[..., dn:])
    sc = np.einsum("qhd,khd->hqk", q_nope, k) \
        + np.einsum("qhd,kd->hqk", q_rope, k_rope)
    expanded = np.einsum("hqk,khd->qhd", soft(sc * scale), v)
    q_abs = np.einsum("qhd,chd->qhc", q_nope, kvb[..., :dn])
    lat = np.concatenate([c, k_rope], -1)
    assert lat.shape[-1] == 576
    sc = np.einsum("qhd,kd->hqk", np.concatenate([q_abs, q_rope], -1), lat)
    o = np.einsum("hqk,kc->qhc", soft(sc * scale), lat[:, :rank])
    absorbed = np.einsum("qhc,chd->qhd", o, kvb[..., dn:])
    assert np.abs(absorbed - expanded).max() < 1e-9


def _layer_params(model, i):
    pre = "model.layers.%d." % i
    return {k[len(pre):]: v for k, v in _params(model).items()
            if k.startswith(pre)}


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut: the routed parts that the four shares
    [0, 2) .. [6, 8) of one expert layer give, plus the shared expert
    counted once, equal what the uncut reference gives for the whole
    layer; and no single share does."""
    whole = _build(seed=11)
    h = jnp.asarray(np.random.RandomState(4).randn(37, 64), jnp.float32)
    want = np.asarray(ref.experts(h, _layer_params(whole, 2),
                                  whole.config.published()))
    total, shared = np.zeros_like(want), None
    for j in range(4):
        part = _build(seed=11, num_experts_held=2, expert_first=2 * j)
        for (n, p), (_, q) in zip(part.named_parameters(),
                                  whole.named_parameters()):
            v = np.asarray(q.value)
            p.set_value(v[2 * j:2 * j + 2] if "mlp.experts_" in n else v)
        ad = part.decode_adapter()
        W = ad.weights["layers"][2]
        shared = np.asarray(ad._swiglu(W["shared"], h))
        routed = np.asarray(ad.moe(W, h)) - shared
        # the reference, given the same share, says the same
        assert np.abs(routed - np.asarray(ref.routed(
            h, _layer_params(part, 2), part.config.published()))).max() \
            < 1e-7
        assert np.abs(routed).max() > 1e-3       # tiny widths, small sums
        total += routed
    assert np.abs(total + shared - want).max() < 1e-7
    assert np.abs(shared - want).max() > 1e-3


def test_routing_is_over_all_experts_and_only_held_pairs_run(model):
    """The router keeps its width and its k; every (token, chosen expert)
    pair whose expert is held lands in the grouped matmuls' rows, none of
    an absent expert does, and the sorted grouped path equals a loop over
    each token's chosen experts that skips the absent ones, with weights
    normalised over ALL the chosen."""
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import sort_dispatch

    ad = model.decode_adapter()
    W = ad.weights["layers"][2]
    h = jnp.asarray(np.random.RandomState(4).randn(37, 64), jnp.float32)
    got = np.asarray(ad.moe(W, h))
    s, sel = ad.route(W, h)
    assert s.shape == (37, 8)
    top_e = np.asarray(jax.lax.top_k(sel, K)[1])
    wts = np.take_along_axis(np.asarray(s), top_e, 1)
    wts = wts / wts.sum(1, keepdims=True) \
        * model.config.routed_scaling_factor
    want = np.array(ad._swiglu(W["shared"], h))
    hn, gate_up, down = (np.asarray(a) for a in (h, W["gate_up"], W["down"]))
    assert gate_up.shape[0] == 2
    held = 0
    for t in range(37):                  # a token at a time, no sorting
        for e, wt in zip(top_e[t], wts[t]):
            if 2 <= e < 4:
                g, u = np.split(hn[t] @ gate_up[e - 2], 2)
                want[t] += wt * ((g / (1 + np.exp(-g)) * u) @ down[e - 2])
                held += 1
    assert 0 < held < 37 * K
    assert np.abs(got - want).max() < 1e-7 < 1e-3 < np.abs(got).max()
    top, wt = ref.route(h, {"mlp.gate_weight": W["router_w"],
                            "mlp.e_score_correction_bias": W["router_b"]},
                        model.config.published())
    assert (np.sort(top_e, 1) == np.sort(top, 1)).all()
    assert np.allclose(wt.sum(1), model.config.routed_scaling_factor)
    d = sort_dispatch(h, s, K, select=sel, first=2, held=2)
    assert int(d["group_sizes"].sum()) == int(d["here"].sum()) == held


def test_configuration_file_is_the_catalog_row():
    """``benchmark/configs/sarvam-105b-l6-ep4.json`` against the published
    ``config.json`` (sarvamai/sarvam-105b; the catalog row of that name),
    written out here: every key not in ``reduced`` equal, each reduced
    key's published value stated beside it."""
    published = {
        "attn_implementation": None, "default_theta": 10000,
        "first_k_dense_replace": 1, "head_dim": 576, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 16384,
        "kv_lora_rank": 512, "max_position_embeddings": 131072,
        "model_type": "sarvam_mla", "moe_intermediate_size": 2048,
        "moe_router_enable_expert_bias": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_shared_experts": 1, "q_head_dim": 192,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "deepseek_yarn"},
        "rope_theta": 10000, "routed_scaling_factor": 2.5,
        "tie_word_embeddings": False, "use_qk_norm": True,
        "v_head_dim": 128, "vocab_size": 262144}
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "sarvam-105b-l6-ep4.json")) as f:
        cfg = json.load(f)
    reduced = {"num_hidden_layers": 6, "num_experts": 32,
               "vocab_size": 65536}
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published} == dict(published, **reduced)
    assert {k: cfg["published"][k] for k in reduced} == \
        {k: published[k] for k in reduced}
    assert "four-way expert-parallel" in cfg["stands_for"] \
        and "data-parallel attention" in cfg["stands_for"]
    assert cfg["dtype"] == "bfloat16" and cfg["source"].endswith(
        "sarvamai/sarvam-105b/blob/main/config.json")
    assert (cfg["n_routed_experts"], cfg["num_experts_held"],
            cfg["num_experts_routed"], cfg["expert_first"]) == \
        (32, 32, 128, 0)
    for k in ("depth", "share", "experts", "attention", "kv_cache",
              "router_init", "max_seq_len", "weights", "expert_init",
              "routed_init", "query_init", "control"):
        assert cfg["assumed"][k]
    # what the program's config class is given says the same
    arch = {k: cfg[k] for k in build.ARCH_KEYS if k in cfg}
    c = pt.models.SarvamMLAConfig(**dict(arch, **cfg["model_kwargs"]))
    want = dict(published, num_hidden_layers=6, vocab_size=65536)
    for k, v in c.published().items():
        if k in want:
            assert v == want[k], k
    assert (c.num_experts, c.num_experts_held, c.expert_first) == \
        (128, 32, 0)
    assert c.latent_dim == 576 and c.norm_topk_prob
    assert c.control_operand_dtype is None    # the margin's control only
    # the cut's arithmetic: parameters by hand, and the engine's pool
    attn = 4096 * 12288 + 4096 * 576 + 512 * 16384 + 8192 * 4096
    expert = 3 * 4096 * 2048
    n = 2 * 65536 * 4096 + (attn + 3 * 4096 * 16384) \
        + 5 * (attn + 33 * expert + 4096 * 128)
    assert round(n / 1e6) == 5461 and round(n * 2 / 2 ** 30, 2) == 10.17
    assert cfg["engine"] == {"max_slots": 48, "block_size": 128,
                             "prefill_chunk": 512, "num_blocks":
                             cfg["engine"]["num_blocks"],
                             "max_seq_len": 4736}
    with open(os.path.join(_ROOT, "benchmark", "traffic",
                           "reason_closed48.json")) as f:
        tf = json.load(f)
    assert tf["clients"] == 48 and len(tf["pairs"]) == 24
    assert all(o == round(0.3 * p) and 1024 <= p <= 3584
               for p, o in tf["pairs"])
    assert max(p + o for p, o in tf["pairs"]) \
        <= tf["reference"]["pad_to"] == cfg["engine"]["max_seq_len"]


def test_control_rounds_the_weights_the_program_reads():
    """``control_operand_dtype`` (the margin's lower-precision control, no
    cell's): the same parameters, read through float8, move the logits
    away from the reference's, which reads them whole."""
    ids = np.random.RandomState(1).randint(0, 512, (1, 24))
    diff = {}
    for ctl in (None, "float8_e5m2"):
        m = _build(seed=7, control_operand_dtype=ctl, **SHARE)
        diff[ctl] = np.abs(m(pt.to_tensor(ids)).numpy()
                           - _ref_logits(m, ids)).max()
    assert diff[None] < TOL and diff["float8_e5m2"] > 100 * TOL


def test_the_random_start_scales_what_it_says():
    """``routed_init_scale`` touches the routed experts' down projection
    alone, ``query_init_scale`` the per-head query norm's gain alone."""
    pt.seed(5)
    a = pt.models.SarvamForCausalLM(pt.models.sarvam_tiny(
        hidden_size=256, moe_intermediate_size=128))
    pt.seed(5)
    b = pt.models.SarvamForCausalLM(pt.models.sarvam_tiny(
        hidden_size=256, moe_intermediate_size=128, routed_init_scale=0.25,
        query_init_scale=1.5))
    for (n, p), (_, q) in zip(a.named_parameters(), b.named_parameters()):
        p, q = np.asarray(p.value), np.asarray(q.value)
        if n.endswith("mlp.experts_down"):
            assert np.allclose(q, 0.25 * p, atol=1e-7)
            assert abs(p.std() / 0.02 - 1) < 0.05
        elif n.endswith("self_attn.q_norm.weight"):
            assert (p == 1).all() and (q == 1.5).all()
        else:
            assert (p == q).all(), n


# ----------------------------------------------------- generate()/engine
def test_generate_follows_the_reference(model):
    p = _prompts(model, (33,))[0]
    assert _shortfall(model, p, _generate(model, p, 8)) < 1e-3


def test_engine_streams_follow_the_reference_and_generate(model):
    """Mixed prompts over several prefill chunks and pages, fewer slots
    than requests, one step in flight: every streamed token is the
    reference's argmax, and the stream is ``generate()``'s token for
    token. The model has expert layers, so every step's result carries
    its two counts (held pairs, live row blocks) behind the rows'
    tokens."""
    prompts = _prompts(model, (5, 37, 50, 20, 3))
    eng = pt.serving.ServingEngine(model, **KNOBS)
    # one latent pool a cache layer where K pools are; no V pools
    assert len(eng._kp) == L and eng._vp == ()
    assert all(p.shape == (1, KNOBS["num_blocks"], 16, 128)
               for p in eng._kp)
    assert eng._no_tokens.shape == (4,)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    eng.step()
    assert eng._flight is not None and eng._flight.nxt.shape == (4,)
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
    composition and the scatter. With a tally, the step also counts its
    live tokens' held pairs and its layouts' live row blocks, and
    computes the same logits."""
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
    args = [jnp.asarray(a) for a in (
        toks, pos, row_of, [0, 21], [21, 30], [21, 30])]
    lg, kp2, vp2 = jax.jit(ad.ragged_chunk)(
        ad.weights, *args, kp, (), jnp.asarray(bt))
    for lo, p in zip((0, 21), prompts):
        want = _ref_logits(model, [p])[0]
        assert np.abs(np.asarray(lg[lo:lo + len(p)]) - want).max() < 1e-3
    assert len(kp2) == L and vp2 == ()
    for pool in kp2:
        pool = np.asarray(pool)
        assert np.abs(pool[0, 4, :, :40]).max() > 0
        assert np.abs(pool[0, 3]).max() == 0          # nobody's page
        assert np.abs(pool[..., 40:]).max() == 0      # the row's padding

    def tallied(w, *a):
        tally = {}
        out = ad.ragged_chunk(w, *a, tally)
        return out[0], tally["moe_pairs_held"], tally["moe_blocks_live"]

    lg2, held, live = jax.jit(tallied)(ad.weights, *args, kp, (),
                                       jnp.asarray(bt))
    assert np.abs(np.asarray(lg2) - np.asarray(lg)).max() == 0
    assert int(held) == sum(_held_pairs(model, p) for p in prompts)
    # all 56 rows are routed, padding too; an expert gets at most a pair
    # a row, so one block: 2 held experts in each of 2 expert layers, of
    # the dispatch_rows(56, 3, 2) / 128 = 4 blocks laid out a layer
    assert 2 <= int(live) <= 2 * 2


def _held_pairs(model, ids):
    """A host recount by the reference's router: how many of the (token,
    chosen expert) pairs of one sequence, over its expert layers, have a
    held expert."""
    tops, route = [], ref.route

    def spy(h, p, config):
        tops.append(route(h, p, config))
        return tops[-1]

    ref.route = spy
    try:
        ref.hidden(_params(model), np.asarray(ids), model.config.published())
    finally:
        ref.route = route
    cfg = model.config
    assert len(tops) == cfg.num_layers - cfg.first_k_dense_replace
    lo, hi = cfg.expert_first, cfg.expert_first + cfg.num_experts_held
    return int(sum(((t >= lo) & (t < hi)).sum() for t, _ in tops))


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
    same codec: a tuple of one pool a cache layer, and no V side; the
    hand-off's first token is read from a result that carries the count
    behind the rows' tokens."""
    prompt = _prompts(model, (37,), seed=6)[0]
    want = _generate(model, prompt, 6)
    src = pt.serving.ServingEngine(model, **KNOBS)
    dst = pt.serving.ServingEngine(model, **KNOBS)
    src.submit(prompt, max_new_tokens=6, handoff=True)
    _drain(src)
    pay = src.take_handoff()
    assert len(pay.k_pages) == L and pay.v_pages == ()
    assert all(p.shape == (1, 3, 16, 128) for p in pay.k_pages)
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


@pytest.mark.parametrize("share", [True, False], ids=["share", "whole"])
def test_step_span_device_wait_and_counters_say_what_ran(share):
    """The launch side says what the step is made of from what the host
    knows; the collect side what the step really dispatched to held
    experts (held to a host recount by the reference's router; every
    routed pair where it holds every expert) and how many row blocks of
    its grouped matmuls held a pair, both counted by the step. Telemetry
    off records none of it."""
    model = _build(**(SHARE if share else {}))
    obs = pt.observability
    eng = pt.serving.ServingEngine(model, **KNOBS)
    eng.warmup()
    prompts = _prompts(model, (20, 7))
    obs.enable()
    try:
        obs.registry.reset()
        obs.tracing.reset()
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        _drain(eng)
        spans = obs.tracing.finished_spans()
        snap = obs.registry.snapshot()["counters"]
    finally:
        obs.disable()
    outs = [eng.result(r) for r in rids]
    assert [len(o) for o in outs] == [5, 5]
    obs.registry.reset()
    obs.tracing.reset()
    quiet = [eng.submit(p, max_new_tokens=5) for p in prompts]
    _drain(eng)
    assert not obs.tracing.finished_spans()
    assert not obs.registry.snapshot()["counters"]
    assert outs == [eng.result(r) for r in quiet]
    assert eng.ragged_compiles == 1
    steps = [s for s in spans if s.name == "serving.ragged_step"]
    waits = [s for s in spans if s.name == "serving.device_wait"]
    assert len(steps) == len(waits)
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import dispatch_rows
    held, routed = (2, 8) if share else (8, 8)
    for s in steps:
        a = s.args
        assert (a["kv_layout"], a["latent_dim"]) == ("latent", 40)
        assert "hc_streams" not in a
        assert (a["experts"], a["experts_routed"], a["experts_per_token"],
                a["moe_layers"]) == (held, routed, K, 2)
        assert a["moe_pairs"] == round(a["tokens"] * K * 2 * held / routed)
        assert a["moe_rows"] == 2 * dispatch_rows(18, K, held)
        assert a["moe_blocks"] == a["moe_rows"] // 128
        assert (a["passes"], a["cache_layers"]) == (1, L)
    # the first step packs the budget's 18 tokens of one prompt, at
    # positions 0..17: token j sees j + 1 keys
    assert steps[0].args["attn_pairs"] == 18 * 19 // 2
    assert steps[0].args["live_pages"] == 2
    # the pairs and the latent pages read are the span's own attributes
    # (live_pages in each of cache_layers pools): no counter repeats them
    assert all(s.args["live_pages"] >= 1 and s.args["cache_layers"] == L
               for s in steps)
    assert not {"serving.moe_pairs", "serving.latent_pages_read"} & set(snap)
    # a step is collected one round after its launch, in order
    assert [w.args["moe_pairs_routed"] for w in waits] == \
        [s.args["tokens"] * K * 2 for s in steps]
    got = sum(w.args["moe_pairs_held"] for w in waits)
    assert snap["serving.moe_pairs_held"] == got
    # every position but a request's last token went through the layers
    want = sum(_held_pairs(model, p + o[:-1])
               for p, o in zip(prompts, outs))
    assert got == want
    if share:
        assert 0 < got < sum(w.args["moe_pairs_routed"] for w in waits)
    else:
        assert all(w.args["moe_pairs_held"] == w.args["moe_pairs_routed"]
                   for w in waits)
    # the row blocks: an expert gets at most a pair a row of the budget's
    # 18, so one block, of the 1 + held laid out a layer
    blocks = steps[0].args["moe_blocks"]
    assert blocks == 2 * (1 + held)
    for w in waits:
        assert w.args["moe_blocks"] == blocks
        assert 2 <= w.args["moe_blocks_live"] <= 2 * held
    skipped = sum(blocks - w.args["moe_blocks_live"] for w in waits)
    assert snap["serving.moe_blocks_skipped"] == skipped > 0
    read = runner.load_module(
        "layer_metrics", "serving_engine.moe_skipped_block_share").read
    rec = {"spans": [{"name": s.name, "args": dict(s.args)} for s in spans]}
    assert abs(read(rec, None)
               - 100.0 * skipped / (blocks * len(waits))) < 1e-9
    # the reader: the held share of the window's routed pairs
    read = runner.load_module(
        "layer_metrics", "serving_engine.moe_held_pair_share").read
    rec = {"spans": [{"name": s.name, "args": dict(s.args)} for s in spans]}
    assert abs(read(rec, None) - 100.0 * got / sum(
        w.args["moe_pairs_routed"] for w in waits)) < 1e-9
    eng.shutdown()


# ------------------------------------------------- the benchmark's readers
def _cell(seconds=40.0):
    class Cell:
        pass
    c = Cell()
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "sarvam-105b-l6-ep4.json")) as f:
        c.config = json.load(f)
    c.traffic, c.seconds = {"traced_s": 2.0}, seconds
    c.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    c.log = lambda msg: None
    return c


def test_held_pair_share_reads_its_span_and_nothing_else():
    read = runner.load_module(
        "layer_metrics", "serving_engine.moe_held_pair_share").read
    cell = _cell()
    wait = {"name": "serving.device_wait", "ts": 1.0, "dur": 1.0}
    for nothing in ({}, {"spans": []},
                    # the parent's span, and a per-head model's: no count
                    {"spans": [dict(wait, args={})]},
                    {"spans": [dict(wait)]},
                    # the attributes on another span are not this metric's
                    {"spans": [{"name": "serving.ragged_step", "args": {
                        "moe_pairs_held": 5, "moe_pairs_routed": 10}}]}):
        assert read(nothing, cell) is None
    rec = {"spans": [dict(wait, args={"moe_pairs_held": 470,
                                      "moe_pairs_routed": 1920}),
                     dict(wait, args={"moe_pairs_held": 5610,
                                      "moe_pairs_routed": 22400}),
                     dict(wait, args={})]}
    assert abs(read(rec, cell) - 100.0 * 6080 / 24320) < 1e-9


def test_skipped_block_share_reads_its_span_and_nothing_else():
    read = runner.load_module(
        "layer_metrics", "serving_engine.moe_skipped_block_share").read
    cell = _cell()
    wait = {"name": "serving.device_wait", "ts": 1.0, "dur": 1.0}
    for nothing in ({}, {"spans": []}, {"spans": [dict(wait)]},
                    # the parent's span: pairs, and no blocks
                    {"spans": [dict(wait, args={"moe_pairs_held": 470,
                                                "moe_pairs_routed": 1920})]},
                    {"spans": [{"name": "serving.ragged_step", "args": {
                        "moe_blocks_live": 5, "moe_blocks": 10}}]}):
        assert read(nothing, cell) is None
    rec = {"spans": [dict(wait, args={"moe_blocks_live": 160,
                                      "moe_blocks": 335}),
                     dict(wait, args={"moe_blocks_live": 190,
                                      "moe_blocks": 335}),
                     dict(wait, args={})]}
    assert abs(read(rec, cell) - 100.0 * (1 - 350 / 670)) < 1e-9


@pytest.mark.parametrize("metric", [
    "kernels.latent_attn_roofline.serve", "kernels.latent_attn_share.serve",
    "kernels.moe_gmm_roofline.serve", "kernels.moe_gmm_share.serve",
    "serving_engine.moe_pad_share"])
def test_the_accepted_readers_read_this_cells_kernels(metric):
    """PR 34's readers tell the two Pallas kernels by their operands from
    the configuration file's own keys: the latent kernel by its one
    640-wide pool, the grouped matmuls by a stack of ``n_routed_experts``
    = the 32 HELD experts. A decode-only step of this cell: 48 rows, 6
    attention calls, 6 writes, 10 grouped matmuls."""
    from lib import xplane

    read = runner.load_module("layer_metrics", metric).read
    cell = _cell()
    attn = xplane.parse_hlo(
        "%c.2 = bf16[35840,512]{1,0} custom-call(s32[3131]{0} %vis, "
        "s32[1]{0} %n, s32[48,37]{1,0} %bt, s32[48]{0} %cl, s32[48]{0} "
        "%ql, s32[48]{0} %qs, bf16[35840,640]{1,0} %q, "
        'bf16[1,1408,128,640]{3,2,1,0} %pool), '
        'custom_call_target="tpu_custom_call"')
    write = xplane.parse_hlo(
        "%c.3 = bf16[1,1408,128,640]{3,2,1,0} custom-call(s32[560]{0} "
        "%vt, s32[8960]{0} %vk, s32[560]{0} %vb, f32[1,560,640]{2,1,0} "
        "%rows, bf16[1,1408,128,640]{3,2,1,0} %pool), "
        'custom_call_target="tpu_custom_call"')
    gmm = xplane.parse_hlo(
        "%c.4 = bf16[8576,4096]{1,0} custom-call(s32[67]{0} %gid, "
        "s32[1]{0} %live, bf16[8576,4096]{1,0} %xp, bf16[32,4096,4096]{2,1,0} %w), "
        'custom_call_target="tpu_custom_call"')
    args = {"rows": 48, "tokens": 48, "live_pages": 1056, "cache_layers": 6,
            "passes": 1, "weight_bytes": 1, "kv_layout": "latent",
            "latent_dim": 576, "attn_pairs": 48 * 2800, "experts": 32,
            "experts_routed": 128, "experts_per_token": 8, "moe_layers": 5,
            "moe_pairs": 480, "moe_rows": 5 * 8576}
    rec = {"trace": {"chips": {"/device:TPU:0": {
        "busy_s": 0.030, "window_s": 0.031, "ops": [
            dict(attn, seconds=0.006, count=6),
            dict(write, seconds=0.0005, count=6),
            dict(gmm, seconds=0.015, count=10)]}}},
        "spans": [{"name": "serving.ragged_step", "ts": 21e6, "dur": 100.0,
                   "args": args}]}
    got = read(rec, cell)
    # a layer's touched experts by the readers' count: 32 (1 - (3/4)^12)
    # = 30.99, within 1.5 % of the exact 32 (1 - (15/16)^48) = 30.55
    touched = 32 * (1 - 0.75 ** 12)
    assert abs(touched / (32 * (1 - (15 / 16) ** 48)) - 1) < 0.015
    gmm_least = (touched * 3 * 4096 * 2048 * 2
                 + 96 * (2 * 4096 + 3 * 2048) * 2) / 819e9
    want = {
        # 1,056 pages x 128 x 576 x 2 B / 819e9 = 0.190 ms against
        # 134,400 pairs x 2 x 64 x 320 / 197e12 = 0.028 ms; 1 ms a call
        "kernels.latent_attn_roofline.serve": 100 * 0.00019012 / 0.001,
        "kernels.latent_attn_share.serve": 20.0,
        # 3 ms a layer's two grouped matmuls
        "kernels.moe_gmm_roofline.serve": 100 * gmm_least / 0.003,
        "kernels.moe_gmm_share.serve": 50.0,
        "serving_engine.moe_pad_share": 100 * (1 - 96 / 8576)}[metric]
    assert abs(got - want) < 0.02 * want
    assert got < 100
