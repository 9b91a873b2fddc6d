"""Ouro LoopLM (models/ouro.py) against its plain float32 reference
(benchmark/references/ouro.py), eager, through ``generate()`` and behind
``ServingEngine``: tiny sizes, float32, CPU. Hidden 64, 4 heads of 16, 3
layers run 3 times, pages of 16 tokens."""
import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax.numpy as jnp  # noqa: E402

import paddle_tpu as pt  # noqa: E402
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
from lib import build, runner  # noqa: E402

ref = runner.load_module("references", "ouro")

R, L = 3, 3
KNOBS = dict(max_slots=2, block_size=16, num_blocks=24, prefill_chunk=16,
             max_seq_len=128)


def _ref_config(cfg, **kw):
    return dict(cfg.published(), **kw)


def _build(seed=3, **kw):
    """A tiny model whose norm weights and gate are not the initial ones
    and noughts: a norm applied in the wrong place, or the wrong norm,
    has to show."""
    pt.seed(seed)
    model = pt.models.OuroForCausalLM(pt.models.ouro_tiny(**kw))
    model.eval()
    rng = np.random.RandomState(seed)
    for n, p in model.named_parameters():
        if "norm" in n:
            p.set_value(rng.uniform(0.5, 1.5, p.shape).astype("float32"))
        elif "early_exit_gate" in n:
            p.set_value(rng.normal(0, 0.3, p.shape).astype("float32"))
    return model


def _params(model):
    return {n: p.value for n, p in model.named_parameters()}


@pytest.fixture(scope="module")
def model():
    return _build()


@pytest.fixture(scope="module")
def gpt():
    pt.seed(5)
    m = pt.models.GPTForCausalLM(pt.models.gpt_tiny(
        dropout=0.0, attention_dropout=0.0))
    m.eval()
    return m


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


def _shortfall(model, prompt, out):
    """How far under the reference's best logit the stream's tokens lie,
    teacher-forced: 0 when every token is the reference's argmax."""
    ids = np.zeros((1, 64), np.int32)    # one shape, one compile: causal
    ids[0, :len(prompt) + len(out)] = prompt + out
    lg = np.asarray(ref.logits(_params(model), ids,
                               _ref_config(model.config)))[0]
    rows = lg[len(prompt) - 1:len(prompt) - 1 + len(out)]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("kw", [{}, {"num_kv_heads": 2},
                                {"total_ut_steps": 1},
                                {"early_exit_threshold": 0.5},
                                {"tie_word_embeddings": True}],
                         ids=["mha", "gqa", "one_pass", "early_exit",
                              "tied"])
def test_logits_equal_the_reference(kw):
    m = _build(seed=7, **kw)
    ids = np.random.RandomState(1).randint(0, m.config.vocab_size, (2, 40))
    got = m(pt.to_tensor(ids)).numpy()
    want = np.asarray(ref.logits(_params(m), ids, _ref_config(m.config)))
    assert np.abs(got - want).max() < 1e-4
    ex = np.asarray(ref.exit_passes(_params(m), ids,
                                    _ref_config(m.config)))
    assert (m.ouro(pt.to_tensor(ids))[1].numpy() == ex).all()
    if kw.get("early_exit_threshold"):
        assert len(np.unique(ex)) > 1, "the gate never let a token out"
    else:
        assert (ex == m.config.total_ut_steps).all()


def test_initial_gains_and_value_channels_are_the_config_s():
    """``sublayer_norm_init`` and ``value_channel_spread`` set where the
    weights start and nothing else: the two norms on the sublayers'
    outputs, the spread of v_proj's channels at the matrix's RMS."""
    pt.seed(11)
    plain = pt.models.OuroForCausalLM(pt.models.ouro_tiny())
    pt.seed(11)
    m = pt.models.OuroForCausalLM(pt.models.ouro_tiny(
        sublayer_norm_init=0.25, value_channel_spread=1.5))
    a, b = _params(plain), _params(m)
    assert {n: v.shape for n, v in a.items()} \
        == {n: v.shape for n, v in b.items()}
    for n in b:
        w = np.asarray(b[n])
        if n.endswith("layernorm_2.weight"):
            assert (w == 0.25).all() and (np.asarray(a[n]) == 1).all()
        elif "norm" in n:
            assert (w == 1).all()
        elif n.endswith("v_proj.weight"):
            col = np.sqrt((w ** 2).mean(0))
            assert col.max() > 8 * np.median(col)
            assert abs(np.sqrt((w ** 2).mean()) / 0.02 - 1) < 0.1
            plain_col = np.sqrt((np.asarray(a[n]) ** 2).mean(0))
            assert plain_col.max() < 2 * np.median(plain_col)
    ids = np.random.RandomState(1).randint(0, 512, (1, 24))
    want = np.asarray(ref.logits(b, ids, _ref_config(m.config)))
    assert np.abs(m(pt.to_tensor(ids)).numpy() - want).max() < 1e-4


def test_passes_share_the_weights_and_one_pass_is_the_plain_stack():
    n = {r: sum(int(np.prod(p.shape)) for p in
                _build(total_ut_steps=r).parameters()) for r in (1, 2, 4)}
    assert n[1] == n[2] == n[4]
    # three passes are not one: the loop does something
    m3, m1 = _build(seed=7), _build(seed=7, total_ut_steps=1)
    ids = np.random.RandomState(2).randint(0, 512, (1, 24))
    a, b = m3(pt.to_tensor(ids)).numpy(), m1(pt.to_tensor(ids)).numpy()
    assert np.abs(a - b).max() > 1e-2
    # and the second pass starts from the first one's normed output
    want = np.asarray(ref.logits(_params(m3), ids,
                                 _ref_config(m3.config, total_ut_steps=1)))
    assert np.abs(b - want).max() < 1e-4


def test_configuration_file_is_the_catalog_row():
    """``benchmark/configs/ouro-2p6b.json`` against the published
    ``config.json`` (ByteDance/Ouro-2.6B), written out here."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_hidden_layers": 48,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152,
        "layer_types": ["full_attention"] * 48}
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "ouro-2p6b.json")) as f:
        cfg = json.load(f)
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["dtype"] == "bfloat16"
    assert cfg["source"].endswith("ByteDance/Ouro-2.6B/blob/main/"
                                  "config.json")
    # what the program's config class is given says the same
    assert (cfg["num_layers"], cfg["num_heads"]) == (48, 16)
    assert cfg["model_kwargs"] == {
        "num_kv_heads": 16, "rms_norm_eps": 1e-06, "rope_base": 1000000.0,
        "total_ut_steps": 4, "early_exit_threshold": 1.0,
        # how the random weights start, no size: assumed["weights"]
        "sublayer_norm_init": 0.102, "value_channel_spread": 1.5}
    assert abs(0.102 - (2 * 48) ** -0.5) < 1e-3
    for k in ("bias", "norms", "final_norm", "kv_cache", "gate",
              "max_seq_len", "weights"):
        assert cfg["assumed"][k]
    arch = {k: cfg[k] for k in build.ARCH_KEYS if k in cfg}
    c = pt.models.OuroConfig(**dict(arch, **cfg["model_kwargs"]))
    assert (c.head_dim, c.num_kv_heads, c.intermediate_size) == \
        (128, 16, 5632)
    n = 48 * (4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048) \
        + 2 * 49152 * 2048 + 2048 + 2048 + 1
    assert n == 2_667_974_657


# ----------------------------------------------------- generate()/engine
@pytest.mark.parametrize("threshold", [1.0, 0.5])
def test_generate_follows_the_reference(threshold, model):
    """At a threshold under 1 the adapter reads each token's logits from
    the hidden state of its exit pass, as the reference does."""
    if threshold != 1.0:
        model = _build(seed=7, early_exit_threshold=threshold)
    for p in _prompts(model, (9, 33)):
        assert _shortfall(model, p, _generate(model, p, 8)) < 1e-3


@pytest.mark.parametrize("passes", [R, 1])
def test_engine_streams_follow_the_reference_and_generate(passes, model):
    """Mixed prompts over several prefill chunks and pages, fewer slots
    than requests: every streamed token is the reference's argmax. A
    stack that runs once is served like one that runs three times."""
    if passes != R:
        model = _build(seed=7, total_ut_steps=passes)
    prompts = _prompts(model, (5, 37, 50, 20, 3))
    eng = pt.serving.ServingEngine(model, **KNOBS)
    assert len(eng._kp) == len(eng._vp) == passes * L
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drain(eng)
    outs = [eng.result(r) for r in rids]
    assert eng.ragged_compiles == 1
    for p, o in zip(prompts, outs):
        assert len(o) == 12
        assert _shortfall(model, p, o) < 1e-3
    # stream == model.generate() (a compile a prompt length: two of them)
    for k in (1, 4):
        assert outs[k] == _generate(model, prompts[k], 12)
    eng.shutdown()


def test_ragged_chunk_logits_equal_the_reference(model):
    """One ragged step of two prefill rows over two pages each: logits of
    every token against the reference's full forward pass."""
    ad = model.decode_adapter()
    bs, pages = 16, 6
    assert (ad.passes, ad.num_layers, ad.cache_layers) == (R, L, R * L)
    shape = (ad.num_kv_heads, pages, bs, ad.head_dim)
    kp = tuple(jnp.zeros(shape) for _ in range(R * L))
    vp = tuple(jnp.zeros(shape) for _ in range(R * L))
    prompts = _prompts(model, (21, 30), seed=4)
    toks = np.zeros(56, np.int32)
    pos = np.full(56, -1, np.int32)
    row_of = np.full(56, -1, np.int32)
    toks[:21], toks[21:51] = prompts
    pos[:21], pos[21:51] = np.arange(21), np.arange(30)
    row_of[:21], row_of[21:51] = 0, 1
    bt = np.asarray([[4, 1, 0], [2, 5, 0]], np.int32)
    lg, kp2, vp2 = ad.ragged_chunk(
        ad.weights, *(jnp.asarray(a) for a in (
            toks, pos, row_of, [0, 21], [21, 30], [21, 30])),
        kp, vp, jnp.asarray(bt))
    for k, (lo, p) in enumerate(zip((0, 21), prompts)):
        want = np.asarray(ref.logits(_params(model), np.asarray([p]),
                                     _ref_config(model.config)))[0]
        assert np.abs(np.asarray(lg[lo:lo + len(p)]) - want).max() < 1e-3
    assert len(kp2) == len(vp2) == R * L
    # a pass's keys are its own: every pass wrote the pages the block
    # tables name, each its own values, and none wrote page 3
    for r in range(R):
        mine, next_ = (np.asarray(kp2[(r + d) % R * L + L - 1])
                       for d in (0, 1))
        assert np.abs(mine[:, 4]).max() > 0
        assert np.abs(mine[:, 3]).max() == 0
        assert np.abs(mine - next_).max() > 1e-3


def test_engine_holds_a_pool_a_pass_and_layer(model):
    eng = pt.serving.ServingEngine(model, **KNOBS)
    nb = KNOBS["num_blocks"]
    assert len(eng._kp) == len(eng._vp) == R * L
    assert all(p.shape == (4, nb, 16, 16) for p in eng._kp + eng._vp)
    assert eng.manager.num_blocks == nb   # one page index space for all
    p = _prompts(model, (40,))[0]
    rid = eng.submit(p, max_new_tokens=4)
    req = eng._requests[rid]
    assert eng.step()                     # admitted: its pages are its own
    blocks = list(req.blocks)
    _drain(eng)
    eng.result(rid)
    for pools in (eng._kp, eng._vp):
        for l in range(L):
            page = [np.asarray(pools[r * L + l][:, blocks[0]])
                    for r in range(R)]
            for r in range(R):
                assert np.abs(page[r]).max() > 0
                assert np.abs(page[r] - page[(r + 1) % R]).max() > 1e-3
    eng.shutdown()


def test_preemption_and_prefix_hit_reproduce_the_tokens(model):
    prompts = _prompts(model, (6, 6), seed=3)
    refs = [_generate(model, p, 30) for p in prompts]
    # 4 pages of 16: both admit, growth exhausts the pool, the younger
    # request is evicted, recomputed in all R x L pools, and still matches
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
    assert req2.num_cached == 32          # two pages, in every pool
    eng.shutdown()


def test_handoff_and_prefix_transfer_carry_every_pool(model):
    prompt = _prompts(model, (37,), seed=6)[0]
    want = _generate(model, prompt, 6)
    src = pt.serving.ServingEngine(model, **KNOBS)
    dst = pt.serving.ServingEngine(model, **KNOBS)
    src.submit(prompt, max_new_tokens=6, handoff=True)
    _drain(src)
    pay = src.take_handoff()
    assert len(pay.k_pages) == len(pay.v_pages) == R * L
    assert pay.num_blocks == 3
    assert all(p.shape == (4, 3, 16, 16)
               for p in pay.k_pages + pay.v_pages)
    assert pay.nbytes() == 2 * R * L * 4 * 3 * 16 * 16 * 4
    rid = dst.adopt_handoff(pay)
    _drain(dst)
    assert [pay.first_token] + dst.result(rid) == want

    k, v, n = src.export_prefix(prompt)
    assert n == 2 and len(k) == len(v) == R * L and k[0].shape[1] == 2
    third = pt.serving.ServingEngine(model, **KNOBS)
    assert third.import_prefix(prompt, n, k, v) == 32
    rid = third.submit(prompt, max_new_tokens=6)
    req = third._requests[rid]
    _drain(third)
    assert third.result(rid) == want and req.num_cached == 32
    for e in (src, dst, third):
        e.shutdown()


@pytest.mark.parametrize("which", ["ouro", "ouro_one_pass", "gpt"])
def test_step_span_and_counters_say_what_ran(which, model, gpt):
    m = {"ouro": model, "gpt": gpt}.get(which) \
        or _build(seed=7, total_ut_steps=1)
    passes = {"ouro": R}.get(which, 1)
    layers = m.config.num_layers
    obs = pt.observability
    eng = pt.serving.ServingEngine(m, **KNOBS)
    eng.warmup()
    obs.enable()
    try:
        obs.registry.reset()
        rids = [eng.submit(p, max_new_tokens=5)
                for p in _prompts(m, (20, 7))]
        rounds = _drain(eng)
        snap = obs.registry.snapshot()["counters"]
        # every round launches a step but the last, which only collects
        steps = int(snap["serving.ragged_steps"])
        assert rounds == steps + 1
        spans = [s for s in obs.tracing.finished_spans()
                 if s.name == "serving.ragged_step"][-steps:]
    finally:
        obs.disable()
    assert [len(eng.result(r)) for r in rids] == [5, 5]
    weight_bytes = sum(int(np.prod(p.shape)) * 4
                       for n, p in m.named_parameters() if ".layers." in n
                       or ".h." in n)
    for s in spans:
        assert s.args["passes"] == passes
        assert s.args["cache_layers"] == passes * layers
        assert s.args["weight_bytes"] == weight_bytes
    # the first step packs a chunk of 16 of one prompt and the budget's
    # other 2 tokens of the second: a page each
    assert spans[0].args["live_pages"] == 2
    # the layer applications are the span's own (no counter repeats them)
    assert sum(s.args["cache_layers"] for s in spans) \
        == steps * passes * layers
    assert "serving.layer_passes" not in snap
    eng.shutdown()
