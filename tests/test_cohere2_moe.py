"""Command A+ (models/cohere2_moe.py: parallel blocks of sliding-window
and global NoPE attention beside sigmoid-routed SwiGLU experts of which a
model may hold a SHARE, and averaged shared experts) against its plain
float32 reference (benchmark/references/cohere2_moe.py), through the
decode adapter's forms, ``generate()`` and ``ServingEngine``, whose
windowed layers keep a RING of pages a slot: tiny sizes, float32, CPU.
Hidden 64, two periods of the published layer order (3 sliding, 1 full),
4 query heads over 2 KV heads of 16, a window of 20 (no whole number of
16-token pages), 8 routed SwiGLU experts top-3 + 2 shared. ``SHARE``
holds experts [2, 6) of the 8."""
import json
import os
import sys

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import importlib  # noqa: E402

import paddle_tpu as pt  # noqa: E402
from paddle_tpu.models import generation  # noqa: E402
paged_attention = importlib.import_module(
    "paddle_tpu.incubate.nn.pallas.paged_attention")
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
from lib import build, runner  # noqa: E402

ref = runner.load_module("references", "cohere2_moe")

K = 3
W = 20
SHARE = dict(num_experts_held=4, expert_first=2)
TINY = dict(num_layers=8, sliding_window=W)
# pages of 16, a prompt's chunk cut to 16: a ring of ceil((20 + 15) / 16)
# + 1 = 4 pages a slot, 64 tokens, which rows of 100 tokens wrap twice
KNOBS = dict(max_slots=3, block_size=16, num_blocks=40, prefill_chunk=16,
             max_seq_len=160)
RING = 4
# float32 everywhere: what separates the adapter from the reference is
# the order of the sums (grouped against per-expert matmuls, blocked
# against whole softmaxes)
TOL = 2e-4
# a streamed token's logit under the reference's best: 0 where it is the
# argmax, and the float32 sums' order leaves a tie no wider than this
SHORTFALL = 1e-3
# the same arithmetic in another order, at float32 and a few terms: the
# rounding of one addition, with room
EXACT = 1e-6


def _build(seed=3, **kw):
    """A tiny model whose norm gains are not the initial ones: a norm in
    the wrong place, or the wrong norm, has to show."""
    pt.seed(seed)
    model = pt.models.Cohere2MoeForCausalLM(
        pt.models.cohere2_moe_tiny(**dict(TINY, **kw)))
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
    """The chip's share: 4 of 8 routed experts held."""
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
    ids = np.zeros((1, 160), np.int32)   # one shape, one compile: causal
    ids[0, :len(prompt) + len(out)] = prompt + out
    rows = _ref_logits(model, ids)[0][len(prompt) - 1:
                                      len(prompt) - 1 + len(out)]
    return float((rows.max(-1) - rows[np.arange(len(out)), out]).max())


# ------------------------------------------------------------ the model
@pytest.mark.parametrize("kw", [
    {}, SHARE, {"num_experts_held": 1, "expert_first": 7},
    {"norm_topk_prob": False, **SHARE}, {"logit_scale": 0.5},
    {"layer_types": ["full_attention"] * 4 + ["sliding_attention"] * 4},
    {"sliding_window": 1}],
    ids=["every_expert", "share", "last_expert", "raw_scores",
         "logit_scale", "full_first", "window_of_one"])
def test_prefill_logits_equal_the_reference(kw):
    """The adapter's prefill (the window a mask, grouped SwiGLU experts
    over the held stacks, the shared experts side by side) against the
    reference (a block of queries at a time over the keys the window
    reaches, a held expert at a time, a shared expert at a time), over 70
    tokens: past the window of 20 more than three times."""
    m = _build(seed=7, **kw)
    ids = np.random.RandomState(1).randint(0, m.config.vocab_size, (2, 70))
    got = m(pt.to_tensor(ids)).numpy()
    assert np.abs(got - _ref_logits(m, ids)).max() < TOL


def test_the_config_refuses_what_the_family_is_not():
    for bad, msg in ((dict(layer_types=["sliding_attention"] * 3), "layer"),
                     (dict(layer_types=["local"] * 4), "layer"),
                     (dict(use_bias=True), "bias"),
                     (dict(use_parallel_block=False), "parallel"),
                     (dict(sliding_window=0), "sliding_window"),
                     (dict(num_experts_held=0), "held experts"),
                     (dict(num_experts_held=4, expert_first=6),
                      "held experts")):
        with pytest.raises(ValueError, match=msg):
            pt.models.cohere2_moe_tiny(**bad)
    with pytest.raises(ValueError, match="ties"):
        pt.models.Cohere2MoeForCausalLM(
            pt.models.cohere2_moe_tiny(tie_word_embeddings=False))
    whole = pt.models.Cohere2MoeConfig(num_experts_held=128)
    assert whole == pt.models.Cohere2MoeConfig()      # the share is data
    assert whole.layer_types[:4] == ["sliding_attention"] * 3 \
        + ["full_attention"] and whole.layer_types.count(
            "full_attention") == 8


def test_adapter_says_what_it_carries(model):
    ad = model.decode_adapter()
    assert (ad.kv_layout, ad.cache_layers, ad.window_layers, ad.window,
            ad.moe_layers, ad.num_layers) == ("kv", 2, 6, W, 8, 8)
    # the cache of each layer: the two full layers (3 and 7) first
    assert ad._cache_of == [2, 3, 4, 0, 5, 6, 7, 1]
    assert (ad.experts, ad.experts_routed, ad.expert_first,
            ad.experts_per_token, ad.expert_act, ad.shared_scale) == \
        (4, 8, 2, K, "swiglu", 0.5)
    blk = model.model.layers[0]
    assert blk.input_layernorm.bias is None
    assert tuple(blk.mlp.experts_gate_up.shape) == (4, 64, 64)
    assert tuple(blk.mlp.experts_down.shape) == (4, 32, 64)
    assert tuple(blk.mlp.gate_weight.shape) == (64, 8)     # the router
    assert tuple(blk.mlp.shared_experts.gate_proj.weight.shape) == (64, 64)
    assert "router_b" not in ad.weights["layers"][0]
    with pytest.raises(NotImplementedError, match="window"):
        ad.chunk_step(ad.weights, None, None, (), ())


def test_interleaved_rope_is_rope_on_channels_permuted_to_halves():
    """GPT-J's form rotates (x[2i], x[2i+1]); ``_rope`` rotates (x[i],
    x[i + d/2]) at the same frequency: the one is the other on the
    channels permuted to halves (evens, then odds), permuted back."""
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 3, 4, 16))
    pos = jnp.arange(15).reshape(5, 3) * 37
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    want = generation._rope(x[..., perm], pos, 50000.0)[..., np.argsort(perm)]
    got = generation._rope_interleaved(x, pos, 50000.0)
    assert np.abs(np.asarray(got - want)).max() < EXACT
    # and it is not the halves' rotation itself
    assert np.abs(np.asarray(got - generation._rope(x, pos, 50000.0))) \
        .max() > 0.1


def _layer_params(model, i):
    pre = "model.layers.%d." % i
    return {k[len(pre):]: v for k, v in _params(model).items()
            if k.startswith(pre)}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut, at eight-way expert parallelism: the
    routed parts that the shares [0, 1) .. [7, 8) of one expert layer
    give, plus the shared experts' average counted once, equal what the
    uncut reference gives for the whole layer; and no single share does.
    The attention beside them is every chip's alike (each runs its own
    requests), so the parallel block's sum is the uncut layer's."""
    whole = _build(seed=11, num_layers=4)
    h = jnp.asarray(np.random.RandomState(4).randn(37, 64), jnp.float32)
    want = np.asarray(ref.experts(h, _layer_params(whole, 1),
                                  whole.config.published()))
    total, shared = np.zeros_like(want), None
    for j in range(8):
        part = _build(seed=11, num_layers=4, num_experts_held=1,
                      expert_first=j)
        for (n, p), (_, q) in zip(part.named_parameters(),
                                  whole.named_parameters()):
            v = np.asarray(q.value)
            p.set_value(v[j:j + 1] if "mlp.experts_" in n else v)
        ad = part.decode_adapter()
        W_ = ad.weights["layers"][1]
        shared = np.asarray(ad._ffn(W_["shared"], h)) * ad.shared_scale
        routed = np.asarray(ad.moe(W_, h)) - shared
        # the reference, given the same share, says the same
        assert np.abs(routed - np.asarray(ref.routed(
            h, _layer_params(part, 1), part.config.published()))).max() \
            < EXACT
        total += routed
    assert np.abs(total + shared - want).max() < EXACT
    # the routed part is no rounding: a thousand times EXACT and more
    assert np.abs(shared - want).max() > 1e-3


def test_control_rounds_the_weights_the_program_reads():
    """``control_operand_dtype`` (the margin's lower-precision control, no
    cell's): the same parameters, read through float8, move the logits
    away from the reference's, which reads them whole."""
    ids = np.random.RandomState(1).randint(0, 512, (1, 40))
    diff = {}
    for ctl in (None, "float8_e5m2"):
        m = _build(seed=7, control_operand_dtype=ctl, **SHARE)
        diff[ctl] = np.abs(m(pt.to_tensor(ids)).numpy()
                           - _ref_logits(m, ids)).max()
    assert diff[None] < TOL and diff["float8_e5m2"] > 100 * TOL


def test_the_random_start_scales_what_it_says():
    """``routed_init_scale`` touches the routed experts' down projection
    alone."""
    out = []
    for kw in ({}, {"routed_init_scale": 0.25}):
        pt.seed(5)
        out.append(pt.models.Cohere2MoeForCausalLM(
            pt.models.cohere2_moe_tiny(**kw)))
    for (n, p), (_, q) in zip(out[0].named_parameters(),
                              out[1].named_parameters()):
        p, q = np.asarray(p.value), np.asarray(q.value)
        if n.endswith("mlp.experts_down"):
            assert np.allclose(q, 0.25 * p, atol=1e-7)
        else:
            assert (p == q).all(), n


# ----------------------------------------------------- generate()/engine
def test_generate_follows_the_reference(model):
    """The dense cache forms: a prompt past the window, then decode
    steps whose window mask moves along the cache."""
    p = _prompts(model, (45,))[0]
    assert _shortfall(model, p, _generate(model, p, 30)) < SHORTFALL


def test_engine_streams_follow_the_reference_and_generate(model):
    """Mixed prompts over several chunks of 16 (cut to 16 a row a step)
    and pages, fewer slots than requests (slots and their rings reused),
    one step in flight: every streamed token is the reference's argmax
    and ``generate()``'s. Rows reach 130 tokens: their windowed layers'
    rings of 64 tokens wrap twice, while the full layers page the whole
    context. No prefix is cached."""
    prompts = _prompts(model, (70, 5, 33, 100, 12))
    eng = pt.serving.ServingEngine(model, **KNOBS)
    assert eng.window_pages == RING
    # the full layers' pools, then the windowed layers' rings
    assert len(eng._kp) == len(eng._vp) == 8
    assert all(p.shape == (2, 40, 16, 16) for p in eng._kp[:2])
    assert all(p.shape == (2, 3 * RING, 16, 16) for p in eng._kp[2:])
    rids = [eng.submit(p, max_new_tokens=30) for p in prompts]
    _drain(eng)
    outs = [eng.result(r) for r in rids]
    assert eng.ragged_compiles == 1
    for p, o in zip(prompts, outs):
        assert len(o) == 30
        assert _shortfall(model, p, o) < SHORTFALL
    for k in (0, 3):
        assert outs[k] == _generate(model, prompts[k], 30)
    st = eng.stats()
    assert st.prefix_cache is False and not eng.manager.enable_prefix_cache
    eng.shutdown()


@pytest.mark.parametrize("chunk, block, want", [
    (16, 16, 4), (512, 128, 37), (256, 128, 35), (16, 8, 6)])
def test_a_ring_holds_a_window_and_a_chunk_at_any_context(chunk, block,
                                                          want):
    """R = ceil((W + prefill_chunk - 1) / block_size) + 1 pages a slot (37
    at the cell's 4,096, 512 and 128), whatever max_seq_len: the pages a
    row's one step reads and writes in a windowed layer, its window before
    its first token and its tokens, never more than that, wherever the
    step starts."""
    cfg = pt.models.cohere2_moe_tiny(num_layers=4, sliding_window=4096
                                     if want in (37, 35) else W)
    pt.seed(0)
    m = pt.models.Cohere2MoeForCausalLM(cfg)
    win = cfg.sliding_window
    for seq in (2 * win, 40 * win):
        eng = pt.serving.ServingEngine(
            m, max_slots=2, block_size=block, num_blocks=4,
            prefill_chunk=chunk, max_seq_len=seq)
        assert eng.window_pages == want
        assert eng._kp[-1].shape[1] == 2 * want
        eng.shutdown()
    # the pages a step can touch, by brute force over where it starts
    most = max((s + n - 1) // block - max(0, s - win + 1) // block + 1
               for n in (1, chunk) for s in range(0, 3 * win + 3 * block))
    assert most <= want


def test_a_prompt_s_chunk_is_cut_to_prefill_chunk(model):
    """One long prompt and nothing else: the budget (the slots + 16) would
    take 19 of its tokens a step; a windowed model's row takes 16."""
    eng = pt.serving.ServingEngine(model, **KNOBS)
    eng.submit(_prompts(model, (60,))[0], max_new_tokens=2)
    seen = []
    real = eng.scheduler.next_prefills

    def spy(budget, max_chunk=None):
        chunks = real(budget, max_chunk)
        seen.extend(len(c.tokens) for c in chunks)
        return chunks

    eng.scheduler.next_prefills = spy
    _drain(eng)
    assert seen == [16, 16, 16, 12]
    eng.shutdown()


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ragged_steps_through_the_rings(impl, model, monkeypatch):
    """Four ragged steps of the adapter's serving form over rings of 4
    pages (64 tokens): chunks that straddle the window, a row whose ring
    wraps more than once, decode rows beside them and an idle slot; the
    per-head kernel (interpreted) or the XLA composition. Every token's
    logits equal the reference's full forward pass."""
    if impl == "pallas":
        monkeypatch.setattr(paged_attention, "ragged_impl",
                            lambda d, page: "pallas")
    ad = model.decode_adapter()
    w = ad.weights
    fn = jax.jit(ad.ragged_chunk)
    A, B = _prompts(model, (150, 42), seed=9)
    R, T, bs = 3, 48, 16
    full = tuple(jnp.zeros((2, 24, bs, 16)) for _ in range(2))
    rings = tuple(jnp.zeros((2, R * RING, bs, 16)) for _ in range(6))
    kp, vp = full + rings, full + rings
    bt = np.zeros((R, 10), np.int32)
    bt[0], bt[2, :3] = np.arange(10), np.arange(10, 13)
    # (slot, tokens, first position) a row
    steps = [[(0, A[:16], 0), (2, B[:16], 0)],
             [(0, A[16:32], 16), (2, B[16:32], 16)],
             [(0, A[32:48], 32), (2, B[32:40], 32)],
             [(2, B[40:41], 40)]] + \
        [[(0, A[p:p + 16], p)] for p in range(48, 144, 16)] + \
        [[(0, A[144:150], 144), (2, B[41:42], 41)]]
    want = {0: _ref_logits(model, [A])[0], 2: _ref_logits(model, [B])[0]}
    for rows in steps:
        toks = np.zeros(T, np.int32)
        pos = np.full(T, -1, np.int32)
        row_of = np.full(T, -1, np.int32)
        qs, ql, cl = (np.zeros(R, np.int32) for _ in range(3))
        cursor = 0
        for r, tk, p0 in rows:
            n = len(tk)
            toks[cursor:cursor + n] = tk
            pos[cursor:cursor + n] = np.arange(p0, p0 + n)
            row_of[cursor:cursor + n] = r
            qs[r], ql[r], cl[r] = cursor, n, p0 + n
            cursor += n
        lg, kp, vp = fn(w, *(jnp.asarray(a) for a in (
            toks, pos, row_of, qs, ql, cl)), kp, vp, jnp.asarray(bt))
        lg = np.asarray(lg)
        for r, tk, p0 in rows:
            n = len(tk)
            got = lg[qs[r]:qs[r] + n]
            # float32, the kernel's blocked softmax against whole rows
            assert np.abs(got - want[r][p0:p0 + n]).max() < 1e-3


def test_prefix_reuse_is_off_and_handoff_and_transfer_refuse(model):
    """A ring keeps a request's last window alone: the engine caches no
    prefix (whatever it is asked), and every path that moves a request or
    a prefix between engines by its pages refuses with a RequestError."""
    prompt = _prompts(model, (37,), seed=5)[0]
    eng = pt.serving.ServingEngine(model, enable_prefix_cache=True,
                                   **KNOBS)
    r1 = eng.submit(prompt, max_new_tokens=4)
    _drain(eng)
    r2 = eng.submit(prompt, max_new_tokens=4)
    req2 = eng._requests[r2]
    _drain(eng)
    assert req2.num_cached == 0 and eng.result(r1) == eng.result(r2)
    assert eng.probe_prefix(prompt) == 0
    for call in (lambda: eng.submit(prompt, handoff=True),
                 eng.take_handoff, lambda: eng.adopt_handoff(None),
                 lambda: eng.export_prefix(prompt),
                 lambda: eng.import_prefix(prompt, 1, (), ())):
        with pytest.raises(pt.serving.RequestError, match="ring of 4 pages"):
            call()
    eng.shutdown()


def test_a_preempted_request_reprefills_to_the_same_tokens(model):
    """Two requests, a pool of 5 full-layer pages: one is preempted and
    prefilled again from position 0 into its slot's ring."""
    prompts = _prompts(model, (30, 30), seed=3)
    want = [_generate(model, p, 40) for p in prompts]
    eng = pt.serving.ServingEngine(
        model, max_slots=2, block_size=16, num_blocks=6, prefill_chunk=16,
        max_seq_len=80, watermark=0.0)
    rids = [eng.submit(p, max_new_tokens=40) for p in prompts]
    _drain(eng)
    assert [eng.result(r) for r in rids] == want
    assert eng.scheduler.preemptions >= 1 and eng.ragged_compiles == 1
    eng.shutdown()


def _window_counts(cl, ql, page, npv, window):
    """(live pages, visits, scored pairs) of one windowed layer, by
    hand."""
    pages = visits = pairs = 0
    for c, n in zip(cl, ql):
        if n:
            first = max(0, c - n - window + 1) // page
            live = -(-c // page) - first
            pages, visits = pages + live, visits + -(-live // npv)
            pairs += sum(min(window, p + 1) for p in range(c - n, c))
    return pages, visits, pairs


def test_step_span_says_what_the_window_does(model):
    """The launch side's window attributes from what the host knows,
    counted as the kernel would; telemetry off records none of it."""
    obs = pt.observability
    eng = pt.serving.ServingEngine(model, **KNOBS)
    eng.warmup()
    prompts = _prompts(model, (50, 7))
    obs.enable()
    try:
        obs.registry.reset()
        obs.tracing.reset()
        rids = [eng.submit(p, max_new_tokens=40) for p in prompts]
        _drain(eng)
        spans = obs.tracing.finished_spans()
    finally:
        obs.disable()
    assert [len(eng.result(r)) for r in rids] == [40, 40]
    steps = [s.args for s in spans if s.name == "serving.ragged_step"]
    assert steps and any(a["window_live_pages"] < a["live_pages"]
                         for a in steps)
    from paddle_tpu.incubate.nn.pallas.paged_attention import _ragged_plan
    _, npv = _ragged_plan(eng._token_budget, 2, 2, 16, 16, 4, False, 4)
    for a in steps:
        assert (a["window_layers"], a["window"], a["cache_layers"],
                a["kv_layout"]) == (6, W, 2, "kv")
        assert 0 < a["window_pairs"] <= a["tokens"] * W
    # one step by hand: the counts are the kernel's rule with the window
    cl = np.array([50, 0, 7], np.int32)
    ql = np.array([16, 0, 7], np.int32)
    attrs = eng._model_step_attrs(np.zeros(3, np.int32), ql, cl, 23)
    assert (attrs["window_live_pages"], attrs["window_visits"],
            attrs["window_pairs"]) == _window_counts(cl, ql, 16, npv, W)
    eng.shutdown()


# ------------------------------------------------- the configuration file
PUBLISHED_TYPES = ["sliding_attention"] * 3 + ["full_attention"]


def _catalog_row():
    """The published config.json of command-a-plus-05-2026, as the
    catalog row has it, written out."""
    return {
        "attention_bias": False, "expert_selection_fn": "sigmoid",
        "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 4096, "intermediate_size": 4096,
        "layer_norm_eps": 1e-05, "layer_switch": 4,
        "layer_types": PUBLISHED_TYPES * 8, "logit_scale": 1,
        "max_position_embeddings": 200000, "model_type": "cohere2_moe",
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 8,
        "num_shared_experts": 4,
        "order_of_interleaved_layers": "local_attn_first",
        "position_embedding_type": "rope_gptj",
        "prefix_dense_intermediate_size": 16384,
        "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
        "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
        "rope_theta": 50000, "rotary_pct": 1,
        "shared_expert_combination_strategy": "average",
        "sliding_window": 4096, "tf_legacy_loss": False,
        "tie_word_embeddings": True, "use_embedding_sharing": True,
        "use_gated_activation": True, "use_parallel_block": True,
        "use_parallel_embedding": False, "use_qk_norm": False,
        "vocab_size": 262144}


def _params_by_hand(c):
    C, hd, i = c.hidden_size, c.head_dim, c.intermediate_size
    attn = 2 * C * c.num_heads * hd + 2 * C * c.num_kv_heads * hd
    moe = C * c.num_experts + (c.num_experts_held + c.num_shared_experts) \
        * 3 * C * i
    return c.num_layers * (attn + moe + C) + c.vocab_size * C + C


def test_configuration_file_is_the_catalog_row():
    """``benchmark/configs/command-a-plus-l4-ep8.json`` against the
    published ``config.json``: every key not in ``reduced`` equal, each
    reduced key's published value beside it; the program's config class
    says the same; the cut's arithmetic."""
    published = _catalog_row()
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "command-a-plus-l4-ep8.json")) as f:
        cfg = json.load(f)
    reduced = {"num_hidden_layers": 4, "layer_types": PUBLISHED_TYPES,
               "num_experts": 16, "vocab_size": 32768}
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published} == dict(published, **reduced)
    assert {k: cfg["published"][k] for k in reduced} == \
        {k: published[k] for k in reduced}
    assert "eight-way expert-parallel" in cfg["stands_for"]
    assert cfg["dtype"] == "bfloat16" and cfg["source"].endswith(
        "CohereLabs/command-a-plus-05-2026/blob/main/config.json")
    for k in ("depth", "share", "shared_experts", "layer_norm",
              "attention", "experts", "head", "residual", "router_init",
              "weights", "expert_init", "routed_init", "control", "vision",
              "max_seq_len"):
        assert cfg["assumed"][k]
    arch = {k: cfg[k] for k in build.ARCH_KEYS if k in cfg}
    c = pt.models.Cohere2MoeConfig(**dict(arch, **cfg["model_kwargs"]))
    want = dict(published, **dict(reduced, num_experts=128))
    for k, v in c.published().items():
        if k in want:
            assert v == want[k], k
    assert (c.num_experts, c.num_experts_held, c.expert_first,
            c.num_layers) == (128, 16, 0, 4)
    assert c.control_operand_dtype is None
    # the cut's arithmetic by hand, and the count it implies on the tiny
    # model's shapes, where count_params can be run
    assert _params_by_hand(c) == 4_733_292_544
    tiny = pt.models.cohere2_moe_tiny(**SHARE)
    pt.seed(0)
    assert _params_by_hand(tiny) == build.count_params(
        pt.models.Cohere2MoeForCausalLM(tiny))
    assert cfg["engine"] == {"max_slots": 32, "block_size": 128,
                             "prefill_chunk": 512, "token_budget": 544,
                             "num_blocks": cfg["engine"]["num_blocks"],
                             "max_seq_len": 31232}


def test_traffic_file_is_the_stated_mix():
    from statistics import NormalDist

    with open(os.path.join(_ROOT, "benchmark", "traffic",
                           "mixed_closed32.json")) as f:
        tf = json.load(f)

    def mid(n, median, sigma, i, lo, hi):
        v = np.exp(np.log(median) + sigma * NormalDist().inv_cdf(
            (i + 0.5) / n))
        return int(min(max(round(v), lo), hi))

    want = [[mid(24, 1024, 0.6, i, 256, 3584), mid(24, 256, 0.5, i, 64, 768)]
            for i in range(24)] + \
        [[mid(8, 16384, 0.4, i, 8192, 30720), mid(8, 256, 0.5, i, 128, 512)]
         for i in range(8)]
    assert tf["pairs"] == want and tf["clients"] == 32
    assert tf["kind"] == "closed_loop" and tf["temperature"] == 0.0
    spec = tf["reference"]
    assert (spec["requests"], spec["pad_to"]) == (2, 16384)
    # at least two long requests (each over twice the window) fit pad_to
    assert sum(p >= 8192 and p + o <= spec["pad_to"]
               for p, o in tf["pairs"]) >= 2
    assert max(p + o for p, o in tf["pairs"]) <= 31232


# ------------------------------------------------- the benchmark's readers
def _traced(phase_seconds, steps, arg_list):
    """A record and a cell whose program maps one instruction a phase."""
    class Cell:
        pass
    cell = Cell()
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "command-a-plus-l4-ep8.json")) as f:
        cell.config = json.load(f)
    cell.traffic, cell.seconds = {"traced_s": 2.0}, 40.0
    cell.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    cell.log = lambda msg: None
    mapped = {"f.%s" % p: {"phase": p, "direction": "fwd", "op_name": p}
              for p in phase_seconds}

    class Obs:
        @staticmethod
        def op_phases(site):
            return {"module": "m", "ops": mapped}

    class Pt:
        observability = Obs

    cell.pt = Pt
    ops = [{"name": "f.%s" % p, "seconds": s, "count": steps,
            "target": "", "operands": [], "results": [], "base": p}
           for p, s in phase_seconds.items()]
    spans = [{"name": "serving.ragged_step", "ts": 10.0 + k, "dur": 1.0,
              "args": dict(a, live_pages=1, cache_layers=1, passes=1,
                           weight_bytes=1)}
             for k, a in enumerate(arg_list)]
    rec = {"trace": {"chips": {"/device:TPU:0": {"ops": ops}}},
           "spans": spans}
    return rec, cell


def test_window_readers_read_the_phase_and_the_spans():
    read_roof = runner.load_module(
        "layer_metrics", "kernels.window_attn_roofline.serve").read
    read_share = runner.load_module(
        "layer_metrics", "kernels.window_attn_share.serve").read
    args = {"window_layers": 3, "window_live_pages": 900,
            "window_pairs": 2_500_000, "tokens": 544}
    secs = {"attn.window": 0.012, "attn.kernel": 0.006, "moe.experts": 0.02,
            "ffn": 0.002}
    rec, cell = _traced(secs, 4, [args, args])
    page = 2 * 8 * 128 * 128 * 2
    least = 3 * max((900 * page + 2 * 544 * 128 * 128 * 2) / 819e9,
                    2_500_000 * 4.0 * 128 * 128 / 197e12)
    assert read_roof(rec, cell) == pytest.approx(100.0 * least * 4 / 0.012)
    assert read_share(rec, cell) == pytest.approx(100.0 * 0.012 / 0.040)
    # the parent's program, or another model's: no window phase or spans
    rec, cell = _traced({"attn.kernel": 0.01, "ffn": 0.02}, 4,
                        [{"tokens": 3}])
    assert read_roof(rec, cell) is None and read_share(rec, cell) is None
    assert read_roof({}, cell) is None and read_share({}, cell) is None
