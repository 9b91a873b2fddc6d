"""Nemotron-H (models/nemotron_h.py: Mamba-2 mixers whose recurrent state
lives per slot beside the paged KV of grouped-query attention blocks,
sigmoid-routed relu² experts of which a model may hold a SHARE) against
its plain float32 reference (benchmark/references/nemotron_h.py), through
the decode adapter's forms, ``generate()`` and ``ServingEngine``: tiny
sizes, float32, CPU. Hidden 64, the pattern ``ME*M-E`` (every letter of
the family), 8 Mamba heads of 8 in 2 groups of a 16-wide state, 4 query
heads over 2 KV heads of 16, 8 routed relu² experts top-3 + 1 shared,
pages of 16 tokens. ``SHARE`` holds experts [2, 4) of the 8."""
import collections
import hashlib
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
from paddle_tpu.incubate.nn.pallas import ssd_scan  # noqa: E402
sys.path.insert(0, os.path.join(_ROOT, "benchmark"))
from lib import build, runner, xplane  # noqa: E402

ref = runner.load_module("references", "nemotron_h")

K = 3
SHARE = dict(num_experts_held=2, expert_first=2)
KNOBS = dict(max_slots=2, block_size=16, num_blocks=24, prefill_chunk=16,
             max_seq_len=128)
# float32 everywhere: what separates the adapter from the reference is
# the order of the sums (chunked against sequential scan, grouped
# against per-expert matmuls)
TOL = 2e-4


def _build(seed=3, **kw):
    """A tiny model whose norm gains are not the initial ones: a norm in
    the wrong place, or the wrong norm, has to show."""
    pt.seed(seed)
    model = pt.models.NemotronHForCausalLM(pt.models.nemotron_h_tiny(**kw))
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
    {"norm_topk_prob": False, **SHARE}, {"tie_word_embeddings": True},
    {"hybrid_override_pattern": "M*EMME", "n_groups": 4}],
    ids=["every_expert", "share", "uneven_share", "raw_scores", "tied",
         "four_groups"])
def test_prefill_logits_equal_the_reference(kw):
    """The adapter's prefill (the scan a token at a time over the batch,
    grouped relu² experts over the held stacks) against the reference (a
    sequence at a time, a held expert at a time)."""
    m = _build(seed=7, **kw)
    ids = np.random.RandomState(1).randint(0, m.config.vocab_size, (2, 40))
    got = m(pt.to_tensor(ids)).numpy()
    assert np.abs(got - _ref_logits(m, ids)).max() < TOL


def test_the_config_refuses_what_the_family_is_not():
    for bad, msg in ((dict(hybrid_override_pattern="ME*M-"), "pattern"),
                     (dict(hybrid_override_pattern="ME*MXE"), "pattern"),
                     (dict(use_bias=True), "bias"),
                     (dict(num_experts_held=0), "held experts"),
                     (dict(num_experts_held=4, expert_first=6),
                      "held experts")):
        with pytest.raises(ValueError, match=msg):
            pt.models.nemotron_h_tiny(**bad)
    whole = pt.models.NemotronHConfig(num_experts_held=128)
    assert whole == pt.models.NemotronHConfig()      # the share is data
    assert (whole.d_inner, whole.conv_dim) == (4096, 6144)
    assert whole.kinds().count("mamba") == 23 \
        and whole.kinds().count("moe") == 23 \
        and whole.kinds().count("attention") == 6


def test_adapter_says_what_it_carries(model):
    ad = model.decode_adapter()
    assert (ad.kv_layout, ad.cache_layers, ad.state_layers, ad.moe_layers,
            ad.num_layers) == ("kv", 1, 2, 2, 6)
    assert (ad.experts, ad.experts_routed, ad.expert_first,
            ad.experts_per_token, ad.expert_act) == (2, 8, 2, K, "relu2")
    shapes = ad.state_shapes()
    assert len(shapes) == 2 and shapes[0] == {
        "ssm": ((8, 8, 16), jnp.dtype("float32")),
        "conv": ((3, 64 + 2 * 2 * 16), ad.dtype)}
    assert ad.state_bytes() == 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    blk = model.model.layers[1].mixer
    assert tuple(blk.experts_up.shape) == (2, 32, 64)     # out x in
    assert tuple(blk.experts_down.shape) == (2, 32, 64)
    assert tuple(blk.gate_weight.shape) == (64, 8)        # the router
    with pytest.raises(NotImplementedError, match="state snapshots"):
        ad.chunk_step(ad.weights, None, None, (), ())


def _layer_params(model, i):
    pre = "model.layers.%d." % i
    return {k[len(pre):]: v for k, v in _params(model).items()
            if k.startswith(pre)}


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The guide's test of the cut, at eight-way expert parallelism: the
    routed parts that the shares [0, 1) .. [7, 8) of one expert layer
    give, plus the shared expert counted once, equal what the uncut
    reference gives for the whole layer; and no single share does."""
    whole = _build(seed=11)
    h = jnp.asarray(np.random.RandomState(4).randn(37, 64), jnp.float32)
    want = np.asarray(ref.experts(h, _layer_params(whole, 1),
                                  whole.config.published()))
    total, shared = np.zeros_like(want), None
    for j in range(8):
        part = _build(seed=11, num_experts_held=1, expert_first=j)
        for (n, p), (_, q) in zip(part.named_parameters(),
                                  whole.named_parameters()):
            v = np.asarray(q.value)
            p.set_value(v[j:j + 1] if "mixer.experts_" in n else v)
        ad = part.decode_adapter()
        W = ad.weights["layers"][1]
        shared = np.asarray(ad._ffn(W["shared"], h))
        routed = np.asarray(ad.moe(W, h)) - shared
        # the reference, given the same share, says the same
        assert np.abs(routed - np.asarray(ref.routed(
            h, _layer_params(part, 1), part.config.published()))).max() \
            < 1e-6
        total += routed
    assert np.abs(total + shared - want).max() < 1e-6
    assert np.abs(shared - want).max() > 1e-3


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


@pytest.mark.parametrize("ctl", ["float8_e5m2", "float8_e4m3fn"])
def test_control_rounds_as_the_float8_type_would(ctl):
    """The control rounds by ``lax.reduce_precision`` (an ``astype`` round
    trip the TPU compiler drops as excess precision, PR 40): in the
    float8 type's normal range the very values of that round trip, under
    jit as the step reads them."""
    ad = _build(seed=7, control_operand_dtype=ctl).decode_adapter()
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 96)) * 0.02
    w = jnp.where(jnp.abs(w) < float(jnp.finfo(ctl).tiny), 0.02, w) \
        .astype(jnp.bfloat16)
    got = jax.jit(lambda t: ad._control(t))({"w": w, "router_w": w})
    assert (got["w"] == w.astype(ctl).astype(jnp.bfloat16)).all()
    assert (got["w"] != w).any() and (got["router_w"] == w).all()


def test_the_random_start_scales_what_it_says():
    """``routed_init_scale`` touches the routed experts' down projection
    alone, ``residual_init_layers`` the mixers' out_proj alone."""
    cfgs = [dict(hidden_size=256, moe_intermediate_size=128),
            dict(hidden_size=256, moe_intermediate_size=128,
                 routed_init_scale=0.25, residual_init_layers=24)]
    a, b = [], []
    for kw, out in zip(cfgs, (a, b)):
        pt.seed(5)
        out.append(pt.models.NemotronHForCausalLM(
            pt.models.nemotron_h_tiny(**kw)))
    for (n, p), (_, q) in zip(a[0].named_parameters(),
                              b[0].named_parameters()):
        p, q = np.asarray(p.value), np.asarray(q.value)
        if n.endswith("mixer.experts_down"):
            assert np.allclose(q, 0.25 * p, atol=1e-7)
        elif n.endswith("out_proj.weight"):
            assert np.allclose(q, p * (6 / 24) ** 0.5, atol=1e-7)
        else:
            assert (p == q).all(), n


# ----------------------------------------------------- generate()/engine
def test_generate_follows_the_reference(model):
    p = _prompts(model, (33,))[0]
    assert _shortfall(model, p, _generate(model, p, 8)) < 1e-3


def test_engine_streams_follow_the_reference_and_generate(model):
    """Mixed prompts over several prefill chunks and pages, fewer slots
    than requests (slots reused), one step in flight: every streamed token
    is the reference's argmax and ``generate()``'s. The state entries ride
    in the K tuple after the one paged pool; no prefix is cached."""
    prompts = _prompts(model, (5, 37, 50, 20, 3))
    eng = pt.serving.ServingEngine(model, **KNOBS)
    assert len(eng._kp) == 3 and len(eng._vp) == 1
    assert eng._kp[0].shape == (2, 24, 16, 16)
    assert eng._kp[1]["ssm"].shape == (2, 8, 8, 16)
    assert eng._kp[1]["conv"].shape == (2, 3, 128)
    rids = [eng.submit(p, max_new_tokens=12) for p in prompts]
    _drain(eng)
    outs = [eng.result(r) for r in rids]
    assert eng.ragged_compiles == 1
    for p, o in zip(prompts, outs):
        assert len(o) == 12
        assert _shortfall(model, p, o) < 1e-3
    for k in (1, 4):
        assert outs[k] == _generate(model, prompts[k], 12)
    st = eng.stats()
    assert st.prefix_cache is False and not eng.manager.enable_prefix_cache
    eng.shutdown()


def _ragged(ad, w, kp, vp, steps_rows, T, pages_of, bs):
    """Run ragged steps: each a list of (slot, tokens, first position)."""
    fn = jax.jit(ad.ragged_chunk)
    R = len(pages_of)
    bt = np.zeros((R, 16), np.int32)
    for r, pg in pages_of.items():
        bt[r, :len(pg)] = pg
    out = []
    for rows in steps_rows:
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
        out.append((np.asarray(lg), dict(zip(
            [r for r, _, _ in rows], [(qs[r], ql[r]) for r, _, _ in rows]))))
    return out, kp, vp


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_ragged_rows_carry_their_state_across_steps(impl, model,
                                                    monkeypatch):
    """Three ragged steps on a 320-token axis (the scan's blocks are 128
    tokens): prefill rows that start anywhere on the axis and cross block
    edges, beside one-token decode rows and idle rows; row 0's prompt is
    spread over three steps (100 + 100 tokens, then a token). Every
    token's logits equal the reference's full forward pass; row 0's state
    after the last step equals the dense prefill's of the same 201
    tokens; the slots that start at position 0 held garbage, which no
    token sees; the idle slot's state is untouched, bit for bit."""
    if impl == "pallas":
        monkeypatch.setattr(ssd_scan, "ssd_impl", lambda: "pallas")
    ad = model.decode_adapter()
    w = ad.weights
    A, B, C = _prompts(model, (201, 220, 132), seed=9)
    R, T, bs = 5, 320, 16
    rng = np.random.RandomState(2)
    kp = (jnp.zeros((2, 64, bs, 16)),) + tuple(
        {"ssm": jnp.asarray(rng.randn(R, 8, 8, 16), jnp.float32),
         "conv": jnp.asarray(rng.randn(R, 3, 128), jnp.float32)}
        for _ in range(2))
    vp = (jnp.zeros((2, 64, bs, 16)),)
    pages_of = {0: range(0, 13), 1: [], 2: range(13, 27), 3: [],
                4: range(27, 36)}
    steps = [[(0, A[:100], 0), (2, B[:150], 0)],
             [(2, B[150:210], 150), (0, A[100:200], 100),
              (4, C[:131], 0)],
             [(0, A[200:201], 200), (2, B[210:220], 210),
              (4, C[131:132], 131)]]
    out, kp2, _ = _ragged(ad, w, kp, vp, steps, T, pages_of, bs)
    want = {0: _ref_logits(model, [A])[0], 2: _ref_logits(model, [B])[0],
            4: _ref_logits(model, [C])[0]}
    for (lg, spans), rows in zip(out, steps):
        for r, tk, p0 in rows:
            q, n = spans[r]
            assert np.abs(lg[q:q + n] - want[r][p0:p0 + n]).max() < 1e-3
    _, ck, _ = jax.jit(lambda w, i: ad.prefill(w, i, 208))(
        w, jnp.asarray([A]))
    for j in range(2):
        dense, got = ck[1 + j], kp2[1 + j]
        assert np.abs(np.asarray(got["ssm"][0])
                      - np.asarray(dense["ssm"][0])).max() < 1e-4
        assert np.abs(np.asarray(got["conv"][0])
                      - np.asarray(dense["conv"][0])).max() < 1e-6
        for key in ("ssm", "conv"):
            assert (np.asarray(got[key])[[1, 3]]
                    == np.asarray(kp[1 + j][key])[[1, 3]]).all()


def test_a_slots_next_request_starts_from_zeros(model):
    """One slot, two requests in turn: the second's tokens are those of a
    fresh ``generate()``, though the slot's state is the first's."""
    a, b = _prompts(model, (30, 21), seed=4)
    eng = pt.serving.ServingEngine(model, **dict(KNOBS, max_slots=1))
    ra = eng.submit(a, max_new_tokens=6)
    _drain(eng)
    assert np.abs(np.asarray(eng._kp[1]["ssm"])).max() > 0
    rb = eng.submit(b, max_new_tokens=6)
    _drain(eng)
    assert eng.result(ra) == _generate(model, a, 6)
    assert eng.result(rb) == _generate(model, b, 6)
    eng.shutdown()


def test_a_preempted_request_reprefills_to_the_same_tokens(model):
    prompts = _prompts(model, (6, 6), seed=3)
    want = [_generate(model, p, 30) for p in prompts]
    eng = pt.serving.ServingEngine(
        model, max_slots=2, block_size=16, num_blocks=4, prefill_chunk=16,
        max_seq_len=64, watermark=0.0)
    rids = [eng.submit(p, max_new_tokens=30) for p in prompts]
    _drain(eng)
    assert [eng.result(r) for r in rids] == want
    assert eng.scheduler.preemptions >= 1 and eng.ragged_compiles == 1
    eng.shutdown()


def test_prefix_reuse_is_off_and_handoff_and_transfer_refuse(model):
    """A cached prefix's pages cannot restore a recurrent state: the
    engine caches none (whatever it is asked), and every path that moves
    a request or a prefix between engines by its pages refuses, naming
    the missing mechanism."""
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
        with pytest.raises(NotImplementedError, match="state snapshots"):
            call()
    eng.shutdown()


def test_step_span_and_counter_say_what_the_state_does(model):
    """The launch side's state attributes from what the host knows, the
    resets counted; telemetry off records none of it."""
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
    assert [len(eng.result(r)) for r in rids] == [5, 5]
    steps = [s.args for s in spans if s.name == "serving.ragged_step"]
    row = 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    for a in steps:
        assert (a["state_layers"], a["ssm_heads"], a["ssm_head_dim"],
                a["ssm_state_size"], a["ssm_groups"], a["state_bytes_row"],
                a["kv_layout"], a["cache_layers"]) == \
            (2, 8, 8, 16, 2, row, "kv", 1)
        assert a["ssm_tokens"] == a["tokens"]
        assert 1 <= a["state_rows"] <= a["rows"]
        assert (a["experts"], a["experts_routed"], a["experts_per_token"],
                a["moe_layers"]) == (2, 8, K, 2)
        # the XLA composition serves here: no kernel visits to count
        assert a["impl"] == "xla" and "ragged_visits" not in a
    # each request restarts once, at its first chunk
    assert sum(a["state_resets"] for a in steps) == 2
    assert snap["serving.ssm_state_resets"] == 2
    assert steps[0]["state_resets"] == 1 and steps[0]["state_rows"] == 1


# ------------------------------------------------ what the step compiles to
# (opcode, result shapes) multisets of the tiny Xing4.0 and sarvam steps
# compiled on the CPU, hashed; read at the parent (PR 39's tree) before
# the expert body was lifted into _ExpertBody: the lift changed no
# instruction. A change that means to change those steps updates these.
STEP_MULTISETS = {"xing4": "652b380aca7e4853", "sarvam": "1eb59ca1ae2ce81a"}


@pytest.mark.parametrize("name", sorted(STEP_MULTISETS))
def test_xing4_and_sarvam_steps_keep_their_op_multiset(name):
    pt.seed(3)
    if name == "xing4":
        m = pt.models.Xing4ForCausalLM(pt.models.xing4_tiny())
    else:
        m = pt.models.SarvamForCausalLM(pt.models.sarvam_tiny(**SHARE))
    m.eval()
    eng = pt.serving.ServingEngine(m, **KNOBS)
    eng.submit(list(range(1, 20)), max_new_tokens=3)
    _drain(eng)
    ops = []
    for line in eng.compiled_step().as_text().splitlines():
        line = line.strip().removeprefix("ROOT ")
        if line.startswith("%") and " = " in line:
            op = xplane.parse_hlo(line)
            ops.append((op["opcode"], tuple(map(tuple, op["results"]))))
    got = sorted([list(map(str, k)) + [v]
                  for k, v in collections.Counter(ops).items()])
    assert hashlib.sha256(json.dumps(got).encode()).hexdigest()[:16] \
        == STEP_MULTISETS[name]
    eng.shutdown()


# ------------------------------------------------- the configuration file
def test_configuration_file_is_the_catalog_row():
    """``benchmark/configs/nemotron3-nano-l18-ep8.json`` against the
    published ``config.json`` (the catalog row
    NVIDIA-Nemotron-3-Nano-30B-A3B-BF16), written out here: every key not
    in ``reduced`` equal, each reduced key's published value beside it;
    the program's config class says the same; the cut's arithmetic."""
    published = {
        "attention_bias": False, "chunk_size": 128, "conv_kernel": 4,
        "expand": 2, "head_dim": 128, "hidden_size": 2688,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
        "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
        "mamba_head_dim": 64, "mamba_hidden_act": "silu",
        "mamba_num_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
        "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_group": 1,
        "n_groups": 8, "n_routed_experts": 128, "n_shared_experts": 1,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 6, "num_hidden_layers": 52,
        "num_key_value_heads": 2, "num_logits_to_keep": 1,
        "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
        "residual_in_fp32": False, "rope_theta": 10000,
        "routed_scaling_factor": 2.5, "sliding_window": None,
        "ssm_state_size": 128, "tie_word_embeddings": False,
        "time_step_floor": 0.0001, "time_step_max": 0.1,
        "time_step_min": 0.001, "topk_group": 1, "use_bias": False,
        "use_conv_bias": True, "use_mamba_kernels": True,
        "vocab_size": 131072}
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "nemotron3-nano-l18-ep8.json")) as f:
        cfg = json.load(f)
    reduced = {"num_hidden_layers": 18,
               "hybrid_override_pattern": "MEMEM*EMEMEM*EMEME",
               "n_routed_experts": 16}
    assert sorted(cfg["reduced"]) == sorted(reduced)
    assert {k: cfg[k] for k in published} == dict(published, **reduced)
    assert {k: cfg["published"][k] for k in reduced} == \
        {k: published[k] for k in reduced}
    assert published["hybrid_override_pattern"][:18] == \
        reduced["hybrid_override_pattern"]
    assert "eight-way expert-parallel" in cfg["stands_for"]
    assert cfg["dtype"] == "bfloat16" and cfg["source"].endswith(
        "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16/blob/main/config.json")
    for k in ("depth", "share", "attention", "mamba", "state",
              "activations", "experts", "residual", "router_init",
              "mamba_init", "weights", "expert_init", "routed_init",
              "control", "max_seq_len"):
        assert cfg["assumed"][k]
    arch = {k: cfg[k] for k in build.ARCH_KEYS if k in cfg}
    c = pt.models.NemotronHConfig(**dict(arch, **cfg["model_kwargs"]))
    want = dict(published, num_hidden_layers=18,
                hybrid_override_pattern=reduced["hybrid_override_pattern"])
    for k, v in c.published().items():
        if k in want:
            assert v == want[k], k
    assert (c.num_experts, c.num_experts_held, c.expert_first) == \
        (128, 16, 0)
    assert c.control_operand_dtype is None and c.ssm_state_dtype == \
        "float32"
    # the cut's arithmetic by hand, and the count it implies on the tiny
    # model's shapes, where count_params can be run
    assert abs(_params_by_hand(c) / 1e6 - 2501.0) < 0.05
    tiny = pt.models.nemotron_h_tiny(num_experts_held=2, expert_first=2)
    pt.seed(0)
    assert _params_by_hand(tiny) == build.count_params(
        pt.models.NemotronHForCausalLM(tiny))
    assert cfg["engine"] == {"max_slots": 128, "block_size": 128,
                             "prefill_chunk": 256, "token_budget": 384,
                             "num_blocks": cfg["engine"]["num_blocks"],
                             "max_seq_len": 3840}


def _params_by_hand(c):
    C, di, h = c.hidden_size, c.d_inner, c.mamba_num_heads
    mamba = C * (di + c.conv_dim + h) + c.conv_kernel * c.conv_dim \
        + c.conv_dim + 3 * h + di + di * C + C
    attn = C * c.head_dim * (c.num_heads + 2 * c.num_kv_heads) \
        + c.num_heads * c.head_dim * C + C
    moe = c.num_experts_held * 2 * C * c.moe_intermediate_size \
        + 2 * C * c.moe_shared_expert_intermediate_size \
        + C * c.num_experts + c.num_experts + C
    mlp = 2 * C * c.intermediate_size + C
    per = {"mamba": mamba, "attention": attn, "moe": moe, "mlp": mlp}
    return sum(per[k] for k in c.kinds()) + 2 * c.vocab_size * C + C


def test_traffic_file_is_the_issues_mix():
    from statistics import NormalDist

    with open(os.path.join(_ROOT, "benchmark", "traffic",
                           "reason_closed128.json")) as f:
        tf = json.load(f)
    want = []
    for i in range(32):
        v = np.exp(np.log(768) + 0.5 * NormalDist().inv_cdf((i + 0.5) / 32))
        p = int(min(max(round(v), 256), 1536))
        want.append([p, round(1.5 * p)])
    assert tf["pairs"] == want and tf["clients"] == 128
    assert tf["kind"] == "closed_loop" and tf["temperature"] == 0.0
    assert max(p + o for p, o in tf["pairs"]) == tf["reference"]["pad_to"] \
        == 3840 and tf["reference"]["requests"] == 2


# ------------------------------------------------- the benchmark's readers
def _cell():
    class Cell:
        pass
    c = Cell()
    with open(os.path.join(_ROOT, "benchmark", "configs",
                           "nemotron3-nano-l18-ep8.json")) as f:
        c.config = json.load(f)
    c.traffic, c.seconds = {"traced_s": 2.0}, 40.0
    c.peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops": 197e12}
    c.log = lambda msg: None
    return c


def _traced(phase_seconds, steps, arg_list):
    """A record and a cell whose program maps one instruction a phase."""
    cell = _cell()
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
              "args": dict(a, live_pages=1, cache_layers=2, passes=1,
                           weight_bytes=1)}
             for k, a in enumerate(arg_list)]
    rec = {"trace": {"chips": {"/device:TPU:0": {"ops": ops}}},
           "spans": spans}
    return rec, cell


def test_ragged_pages_per_visit_reads_the_step_spans():
    """Sum of ``ragged_page_visits`` over sum of ``ragged_visits`` of the
    window's step spans; nothing where no span carries them (the parent's
    program, which visits a page a grid step)."""
    read = runner.load_module(
        "layer_metrics", "kernels.ragged_pages_per_visit.serve").read
    step = {"name": "serving.ragged_step", "ts": 1.0, "dur": 1.0}
    rec = {"spans": [dict(step, args={"ragged_page_visits": 1740,
                                      "ragged_visits": 250}),
                     dict(step, args={"ragged_page_visits": 1760,
                                      "ragged_visits": 254}),
                     dict(step, name="serving.device_wait",
                          args={"ragged_page_visits": 9,
                                "ragged_visits": 1})]}
    assert read(rec, None) == pytest.approx(3500 / 504)
    assert read({"spans": [dict(step, args={"live_pages": 5})]}, None) \
        is None
    assert read({}, None) is None


def test_scan_roofline_and_ssm_share_read_the_phases():
    read_roof = runner.load_module(
        "layer_metrics", "kernels.ssm_scan_roofline.serve").read
    read_share = runner.load_module(
        "layer_metrics", "serving_engine.ssm_share.serve").read
    args = {"state_rows": 128, "state_layers": 8, "ssm_tokens": 384,
            "ssm_heads": 64, "ssm_head_dim": 64, "ssm_state_size": 128}
    secs = {"ssm.scan": 0.020, "ssm.proj": 0.004, "ssm.conv": 0.002,
            "ssm.gate": 0.001, "head": 0.007, "moe.experts": 0.006}
    rec, cell = _traced(secs, 4, [args, args])
    least = 128 * 8 * 64 * 64 * 128 * 4 * 2 / 819e9
    assert read_roof(rec, cell) == pytest.approx(
        100.0 * least * 4 / 0.020)
    assert read_share(rec, cell) == pytest.approx(100.0 * 0.027 / 0.040)
    # the parent's program, or another model's: no state phases or spans
    rec, cell = _traced({"head": 0.01, "ffn": 0.02}, 4,
                        [{"tokens": 3}])
    assert read_roof(rec, cell) is None and read_share(rec, cell) is None
    assert read_roof({}, cell) is None and read_share({}, cell) is None
