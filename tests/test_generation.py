"""Fused compiled decode path (VERDICT r1 next #8; reference analogs:
fused_multi_transformer / masked_multihead_attention serving kernels +
PaddleNLP generate)."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as pt


def _model(seed=11):
    pt.seed(seed)
    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    return m, cfg


def test_generate_matches_eager_cached_decode():
    """Greedy fused generate == step-by-step eager decode with the
    concat-cache path (same weights, same prompt)."""
    m, cfg = _model()
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (2, 7)).astype(np.int32)
    n_new = 6

    got = m.generate(pt.to_tensor(ids), max_new_tokens=n_new).numpy()

    # eager reference: argmax over logits, concat-cache path
    with pt.no_grad():
        caches = m.init_caches(2)
        logits, caches = m(pt.to_tensor(ids), caches=caches)
        ref = []
        tok = logits.numpy()[:, -1].argmax(-1).astype(np.int32)
        ref.append(tok)
        for _ in range(n_new - 1):
            logits, caches = m(pt.to_tensor(tok[:, None]), caches=caches)
            tok = logits.numpy()[:, -1].argmax(-1).astype(np.int32)
            ref.append(tok)
    ref = np.stack(ref, axis=1)
    np.testing.assert_array_equal(got, ref)


def test_generate_eos_clamps():
    m, cfg = _model()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (1, 5)).astype(np.int32)
    out = m.generate(pt.to_tensor(ids), max_new_tokens=8).numpy()
    eos = int(out[0, 2])  # force the 3rd generated token to be "eos"
    out2 = m.generate(pt.to_tensor(ids), max_new_tokens=8,
                      eos_token_id=eos).numpy()
    seen = False
    for t in out2[0]:
        if seen:
            assert t == eos  # everything after eos is clamped
        if t == eos:
            seen = True


def test_generate_top_p_valid_tokens():
    m, cfg = _model()
    rng = np.random.RandomState(2)
    ids = rng.randint(0, cfg.vocab_size, (2, 4)).astype(np.int32)
    out = m.generate(pt.to_tensor(ids), max_new_tokens=5,
                     temperature=0.8, top_p=0.9).numpy()
    assert out.shape == (2, 5)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_predictor_from_model_generate():
    from paddle_tpu import inference

    m, cfg = _model()
    pred = inference.Predictor.from_model(m)
    rng = np.random.RandomState(3)
    ids = rng.randint(0, cfg.vocab_size, (1, 6)).astype(np.int32)
    out = pred.generate(ids, max_new_tokens=4)
    assert out.shape == (1, 4)
    ref = m.generate(pt.to_tensor(ids), max_new_tokens=4).numpy()
    np.testing.assert_array_equal(out, ref)


def test_generate_temperature_one_samples():
    """T=1.0 with top_p=None must SAMPLE (advisor r2 medium #1), not
    silently argmax."""
    m, cfg = _model()
    rng = np.random.RandomState(5)
    ids = rng.randint(0, cfg.vocab_size, (4, 6)).astype(np.int32)
    greedy = m.generate(pt.to_tensor(ids), max_new_tokens=8,
                        temperature=0.0).numpy()
    sampled = m.generate(pt.to_tensor(ids), max_new_tokens=8,
                         temperature=1.0).numpy()
    # With an untrained model the logit distribution is near-uniform over
    # the vocab; 32 sampled tokens matching argmax exactly is ~impossible.
    assert not np.array_equal(greedy, sampled)


def test_generate_rejects_overlong():
    m, cfg = _model()
    ids = np.zeros((1, cfg.max_position_embeddings - 2), np.int32)
    with pytest.raises(ValueError):
        m.generate(pt.to_tensor(ids), max_new_tokens=8)


def _llama_model(seed=13):
    pt.seed(seed)
    cfg = pt.models.llama_tiny()
    m = pt.models.LlamaForCausalLM(cfg)
    m.eval()
    return m, cfg


def test_llama_generate_matches_eager_cached_decode():
    """Greedy fused Llama generate == step-by-step eager decode (GQA +
    rope + RMSNorm adapter; VERDICT r2 next #7)."""
    m, cfg = _llama_model()
    rng = np.random.RandomState(1)
    ids = rng.randint(0, cfg.vocab_size, (2, 7)).astype(np.int32)
    n_new = 6

    got = m.generate(pt.to_tensor(ids), max_new_tokens=n_new).numpy()

    with pt.no_grad():
        caches = m.init_caches(2)
        logits, caches = m(pt.to_tensor(ids), caches=caches)
        ref = []
        tok = logits.numpy()[:, -1].argmax(-1).astype(np.int32)
        ref.append(tok)
        for _ in range(n_new - 1):
            logits, caches = m(pt.to_tensor(tok[:, None]), caches=caches)
            tok = logits.numpy()[:, -1].argmax(-1).astype(np.int32)
            ref.append(tok)
    ref = np.stack(ref, axis=1)
    np.testing.assert_array_equal(got, ref)


def _brute_force_beams(m, ids, n_new, K, vocab):
    """Exhaustive beam search over the eager forward as reference."""
    import itertools

    with pt.no_grad():
        best = {}
        for b in range(ids.shape[0]):
            beams = [((), 0.0)]
            for t in range(n_new):
                cand = []
                for seq, sc in beams:
                    full = np.concatenate(
                        [ids[b], np.array(seq, np.int32)])[None]
                    lg = m(pt.to_tensor(full.astype(np.int32))).numpy()
                    lp = lg[0, -1].astype(np.float64)
                    lp = lp - lp.max()
                    lp = lp - np.log(np.exp(lp).sum())
                    for v in range(vocab):
                        cand.append((seq + (v,), sc + lp[v]))
                cand.sort(key=lambda x: -x[1])
                beams = cand[:K]
            best[b] = beams[0][0]
    return np.stack([np.array(best[b], np.int32)
                     for b in range(ids.shape[0])])


def test_beam_search_matches_brute_force():
    """beam-width-4 compiled beam search == exhaustive reference on a
    tiny vocab (VERDICT r2 next #7 done-criterion)."""
    from paddle_tpu.models.gpt import GPTConfig

    pt.seed(21)
    cfg = GPTConfig(vocab_size=32, hidden_size=64, num_layers=2,
                    num_heads=4, max_position_embeddings=64, dropout=0.0,
                    attention_dropout=0.0)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(2)
    ids = rng.randint(0, cfg.vocab_size, (2, 5)).astype(np.int32)
    got = m.beam_search(pt.to_tensor(ids), max_new_tokens=3,
                        num_beams=4).numpy()
    ref = _brute_force_beams(m, ids, 3, 4, cfg.vocab_size)
    np.testing.assert_array_equal(got, ref)


def test_llama_beam_search_runs():
    m, cfg = _llama_model()
    rng = np.random.RandomState(3)
    ids = rng.randint(0, cfg.vocab_size, (2, 6)).astype(np.int32)
    out = m.beam_search(pt.to_tensor(ids), max_new_tokens=5,
                        num_beams=4).numpy()
    assert out.shape == (2, 5)
    assert (out >= 0).all() and (out < cfg.vocab_size).all()
    # beam-1 greedy beam search == greedy generate
    b1 = m.beam_search(pt.to_tensor(ids), max_new_tokens=5,
                       num_beams=1).numpy()
    g = m.generate(pt.to_tensor(ids), max_new_tokens=5).numpy()
    np.testing.assert_array_equal(b1, g)


def test_int8_weight_quant_decode():
    """Weight-only int8 decode (VERDICT r3 weak #4): logits track the bf16
    path closely and the quant cache is reused deterministically."""
    import paddle_tpu as pt
    from paddle_tpu.models import generation as G
    from paddle_tpu.models.gpt import GPTConfig

    cfg = GPTConfig(vocab_size=128, hidden_size=512, num_layers=2,
                    num_heads=4, max_position_embeddings=64)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    ad = m.decode_adapter()
    w = ad.weights
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 128, (2, 8)),
                      jnp.int32)
    x, _, _ = ad.prefill(w, ids, 16)
    lg_fp = np.asarray(ad.logits(w, x[:, -1]))
    w2 = dict(w)
    w2["lm_head"] = w["wte"].T
    qw = G._quantize_tree(w2)
    x2, _, _ = ad.prefill(qw, ids, 16)
    lg_q = np.asarray(ad.logits(qw, x2[:, -1]))
    corr = np.corrcoef(lg_fp.ravel(), lg_q.ravel())[0, 1]
    assert corr > 0.995, corr
    # whole-generation path runs and is deterministic across calls
    out1 = m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=4,
                      weight_quant="int8")
    out2 = m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=4,
                      weight_quant="int8")
    np.testing.assert_array_equal(out1.numpy(), out2.numpy())
    # int8 payloads actually present in the cached quant tree
    q = m._gen_quant_w
    assert q["layers"][0]["qkv_w"]["q8"].dtype == jnp.int8


def test_int8_kv_cache_decode():
    """int8 KV cache (VERDICT r4 next #5; reference surface:
    masked_multihead_attention cache_k/v_quant_scales): greedy tokens
    track the bf16-cache path and the cache really holds int8."""
    m, cfg = _model()
    rng = np.random.RandomState(3)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (3, 8))
                       .astype(np.int32))
    ref = m.generate(ids, max_new_tokens=12).numpy()
    got = m.generate(ids, max_new_tokens=12, kv_cache_quant="int8").numpy()
    assert (got == ref).mean() > 0.8, (got, ref)
    # adapter-level: quantized cache representation is int8 + scales
    ad = m.decode_adapter()
    _, ck, _ = ad.prefill(ad.weights, jnp.asarray(ids.numpy()), 16,
                          kv_quant=True)
    assert ck[0]["q8"].dtype == jnp.int8
    # head-major layout [b, nh, T, hd]; scales [b, nh, T]
    assert ck[0]["s"].shape == ck[0]["q8"].shape[:-1]
    # dequant error of the written rows is within int8 resolution
    _, ck_fp, _ = ad.prefill(ad.weights, jnp.asarray(ids.numpy()), 16)
    deq = ck[0]["q8"].astype(np.float32) * ck[0]["s"][..., None]
    err = np.abs(deq - np.asarray(ck_fp[0], np.float32))[:, :, :8]
    scale = np.abs(np.asarray(ck_fp[0], np.float32))[:, :, :8].max()
    assert err.max() <= scale / 127.0 + 1e-6


def test_int8_kv_cache_llama_gqa():
    from paddle_tpu.models.llama import LlamaConfig

    pt.seed(5)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, intermediate_size=128)
    m = pt.models.LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(4)
    ids = pt.to_tensor(rng.randint(0, 256, (2, 6)).astype(np.int32))
    ref = m.generate(ids, max_new_tokens=10).numpy()
    got = m.generate(ids, max_new_tokens=10, kv_cache_quant="int8").numpy()
    assert (got == ref).mean() > 0.8


def test_speculative_generate_exact_greedy():
    """Speculative decode returns EXACTLY the greedy tokens (the
    correctness contract of speculative sampling with temperature 0),
    for both draft modes, with per-row acceptance (batch of different
    prompts)."""
    m, cfg = _model()
    rng = np.random.RandomState(7)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (3, 9))
                       .astype(np.int32))
    ref = m.generate(ids, max_new_tokens=15).numpy()

    toks, stats = pt.models.speculative_generate(
        m, ids, max_new_tokens=15, gamma=3, draft_layers=1,
        return_stats=True)
    np.testing.assert_array_equal(toks.numpy(), ref)
    assert stats["iterations"] >= 1
    assert 0.0 <= stats["mean_accepted"] <= 3.0

    pt.seed(23)
    draft = pt.models.GPTForCausalLM(cfg)
    draft.eval()
    toks2 = pt.models.speculative_generate(
        m, ids, max_new_tokens=15, gamma=4, draft_model=draft)
    np.testing.assert_array_equal(toks2.numpy(), ref)


def test_speculative_generate_int8_and_eos():
    m, cfg = _model()
    rng = np.random.RandomState(9)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (2, 6))
                       .astype(np.int32))
    ref = m.generate(ids, max_new_tokens=10, weight_quant="int8",
                     kv_cache_quant="int8").numpy()
    got = pt.models.speculative_generate(
        m, ids, max_new_tokens=10, gamma=2, draft_layers=1,
        weight_quant="int8", kv_cache_quant="int8").numpy()
    np.testing.assert_array_equal(got, ref)
    # eos clamp matches generate's contract
    eos = int(ref[0, 4])
    got2 = pt.models.speculative_generate(
        m, ids, max_new_tokens=10, gamma=2, draft_layers=1,
        weight_quant="int8", kv_cache_quant="int8",
        eos_token_id=eos).numpy()
    seen = False
    for t in got2[0]:
        if seen:
            assert t == eos
        if t == eos:
            seen = True


def test_speculative_generate_llama():
    from paddle_tpu.models.llama import LlamaConfig

    pt.seed(13)
    cfg = LlamaConfig(vocab_size=256, hidden_size=64, num_layers=3,
                      num_heads=4, num_kv_heads=2, intermediate_size=128)
    m = pt.models.LlamaForCausalLM(cfg)
    m.eval()
    rng = np.random.RandomState(8)
    ids = pt.to_tensor(rng.randint(0, 256, (2, 5)).astype(np.int32))
    ref = m.generate(ids, max_new_tokens=9).numpy()
    got = pt.models.speculative_generate(
        m, ids, max_new_tokens=9, gamma=2, draft_layers=1).numpy()
    np.testing.assert_array_equal(got, ref)


def test_speculative_generate_arg_validation():
    m, cfg = _model()
    ids = pt.to_tensor(np.zeros((1, 4), np.int32))
    with pytest.raises(ValueError):
        pt.models.speculative_generate(m, ids)  # no draft
    with pytest.raises(ValueError):
        pt.models.speculative_generate(m, ids, draft_layers=99)
    with pytest.raises(ValueError):
        pt.models.speculative_generate(m, ids, draft_layers=1, gamma=0)


def test_int4_weight_quant_decode():
    """Weight-only int4 with group-wise scales (reference:
    nn/quant/quantized_linear.py weight_only_linear weight_dtype='int4'):
    logits track fp closely at the adapter level; lm_head stays int8;
    nibbles are stored as int8 and activated to jnp.int4 inside the
    compiled program."""
    from paddle_tpu.models import generation as G
    from paddle_tpu.models.gpt import GPTConfig

    pt.seed(21)
    cfg = GPTConfig(vocab_size=256, hidden_size=256, num_layers=2,
                    num_heads=4, max_position_embeddings=64)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    ad = m.decode_adapter()
    w = dict(ad.weights)
    w["lm_head"] = w["wte"].T
    qw = G._quantize_tree(w, bits=4)
    assert "q4i8" in qw["layers"][0]["qkv_w"]
    assert "q8" in qw["lm_head"]          # head stays int8
    ids = jnp.asarray(np.random.RandomState(0).randint(0, 256, (2, 8)),
                      jnp.int32)
    x, _, _ = ad.prefill(w, ids, 16)
    lg_fp = np.asarray(ad.logits(w, x[:, -1]))
    aq = G._activate_q4(qw)
    assert aq["layers"][0]["qkv_w"]["q4"].dtype == jnp.int4
    x2, _, _ = ad.prefill(aq, ids, 16)
    lg_q = np.asarray(ad.logits(aq, x2[:, -1]))
    corr = np.corrcoef(lg_fp.ravel(), lg_q.ravel())[0, 1]
    assert corr > 0.95, corr
    # whole path runs + deterministic; spec decode matches its greedy
    out1 = m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=6,
                      weight_quant="int4", kv_cache_quant="int8")
    out2 = m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=6,
                      weight_quant="int4", kv_cache_quant="int8")
    np.testing.assert_array_equal(out1.numpy(), out2.numpy())
    ref4 = m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=6,
                      weight_quant="int4").numpy()
    sp4 = pt.models.speculative_generate(
        m, pt.to_tensor(np.asarray(ids)), max_new_tokens=6, gamma=2,
        draft_layers=1, weight_quant="int4").numpy()
    np.testing.assert_array_equal(sp4, ref4)
    with pytest.raises(ValueError):
        m.generate(pt.to_tensor(np.asarray(ids)), max_new_tokens=4,
                   weight_quant="int2")


def test_beam_search_quant_tiers():
    """Beam search rides the same serving quant tiers as generate
    (weight int8/int4 + int8 KV): results stay close to the fp beam
    and the quant caches survive the parent-beam reorder gathers."""
    m, cfg = _model(seed=17)
    rng = np.random.RandomState(11)
    ids = pt.to_tensor(rng.randint(0, cfg.vocab_size, (2, 6))
                       .astype(np.int32))
    ref = m.beam_search(ids, max_new_tokens=8, num_beams=3).numpy()
    for wq in ("int8", "int4"):
        q = m.beam_search(ids, max_new_tokens=8, num_beams=3,
                          weight_quant=wq,
                          kv_cache_quant="int8").numpy()
        assert q.shape == ref.shape
        assert (q == ref).mean() > 0.6, (wq, q, ref)
    # beam-1 quant beam search == quant greedy generate (exact contract)
    b1 = m.beam_search(ids, max_new_tokens=8, num_beams=1,
                       weight_quant="int8", kv_cache_quant="int8").numpy()
    g = m.generate(ids, max_new_tokens=8, weight_quant="int8",
                   kv_cache_quant="int8").numpy()
    np.testing.assert_array_equal(b1, g)


# ------------------------------------------- the adapters' cache forms
# DecodeAdapter runs one layer body a model over four cache forms; these
# hold the forms to each other directly, logit row by logit row, on one
# teacher-forced token sequence a batch row.

_PAGE, _PAGES_PER_SEQ = 8, 4
_TOKENS, _PROMPT = 13, 7


def _tiny(name):
    pt.seed(11)
    kw = dict(dropout=0.0, attention_dropout=0.0) \
        if name == "gpt_tiny" else {}
    cfg = getattr(pt.models, name)(**kw)
    cls = {"gpt_tiny": pt.models.GPTForCausalLM,
           "llama_tiny": pt.models.LlamaForCausalLM,
           "ouro_tiny": pt.models.OuroForCausalLM}[name]
    m = cls(cfg)
    m.eval()
    if name == "ouro_tiny":
        # an exit gate that is not nought, so the passes differ a token
        rng = np.random.RandomState(3)
        for n, p in m.named_parameters():
            if "early_exit_gate" in n:
                p.set_value(rng.normal(0, 0.3, p.shape).astype("float32"))
    ad = m.decode_adapter()
    ids = np.random.RandomState(5).randint(
        0, cfg.vocab_size, (2, _TOKENS)).astype(np.int32)
    return ad, ad.weights, ids


def _ragged_rows(ad, w, ids, spans):
    """Teacher-force ``ids`` [b, n] through ``ragged_chunk`` on empty
    paged pools, row r of the batch in pages of its own, ``spans``
    giving the (start, stop) token span each step runs for every row.
    -> logits [b, n, V]."""
    b = ids.shape[0]
    shape = (ad.num_kv_heads, b * _PAGES_PER_SEQ, _PAGE, ad.head_dim)
    kp = tuple(jnp.zeros(shape, ad.dtype) for _ in range(ad.cache_layers))
    vp = tuple(jnp.zeros(shape, ad.dtype) for _ in range(ad.cache_layers))
    bt = jnp.arange(b * _PAGES_PER_SEQ, dtype=jnp.int32) \
        .reshape(b, _PAGES_PER_SEQ)
    T = b * max(e - s for s, e in spans) + 3          # some padding
    rows = []
    for s, e in spans:
        n = e - s
        toks = np.zeros(T, np.int32)
        pos = np.full(T, -1, np.int32)
        row_of = np.full(T, -1, np.int32)
        for r in range(b):
            toks[r * n:(r + 1) * n] = ids[r, s:e]
            pos[r * n:(r + 1) * n] = np.arange(s, e)
            row_of[r * n:(r + 1) * n] = r
        lg, kp, vp = ad.ragged_chunk(
            w, jnp.asarray(toks), jnp.asarray(pos), jnp.asarray(row_of),
            jnp.arange(b, dtype=jnp.int32) * n,
            jnp.full((b,), n, jnp.int32), jnp.full((b,), e, jnp.int32),
            kp, vp, bt)
        rows.append(np.asarray(lg[:b * n]).reshape(b, n, -1))
    return np.concatenate(rows, axis=1)


_ADAPTERS = ["gpt_tiny", "llama_tiny", "ouro_tiny"]


@pytest.mark.parametrize("name", _ADAPTERS)
def test_prefill_and_step_match_ragged_chunk(name):
    ad, w, ids = _tiny(name)
    x, ck, cv = ad.prefill(w, jnp.asarray(ids[:, :_PROMPT]), _TOKENS)
    dense = [np.asarray(ad.logits(w, x))]
    for p in range(_PROMPT, _TOKENS):
        lg, ck, cv = ad.step(w, jnp.asarray(ids[:, p]), jnp.int32(p), ck,
                             cv, jnp.arange(_TOKENS) <= p)
        dense.append(np.asarray(lg)[:, None])
    paged = _ragged_rows(
        ad, w, ids,
        [(0, _PROMPT)] + [(p, p + 1) for p in range(_PROMPT, _TOKENS)])
    np.testing.assert_allclose(np.concatenate(dense, axis=1), paged,
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", _ADAPTERS)
def test_chunk_step_matches_ragged_chunk(name):
    ad, w, ids = _tiny(name)
    g = 3
    spans = [(s, s + g) for s in range(_PROMPT, _TOKENS, g)]
    _, ck, cv = ad.prefill(w, jnp.asarray(ids[:, :_PROMPT]), _TOKENS)
    dense = []
    for s, e in spans:
        pos = jnp.broadcast_to(jnp.arange(s, e), (ids.shape[0], g))
        lg, ck, cv = ad.chunk_step(w, jnp.asarray(ids[:, s:e]), pos, ck,
                                   cv)
        dense.append(np.asarray(lg))
    paged = _ragged_rows(ad, w, ids, [(0, _PROMPT)] + spans)
    np.testing.assert_allclose(np.concatenate(dense, axis=1),
                               paged[:, _PROMPT:], rtol=2e-4, atol=2e-4)


def test_speculative_generate_ouro_exact_greedy():
    """The looped model has ``chunk_step`` from the base class like any
    other adapter: a draft model's proposals, verified by it, give the
    greedy stream."""
    pt.seed(11)
    cfg = pt.models.ouro_tiny()
    m = pt.models.OuroForCausalLM(cfg)
    m.eval()
    pt.seed(23)
    draft = pt.models.OuroForCausalLM(
        pt.models.ouro_tiny(num_layers=1, total_ut_steps=1))
    draft.eval()
    ids = pt.to_tensor(np.random.RandomState(5).randint(
        0, cfg.vocab_size, (2, 9)).astype(np.int32))
    ref = m.generate(ids, max_new_tokens=12).numpy()
    got, stats = pt.models.speculative_generate(
        m, ids, max_new_tokens=12, gamma=3, draft_model=draft,
        return_stats=True)
    np.testing.assert_array_equal(got.numpy(), ref)
    assert stats["iterations"] >= 1
