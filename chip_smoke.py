#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the main path once, through the entry points a user
would call, at the full width of GPT-3 1.3B (hidden 2048, 16 heads of
128, 24 layers, full vocabulary, bf16; random weights from a seed):

* **kernels** — every Pallas kernel the other two phases can select on a
  chip, compiled (not interpreted) at the phases' shapes and compared
  with its XLA reference;
* **train** — the ``bench.py`` headline configuration through
  ``pt.jit.TrainStep``: a few single steps and one ``run_steps(4, ...)``
  on a repeated batch; the loss must be finite and fall;
* **serve** — the same-width model behind ``pt.serving.ServingEngine``
  (``start()``, concurrent ``submit``/``stream``), once with
  ``block_size=16`` and once with ``block_size=128``; every stream must
  equal ``model.generate()`` token for token. Then the looped step:
  Ouro-2.6B whole (48 layers run 4 times, keys and values a pass and
  layer) behind the same engine, every streamed token within
  ``OURO_MARGIN`` of the best logit of its plain float32 reference. Then
  the latent step: Xing4.0-29B-A4B cut to 1 dense + 5 expert layers at
  published widths (latent KV pages, 64 sigmoid-routed experts, four mHC
  streams) at a reduced page count, held to its reference likewise.

The first act is to require a TPU: any other backend, an unknown
``device_kind``, a non-finite loss, a wrong token or any exception exits
non-zero and prints no result line. The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``. The times it prints are a
first sighting — one run, not a measurement.

The phase bodies are importable functions of a size so that
``tests/test_chip_smoke.py`` can drive them at ``gpt_tiny`` on the CPU
(Pallas kernels interpreted there); the *script* runs only on a chip.
"""
from __future__ import annotations

import gc
import json
import os
import sys
import threading
import time

import numpy as np

# ---------------------------------------------------------------- sizes
# What the script runs on the chip. The CPU test passes its own.
TRAIN_SIZE = dict(batch=8, seq=1024, single_steps=3, chained=4)
SERVE_SIZE = dict(max_slots=4, prefill_chunk=32, pool_tokens=4096,
                  prompt_lens=(7, 40, 70, 40, 7), max_new_tokens=8)
SERVE_BLOCK_SIZES = (16, 128)
# a page of 128 tokens is 192 MiB in Ouro-2.6B's 192 cache layers: 8 pages
# beside 5 GiB of weights
OURO_SERVE_SIZE = dict(block_size=128, max_slots=4, prefill_chunk=32,
                       pool_tokens=1024, max_seq_len=1024,
                       prompt_lens=(7, 40, 70, 40, 7), max_new_tokens=8)
# random weights as the serving cell's configuration starts them
# (benchmark/configs/ouro-2p6b.json, ``assumed``). The cell's margin
# against float32 is 0.12 at contexts of 100 to 550 tokens
# (benchmark/traffic/reason_closed6.json; PERF.md); the 40 tokens here
# sit at contexts under 80, where bf16's error is about twice that: twice
# the margin. A token picked without regard to the reference lies about
# 3.8 under its best logit (49,152 logits of spread 0.9).
OURO_INIT = dict(sublayer_norm_init=0.102, value_channel_spread=1.5)
OURO_MARGIN = 0.25
# Xing4.0-29B-A4B as its serving cell cuts it and starts its random
# weights (benchmark/configs/xing4-29b-a4b-l6.json, ``reduced`` and
# ``assumed``: 1 dense + 5 expert layers at published widths, 8.93 GiB;
# with independent experts, ``expert_init_spread`` 1, one rounding-made
# change of a token's fourth expert reads 1.5 under the reference's best
# logit here), at a reduced page count: 64 latent pages of 128 tokens are
# 60 MiB in the 6 pools. The margin is the cell's
# (benchmark/traffic/longdoc_closed24.json; PERF.md section 2).
XING4_SERVE_SIZE = dict(block_size=128, max_slots=4, prefill_chunk=32,
                        pool_tokens=8192, max_seq_len=1024,
                        prompt_lens=(7, 40, 70, 40, 7), max_new_tokens=8)
XING4_CUT = dict(num_layers=6, first_k_dense_replace=1,
                 expert_init_spread=0.05, query_init_scale=3.0)
XING4_MARGIN = 0.3
KERNEL_SIZE = dict(
    flash=((8, 1024), (4, 2048)),         # (batch, seq) at heads x head_dim
    heads=16, head_dim=128,
    page=128, pages=32, pages_per_seq=16, rows=4, tokens=36,
    norm_rows=8192, hidden=2048,
    moe=dict(tokens=8192, hidden=2048, dff=2816, experts=8, topk=2),
    # the ragged step of the serving cell (benchmark serve_chat_1p3b):
    # 48 slots x 16 pages over a pool of 240, token budget 304
    cell=dict(rows=48, pages=240, pages_per_seq=16, tokens=304, chunk=256),
)


def model_config(pt, num_layers=None):
    """GPT-3 1.3B as ``bench.py`` trains it; only depth may be cut."""
    cfg = pt.models.gpt3_1p3B(dropout=0.0, attention_dropout=0.0,
                              recompute=False, lm_ce_chunks=8)
    if num_layers is not None:
        cfg.num_layers = num_layers
    return cfg


# --------------------------------------------------------------- device
def require_tpu():
    """First act: a TPU with published peaks, or an error that names
    what jax found. Returns the ``device`` object of the result line."""
    import jax

    from paddle_tpu.device.peaks import require_chip

    dev = require_chip()
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def _versions():
    from importlib import metadata

    import jax
    import jaxlib

    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu, "python": sys.version.split()[0]}


def _peak_bytes():
    """The allocator's high-water mark. On TPU it counts live buffers
    (parameters, optimizer state, pools) but not the temporaries XLA
    reserves inside a running program, so it is a floor on the peak."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return None if not stats else int(stats["peak_bytes_in_use"])


class _CacheCounter:
    """Counts JAX's persistent-compilation-cache hits and misses through
    its own monitoring events, so a second run can show the cache hit."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _rel_err(got, ref):
    """Max abs error over the reference's max magnitude: one scale-free
    number per comparison, in float32."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    if got.shape != ref.shape:
        raise AssertionError("shape %s != reference %s"
                             % (got.shape, ref.shape))
    if not np.isfinite(got).all():
        raise AssertionError("non-finite values")
    return float(np.abs(got - ref).max() / (np.abs(ref).max() + 1e-12))


# -------------------------------------------------------------- kernels
def ragged_cell_batches(rows, pages, pages_per_seq, tokens, chunk, page,
                        seed=0):
    """Three ragged batches as the engine packs them — rows indexed by
    slot, every decode token first on the flat axis, then the prefill
    chunk, padding after it — ``{name: (bt, cl, ql, qs)}``:

    * ``chat``: every slot but one decoding, contexts long-tailed as the
      chat traffic's, and one ``chunk``-token prefill chunk, the second
      of its prompt; 79 % of the pool live (190 of 240 pages);
    * ``prefill_heavy``: three decode rows at 55-87 % of the longest
      context, one chunk that ends at 75 % of it, the other slots idle;
    * ``one_decode``: one decode row a quarter full, the others idle."""
    rng = np.random.default_rng(seed)
    cap = pages_per_seq * page

    def batch(decode_ctx, chunk_ctx=None):
        """decode_ctx: {slot: context}; chunk_ctx: (slot, context)."""
        bt = np.zeros((rows, pages_per_seq), np.int32)
        cl, ql, qs = (np.zeros(rows, np.int32) for _ in range(3))
        for cursor, (s, ctx) in enumerate(sorted(decode_ctx.items())):
            qs[s], ql[s], cl[s] = cursor, 1, ctx
        if chunk_ctx is not None:
            s, ctx = chunk_ctx
            qs[s], ql[s], cl[s] = len(decode_ctx), chunk, ctx
        assert int(ql.sum()) <= tokens
        free = iter(rng.permutation(pages))
        for s in range(rows):
            n = -(-int(cl[s]) // page)
            bt[s, :n] = [next(free) for _ in range(n)]
        return bt, cl, ql, qs

    slots = rng.permutation(rows)
    chunk_ctx = min(2 * chunk, cap)
    longest = max(1, pages_per_seq * 13 // 16)     # chat: 1-13 pages of 16
    live = min(round(0.79 * pages) - -(-chunk_ctx // page),
               longest * (rows - 1))
    w = rng.lognormal(0.0, 0.8, rows - 1)
    npg = np.minimum(1 + ((live - rows + 1) * w / w.sum()).astype(int),
                     longest)
    while npg.sum() < live:
        npg[rng.choice(np.flatnonzero(npg < longest))] += 1
    chat = {int(s): int((n - 1) * page + rng.integers(1, page + 1))
            for s, n in zip(slots[1:], npg)}
    return {
        "chat": batch(chat, (int(slots[0]), chunk_ctx)),
        "prefill_heavy": batch(
            {int(s): int(f * cap) for s, f in
             zip(slots[1:4], (0.55, 0.70, 0.87))},
            (int(slots[0]), max(chunk, int(0.75 * cap)))),
        "one_decode": batch({int(slots[1]): max(1, cap // 4 - 12)}),
    }


def kernels_phase(size=KERNEL_SIZE, dtype="bfloat16", tol=2e-2):
    """Compile each Pallas kernel (interpreted off-TPU) and compare it
    with its XLA reference. Returns ``{kernel: rel_err}``; raises on the
    first mismatch, compile error or non-finite value."""
    import importlib

    import jax
    import jax.numpy as jnp

    from paddle_tpu.incubate.nn.functional.flash_attention import \
        _xla_attention
    from paddle_tpu.incubate.nn.pallas import flash_attn, norms
    from paddle_tpu.incubate.nn.pallas.moe_dispatch import moe_ffn_sorted

    paged = importlib.import_module(
        "paddle_tpu.incubate.nn.pallas.paged_attention")
    dt = jnp.dtype(dtype)
    rng = np.random.default_rng(0)
    out = {}

    def arr(*shape, scale=1.0, d=dt):
        return jnp.asarray(rng.standard_normal(shape) * scale, d)

    def check(name, got, ref):
        err = _rel_err(got, ref)
        out[name] = round(err, 5)
        if err > tol:
            raise AssertionError("kernel %s: rel err %.4g > %.4g vs its "
                                 "XLA reference" % (name, err, tol))

    # ---- flash attention, forward and backward
    h, d = size["heads"], size["head_dim"]
    for b, s in size["flash"]:
        q, k, v, w = (arr(b, s, h, d) for _ in range(4))

        def fwd_bwd(attn):
            """(out, (dq, dk, dv)) for cotangent ``w``, one compile."""
            def f(q, k, v):
                out, vjp = jax.vjp(attn, q, k, v)
                return out, vjp(w)
            return jax.jit(f)(q, k, v)

        out_p, g_p = fwd_bwd(lambda q, k, v: flash_attn.flash_attention(
            q, k, v, causal=True))
        out_x, g_x = fwd_bwd(lambda q, k, v: _xla_attention(q, k, v, True))
        check("flash_fwd_s%d" % s, out_p, out_x)
        for nm, a, r in zip(("dq", "dk", "dv"), g_p, g_x):
            check("flash_bwd_s%d_%s" % (s, nm), a, r)

    # ---- paged pools: one mixed ragged batch of four rows (a decode row,
    # a prefill chunk with history, an idle row, a prefill from empty,
    # then padding tokens) and one decode batch
    page, pages, pps = size["page"], size["pages"], size["pages_per_seq"]
    rows, T = size["rows"], size["tokens"]
    assert rows == 4, "the mixed batch below is written for four rows"
    kp, vp = arr(h, pages, page, d), arr(h, pages, page, d)
    n_pg = min(pages // rows, pps)                 # pages per row
    cap = n_pg * page                              # tokens a row can hold
    bt = np.zeros((rows, pps), np.int32)
    bt[:, :n_pg] = rng.permutation(pages)[:rows * n_pg].reshape(rows, n_pg)
    n1 = (T - 1) // 2
    n3 = T - 2 - n1                                # leaves one padding token
    ql = np.asarray([1, n1, 0, n3], np.int32)
    cl = np.minimum(cap, [2 * page + 44, page + 22 + n1, 0, n3]) \
        .astype(np.int32)
    qs = np.concatenate([[0], np.cumsum(ql)[:-1]]).astype(np.int32)
    qr = arr(T, h, d)
    meta = (jnp.asarray(bt), jnp.asarray(cl), jnp.asarray(ql))
    for tag, kk, vv in (("fp", kp, vp),
                        ("int8", paged.quantize_kv_pages(kp),
                         paged.quantize_kv_pages(vp))):
        got, ref = (paged.ragged_paged_attention(
            qr, kk, vv, *meta, q_starts=jnp.asarray(qs), use_kernel=uk)
            for uk in (True, False))
        check("ragged_" + tag, got, ref)
    # ---- the same kernel at the serving cell's shape, the chat batch
    c = size["cell"]
    kc, vc = arr(h, c["pages"], page, d), arr(h, c["pages"], page, d)
    bt_c, cl_c, ql_c, qs_c = ragged_cell_batches(page=page, **c)["chat"]
    qc = arr(c["tokens"], h, d)
    for tag, kk, vv in (("fp", kc, vc),
                        ("int8", paged.quantize_kv_pages(kc),
                         paged.quantize_kv_pages(vc))):
        got, ref = (paged.ragged_paged_attention(
            qc, kk, vv, jnp.asarray(bt_c), jnp.asarray(cl_c),
            jnp.asarray(ql_c), q_starts=jnp.asarray(qs_c), use_kernel=uk)
            for uk in (True, False))
        check("ragged_cell_" + tag, got, ref)
        out["ragged_cell_%s_max_abs" % tag] = round(float(np.abs(
            np.asarray(got, np.float32) - np.asarray(ref, np.float32))
            .max()), 5)
    qd = arr(rows, h, d)
    lens = jnp.asarray(np.minimum(cap, [1, page, page + 7, 0]), jnp.int32)
    got, ref = (paged.paged_attention(qd, kp, vp, meta[0], lens,
                                      use_kernel=uk) for uk in (True, False))
    check("paged_decode", got, ref)

    # ---- sorted-dispatch MoE FFN: Pallas grouped GEMM vs lax.ragged_dot
    m = size["moe"]
    x = arr(m["tokens"], m["hidden"])
    probs = jax.nn.softmax(arr(m["tokens"], m["experts"], d=jnp.float32), -1)
    w1 = arr(m["experts"], m["hidden"], 2 * m["dff"], scale=0.02)
    w2 = arr(m["experts"], m["dff"], m["hidden"], scale=0.02)
    ffn = jax.jit(moe_ffn_sorted, static_argnames=("k", "impl"))
    check("moe_ffn_sorted",
          ffn(x, probs, w1, w2, k=m["topk"], impl="pallas"),
          ffn(x, probs, w1, w2, k=m["topk"], impl="ragged"))

    # ---- norms
    xn = arr(size["norm_rows"], size["hidden"])
    wn, bn = arr(size["hidden"]), arr(size["hidden"])
    check("rms_norm", jax.jit(norms.rms_norm)(xn, wn),
          norms._rms_ref(xn, wn, None, 1e-6))
    check("layer_norm", jax.jit(norms.layer_norm)(xn, wn, bn),
          norms._ln_ref(xn, wn, bn, 1e-5))
    return out


# ---------------------------------------------------------------- train
def build_train_step(cfg, batch, seq, dtype="bfloat16", **step_kw):
    """``bench.py``'s headline build: the model in ``dtype``, AdamW with
    a bf16 first moment and a factored second, ``TrainStep`` with grad
    clipping, and one random batch. ``step_kw`` (a mesh, batch specs)
    goes to ``TrainStep``."""
    import paddle_tpu as pt
    from paddle_tpu.jit import TrainStep

    pt.seed(0)
    pt.set_default_dtype(dtype)
    try:
        model = pt.models.GPTForCausalLM(cfg)
    finally:
        pt.set_default_dtype("float32")
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             parameters=model.parameters(),
                             factored_v=True, moment_dtype="bfloat16")
    step = TrainStep(model, opt, grad_clip_norm=1.0, **step_kw)
    rng = np.random.default_rng(0)
    ids, labels = (pt.to_tensor(rng.integers(0, cfg.vocab_size,
                                             (batch, seq)), dtype="int64")
                   for _ in range(2))
    return model, step, ids, labels


def train_phase(cfg, batch, seq, single_steps=3, chained=4,
                dtype="bfloat16"):
    """``bench.py``'s ``_build`` through ``TrainStep``: ``single_steps``
    single dispatches and one ``run_steps(chained, ...)`` on one repeated
    batch. Loss finite throughout and lower at the end than at the
    start. Returns the phase report."""
    from paddle_tpu.incubate.nn.functional.flash_attention import \
        attention_impl

    model, step, ids, labels = build_train_step(cfg, batch, seq, dtype)

    # which attention the step resolves to, and what the lowered program
    # really contains: one flash forward and one fused backward per layer
    impl = attention_impl((batch, seq, cfg.num_heads, cfg.head_dim), seq,
                          cfg.head_dim)
    hlo = step.lower(ids, labels).as_text()
    flash_fwd = hlo.count('kernel_name = "flash_fwd"')
    flash_bwd = hlo.count('kernel_name = "flash_bwd_fused"')
    del hlo

    losses, times = [], []
    for _ in range(single_steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels)))    # float() waits
        times.append(time.perf_counter() - t0)
    t0 = time.perf_counter()
    last = float(step.run_steps(chained, ids, labels))
    chained_first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    last = float(step.run_steps(chained, ids, labels))
    chained_s = time.perf_counter() - t0
    losses.append(last)

    if not all(np.isfinite(losses)):
        raise AssertionError("train: non-finite loss %r" % (losses,))
    if not losses[-1] < losses[0]:
        raise AssertionError("train: loss did not fall on a repeated "
                             "batch: %r" % (losses,))
    step_s = min(times[1:]) if len(times) > 1 else times[0]
    report = {
        "params": sum(int(np.prod(p.shape)) for p in model.parameters()),
        "layers": cfg.num_layers, "batch": batch, "seq": seq,
        "losses": [round(x, 4) for x in losses],
        "attention_impl": impl,
        "flash_fwd_kernels": flash_fwd, "flash_bwd_kernels": flash_bwd,
        "compile_s": round(times[0] - step_s, 2),
        "step_s": round(step_s, 4),
        "run_steps_compile_s": round(chained_first_s - chained_s, 2),
        "run_steps_step_s": round(chained_s / chained, 4),
        "peak_bytes_in_use": _peak_bytes(),
    }
    del step, model
    gc.collect()
    return report


# ---------------------------------------------------------------- serve
def build_serve_model(cfg, dtype="bfloat16"):
    import paddle_tpu as pt

    pt.seed(11)
    pt.set_default_dtype(dtype)
    cls = {pt.models.OuroConfig: pt.models.OuroForCausalLM,
           pt.models.Xing4Config: pt.models.Xing4ForCausalLM} \
        .get(type(cfg), pt.models.GPTForCausalLM)
    try:
        model = cls(cfg)
    finally:
        pt.set_default_dtype("float32")
    model.eval()
    return model


def serve_references(model, prompts, max_new_tokens):
    """``model.generate()`` greedy stream for each prompt: what every
    engine stream must equal token for token."""
    import paddle_tpu as pt

    return [model.generate(pt.to_tensor(np.asarray([p], np.int64)),
                           max_new_tokens=max_new_tokens)
            .numpy()[0].tolist() for p in prompts]


def reference_shortfall(model, prompts, outs, family):
    """How far under the best logit of the family's plain float32
    reference (``benchmark/references/<family>.py``) the streamed tokens
    lie at worst, each stream teacher-forced through the reference."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        family + "_reference", os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "benchmark",
            "references", family + ".py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)

    ids = np.zeros((len(prompts), max(len(p) + len(o) for p, o in
                                      zip(prompts, outs))), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        ids[i, :len(p) + len(o)] = p + o
    lg = np.asarray(ref.logits(
        {n: p.value for n, p in model.named_parameters()}, ids,
        model.config.published()))
    worst = 0.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = lg[i, len(p) - 1:len(p) - 1 + len(o)]
        worst = max(worst, float(
            (rows.max(-1) - rows[np.arange(len(o)), o]).max()))
    return worst


def serve_phase(model, prompts, refs, block_size, max_slots, prefill_chunk,
                pool_tokens, max_new_tokens, max_seq_len=None,
                stream_timeout_s=600.0):
    """The model behind a started ``ServingEngine``: all prompts
    submitted at once, one consumer thread per stream. Streams equal
    ``refs`` (where the caller has them: ``None`` leaves the streams to
    the caller), exactly one ragged compile, the pools the engine was
    built with donated to its steps (off the CPU), and the pool drains
    on ``shutdown()``. Returns the phase report."""
    import jax

    import paddle_tpu as pt

    eng = pt.serving.ServingEngine(
        model, max_slots=max_slots, block_size=block_size,
        num_blocks=max(pool_tokens // block_size, 1),
        prefill_chunk=prefill_chunk, max_seq_len=max_seq_len)
    first_pools = eng._kp + eng._vp
    outs = [None] * len(prompts)
    errors = []

    def consume(i, rid):
        try:
            outs[i] = list(eng.stream(rid))
        except Exception as e:       # re-raised on the main thread below
            errors.append((i, e))

    t0 = time.perf_counter()
    eng.start()
    try:
        rids = [eng.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts]
        threads = [threading.Thread(target=consume, args=(i, r),
                                    daemon=True)
                   for i, r in enumerate(rids)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=stream_timeout_s)
        if any(th.is_alive() for th in threads):
            raise AssertionError("serve: a stream did not end within "
                                 "%.0f s" % stream_timeout_s)
        if errors:
            raise AssertionError("serve: stream %d failed: %r"
                                 % errors[0]) from errors[0][1]
        wall = time.perf_counter() - t0
        if refs is not None and outs != refs:
            raise AssertionError(
                "serve(block_size=%d): stream != generate(): %r vs %r"
                % (block_size, outs, refs))
        if eng.ragged_compiles != 1:
            raise AssertionError("serve: ragged step compiled %d times"
                                 % eng.ragged_compiles)
        donated = all(p.is_deleted() for p in first_pools)
        if not donated and jax.default_backend() != "cpu":
            raise AssertionError("serve: the steps did not consume the "
                                 "pools they were given (not donated)")
    finally:
        eng.shutdown()               # raises if the pool did not drain
    return {"block_size": block_size, "attention_impl": eng.attention_impl,
            "kv_write": eng.kv_write_impl, "pools_donated": donated,
            "requests": len(prompts), "kv_pools": len(eng._kp),
            "pool_pages": eng.config.num_blocks,
            "prompt_lens": [len(p) for p in prompts],
            "tokens": sum(len(o) for o in outs), "streams": outs,
            "ragged_compiles": eng.ragged_compiles,
            "wall_s_incl_compile": round(wall, 2),
            "pool_drained": True}


def reference_serve_phase(cfg, size, margin, family, dtype="bfloat16"):
    """A model behind the engine, its streams within ``margin`` of its
    family's float32 reference's best logits."""
    size = dict(size)
    model = build_serve_model(cfg, dtype)
    prompts = make_prompts(cfg.vocab_size, size.pop("prompt_lens"))
    rep = serve_phase(model, prompts, None, **size)
    rep["reference_shortfall"] = reference_shortfall(
        model, prompts, rep["streams"], family)
    if not rep["reference_shortfall"] <= margin:
        raise AssertionError(
            "serve[%s]: a streamed token is %.4f under the float32 "
            "reference's best logit (margin %g)"
            % (family, rep["reference_shortfall"], margin))
    rep["layers"] = cfg.num_layers
    return rep


def ouro_serve_phase(cfg, size, margin, dtype="bfloat16"):
    """The looped step: an Ouro model behind the engine."""
    rep = reference_serve_phase(cfg, size, margin, "ouro", dtype)
    rep["passes"] = cfg.total_ut_steps
    return rep


def xing4_serve_phase(cfg, size, margin, dtype="bfloat16"):
    """Latent pages, expert layers and mHC streams in the one step: a
    Xing4.0 model behind the engine; ``kv_pools`` counts one latent pool
    a layer."""
    rep = reference_serve_phase(cfg, size, margin, "xing4", dtype)
    rep["experts"], rep["hc_streams"] = cfg.n_routed_experts, cfg.hc_mult
    return rep


def make_prompts(vocab_size, prompt_lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, vocab_size, n).tolist() for n in prompt_lens]


# ----------------------------------------------------------------- main
def main() -> int:
    device = require_tpu()
    import jax

    import paddle_tpu as pt
    from paddle_tpu.config.compile_cache import place_compile_cache
    from paddle_tpu.core import native

    cache_dir = place_compile_cache()
    cache = _CacheCounter()
    print("chip_smoke: device %s" % json.dumps(device))
    print("chip_smoke: versions %s" % json.dumps(_versions()))
    print("chip_smoke: compile cache dir %s" % cache_dir)
    print("chip_smoke: native tier %s"
          % ("loaded" if native.available() else "NOT loaded "
             "(make -C native failed; pure-Python fallbacks in use)"))
    t_start = time.perf_counter()

    def phase(name, fn, *a, **kw):
        t0 = time.perf_counter()
        rep = fn(*a, **kw)
        print("chip_smoke: phase %s ok in %.1f s: %s"
              % (name, time.perf_counter() - t0, json.dumps(rep)),
              flush=True)
        return rep

    phase("kernels", kernels_phase)

    cfg = model_config(pt)
    print("chip_smoke: model GPT-3 1.3B width: hidden %d, heads %d x %d, "
          "layers %d (full depth), vocab %d, bf16"
          % (cfg.hidden_size, cfg.num_heads, cfg.head_dim,
             cfg.num_layers, cfg.vocab_size))
    train = phase("train", train_phase, cfg, **TRAIN_SIZE)
    if train["attention_impl"] != "pallas" or \
            train["flash_fwd_kernels"] != cfg.num_layers or \
            train["flash_bwd_kernels"] != cfg.num_layers:
        raise AssertionError(
            "train: attention did not run the Pallas flash kernel forward "
            "and backward in every layer: %r" % (train,))

    sv = dict(SERVE_SIZE)
    prompt_lens = sv.pop("prompt_lens")
    model = build_serve_model(cfg)
    prompts = make_prompts(cfg.vocab_size, prompt_lens)
    t0 = time.perf_counter()
    refs = serve_references(model, prompts, sv["max_new_tokens"])
    print("chip_smoke: generate() references in %.1f s"
          % (time.perf_counter() - t0), flush=True)
    print("chip_smoke: serve pool of %d tokens of KV, donated to the "
          "engine's steps" % sv["pool_tokens"])
    impls = {}
    for bs in SERVE_BLOCK_SIZES:
        rep = phase("serve[block_size=%d]" % bs, serve_phase, model,
                    prompts, refs, block_size=bs, **sv)
        impls[bs] = rep["attention_impl"], rep["kv_write"]
    if impls[128] != ("pallas", "pallas"):
        raise AssertionError("serve: block_size=128 did not resolve to "
                             "the Pallas ragged kernel and the in-place "
                             "KV write: %r" % (impls,))
    del model
    gc.collect()
    ouro = phase("serve[ouro-2.6b]", ouro_serve_phase,
                 pt.models.ouro_2p6B(**OURO_INIT),
                 OURO_SERVE_SIZE, OURO_MARGIN)
    if (ouro["attention_impl"], ouro["kv_write"]) != ("pallas", "pallas"):
        raise AssertionError("serve[ouro]: did not resolve to the Pallas "
                             "ragged kernel and the in-place KV write: %r"
                             % (ouro,))

    del ouro
    gc.collect()
    xing4 = phase("serve[xing4.0-29b-a4b, 6 layers]", xing4_serve_phase,
                  pt.models.xing4_29B_A4B(**XING4_CUT), XING4_SERVE_SIZE,
                  XING4_MARGIN)
    if (xing4["attention_impl"], xing4["kv_write"]) != ("pallas", "pallas"):
        raise AssertionError("serve[xing4]: did not resolve to the latent "
                             "Pallas kernel and the in-place latent "
                             "write: %r" % (xing4,))

    print("chip_smoke: peak_bytes_in_use %s of bytes_limit %s"
          % (_peak_bytes(),
             jax.devices()[0].memory_stats().get("bytes_limit")))
    print("chip_smoke: compile cache hits %d, misses %d (dir %s)"
          % (cache.hits, cache.misses, cache_dir))
    print("chip_smoke: all phases ok in %.1f s (times are one run, not a "
          "measurement)" % (time.perf_counter() - t_start))
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
