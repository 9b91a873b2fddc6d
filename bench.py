#!/usr/bin/env python
"""Flagship benchmark: GPT-3 single-chip full-training-step throughput.

Prints ONE JSON line:
  {"metric": ..., "value": tokens/sec/chip, "unit": "tokens/s",
   "vs_baseline": MFU / 0.45}

vs_baseline is measured MFU over the north-star target (BASELINE.json:
>=45% MFU); >1.0 beats the target. The reference publishes no in-tree
numbers (BASELINE.md), so MFU-vs-north-star is the comparable scalar.

Headline config (round 3): GPT-3-1.3B, batch 8 x seq 1024, bf16 params,
AdamW with bf16 first moment + Adafactor-style factored second moment
(fp32 update math), fused chunked lm_head+CE (8 chunks), NO block
rematerialization — factoring the second moment frees the ~5.3GB that
remat was buying back, so the step does the true 6N FLOPs/token instead
of ~8N. (The figures this configuration was chosen on predate this
round of work, on a rig that no longer exists; PERF.md says what has
been measured on today's code.)

extra carries two sub-benches: a seq-2048 config and a STREAMING variant
feeding fresh per-step batches through run_steps_stream (the headline
with a live input pipeline, VERDICT r2 next #4).

MFU counts the standard 6N FLOPs/token. Every measuring arm (default,
--serving, --cluster) fails without a TPU and for a chip with no entry
in paddle_tpu/device/peaks.py; --multichip also runs as a host-device
dry run under JAX_PLATFORMS=cpu, under a ``_cpu_smoke`` metric name.
"""
from __future__ import annotations

import json
import os
import sys

import numpy as np

from paddle_tpu.config import knobs as _knobs
from paddle_tpu.device.peaks import chip_peaks, require_chip
from paddle_tpu.observability import stopwatch as _stopwatch


def _build(pt, cfg, batch, seq, opt_kwargs):
    from paddle_tpu.jit import TrainStep

    pt.set_default_dtype("bfloat16")
    try:
        model = pt.models.GPTForCausalLM(cfg)
    finally:
        pt.set_default_dtype("float32")
    opt = pt.optimizer.AdamW(learning_rate=1e-4, weight_decay=0.01,
                             parameters=model.parameters(), **opt_kwargs)
    step = TrainStep(model, opt, grad_clip_norm=1.0)
    rng = np.random.default_rng(0)
    ids = pt.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                       dtype="int64")
    labels = pt.to_tensor(rng.integers(0, cfg.vocab_size, (batch, seq)),
                          dtype="int64")
    return model, step, ids, labels


def _measure(step, ids, labels, iters):
    # run_steps chains N optimizer steps in ONE dispatch (amortises the
    # per-dispatch host cost); float(loss) reads the scalar back, which
    # waits for the device like block_until_ready does
    loss = step.run_steps(iters, ids, labels)   # warmup/compile
    float(loss)
    # telemetry stopwatch: identical perf_counter window (elapsed is
    # always measured); the observation lands in the registry only when
    # telemetry is enabled
    with _stopwatch("bench.train_window") as sw:
        loss = step.run_steps(iters, ids, labels)
        float(loss)                             # waits for the device
    return sw.elapsed, loss


def _bench_decode(pt, cfg):
    """Serving decode tok/s: whole-generation compiled path, int8/int4
    weights + int8 KV (models/generation.py; reference surfaces:
    weight_only_linear int8/int4, masked_multihead_attention
    cache-quant args). Also one speculative-decode datapoint with its
    measured acceptance — on this RANDOM-INIT model acceptance is low,
    so the number is the mechanism's floor, not its trained-model
    value."""
    import numpy as np

    pt.set_default_dtype("bfloat16")
    try:
        model = pt.models.GPTForCausalLM(cfg)
    finally:
        pt.set_default_dtype("float32")
    model.eval()
    b, plen = 8, 128
    rng = np.random.default_rng(2)
    ids = pt.to_tensor(rng.integers(0, cfg.vocab_size, (b, plen))
                       .astype(np.int32))

    def timed_gen(new, **kw):
        out = model.generate(ids, max_new_tokens=new, **kw)
        _ = out.numpy()
        with _stopwatch("bench.decode_window") as sw:
            out = model.generate(ids, max_new_tokens=new, **kw)
            _ = out.numpy()
        return sw.elapsed

    res = {"batch": b, "prompt": plen}
    for tag, kw in (
            ("int8_kv8", {"weight_quant": "int8",
                          "kv_cache_quant": "int8"}),
            ("int4_kv8", {"weight_quant": "int4",
                          "kv_cache_quant": "int8"})):
        # two-point window 64 vs 192 new tokens: the delta isolates the
        # 128 decode steps at context 192..320 (per-step cost grows
        # with context, so both points must share the workload shape —
        # a wider second point would silently measure a heavier regime)
        t1 = timed_gen(64, **kw)
        t2 = timed_gen(192, **kw)
        per_step = (t2 - t1) / 128
        res[tag] = {"device_tokens_per_s": round(b / per_step, 1),
                    "ms_per_step": round(per_step * 1e3, 3)}

    # speculative decode: one raw datapoint + measured acceptance
    from paddle_tpu.models import speculative_generate

    kw = dict(weight_quant="int8", kv_cache_quant="int8", gamma=4,
              draft_layers=6, return_stats=True)
    out, _ = speculative_generate(model, ids, max_new_tokens=128, **kw)
    _ = out.numpy()
    with _stopwatch("bench.decode_window") as sw:
        out, st = speculative_generate(model, ids, max_new_tokens=128,
                                       **kw)
        _ = out.numpy()
    el = sw.elapsed
    res["speculative_int8"] = {
        "tokens_per_s_raw": round(b * 128 / el, 1),
        "mean_accepted": round(st["mean_accepted"], 3),
        "note": "random-init model: acceptance is the floor; exact-"
                "greedy contract is test-enforced",
    }
    del model
    return res


def _bench_moe():
    """Sorted-dispatch MoE FFN step (incubate/nn/pallas/moe_dispatch.py)
    on the chip — the driver-visible MoE entry (VERDICT r4 #5)."""
    import functools

    import jax
    import jax.lax as lax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.incubate.nn.pallas.moe_dispatch import moe_ffn_sorted

    S, M, DFF, E, K = 8192, 2048, 2816, 8, 2
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(S, M), jnp.bfloat16)
    probs = jax.nn.softmax(jnp.asarray(rng.randn(S, E), jnp.float32), -1)
    w1 = jnp.asarray(rng.randn(E, M, 2 * DFF) * 0.02, jnp.bfloat16)
    w2 = jnp.asarray(rng.randn(E, DFF, M) * 0.02, jnp.bfloat16)

    # weights ride as jit ARGS — closure constants would be inlined
    # into the HLO as multi-MB literals
    @functools.partial(jax.jit, static_argnames="n")
    def chained(xx, pp, a, b2, n):
        def body(c, _):
            return moe_ffn_sorted(c, pp, a, b2, k=K).astype(c.dtype), \
                None

        out, _ = lax.scan(body, xx, None, length=n)
        return out

    def run(n):
        out = chained(x, probs, w1, w2, n=n)
        _ = np.asarray(out[:1, :1])
        with _stopwatch("bench.moe_window") as sw:
            out = chained(x, probs, w1, w2, n=n)
            _ = np.asarray(out[:1, :1])
        return sw.elapsed

    t1 = run(8)
    t3 = run(24)
    step = max(t3 - t1, 1e-9) / 16
    flops = 2 * S * K * M * 2 * DFF + 2 * S * K * DFF * M
    return {"tokens": S, "experts": E, "topk": K,
            "step_ms": round(step * 1e3, 3),
            "tflops": round(flops / step / 1e12, 2)}


def _bench_fusion(pt):
    """Operator-fusion sub-bench (paddle_tpu/fusion/): eager
    fused-vs-unfused step_ms per epilogue (one run_op region vs the
    op-by-op composition — same math, so the delta is dispatch count +
    intermediate HBM traffic), quantized-matmul on/off delta, and a
    tiny-GPT train-step fused-vs-``PADDLE_TPU_FUSION=off`` delta (the
    headline number above is the fused-on large-scale datapoint)."""
    import numpy as np

    import paddle_tpu.nn.functional as PF
    from paddle_tpu import fusion

    rng = np.random.default_rng(3)
    B, D, H, reps = 4096, 2048, 8192, 20

    def t(a):
        return pt.to_tensor(np.asarray(a, dtype=np.float32))

    x = t(rng.standard_normal((B, D)) * 0.1)
    w1 = t(rng.standard_normal((D, H)) * 0.02)
    b1 = t(np.zeros(H))
    wu = t(rng.standard_normal((D, H)) * 0.02)
    wn = t(np.ones(D))
    y = t(rng.standard_normal((B, D)) * 0.1)
    res_in = t(rng.standard_normal((B, D)) * 0.1)

    def timed(fn):
        fn().numpy()                     # warmup: compile eager kernels
        with _stopwatch("bench.fusion_window") as sw:
            out = None
            for _ in range(reps):
                out = fn()
            out.numpy()                  # waits for the device
        return sw.elapsed / reps * 1e3

    pairs = {
        "bias_gelu": (
            lambda: fusion.linear_gelu(x, w1, b1),
            lambda: PF.gelu(PF.linear(x, w1, b1), approximate=True)),
        "swiglu": (
            lambda: fusion.swiglu_linear(x, w1, wu),
            lambda: PF.silu(pt.matmul(x, w1)) * pt.matmul(x, wu)),
        "add_rms_norm": (
            lambda: fusion.add_rms_norm(y, res_in, wn)[0],
            lambda: PF.rms_norm(res_in + y, weight=wn)),
        "dropout_add": (
            lambda: fusion.dropout_add(y, res_in, p=0.1, training=True),
            lambda: res_in + PF.dropout(y, p=0.1, training=True)),
    }
    out = {"mode": fusion.mode(), "mm_quant": fusion.mm_quant()}
    for name, (fused, unfused) in pairs.items():
        f_ms, u_ms = timed(fused), timed(unfused)
        out[name] = {"fused_ms": round(f_ms, 3),
                     "unfused_ms": round(u_ms, 3),
                     "speedup": round(u_ms / f_ms, 3) if f_ms else 0.0}

    dense_ms = timed(lambda: PF.linear(x, w1))
    quant = {"dense_ms": round(dense_ms, 3)}
    modes = ["int8"] + (["fp8"] if fusion.quant.fp8_supported() else [])
    for qm in modes:
        q_ms = timed(lambda qm=qm: fusion.quantized_linear(x, w1, mode=qm))
        quant[f"{qm}_ms"] = round(q_ms, 3)
        quant[f"{qm}_speedup"] = round(dense_ms / q_ms, 3) if q_ms else 0.0
    out["quant_matmul"] = quant

    # train-level fused-vs-off delta at tiny scale (bounded bench time)
    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    train = {}
    for tag, mode in (("fused", "on"), ("unfused", "off")):
        with fusion.override(fusion=mode, quant_mode="off"):
            _, stp, ids, labels = _build(pt, cfg, 2, 128, {})
            el, _ = _measure(stp, ids, labels, 2)
        train[f"{tag}_step_ms"] = round(el / 2 * 1e3, 2)
    train["speedup"] = round(
        train["unfused_step_ms"] / train["fused_step_ms"], 3) \
        if train["fused_step_ms"] else 0.0
    out["train_tiny"] = train
    return out


def _slo_verdict(report):
    """Slim per-objective verdict for the bench JSON, read straight
    off an SLOEngine report — the SAME rolling windows the dashboard
    uses, no parallel bespoke math."""
    return {"state": report["state"],
            "objectives": {
                name: {"state": o["state"],
                       "value": round(o["value_slow"], 4),
                       "threshold": o["threshold"],
                       "burn_slow": round(o["burn_slow"], 2),
                       "samples": o["samples"]}
                for name, o in report["objectives"].items()}}


def _round_attribution(att):
    return {k: (round(v, 2) if isinstance(v, float) else v)
            for k, v in att.items()}


def _bench_serving():
    """Continuous-batching serving bench: seeded Poisson arrivals
    streamed through ServingEngine. Emits tokens/s plus p50/p99
    per-token latency and TTFT (JSON, same shape as the training
    bench), plus the request-log latency attribution and
    rolling-window SLO verdicts. Fails without a chip."""
    import threading
    import time

    import paddle_tpu as pt

    require_chip()

    # the serving arms run with telemetry ON: the attribution and SLO
    # sections below come from the request-scoped windows
    pt.observability.enable()
    cfg = pt.models.gpt3_125M(dropout=0.0, attention_dropout=0.0)
    n_req, max_new, rate = 48, 64, 24.0
    slots, blocks, metric = 16, 2048, "serving_tokens_per_s_chip"
    pt.seed(0)
    model = pt.models.GPTForCausalLM(cfg)
    model.eval()
    eng = pt.serving.ServingEngine(model, max_slots=slots, block_size=16,
                                   num_blocks=blocks, prefill_chunk=32)
    eng.start()
    rng = np.random.default_rng(1234)       # seeded arrival trace
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 48))).tolist()
               for _ in range(n_req)]
    gaps = rng.exponential(1.0 / rate, n_req)

    # warmup request pays the step compile(s) outside the timed window
    wid = eng.submit(prompts[0], max_new_tokens=4)
    for _ in eng.stream(wid):
        pass

    ttfts, tok_gaps = [], []
    lock = threading.Lock()

    def consume(rid, t_submit):
        last = None
        for _tok in eng.stream(rid):
            now = time.monotonic()
            with lock:
                if last is None:
                    ttfts.append(now - t_submit)
                else:
                    tok_gaps.append(now - last)
            last = now

    threads = []
    with _stopwatch("bench.serving_window") as sw:
        for p, g in zip(prompts, gaps):
            time.sleep(float(g))
            ts = time.monotonic()
            rid = eng.submit(p, max_new_tokens=max_new)
            th = threading.Thread(target=consume, args=(rid, ts))
            th.start()
            threads.append(th)
        for th in threads:
            th.join()
    wall = sw.elapsed
    ragged_compiles = eng.ragged_compiles
    preempts = eng.scheduler.preemptions
    attribution = _round_attribution(eng.request_log.attribution())
    slo = _slo_verdict(eng.slo.evaluate())
    snap_path = _knobs.get_str("PADDLE_TPU_OPS_SNAPSHOT")
    if snap_path:
        eng.dump_ops_snapshot(snap_path)
    eng.shutdown()
    total = n_req * max_new
    print(json.dumps({
        "metric": metric,
        "value": round(total / wall, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "extra": {
            "requests": n_req, "max_new_tokens": max_new,
            "poisson_rate_req_per_s": rate,
            "arrival_rate_req_per_s": rate, "seed": 1234,
            "slots": slots, "wall_s": round(wall, 3),
            "ttft_p50_ms": round(1e3 * float(np.percentile(ttfts, 50)), 2),
            "ttft_p99_ms": round(1e3 * float(np.percentile(ttfts, 99)), 2),
            "token_latency_p50_ms": round(
                1e3 * float(np.percentile(tok_gaps, 50)), 2),
            "token_latency_p99_ms": round(
                1e3 * float(np.percentile(tok_gaps, 99)), 2),
            "ragged_compiles": ragged_compiles,
            "preemptions": preempts,
            "shed": 0,      # single engine, no admission control
            "attribution": attribution,
            "slo": slo,
        },
    }))
    return 0


def _bench_cluster():
    """Multi-replica cluster bench: seeded Poisson arrivals swept
    across offered rates into saturation through the prefix-affinity
    router. Emits the saturated aggregate tokens/s plus a degradation
    curve — per sweep point: achieved tokens/s, p50/p99 TTFT, shed
    rate, preemptions. Rates auto-scale off a measured capacity probe
    (1 replica vs N), so the curve shape is machine-independent:
    graceful degradation means p99 TTFT stays bounded and shed rate
    rises smoothly past 1.0x offered load, with no cliff.

    A second phase (``extra["ramp"]``) drives the control-plane +
    Autoscaler loop end to end: a seeded Poisson wave at ~2.5x ONE
    replica's capacity into a pool that starts at a single replica,
    with a seeded mid-wave ``hang``. See :func:`_cluster_ramp`."""
    import threading
    import time

    import paddle_tpu as pt
    from paddle_tpu.serving.cluster import (ClusterRouter, Overloaded,
                                            Replica)

    require_chip()
    # telemetry ON: attribution + SLO verdicts read the request-scoped
    # rolling windows of the long-lived sweep router
    pt.observability.enable()
    host_cores = len(os.sched_getaffinity(0)) \
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1)
    n_rep = _knobs.get_int("PADDLE_TPU_CLUSTER_REPLICAS")
    cfg = pt.models.gpt3_125M(dropout=0.0, attention_dropout=0.0)
    n_req, max_new = 48, 64
    slots, blocks = 16, 2048
    metric = "cluster_tokens_per_s_chip"
    pt.seed(0)
    model = pt.models.GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.default_rng(1234)       # seeded arrival trace

    def mk_router(n, max_queue=None):
        reps = [Replica("r%d" % i, model, max_slots=slots,
                        block_size=16, num_blocks=blocks,
                        prefill_chunk=32) for i in range(n)]
        for r in reps:
            r.warmup()                      # compiles outside any window
        return ClusterRouter(reps, max_queue=max_queue)

    def mk_prompts(n):
        return [rng.integers(0, cfg.vocab_size,
                             int(rng.integers(4, 32))).tolist()
                for _ in range(n)]

    # --- capacity probe: all requests offered at once (saturated);
    # best of two trials — peak sustainable rate, not a noisy single
    def capacity(n):
        best = 0.0
        for _ in range(2):
            router = mk_router(n)
            router.start()
            crids = [router.submit(p, max_new_tokens=max_new)
                     for p in mk_prompts(n_req)]
            t0 = time.monotonic()
            toks = sum(len(router.result(c)) for c in crids)
            wall = time.monotonic() - t0
            router.shutdown()
            best = max(best, toks / wall)
        return best

    cap1 = capacity(1)
    capn = capacity(n_rep) if n_rep > 1 else cap1
    cap_req = capn / max_new                # capacity in requests/s

    # --- rate sweep into saturation on one long-lived router; the
    # tight per-replica queue bound is what makes overload shed
    # (typed Overloaded) instead of growing an unbounded backlog
    router = mk_router(n_rep, max_queue=2)
    sweep = []
    for offered in (0.4, 0.8, 1.5, 3.0, 6.0):
        rate = offered * cap_req
        prompts = mk_prompts(n_req)
        due = np.cumsum(rng.exponential(1.0 / rate, n_req))
        ttfts, toks, shed = [], [0], 0
        lock = threading.Lock()

        def consume(crid, t_submit):
            first = True
            for _tok in router.stream(crid):
                with lock:
                    if first:
                        ttfts.append(time.monotonic() - t_submit)
                        first = False
                    toks[0] += 1

        pre0 = sum(r.engine.scheduler.preemptions
                   for r in router.replicas)
        threads = []
        # single-threaded load generator: the SAME loop submits due
        # arrivals (absolute-clock: falling behind the Poisson schedule
        # bursts, never stretches the trace) and steps the replicas, so
        # offered-vs-service is pure queueing — a GIL-starved submit
        # thread can't silently throttle the offered load. Consumers
        # only drain finished tokens off the stream queues.
        with _stopwatch("bench.cluster_window") as sw:
            t_start = time.monotonic()
            i = 0
            while True:
                now = time.monotonic() - t_start
                while i < n_req and float(due[i]) <= now:
                    ts = time.monotonic()
                    try:
                        crid = router.submit(prompts[i],
                                             max_new_tokens=max_new)
                        th = threading.Thread(target=consume,
                                              args=(crid, ts))
                        th.start()
                        threads.append(th)
                    except Overloaded:
                        shed += 1
                    i += 1
                busy = router.step()
                if not busy:
                    if i >= n_req:
                        break
                    left = t_start + float(due[i]) - time.monotonic()
                    if left > 0:
                        time.sleep(min(left, 0.01))
            for th in threads:
                th.join()
        pre = sum(r.engine.scheduler.preemptions
                  for r in router.replicas) - pre0
        pct = (lambda q: round(
            1e3 * float(np.percentile(ttfts, q)), 2)) if ttfts else \
            (lambda q: None)
        sweep.append({
            "offered_x_capacity": offered,
            "arrival_rate_req_per_s": round(rate, 2),
            "tokens_per_s": round(toks[0] / sw.elapsed, 1),
            "ttft_p50_ms": pct(50), "ttft_p99_ms": pct(99),
            "shed": shed, "shed_rate": round(shed / n_req, 3),
            "preemptions": pre,
        })
    # one merged snapshot over router + all replica windows, taken
    # while the sweep router is still live; optionally dumped for
    # ptop --snapshot
    snap = router.ops_snapshot()
    attribution = _round_attribution(snap["attribution"])
    slo = _slo_verdict(snap["slo"])
    snap_path = _knobs.get_str("PADDLE_TPU_OPS_SNAPSHOT")
    if snap_path:
        from paddle_tpu.observability.request_log import write_snapshot
        write_snapshot(snap, snap_path)
    router.shutdown()

    # --- ramp phase: the autoscaled pool under a traffic wave plus a
    # silent replica hang (lease eviction + token-exact replay)
    ramp = _cluster_ramp(pt, model, cfg, rng, slots=slots,
                         blocks=blocks, n_req=n_req, max_new=max_new,
                         cap1=cap1)

    # --- cluster-wide KV cache: long-shared-prefix workload, tier-on
    # vs tier-off (cross-replica index fetch + host-tier restore)
    kv_store = _cluster_kv(pt, model, cfg, rng, slots=slots,
                           blocks=blocks, max_new=max_new)

    print(json.dumps({
        "metric": metric,
        "value": round(capn, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,
        "extra": {
            "replicas": n_rep, "requests_per_point": n_req,
            "max_new_tokens": max_new, "seed": 1234,
            "slots": slots, "max_queue": 2,
            "host_cores": host_cores,
            "capacity_1rep_tokens_per_s": round(cap1, 1),
            "capacity_tokens_per_s": round(capn, 1),
            "scaling_x": round(capn / cap1, 2) if cap1 else 0.0,
            # the replicas share this process's one chip, so
            # scaling_x says how the router behaves, not what N chips
            # would deliver
            "sweep": sweep,
            "attribution": attribution,
            "slo": slo,
            "ramp": ramp,
            "kv_store": kv_store,
        },
    }))
    return 0


def _cluster_ramp(pt, model, cfg, rng, slots, blocks, n_req, max_new,
                  cap1):
    """Autoscale ramp scenario: a seeded Poisson traffic wave offered
    at ~2.5x ONE replica's measured capacity into a pool that starts
    at a single replica behind the shared control plane. Exercises the
    full elastic serving loop on the wall clock:

    * queue pressure, sustained -> scale-out with warm joins (every
      spawned replica must still show exactly ONE ragged compile),
    * a seeded mid-wave ``hang`` — the replica goes silent without
      reporting, so only the missed-lease scan can find it — followed
      by eviction inside the lease budget and token-exact replay of
      its in-flight work onto survivors,
    * the idle tail after the wave -> scale-in back to one replica.

    Token exactness and the recovery bound are asserted (greedy
    decoding makes both deterministic); latency numbers are recorded,
    not asserted, so the bench stays machine-independent. Returns the
    ``extra["ramp"]`` record.
    """
    import threading
    import time

    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.observability.slo import BURN
    from paddle_tpu.serving.cluster import (AutoscaleConfig, Autoscaler,
                                            ClusterControlPlane,
                                            ClusterRouter, Replica)

    knobs = dict(max_slots=slots, block_size=16, num_blocks=blocks,
                 prefill_chunk=32)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, 32))).tolist()
               for _ in range(n_req)]

    # greedy references through a single engine (token-exact vs
    # generate() by the serve_smoke invariant) — what the wave must
    # reproduce no matter how the pool scales or fails underneath
    ref = pt.serving.ServingEngine(model, **knobs)
    rrids = [ref.submit(p, max_new_tokens=max_new) for p in prompts]
    while ref.step():
        pass
    refs = [ref.result(r) for r in rrids]
    ref.shutdown()

    lease_s = 1.0
    cp = ClusterControlPlane(lease_timeout=lease_s)
    spawned = []

    # warm standbys, compiled BEFORE the wave: in this single-threaded
    # loop a mid-wave cold compile would stall every replica's beats
    # past the lease and the scan would evict the whole pool (a real
    # warm pool keeps joins off the serving threads the same way)
    standby = [Replica("r%d" % i, model, **knobs) for i in (1, 2, 3)]
    for r in standby:
        r.warmup()

    def spawn(name):
        if standby and standby[0].name == name:
            rep = standby.pop(0)
        else:
            rep = Replica(name, model, **knobs)
            rep.warmup()
        spawned.append(rep)
        return rep

    first = Replica("r0", model, **knobs)
    first.warmup()
    spawned.append(first)
    router = ClusterRouter([first], control_plane=cp)
    scaler = Autoscaler(router, spawn,
                        AutoscaleConfig(min_replicas=1, max_replicas=3,
                                        up_ticks=2, idle_ticks=25,
                                        cooldown_ticks=10, queue_hwm=2))

    rate = 2.5 * cap1 / max_new             # req/s, 2.5x one replica
    due = np.cumsum(rng.exponential(1.0 / rate, n_req))
    hang_i = (2 * n_req) // 3               # arm mid-wave

    ttfts, outs = [], {}
    lock = threading.Lock()
    threads, events = [], []
    state_at_first_up = [None]
    t_hang, t_evict = [None], [None]
    peak = 1

    def consume(idx, crid, t_submit):
        first_tok = True
        got = []
        for tok in router.stream(crid):
            if first_tok:
                with lock:
                    ttfts.append(time.monotonic() - t_submit)
                first_tok = False
            got.append(tok)
        with lock:
            outs[idx] = got

    try:
        t_start = time.monotonic()
        i = 0
        while True:
            now = time.monotonic() - t_start
            while i < n_req and float(due[i]) <= now:
                if i == hang_i:
                    # the NEXT replica step across the pool goes
                    # silent: no death report, beats just stop
                    faults.configure("cluster.replica:hang@1", seed=0)
                    t_hang[0] = time.monotonic()
                ts = time.monotonic()
                crid = router.submit(prompts[i],
                                     max_new_tokens=max_new)
                th = threading.Thread(target=consume,
                                      args=(i, crid, ts))
                th.start()
                threads.append(th)
                i += 1
            busy = router.step()
            ev = scaler.tick()
            if ev is not None:
                events.append(ev)
                if ev["kind"] == "scale_up" and \
                        state_at_first_up[0] is None:
                    state_at_first_up[0] = \
                        router.slo.evaluate()["state"]
            peak = max(peak, router.num_alive())
            if t_hang[0] is not None and t_evict[0] is None and \
                    any(r.hung and not r.alive for r in spawned):
                t_evict[0] = time.monotonic()
            if not busy:
                if i >= n_req and \
                        all(not th.is_alive() for th in threads):
                    break
                assert time.monotonic() - t_start < 120.0, \
                    "ramp failed to drain"
                time.sleep(0.002)
        for th in threads:
            th.join()
        # idle tail: the scaler must walk the pool back to min
        deadline = time.monotonic() + 30.0
        while router.num_alive() > 1 and time.monotonic() < deadline:
            router.step()
            scaler.tick()
            time.sleep(0.001)
    finally:
        faults.reset()

    assert [outs[k] for k in range(n_req)] == refs, \
        "ramp streams diverged from single-engine references"
    assert len(ttfts) == n_req, \
        "%d/%d requests never got a first token" % (len(ttfts), n_req)
    assert peak >= 2, "wave never scaled the pool out"
    assert t_evict[0] is not None, \
        "seeded hang was never evicted via the lease"
    recovery = t_evict[0] - t_hang[0]
    assert recovery <= lease_s + 2.0, \
        "hang->eviction took %.2fs (lease %.1fs)" % (recovery, lease_s)
    assert router.num_alive() == 1, \
        "idle scale-in left %d replicas" % router.num_alive()
    for r in spawned:
        assert r.engine.ragged_compiles == 1, \
            "replica %s compiled ragged %d times (joins must be warm)" \
            % (r.name, r.engine.ragged_compiles)

    pct = (lambda q: round(
        1e3 * float(np.percentile(ttfts, q)), 2)) if ttfts else \
        (lambda q: None)
    ramp = {
        "offered_x_1rep_capacity": 2.5,
        "arrival_rate_req_per_s": round(rate, 2),
        "requests": n_req,
        "ttft_p50_ms": pct(50), "ttft_p99_ms": pct(99),
        "peak_replicas": peak,
        "final_replicas": router.num_alive(),
        "scale_events": [
            {k: (round(v, 3) if isinstance(v, float) else v)
             for k, v in e.items() if k != "t"} for e in events],
        "slo_state_at_first_scale_out": state_at_first_up[0],
        "scaled_out_before_sustained_burn":
            state_at_first_up[0] != BURN,
        "hang_to_eviction_s": round(recovery, 3),
        "lease_timeout_s": lease_s,
        "replay_token_exact": True,          # asserted above
        "warm_joins_one_compile_each": True,  # asserted above
    }
    router.shutdown()
    for r in standby:                        # never-promoted standbys
        r.shutdown()
    return ramp


def _cluster_kv(pt, model, cfg, rng, slots, blocks, max_new):
    """Cluster-wide KV cache workload (``extra["kv_store"]``): a long
    shared system prompt served tier-ON vs tier-OFF through identical
    2-replica routers. Three phases per arm:

    * seed — plant the prefix on r0 through normal serving;
    * cross — saturate r0 (``max_queue=1``) so the next shared-prefix
      request lands on r1: tier-on imports the prefix pages through
      the global index instead of recomputing them;
    * host — force-demote every cached block on both replicas (tier-on
      spills to host RAM, tier-off discards — the pre-tier behavior),
      then serve the prefix again: tier-on promotes from host, tier-off
      recomputes the full prefill.

    Reports prefill tokens saved (the index/host fetches) and the TTFT
    delta per phase. Token parity vs a single tier-off engine and one
    ragged compile per replica are asserted; latency is recorded, not
    asserted, so the bench stays machine-independent."""
    import threading
    import time

    from paddle_tpu.serving.cluster import ClusterRouter, Replica
    from paddle_tpu.serving.kv_store import (ClusterKVStore,
                                             KVStoreConfig)

    # int8 KV pools: the host spill is the pool layout, so tiered
    # streams can stay token-exact vs the recompute references
    knobs = dict(max_slots=slots, block_size=16, num_blocks=blocks,
                 prefill_chunk=32, kv_quant="int8")
    max_new = min(int(max_new), 12)
    shared = rng.integers(0, cfg.vocab_size, 128).tolist()  # 8 blocks
    tails = [rng.integers(0, cfg.vocab_size, int(n)).tolist()
             for n in (6, 9, 13)]
    reqs = [shared + t for t in tails]       # seed / cross / host
    junk = rng.integers(0, cfg.vocab_size, 24).tolist()

    ref = pt.serving.ServingEngine(model, **knobs)
    refs = []
    for p in reqs:
        rid = ref.submit(list(p), max_new_tokens=max_new)
        while ref.step():
            pass
        refs.append(ref.result(rid))
    ref.shutdown()

    def run(tier_on):
        reps = [Replica("r%d" % i, model, **knobs) for i in range(2)]
        for r in reps:
            r.warmup()
        kv = ClusterKVStore(config=KVStoreConfig(
            tier="host", host_mb=64)) if tier_on else None
        router = ClusterRouter(reps, max_queue=1, kv_store=kv)
        outs, ttft = {}, {}
        lock = threading.Lock()

        def consume(crid, key, t0):
            got, first = [], True
            for tok in router.stream(crid):
                if first:
                    with lock:
                        ttft[key] = time.monotonic() - t0
                    first = False
                got.append(tok)
            with lock:
                outs[key] = got

        def drive(key, prompt, prime=None):
            jc = router.submit(junk, max_new_tokens=max_new) \
                if prime else None           # queues on r0, unstepped
            crid = router.submit(list(prompt),
                                 max_new_tokens=max_new)
            th = threading.Thread(target=consume,
                                  args=(crid, key, time.monotonic()))
            th.start()
            while router.step():
                pass
            th.join(timeout=60.0)
            if jc is not None:
                router.result(jc)

        drive("seed", reqs[0])               # prefix lands on r0
        c0 = dict(kv.counts) if kv else {}
        drive("cross", reqs[1], prime=True)  # r0 full -> r1 serves
        c1 = dict(kv.counts) if kv else {}
        # forced demotion sweep: tier-on spills through the pump,
        # tier-off discards (exactly the pre-tier eviction behavior)
        for r in reps:
            with r.engine._lock:
                r.engine.manager.pop_evictable(blocks)
        if kv is not None:
            while kv.pump() > 0:
                pass
        drive("host", reqs[2])               # restore vs recompute
        c2 = dict(kv.counts) if kv else {}
        for r in reps:
            assert r.engine.ragged_compiles == 1, \
                "replica %s compiled ragged %d times" \
                % (r.name, r.engine.ragged_compiles)
        router.shutdown()
        return ([outs[k] for k in ("seed", "cross", "host")],
                {k: round(1e3 * v, 2) for k, v in ttft.items()},
                (c0, c1, c2))

    outs_off, ttft_off, _ = run(tier_on=False)
    outs_on, ttft_on, (c0, c1, c2) = run(tier_on=True)
    assert outs_off == refs, "tier-off streams != references"
    assert outs_on == refs, "tier-on streams != references"
    cross_saved = c1["fetch_tokens"] - c0["fetch_tokens"]
    host_saved = c2["fetch_tokens"] - c1["fetch_tokens"]
    assert c1["fetches_replica"] > c0["fetches_replica"], \
        "cross phase never fetched through the global index"
    assert c2["fetches_host"] > c1["fetches_host"], \
        "host phase never promoted from the host tier"
    return {
        "shared_prefix_tokens": len(shared),
        "requests": len(reqs),
        "cross_replica": {
            "prefill_tokens_saved": cross_saved,
            "ttft_on_ms": ttft_on.get("cross"),
            "ttft_off_ms": ttft_off.get("cross"),
            "ttft_delta_ms": round(ttft_off.get("cross", 0.0)
                                   - ttft_on.get("cross", 0.0), 2),
        },
        "host_restore": {
            "prefill_tokens_saved": host_saved,
            "ttft_on_ms": ttft_on.get("host"),
            "ttft_off_ms": ttft_off.get("host"),
            "ttft_delta_ms": round(ttft_off.get("host", 0.0)
                                   - ttft_on.get("host", 0.0), 2),
        },
        "demoted_blocks": c2["demotes"],
        "crc_failures": c2["crc_failures"],
        "token_parity_vs_tier_off": True,    # asserted above
        "one_ragged_compile_per_replica": True,
    }


def _bench_elastic():
    """Elastic-training bench, three arms:

    1. recovery latency — the seeded 3-process chaos drill
       (tools/elastic_drill.py): kill rank 2 mid-step, survivors commit
       a shrink epoch and resume from peer-replicated snapshots; the
       reported number is kill -> first post-epoch step completion,
       minus the ordinary per-step cost that would have been paid
       anyway.
    2. disk-restore baseline — the PR 3 path this subsystem replaces:
       a fresh process restores the SAME payload through
       CheckpointManager (latest_valid + load), timed end-to-end
       including process start. Peer recovery must beat it.
    3. snapshot overhead — single-rank ElasticDataParallel steps with
       SNAP_FREQ in {1, 10, 50} vs a never-snapshot baseline on a
       ~256 KB parameter set; reports the added % per setting.
    """
    import subprocess
    import tempfile
    import time

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import elastic_drill

    # --- arm 1: chaos drill (asserts its own acceptance criteria)
    with _stopwatch("bench.elastic_window"):
        summary = elastic_drill.main(snap_freq=1)
    recovery_s = float(summary["recovery_wall_s"])

    from paddle_tpu.distributed.elastic import (ElasticConfig,
                                                ElasticDataParallel)
    from paddle_tpu.distributed.resilience.checkpoint_manager import \
        CheckpointManager
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.optimizer.optimizers import Adam

    rng = np.random.default_rng(7)
    base_params = [rng.standard_normal((128, 128)).astype(np.float32)
                   for _ in range(4)]
    payload_bytes = int(sum(p.nbytes for p in base_params))

    # --- arm 2: fresh-process disk restore of an equivalent payload
    repo = os.path.dirname(os.path.abspath(__file__))
    with tempfile.TemporaryDirectory(prefix="elastic_bench_ckpt_") as td:
        mgr = CheckpointManager(td, rank=0, world_size=1)
        mgr.save({"__elastic_state__": {
            "params": [np.asarray(p) for p in base_params],
            "opt": {"m": [np.zeros(p.size, np.float32)
                          for p in base_params],
                    "v": [np.zeros(p.size, np.float32)
                          for p in base_params],
                    "count": 10},
            "step": 10}}, 10, blocking=True)
        code = (
            "import os, sys, time; t0 = time.time();"
            "os.environ.setdefault('JAX_PLATFORMS', 'cpu');"
            f"sys.path.insert(0, {repo!r});"
            "from paddle_tpu.distributed.resilience.checkpoint_manager "
            "import CheckpointManager;"
            f"m = CheckpointManager({td!r}, rank=0, world_size=1);"
            "step, path = m.latest_valid();"
            "state = {'__elastic_state__': None}; m.load(state, path);"
            "assert state['__elastic_state__'] is not None;"
            "print(time.time() - t0)")
        t0 = time.monotonic()
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, check=True)
        disk_wall_s = time.monotonic() - t0
        disk_load_s = float(out.stdout.strip().splitlines()[-1])

    # --- arm 3: snapshot overhead vs a never-snapshot baseline
    def grad_fn(params, X, Y):
        grads = [0.001 * p for p in params]
        return float(sum(float(np.vdot(p, p)) for p in params)), grads

    def data_fn(step):
        z = np.zeros((1, 1), np.float32)
        return z, z

    steps = 40

    def timed_run(freq, ns):
        store = TCPStore("127.0.0.1", 0, is_master=True)
        trainer = ElasticDataParallel(
            store, 0, 1, [p.copy() for p in base_params],
            grad_fn, data_fn, Adam(learning_rate=0.01),
            config=ElasticConfig(snap_freq=freq, beat_interval=0.2,
                                 timeout=10.0),
            namespace=ns)
        t0 = time.monotonic()
        trainer.run(steps)
        wall = time.monotonic() - t0
        trainer.shutdown()
        return wall

    timed_run(steps + 1, "bench_warm")        # pay one-time costs
    never = steps + 1                          # freq > steps: no pushes
    t_base = min(timed_run(never, f"bench_base{i}") for i in range(3))
    overhead = {}
    for freq in (1, 10, 50):
        t = min(timed_run(freq, f"bench_f{freq}_{i}") for i in range(3))
        overhead[str(freq)] = round(100.0 * (t - t_base) / t_base, 1)

    # Failure detection (lease expiry -> shrink commit) is common to
    # both recovery tiers, so the head-to-head is post-detection: the
    # survivors' join+adopt from peer memory vs the PR 3 path's fresh
    # process + CheckpointManager restore of the same payload.
    peer_restore_s = max(float(r["latency_ms"])
                         for r in summary["recoveries"]) / 1e3
    detect_s = float(summary["t_kill_to_shrink_commit_s"])

    print(json.dumps({
        "metric": "elastic_recovery_s_cpu_smoke",
        "value": round(recovery_s, 3),
        "unit": "s",
        "vs_baseline": round(disk_wall_s / peer_restore_s, 2)
        if peer_restore_s > 0 else 0.0,
        "extra": {
            "recovery_wall_s": round(recovery_s, 3),
            "t_kill_to_shrink_commit_s": round(detect_s, 3),
            "step_baseline_s": round(
                float(summary["step_baseline_s"]), 4),
            "epoch_log": summary["epoch_log"],
            "peer_restore_s": round(peer_restore_s, 3),
            "disk_restore_baseline_s": round(disk_wall_s, 3),
            "disk_restore_load_s": round(disk_load_s, 3),
            "beats_disk_restore": peer_restore_s < disk_wall_s,
            "end_to_end_peer_s": round(recovery_s, 3),
            "end_to_end_disk_s": round(detect_s + disk_wall_s, 3),
            "snapshot_overhead_pct": overhead,
            "snapshot_steps": steps,
            "payload_bytes": payload_bytes,
            "drill_snap_freq": 1,
        },
    }))
    return 0


def _bench_ps():
    """Parameter-server bench, four arms:

    1. failover recovery — the seeded 3-process kill drill
       (tools/ps_drill.py): kill the primary server mid-epoch, the
       backup promotes inside the lease budget, and the recommender
       loop finishes bit-exact; reports kill-step extra latency vs an
       ordinary step, head-to-head with a cold process restart.
    2. exactly-once — the in-process lost-ack drill: a ``ps.push``
       fault after delivery forces a retransmit; requires dedup hits
       and a bit-equal table digest vs the clean run.
    3. pull/push throughput — a single-process LocalTransport worker
       hammering one sparse shard; reports rows/s both ways plus
       p50/p99 pull latency.
    4. bounded-capacity eviction — zipfian pushes into a
       capacity-bounded SparseTable; reports the eviction rate and the
       resident-row ceiling holding.
    """
    import time

    tools_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "tools")
    if tools_dir not in sys.path:
        sys.path.insert(0, tools_dir)
    import ps_drill

    # --- arm 1: kill drill (asserts its own acceptance criteria)
    with _stopwatch("bench.ps_window"):
        summary = ps_drill.main()
    recovery_s = float(summary["recovery_wall_s"])
    cold_restart_s = float(summary["cold_restart_s"])
    fo = summary["failovers"][0]

    # --- arm 2: lost-ack retransmit dedup (asserts digest equality)
    dedup = ps_drill.dedup_drill()

    from paddle_tpu.distributed.ps import (LocalTransport, PSServer,
                                           PSWorker)
    from paddle_tpu.distributed.ps.tables import SparseTable

    # --- arm 3: LocalTransport pull/push throughput + pull latency
    dim, batch, rounds = 32, 2048, 30
    srv = PSServer(0, n_servers=1)
    try:
        srv.add_sparse_table(0, dim, optimizer="adagrad", lr=0.1)
        w = PSWorker(1, 1, worker_id="bench",
                     transport=LocalTransport())
        rng = np.random.default_rng(11)
        ids = rng.integers(0, 200_000, size=batch)
        grads = rng.standard_normal((batch, dim)).astype(np.float32)
        w.pull_sparse(0, ids, dim=dim)           # materialize rows
        w.push_sparse(0, ids, grads)             # pay one-time costs
        pull_lat, push_lat = [], []
        for _ in range(rounds):
            t0 = time.perf_counter()
            w.pull_sparse(0, ids, dim=dim)
            pull_lat.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            w.push_sparse(0, ids, grads)
            push_lat.append(time.perf_counter() - t0)
        pull_rows_per_s = batch * rounds / sum(pull_lat)
        push_rows_per_s = batch * rounds / sum(push_lat)
        pull_p50_ms = float(np.percentile(pull_lat, 50)) * 1e3
        pull_p99_ms = float(np.percentile(pull_lat, 99)) * 1e3
    finally:
        srv.shutdown_local()

    # --- arm 4: eviction rate under zipfian skew at bounded capacity
    cap, evict_rounds = 1024, 20
    tbl = SparseTable(16, optimizer="sgd", lr=0.1, seed=0,
                      capacity=cap)
    zrng = np.random.default_rng(13)
    pushed = 0
    for _ in range(evict_rounds):
        zids = zrng.zipf(1.3, size=512) % 100_000
        tbl.push(zids, zrng.standard_normal(
            (512, 16)).astype(np.float32))
        pushed += 512
    ev = tbl.counters()
    assert ev["rows"] <= cap, ev

    print(json.dumps({
        "metric": "ps_failover_recovery_s_cpu_smoke",
        "value": round(recovery_s, 3),
        "unit": "s",
        "vs_baseline": round(cold_restart_s / recovery_s, 2)
        if recovery_s > 0 else 0.0,
        "extra": {
            "recovery_wall_s": round(recovery_s, 3),
            "failover_latency_s": round(float(fo["latency_s"]), 3),
            "failover_budget_s": ps_drill.FAILOVER_S,
            "step_baseline_s": round(
                float(summary["step_baseline_s"]), 4),
            "cold_restart_s": round(cold_restart_s, 3),
            "beats_cold_restart": recovery_s < cold_restart_s,
            "drill_steps": summary["total_steps"],
            "kill_step": summary["kill_step"],
            "push_dedup_hits": dedup["dedup_hits"],
            "dedup_bit_equal": True,     # dedup_drill asserts it
            "pull_rows_per_s": round(pull_rows_per_s, 1),
            "push_rows_per_s": round(push_rows_per_s, 1),
            "pull_p50_ms": round(pull_p50_ms, 3),
            "pull_p99_ms": round(pull_p99_ms, 3),
            "throughput_batch": batch,
            "eviction_rate": round(ev["evictions"] / pushed, 4),
            "evictions": ev["evictions"],
            "resident_rows": ev["rows"],
            "capacity": cap,
        },
    }))
    return 0


def _tp_overlap_result(on_tpu):
    """tp_overlap sub-bench: decomposed ring all-gather-matmul vs the
    serial gather-then-GEMM pair on a 2-device mp mesh.

    The serial arm materializes the full gathered [T, K] operand before
    the GEMM can start; the ring arm streams per-rank blocks, so each
    shift's bytes ride inside the previous block's GEMM (and on host CPU
    it also moves half the gather bytes — the measurable win there).
    Sweeps chunk counts, asserts the steady state never retraces and the
    2-rank ring output is bitwise equal to the serial composition."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.fusion import overlap_mm

    if len(jax.devices()) < 2:
        return {"skipped": True, "reason": "needs >= 2 devices"}
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("mp",))
    if on_tpu:
        T, K, N, iters = 16384, 4096, 1024, 16
    else:
        # host-CPU smoke: bandwidth-bound shape (small N) so the gather
        # buffer traffic, not the GEMM, decides the race
        T, K, N, iters = 8192, 1024, 128, 8
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((T, K)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((K, N)) * 0.05, jnp.float32)

    def timed(fn):
        out = fn(x, w)
        jax.block_until_ready(out)          # warmup pays the compile
        with _stopwatch("bench.tp_overlap_window") as sw:
            for _ in range(iters):
                out = fn(x, w)
            jax.block_until_ready(out)
        return sw.elapsed / iters * 1e3, out

    def _serial(xl, wl):
        return jnp.matmul(jax.lax.all_gather(xl, "mp", tiled=True), wl)

    serial = jax.jit(overlap_mm._shard_map(
        _serial, mesh, (P("mp", None), P(None, "mp")), P(None, "mp")))
    off_ms, ref = timed(serial)

    traces = []

    def _overlap(chunks):
        def fn(a, b):
            traces.append(0)
            return overlap_mm.sharded_all_gather_matmul(
                a, b, mesh=mesh, chunks=chunks)
        return jax.jit(fn)

    sweep = {}
    best = None
    for chunks in (1, 2, 4):
        jov = _overlap(chunks)
        n0 = len(traces)
        ms, out = timed(jov)
        assert len(traces) == n0 + 1, \
            f"tp_overlap chunks={chunks} retraced in steady state"
        # 2-rank ring == serial composition bitwise (every partial sum
        # has exactly two terms) — same contract tests/test_tp_overlap.py
        # enforces on loss and grads
        assert np.array_equal(np.asarray(ref), np.asarray(out)), chunks
        sweep[str(chunks)] = round(ms, 3)
        if best is None or ms < best[1]:
            best = (chunks, ms)

    speedup = off_ms / best[1]
    if not on_tpu:
        assert speedup > 1.0, \
            f"tp_overlap smoke lost to serial: {speedup:.3f}x"
    return {
        "primitive": "all_gather_matmul", "mesh": "mp=2",
        "shape": [T, K, N],
        "off_step_ms": round(off_ms, 3),
        "on_step_ms": round(best[1], 3),
        "on_chunks": best[0],
        "chunk_sweep_ms": sweep,
        "speedup": round(speedup, 3),
    }


def _multichip_result():
    """Body of the multichip pipeline bench (shared with the
    ``dryrun_multichip`` artifact in ``__graft_entry__.py``).

    Runs the SAME pure-function transformer through two pipeline legs on
    ``S`` devices:

    * device leg — :class:`CompiledPipeline`: the whole 1F1B schedule is
      one jit; stage boundaries move by ring ``collective-permute`` and
      grad reduction is bucketed into the backward.
    * host leg — the pre-existing host-driven path: ``StagedProgram`` +
      ``Pipeline1F1BPass.apply`` (eager per-job vjp, host-orchestrated
      stage hops), i.e. what ``_StagedTrainStep`` executes.

    Returns the structured metric dict (tokens/s, MFU, n_devices,
    schedule, speedup_vs_host) instead of a raw stdout tail."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.distributed.passes.pipeline_scheduler_pass import (
        Pipeline1F1BPass, StagedProgram)
    from paddle_tpu.distributed.pipeline import (
        CompiledPipeline, overlap_bucket_bytes)
    from paddle_tpu.observability import profiler as _prof

    # profiling on for the whole leg (this arm owns the process):
    # the PP/DP overlap notes fire at trace time during warmup, the TP
    # note during the tp_overlap sub-bench, and the fenced attribution
    # step at the end reads them all
    _prof.enable_profiling("on")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    n_dev = len(jax.devices())
    S = 2
    if n_dev < S:
        raise SystemExit(
            f"bench --multichip needs >= {S} devices, jax found {n_dev} "
            f"({dev.platform}: {dev.device_kind}); nothing was measured")
    if on_tpu:
        # GPT-3 1.3B width, cut in depth: the one-jit schedule keeps every
        # tick's residuals until its backward, so 12 blocks/stage x 8
        # micro-batches asked for 41.5 GB of a chip's 15.75 (chip run,
        # PR 21). 2 blocks/stage x 4 micro-batches fits.
        hidden, heads, vocab, seq = 2048, 16, 50304, 1024
        B, mb, M, iters = 2, 1, 4, 4      # blocks/stage, micro size/count
    else:
        hidden, heads, vocab, seq = 128, 4, 1024, 128
        B, mb, M, iters = 1, 2, 4, 4
    L, h4 = S * B, 4 * hidden
    rng = np.random.default_rng(0)

    def w(*shape):
        return (rng.standard_normal(shape) * 0.02).astype(np.float32)

    def per_layer():
        return [
            np.ones(hidden, np.float32), np.zeros(hidden, np.float32),
            w(hidden, 3 * hidden), np.zeros(3 * hidden, np.float32),
            w(hidden, hidden), np.zeros(hidden, np.float32),
            np.ones(hidden, np.float32), np.zeros(hidden, np.float32),
            w(hidden, h4), np.zeros(h4, np.float32),
            w(h4, hidden), np.zeros(hidden, np.float32),
        ]

    layers = [per_layer() for _ in range(L)]
    # 12 leaves, each [S, B, ...]: stage s owns layers [s*B, (s+1)*B)
    stacked = [np.stack([np.stack([layers[s * B + b][i] for b in range(B)])
                         for s in range(S)]) for i in range(12)]
    extra = {"wte": w(vocab, hidden), "wpe": w(seq, hidden),
             "lnfw": np.ones(hidden, np.float32),
             "lnfb": np.zeros(hidden, np.float32),
             "head": w(hidden, vocab)}

    def _ln(x, wt, bs):
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * wt + bs

    def _blk(p, x):
        ln1w, ln1b, wqkv, bqkv, wo, bo, ln2w, ln2b, w1, b1, w2, b2 = p
        b, s, d = x.shape
        hd = d // heads
        q, k, v = jnp.split(_ln(x, ln1w, ln1b) @ wqkv + bqkv, 3, axis=-1)

        def sp(t):
            return t.reshape(b, s, heads, hd).transpose(0, 2, 1, 3)

        att = (sp(q) @ sp(k).transpose(0, 1, 3, 2)) * (1.0 / np.sqrt(hd))
        att = jnp.where(np.tril(np.ones((s, s), bool)), att, -1e9)
        o = (jax.nn.softmax(att, -1) @ sp(v)).transpose(0, 2, 1, 3)
        x = x + o.reshape(b, s, d) @ wo + bo
        z = _ln(x, ln2w, ln2b)
        return x + jax.nn.gelu(z @ w1 + b1) @ w2 + b2

    def stage_fn(params, x):
        for i in range(B):
            x = _blk([a[i] for a in params], x)
        return x

    def pre_fn(ex, ids):
        return ex["wte"][ids] + ex["wpe"][None, :]

    def _head_loss(lnfw, lnfb, head, hh, ym):
        z = _ln(hh, lnfw, lnfb) @ head
        lp = jax.nn.log_softmax(z.astype(jnp.float32), -1)
        return -jnp.take_along_axis(lp, ym[..., None], -1).mean()

    def loss_fn(ex, hh, ym):
        return _head_loss(ex["lnfw"], ex["lnfb"], ex["head"], hh, ym)

    gb = M * mb
    ids = jnp.asarray(rng.integers(0, vocab, (gb, seq)), jnp.int32)
    labels = jnp.asarray(rng.integers(0, vocab, (gb, seq)), jnp.int32)

    # ---- device leg: one-jit compiled 1F1B over S devices
    pipe = CompiledPipeline(
        stage_fn, stacked, loss_fn, num_stages=S, num_micro=M,
        optimizer=pt.optimizer.SGD(learning_rate=0.01),
        extra_params=extra, pre_fn=pre_fn)
    # each stage's parameters really sit on its own device (on a TPU
    # host: a real chip each, not a forced host device)
    stage_devs = sorted({sh.device.id
                         for leaf in jax.tree_util.tree_leaves(pipe.params)
                         for sh in leaf.addressable_shards})
    assert len(stage_devs) == S, \
        f"pipeline stages share devices: params on {stage_devs}"
    loss_dev = float(pipe.step(ids, labels))       # warmup: pays the compile
    with _stopwatch("bench.multichip_window") as sw:
        for _ in range(iters):
            last = pipe.step(ids, labels)
        float(last)
        jax.block_until_ready(pipe.params)
    el_dev = sw.elapsed

    # ---- host leg: same math through the host-driven schedule
    host_params = [[jnp.asarray(leaf[s]) for leaf in stacked]
                   for s in range(S)]
    host_params[0] = [jnp.asarray(extra["wte"]),
                      jnp.asarray(extra["wpe"])] + host_params[0]
    host_params[-1] = host_params[-1] + [
        jnp.asarray(extra["lnfw"]), jnp.asarray(extra["lnfb"]),
        jnp.asarray(extra["head"])]

    def host_first(p, xi):
        return stage_fn(p[2:], p[0][xi] + p[1][None, :])

    def host_mid(p, hh):
        return stage_fn(p, hh)

    def host_last(p, hh, ym):
        return _head_loss(p[12], p[13], p[14], stage_fn(p[:12], hh), ym)

    prog = StagedProgram(
        [host_first] + [host_mid] * (S - 2) + [host_last], host_params,
        loss_fn=None, devices=list(jax.devices()[:S]),
        last_takes_label=True)
    sched = Pipeline1F1BPass()
    opt_h = pt.optimizer.SGD(learning_rate=0.01)
    state_h = opt_h.init_state([a for st in prog.params for a in st])
    micros_x = [ids[i * mb:(i + 1) * mb] for i in range(M)]
    micros_y = [labels[i * mb:(i + 1) * mb] for i in range(M)]

    def host_step():
        nonlocal state_h
        loss, grads, _ = sched.apply(prog, micros_x, micros_y)
        flat_p = [a for st in prog.params for a in st]
        flat_g = [g for gs in grads for g in gs]
        new_p, state_h = opt_h.update(flat_p, flat_g, state_h)
        i = 0
        for st in prog.params:
            for j in range(len(st)):
                st[j] = new_p[i]
                i += 1
        return loss

    loss_host = float(host_step())                 # warmup leg symmetry
    with _stopwatch("bench.multichip_window") as sw:
        for _ in range(iters):
            last_h = host_step()
        float(last_h)
        jax.block_until_ready([a for st in prog.params for a in st])
    el_host = sw.elapsed

    n_params = sum(int(np.prod(a.shape)) for a in stacked)
    n_params += sum(int(np.prod(v.shape)) for v in extra.values())
    fpt = 6 * n_params + 6 * L * hidden * seq
    tps = gb * seq * iters / el_dev
    tps_host = gb * seq * iters / el_host
    # MFU only against a chip's published peak; the host-device dry run
    # reports 0 rather than a ratio to a made-up CPU number
    peak = chip_peaks(dev).bf16_flops if on_tpu else 0.0
    mfu = tps * fpt / (peak * S) if peak else 0.0

    # TP overlap sub-bench first: it fires the profiler's "tp" ring
    # note, so the overlap report below covers all three mechanisms
    tp_overlap = _tp_overlap_result(on_tpu)

    # ---- profiled attribution step: one more compiled step, device-
    # fenced between dispatch and drain so the profiler attributes wall
    # time to phases. Runs OUTSIDE the timed windows.
    _prof.configure(flops_per_step=float(fpt) * gb * seq,
                    tokens_per_step=gb * seq,
                    peak_flops=(peak * S) if peak else 0.0)
    rec = _prof.StepRecord(iters + 1)
    rec.mark("data_wait")                     # batch already resident
    loss_prof = pipe.step(ids, labels)
    rec.mark("dispatch")
    jax.block_until_ready(loss_prof)
    rec.mark("device")
    prof_rep = rec.close(tokens=gb * seq)
    segs = prof_rep["segments"]
    wall = prof_rep["wall_s"]
    # the tentpole invariant, asserted on the smoke arm: phase segments
    # sum to the measured step time exactly (fp telescoping only)
    assert abs(sum(segs.values()) - wall) <= 1e-9 + 1e-6 * wall, \
        f"attribution segments {sum(segs.values())} != wall {wall}"
    overlap = _prof.overlap_report()

    metric = ("multichip_pp_train_tokens_per_s_chip" if on_tpu
              else "multichip_pp_tokens_per_s_cpu_smoke")
    res = {
        "metric": metric,
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4) if peak else 0.0,
        "extra": {
            "n_devices": S, "stage_devices": stage_devs,
            "platform": dev.platform, "device_kind": dev.device_kind,
            "schedule": "1F1B-compiled",
            "transport": "device(ppermute)",
            "micro_batches": M, "micro_batch": mb, "seq": seq,
            "params": n_params, "mfu": round(mfu, 4),
            "loss_device": round(loss_dev, 6),
            "loss_host": round(loss_host, 6),
            "host_tokens_per_s": round(tps_host, 1),
            "speedup_vs_host": round(el_host / el_dev, 3),
            "pp_bucket_mb": overlap_bucket_bytes() / float(1 << 20),
            "compiles": pipe.trace_count,
            "tp_overlap": tp_overlap,
            "attribution": {
                "step_mfu": round(prof_rep["mfu"], 4),
                "wall_ms": round(wall * 1e3, 4),
                "segments_ms": {k: round(v * 1e3, 4)
                                for k, v in segs.items()},
            },
            "overlap_efficiency": {
                m: round(o["efficiency"], 4)
                for m, o in sorted(overlap.items())
            },
        },
    }
    # contract checks: one trace total (the profiled extra step must
    # NOT have retraced), and both legs computed the same first-step
    # loss from identical init params
    assert pipe.trace_count == 1, \
        f"compiled pipeline retraced: {pipe.trace_count}"
    assert abs(loss_dev - loss_host) <= 2e-3 * max(1.0, abs(loss_host)), \
        f"leg disparity: device {loss_dev} vs host {loss_host}"
    return res


def _bench_multichip():
    """``--multichip``: one process. On a TPU host it drives the real
    chips; under ``JAX_PLATFORMS=cpu`` it is the host-device dry run (the
    host-platform device count below only affects the CPU backend, and is
    read when that backend is first created — nothing has touched jax
    yet). A parent that re-executed itself would have to stay off jax
    for its child to get the chips; running in-process removes the
    question."""
    from paddle_tpu.distributed.log_utils import install_stderr_filter

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    os.environ.setdefault("PADDLE_TPU_PP_TRANSPORT", "device")
    install_stderr_filter()
    result = _multichip_result()
    print(json.dumps(result))
    return _maybe_perfdiff(result)


def main():
    if "--multichip" in sys.argv:
        return _bench_multichip()
    if "--elastic" in sys.argv:
        return _bench_elastic()
    if "--ps" in sys.argv:
        return _bench_ps()

    import paddle_tpu as pt
    from paddle_tpu.config.compile_cache import place_compile_cache

    place_compile_cache()
    if "--serving" in sys.argv:
        return _bench_serving()
    if "--cluster" in sys.argv:
        return _bench_cluster()

    dev = require_chip()
    small = (_knobs.get_str("PADDLE_TPU_BENCH") or "").lower() == "125m"

    if small:
        cfg = pt.models.gpt3_125M(dropout=0.0, attention_dropout=0.0,
                                  lm_ce_chunks=8)
        batch, seq = 64, 512
        metric = "gpt3_125m_train_tokens_per_sec_chip"
        opt_kwargs = {"factored_v": True, "moment_dtype": "bfloat16"}
        iters = 8
    else:
        cfg = pt.models.gpt3_1p3B(dropout=0.0, attention_dropout=0.0,
                                  recompute=False, lm_ce_chunks=8)
        batch, seq = (8, 1024)
        metric = "gpt3_1p3b_train_tokens_per_sec_chip"
        opt_kwargs = {"factored_v": True, "moment_dtype": "bfloat16"}
        iters = 4

    model, step, ids, labels = _build(pt, cfg, batch, seq, opt_kwargs)
    el, loss = _measure(step, ids, labels, iters)
    tokens_per_sec = batch * seq * iters / el
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    # training FLOPs/token: 6N for the matmuls + causal attention term
    attn_flops = 6 * cfg.num_layers * cfg.hidden_size * seq  # fwd+bwd
    flops_per_token = 6 * n_params + attn_flops
    peak = chip_peaks(dev).bf16_flops
    mfu = tokens_per_sec * flops_per_token / peak

    extra = {
        "device": getattr(dev, "device_kind", str(dev)),
        "batch": batch, "seq": seq, "params": n_params,
        "mfu": round(mfu, 4), "loss": round(float(loss), 4),
        "recompute": bool(getattr(cfg, "recompute", False)),
        "optimizer": "AdamW bf16-m + factored-v (Adafactor rank-1)",
        "lm_ce_chunks": int(getattr(cfg, "lm_ce_chunks", 0)),
    }
    # headline MFU is measured with overlap routing live (auto -> on);
    # single-chip runs have no mp mesh, so the serial GEMMs are untouched
    # and the number stays comparable to earlier rounds
    from paddle_tpu.fusion import overlap_mm as _ov
    extra["tp_overlap"] = {"mode": _ov.mode(),
                           "chunks": _ov.default_chunks()}
    extra["fusion"] = _bench_fusion(pt)

    # flops cross-check (the "MFU is never silently wrong" promise):
    # XLA's own HLO cost model vs the 6N analytic model the headline
    # MFU divides by. >10% disagreement means one of them is lying —
    # flagged on stderr, never silent.
    try:
        ca = step.lower(ids, labels).cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        xla_flops = float(ca.get("flops", 0.0)) if isinstance(ca, dict) \
            else 0.0
    except Exception:
        xla_flops = 0.0
    if xla_flops > 0:
        model_flops = float(flops_per_token) * batch * seq
        div = abs(xla_flops - model_flops) / model_flops
        extra["flops_check"] = {
            "model": model_flops, "xla": xla_flops,
            "divergence": round(div, 4),
        }
        from paddle_tpu.observability import profiler as _prof
        _prof.flops_divergence(model_flops, xla_flops)
        if div > 0.10:
            print(f"bench: WARNING: analytic 6N FLOPs model diverges "
                  f"{div:.1%} from XLA cost analysis "
                  f"(model={model_flops:.3e}, xla={xla_flops:.3e}) — "
                  f"headline MFU is suspect", file=sys.stderr)

    if not small:
        # streaming variant: fresh per-step batches via run_steps_stream
        # (genuine-training throughput next to the same-batch headline)
        rng = np.random.default_rng(1)
        xs = rng.integers(0, cfg.vocab_size, (iters, batch, seq))
        stream_ids = pt.to_tensor(xs, dtype="int64")
        loss_s = step.run_steps_stream(iters, stream_ids, stream_ids)
        float(loss_s)
        xs2 = rng.integers(0, cfg.vocab_size, (iters, batch, seq))
        s_ids2 = pt.to_tensor(xs2, dtype="int64")
        with _stopwatch("bench.train_window") as sw:
            float(step.run_steps_stream(iters, s_ids2, s_ids2))
        el_s = sw.elapsed
        tps_s = batch * seq * iters / el_s
        extra["stream_fresh_data"] = {
            "tokens_per_s": round(tps_s, 1),
            "mfu": round(tps_s * flops_per_token / peak, 4),
            "of_headline": round(tps_s / tokens_per_sec, 3),
        }

        # seq-2048 sub-bench (round-2 weak #1: 0.30 MFU there; round-5:
        # fused single-pass flash bwd + ce-chunks 8 -> 0.667)
        del model, step, ids, labels
        cfg2 = pt.models.gpt3_1p3B(dropout=0.0, attention_dropout=0.0,
                                   recompute=False, lm_ce_chunks=8)
        m2, step2, ids2, labels2 = _build(pt, cfg2, 4, 2048, opt_kwargs)
        el2, _ = _measure(step2, ids2, labels2, iters)
        tps2 = 4 * 2048 * iters / el2
        fpt2 = 6 * n_params + 6 * cfg2.num_layers * cfg2.hidden_size * 2048
        extra["seq2048"] = {
            "batch": 4, "tokens_per_s": round(tps2, 1),
            "mfu": round(tps2 * fpt2 / peak, 4),
        }

        # ---- decode (serving) bench, driver-visible (VERDICT r4 #5):
        # GPT-1.3B b8 plen128, quantized weights + int8 KV cache.
        # Two-point (64 vs 192 new tokens) differencing cancels the
        # fixed prefill, dispatch and read cost, leaving decode step time.
        del m2, step2, ids2, labels2
        extra["decode"] = _bench_decode(pt, cfg2)
        extra["moe"] = _bench_moe()

    result = {
        "metric": metric,
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        # mfu is a fraction (0..1); north star is 0.45 (BASELINE.json)
        "vs_baseline": round(mfu / 0.45, 4),
        "extra": extra,
    }
    print(json.dumps(result))
    return _maybe_perfdiff(result)


def _maybe_perfdiff(result: dict) -> int:
    """Optional regression gate: ``--diff BASE.json`` (or env
    ``PADDLE_TPU_PERFDIFF_BASE``) compares the just-printed result
    against a baseline via tools/perfdiff.py and makes the bench exit
    nonzero on a regression beyond the noise bounds."""
    base = None
    if "--diff" in sys.argv:
        i = sys.argv.index("--diff")
        if i + 1 >= len(sys.argv):
            print("bench: --diff needs a baseline JSON path",
                  file=sys.stderr)
            return 2
        base = sys.argv[i + 1]
    base = base or _knobs.get_str("PADDLE_TPU_PERFDIFF_BASE")
    if not base:
        return 0
    import importlib.util

    pd_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "tools", "perfdiff.py")
    spec = importlib.util.spec_from_file_location("_perfdiff", pd_path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    try:
        old = mod.load_doc(base)
    except ValueError as e:
        print(f"bench: perfdiff baseline unusable: {e}", file=sys.stderr)
        return 2
    regressions, notes = mod.compare(old, result, mod.DEFAULT_NOISE)
    for n in notes:
        print(f"perfdiff ok: {n}", file=sys.stderr)
    for r in regressions:
        print(f"perfdiff REGRESSION: {r}", file=sys.stderr)
    if regressions:
        print(f"bench: {len(regressions)} regression(s) vs {base}",
              file=sys.stderr)
        return 1
    print(f"bench: no regression vs {base}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
