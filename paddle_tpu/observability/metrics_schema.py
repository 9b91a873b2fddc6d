"""Central metric-name schema: every metric the framework emits is
declared HERE, once, with its kind and unit (the analog of the reference's
fixed stats registry phi/core/memory/stats.h — stat names are compile-time
identifiers there; here `tools/check_metric_names.py` lints every
``registry.counter/gauge/histogram("...")`` call site against this table,
and the README observability section is generated from the same rows).

Adding a metric = add a row here + instrument the call site; the lint run
in tier-1 (tests/test_metric_names.py) fails on undeclared names, so the
table cannot rot.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple


class MetricSpec(NamedTuple):
    kind: str                      # "counter" | "gauge" | "histogram"
    unit: str
    desc: str
    buckets: Optional[Tuple[float, ...]] = None  # histograms only
    tags: Tuple[str, ...] = ()     # allowed tag keys


class NamespaceSpec(NamedTuple):
    doc: str
    # True: ptlint's metric-names reverse sweep requires every declared
    # name in the namespace to be recorded at some literal call site
    # (the schema cannot hold dead rows). False: declaration-only lint
    # (names recorded conditionally / from runtime-built strings).
    require_used: bool = True


# every first dotted segment of a METRICS/SPANS key must be declared
# here — the metric-names pass derives its REQUIRE_USED sweep from the
# require_used flags instead of a hand-grown prefix list, and fails on
# keys whose namespace is missing (a typo'd namespace can't slip in as
# a fresh one)
NAMESPACES = {
    "bench":      NamespaceSpec("bench.py harness self-metrics",
                                require_used=False),
    "ckpt":       NamespaceSpec("checkpoint save/restore",
                                require_used=False),
    "cluster":    NamespaceSpec("serving cluster router/replicas"),
    "cp":         NamespaceSpec("control plane: leases + epochs"),
    "decode":     NamespaceSpec("fused single-model decode",
                                require_used=False),
    "device":     NamespaceSpec("device memory/occupancy samples",
                                require_used=False),
    "elastic":    NamespaceSpec("elastic membership + reshard"),
    "engine":     NamespaceSpec("Engine.fit training loop",
                                require_used=False),
    "fleet":      NamespaceSpec("fleet executor actors",
                                require_used=False),
    "fusion":     NamespaceSpec("operator-fusion routing",
                                require_used=False),
    "jit":        NamespaceSpec("jit compile/recompile tracking",
                                require_used=False),
    "kv":         NamespaceSpec("cluster KV store: index + host tier"),
    "moe":        NamespaceSpec("mixture-of-experts dispatch",
                                require_used=False),
    "pg":         NamespaceSpec("process-group collectives",
                                require_used=False),
    "pipeline":   NamespaceSpec("pipeline schedules", require_used=False),
    "pp":         NamespaceSpec("pipeline transport + grad sync",
                                require_used=False),
    "prof":       NamespaceSpec("sampled step profiler"),
    "ps":         NamespaceSpec("parameter-server tier"),
    "resilience": NamespaceSpec("retry/fault-injection substrate",
                                require_used=False),
    "rpc":        NamespaceSpec("rpc transport", require_used=False),
    "rt":         NamespaceSpec("request-scoped serving telemetry"),
    "serving":    NamespaceSpec("single-replica serving engine"),
    "slo":        NamespaceSpec("rolling-window SLO engine"),
    "tp":         NamespaceSpec("tensor-parallel overlap",
                                require_used=False),
    "train":      NamespaceSpec("training health/grad-norm",
                                require_used=False),
    "xla":        NamespaceSpec("XLA compile/memory ledgers",
                                require_used=False),
}


# fixed bucket boundaries (seconds) — histograms never grow buckets at
# runtime, so exposition stays O(1) and mergeable across snapshots
TIME_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
                60.0)
TOKEN_LATENCY_BUCKETS = (1e-5, 2.5e-5, 5e-5, 1e-4, 2.5e-4, 5e-4, 1e-3,
                         2.5e-3, 5e-3, 0.01, 0.025, 0.05, 0.1, 0.5, 1.0)
# millisecond-scale boundaries for elastic step/recovery latencies —
# a recovery budget is PADDLE_TPU_ELASTIC_TIMEOUT seconds, so the tail
# buckets must resolve multi-second waits without losing the sub-ms
# fast path
ELASTIC_MS_BUCKETS = (0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 25.0, 50.0,
                      100.0, 250.0, 500.0, 1000.0, 2500.0, 5000.0,
                      10000.0, 30000.0, 60000.0)

METRICS = {
    # ---- Engine.fit (distributed/auto_parallel/engine.py)
    "engine.step_time": MetricSpec(
        "histogram", "s", "wall time per Engine.fit step incl. the "
        "device->host loss sync", TIME_BUCKETS),
    "engine.steps": MetricSpec(
        "counter", "steps", "optimizer steps run by Engine.fit"),
    "engine.tokens_per_s": MetricSpec(
        "gauge", "tokens/s", "last-step training throughput (batch "
        "elements x seq when the input is [b, s], else batch elements)"),
    "engine.loss": MetricSpec(
        "gauge", "loss", "last training loss seen by Engine.fit"),
    "engine.pp_bubble_fraction": MetricSpec(
        "gauge", "fraction", "schedule-analytic pipeline bubble fraction "
        "(pp-1)/(m*vpp+pp-1) when pp_degree>1; 0 for zero-bubble"),
    # ---- fused decode (models/generation.py)
    "decode.prefill_time": MetricSpec(
        "histogram", "s", "prefill dispatch wall time per generate() call "
        "(telemetry-enabled two-phase path)", TIME_BUCKETS),
    "decode.decode_time": MetricSpec(
        "histogram", "s", "decode-scan dispatch wall time per generate() "
        "call", TIME_BUCKETS),
    "decode.token_latency": MetricSpec(
        "histogram", "s/token", "per-token decode latency "
        "(decode_time / decoded tokens)", TOKEN_LATENCY_BUCKETS),
    "decode.prefill_tokens": MetricSpec(
        "counter", "tokens", "prompt tokens prefilled"),
    "decode.decode_tokens": MetricSpec(
        "counter", "tokens", "tokens produced by the decode scan"),
    "decode.cache_hit": MetricSpec(
        "counter", "calls", "generate()/beam/speculative compiled-fn "
        "cache hits"),
    "decode.cache_miss": MetricSpec(
        "counter", "compiles", "generate()/beam/speculative compiled-fn "
        "cache misses (fresh trace+compile)"),
    "decode.spec_acceptance_rate": MetricSpec(
        "gauge", "tokens/iter", "speculative decoding: mean accepted "
        "draft tokens per verify pass"),
    "decode.spec_tokens_per_pass": MetricSpec(
        "gauge", "tokens", "speculative decoding: emitted tokens per "
        "target forward pass (1 + acceptance)"),
    # ---- jit caches (jit/__init__.py, jit/sot.py, jit/train_step.py)
    "jit.cache_hit": MetricSpec(
        "counter", "calls", "compiled-program cache hits",
        tags=("site",)),
    "jit.cache_miss": MetricSpec(
        "counter", "compiles", "compiled-program cache misses",
        tags=("site",)),
    "jit.recompile": MetricSpec(
        "counter", "compiles", "fresh trace+compile with its cause",
        tags=("site", "cause")),
    "jit.graph_break": MetricSpec(
        "counter", "breaks", "graph breaks (to_static eager fallback / "
        "SOT guard subgraph splits)", tags=("site",)),
    # ---- MoE dispatch (incubate moe_layer.py, pallas/moe_dispatch.py)
    "moe.tokens_routed": MetricSpec(
        "counter", "tokens", "(token, expert) pairs routed through MoE "
        "dispatch"),
    "moe.capacity_dropped_tokens": MetricSpec(
        "counter", "tokens", "dispatches dropped by capacity limits"),
    "moe.expert_load_imbalance": MetricSpec(
        "gauge", "ratio", "max/mean per-expert token load of the last "
        "dispatch (1.0 = perfectly balanced)"),
    # ---- FleetExecutor MessageBus (distributed/fleet_executor.py)
    "fleet.messages": MetricSpec(
        "counter", "messages", "MessageBus messages sent",
        tags=("kind",)),
    "fleet.credit_stall_s": MetricSpec(
        "counter", "s", "time interceptors spent data-ready but blocked "
        "on downstream credit"),
    # ---- device memory (observability/memory.py)
    "device.memory_in_use_bytes": MetricSpec(
        "gauge", "bytes", "device bytes in use at last sample "
        "(jax.Device.memory_stats, native alloc_stats fallback)"),
    "device.memory_peak_bytes": MetricSpec(
        "gauge", "bytes", "peak device bytes in use (max over samples)"),
    # ---- per-compilation XLA cost accounting (observability/xla_cost.py)
    "xla.flops": MetricSpec(
        "gauge", "flops", "XLA cost_analysis FLOPs per execution of the "
        "tagged executable", tags=("executable",)),
    "xla.bytes_accessed": MetricSpec(
        "gauge", "bytes", "XLA cost_analysis bytes accessed per "
        "execution of the tagged executable", tags=("executable",)),
    # ---- training health (observability/health.py, jit/train_step.py)
    "train.grad_norm": MetricSpec(
        "gauge", "norm", "last finite fused global gradient norm (one "
        "whole-model reduction inside the compiled step)"),
    "train.nonfinite_steps": MetricSpec(
        "counter", "steps", "training steps whose global grad norm (or "
        "loss) was NaN/Inf; the health policy decides warn/skip/raise"),
    # ---- fault tolerance (distributed/resilience/)
    "resilience.retries": MetricSpec(
        "counter", "retries", "retried distributed I/O attempts "
        "(store ops, rpc posts/resends, pg init) under the shared "
        "backoff policy", tags=("site",)),
    "resilience.resumes": MetricSpec(
        "counter", "resumes", "Engine.fit resumes from a valid "
        "checkpoint (resume=True restore path)"),
    "resilience.checkpoint_saves": MetricSpec(
        "counter", "saves", "periodic checkpoints finalized "
        "(CRC manifest written) by the CheckpointManager"),
    "resilience.emergency_saves": MetricSpec(
        "counter", "saves", "best-effort synchronous emergency "
        "checkpoints (watchdog timeout / non-finite raise paths)"),
    "resilience.corrupt_checkpoints": MetricSpec(
        "counter", "checkpoints", "checkpoint directories skipped by "
        "latest_valid() for failing CRC/manifest validation"),
    "resilience.injected_faults": MetricSpec(
        "counter", "faults", "faults fired by the deterministic "
        "injection harness (PADDLE_TPU_FAULT_PLAN)",
        tags=("site", "kind")),
    # ---- continuous-batching serving engine (serving/engine.py)
    "serving.prefill_tokens": MetricSpec(
        "counter", "tokens", "prompt tokens prefilled by the serving "
        "engine (chunked; prefix-cache hits are NOT recomputed so "
        "they don't count here)"),
    "serving.decode_tokens": MetricSpec(
        "counter", "tokens", "tokens emitted by serving decode steps"),
    "serving.prefix_hit_tokens": MetricSpec(
        "counter", "tokens", "prompt tokens restored from the paged "
        "prefix cache at admission (prefill skipped)"),
    "serving.preemptions": MetricSpec(
        "counter", "requests", "running requests evicted to reclaim KV "
        "blocks (evict-and-recompute)"),
    "serving.deadline_cancels": MetricSpec(
        "counter", "requests", "requests cancelled for exceeding their "
        "per-request deadline"),
    "serving.requests": MetricSpec(
        "counter", "requests", "request stream terminations by outcome "
        "(eos/length/cancelled/deadline/shutdown)",
        tags=("outcome",)),
    "serving.ttft": MetricSpec(
        "histogram", "s", "time to first token: request arrival to the "
        "prefill-completion sample", TIME_BUCKETS),
    "serving.ragged_steps": MetricSpec(
        "counter", "steps", "ragged mixed prefill+decode dispatches — "
        "ONE jitted program per scheduler tick"),
    "serving.sampled_steps": MetricSpec(
        "counter", "steps", "ragged steps that held at least one live "
        "row with temperature > 0: the steps whose sampler ran its "
        "lane (softmax, sort, cumsum, draw); the others took argmax "
        "alone"),
    "serving.moe_pairs_held": MetricSpec(
        "counter", "pairs", "(token, chosen expert) pairs collected "
        "ragged steps really dispatched to experts held by this model, "
        "over their expert layers: counted by the step and read with "
        "its tokens (the routed pairs themselves where it holds every "
        "routed expert)"),
    "serving.moe_blocks_skipped": MetricSpec(
        "counter", "blocks", "128-row blocks of collected ragged steps' "
        "grouped expert matmuls that held no (token, expert) pair, over "
        "their expert layers: the static moe_blocks less the live "
        "blocks the step counted and returned with its tokens; the "
        "kernel fetches, multiplies and writes nothing for them"),
    "serving.ssm_state_resets": MetricSpec(
        "counter", "rows", "rows of launched ragged steps whose first "
        "token was at position 0 in a model with recurrent state (a new "
        "request, a re-prefill after preemption, a slot's next occupant): "
        "the step read their state as zeros, whatever the slot held"),
    "serving.lookahead_steps": MetricSpec(
        "counter", "steps", "ragged steps launched while the step "
        "before was still in flight (its tokens not read yet): the "
        "rounds in which the host's work overlapped the device's; at "
        "most serving.ragged_steps"),
    "serving.drained_rounds": MetricSpec(
        "counter", "rounds", "times the engine collected the step in "
        "flight BEFORE going on, because what came next needed every "
        "launched token on the host or the pools at rest, by reason: "
        "preempt (a round whose pool was too dry to go on without "
        "preempting), handoff (take_handoff with a hand-off's first "
        "token in flight), export (prefix export or import), shutdown "
        "(fail_all, shutdown)",
        tags=("reason",)),
    "serving.overrun_rows": MetricSpec(
        "counter", "rows", "rows of a launched step whose request "
        "ended before the step was collected (eos, cancel, deadline: "
        "ends the host could not foresee at launch); their tokens are "
        "dropped. An end by length is foreseen and leaves none"),
    "serving.ragged_compiles": MetricSpec(
        "counter", "compiles", "traces of the fixed-shape ragged step; "
        "MUST stay at 1 per engine — rows join/leave and chunk packing "
        "varies by mask (query_lens == 0 = idle row), never by shape"),
    # ---- multi-replica serving cluster (serving/cluster/)
    "cluster.submitted": MetricSpec(
        "counter", "requests", "requests admitted by the cluster "
        "router, by routing decision (affinity / least_loaded)",
        tags=("route",)),
    "cluster.shed": MetricSpec(
        "counter", "requests", "requests shed by admission control "
        "(every alive replica past its queue bound or free-list "
        "watermark); clients get a typed Overloaded, never a hang"),
    "cluster.affinity_hits": MetricSpec(
        "counter", "requests", "requests routed to the replica whose "
        "prefix cache holds their deepest known block-hash chain"),
    "cluster.replica_deaths": MetricSpec(
        "counter", "replicas", "replica crashes observed (injected via "
        "fault site cluster.replica or real)"),
    "cluster.replays": MetricSpec(
        "counter", "requests", "in-flight requests drained from a dead "
        "replica and replayed on a survivor (prompt+generated "
        "resubmitted; greedy decoding makes the continuation exact)"),
    "cluster.handoffs": MetricSpec(
        "counter", "requests", "disaggregated prefill->decode KV-page "
        "handoffs adopted by a decode replica"),
    "cluster.replicas_alive": MetricSpec(
        "gauge", "replicas", "alive replicas after the last router "
        "step"),
    "cluster.queue_depth": MetricSpec(
        "gauge", "requests", "sum of per-replica admission queues "
        "after the last router step"),
    "cluster.step_time": MetricSpec(
        "histogram", "s", "wall time of one synchronous router step "
        "(round-robin replica steps + disagg pump)", TIME_BUCKETS),
    "cluster.scale_up": MetricSpec(
        "counter", "replicas", "autoscaler scale-out events: a fresh "
        "replica warmed up, granted a lease, and committed into the "
        "pool epoch under sustained pressure"),
    "cluster.scale_down": MetricSpec(
        "counter", "replicas", "autoscaler scale-in events: a replica "
        "drained (clean leave + token-exact replay of in-flight work) "
        "after sustained idle / want_scale_down"),
    # ---- cluster-wide KV store (serving/kv_store/)
    "kv.index_hits": MetricSpec(
        "counter", "lookups", "admission-time global-index lookups "
        "that found a VALID cached prefix deeper than the target "
        "replica's own cache (lease-fresh + generation-matched owner "
        "or host-tier-resident)"),
    "kv.index_misses": MetricSpec(
        "counter", "lookups", "admission-time global-index lookups "
        "with no usable location (nothing registered, everything "
        "stale, or the target already holds the deepest copy)"),
    "kv.fetches": MetricSpec(
        "counter", "fetches", "prefix page fetches completed into the "
        "routed replica, by source tier (replica = cross-replica "
        "export/import, host = host-tier promotion)",
        tags=("source",)),
    "kv.fetch_tokens": MetricSpec(
        "counter", "tokens", "prompt tokens made KV-resident by "
        "cluster fetches — prefill work the target replica skipped, "
        "by source tier", tags=("source",)),
    "kv.stale_skips": MetricSpec(
        "counter", "fetches", "index hits that could not be served "
        "(owner evicted the blocks between lookup and export, or "
        "pool-layout mismatch) — the request fell back to recompute"),
    "kv.promotes": MetricSpec(
        "counter", "fetches", "host-tier promotions: spilled int8 "
        "pages restored into a replica's pool instead of recomputing "
        "the prefix"),
    "kv.demotes": MetricSpec(
        "counter", "blocks", "evicted prefix blocks spilled to the "
        "host tier by the async pump (instead of discarded)"),
    "kv.host_evictions": MetricSpec(
        "counter", "blocks", "host-tier entries evicted LRU to fit "
        "new spills under PADDLE_TPU_KV_HOST_MB"),
    "kv.crc_failures": MetricSpec(
        "counter", "blocks", "host-tier round trips failing CRC "
        "verification: the entry is dropped and the prefix "
        "recomputed — never served"),
    "kv.promote_time": MetricSpec(
        "histogram", "s", "wall time of one host-tier promotion "
        "(CRC-verified fetch + concat + pool import)", TIME_BUCKETS),
    "kv.demote_time": MetricSpec(
        "histogram", "s", "wall time of one block demotion "
        "(quantize to int8 spill + CRC + host-tier insert)",
        TIME_BUCKETS),
    "kv.host_blocks": MetricSpec(
        "gauge", "blocks", "blocks currently parked in the host-RAM "
        "tier after the last pump"),
    "kv.host_bytes": MetricSpec(
        "gauge", "bytes", "host-RAM tier payload bytes after the "
        "last pump (bounded by PADDLE_TPU_KV_HOST_MB)"),
    "kv.index_entries": MetricSpec(
        "gauge", "hashes", "distinct chain hashes registered in the "
        "global prefix index after the last pump"),
    # rolling-window twins (ClusterKVStore.windows, like rt.*): the
    # ptop KV panel's hit RATE reads these, not the lifetime counters
    "kv.lookups": MetricSpec(
        "counter", "lookups", "admission-time index consults over the "
        "rolling window (hit-rate denominator)"),
    "kv.hits": MetricSpec(
        "counter", "fetches", "cluster fetches served (replica or "
        "host tier) over the rolling window (hit-rate numerator)"),
    # ---- shared control-plane substrate (distributed/control_plane/)
    "cp.beats": MetricSpec(
        "counter", "beats", "heartbeat lease beats written through the "
        "shared substrate, all namespaces (beats dropped at fault site "
        "cp.lease do NOT count)"),
    "cp.fenced_rejects": MetricSpec(
        "counter", "beats", "stale-generation lease beats rejected by "
        "fencing (a zombie writer beating with a superseded lease "
        "generation)"),
    "cp.lease_expiries": MetricSpec(
        "counter", "leases", "members evicted because their lease "
        "expired WITHOUT a clean-leave marker (missed beats, not "
        "planned departures or self-reported deaths)"),
    "cp.epochs": MetricSpec(
        "counter", "epochs", "membership epochs committed through the "
        "shared substrate (joins, leaves, evictions)"),
    "cp.members": MetricSpec(
        "gauge", "members", "member count of the most recently "
        "committed epoch"),
    # ---- elastic self-healing training (distributed/elastic/)
    "elastic.heartbeats": MetricSpec(
        "counter", "beats", "membership lease beats written by this "
        "rank (dropped-beat injections via fault site elastic.heartbeat "
        "do NOT count)"),
    "elastic.missed_beats": MetricSpec(
        "counter", "leases", "peer leases seen expired by this rank's "
        "membership watch (each expiry observation counts once per "
        "proposal it feeds)"),
    "elastic.epochs": MetricSpec(
        "counter", "epochs", "group epochs this rank committed into "
        "(initial formation + every shrink/expand)"),
    "elastic.members": MetricSpec(
        "gauge", "ranks", "member count of the current group epoch"),
    "elastic.step_ms": MetricSpec(
        "histogram", "ms", "per-rank train step time as reported on the "
        "heartbeat lease (the straggler-policy input)",
        ELASTIC_MS_BUCKETS),
    "elastic.stragglers": MetricSpec(
        "gauge", "ranks", "ranks currently flagged by the rolling-p50 "
        "straggler policy (median step time > factor x group p50)"),
    "elastic.hangs": MetricSpec(
        "counter", "hangs", "watchdog-reported collective hangs claimed "
        "by the membership coordinator's abort interceptor (converted "
        "to epoch changes instead of process death)"),
    "elastic.snapshots": MetricSpec(
        "counter", "snapshots", "peer-replicated in-memory checkpoints "
        "pushed to the left-neighbor mailbox"),
    "elastic.snapshot_bytes": MetricSpec(
        "gauge", "bytes", "encoded size of the last peer-replicated "
        "snapshot (CRC header included)"),
    "elastic.recoveries": MetricSpec(
        "counter", "recoveries", "epoch-change recoveries completed, by "
        "state source (peer mailbox / disk manifest / none)",
        tags=("source",)),
    "elastic.recovery_ms": MetricSpec(
        "histogram", "ms", "epoch-change recovery latency: EpochChanged "
        "raised -> new epoch joined + state adopted",
        ELASTIC_MS_BUCKETS),
    # ---- device-native pipeline transport (distributed/pipeline/)
    "pipeline.p2p_bytes": MetricSpec(
        "counter", "bytes", "stage-boundary payload bytes moved by the "
        "pipeline transport", tags=("transport",)),
    "pipeline.p2p_messages": MetricSpec(
        "counter", "messages", "stage-boundary tensors moved by the "
        "pipeline transport", tags=("transport",)),
    "pipeline.compiles": MetricSpec(
        "counter", "compiles", "traces of the compiled 1F1B pipeline "
        "step; MUST stay at 1 per CompiledPipeline — steady-state "
        "micro-batch steps never recompile"),
    "pipeline.steps": MetricSpec(
        "counter", "steps", "compiled pipeline train steps dispatched"),
    "pipeline.overlap_buckets": MetricSpec(
        "gauge", "buckets", "gradient-sync buckets formed for "
        "comm/compute overlap (PADDLE_TPU_PP_BUCKET_MB)"),
    # ---- fusion rewrite layer (paddle_tpu/fusion/)
    "fusion.fused_calls": MetricSpec(
        "counter", "calls", "call sites routed through a fused region "
        "(trace-time decisions, not per-device-step)", tags=("op",)),
    "fusion.fallback_calls": MetricSpec(
        "counter", "calls", "call sites routed through the unfused "
        "fallback composition (PADDLE_TPU_FUSION=off or cached path)",
        tags=("op",)),
    "fusion.quantized_matmuls": MetricSpec(
        "counter", "calls", "MLP matmul sites dispatched to the "
        "quantized hot path (PADDLE_TPU_MM_QUANT)", tags=("mode", "op")),
    "fusion.builds": MetricSpec(
        "counter", "builds", "train-step builds with the fusion/quant "
        "modes captured for the trace", tags=("mode", "quant")),
    # ---- TP/DP computation-collective overlap (fusion/overlap_mm.py)
    "tp.overlap_calls": MetricSpec(
        "counter", "calls", "sharded-matmul call sites routed through "
        "the decomposed-overlap path, by resolved PADDLE_TPU_TP_OVERLAP "
        "mode (trace-time decisions)", tags=("op", "mode")),
    "tp.overlap_chunks": MetricSpec(
        "gauge", "chunks", "row chunks per ring step in effect for the "
        "decomposed sharded matmuls (PADDLE_TPU_TP_OVERLAP_CHUNKS, "
        "clamped to a divisor of the token dim)"),
    # ---- bench harness windows (bench.py, tools/bench_*.py)
    "bench.train_window": MetricSpec(
        "histogram", "s", "bench.py timed training window (N chained "
        "steps, d2h barrier included)", TIME_BUCKETS),
    "bench.decode_window": MetricSpec(
        "histogram", "s", "decode bench timed generation window",
        TIME_BUCKETS),
    "bench.moe_window": MetricSpec(
        "histogram", "s", "MoE bench timed window", TIME_BUCKETS),
    "bench.serving_window": MetricSpec(
        "histogram", "s", "serving bench window (Poisson arrivals "
        "through ServingEngine, warmup excluded)", TIME_BUCKETS),
    "bench.multichip_window": MetricSpec(
        "histogram", "s", "multichip pipeline bench timed window "
        "(N chained steps, d2h barrier included)", TIME_BUCKETS),
    "bench.fusion_window": MetricSpec(
        "histogram", "s", "fusion sub-bench timed window (fused vs "
        "unfused epilogue / quantized matmul arms)", TIME_BUCKETS),
    "bench.tp_overlap_window": MetricSpec(
        "histogram", "s", "tp_overlap sub-bench timed window (serial "
        "gather-then-GEMM vs decomposed ring arms)", TIME_BUCKETS),
    "bench.cluster_window": MetricSpec(
        "histogram", "s", "cluster bench timed window (one Poisson "
        "arrival-rate sweep point through the replica router)",
        TIME_BUCKETS),
    "bench.elastic_window": MetricSpec(
        "histogram", "s", "elastic bench timed window (kill->recovery "
        "arm and snapshot-overhead arms)", TIME_BUCKETS),
    "bench.ps_window": MetricSpec(
        "histogram", "s", "parameter-server bench timed window "
        "(recommender pull/push arms and the failover drill arm)",
        TIME_BUCKETS),
    # ---- request-scoped serving telemetry: ROLLING-WINDOW instruments
    # (observability/request_log.py + windows.py). Unlike everything
    # above, rt.* names live in per-engine/per-router Windows
    # collections (ring-of-buckets, time-windowed) — the lint treats
    # the call sites identically, so the names stay schema-checked.
    "rt.submitted": MetricSpec(
        "counter", "requests", "requests arriving at an engine or "
        "router (shed arrivals included on the router side)"),
    "rt.shed": MetricSpec(
        "counter", "requests", "arrivals refused by router admission "
        "control (the SLO shed-rate numerator)"),
    "rt.finished": MetricSpec(
        "counter", "requests", "access-log records closed (any "
        "terminal outcome)"),
    "rt.tokens": MetricSpec(
        "counter", "tokens", "tokens streamed to clients"),
    "rt.prefix_hit_tokens": MetricSpec(
        "counter", "tokens", "prompt tokens restored from the prefix "
        "cache at admission (windowed twin of "
        "serving.prefix_hit_tokens)"),
    "rt.preemptions": MetricSpec(
        "counter", "requests", "preemption events (evict-and-"
        "recompute) over the rolling window"),
    "rt.ttft": MetricSpec(
        "histogram", "s", "time to first token over the rolling "
        "window (SLO objective ttft_p99 reads this)", TIME_BUCKETS),
    "rt.token_gap": MetricSpec(
        "histogram", "s", "gap between consecutive streamed tokens of "
        "one request, rolling (SLO objective token_gap_p99)",
        TOKEN_LATENCY_BUCKETS),
    "rt.e2e": MetricSpec(
        "histogram", "s", "end-to-end request latency: arrival to "
        "terminal outcome", TIME_BUCKETS),
    "rt.lock_wait": MetricSpec(
        "histogram", "s", "time the request's submit() waited for the "
        "engine's lock, before arrival is stamped (not a segment of "
        "rt.e2e: the client waited lock_wait + e2e)", TIME_BUCKETS),
    "rt.queue_wait": MetricSpec(
        "histogram", "s", "attribution segment: time waiting for "
        "first admission", TIME_BUCKETS),
    "rt.prefill_time": MetricSpec(
        "histogram", "s", "attribution segment: time in PREFILL "
        "(re-prefill after preemption included — it is real compute)",
        TIME_BUCKETS),
    "rt.decode_time": MetricSpec(
        "histogram", "s", "attribution segment: time decoding "
        "(first token to finish, preempt stalls excluded)",
        TIME_BUCKETS),
    "rt.preempt_stall": MetricSpec(
        "histogram", "s", "attribution segment: pure stall between "
        "eviction and re-admission", TIME_BUCKETS),
    "rt.slot_util": MetricSpec(
        "gauge", "fraction", "EWMA of occupied decode slots / "
        "max_slots (per engine)"),
    "rt.queue_depth": MetricSpec(
        "gauge", "requests", "EWMA of the admission queue depth "
        "(per engine)"),
    # ---- SLO burn-rate engine (observability/slo.py)
    "slo.evaluations": MetricSpec(
        "counter", "evaluations", "SLOEngine.evaluate() passes per "
        "objective", tags=("objective",)),
    "slo.state": MetricSpec(
        "gauge", "state", "objective state after the last evaluation "
        "(0=OK 1=WARN 2=BURN)", tags=("objective",)),
    "slo.burn_fast": MetricSpec(
        "gauge", "x budget", "fast-window error-budget burn rate of "
        "the objective", tags=("objective",)),
    "slo.burn_slow": MetricSpec(
        "gauge", "x budget", "slow-window error-budget burn rate of "
        "the objective", tags=("objective",)),
    # ---- parameter-server tier (distributed/ps/)
    "ps.pulls": MetricSpec(
        "counter", "rows", "sparse/dense rows served by PS pull "
        "handlers (primary side)"),
    "ps.pushes": MetricSpec(
        "counter", "rows", "gradient rows applied by PS push handlers "
        "(post-dedup; admission-denied rows included)"),
    "ps.push_dedup_hits": MetricSpec(
        "counter", "pushes", "push batches acked WITHOUT re-applying: "
        "the (worker, shard, table) sequence number was at or below "
        "the server's high-water mark (rpc retransmit, lost ack, or "
        "failover replay)"),
    "ps.evictions": MetricSpec(
        "counter", "rows", "sparse rows evicted by the capacity-"
        "bounded LRU-by-push policy (tables.py)"),
    "ps.admission_denied": MetricSpec(
        "counter", "rows", "sparse push rows dropped by the EntryAttr "
        "admission filter before the row materialized"),
    "ps.repl_records": MetricSpec(
        "counter", "records", "replication-log records applied by a "
        "backup's applier thread (or drained during promotion)"),
    "ps.repl_degraded": MetricSpec(
        "counter", "shards", "shards that dropped to unreplicated "
        "service because the backup's lease went stale"),
    "ps.promotions": MetricSpec(
        "counter", "promotions", "backup shards promoted to primary "
        "after the primary's lease expired"),
    "ps.failovers": MetricSpec(
        "counter", "failovers", "worker-observed shard-map moves "
        "(typed PSFailover adopted: re-resolve + window replay)"),
    "ps.replays": MetricSpec(
        "counter", "pushes", "in-flight window records a worker "
        "re-sent against a newly promoted primary"),
    "ps.pull_time": MetricSpec(
        "histogram", "s", "whole worker-side pull_sparse latency "
        "(all shards, retries and failover included)", TIME_BUCKETS),
    "ps.push_time": MetricSpec(
        "histogram", "s", "whole worker-side push_sparse latency "
        "(all shards, retries and failover included)", TIME_BUCKETS),
    # ---- training step profiler (observability/profiler.py)
    "prof.steps_sampled": MetricSpec(
        "counter", "steps", "train steps device-fenced by the sampled "
        "step profiler (PADDLE_TPU_PROFILE gate)"),
    "prof.step_time": MetricSpec(
        "histogram", "s", "wall time of sampled (device-fenced) train "
        "steps", TIME_BUCKETS),
    "prof.mfu": MetricSpec(
        "gauge", "fraction", "rolling model-FLOPs utilization over "
        "sampled steps (flops_per_step / wall / peak_flops)"),
    "prof.tokens_per_s": MetricSpec(
        "gauge", "tokens/s", "rolling token throughput over sampled "
        "steps"),
    "prof.phase_frac": MetricSpec(
        "gauge", "fraction", "share of the last sampled step's wall "
        "time attributed to the phase (segments sum to 1)",
        tags=("phase",)),
    "prof.overlap_efficiency": MetricSpec(
        "gauge", "fraction", "estimated comm time hidden / total comm "
        "time for the overlap mechanism (pp ring, tp in-loop ring, dp "
        "bucket psum)", tags=("mechanism",)),
    "prof.comm_hidden_s": MetricSpec(
        "gauge", "s", "estimated per-step communication seconds hidden "
        "under compute, per mechanism", tags=("mechanism",)),
    "prof.comm_exposed_s": MetricSpec(
        "gauge", "s", "estimated per-step communication seconds on the "
        "critical path (not overlapped), per mechanism",
        tags=("mechanism",)),
    "prof.flops_divergence": MetricSpec(
        "gauge", "fraction", "relative disagreement between the 6N "
        "analytic FLOPs model and XLA cost analysis "
        "(|xla - model| / model; bench warns above 0.10)"),
    "prof.compiles": MetricSpec(
        "counter", "compiles", "compile-ledger compiles per jit site "
        "with recompile-cause attribution (which arg's "
        "shape/dtype/static value changed)", tags=("site", "cause")),
    "prof.compile_time": MetricSpec(
        "histogram", "s", "trace+compile duration of compile-ledger "
        "misses (measured at dispatch for jit, AOT for lowered "
        "programs)", TIME_BUCKETS),
    "prof.mem_phase_bytes": MetricSpec(
        "gauge", "bytes", "device HBM live bytes sampled at the named "
        "training phase boundary (memory ledger)", tags=("phase",)),
    "prof.mem_peak_bytes": MetricSpec(
        "gauge", "bytes", "running peak of device HBM peak_bytes_in_use "
        "across all memory-ledger samples"),
}


def spec(name: str) -> Optional[MetricSpec]:
    return METRICS.get(name)


# ---------------------------------------------------------------- spans
# Span-name schema (observability/tracing.py): every IN-TREE
# ``span("...")`` call site with a literal dotted name must use a name
# declared here — tools/check_metric_names.py lints span call sites
# against this table exactly like metric call sites. Names built at
# runtime (f-strings, variables) are out of lint scope by design.
SPANS = {
    "engine.step": "one Engine.fit optimizer step (dispatch + loss d2h)",
    "engine.build": "Engine._build: pass pipeline + train-step trace",
    "train.step": "TrainStep dispatch (single or chained chunk)",
    "decode.generate": "whole generate() call",
    "decode.prefill": "prefill dispatch (telemetry two-phase path)",
    "decode.decode": "decode-scan dispatch",
    "jit.compile": "fresh trace+compile of a jitted program",
    "fleet.run": "FleetExecutor.run window (feed -> sink drain)",
    "fleet.node": "one interceptor fire (TaskNode fn on its actor)",
    "rpc.call": "outgoing rpc (client side, until posted)",
    "rpc.handle": "incoming rpc execution (server side)",
    "pg.collective": "ProcessGroup collective (op/group in args)",
    "ckpt.save": "CheckpointManager.save (snapshot + flush + manifest)",
    "ckpt.restore": "CheckpointManager.load (read + reshard + adopt)",
    "serving.step": "one ServingEngine round under the engine's lock: "
                    "launch the next ragged step, then collect the one "
                    "launched the round before; at its end "
                    "running/prefilling/waiting/slots_max, "
                    "pages_in_use/pages_max and tokens (of the step "
                    "launched) in args. Its children, in order, are "
                    "schedule, build_batch, transfer, ragged_step (the "
                    "step launched), device_wait, emit (the step "
                    "collected); a round that has to preempt collects "
                    "first: schedule (cut short), device_wait, emit, "
                    "then the four of the launch",
    "serving.schedule": "deadline expiry, admission, decode-block "
                        "allocation and prefill packing of one ragged "
                        "step (admitted/preempted in args)",
    "serving.build_batch": "packing one ragged step's host arrays and "
                           "splitting its sampling key",
    "serving.transfer": "the host-to-device transfers of one ragged "
                        "step's arrays",
    "serving.ragged_step": "enqueue of one ragged mixed prefill+decode "
                           "dispatch, returns before the device is "
                           "done (rows/tokens/impl in args, and "
                           "live_pages: the sum over its rows of "
                           "ceil(context / block_size), the pages "
                           "the attention kernel reads in one cache "
                           "layer; passes: times the stack of layers "
                           "runs in the step; cache_layers: KV pools "
                           "read and written, passes x layers; "
                           "weight_bytes: bytes of layer weights one "
                           "pass streams; sampled_rows: live rows "
                           "with temperature > 0, and with none the "
                           "step's sampler skips its lane; kv_layout: "
                           "'kv' for per-head K and V pools, which "
                           "adds, where impl is 'pallas', the per-head "
                           "kernel's work in one "
                           "cache layer: ragged_page_visits, its live "
                           "(row, page) pairs, and ragged_visits, its "
                           "(row, group of pages) visits of a head "
                           "block; 'latent' "
                           "for one latent pool a cache layer, which "
                           "then adds latent_dim, values a token "
                           "leaves in it, attn_pairs, (query "
                           "token, key) pairs the step's attention "
                           "scores in one cache layer, and the latent "
                           "kernel's work in one cache layer: "
                           "latent_page_visits, its (q block, row, "
                           "page) visits, and latent_visits, its grid "
                           "steps, a group of pages each; a model with "
                           "expert layers adds experts (the routed "
                           "experts it HOLDS), experts_routed (the "
                           "router's width), experts_per_token, "
                           "moe_layers, moe_pairs, live tokens x "
                           "experts a token x expert layers x held / "
                           "routed, moe_rows, rows its grouped "
                           "matmuls' layouts hold, static, and "
                           "moe_blocks, their 128-row blocks; one with "
                           "several "
                           "residual streams a token hc_streams; "
                           "in_flight: 1 when the step before was "
                           "still running on the device while this one "
                           "was built and enqueued, else 0)",
    "serving.device_wait": "the host's wait for the sampled tokens of "
                           "the step being collected (the "
                           "device-to-host read): the step launched "
                           "the round before, while the one launched "
                           "this round is queued behind it (a model "
                           "with expert layers: moe_pairs_held, the "
                           "step's pairs dispatched to experts held "
                           "here, moe_pairs_routed, its tokens x "
                           "experts a token x expert layers, "
                           "moe_blocks_live, the row blocks that held a "
                           "pair and were computed, and moe_blocks, all "
                           "of them, in args)",
    "serving.emit": "streaming the collected step's tokens to their "
                    "requests: first tokens, finishes, hand-offs "
                    "(tokens in args); rows whose request ended after "
                    "the launch are dropped here",
    "serving.lock_wait": "one caller's wait for the engine's lock "
                         "(site = submit/stream/events/cancel/stats/"
                         "step, and rid where there is one, in args)",
    "cluster.route": "one router admission decision (affinity lookup + "
                     "health snapshots + submit)",
    "cluster.handoff": "one disaggregated prefill->decode KV-page "
                       "handoff (blocks/bytes in args)",
    "cluster.replay": "one drained descriptor replayed on a survivor "
                      "after a replica death",
    "kv.fetch": "one admission-time cluster KV consult: global-index "
                "lookup + (on a hit) cross-replica or host-tier page "
                "fetch into the routed replica",
    "kv.promote": "one host-tier promotion: CRC-verified spill fetch "
                  "+ concat + pool import (blocks in args)",
    "kv.demote": "one evicted block quantized + CRC-stamped into the "
                 "host tier by the async pump (hash in args)",
    "elastic.epoch": "one epoch join: propose/ack/commit barrier-with-"
                     "deadline (epoch + members in args)",
    "elastic.reshard": "shrink/expand state adoption: peer-snapshot "
                       "fetch + shard remap (or disk fallback)",
    "pp.send": "pipeline stage-boundary send (device collective or "
               "host-buffered, transport in args)",
    "pp.recv": "pipeline stage-boundary recv (transport in args)",
    "pp.bucket_reduce": "one bucketed gradient all-reduce issued during "
                        "backward/cooldown (bucket index + bytes in args)",
    "pipeline.step": "one compiled 1F1B pipeline train-step dispatch",
    "tp.overlap_window": "one chunked computation-collective overlap "
                         "region (eager TP/SP linear fwd/bwd; op + chunk "
                         "count in args)",
    "ps.pull": "one worker-side sharded pull_sparse (table + rows in "
               "args; spans retries and failover)",
    "ps.push": "one worker-side sharded push_sparse (table + rows in "
               "args; spans retries and failover)",
    "ps.promote": "backup->primary promotion: replication-log drain + "
                  "shard-map takeover (shard in args)",
    "ps.replay": "in-flight window replay against a new primary "
                 "(shard + record count in args)",
    "rt.request": "one request's whole lifecycle, synthesized at close "
                  "by the access log via tracing.record_complete "
                  "(outcome + attribution segments in args) — the bar "
                  "that spans router -> replica -> ragged steps in "
                  "Perfetto",
    "slo.evaluate": "one SLOEngine.evaluate() pass over the rolling "
                    "windows (all objectives)",
    "prof.step": "one sampled (device-fenced) train step, synthesized "
                 "at close by the step profiler via "
                 "tracing.record_complete (attribution segments + mfu "
                 "in args)",
    "prof.phase": "one phase bar inside a sampled step (data_wait / "
                  "dispatch / device / host_stall) — children of the "
                  "prof.step bar in Perfetto",
}


def span_spec(name: str) -> Optional[str]:
    return SPANS.get(name)
