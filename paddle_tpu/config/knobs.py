"""The typed registry of every ``PADDLE_TPU_*`` environment knob.

Every environment read of a ``PADDLE_TPU_*`` name anywhere in the tree
MUST go through this module — ptlint's ``env-knobs`` pass rejects raw
``os.environ`` reads and accessor calls on undeclared names, and the
README env-var tables are generated from this schema by
``tools/gen_env_docs.py`` (drift is a lint finding too).

Design constraints:

* **stdlib-only, no paddle_tpu imports.** Observability modules read
  knobs at import time, so this module must sit below everything; it is
  also loaded standalone (``importlib.util.spec_from_file_location``)
  by repo tools that must not import jax (``tools/perfdiff.py``,
  ``tools/gen_env_docs.py``, ptlint, ``__graft_entry__``).
* **Declared type + default, call-site default override.** The schema
  default is the documented one; a call site may pass its own default
  (e.g. ``PADDLE_TPU_SYNTH_SAMPLES`` defaults per dataset) without
  redeclaring the knob.
* **Lenient parsing.** An unset or empty value yields the default; a
  malformed numeric value ALSO yields the default (a typo'd knob must
  degrade to documented behavior, not crash a training job at import).
* **Bool semantics**: ``"", "0", "false", "off", "no"`` (any case)
  are False, anything else set is True.
"""
from __future__ import annotations

import os
from typing import Any, Dict, Iterable, NamedTuple, Optional

__all__ = ["Knob", "KNOBS", "get_str", "get_int", "get_float",
           "get_bool", "is_set", "get_raw", "iter_knobs", "validate"]

_TYPES = ("str", "int", "float", "bool")


class Knob(NamedTuple):
    name: str
    type: str           # str | int | float | bool
    default: Any        # documented default; None = unset/derived
    subsystem: str      # README table section
    doc: str            # one line


def _k(name: str, type: str, default: Any, subsystem: str,
       doc: str) -> Knob:
    assert type in _TYPES, type
    return Knob("PADDLE_TPU_" + name, type, default, subsystem, doc)


_ALL = (
    # ------------------------------------------------------- serving
    _k("SERVE_SLOTS", "int", 8, "serving",
       "Max concurrent decode slots per serving engine."),
    _k("SERVE_BLOCK_SIZE", "int", 16, "serving",
       "KV page size in token slots."),
    _k("SERVE_NUM_BLOCKS", "int", 512, "serving",
       "KV pool size in pages (shared across layers)."),
    _k("SERVE_PREFILL_CHUNK", "int", 32, "serving",
       "Prefill tokens admitted per engine step."),
    _k("SERVE_TOKEN_BUDGET", "int", None, "serving",
       "Token axis of the ragged step "
       "(default: SERVE_SLOTS + SERVE_PREFILL_CHUNK)."),
    # ------------------------------------------------------- cluster
    _k("CLUSTER_REPLICAS", "int", 2, "cluster",
       "Replica count for bench --cluster runs."),
    _k("CLUSTER_MAX_QUEUE", "int", 32, "cluster",
       "Router admission queue depth before shedding."),
    _k("CLUSTER_BEAT", "float", 0.5, "cluster",
       "Cluster control-plane heartbeat interval (s)."),
    _k("CLUSTER_LEASE_TIMEOUT", "float", 2.0, "cluster",
       "Replica lease freshness timeout (s)."),
    _k("AUTOSCALE_MIN", "int", 1, "cluster",
       "Autoscaler floor (replicas)."),
    _k("AUTOSCALE_MAX", "int", 4, "cluster",
       "Autoscaler ceiling (replicas)."),
    _k("AUTOSCALE_UP_TICKS", "int", 3, "cluster",
       "Consecutive pressured ticks before scale-out."),
    _k("AUTOSCALE_IDLE_TICKS", "int", 10, "cluster",
       "Consecutive idle ticks before scale-in."),
    _k("AUTOSCALE_COOLDOWN_TICKS", "int", 10, "cluster",
       "Ticks to hold after any scaling action."),
    _k("AUTOSCALE_QUEUE_HWM", "int", 4, "cluster",
       "Queue depth counting as sustained pressure."),
    _k("AUTOSCALE_SHED_THRESHOLD", "float", 0.0, "cluster",
       "Shed-rate fraction counting as pressure (0 = any shed)."),
    # ------------------------------------------------------ kv_store
    _k("KV_TIER", "str", "host", "kv_store",
       "Cluster KV tier: off (index only) | host (adds host-RAM "
       "spill tier)."),
    _k("KV_HOST_MB", "float", 64.0, "kv_store",
       "Host-RAM tier capacity (MiB of int8 spills)."),
    _k("KV_PUMP_S", "float", 0.02, "kv_store",
       "Async promote/demote pump interval (s)."),
    # ------------------------------------------------- observability
    _k("TELEMETRY", "bool", False, "observability",
       "Master switch for the metrics registry."),
    _k("TRACE_CAPACITY", "int", 65536, "observability",
       "Finished-span ring capacity (oldest dropped first)."),
    _k("FLIGHT_CAPACITY", "int", 4096, "observability",
       "Flight-recorder event ring capacity."),
    _k("DUMP_DIR", "str", None, "observability",
       "Crash/debug bundle output directory."),
    _k("ACCESS_LOG", "str", None, "observability",
       "Serving access-log path (JSONL)."),
    _k("HEALTH", "str", "off", "observability",
       "Non-finite grad policy: off|warn|skip|raise."),
    _k("WINDOW_S", "float", 60.0, "observability",
       "Rolling telemetry window span (s)."),
    _k("WINDOW_BUCKETS", "int", 12, "observability",
       "Buckets per rolling window."),
    _k("SLO_TTFT_P99_MS", "float", 2000.0, "observability",
       "SLO objective: p99 time-to-first-token (ms)."),
    _k("SLO_TOKEN_GAP_P99_MS", "float", 500.0, "observability",
       "SLO objective: p99 inter-token gap (ms)."),
    _k("SLO_SHED_RATE", "float", 0.05, "observability",
       "SLO objective: max shed-rate fraction."),
    _k("SLO_FAST_S", "float", 10.0, "observability",
       "Fast burn-rate window (s)."),
    _k("SLO_WINDOW_S", "float", 0.0, "observability",
       "Slow burn-rate window (s); 0 = the windows' full span."),
    _k("SLO_PAGE_BURN", "float", 4.0, "observability",
       "Burn-rate multiple that pages (BURN state)."),
    _k("SLO_UTIL_LOW", "float", 0.25, "observability",
       "Utilization below which scale-in is suggested."),
    _k("PROFILE", "str", "off", "observability",
       "Step attribution profiler: off|on|sample:N."),
    _k("PROF_PEAK_FLOPS", "float", None, "observability",
       "Override peak FLOP/s for MFU math."),
    _k("PROF_LINK_GBPS", "float", None, "observability",
       "Override interconnect GB/s for overlap estimators."),
    _k("PROFILE_DIR", "str", "/tmp/paddle_tpu_profile", "observability",
       "Device-trace output directory (jax profiler)."),
    # --------------------------------------------------- distributed
    _k("PP_TRANSPORT", "str", "auto", "distributed",
       "Pipeline stage transport: auto|device|host."),
    _k("PP_BUCKET_MB", "float", 4.0, "distributed",
       "Overlap bucket size (MiB) for DP grad fusion / PP ring."),
    _k("COMM_TIMEOUT", "float", None, "distributed",
       "Collective watchdog timeout (s); unset disables."),
    _k("PURE_PY_STORE", "bool", False, "distributed",
       "Force the pure-Python TCPStore (skip the native daemon)."),
    _k("RPC_RETRIES", "int", 4, "distributed",
       "Max re-posts of a lost rpc request."),
    _k("RPC_RETRY_BASE_DELAY", "float", 0.25, "distributed",
       "Base backoff (s) of the rpc retransmit schedule."),
    _k("ELASTIC", "bool", False, "distributed",
       "Opt the auto-parallel engine into elastic membership."),
    _k("ELASTIC_BEAT", "float", 0.5, "elastic",
       "Elastic membership heartbeat interval (s)."),
    _k("ELASTIC_TIMEOUT", "float", 10.0, "elastic",
       "Elastic lease timeout (s) before a member is declared dead."),
    _k("ELASTIC_SNAP_FREQ", "int", 10, "elastic",
       "Steps between peer snapshots."),
    _k("ELASTIC_STRAGGLER_FACTOR", "float", 3.0, "elastic",
       "Step-time multiple over the median that flags a straggler."),
    _k("ELASTIC_STRAGGLER_POLICY", "str", "flag", "elastic",
       "Straggler handling: flag|demote."),
    _k("ELASTIC_MAX_NODES", "int", 16, "elastic",
       "Upper bound on elastic group size."),
    # ------------------------------------------------------------ ps
    _k("PS_TIMEOUT", "float", 30.0, "ps",
       "Whole-op deadline (s) for one sharded pull/push."),
    _k("PS_RPC_TIMEOUT", "float", 2.0, "ps",
       "Per-rpc timeout (s) inside a sharded op."),
    _k("PS_BEAT", "float", 0.15, "ps",
       "PS primary heartbeat interval (s)."),
    _k("PS_FAILOVER_TIMEOUT", "float", 5.0, "ps",
       "Lease silence (s) before a replica takes over a shard."),
    _k("PS_REPLICATION", "str", "auto", "ps",
       "Chain replication mode: auto|on|off."),
    # ---------------------------------------------------- resilience
    _k("FAULT_PLAN", "str", None, "resilience",
       "Fault injection plan: 'site:kind[=value]@spec,...'."),
    _k("FAULT_SEED", "int", 0, "resilience",
       "Seed for probabilistic fault plans."),
    _k("RETRY_MAX_ATTEMPTS", "int", 5, "resilience",
       "Default retry policy: max attempts."),
    _k("RETRY_BASE_DELAY", "float", 0.05, "resilience",
       "Default retry policy: base backoff (s)."),
    _k("RETRY_MAX_DELAY", "float", 2.0, "resilience",
       "Default retry policy: backoff cap (s)."),
    _k("RETRY_SEED", "int", 0, "resilience",
       "Seed for retry jitter rngs."),
    # -------------------------------------------------------- fusion
    _k("FUSION", "str", "auto", "fusion",
       "Fused-epilogue dispatch: auto|on|off."),
    _k("MM_QUANT", "str", "off", "fusion",
       "Quantized GEMM path: off|int8|fp8."),
    _k("TP_OVERLAP", "str", "auto", "fusion",
       "TP comm/compute overlap: auto|on|off."),
    _k("TP_OVERLAP_CHUNKS", "int", 2, "fusion",
       "Ring chunks per overlapped TP GEMM."),
    # ---------------------------------------------------------- data
    _k("DATA_HOME", "str", "~/.cache/paddle_tpu", "data",
       "Dataset cache root."),
    _k("SYNTH_SAMPLES", "int", 32, "data",
       "Synthetic-fallback dataset size (datasets override the "
       "default per split)."),
    # --------------------------------------------------------- tools
    _k("BENCH", "str", None, "tools",
       "Bench model-size preset override (e.g. '125m')."),
    _k("OPS_SNAPSHOT", "str", None, "tools",
       "Write/read op-coverage snapshots at this path."),
    _k("PERFDIFF_BASE", "str", None, "tools",
       "Baseline metrics file/dir for tools/perfdiff.py."),
    _k("PERFDIFF_NOISE", "float", 0.10, "tools",
       "Relative noise floor for perfdiff regressions."),
    _k("WRITE_MANIFEST", "bool", False, "tools",
       "Let test_op_coverage rewrite the op manifest."),
    _k("KEEP_BACKEND_LOGS", "bool", False, "tools",
       "Keep spawned-backend log files after a clean exit."),
)

KNOBS: Dict[str, Knob] = {k.name: k for k in _ALL}
assert len(KNOBS) == len(_ALL), "duplicate knob declaration"

_FALSE = ("", "0", "false", "off", "no")
_MISSING = object()


def _declared(name: str, want: str) -> Knob:
    k = KNOBS.get(name)
    if k is None:
        raise KeyError(
            "undeclared env knob %r — declare it in "
            "paddle_tpu/config/knobs.py" % (name,))
    if k.type != want:
        raise TypeError("knob %s is declared %s, read as %s"
                        % (name, k.type, want))
    return k


def get_raw(name: str) -> Optional[str]:
    """The raw env string (declared names only), or None when unset."""
    if name not in KNOBS:
        raise KeyError("undeclared env knob %r" % (name,))
    return os.environ.get(name)


def is_set(name: str) -> bool:
    """Whether the knob is present in the environment at all."""
    if name not in KNOBS:
        raise KeyError("undeclared env knob %r" % (name,))
    return name in os.environ


def get_str(name: str, default: Any = _MISSING) -> Optional[str]:
    k = _declared(name, "str")
    v = os.environ.get(name)
    if v is None or not v.strip():
        return k.default if default is _MISSING else default
    return v


def get_int(name: str, default: Any = _MISSING) -> Optional[int]:
    k = _declared(name, "int")
    v = os.environ.get(name)
    d = k.default if default is _MISSING else default
    if v is None or not v.strip():
        return d
    try:
        return int(v)
    except ValueError:
        return d


def get_float(name: str, default: Any = _MISSING) -> Optional[float]:
    k = _declared(name, "float")
    v = os.environ.get(name)
    d = k.default if default is _MISSING else default
    if v is None or not v.strip():
        return d
    try:
        return float(v)
    except ValueError:
        return d


def get_bool(name: str, default: Any = _MISSING) -> bool:
    k = _declared(name, "bool")
    v = os.environ.get(name)
    if v is None:
        return bool(k.default if default is _MISSING else default)
    return v.strip().lower() not in _FALSE


def iter_knobs() -> Iterable[Knob]:
    """Declared knobs in declaration (= README table) order."""
    return iter(_ALL)


def validate() -> None:
    """Schema self-check: unique names, known types, prefix, doc."""
    seen = set()
    for k in _ALL:
        assert k.name.startswith("PADDLE_TPU_"), k.name
        assert k.name not in seen, "duplicate knob %s" % k.name
        seen.add(k.name)
        assert k.type in _TYPES, (k.name, k.type)
        assert k.subsystem and k.doc, k.name
        if k.default is not None:
            want = {"str": str, "int": int, "float": float,
                    "bool": bool}[k.type]
            assert isinstance(k.default, want), (k.name, k.default)


validate()
