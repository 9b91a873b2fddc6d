"""Sampled training-step profiler: device-time attribution, rolling
MFU gauges, and a per-mechanism comm-overlap estimator.

Host-side spans measure *dispatch* under async execution, not device
time — a step that "takes 3 ms" on the host may be 80 ms of device
work draining later. This module device-fences every Nth train step
(``PADDLE_TPU_PROFILE=off|sample:N|on``) and produces an exact phase
breakdown whose segments sum to wall step time, the same closing
discipline as the serving tier's ``RequestTimeline`` (request_log.py):
every boundary reads the clock once, and the final segment is the
remainder, so the invariant holds by construction::

    data_wait + dispatch + device_compute + collective_exposed
        + optimizer + host_stall == wall          (exactly)

The three *measured* host boundaries are data-wait, dispatch (the
async call returning) and the device fence (``block_until_ready``);
the device segment is then sub-attributed analytically: exposed
collective time comes from the :func:`note_overlap` estimates, the
optimizer share from the configured flop split, and device compute is
the remainder — so the sub-split also sums exactly.

**Overlap-efficiency estimator.** The three overlap mechanisms (PP
ring ticks in ``distributed/pipeline/schedule.py``, TP in-loop ring
GEMMs in ``fusion/overlap_mm.py``, DP bucket psums in
``distributed/pipeline/overlap.py``) report their geometry at trace
time; :func:`ring_overlap` / :func:`bucket_overlap` /
:func:`pipeline_overlap` convert it into hidden-vs-exposed comm
seconds under a simple device model (link bandwidth + peak FLOP/s,
env-overridable). The estimate is a *model*, not a measurement — it
is the honest upper bound each MFU PR is argued against, and the
per-mechanism ``prof.overlap_efficiency`` gauge is what
``bench.py --multichip`` reports for PP/TP/DP.

Zero-cost when off: every entry point checks :func:`profiling_enabled`
(one module-global read) and returns immediately — the off path adds
zero host callbacks and zero recompiles to a train loop
(trace-counter-proven in tests/test_profiler.py). Registry/windows
writes additionally respect the telemetry gate, so profiling without
``PADDLE_TPU_TELEMETRY`` still yields reports and bundles, just no
exported metrics.

Reference: arXiv:2401.16677 (T3) — overlap cannot be optimized before
it can be measured; arXiv:2510.08726 (Neptune) for the fusion depth
this measurement substrate gates.
"""
from __future__ import annotations

import os
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..config import knobs
from . import tracing as _tracing
from . import windows as _windows
from .registry import registry as _registry

__all__ = [
    "profiling_enabled", "profile_mode", "sample_every",
    "enable_profiling", "disable_profiling", "should_sample",
    "begin_step", "StepRecord", "last_report", "reports", "report",
    "configure", "ring_overlap", "bucket_overlap", "pipeline_overlap",
    "note_overlap", "note_ring_overlap", "note_bucket_overlap",
    "note_pipeline_overlap", "overlap_report", "flops_divergence",
    "link_bandwidth", "peak_flops", "reset", "debug_invocations",
]

# the canonical phase order of a step attribution (and the invariant's
# summands); perfdiff and the bench assert against these names
PHASES = ("data_wait", "dispatch", "device_compute",
          "collective_exposed", "optimizer", "host_stall")

_MECHANISMS = ("pp", "tp", "dp")


def _parse_mode(raw: str):
    raw = (raw or "").strip().lower()
    if raw in ("", "0", "off", "false"):
        return "off", 0
    if raw in ("1", "on", "true"):
        return "on", 1
    if raw.startswith("sample:"):
        try:
            n = max(1, int(raw.split(":", 1)[1]))
        except ValueError:
            n = 100
        return "sample", n
    return "off", 0


_mode, _every = _parse_mode(knobs.get_str("PADDLE_TPU_PROFILE"))
# THE gate: a single module-global bool read on every hot-path check
_active = _mode != "off"

_lock = threading.Lock()
_invocations = 0            # debug: active profiler entry-point calls
_reports: deque = deque(maxlen=64)
_last_report: Optional[dict] = None
_overlap: Dict[str, dict] = {}   # mechanism -> hidden/exposed estimate
_config: dict = {"flops_per_step": 0.0, "tokens_per_step": 0,
                 "optimizer_flops": 0.0, "peak_flops": 0.0}
_last_divergence: Optional[dict] = None

# rolling MFU / step-time gauges ride the PR-16 windows machinery; the
# collection is named so flight-recorder snapshots pick it up
_wins = _windows.Windows("prof")


def profiling_enabled() -> bool:
    return _active


def profile_mode() -> str:
    return _mode


def sample_every() -> int:
    return _every


def enable_profiling(mode: str = "on") -> None:
    """Turn profiling on at runtime (same strings as the env var)."""
    global _mode, _every, _active
    _mode, _every = _parse_mode(mode)
    _active = _mode != "off"


def disable_profiling() -> None:
    global _mode, _every, _active
    _mode, _every, _active = "off", 0, False


def should_sample(step: int) -> bool:
    """True when ``step`` is one of the device-fenced sampled steps."""
    if not _active:
        return False
    if _mode == "on":
        return True
    return int(step) % _every == 0


def debug_invocations() -> int:
    """Active profiler calls since reset — the zero-cost-when-disabled
    proof counter (stays 0 with PADDLE_TPU_PROFILE=off)."""
    return _invocations


def _count_invocation() -> None:
    global _invocations  # ptlint: disable=jit-purity (host-side proof counter, gated off under jit-off)
    with _lock:
        _invocations += 1


# ------------------------------------------------------------ device model
def _tpu_peaks():
    """Published peaks of the chip this process runs on (one table:
    device/peaks.py; an unknown ``device_kind`` raises), or None off-TPU."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    from ..device.peaks import chip_peaks

    return chip_peaks(jax.devices()[0])


def peak_flops() -> float:
    """Per-chip peak FLOP/s for MFU math: PADDLE_TPU_PROF_PEAK_FLOPS,
    else the configured value, else the chip's published peak; 0 off-TPU
    (MFU reads 0 rather than a made-up CPU number)."""
    env = knobs.get_float("PADDLE_TPU_PROF_PEAK_FLOPS")
    if env:
        return env
    if _config["peak_flops"] > 0:
        return _config["peak_flops"]
    pk = _tpu_peaks()
    return pk.bf16_flops if pk is not None else 0.0


def link_bandwidth() -> float:
    """Inter-chip link bandwidth (bytes/s) for the overlap estimator:
    PADDLE_TPU_PROF_LINK_GBPS, else the chip's published per-link ICI
    figure; off-TPU a loopback-class 10 GB/s placeholder that only
    orders the CPU dry run's estimates."""
    env = knobs.get_float("PADDLE_TPU_PROF_LINK_GBPS")
    if env:
        return env * 1e9
    pk = _tpu_peaks()
    return pk.ici_link_bytes_per_s if pk is not None else 10e9


def configure(flops_per_step: Optional[float] = None,
              tokens_per_step: Optional[int] = None,
              optimizer_flops: Optional[float] = None,
              peak_flops: Optional[float] = None) -> None:
    """Install the step cost model (engine build telemetry calls this):
    total FLOPs per executed step, tokens per step, the optimizer's
    FLOP share, and optionally the chip's peak FLOP/s."""
    with _lock:
        if flops_per_step is not None:
            _config["flops_per_step"] = float(flops_per_step)
        if tokens_per_step is not None:
            _config["tokens_per_step"] = int(tokens_per_step)
        if optimizer_flops is not None:
            _config["optimizer_flops"] = float(optimizer_flops)
        if peak_flops is not None:
            _config["peak_flops"] = float(peak_flops)


# -------------------------------------------------------- overlap estimator
def ring_overlap(comm_s_per_step: float, compute_s_per_step: float,
                 steps: int = 1):
    """Hidden/exposed split for a ring whose permutes ride inside
    per-step GEMMs (the TP decomposed matmuls): each of ``steps`` hops
    hides up to the step's compute time."""
    c = max(0.0, float(comm_s_per_step))  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
    g = max(0.0, float(compute_s_per_step))  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
    hidden = min(c, g) * steps
    exposed = (c - min(c, g)) * steps
    return hidden, exposed


def bucket_overlap(comm_s_total: float, n_buckets: int):
    """Hidden/exposed split for bucketed gradient sync issued during
    backward: every bucket's reduction overlaps the remaining backward
    compute except the LAST one (nothing left to hide behind), so one
    bucket hides nothing and ``n`` buckets hide ``(n-1)/n``."""
    c = max(0.0, float(comm_s_total))  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
    n = max(1, int(n_buckets))  # ptlint: disable=jit-purity (static bucket count)
    exposed = c / n
    return c - exposed, exposed


def pipeline_overlap(hop_s: float, num_micro: int, num_stages: int):
    """Hidden/exposed split for the compiled 1F1B ring: one boundary
    hop per tick over ``M + S - 1`` ticks; steady-state hops ride
    inside stage compute, the fill/drain bubble's ``S - 1`` hops have
    no compute to hide behind."""
    h = max(0.0, float(hop_s))  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
    M = max(1, int(num_micro))  # ptlint: disable=jit-purity (static schedule shape)
    S = max(1, int(num_stages))  # ptlint: disable=jit-purity (static schedule shape)
    ticks = M + S - 1
    exposed = (S - 1) * h
    return (ticks - (S - 1)) * h, exposed


def note_overlap(mechanism: str, hidden_s: float, exposed_s: float,
                 detail: Optional[dict] = None) -> None:
    """Record one mechanism's per-step hidden/exposed comm estimate
    (latest note wins — mechanisms re-note on retrace)."""
    if not _active:
        return
    _count_invocation()
    hidden_s = max(0.0, float(hidden_s))  # ptlint: disable=jit-purity (host seconds from the device model, never a tracer)
    exposed_s = max(0.0, float(exposed_s))  # ptlint: disable=jit-purity (host seconds from the device model, never a tracer)
    total = hidden_s + exposed_s
    eff = hidden_s / total if total > 0 else 1.0
    entry = {"hidden_s": hidden_s, "exposed_s": exposed_s,
             "efficiency": eff}
    if detail:
        entry["detail"] = dict(detail)
    with _lock:
        _overlap[mechanism] = entry
    _registry.gauge("prof.overlap_efficiency",
                    tags={"mechanism": mechanism}).set(eff)
    _registry.gauge("prof.comm_hidden_s",
                    tags={"mechanism": mechanism}).set(hidden_s)
    _registry.gauge("prof.comm_exposed_s",
                    tags={"mechanism": mechanism}).set(exposed_s)


def note_ring_overlap(mechanism: str, comm_bytes_per_step: float,
                      compute_flops_per_step: float, steps: int,
                      detail: Optional[dict] = None) -> None:
    if not _active:
        return
    c = comm_bytes_per_step / link_bandwidth()
    pk = peak_flops()
    g = compute_flops_per_step / pk if pk > 0 else c  # assume hidden
    hidden, exposed = ring_overlap(c, g, steps)
    d = {"comm_bytes_per_step": int(comm_bytes_per_step),  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
         "flops_per_step": float(compute_flops_per_step),  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
         "ring_steps": int(steps)}  # ptlint: disable=jit-purity (static ring size)
    if detail:
        d.update(detail)
    note_overlap(mechanism, hidden, exposed, d)


def note_bucket_overlap(mechanism: str, comm_bytes_total: float,
                        n_buckets: int,
                        detail: Optional[dict] = None) -> None:
    if not _active:
        return
    c = comm_bytes_total / link_bandwidth()
    hidden, exposed = bucket_overlap(c, n_buckets)
    d = {"comm_bytes": int(comm_bytes_total),  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
         "n_buckets": int(n_buckets)}  # ptlint: disable=jit-purity (static bucket count)
    if detail:
        d.update(detail)
    note_overlap(mechanism, hidden, exposed, d)


def note_pipeline_overlap(mechanism: str, hop_bytes: float,
                          num_micro: int, num_stages: int,
                          detail: Optional[dict] = None) -> None:
    if not _active:
        return
    h = hop_bytes / link_bandwidth()
    hidden, exposed = pipeline_overlap(h, num_micro, num_stages)
    d = {"hop_bytes": int(hop_bytes), "num_micro": int(num_micro),  # ptlint: disable=jit-purity (trace-time static geometry, never a tracer)
         "num_stages": int(num_stages)}  # ptlint: disable=jit-purity (static schedule shape)
    if detail:
        d.update(detail)
    note_overlap(mechanism, hidden, exposed, d)


def overlap_report() -> Dict[str, dict]:
    with _lock:
        return {k: dict(v) for k, v in _overlap.items()}


# -------------------------------------------------------- flops cross-check
def flops_divergence(model_flops: float,
                     xla_flops: Optional[float]) -> Optional[dict]:
    """Cross-check the 6N analytic FLOPs model against XLA's cost
    analysis; records the ``prof.flops_divergence`` gauge and returns
    ``{model, xla, divergence}`` (None when either side is missing).
    bench.py warns when the two disagree by more than 10% — the "MFU
    is never silently wrong" promise, made checkable."""
    global _last_divergence
    if not model_flops or xla_flops is None or xla_flops <= 0:
        return None
    div = abs(float(xla_flops) - float(model_flops)) / float(model_flops)
    entry = {"model": float(model_flops), "xla": float(xla_flops),
             "divergence": div}
    with _lock:
        _last_divergence = entry
    _registry.gauge("prof.flops_divergence").set(div)
    return entry


# ------------------------------------------------------------- step records
class StepRecord:
    """One sampled step's attribution. Boundary discipline: every
    ``mark`` reads the clock once and charges the elapsed interval to
    that phase; ``close`` reads the clock ONCE and the remainder is
    host stall — so the segments sum to wall time exactly. Re-marking
    a phase (a retried dispatch after a preempted step) accumulates
    into it without breaking the invariant."""

    __slots__ = ("step", "_clock", "_t0", "_epoch0", "_last", "_seg",
                 "_bars", "closed")

    def __init__(self, step: int, clock: Callable[[], float] = None,
                 epoch: Optional[float] = None):
        self.step = int(step)
        self._clock = clock or time.perf_counter
        self._t0 = self._clock()
        self._epoch0 = time.time() if epoch is None else float(epoch)
        self._last = self._t0
        self._seg: Dict[str, float] = {}
        self._bars: List[tuple] = []   # (phase, rel_start, rel_end)
        self.closed: Optional[dict] = None

    def mark(self, phase: str) -> None:
        """Charge the time since the previous boundary to ``phase``."""
        t = self._clock()
        self._seg[phase] = self._seg.get(phase, 0.0) + (t - self._last)
        self._bars.append((phase, self._last - self._t0, t - self._t0))
        self._last = t

    def close(self, tokens: int = 0) -> dict:
        """Finalize: read the clock once, assign the remainder to host
        stall, sub-attribute the device segment (exposed collectives
        from the overlap estimator, optimizer from the flop split,
        compute as the remainder) and publish gauges/trace bars."""
        t_end = self._clock()
        wall = t_end - self._t0
        data_wait = self._seg.get("data_wait", 0.0)
        dispatch = self._seg.get("dispatch", 0.0)
        device_s = self._seg.get("device", 0.0)
        if t_end > self._last:
            self._bars.append(("host_stall", self._last - self._t0,
                               t_end - self._t0))
        # exact-sum remainder (can be ~-1e-18 from fp telescoping)
        host_stall = wall - (data_wait + dispatch + device_s)

        with _lock:
            exposed_est = sum(v["exposed_s"] for v in _overlap.values())
            flops = _config["flops_per_step"]
            opt_flops = _config["optimizer_flops"]
        collective_exposed = min(device_s, max(0.0, exposed_est))
        opt_frac = opt_flops / (flops + opt_flops) \
            if flops + opt_flops > 0 else 0.0
        optimizer = min(device_s - collective_exposed,
                        device_s * opt_frac)
        device_compute = device_s - collective_exposed - optimizer

        segments = {"data_wait": data_wait, "dispatch": dispatch,
                    "device_compute": device_compute,
                    "collective_exposed": collective_exposed,
                    "optimizer": optimizer, "host_stall": host_stall}
        pk = peak_flops()
        mfu = flops / wall / pk if (flops > 0 and wall > 0 and pk > 0) \
            else 0.0
        tps = tokens / wall if (tokens and wall > 0) else 0.0
        rep = {"step": self.step, "wall_s": wall, "segments": segments,
               "tokens": int(tokens), "tokens_per_s": tps, "mfu": mfu}
        self.closed = rep
        _publish(self, rep)
        return rep


def _publish(rec: StepRecord, rep: dict) -> None:
    """Registry/windows/trace/flight-recorder export of one closed
    sampled step (registry writes are no-ops when telemetry is off)."""
    global _last_report
    wall = rep["wall_s"]
    _registry.counter("prof.steps_sampled").inc()
    _registry.histogram("prof.step_time").observe(wall)
    _wins.histogram("prof.step_time").observe(wall)
    _wins.gauge("prof.mfu").set(rep["mfu"])
    _wins.gauge("prof.tokens_per_s").set(rep["tokens_per_s"])
    for phase in PHASES:
        frac = rep["segments"][phase] / wall if wall > 0 else 0.0
        _registry.gauge("prof.phase_frac",
                        tags={"phase": phase}).set(frac)
    args = {"step": rep["step"], "tokens": rep["tokens"],
            "mfu": round(rep["mfu"], 4)}
    args.update({k: round(v, 6) for k, v in rep["segments"].items()})
    _tracing.record_complete("prof.step", rec._epoch0, wall,
                             cat="profiler", args=args)
    for phase, rel0, rel1 in rec._bars:
        _tracing.record_complete("prof.phase", rec._epoch0 + rel0,
                                 rel1 - rel0, cat="profiler",
                                 args={"phase": phase,
                                       "step": rep["step"]})
    from . import flight_recorder as _fr

    _fr.record("prof.step", step=rep["step"], wall_s=round(wall, 6),
               **{k: round(v, 6) for k, v in rep["segments"].items()})
    with _lock:
        _last_report = rep
        _reports.append(rep)


def begin_step(step: int) -> Optional[StepRecord]:
    """Start a sampled-step record, or None when this step is not
    sampled (one global read on the off path — zero work)."""
    if not _active:
        return None
    if not should_sample(step):
        return None
    _count_invocation()
    from . import memory as _memory

    _memory.note_phase("step_begin")
    return StepRecord(step)


def last_report() -> Optional[dict]:
    with _lock:
        return dict(_last_report) if _last_report else None


def reports(limit: int = 64) -> List[dict]:
    with _lock:
        out = [dict(r) for r in _reports]
    return out[-limit:]


def report() -> dict:
    """Full profiler report for bundles (profiler_report.json): mode,
    cost-model config, rolling-window snapshot, the per-mechanism
    overlap estimate, the memory phase ledger, the flops cross-check,
    and the last sampled step's attribution (hang post-mortems read
    this — it is the last known-good step breakdown)."""
    from . import memory as _memory

    with _lock:
        rep = {
            "mode": _mode, "sample_every": _every,
            "config": dict(_config),
            "last": dict(_last_report) if _last_report else None,
            "recent": [dict(r) for r in _reports],
            "flops_check": dict(_last_divergence)
            if _last_divergence else None,
        }
    rep["overlap"] = overlap_report()
    rep["memory_phases"] = _memory.phase_report()
    rep["windows"] = _wins.snapshot()
    return rep


def reset() -> None:
    """Test hook: clear reports, overlap notes, config and counters
    (does not touch the mode)."""
    global _last_report, _invocations, _last_divergence
    from . import memory as _memory

    with _lock:
        _reports.clear()
        _last_report = None
        _overlap.clear()
        _invocations = 0
        _last_divergence = None
        _config.update({"flops_per_step": 0.0, "tokens_per_step": 0,
                        "optimizer_flops": 0.0, "peak_flops": 0.0})
    _memory.reset_phases()
