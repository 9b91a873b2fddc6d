"""Per-request serving lifecycle records: the access log.

Every request that enters a :class:`~paddle_tpu.serving.engine.
ServingEngine` (or is shed at a :class:`~paddle_tpu.serving.cluster.
router.ClusterRouter`) gets one :class:`RequestTimeline` — a tiny
phase state machine threaded through the scheduler and the engine hot
paths:

    arrival ──queue──▶ admission ──prefill──▶ first token
        ──decode──▶ ( preempt ──▶ prefill ──▶ decode )* ──▶ finish

Each transition banks the elapsed time into the phase being *left*, so
at close the four attribution segments (``queue_s`` / ``prefill_s`` /
``decode_s`` / ``preempt_s``) sum to the end-to-end latency exactly —
the acceptance invariant serve_smoke asserts. Re-prefill after a
preemption counts as *prefill* (it is real compute); the ``preempt``
bucket is pure stall: time spent waiting for re-admission.

``arrival`` is stamped under the engine's lock, so the time a client's
``submit()`` waited for that lock is before every segment. The engine
measures it and hands it to :meth:`RequestLog.open`; the record carries
it as ``lock_wait_s`` beside ``queue_s``. ``e2e_s`` stays the sum of the
four segments: what the client waited is ``lock_wait_s + e2e_s``.

Closing a record does three things with one math path:

* updates the owning :class:`~.windows.Windows` rolling instruments
  (``rt.*`` family) — the SAME windows the SLO engine, ptop, and the
  bench verdicts read;
* appends a JSON line to the structured access log
  (``PADDLE_TPU_ACCESS_LOG`` or an explicit path) and to a bounded
  in-memory tail (the flight-recorder bundle section);
* injects a finished ``rt.request`` span into the PR-2 tracer
  (:func:`~.tracing.record_complete`), so one Perfetto timeline shows
  the request bar spanning router → replica → ragged steps.

Everything is clock-injectable and allocation-light; nothing here runs
unless telemetry is enabled (call sites gate on ``_obs.enabled()``
before creating timelines).
"""
from __future__ import annotations

import json
import os
import threading
import time
import weakref
from collections import deque
from typing import Callable, Dict, List, Optional

from ..config import knobs
from . import tracing as _tracing
from . import windows as _w

__all__ = ["RequestTimeline", "RequestLog", "tail_all", "OUTCOMES",
           "QUEUE", "PREFILL", "DECODE", "PREEMPT", "attribution_of",
           "write_snapshot"]

# attribution phases (segment keys = phase + "_s" in the record)
QUEUE, PREFILL, DECODE, PREEMPT = "queue", "prefill", "decode", "preempt"
_SEGMENTS = (QUEUE, PREFILL, DECODE, PREEMPT)

# terminal states of a record — serve_smoke asserts membership
OUTCOMES = ("finished", "shed", "cancelled")

_FINISHED_REASONS = ("eos", "length")
_SHED_REASONS = ("shed", "overloaded")


def _outcome(reason: str) -> str:
    if reason in _FINISHED_REASONS:
        return "finished"
    if reason in _SHED_REASONS:
        return "shed"
    return "cancelled"      # deadline / shutdown / replica_dead / ...


class RequestTimeline:
    """Lifecycle + attribution accumulator for ONE request. Mutated
    only from the engine's locked sections (submit/step), so it needs
    no lock of its own."""

    __slots__ = ("rid", "log", "arrived", "wall_arrived", "phase",
                 "phase_t0", "segs", "ttft", "last_emit", "tokens",
                 "prompt_tokens", "prefix_hit_tokens", "preemptions",
                 "closed", "lock_wait")

    def __init__(self, log: "RequestLog", rid, prompt_tokens: int = 0,
                 lock_wait_s: float = 0.0):
        self.log = log
        self.rid = rid
        self.lock_wait = float(lock_wait_s)
        now = log._clock()
        self.arrived = now
        self.wall_arrived = log._wall()
        self.phase = QUEUE
        self.phase_t0 = now
        self.segs: Dict[str, float] = dict.fromkeys(_SEGMENTS, 0.0)
        self.ttft: Optional[float] = None
        self.last_emit: Optional[float] = None
        self.tokens = 0
        self.prompt_tokens = int(prompt_tokens)
        self.prefix_hit_tokens = 0
        self.preemptions = 0
        self.closed = False

    def _to_phase(self, phase: str) -> None:
        """Bank the elapsed time into the phase being left."""
        now = self.log._clock()
        self.segs[self.phase] += now - self.phase_t0
        self.phase = phase
        self.phase_t0 = now

    # ------------------------------------------------------- transitions
    def mark_admitted(self) -> None:
        """WAITING -> PREFILL (first admission or post-preempt
        re-admission): queue/preempt stall ends, compute begins."""
        if not self.closed:
            self._to_phase(PREFILL)

    def mark_running(self, stamp_ttft: bool = True) -> None:
        """Prefill complete, first token sampled: decode begins. TTFT
        stamps only the FIRST time — a preempted request re-prefills
        but its first token streamed long ago. ``stamp_ttft=False``
        skips the stamp entirely (adopted disagg handoffs: the first
        token streamed on the prefill replica, a local 0 would corrupt
        the window)."""
        if self.closed:
            return
        self._to_phase(DECODE)
        if stamp_ttft and self.ttft is None:
            self.ttft = self.log._clock() - self.arrived
            self.log.windows.histogram("rt.ttft").observe(self.ttft)

    def mark_preempted(self) -> None:
        """Evicted mid-flight: everything until re-admission is stall."""
        if self.closed:
            return
        self._to_phase(PREEMPT)
        self.preemptions += 1
        self.log.windows.counter("rt.preemptions").inc()

    def mark_emit(self) -> None:
        """One token streamed to the client."""
        if self.closed:
            return
        self.tokens += 1
        now = self.log._clock()
        win = self.log.windows
        win.counter("rt.tokens").inc()
        if self.last_emit is not None:
            win.histogram("rt.token_gap").observe(now - self.last_emit)
        self.last_emit = now

    def mark_prefix_hit(self, n_tokens: int) -> None:
        """Prompt tokens restored from the paged prefix cache."""
        if self.closed or n_tokens <= 0:
            return
        self.prefix_hit_tokens += int(n_tokens)
        self.log.windows.counter("rt.prefix_hit_tokens").inc(n_tokens)

    def close(self, reason: str) -> Optional[dict]:
        """Terminal transition (idempotent): bank the open phase, emit
        the record. Returns the record dict (None on double close)."""
        if self.closed:
            return None
        now = self.log._clock()
        self.segs[self.phase] += now - self.phase_t0  # bank open phase
        self.phase_t0 = now
        self.closed = True
        e2e = now - self.arrived   # same read: segments sum to e2e EXACTLY
        rec = {"rid": self.rid, "source": self.log.source,
               "ts": self.wall_arrived, "outcome": _outcome(reason),
               "reason": reason, "e2e_s": e2e,
               "lock_wait_s": self.lock_wait,
               "queue_s": self.segs[QUEUE],
               "prefill_s": self.segs[PREFILL],
               "decode_s": self.segs[DECODE],
               "preempt_s": self.segs[PREEMPT],
               "ttft_s": self.ttft, "tokens": self.tokens,
               "prompt_tokens": self.prompt_tokens,
               "prefix_hit_tokens": self.prefix_hit_tokens,
               "preemptions": self.preemptions}
        self.log._finish(rec)
        return rec


class RequestLog:
    """The per-engine (or per-router) access log: owns the rolling
    windows the records feed, the JSONL sink, and a bounded in-memory
    tail for debug bundles."""

    def __init__(self, source: str = "", windows: Optional[_w.Windows]
                 = None, path: Optional[str] = None, tail: int = 512,
                 clock: Callable[[], float] = time.monotonic,
                 wall: Callable[[], float] = time.time):
        self.source = source
        self._clock = clock
        self._wall = wall
        self.windows = windows if windows is not None \
            else _w.Windows(source or "rt", clock=clock)
        self.path = path if path is not None \
            else knobs.get_str("PADDLE_TPU_ACCESS_LOG") or None
        self._tail: deque = deque(maxlen=max(int(tail), 1))
        self._lock = threading.Lock()
        self._file = None  # guarded by: _lock
        self.opened = 0
        self.closed = 0
        _live_logs.add(self)

    # ------------------------------------------------------------ intake
    def open(self, rid, prompt_tokens: int = 0,
             lock_wait_s: float = 0.0) -> RequestTimeline:
        """New request entering the queue (counts as submitted).
        ``lock_wait_s``: how long its ``submit()`` waited for the
        engine's lock before this call."""
        self.windows.counter("rt.submitted").inc()
        with self._lock:
            self.opened += 1
        return RequestTimeline(self, rid, prompt_tokens, lock_wait_s)

    def shed(self, prompt_tokens: int = 0, rid=None,
             reason: str = "overloaded") -> dict:
        """A request refused at admission: one arrival, one shed — a
        complete record closed on the spot (zero-length segments)."""
        self.windows.counter("rt.submitted").inc()
        self.windows.counter("rt.shed").inc()
        with self._lock:
            self.opened += 1
            if rid is None:
                rid = "shed-%d" % self.opened
        tl = RequestTimeline(self, rid, prompt_tokens)
        return tl.close(reason)

    # ------------------------------------------------------------- sinks
    def _finish(self, rec: dict) -> None:
        win = self.windows
        win.counter("rt.finished").inc()
        win.histogram("rt.e2e").observe(rec["e2e_s"])
        win.histogram("rt.lock_wait").observe(rec["lock_wait_s"])
        win.histogram("rt.queue_wait").observe(rec["queue_s"])
        win.histogram("rt.prefill_time").observe(rec["prefill_s"])
        win.histogram("rt.decode_time").observe(rec["decode_s"])
        win.histogram("rt.preempt_stall").observe(rec["preempt_s"])
        with self._lock:
            self.closed += 1
            self._tail.append(rec)
            self._write_line(rec)
        _tracing.record_complete(
            "rt.request", ts_s=rec["ts"], dur_s=rec["e2e_s"],
            cat="request",
            args={"rid": str(rec["rid"]), "source": rec["source"],
                  "outcome": rec["outcome"], "reason": rec["reason"],
                  "tokens": rec["tokens"],
                  "lock_wait_s": round(rec["lock_wait_s"], 6),
                  "queue_s": round(rec["queue_s"], 6),
                  "prefill_s": round(rec["prefill_s"], 6),
                  "decode_s": round(rec["decode_s"], 6),
                  "preempt_s": round(rec["preempt_s"], 6)})

    def _write_line(self, rec: dict) -> None:  # ptlint: holds=_lock
        if not self.path:
            return
        try:
            if self._file is None:
                d = os.path.dirname(self.path)
                if d:
                    os.makedirs(d, exist_ok=True)
                self._file = open(self.path, "a", encoding="utf-8")
            self._file.write(json.dumps(rec) + "\n")
            self._file.flush()
        except OSError:
            self.path = None            # disk gone: stop trying

    # ----------------------------------------------------------- queries
    def tail(self, n: Optional[int] = None) -> List[dict]:
        with self._lock:
            out = list(self._tail)
        return out if n is None else out[-int(n):]

    def attribution(self, window_s: Optional[float] = None) -> dict:
        """Mean per-segment milliseconds over the rolling window — read
        from the SAME windows the dashboard and SLO engine use, so the
        bench JSON and ptop can never disagree."""
        return attribution_of([self.windows], window_s)

    def flush_close(self) -> None:
        with self._lock:
            if self._file is not None:
                try:
                    self._file.close()
                except OSError:
                    pass
                self._file = None


# weak registry of live logs so the flight recorder can dump every
# access-log tail without plumbing handles through layers
_live_logs: "weakref.WeakSet[RequestLog]" = weakref.WeakSet()


def attribution_of(windows_list, window_s: Optional[float] = None
                   ) -> dict:
    """Mean per-segment milliseconds over one or more Windows
    collections, merged at the histogram-state level (the cluster
    case: per-replica windows sum into one attribution row)."""
    def _mean_ms(metric: str) -> float:
        st = _w.merge_states([w.histogram(metric).state(window_s)
                              for w in windows_list])
        return st["sum"] / st["count"] * 1e3 if st["count"] else 0.0

    e2e = _w.merge_states([w.histogram("rt.e2e").state(window_s)
                           for w in windows_list])
    return {
        "mean_queue_ms": _mean_ms("rt.queue_wait"),
        "mean_prefill_ms": _mean_ms("rt.prefill_time"),
        "mean_decode_ms": _mean_ms("rt.decode_time"),
        "mean_preempt_ms": _mean_ms("rt.preempt_stall"),
        "mean_e2e_ms": e2e["sum"] / e2e["count"] * 1e3
                       if e2e["count"] else 0.0,
        "requests": e2e["count"],
    }


def write_snapshot(snap: dict, path: str) -> None:
    """Atomically write an ops snapshot (tmp + rename) — the file
    ``tools/ptop.py --snapshot`` renders."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=1)
    os.replace(tmp, path)


def tail_all(n: int = 50) -> List[dict]:
    """Most-recent closed records across every live RequestLog, oldest
    first (the debug-bundle section)."""
    recs: List[dict] = []
    for log in list(_live_logs):
        recs.extend(log.tail(n))
    recs.sort(key=lambda r: r.get("ts", 0.0))
    return recs[-n:]
