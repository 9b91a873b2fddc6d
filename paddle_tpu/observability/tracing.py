"""Distributed span tracing over the PR-1 metrics registry.

A ``Span`` is one timed window (chrome-trace ``"ph": "X"`` complete
event); the process-wide ``Tracer`` keeps a thread-local span stack (so
nesting gives parent/child edges without any user bookkeeping) and a
bounded ring of finished spans. Everything shares the registry's
zero-cost-when-disabled contract: ``span(...)`` returns ONE shared no-op
object when telemetry is off — no id generation, no clock read, no
allocation on any hot path.

Cross-rank stitching: ``current_context()`` captures the active
``{trace_id, span_id}``; carriers (FleetExecutor ``_Msg``, rpc payloads)
ship it to the peer rank, which adopts it with ``activate_context`` so
its spans join the SAME trace. Each rank exports with its own chrome
``pid`` (``set_rank``), so ``merge_chrome_traces`` over the per-rank
files yields one Perfetto timeline with one row-group per rank.

One clock with the device trace: an open span also holds a
``jax.profiler.TraceAnnotation`` of its name, so while a ``jax.profiler``
trace runs every program span is an event on its thread's line of the
``/host:CPU`` plane of the ``.xplane.pb``, beside the device's lines.

Reference analog: fluid/platform/profiler host tracer spans +
RecordEvent; the trace-id plumbing plays the role NCCL/brpc sequence
numbers play in the reference's cross-rank hang reports.
"""
from __future__ import annotations

import json
import os
import random
import threading
import time
from collections import deque
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation as _TraceAnnotation

from ..config import knobs
from .registry import enabled as _enabled

__all__ = ["Span", "Tracer", "tracer", "span", "current_context",
           "activate_context", "set_rank", "get_rank", "trace_pid",
           "export_chrome_trace", "merge_chrome_traces", "reset",
           "finished_spans", "record_complete"]

# ring capacity: finished spans kept for export (oldest dropped first)
_DEFAULT_CAPACITY = knobs.get_int("PADDLE_TPU_TRACE_CAPACITY")

_rank: Optional[int] = None


def set_rank(rank: int) -> None:
    """Pin the chrome-trace pid of this process to ``rank`` so merged
    multi-rank traces get one process row-group per rank (defaults to
    PADDLE_TRAINER_ID, falling back to the real pid)."""
    global _rank
    _rank = int(rank)


def get_rank() -> Optional[int]:
    if _rank is not None:
        return _rank
    v = os.environ.get("PADDLE_TRAINER_ID")
    return int(v) if v else None


def trace_pid() -> int:
    r = get_rank()
    return r if r is not None else os.getpid()


# ids come from a generator of this module's own, seeded from the OS once
# (so ranks differ, and ``random.seed`` elsewhere does not touch it). Not
# ``os.urandom`` per id: that is a system call, which lets go of the GIL,
# and a span opened just before a lock is taken then hands the lock to
# whoever waits for it. The instrument would change the order of events.
_ids = random.Random()


def _new_id() -> str:
    return "%016x" % _ids.getrandbits(64)


class Span:
    """One timed window. Use via ``with tracer.span("engine.step"): ...``
    — never constructed on the disabled path."""

    __slots__ = ("name", "cat", "args", "trace_id", "span_id",
                 "parent_id", "ts", "dur", "tid", "_tracer", "_t0",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[dict]):
        self.name = name
        self.cat = cat
        self.args = dict(args) if args else {}
        self._tracer = tracer
        self.trace_id = ""
        self.span_id = _new_id()
        self.parent_id = ""
        self.ts = 0.0          # µs since epoch (chrome convention)
        self.dur = 0.0         # µs
        self.tid = 0
        self._t0 = 0.0
        self._annotation = None

    def set_arg(self, key: str, value) -> None:
        self.args[str(key)] = value

    def __enter__(self) -> "Span":
        self._tracer._push(self)
        # the same window on the profiler's clock: the args known now
        # (scalars only) become the event's stats; later set_arg()s
        # reach the ring alone
        scalars = {k: v for k, v in self.args.items()
                   if isinstance(v, (str, int, float, bool))} \
            if self.args else self.args
        self._annotation = _TraceAnnotation(self.name, **scalars)
        self._annotation.__enter__()
        self.ts = time.time() * 1e6
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.dur = (time.perf_counter() - self._t0) * 1e6
        self._annotation.__exit__(exc_type, exc, tb)
        self._annotation = None
        if exc_type is not None:
            self.args["error"] = exc_type.__name__
        self._tracer._pop(self)

    def to_event(self, pid: Optional[int] = None) -> dict:
        ev = {"ph": "X", "name": self.name, "cat": self.cat,
              "ts": self.ts, "dur": self.dur,
              "pid": trace_pid() if pid is None else pid,
              "tid": self.tid,
              "args": dict(self.args)}
        ev["args"]["trace_id"] = self.trace_id
        ev["args"]["span_id"] = self.span_id
        if self.parent_id:
            ev["args"]["parent_span_id"] = self.parent_id
        return ev


class _NoopSpan:
    """Shared span handed out while telemetry is disabled."""

    __slots__ = ()

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def set_arg(self, key, value) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _RemoteParent:
    """Stack entry adopting a context that arrived from another rank (or
    thread): children parent onto it, but it emits no event of its own —
    the real span lives wherever the context was captured."""

    __slots__ = ("trace_id", "span_id")

    def __init__(self, trace_id: str, span_id: str):
        self.trace_id = trace_id
        self.span_id = span_id


class _ContextScope:
    def __init__(self, tracer: "Tracer", ctx: Optional[dict]):
        self._tracer = tracer
        self._entry = None
        if ctx and ctx.get("trace_id"):
            self._entry = _RemoteParent(str(ctx["trace_id"]),
                                        str(ctx.get("span_id", "")))

    def __enter__(self):
        if self._entry is not None:
            self._tracer._stack().append(self._entry)
        return self

    def __exit__(self, *exc):
        if self._entry is not None:
            stack = self._tracer._stack()
            if stack and stack[-1] is self._entry:
                stack.pop()
            elif self._entry in stack:   # unbalanced nesting: best effort
                stack.remove(self._entry)


class Tracer:
    """Process-wide tracer: thread-local span stacks feeding one bounded
    ring of finished spans."""

    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self._local = threading.local()
        # lock-free by design: deque.append / snapshot-copy are atomic
        # under the GIL (deque is documented thread-safe for these), so
        # the finished-span ring needs no lock on the hot span-exit path
        self._done: deque = deque(  # ptlint: disable=thread-escape
            maxlen=max(int(capacity), 1))
        self._lock = threading.Lock()
        self._tids: Dict[int, int] = {}

    # ------------------------------------------------------------ stack
    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _tid(self) -> int:
        ident = threading.get_ident()
        t = self._tids.get(ident)
        if t is None:
            with self._lock:
                t = self._tids.setdefault(ident, len(self._tids))
        return t

    def _push(self, sp: Span) -> None:
        stack = self._stack()
        if stack:
            parent = stack[-1]
            sp.trace_id = parent.trace_id
            sp.parent_id = parent.span_id
        else:
            sp.trace_id = _new_id()
        sp.tid = self._tid()
        stack.append(sp)

    def _pop(self, sp: Span) -> None:
        stack = self._stack()
        if stack and stack[-1] is sp:
            stack.pop()
        elif sp in stack:               # unbalanced exit: best effort
            stack.remove(sp)
        self._done.append(sp)

    # -------------------------------------------------------------- api
    def span(self, name: str, cat: str = "host",
             args: Optional[dict] = None):
        """Open a span (context manager). The ONE gate: disabled
        telemetry returns the shared no-op."""
        if not _enabled():
            return _NOOP_SPAN
        return Span(self, name, cat, args)

    def record_complete(self, name: str, ts_s: float, dur_s: float,
                        cat: str = "host",
                        args: Optional[dict] = None) -> Optional[Span]:
        """Inject an ALREADY-finished span into the ring — for events
        whose start/end were measured elsewhere (a request's lifecycle
        closed by the access log, a remote worker's reported window).
        ``ts_s`` is wall-clock epoch seconds, ``dur_s`` the duration;
        chrome-trace convention (µs) is applied here. Parents onto the
        caller's open span if any, so the synthesized bar lands inside
        the live trace tree. No-op (returns None) when disabled."""
        if not _enabled():
            return None
        sp = Span(self, name, cat, args)
        stack = getattr(self._local, "stack", None)
        if stack:
            sp.trace_id = stack[-1].trace_id
            sp.parent_id = stack[-1].span_id
        else:
            sp.trace_id = _new_id()
        sp.tid = self._tid()
        sp.ts = float(ts_s) * 1e6
        sp.dur = max(0.0, float(dur_s)) * 1e6
        self._done.append(sp)
        return sp

    def current_context(self) -> Optional[dict]:
        """The active ``{trace_id, span_id}`` for cross-rank/thread
        propagation; None when disabled or no span is open."""
        if not _enabled():
            return None
        stack = getattr(self._local, "stack", None)
        if not stack:
            return None
        top = stack[-1]
        return {"trace_id": top.trace_id, "span_id": top.span_id}

    def activate_context(self, ctx: Optional[dict]) -> _ContextScope:
        """Adopt a propagated context: spans opened inside the scope
        parent onto it (joining the remote trace). A None/empty ctx is a
        no-op scope, so call sites never need to branch."""
        return _ContextScope(self, ctx if _enabled() else None)

    def finished_spans(self) -> List[Span]:
        return list(self._done)

    def reset(self) -> None:
        self._done.clear()
        self._tids.clear()
        self._local = threading.local()

    # ----------------------------------------------------------- export
    def chrome_events(self) -> List[dict]:
        pid = trace_pid()
        rank = get_rank()
        label = f"rank{rank}" if rank is not None else f"pid{pid}"
        events: List[dict] = [
            {"ph": "M", "name": "process_name", "pid": pid, "tid": 0,
             "args": {"name": f"paddle_tpu {label}"}}]
        events.extend(sp.to_event(pid) for sp in self.finished_spans())
        return events

    def export_chrome_trace(self, path: str) -> dict:
        """Write finished spans as a chrome-trace JSON file (atomic:
        temp file + rename). Compose with
        ``exporters.merge_counters_into_trace(path)`` for counter
        tracks."""
        doc = {"traceEvents": self.chrome_events(),
               "displayTimeUnit": "ms"}
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return doc


tracer = Tracer()


def span(name: str, cat: str = "host", args: Optional[dict] = None):
    return tracer.span(name, cat, args)


def current_context() -> Optional[dict]:
    return tracer.current_context()


def activate_context(ctx: Optional[dict]) -> _ContextScope:
    return tracer.activate_context(ctx)


def record_complete(name: str, ts_s: float, dur_s: float,
                    cat: str = "host",
                    args: Optional[dict] = None) -> Optional[Span]:
    return tracer.record_complete(name, ts_s, dur_s, cat, args)


def finished_spans() -> List[Span]:
    return tracer.finished_spans()


def reset() -> None:
    tracer.reset()


def export_chrome_trace(path: str) -> dict:
    return tracer.export_chrome_trace(path)


def merge_chrome_traces(paths: List[str], out_path: str) -> dict:
    """Stitch per-rank chrome-trace files into ONE timeline: concatenates
    ``traceEvents`` (ranks already carry distinct pids via set_rank).
    Unreadable inputs are skipped — a crashed rank must not take the
    surviving ranks' trace with it."""
    events: List[dict] = []
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
            events.extend(doc.get("traceEvents", []))
        except Exception:
            continue
    merged = {"traceEvents": events, "displayTimeUnit": "ms"}
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = f"{out_path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(merged, f)
    os.replace(tmp, out_path)
    return merged
