"""Compile ledger: per-jit-site compile count, duration, HLO size and
donation stats — with recompile-*cause* attribution.

The PR-1 ``jit.recompile`` counters say a retrace happened; when one
shows up in a 40-hour run nobody can say *why*. This ledger keeps, per
jit site, the last-seen argument signature (shape, dtype, or static
value per arg) and diffs the new signature against it on every call,
so a cache miss carries its cause: ``"arg2 shape (2,16)->(4,16)"``
names the offending argument instead of leaving a bare count. The
trap this exists to catch is the classic silent-retrace-per-step bug —
a Python int riding in a traced position, a data loader that emits a
ragged final batch — which turns into a compile storm visible only as
mysteriously slow steps.

Call sites (``jit/train_step.py``) are gated on
``profiler.profiling_enabled()``: with ``PADDLE_TPU_PROFILE=off``
nothing here runs, preserving the zero-cost contract. Signature
computation is shapes/dtypes only — no device sync, no data reads.

The ledger exports as ``prof.compiles`` / ``prof.compile_time``
metrics and the ``compile_ledger.json`` bundle section rendered by
``tools/diagnose.py``.

A site may also keep a way back to its PROGRAM (:class:`SiteProgram`,
:func:`register_program`): stored once when the site's jit is built,
whatever profiling or telemetry say, and asked only from outside the hot
path (``observability.op_phases``).
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

from .registry import registry as _registry

__all__ = ["signature", "diff_cause", "observe_call", "note_compile",
           "report", "reset", "SiteProgram", "abstract_args",
           "register_program", "program"]

_lock = threading.Lock()
# site -> {"compiles", "calls", "durations": [..], "hlo_bytes",
#          "donated_args", "causes": {cause: n}, "last_sig", "seen"}
_sites: Dict[str, dict] = {}

_MAX_DUR_SAMPLES = 32
# site -> the LAST program registered there
_programs: Dict[str, "SiteProgram"] = {}


class SiteProgram:
    """The way from a jit site back to the program its hot path runs.

    ``lower`` is a zero-argument callable that returns the
    ``jax.stages.Lowered`` of exactly the jit the hot path calls: the same
    function object and donation, abstract arguments (shapes and
    shardings, never arrays). :meth:`compiled` lowers and compiles on the
    first ask and keeps the ``jax.stages.Compiled``; ``lower`` and all it
    closes over are let go then. Until then the ledger holds them (a
    strong reference: the readers ask after the engine or the
    ``TrainStep`` has gone out of scope), so ``lower`` should close over
    what tracing needs and no more."""

    def __init__(self, lower):
        self._lower = lower
        self._compiled = None
        self._derived = {}
        self._lock = threading.Lock()

    def compiled(self):
        with self._lock:
            if self._compiled is None:
                self._compiled = self._lower().compile()
                self._lower = None
            return self._compiled

    def derived(self, key: str, make):
        """``make(compiled)``, computed once a ``key`` and kept."""
        compiled = self.compiled()
        with self._lock:
            if key not in self._derived:
                self._derived[key] = make(compiled)
            return self._derived[key]


def abstract_args(tree):
    """A call's arguments -> what ``jit(...).lower`` needs of them: each
    array's shape, dtype and, where it is committed, sharding
    (``jax.ShapeDtypeStruct`` leaves pass through). No array is kept."""
    import jax

    def one(a):
        if isinstance(a, jax.ShapeDtypeStruct):
            return a
        return jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=a.sharding if a.committed else None)

    return jax.tree_util.tree_map(one, tree)


def register_program(site: str, program: SiteProgram) -> SiteProgram:
    """Keep ``program`` as the site's: the last one registered wins, the
    one before is let go. Costs a dict store; nothing is lowered."""
    with _lock:
        _programs[site] = program
    return program


def program(site: str) -> Optional[SiteProgram]:
    with _lock:
        return _programs.get(site)


def signature(args) -> Tuple:
    """Cheap trace-cache signature of a call's arguments: ``(shape,
    dtype)`` for array-likes (pytrees flattened), ``("static", repr)``
    for everything else. Mirrors what jit keys on, minus weak-type and
    sharding detail — close enough to name the changing arg."""
    out = []
    for a in args:
        sig = _one_sig(a)
        if isinstance(sig, list):
            out.extend(sig)
        else:
            out.append(sig)
    return tuple(out)


def _one_sig(a):
    shape = getattr(a, "shape", None)
    dtype = getattr(a, "dtype", None)
    if shape is not None and dtype is not None:
        return ("array", tuple(shape), str(dtype))
    if isinstance(a, (list, tuple)):
        flat = []
        for x in a:
            s = _one_sig(x)
            flat.extend(s if isinstance(s, list) else [s])
        return flat
    if isinstance(a, dict):
        flat = []
        for k in sorted(a, key=str):
            s = _one_sig(a[k])
            flat.extend(s if isinstance(s, list) else [s])
        return flat
    return ("static", repr(a)[:80])


def diff_cause(old: Optional[Tuple], new: Tuple) -> str:
    """Human-readable cause of a retrace: the first arg whose
    signature differs from the previous call's, and in what way."""
    if old is None:
        return "first_call"
    if len(old) != len(new):
        return f"arity {len(old)}->{len(new)}"
    for i, (o, n) in enumerate(zip(old, new)):
        if o == n:
            continue
        if o[0] == "array" and n[0] == "array":
            if o[1] != n[1]:
                return f"arg{i} shape {o[1]}->{n[1]}"
            return f"arg{i} dtype {o[2]}->{n[2]}"
        if o[0] != n[0]:
            return f"arg{i} kind {o[0]}->{n[0]}"
        return f"arg{i} static {o[1]}->{n[1]}"
    return "unknown"


def _entry(site: str) -> dict:  # ptlint: holds=_lock
    e = _sites.get(site)
    if e is None:
        e = _sites[site] = {
            "compiles": 0, "calls": 0, "durations": [],
            "hlo_bytes": 0, "donated_args": 0,
            "causes": {}, "last_sig": None, "seen": set(),
        }
    return e


def observe_call(site: str, sig: Tuple) -> Tuple[bool, Optional[str]]:
    """Record one call at ``site`` with argument signature ``sig``.
    Returns ``(miss, cause)`` — miss means this signature has not been
    traced at this site before; cause diffs it against the previous
    call (None on a hit). The caller decides what to do with a miss
    (time the dispatch, call :func:`note_compile`)."""
    with _lock:
        e = _entry(site)
        e["calls"] += 1
        miss = sig not in e["seen"]
        cause = diff_cause(e["last_sig"], sig) if miss else None
        e["seen"].add(sig)
        e["last_sig"] = sig
    return miss, cause


def note_compile(site: str, duration_s: Optional[float] = None,
                 cause: str = "first_call",
                 hlo_bytes: Optional[int] = None,
                 donated_args: Optional[int] = None) -> None:
    """Record one compile at ``site``: bump the per-cause counter,
    keep the duration sample, and fold in HLO size / donation stats
    when the caller has them (AOT paths do, dispatch paths don't)."""
    with _lock:
        e = _entry(site)
        e["compiles"] += 1
        e["causes"][cause] = e["causes"].get(cause, 0) + 1
        if duration_s is not None:
            if len(e["durations"]) >= _MAX_DUR_SAMPLES:
                e["durations"].pop(0)
            e["durations"].append(float(duration_s))
        if hlo_bytes:
            e["hlo_bytes"] = max(e["hlo_bytes"], int(hlo_bytes))
        if donated_args is not None:
            e["donated_args"] = int(donated_args)
    _registry.counter("prof.compiles",
                      tags={"site": site, "cause": cause}).inc()
    if duration_s is not None:
        _registry.histogram("prof.compile_time").observe(duration_s)


def report() -> dict:
    """``{"sites": {site: {...}}}`` for compile_ledger.json: per site
    the compile/call counts, cause breakdown, duration stats, and the
    last argument signature (so a post-mortem can see what shape the
    site settled on)."""
    with _lock:
        sites = {}
        for site, e in _sites.items():
            durs = e["durations"]
            sites[site] = {
                "compiles": e["compiles"], "calls": e["calls"],
                "causes": dict(e["causes"]),
                "unique_signatures": len(e["seen"]),
                "compile_time_s": {
                    "total": round(sum(durs), 6),
                    "max": round(max(durs), 6) if durs else 0.0,
                    "samples": len(durs),
                },
                "hlo_bytes": e["hlo_bytes"],
                "donated_args": e["donated_args"],
                "last_signature": [list(s) for s in e["last_sig"]]
                if e["last_sig"] else None,
            }
    return {"sites": sites}


def reset() -> None:
    with _lock:
        _sites.clear()
        _programs.clear()
