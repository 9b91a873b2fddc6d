"""Device time by PHASE of the program: the traced window's operations of
the first chip (``trace["chips"][..]["ops"]``: self seconds by HLO
instruction ``name``) joined with the program's own account of which
phase each instruction of ITS compiled step belongs to
(``paddle_tpu.observability.op_phases(site)``: the serving step's site is
``serving.ragged_step``, the train dispatch's
``train_step.run_steps_stream``). A backward instruction counts in its
forward phase; its direction is kept beside it.

What has no phase (``None``): instructions the compiler made without
metadata (copies, bitcasts, the halves of asynchronous pairs, collectives
it put in), and operations of the window's other programs (the split of
the engine's random key, a transfer), which the step's map does not hold.
Nothing is guessed for them; the table lists the largest by instruction.

A program without ``op_phases`` (the parent of the PR that brought it), a
site that registered no program, or a record without a trace gives
nothing to read: every reader over this helper then returns None.

The join runs once a run and logs two lines, which ``PERF.md`` section 5 is
written from: ``bench: device time by phase:`` (seconds by phase and
direction, the rest, what the lowering took) and ``bench: device ops by
phase:`` (the largest shape-named operations of ``breakdown.device_ops``,
each with the phases and instructions it pools).
"""
from __future__ import annotations

import json
import time

from . import xplane

SITES = {"serve": "serving.ragged_step",
         "train": "train_step.run_steps_stream"}
TOP_NONE = 12          # unphased instructions listed in the log
TOP_LABELS = 24        # shape-named operations listed in the log


def _join(record, cell, site):
    trace = record.get("trace")
    op_phases = getattr(cell.pt.observability, "op_phases", None)
    if not trace or not trace.get("chips") or op_phases is None:
        return None
    t0 = time.perf_counter()
    found = op_phases(site)
    took = time.perf_counter() - t0
    if not found:
        return None
    mapped = found["ops"]
    ops = trace["chips"][sorted(trace["chips"])[0]]["ops"]
    seconds, none, labels = {}, [], {}
    total = 0.0
    for op in ops:
        entry = mapped.get(op["name"])
        phase = entry["phase"] if entry else None
        direction = entry["direction"] if entry else "fwd"
        s = op["seconds"]
        total += s
        by = seconds.setdefault(phase, {"fwd": 0.0, "bwd": 0.0})
        by[direction] += s
        if phase is None:
            none.append((s, op["name"], xplane.op_label(op),
                         "no metadata" if entry else "not of the program"))
        lab = labels.setdefault(xplane.op_label(op),
                                {"seconds": 0.0, "phases": {}, "names": []})
        lab["seconds"] += s
        key = "%s%s" % (phase, "/bwd" if direction == "bwd" else "")
        lab["phases"][key] = lab["phases"].get(key, 0.0) + s
        lab["names"].append((s, op["name"]))
    none.sort(reverse=True)
    # every instruction of a step runs once a step: the median count of the
    # program's instructions in the window is the steps it traced
    counts = sorted(op["count"] for op in ops if op["name"] in mapped)
    table = {"site": site, "module": found.get("module"),
             "busy_s": total, "seconds": seconds,
             "steps": counts[len(counts) // 2] if counts else 0,
             "instructions": {"in_trace": len(ops), "in_program": len(mapped)},
             "none": none, "labels": labels, "op_phases_s": took}
    _log(cell, table)
    return table


def _log(cell, table):
    r = lambda x: round(x, 6)                                   # noqa: E731
    phases = {str(p): {d: r(s) for d, s in by.items() if s}
              for p, by in sorted(table["seconds"].items(),
                                  key=lambda kv: -sum(kv[1].values()))}
    cell.log("device time by phase: %s" % json.dumps({
        "site": table["site"], "module": table["module"],
        "busy_s": r(table["busy_s"]), "steps": table["steps"],
        "op_phases_s": r(table["op_phases_s"]),
        "instructions": table["instructions"], "seconds": phases,
        "largest_without_phase": [
            [name, label, r(s), why]
            for s, name, label, why in table["none"][:TOP_NONE]]}))
    top = sorted(table["labels"].items(),
                 key=lambda kv: -kv[1]["seconds"])[:TOP_LABELS]
    cell.log("device ops by phase: %s" % json.dumps([
        [label, r(v["seconds"]),
         {k: r(s) for k, s in sorted(v["phases"].items(),
                                     key=lambda kv: -kv[1])},
         len(v["names"]), [n for _, n in sorted(v["names"],
                                                reverse=True)[:3]]]
        for label, v in top]))


def table(record, cell, kind):
    """The run's phase table for ``kind`` (``"serve"`` or ``"train"``),
    joined and logged on the first ask, or None."""
    memo = record.setdefault("phase_tables", {})
    if kind not in memo:
        memo[kind] = _join(record, cell, SITES[kind])
    return memo[kind]


def share(record, cell, kind, phases=None):
    """100 x the seconds of ``phases`` (both directions; None: every phase
    that is not ``None``) over the self seconds the table summed."""
    t = table(record, cell, kind)
    if not t or not t["busy_s"] > 0:
        return None
    spent = sum(sum(by.values()) for p, by in t["seconds"].items()
                if (p is not None if phases is None else p in phases))
    return 100.0 * spent / t["busy_s"]
