"""Reduction of a ``jax.profiler`` trace (``.xplane.pb``) to what the
per-layer metrics and the result line's ``breakdown`` read.

What the trace of a TPU v5e offers (looked at by hand, PR 24): one plane
``/device:TPU:<n>`` per chip with the lines ``Steps``, ``XLA Modules``
(one event per executed program), ``XLA Ops`` (what the core executes,
one event per HLO instruction, named by the instruction's full text; a
loop's event spans the events of its body) and
``Async XLA Ops``; and one plane ``/host:CPU`` with a line per host
thread (``python3`` holds the Python tracer's frames).

The traced window of a chip runs from the start of its first program to
the start of its last one: whole periods of (program, gap after it), so
a trace that starts or stops in the middle of a step counts no partial
gap. Busy time is the union of the ``XLA Ops`` intervals inside it.
"""
from __future__ import annotations

import bisect
import re

_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all")


def parse_hlo(text: str) -> dict:
    """``%name.3 = <result type> opcode(<operands>), attrs`` -> its parts.
    Shapes are ``(dtype, dims)`` pairs; layouts and tiling are dropped."""
    name, _, rest = text.partition(" = ")
    name = name.lstrip("%")
    base = re.sub(r"\.[0-9]+$", "", name)
    if not rest:                       # not an HLO instruction
        return {"name": name, "base": base, "opcode": "", "results": [],
                "operands": [], "target": ""}
    # the result type is one token, or a parenthesised tuple
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        rtype, tail = rest[:i + 1], rest[i + 1:].lstrip()
    else:
        rtype, _, tail = rest.partition(" ")
    opcode, _, args = tail.partition("(")
    # operands end at the parenthesis that closes the call
    depth, end = 1, len(args)
    for i, ch in enumerate(args):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break
    m = _TARGET.search(args, end)

    def shapes(s):
        return [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in _SHAPE.findall(s)]

    return {"name": name, "base": base, "opcode": opcode.strip(),
            "results": shapes(rtype), "operands": shapes(args[:end]),
            "target": m.group(1) if m else ""}


def op_label(op: dict) -> str:
    """Short name for the breakdown: instruction base name + first result."""
    if not op["results"]:
        return op["base"]
    d, dims = op["results"][0]
    return "%s %s[%s]" % (op["base"], d, ",".join(map(str, dims)))


def is_collective(op: dict) -> bool:
    code = op["opcode"]
    return any(code == c or code == c + "-start" or code == c + "-done"
               for c in _COLLECTIVES)


def classify_kernel(op: dict):
    """Which Pallas kernel a ``tpu_custom_call`` is, told by its shapes
    (the instruction's name comes from the wrapping transform — ``jvp__``,
    ``transpose_jvp___`` — and is not the kernel's). Returns
    ``(kind, dims)`` or ``None``."""
    if op["target"] != "tpu_custom_call":
        return None
    ins, outs = op["operands"], op["results"]
    rank3 = [s for s in ins if len(s[1]) == 3]
    if len(ins) == 3 and len(outs) == 2 and len(rank3) == 3 \
            and len({s[1] for s in ins}) == 1 and outs[0][1] == ins[0][1]:
        bh, seq, hd = ins[0][1]
        return "flash_fwd", {"bh": bh, "seq": seq, "head_dim": hd}
    if len(outs) == 3 and len({s[1] for s in outs}) == 1 \
            and len(outs[0][1]) == 3 and len(ins) >= 5 \
            and all(s[1] == outs[0][1] for s in ins[:4]):
        bh, seq, hd = outs[0][1]
        return "flash_bwd", {"bh": bh, "seq": seq, "head_dim": hd}
    pools = [s for s in ins if len(s[1]) == 4 and s[0] != "s32"]
    if ins and ins[0][0] == "s32" and len(ins[0][1]) == 2 \
            and len(pools) >= 2 and pools[-1][1] == pools[-2][1]:
        return "ragged_attn", {"pool": pools[-1][1]}
    return "other_pallas", {}


def _union(intervals):
    """Merged, sorted ``[start, end]`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _host_events(planes):
    """Per host line, events sorted by start: (starts, [(s, e, name)])."""
    lines = {}
    for pl in planes:
        if pl.name != "/host:CPU":
            continue
        for ln in pl.lines:
            evs = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in ln.events)
            if evs:
                lines[ln.name] = ([e[0] for e in evs], evs)
    return lines


def _innermost(host_lines, t):
    """The shortest host event over instant ``t``, as ``line: name``. A
    Python frame wins over a runtime thread's event: it says what the
    program was doing, the other only that the runtime was awake."""
    best = None
    for line, (starts, evs) in host_lines.items():
        i = bisect.bisect_right(starts, t)
        # frames nest, so the innermost live one is near the last start
        for s, e, name in reversed(evs[max(0, i - 64):i]):
            if e >= t:
                rank = (0 if line.startswith("python") else 1, e - s)
                if best is None or rank < best[0]:
                    best = (rank, "%s: %s" % (line.split("/")[0], name))
                break
    return best[1] if best else "(no host event)"


def reduce_trace(path: str, top: int = 10) -> dict:
    """-> ``{"chips": {plane: {...}}, "busy_s", "window_s", "breakdown"}``.
    ``busy_s`` and ``window_s`` are means over the chips; each chip's
    entry has its own, its ops aggregated per instruction (``ops``, with
    parsed shapes, seconds and count) and its idle gaps."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    host_lines = _host_events(planes)
    chips = {}
    for pl in planes:
        if not pl.name.startswith("/device:TPU:"):
            continue
        lines = {ln.name: ln for ln in pl.lines}
        if "XLA Ops" not in lines or "XLA Modules" not in lines:
            continue
        mods = sorted(e.start_ns for e in lines["XLA Modules"].events)
        if len(mods) < 2:
            continue
        w0, w1 = mods[0], mods[-1]
        ops, intervals, stack = {}, [], []
        clipped = sorted(
            (max(e.start_ns, w0), -min(e.start_ns + e.duration_ns, w1),
             e.name) for e in lines["XLA Ops"].events)
        for s, neg_t, name in clipped:
            t = -neg_t
            if t <= s:
                continue
            intervals.append((s, t))
            rec = ops.get(name)
            if rec is None:
                rec = ops[name] = dict(parse_hlo(name), seconds=0.0,
                                       count=0)
            # the line nests: a while loop's event spans its body's. An
            # op's seconds are its own, without the ops inside it.
            while stack and stack[-1][0] <= s:
                stack.pop()
            if stack:
                stack[-1][1]["seconds"] -= (t - s) * 1e-9
            stack.append((t, rec))
            rec["seconds"] += (t - s) * 1e-9
            rec["count"] += 1
        merged = _union(intervals)
        busy = sum(e - s for s, e in merged) * 1e-9
        gaps = {}
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                who = _innermost(host_lines, (a + b) / 2)
                gaps[who] = gaps.get(who, 0.0) + (b - a) * 1e-9
        chips[pl.name] = {"window_s": (w1 - w0) * 1e-9, "busy_s": busy,
                          "programs": len(mods) - 1,
                          "ops": list(ops.values()), "gaps": gaps}
    if not chips:
        return {"chips": {}, "busy_s": 0.0, "window_s": 0.0,
                "breakdown": {"device_ops": [], "idle_gaps": []}}
    n = len(chips)
    first = chips[sorted(chips)[0]]
    by_label = {}
    for op in first["ops"]:
        lab = op_label(op)
        by_label[lab] = by_label.get(lab, 0.0) + op["seconds"]

    def top_of(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"chips": chips,
            "busy_s": sum(c["busy_s"] for c in chips.values()) / n,
            "window_s": sum(c["window_s"] for c in chips.values()) / n,
            "breakdown": {"device_ops": top_of(by_label),
                          "idle_gaps": top_of(first["gaps"])}}


def worst_idle_share(trace: dict):
    """1 - busy/window on the chip where that is largest, in percent."""
    shares = [100.0 * (1.0 - c["busy_s"] / c["window_s"])
              for c in trace["chips"].values() if c["window_s"] > 0]
    return max(shares) if shares else None
