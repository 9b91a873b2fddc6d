"""Which PHASE of the program a device operation belongs to.

The host's side of a step is told by spans (``tracing.py``); this is
their counterpart on the device's side. Three parts, one mechanism:

1. A small fixed vocabulary (:data:`PHASES`) and :func:`phase`, which is
   ``jax.named_scope`` for a name of it. The serving step
   (``serving/engine.py``, ``models/generation.py``) and the train step
   (``jit/train_step.py``, ``models/gpt.py``) wrap their own code in it,
   where the work is written. A named scope changes no instruction of the
   compiled program, only its metadata: the compiler keeps the path of
   scopes an instruction was traced under as its ``op_name``
   (``jit(step)/attn.proj/attn.kernel/pallas_call``), on fusions too.
   The tape (``core/autograd.py``) records the phase an op ran under and
   runs its backward under :func:`backward_of` the same phase, so a
   backward instruction counts in its forward phase.
2. A jit site of the compile ledger keeps a way back to the program its
   hot path runs (``compile_ledger.register_program``;
   ``ServingEngine.compiled_step()``, ``TrainStep.compiled_dispatch(n)``).
3. :func:`op_phases` reads that program's text once and says, for every
   instruction by its name, which phase it belongs to: what a device
   trace, which knows an operation by its instruction's name and nothing
   of the program, is joined with.

Nothing here runs in a step: a scope exists while a function is traced,
the ledger's reference is stored once an engine or a dispatch's jit, and
the lowering happens on the first :func:`op_phases` call, telemetry on or
off.
"""
from __future__ import annotations

import contextlib
import re
import threading
from typing import Optional, Tuple

import jax

from . import compile_ledger as _ledger

__all__ = ["PHASES", "phase", "backward_of", "innermost", "phase_of",
           "parse_hlo_phases", "op_phases"]

# serving step: carry ... sample (a Mamba mixer's: ssm.proj, in and out
# projections, dt and A; ssm.conv, the causal conv with its state;
# ssm.scan, the scan kernel alone; ssm.gate, the gated norm); train
# dispatch: embed, attn.proj, attn.kernel, ffn, head and loss ...
# optimizer
PHASES = ("carry", "embed", "attn.proj", "attn.kernel", "attn.kv_write",
          "ffn", "moe.route", "moe.dispatch", "moe.experts", "moe.act",
          "moe.combine", "mix", "head", "sample", "loss", "grad_norm",
          "clip", "optimizer", "ssm.proj", "ssm.conv", "ssm.scan",
          "ssm.gate")
_KNOWN = frozenset(PHASES)


class _Active(threading.local):
    names: Tuple[str, ...] = ()


_active = _Active()


class _Phase:
    """One entry of a phase: the named scope, and the name on this
    thread's stack for the tape to read (:func:`innermost`)."""

    __slots__ = ("_name", "_scope", "_before")

    def __init__(self, name: str):
        self._name = name

    def __enter__(self):
        self._before = _active.names
        _active.names = self._before + (self._name,)
        self._scope = jax.named_scope(self._name)
        self._scope.__enter__()

    def __exit__(self, *exc):
        _active.names = self._before
        return self._scope.__exit__(*exc)


def phase(name: str) -> _Phase:
    """``jax.named_scope(name)`` for a name of :data:`PHASES`; any other
    name raises. Phases nest (``attn.kernel`` inside ``attn.proj``): an
    instruction belongs to the innermost."""
    if name not in _KNOWN:
        raise ValueError("phase %r is not of the vocabulary %r"
                         % (name, PHASES))
    return _Phase(name)


def innermost() -> Optional[str]:
    """The phase this thread is tracing (or running eagerly) under."""
    names = _active.names
    return names[-1] if names else None


def backward_of(name: Optional[str]):
    """The scope the tape runs an op's backward under, for an op recorded
    under phase ``name``: ``transpose(<name>)``, which is how
    ``jax.grad`` itself marks a transposed equation's path, so the
    instruction reads phase ``name``, direction ``bwd``. Outside any
    phase: nothing."""
    if name is None:
        return contextlib.nullcontext()
    return jax.named_scope("transpose(%s)" % name)


# ------------------------------------------------------------ the HLO side
# a path is components joined by "/"; a component is a name or a wrapper
# "name(path)". A function's name in "jit(...)"/"pjit(...)" is no scope
# (jnp.clip traces as "jit(clip)"); every other wrapper is a transform
# around scopes (jvp, transpose, vmap, checkpoint, shard_map, ...).
_TOKEN = re.compile(r"[()/]|[^()/]+")
_FUNCTION_WRAPPERS = frozenset({"jit", "pjit"})


def phase_of(op_name: str) -> Tuple[Optional[str], str]:
    """-> (the innermost vocabulary name on the path or None, ``"bwd"``
    where ``transpose(`` is anywhere on it, else ``"fwd"``)."""
    found = None
    wrappers = []                      # open "name(" wrappers, outermost first
    last = None
    for tok in _TOKEN.findall(op_name):
        if tok == "(":
            wrappers.append(last)
        elif tok == ")":
            if wrappers:
                wrappers.pop()
        elif tok != "/":
            if tok in _KNOWN and not (
                    wrappers and wrappers[-1] in _FUNCTION_WRAPPERS):
                found = tok
        last = tok
    return found, "bwd" if "transpose(" in op_name else "fwd"


_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([A-Za-z_][\w.\-]*) = ")
_OP_NAME = re.compile(r'\bop_name="([^"]*)"')


def parse_hlo_phases(text: str) -> dict:
    """A compiled module's text -> ``{"module", "ops": {instruction name
    without "%": {"phase", "direction", "op_name"}}}``, every instruction
    of every computation (names are unique in a module). An instruction
    the compiler made without metadata (copies, bitcasts, the halves of
    an asynchronous pair, collectives it put in) has phase None: nothing
    is guessed from its neighbours."""
    module, ops = None, {}
    for line in text.splitlines():
        if module is None:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        named = _OP_NAME.search(line, m.end())
        op_name = named.group(1) if named else ""
        ph, direction = phase_of(op_name)
        ops[m.group(1)] = {"phase": ph, "direction": direction,
                           "op_name": op_name}
    return {"module": module, "ops": ops}


def op_phases(site: str) -> Optional[dict]:
    """The instruction-to-phase map of the program that jit site ``site``
    of the compile ledger runs (``"serving.ragged_step"``,
    ``"train_step.run_steps_stream"``): :func:`parse_hlo_phases` of its
    compiled text. None for a site that registered no program. The first
    call lowers and compiles (a persistent-cache load: it is the hot
    path's own jit with its own shapes) and parses; the result is kept
    with the registration. Answers with telemetry on or off, and after
    the engine or the ``TrainStep`` is gone: never call it inside a
    step."""
    program = _ledger.program(site)
    if program is None:
        return None
    return program.derived("op_phases",
                           lambda c: parse_hlo_phases(c.as_text()))
