"""Which PHASE of the program a device operation belongs to (PR 38):
``observability/scopes`` (the vocabulary, ``phase``, the parser,
``op_phases``), the way from the compile ledger back to the two hot
programs (``ServingEngine.compiled_step``, ``TrainStep.compiled_dispatch``),
and the readers of ``benchmark/layer_metrics/`` over
``benchmark/lib/phases.py``."""
import collections
import contextlib
import gc
import os
import sys
import types
import weakref
from unittest import mock

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import models
from paddle_tpu import observability as obs
from paddle_tpu.jit import TrainStep
from paddle_tpu.observability import compile_ledger, scopes
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import phases, runner, xplane  # noqa: E402

SERVE, TRAIN = "serving.ragged_step", "train_step.run_steps_stream"
KNOBS = dict(max_slots=4, block_size=16, num_blocks=16, prefill_chunk=16,
             max_seq_len=64)
BUILD = {
    "gpt": lambda: models.GPTForCausalLM(models.gpt_tiny()),
    "llama": lambda: models.LlamaForCausalLM(models.llama_tiny()),
    "ouro": lambda: models.OuroForCausalLM(models.ouro_tiny()),
    "xing4": lambda: models.Xing4ForCausalLM(models.xing4_tiny()),
    "sarvam": lambda: models.SarvamForCausalLM(models.sarvam_tiny()),
}
EVERY_STEP = {"carry", "embed", "attn.proj", "attn.kernel", "attn.kv_write",
              "ffn", "head", "sample"}
MOE = {"moe.route", "moe.dispatch", "moe.experts", "moe.act", "moe.combine"}
MECHANISMS = {"gpt": set(), "llama": set(), "ouro": {"mix"},
              "xing4": MOE | {"mix"}, "sarvam": MOE}
# what does the arithmetic: the instructions a phase table is made of
WORK = ("fusion", "dot", "convolution", "custom-call", "reduce", "scatter",
        "gather", "sort")
NAMES = sorted(BUILD)


def _model(name):
    pt.seed(3)
    m = BUILD[name]()
    m.eval()
    return m


def _instructions(text):
    """Every instruction line of a module's text, parsed."""
    out = []
    for line in text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        if line.startswith("%") and " = " in line:
            out.append(xplane.parse_hlo(line))
    return out


def _multiset(text):
    return collections.Counter(
        (op["opcode"], tuple(op["results"])) for op in _instructions(text))


@pytest.fixture(scope="module", params=NAMES)
def served(request):
    """A tiny engine of each family after one request, its compiled step
    and the phase map the ledger hands out for it."""
    eng = ServingEngine(_model(request.param), **KNOBS)
    rid = eng.submit(list(range(1, 20)), max_new_tokens=3)
    while eng.step():
        pass
    assert len(eng.result(rid)) == 3 and eng.ragged_compiles == 1
    compiled = eng.compiled_step()
    return request.param, eng, compiled, obs.op_phases(SERVE)


def test_every_instruction_that_carries_a_path_has_a_phase(served):
    """The instructions that do the step's arithmetic: each that the
    compiler gave a path (``op_name``) reads a phase of the vocabulary.
    The CPU's compiler also makes instructions without any metadata (its
    reduce-window rewrite of a cumulative sum, layout copies): nothing is
    guessed for them, they are counted and listed."""
    name, _, compiled, found = served
    work = [op for op in _instructions(compiled.as_text())
            if op["opcode"] in WORK]
    entries = [(op["name"], found["ops"][op["name"]]) for op in work]
    assert all(e["phase"] in scopes.PHASES + (None,) for _, e in entries)
    with_path = [(n, e) for n, e in entries if e["op_name"]]
    unphased = [(n, e["op_name"]) for n, e in with_path if not e["phase"]]
    assert len(with_path) - len(unphased) >= 0.95 * len(with_path), unphased
    bare = [n for n, e in entries if not e["op_name"]]
    assert len(work) - len(bare) - len(unphased) >= 0.8 * len(work), \
        (bare, unphased)


def test_phases_are_the_ones_the_models_mechanisms_imply(served):
    name, _, _, found = served
    seen = {e["phase"] for e in found["ops"].values()} - {None}
    assert seen == EVERY_STEP | MECHANISMS[name]
    assert all(e["direction"] == "fwd" for e in found["ops"].values())
    assert found["module"].startswith("jit__ragged_step")


def test_compiled_step_is_kept_and_counts_as_no_compile(served):
    _, eng, compiled, found = served
    assert eng.compiled_step() is compiled
    assert obs.op_phases(SERVE) is found
    assert eng.ragged_compiles == 1 and eng.stats().ragged_compiles == 1


def test_scopes_change_no_instruction(served):
    """With ``scopes.phase`` a null context the compiled step has the same
    (opcode, result shapes) multiset: the instrument is metadata only."""
    name, _, compiled, _ = served
    with mock.patch.object(scopes, "phase",
                           lambda name: contextlib.nullcontext()):
        bare = ServingEngine(_model(name), **KNOBS).compiled_step()
    assert "attn.proj" not in bare.as_text()
    assert _multiset(bare.as_text()) == _multiset(compiled.as_text())


def test_compiled_step_is_one_lowering_of_the_hot_paths_jit():
    obs.registry.reset()
    compile_ledger.reset()
    obs.enable()
    try:
        eng = ServingEngine(_model("gpt"), **KNOBS)
        eng.warmup()
        lowerings = []
        inner = eng._program._lower
        eng._program._lower = lambda: (lowerings.append(1), inner())[1]
        first = eng.compiled_step()
        assert eng.compiled_step() is first and lowerings == [1]
        assert eng.ragged_compiles == 1
        counters = obs.registry.snapshot()["counters"]
        assert counters["serving.ragged_compiles"] == 1
        assert compile_ledger.report()["sites"][SERVE]["compiles"] == 1
        # the hot path goes on with the program it had
        rid = eng.submit([5, 6, 7], max_new_tokens=2)
        while eng.step():
            pass
        assert len(eng.result(rid)) == 2 and eng.ragged_compiles == 1
    finally:
        obs.disable()
        obs.registry.reset()
        compile_ledger.reset()


def test_a_lowering_before_the_first_step_is_the_hot_paths_trace():
    eng = ServingEngine(_model("gpt"), **KNOBS)
    eng.compiled_step()
    eng.warmup()
    assert eng.ragged_compiles == 1


def test_op_phases_answers_after_the_engine_is_gone():
    """The readers ask after ``shutdown()`` and after the engine left its
    scope; telemetry is off. Until it is asked the ledger pins the step's
    body (the adapter without its weights), never the engine with its
    weights and pools; the first lowering lets the body go too."""
    compile_ledger.reset()
    assert obs.op_phases(SERVE) is None and not obs.enabled()
    eng = ServingEngine(_model("gpt"), **KNOBS)
    eng.warmup()
    gone, body = weakref.ref(eng), weakref.ref(eng._body)
    eng.shutdown()
    del eng
    gc.collect()
    assert gone() is None and body() is not None
    found = obs.op_phases(SERVE)            # lowers now
    assert {e["phase"] for e in found["ops"].values()} >= EVERY_STEP
    assert obs.op_phases(SERVE) is found
    gc.collect()
    assert body() is None
    compile_ledger.reset()
    assert obs.op_phases(SERVE) is None


def test_a_second_engines_registration_lets_the_first_go():
    compile_ledger.reset()
    first = ServingEngine(_model("gpt"), **KNOBS)
    kept = compile_ledger.program(SERVE)
    assert kept is first._program
    gone, body = weakref.ref(first), weakref.ref(first._body)
    del first
    gc.collect()
    assert gone() is None and body() is not None
    second = ServingEngine(_model("llama"), **KNOBS)
    assert compile_ledger.program(SERVE) is second._program is not kept
    del kept
    gc.collect()
    assert body() is None
    compile_ledger.reset()


# ---------------------------------------------------------------- training
def _train_step(mesh=None):
    pt.seed(5)
    cfg = models.gpt_tiny(lm_ce_chunks=4)
    m = models.GPTForCausalLM(cfg)
    opt = pt.optimizer.AdamW(parameters=m.parameters(), learning_rate=1e-3,
                             weight_decay=0.01, factored_v=True)
    kw = {} if mesh is None else dict(
        mesh=mesh, batch_specs=[("dp", "sp"), ("dp", "sp")])
    step = TrainStep(m, opt, grad_clip_norm=1.0, **kw)
    ids = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (2, 4, 32), dtype=np.int32)
    return step, ids


TRAIN_PHASES = {"embed", "attn.proj", "attn.kernel", "ffn", "head", "loss",
                "grad_norm", "clip", "optimizer"}


@pytest.fixture(scope="module")
def trained():
    step, ids = _train_step()
    with pytest.raises(RuntimeError, match="has not run"):
        step.compiled_dispatch(2)
    assert np.isfinite(float(step.run_steps_stream(2, ids, ids)))
    compiled = step.compiled_dispatch(2)
    assert step.compiled_dispatch(2) is compiled
    return compiled, obs.op_phases(TRAIN)


def test_train_dispatch_has_its_phases(trained):
    compiled, found = trained
    seen = {e["phase"] for e in found["ops"].values()} - {None}
    assert seen == TRAIN_PHASES
    work = [found["ops"][op["name"]]
            for op in _instructions(compiled.as_text())
            if op["opcode"] in WORK]
    with_path = [e for e in work if e["op_name"]]
    unphased = [e["op_name"] for e in with_path if not e["phase"]]
    assert len(with_path) - len(unphased) >= 0.95 * len(with_path), unphased


def test_a_backward_instruction_counts_in_its_forward_phase(trained):
    _, found = trained
    for e in found["ops"].values():
        assert (e["direction"] == "bwd") == ("transpose(" in e["op_name"])
    bwd = {e["phase"] for e in found["ops"].values()
           if e["direction"] == "bwd"}
    assert {"embed", "attn.proj", "attn.kernel", "ffn", "head", "loss"} <= bwd
    # what follows the backward pass has no backward of its own
    assert not bwd & {"grad_norm", "clip", "optimizer"}


def test_train_dispatch_on_a_mesh_has_the_same_phases():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 host devices")
    from paddle_tpu.distributed.auto_parallel.process_mesh import (
        ProcessMesh, set_mesh)

    mesh = ProcessMesh(np.arange(4).reshape(2, 1, 2),
                       dim_names=["dp", "sp", "mp"])
    try:
        step, ids = _train_step(mesh)
        assert np.isfinite(float(step.run_steps_stream(2, ids, ids)))
        text = step.compiled_dispatch(2).as_text()
    finally:
        set_mesh(None)
    found = obs.op_phases(TRAIN)
    assert "all-reduce" in text
    assert {e["phase"] for e in found["ops"].values()} - {None} \
        == TRAIN_PHASES


# ------------------------------------------------- vocabulary and parser
def test_phase_refuses_a_name_outside_the_vocabulary():
    with pytest.raises(ValueError, match="nonsense"):
        scopes.phase("nonsense")
    with scopes.phase("head"):
        assert scopes.innermost() == "head"
        with scopes.phase("sample"):
            assert scopes.innermost() == "sample"
        assert scopes.innermost() == "head"
    assert scopes.innermost() is None


@pytest.mark.parametrize("op_name, want", [
    ("jit(_ragged_step)/head/dot_general", ("head", "fwd")),
    ("jit(_ragged_step)/attn.proj/attn.kernel/pallas_call",
     ("attn.kernel", "fwd")),
    ("jit(f)/jvp(head)/dot_general", ("head", "fwd")),
    ("jit(f)/transpose(jvp(attn.proj))/dot_general", ("attn.proj", "bwd")),
    ("jit(multi)/while/body/closed_call/transpose(ffn)/transpose(jvp())/mul",
     ("ffn", "bwd")),
    ("jit(g)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/ffn/tanh", ("ffn", "bwd")),
    ("jit(f)/attn.proj/shard_map(attn.kernel/pallas_call)",
     ("attn.kernel", "fwd")),
    # a function's name is no scope: jnp.clip traces as jit(clip)
    ("jit(f)/optimizer/jit(clip)/max", ("optimizer", "fwd")),
    ("jit(f)/jit(clip)/max", (None, "fwd")),
    ("jit(f)/clip/jit(clip)/max", ("clip", "fwd")),
    ("w['layers'][1]['down']", (None, "fwd")),
    ("", (None, "fwd")),
])
def test_phase_of_a_path(op_name, want):
    assert scopes.phase_of(op_name) == want


def test_parse_hlo_phases_reads_every_computation():
    text = """HloModule jit_step, is_scheduled=true, frontend_attributes={a = b}

%fused_computation.7 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.3 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/ffn/mul"}
}

ENTRY %main.1 (x.1: f32[4]) -> f32[4] {
  %x.1 = f32[4]{0} parameter(0), metadata={op_name="x"}
  %copy-start.2 = (f32[4]{0}, f32[4]{0}, u32[]) copy-start(%x.1)
  %copy-done.2 = f32[4]{0} copy-done(%copy-start.2)
  ROOT %fusion.7 = f32[4]{0} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.7, metadata={op_name="jit(step)/transpose(jvp(ffn))/mul" stack_frame_id=2}, backend_config={"x":1}
}
"""
    found = scopes.parse_hlo_phases(text)
    assert found["module"] == "jit_step"
    assert set(found["ops"]) == {"p", "mul.3", "x.1", "copy-start.2",
                                 "copy-done.2", "fusion.7"}
    assert found["ops"]["fusion.7"] == {
        "phase": "ffn", "direction": "bwd",
        "op_name": "jit(step)/transpose(jvp(ffn))/mul"}
    assert found["ops"]["mul.3"]["phase"] == "ffn"
    # made by the compiler, without metadata: nothing is guessed
    assert found["ops"]["copy-done.2"] == {
        "phase": None, "direction": "fwd", "op_name": ""}
    assert found["ops"]["x.1"]["phase"] is None


# ------------------------------------------------- the benchmark's readers
SERVE_READERS = {
    "device.phase_coverage.serve": 90.0,
    "serving_engine.head_share.serve": 20.0,
    "serving_engine.proj_ffn_share.serve": 30.0,
    "serving_engine.sample_share.serve": 5.0,
    "serving_engine.moe_glue_share.serve": 10.0,
}
TRAIN_READERS = {
    "device.phase_coverage.train": 95.0,
    "train_step.optimizer_share.train": 25.0,
    "train_step.loss_share.train": 15.0,
}


def _reader(name):
    return runner.load_module("layer_metrics", name).read


def _op(name, seconds):
    return dict(xplane.parse_hlo("%%%s = f32[8]{0} fusion(%%p)" % name),
                seconds=seconds, count=1)


def _cell(mapped, logs=None):
    asked = []

    def op_phases(site):
        asked.append(site)
        return {"module": "jit_x", "ops": {
            n: {"phase": p, "direction": d, "op_name": p or ""}
            for n, (p, d) in mapped.items()}}

    return types.SimpleNamespace(
        pt=types.SimpleNamespace(
            observability=types.SimpleNamespace(op_phases=op_phases)),
        log=(logs.append if logs is not None else lambda m: None),
        asked=asked)


def _serve_record():
    """100 ms of self time: head 20, embed + attn.proj + ffn 30, sample +
    carry 5, the moe glue 10, the kernels 25, no metadata 6, an operation
    of another program 4."""
    secs = {"f.head": 20, "f.embed": 5, "f.proj": 15, "f.ffn": 10,
            "f.sample": 4, "f.carry": 1, "f.route": 2, "f.disp": 4,
            "f.act": 1, "f.comb": 3, "f.kern": 15, "f.gmm": 10,
            "copy.1": 6, "threefry.9": 4}
    mapped = {"f.head": "head", "f.embed": "embed", "f.proj": "attn.proj",
              "f.ffn": "ffn", "f.sample": "sample", "f.carry": "carry",
              "f.route": "moe.route", "f.disp": "moe.dispatch",
              "f.act": "moe.act", "f.comb": "moe.combine",
              "f.kern": "attn.kernel", "f.gmm": "moe.experts",
              "copy.1": None}
    rec = {"trace": {"chips": {"/device:TPU:0": {
        "ops": [_op(n, s * 1e-3) for n, s in secs.items()]}}}}
    return rec, {n: (p, "fwd") for n, p in mapped.items()}


def _train_record():
    secs = {"f.fwd": 30, "f.bwd": 25, "f.loss": 6, "f.lossb": 8, "f.lnf": 1,
            "f.gn": 12, "f.clip": 3, "f.adam": 10, "all-reduce.4": 5}
    mapped = {"f.fwd": ("ffn", "fwd"), "f.bwd": ("ffn", "bwd"),
              "f.loss": ("loss", "fwd"), "f.lossb": ("loss", "bwd"),
              "f.lnf": ("head", "fwd"), "f.gn": ("grad_norm", "fwd"),
              "f.clip": ("clip", "fwd"), "f.adam": ("optimizer", "fwd"),
              "all-reduce.4": (None, "fwd")}
    rec = {"trace": {"chips": {"/device:TPU:0": {
        "ops": [_op(n, s * 1e-3) for n, s in secs.items()]},
        "/device:TPU:1": {"ops": [_op("f.fwd", 1.0)]}}}}
    return rec, mapped


@pytest.mark.parametrize("name", sorted(SERVE_READERS))
def test_serve_reader_on_a_synthetic_record(name):
    rec, mapped = _serve_record()
    logs = []
    cell = _cell(mapped, logs)
    assert _reader(name)(rec, cell) == pytest.approx(SERVE_READERS[name])
    # asked of the program once, of the serving step's site, however many
    # readers follow; the table is logged once
    assert _reader("device.phase_coverage.serve")(rec, cell) \
        == pytest.approx(90.0)
    assert cell.asked == [SERVE]
    assert [m.split(":")[0] for m in logs] == ["device time by phase",
                                               "device ops by phase"]


@pytest.mark.parametrize("name", sorted(TRAIN_READERS))
def test_train_reader_on_a_synthetic_record(name):
    rec, mapped = _train_record()
    cell = _cell(mapped)
    assert _reader(name)(rec, cell) == pytest.approx(TRAIN_READERS[name])
    assert cell.asked == [TRAIN]


def test_phase_table_adds_up_with_the_rest_to_the_busy_time():
    rec, mapped = _serve_record()
    t = phases.table(rec, _cell(mapped), "serve")
    assert t["busy_s"] == pytest.approx(0.1)
    assert sum(sum(by.values()) for by in t["seconds"].values()) \
        == pytest.approx(t["busy_s"])
    none = sum(t["seconds"][None].values())
    assert 100.0 * none / t["busy_s"] + phases.share(
        rec, _cell(mapped), "serve") == pytest.approx(100.0)
    assert [(n, why) for _, n, _, why in t["none"]] == [
        ("copy.1", "no metadata"), ("threefry.9", "not of the program")]
    rec, mapped = _train_record()
    t = phases.table(rec, _cell(mapped), "train")
    assert t["seconds"]["ffn"] == {"fwd": pytest.approx(0.030),
                                   "bwd": pytest.approx(0.025)}


@pytest.mark.parametrize("name", sorted(SERVE_READERS) + sorted(TRAIN_READERS))
def test_phase_reader_finds_nothing_in_the_parents_record(name):
    """A record without a trace, a program without ``op_phases`` (the
    parent's), a site that registered nothing: no reader raises, each
    leaves its metric out."""
    rec, mapped = _serve_record()
    parent = types.SimpleNamespace(
        pt=types.SimpleNamespace(observability=types.SimpleNamespace()),
        log=lambda m: None)
    assert _reader(name)(dict(rec), parent) is None
    assert _reader(name)({"trace": None}, _cell(mapped)) is None
    assert _reader(name)({}, _cell(mapped)) is None
    silent = _cell(mapped)
    silent.pt.observability.op_phases = lambda site: None
    assert _reader(name)(dict(rec), silent) is None


def _sp(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


def test_starved_round_share_reads_the_rounds_own_clock():
    read = _reader("serving_engine.starved_round_share")
    spans, logs = [], []
    for k in range(10):
        t0 = k * 2e6
        # three rounds found their step done: the read of ready tokens
        wait = 400.0 if k in (0, 1, 5) else 9e3
        spans += [_sp("serving.step", t0, 15e3 + k, tokens=7),
                  _sp("serving.schedule", t0 + 1, 1e3),
                  _sp("serving.ragged_step", t0 + 2e3, 2e3),
                  _sp("serving.device_wait", t0 + 5e3, wait),
                  _sp("serving.emit", t0 + 5e3 + wait, 500.0),
                  _sp("serving.lock_wait", t0 + 3e3, 9e3, site="submit")]
    cell = types.SimpleNamespace(log=logs.append, seconds=20.0,
                                 traffic={"traced_s": 2.0})
    assert read({"spans": spans}, cell) == pytest.approx(30.0)
    waits = next(m for m in logs if m.startswith("device_wait_ms"))
    # round 5 starts at 10 s, where the traced run's profiler does
    assert "30.000 % of 10 rounds, 1 of them in" in waits
    longest = next(m for m in logs if m.startswith("longest rounds"))
    assert '"ms": 15.009' in longest and "lock_wait" not in longest
    assert '["device_wait", 9.0]' in longest
    # the five longest are rounds 9 to 5; 5 and 6 start in 9.7-12.7 s
    assert longest.count('"profiler": true') == 2
    # the parent's record, or one without spans: nothing to read
    old = {"spans": [_sp("serving.step", 0, 100e3)]}
    assert read(old, cell) is None and read({}, cell) is None
