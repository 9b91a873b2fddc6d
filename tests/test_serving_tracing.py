"""The serving step measured from inside (PR 27): program spans on the
profiler's clock, the step's six phases, the wait for the engine's lock
per request, slot and pool occupancy on the step's span, and the five
per-layer readers of ``benchmark/layer_metrics/`` that read them."""
import glob
import importlib
import math
import os
import sys
import threading
import time

import pytest

import jax

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import metrics_schema, tracing
from paddle_tpu.serving import ServingEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from lib import runner  # noqa: E402

PHASES = ("serving.schedule", "serving.build_batch", "serving.transfer",
          "serving.ragged_step", "serving.device_wait", "serving.emit")
NEW_METRICS = ("serving_engine.host_ms_per_step",
               "serving_engine.lock_wait_ms_p50",
               "serving_engine.queue_wait_ms_p50",
               "serving_engine.slot_occupancy",
               "serving_engine.pool_pages_in_use")
REMOVED = ("serving.step_time", "serving.token_latency",
           "serving.queue_depth", "serving.slot_occupancy",
           "serving.ragged_fill", "serving.layer_passes",
           "serving.latent_pages_read", "serving.moe_pairs")


def _reader(name):
    return runner.load_module("layer_metrics", name).read


@pytest.fixture
def telemetry():
    obs.registry.reset()
    obs.tracing.reset()
    obs.compile_ledger.reset()
    obs.enable()
    yield
    obs.disable()
    obs.registry.reset()
    obs.tracing.reset()


@pytest.fixture(scope="module")
def model():
    pt.seed(11)
    m = pt.models.GPTForCausalLM(
        pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0))
    m.eval()
    return m


def _engine(model, **kw):
    kw = dict(dict(max_slots=4, block_size=4, num_blocks=64,
                   prefill_chunk=8), **kw)
    return ServingEngine(model, **kw)


def _spans(name=None):
    return [s for s in tracing.finished_spans()
            if name is None or s.name == name]


# ------------------------------------------------- (a) one clock
@pytest.fixture(scope="module")
def host_plane(tmp_path_factory):
    """One short ``jax.profiler`` trace on the CPU backend: a span opened
    with telemetry off, then two nested ones with it on. -> the events of
    the ``/host:CPU`` plane by name, and the two objects ``span()`` gave
    while telemetry was off."""
    d = str(tmp_path_factory.mktemp("trace"))
    obs.disable()
    obs.tracing.reset()
    jax.profiler.start_trace(d)
    try:
        off = [tracing.span("serving.emit", args={"tokens": 1})
               for _ in range(2)]
        with off[0]:
            time.sleep(0.001)
        obs.enable()
        with tracing.span("serving.step", args={"rows": 3, "impl": "xla",
                                                "skipped": [1, 2]}):
            with tracing.span("serving.schedule"):
                time.sleep(0.002)
    finally:
        obs.disable()
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    assert found
    events = {}
    for plane in jax.profiler.ProfileData.from_file(found[0]).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                events.setdefault(ev.name, []).append(
                    (line.name, ev.start_ns, ev.duration_ns,
                     dict(ev.stats)))
    ring = {s.name: s for s in _spans()}
    obs.tracing.reset()
    return events, off, ring


def test_span_is_an_event_of_the_host_plane_with_its_args(host_plane):
    events, _, ring = host_plane
    assert len(events["serving.step"]) == 1
    _, _, dur_ns, stats = events["serving.step"][0]
    # scalar args become the event's stats; a list does not travel
    assert stats == {"rows": 3, "impl": "xla"}
    assert ring["serving.step"].args["skipped"] == [1, 2]
    assert dur_ns >= 2e6


def test_child_span_nests_inside_its_parent_on_the_same_line(host_plane):
    events, _, ring = host_plane
    (line_p, t_p, d_p, _), = events["serving.step"]
    (line_c, t_c, d_c, _), = events["serving.schedule"]
    assert line_p == line_c
    assert t_p <= t_c and t_c + d_c <= t_p + d_p
    # the ring's clock and the profiler's agree on the duration
    assert ring["serving.schedule"].dur * 1e3 == pytest.approx(d_c, rel=0.2)


def test_telemetry_off_is_the_shared_noop_and_makes_no_annotation(
        host_plane):
    events, off, ring = host_plane
    assert off[0] is off[1] is tracing._NOOP_SPAN
    assert "serving.emit" not in events and "serving.emit" not in ring


def test_record_complete_spans_are_not_bridged(telemetry):
    sp = tracing.record_complete("rt.request", ts_s=time.time(), dur_s=0.1)
    assert sp._annotation is None and sp in _spans("rt.request")


# ------------------------------------------------- (b) the step's phases
@pytest.fixture
def stepped(model, telemetry):
    """Five requests on four slots, stepped by hand to the end: after
    every ``step()`` what ``stats()`` reports."""
    eng = _engine(model)
    for n in (3, 9, 14, 5, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    seen = []
    while eng.step():
        st = eng.stats()
        seen.append({"running": st.running, "prefilling": st.prefilling,
                     "waiting": st.queue_depth,
                     "pages_in_use": st.total_blocks - st.free_blocks})
        assert len(seen) < 200
    eng.shutdown()
    return eng, seen


def _children(step, spans):
    return [s for s in spans if s.name in PHASES
            and step.ts <= s.ts < step.ts + step.dur]


def test_every_working_step_has_its_six_phases_in_order(stepped):
    spans = sorted(_spans(), key=lambda s: s.ts)
    steps = [s for s in spans if s.name == "serving.step"]
    kids = [_children(st, spans) for st in steps]
    names = [tuple(k.name for k in ks) for ks in kids]
    # the first round only launches, the last two only collect and find
    # nothing; every round between launches a step with one in flight
    # and then collects that one: the six phases
    assert names[0] == PHASES[:4]
    assert names[-2:] == [PHASES[:1] + PHASES[4:], PHASES[:1]]
    assert len(names) >= 8 and set(names[1:-2]) == {PHASES}
    flying = [k.args["in_flight"] for ks in kids for k in ks
              if k.name == "serving.ragged_step"]
    assert flying == [0] + [1] * (len(steps) - 3)
    c = obs.registry.counter
    assert c("serving.lookahead_steps").value == len(flying) - 1 \
        <= c("serving.ragged_steps").value == len(flying)
    for st, ks in zip(steps, kids):
        assert all(k.parent_id == st.span_id for k in ks)
        for a, b in zip(ks, ks[1:]):
            assert a.ts + a.dur <= b.ts + 50          # us: clock grain
        assert ks[-1].ts + ks[-1].dur <= st.ts + st.dur + 50
        assert sum(k.dur for k in ks) <= st.dur


def test_phase_spans_carry_their_args(stepped):
    eng, _ = stepped
    first = min(_spans("serving.schedule"), key=lambda s: s.ts)
    assert first.args == {"admitted": 4, "preempted": 0}
    rs = _spans("serving.ragged_step")
    assert all(set(s.args) == {"rows", "tokens", "impl", "kv_write",
                               "live_pages", "sampled_rows", "passes",
                               "cache_layers", "weight_bytes",
                               "kv_layout", "in_flight"}
               for s in rs)
    # a model that runs its stack once: one pass, a cache layer a layer
    layers = eng._ad.num_layers
    assert {(s.args["passes"], s.args["cache_layers"]) for s in rs} \
        == {(1, layers)}
    assert {s.args["impl"] for s in rs} == {eng.attention_impl}
    # per-head K and V pools: nothing of a latent cache, experts or
    # several streams (tests/test_xing4.py has the model that says those)
    assert {s.args["kv_layout"] for s in rs} == {"kv"}
    # on the CPU the scatter writes KV and the step is donated nothing:
    # the span and the compile ledger's entry of the step say so
    assert {s.args["kv_write"] for s in rs} == {eng.kv_write_impl} == {"xla"}
    entry = obs.compile_ledger.report()["sites"]["serving.ragged_step"]
    assert (entry["compiles"], entry["donated_args"]) == (1, 0)
    # a row reads ceil(context / block_size) pages: at least one each,
    # and never more than the pool held at that step's end
    assert all(s.args["rows"] <= s.args["live_pages"] <= 64 for s in rs)
    # every request here is greedy: no row asks the sampler for a draw
    assert {s.args["sampled_rows"] for s in rs} == {0}
    assert obs.registry.counter("serving.sampled_steps").value == 0
    # every generated token was emitted inside a serving.emit span
    assert sum(s.args["tokens"] for s in _spans("serving.emit")) == 5 * 6


def test_step_span_counts_the_kernel_s_visits_where_it_runs(
        model, telemetry, monkeypatch):
    """Where the engine resolves to the per-head kernel (on a TPU; here
    its choice is patched, and the XLA composition still computes), each
    step span carries the kernel's work in one cache layer by the
    kernel's own rule: the live (row, page) pairs, the pages the step
    reads, and its visits, a page each at this head dim of 32."""
    engine = importlib.import_module("paddle_tpu.serving.engine")
    monkeypatch.setattr(engine, "ragged_impl", lambda *a: "pallas")
    eng = _engine(model)
    for n in (3, 9, 14, 5, 7):
        eng.submit(list(range(1, n + 1)), max_new_tokens=6)
    while eng.step():
        pass
    eng.shutdown()
    rs = _spans("serving.ragged_step")
    assert rs and {s.args["impl"] for s in rs} == {"pallas"}
    assert all(s.args["ragged_page_visits"] == s.args["ragged_visits"]
               == s.args["live_pages"] for s in rs)


def test_step_span_ends_with_what_stats_then_reports(stepped):
    eng, seen = stepped
    steps = sorted((s for s in _spans("serving.step")
                    if "slots_max" in s.args), key=lambda s: s.ts)
    assert len(steps) == len(seen) + 1        # the last one found nothing
    for sp, st in zip(steps, seen):
        assert {k: sp.args[k] for k in st} == st
        assert sp.args["slots_max"] == 4 and sp.args["pages_max"] == 64
    assert any(s["waiting"] for s in seen)    # five requests, four slots
    assert steps[-1].args["tokens"] == 0
    assert steps[-1].args["pages_in_use"] == 0


def test_ragged_step_tokens_match_the_counters(stepped):
    packed = sum(s.args["tokens"] for s in _spans("serving.ragged_step"))
    c = obs.registry.counter
    assert packed == c("serving.decode_tokens").value \
        + c("serving.prefill_tokens").value
    assert len(_spans("serving.ragged_step")) \
        == c("serving.ragged_steps").value


def test_disabled_path_records_nothing_and_keeps_no_emit_clock(model):
    assert not obs.enabled()
    obs.tracing.reset()
    eng = _engine(model)
    rid = eng.submit([1, 2, 3], max_new_tokens=3)
    while eng.step():
        pass
    assert len(eng.result(rid)) == 3
    eng.shutdown()
    assert _spans() == [] and not hasattr(eng, "_last_emit")
    # with telemetry off the helper hands out the lock itself
    assert eng._lock("submit", 1) is eng._lock


# ------------------------------------------------- (c) the lock
def test_submit_behind_a_held_lock_records_the_wait(model, telemetry):
    eng = _engine(model)
    held = threading.Event()

    def hold():
        with eng._lock:
            held.set()
            time.sleep(0.05)

    th = threading.Thread(target=hold)
    th.start()
    held.wait()
    rid = eng.submit([1, 2, 3, 4, 5], max_new_tokens=2)
    th.join()
    while eng.step():
        pass
    assert len(eng.result(rid)) == 2
    eng.shutdown()
    waits = [s for s in _spans("serving.lock_wait")
             if s.args.get("rid") == rid]
    by_site = {s.args["site"]: s for s in waits}
    assert set(by_site) == {"submit", "stream"}
    assert by_site["submit"].dur >= 45e3
    rec, = eng.request_log.tail()
    assert rec["lock_wait_s"] == pytest.approx(by_site["submit"].dur / 1e6)
    segs = rec["queue_s"] + rec["prefill_s"] + rec["decode_s"] \
        + rec["preempt_s"]
    assert segs == pytest.approx(rec["e2e_s"], abs=1e-9)
    req, = _spans("rt.request")
    assert req.args["lock_wait_s"] == pytest.approx(rec["lock_wait_s"],
                                                    abs=1e-6)
    win = eng.ops_snapshot()["replicas"]["engine"]["windows"]
    assert win["rt.lock_wait"]["count"] == 1


def test_lock_is_not_kept_by_asking_again_at_once(model):
    """A thread that lets the lock go and asks again at once (the step
    loop) does not overtake one that waits at the gate: the waiter is in
    before the next round, however the machine is loaded (no sleep and
    no round number is assumed: the hot thread itself says in which
    round it first saw the waiter at the gate)."""
    lock = _engine(model)._lock
    rounds, seen, got = [0], [], []
    started, stop = threading.Event(), threading.Event()

    def hot():
        while not stop.is_set():
            with lock:
                rounds[0] += 1
                started.set()
                time.sleep(0.002)        # the step: lock held, GIL free
                if lock._gate.locked():  # the waiter stands at the gate
                    seen.append(rounds[0])

    def waiter():
        started.wait()
        with lock:
            got.append(rounds[0])
            with lock:                   # re-entrant for its holder
                pass
        with lock:                       # and free again after both exits
            pass

    ths = [threading.Thread(target=f) for f in (hot, waiter)]
    for th in ths:
        th.start()
    ths[1].join(timeout=5.0)
    stop.set()
    ths[0].join(timeout=5.0)
    assert got and not ths[1].is_alive()
    # seen at the gate in round n: in before round n + 1 began
    assert not seen or got[0] <= seen[0]


@pytest.mark.parametrize("site", ["events", "cancel", "stats", "step"])
def test_every_outside_caller_of_the_lock_is_a_site(model, telemetry, site):
    eng = _engine(model)
    rid = eng.submit([1, 2, 3], max_new_tokens=2)
    while not eng._requests[rid].generated:      # until its first token
        assert eng.step()
    eng.stats()
    it = eng.events(rid)
    next(it)
    eng.cancel(rid)
    eng.shutdown()
    got = [s for s in _spans("serving.lock_wait")
           if s.args["site"] == site]
    assert got
    if site in ("events", "cancel"):
        assert all(s.args["rid"] == rid for s in got)
    else:
        assert all("rid" not in s.args for s in got)


# ------------------------------------------------- (d) the five readers
def _sp(name, ts, dur, **args):
    return {"name": name, "ts": ts, "dur": dur, "args": args}


HAND_MADE = {"spans": [
    # two working steps of 100 and 60 ms, the device waited for 70 and 40
    _sp("serving.step", 0, 100e3, running=3, prefilling=1, waiting=0,
        slots_max=4, pages_in_use=30, pages_max=60, tokens=9),
    _sp("serving.device_wait", 20e3, 70e3),
    _sp("serving.step", 200e3, 60e3, running=1, prefilling=0, waiting=2,
        slots_max=4, pages_in_use=15, pages_max=60, tokens=1),
    _sp("serving.device_wait", 210e3, 40e3),
    # a step that found nothing to run: no wait inside it
    _sp("serving.step", 300e3, 1e3, running=0, prefilling=0, waiting=0,
        slots_max=4, pages_in_use=0, pages_max=60, tokens=0),
    # request 7 waited 10 + 5 ms, request 8 40 + 2 ms; request 9's
    # submit() lies before the window, so only its events() is here
    _sp("serving.lock_wait", 1e3, 10e3, site="submit", rid=7),
    _sp("serving.lock_wait", 12e3, 5e3, site="events", rid=7),
    _sp("serving.lock_wait", 30e3, 40e3, site="submit", rid=8),
    _sp("serving.lock_wait", 71e3, 2e3, site="events", rid=8),
    _sp("serving.lock_wait", 2e3, 90e3, site="events", rid=9),
    _sp("serving.lock_wait", 0, 3e3, site="step"),
    _sp("serving.lock_wait", 5e3, 500e3, site="cancel", rid=7),
    _sp("rt.request", 1e3, 9e5, queue_s=0.004, lock_wait_s=0.01),
    _sp("rt.request", 2e3, 9e5, queue_s=0.012, lock_wait_s=0.04),
    _sp("rt.request", 3e3, 9e5, queue_s=0.2, lock_wait_s=0.0),
]}


@pytest.mark.parametrize("name,want", [
    ("serving_engine.host_ms_per_step", 25.0),       # median of 30, 20
    ("serving_engine.lock_wait_ms_p50", 28.5),       # median of 15, 42
    ("serving_engine.queue_wait_ms_p50", 12.0),
    ("serving_engine.slot_occupancy", 100 * (1.0 + 0.25 + 0.0) / 3),
    ("serving_engine.pool_pages_in_use", 100 * (0.5 + 0.25 + 0.0) / 3),
])
def test_reader_on_a_hand_made_record(name, want):
    assert _reader(name)(HAND_MADE, None) == pytest.approx(want)


def test_lookahead_share_counts_the_steps_launched_with_one_in_flight():
    read = _reader("serving_engine.lookahead_share")
    rec = {"spans": [_sp("serving.ragged_step", k * 10e3, 2e3, rows=2,
                         in_flight=f)
                     for k, f in enumerate((0, 1, 1, 1, 0, 1, 1, 1))]
           + [_sp("serving.step", 0, 9e3, tokens=3)]}
    assert read(rec, None) == pytest.approx(75.0)


@pytest.mark.parametrize("name", NEW_METRICS
                         + ("serving_engine.lookahead_share",))
def test_reader_finds_nothing_in_the_parents_record(name):
    """What the program before this PR gives: one ``serving.step`` span
    without args, no phases, no lock spans. No reader raises, and each
    leaves its metric out."""
    old = {"spans": [_sp("serving.step", 0, 100e3),
                     _sp("serving.ragged_step", 1e3, 6e3, rows=2, tokens=5,
                         impl="xla")]}
    assert _reader(name)(old, None) is None
    assert _reader(name)({}, None) is None


TINY = dict(
    model_class="GPTForCausalLM", config_class="GPTConfig",
    reference="gpt", dtype="bfloat16", vocab_size=1024, hidden_size=128,
    num_layers=2, num_heads=4, intermediate_size=512,
    max_position_embeddings=256,
    model_kwargs={"dropout": 0.0, "attention_dropout": 0.0,
                  "recompute": False, "lm_ce_chunks": 8},
    engine={"max_slots": 4, "block_size": 16, "prefill_chunk": 32,
            "num_blocks": 64})
TINY_CHAT = {
    "kind": "closed_loop", "clients": 4,
    "pairs": [[8, 12], [14, 30], [19, 9], [24, 16], [29, 22], [34, 5],
              [40, 18], [47, 48], [55, 11], [66, 26], [82, 14], [120, 20]],
    "ramp_s": 0.5, "traced_s": 0.3, "temperature": 0.0,
    "reference": {"requests": 2, "pad_to": 128, "margin": 0.15}}


@pytest.fixture(scope="module")
def closed_loop_line():
    """``kinds/closed_loop.run`` at ``gpt_tiny``, traced, and the result
    line the runner makes of it with this tree's manifest."""
    from lib.compiles import CompileCounter

    cell = runner.Cell(pt, TINY, TINY_CHAT, 2 ** 31 + 52, 1.5, 1, 1,
                       jax.devices()[:1], None, CompileCounter(),
                       time.perf_counter())
    rec = runner.run_cell(cell)
    assert rec["attempted"] > 0 and not obs.enabled()
    # the CPU has no device plane: that alone makes the line incorrect
    line = runner.result_line(cell, rec, runner.load_manifest(),
                              "serve_chat_1p3b",
                              {"platform": "cpu", "kind": "cpu", "count": 1})
    assert rec["problems"] == ["no device operation in the trace"]
    return rec, line


@pytest.mark.parametrize("name", NEW_METRICS)
def test_reader_on_the_closed_loop_record_at_gpt_tiny(closed_loop_line,
                                                      name):
    rec, line = closed_loop_line
    v = line["metrics"][name]["value"]
    assert math.isfinite(v) and v >= 0
    if line["metrics"][name]["unit"] == "%":
        assert 0 < v <= 100
    assert _reader(name)(rec, None) == v


def test_inside_and_outside_agree_at_gpt_tiny(closed_loop_line):
    rec, line = closed_loop_line
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # the seven the cell had, the five of PR 27, the look-ahead's and
    # PR 38's reading of the rounds' own clock (its phase readers find no
    # device plane on the CPU and leave their metrics out; the XLA
    # composition serves here, so no step counts the per-head kernel's
    # visits and its pages a visit are left out too)
    assert len(m) == 12 and set(NEW_METRICS) <= set(m)
    assert "kernels.ragged_pages_per_visit.serve" not in m
    assert 0 <= m["serving_engine.starved_round_share"] <= 100
    # a closed loop keeps the engine busy: nearly every step is launched
    # with the one before still in flight
    assert m["serving_engine.lookahead_share"] > 90
    assert m["serving_engine.host_ms_per_step"] \
        <= m["serving_engine.step_ms_p50"]
    # a client's wait for the lock is inside its submit() and more
    assert m["serving_engine.lock_wait_ms_p50"] >= 0
    steps = [s for s in rec["spans"] if s["name"] == "serving.step"]
    assert steps and all(s["args"]["slots_max"] == 4 for s in steps)


# ------------------------------------------------- (e) the schema
@pytest.mark.parametrize("name", REMOVED)
def test_removed_instrument_is_gone_from_schema_and_tree(name):
    assert name not in metrics_schema.METRICS
    needle = '"%s"' % name
    for path in glob.glob(os.path.join(ROOT, "paddle_tpu", "**", "*.py"),
                          recursive=True) + \
            glob.glob(os.path.join(ROOT, "tools", "**", "*.py"),
                      recursive=True) + [os.path.join(ROOT, "bench.py")]:
        with open(path, encoding="utf-8") as f:
            assert needle not in f.read(), path


@pytest.mark.parametrize("name", PHASES + ("serving.lock_wait",
                                           "serving.step"))
def test_span_name_is_declared(name):
    assert metrics_schema.span_spec(name)


def test_lock_wait_window_is_declared():
    spec = metrics_schema.spec("rt.lock_wait")
    assert spec.kind == "histogram" and spec.unit == "s"
