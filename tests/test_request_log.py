"""Request-scoped serving observability: lifecycle timelines, the
access log, ops snapshots, ptop rendering, and the debug-bundle /
diagnose sections.

Unit tests drive RequestTimeline with a ManualClock (exact segment
math, zero sleeps); integration tests run real ServingEngine traffic
with telemetry on and audit the records end-to-end.
"""
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.observability import flight_recorder
from paddle_tpu.observability.request_log import (OUTCOMES, RequestLog,
                                                  attribution_of,
                                                  tail_all)
from paddle_tpu.observability.windows import ManualClock

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))
import ptop  # noqa: E402


@pytest.fixture
def telemetry():
    obs.registry.reset()
    obs.tracing.reset()
    flight_recorder.reset()
    obs.enable()
    yield obs.registry
    obs.disable()
    obs.registry.reset()
    obs.tracing.reset()
    flight_recorder.reset()


@pytest.fixture(scope="module")
def model():
    pt.seed(11)
    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    return m


def _drain(eng, cap=500):
    n = 0
    while eng.step() and n < cap:
        n += 1
    assert n < cap, "engine failed to drain"


# --------------------------------------------------- timeline unit math
class TestTimelineUnit:
    def _log(self, clk, **kw):
        return RequestLog("test", path=kw.pop("path", None),
                          clock=clk, wall=clk, **kw)

    def test_plain_lifecycle_segments(self, telemetry):
        clk = ManualClock(100.0)
        log = self._log(clk)
        tl = log.open(rid=1, prompt_tokens=8)
        clk.advance(2.0)                # queued 2 s
        tl.mark_admitted()
        clk.advance(3.0)                # prefill 3 s
        tl.mark_running()
        assert tl.ttft == pytest.approx(5.0)
        clk.advance(1.0)
        tl.mark_emit()
        clk.advance(1.0)
        tl.mark_emit()
        rec = tl.close("eos")
        assert rec["outcome"] == "finished"
        assert rec["queue_s"] == pytest.approx(2.0)
        assert rec["prefill_s"] == pytest.approx(3.0)
        assert rec["decode_s"] == pytest.approx(2.0)
        assert rec["preempt_s"] == 0.0
        assert rec["e2e_s"] == pytest.approx(7.0)
        assert rec["tokens"] == 2
        assert rec["prompt_tokens"] == 8
        # the acceptance invariant: segments sum to e2e EXACTLY
        segs = (rec["queue_s"] + rec["prefill_s"] + rec["decode_s"]
                + rec["preempt_s"])
        assert segs == rec["e2e_s"]

    def test_preemption_attribution(self, telemetry):
        """preempt bucket = pure re-admission stall; the re-prefill
        after it counts as prefill; TTFT stamps only once."""
        clk = ManualClock(0.0)
        log = self._log(clk)
        tl = log.open(rid=2)
        tl.mark_admitted()              # no queue time
        clk.advance(1.0)
        tl.mark_running()               # ttft = 1.0
        clk.advance(1.0)                # decoded 1 s
        tl.mark_preempted()
        clk.advance(4.0)                # stalled 4 s
        tl.mark_admitted()              # re-admitted
        clk.advance(2.0)                # re-prefill 2 s
        tl.mark_running()               # must NOT restamp ttft
        clk.advance(1.0)                # decode 1 s more
        rec = tl.close("length")
        assert rec["ttft_s"] == pytest.approx(1.0)
        assert rec["preemptions"] == 1
        assert rec["queue_s"] == 0.0
        assert rec["prefill_s"] == pytest.approx(3.0)   # 1 + 2
        assert rec["decode_s"] == pytest.approx(2.0)
        assert rec["preempt_s"] == pytest.approx(4.0)
        assert rec["e2e_s"] == pytest.approx(9.0)

    def test_outcome_mapping_and_idempotent_close(self, telemetry):
        clk = ManualClock(0.0)
        log = self._log(clk)
        for reason, want in (("eos", "finished"), ("length", "finished"),
                             ("overloaded", "shed"),
                             ("deadline", "cancelled"),
                             ("replica_dead", "cancelled")):
            tl = log.open(rid=reason)
            rec = tl.close(reason)
            assert rec["outcome"] == want
            assert rec["outcome"] in OUTCOMES
            assert tl.close(reason) is None     # double close: no-op
        assert log.closed == 5

    def test_shed_is_one_arrival_one_shed(self, telemetry):
        clk = ManualClock(0.0)
        log = self._log(clk)
        log.open(rid=1)
        rec = log.shed(prompt_tokens=4)
        assert rec["outcome"] == "shed"
        assert log.windows.counter("rt.submitted").total() == 2.0
        assert log.windows.counter("rt.shed").total() == 1.0

    def test_jsonl_access_log(self, telemetry, tmp_path):
        clk = ManualClock(0.0)
        path = str(tmp_path / "access.jsonl")
        log = self._log(clk, path=path)
        for i in range(3):
            tl = log.open(rid=i)
            clk.advance(0.5)
            tl.close("eos")
        log.flush_close()
        lines = [json.loads(ln) for ln in
                 open(path).read().splitlines() if ln]
        assert [r["rid"] for r in lines] == [0, 1, 2]
        assert all(r["outcome"] == "finished" for r in lines)

    def test_finish_emits_rt_request_span(self, telemetry):
        clk = ManualClock(50.0)
        log = self._log(clk)
        tl = log.open(rid=7)
        clk.advance(1.0)
        tl.close("eos")
        spans = [s for s in obs.tracing.finished_spans()
                 if s.name == "rt.request"]
        assert len(spans) == 1
        assert spans[0].args["rid"] == "7"
        assert spans[0].dur == pytest.approx(1e6)   # µs

    def test_attribution_merges_windows(self, telemetry):
        clk = ManualClock(0.0)
        a, b = self._log(clk), self._log(clk)
        for log, q in ((a, 1.0), (b, 3.0)):
            tl = log.open(rid=0)
            clk.advance(q)              # all queue time
            tl.close("eos")
        att = attribution_of([a.windows, b.windows])
        assert att["requests"] == 2
        assert att["mean_queue_ms"] == pytest.approx(2000.0)
        assert att["mean_e2e_ms"] == pytest.approx(2000.0)

    def test_tail_all_sorted_across_logs(self, telemetry):
        clk = ManualClock(10.0)
        a, b = self._log(clk), self._log(clk)
        a.open(rid="a").close("eos")
        clk.advance(1.0)
        b.open(rid="b").close("eos")
        # every live log's records, however many engines of earlier test
        # files this worker still holds: their wall-clock stamps sort
        # after this manual clock's and would push a and b out of a tail
        recs = tail_all(10 ** 6)
        rids = [r["rid"] for r in recs if r["rid"] in ("a", "b")]
        assert rids == ["a", "b"]


# ------------------------------------------------- engine integration
class TestEngineIntegration:
    def test_one_record_per_request_segments_sum(self, telemetry,
                                                 model):
        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8)
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 64, n).tolist() for n in (5, 9, 7)]
        rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
        _drain(eng)
        recs = eng.request_log.tail()
        assert sorted(r["rid"] for r in recs) == sorted(rids)
        for r in recs:
            assert r["outcome"] == "finished"
            assert r["tokens"] == 5
            assert r["ttft_s"] is not None and r["ttft_s"] > 0
            segs = (r["queue_s"] + r["prefill_s"] + r["decode_s"]
                    + r["preempt_s"])
            assert segs == pytest.approx(r["e2e_s"], abs=1e-9)
            # within 5% of e2e (the acceptance bound, trivially exact)
            assert abs(segs - r["e2e_s"]) <= 0.05 * r["e2e_s"]
        eng.shutdown()

    def test_cancel_maps_to_cancelled(self, telemetry, model):
        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8)
        rid = eng.submit([1, 2, 3], max_new_tokens=50)
        while not eng._requests[rid].generated:   # until its first token
            assert eng.step()
        eng.cancel(rid)                  # with its next row in flight
        _drain(eng)
        (rec,) = eng.request_log.tail()
        assert rec["outcome"] == "cancelled"
        eng.shutdown()

    def test_disabled_telemetry_attaches_nothing(self, model):
        assert not obs.enabled()
        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8)
        eng.submit([1, 2, 3], max_new_tokens=3)
        _drain(eng)
        assert eng._log is None         # lazy log never materialized
        eng.shutdown()

    def test_ops_snapshot_and_ptop_render(self, telemetry, model,
                                          tmp_path):
        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8,
                                       name="e0")
        eng.submit([1, 2, 3, 4], max_new_tokens=4)
        _drain(eng)
        snap = eng.ops_snapshot()
        assert snap["kind"] == "ops_snapshot"
        assert snap["source"] == "e0"
        assert "e0" in snap["replicas"]
        assert snap["slo"]["state"] in ("OK", "WARN", "BURN")
        assert snap["attribution"]["requests"] >= 1
        assert len(snap["requests"]) == 1
        # pure render: every section shows up in the text
        text = ptop.render(snap)
        assert "SLO" in text and "ttft_p99" in text
        assert "e0" in text and "attribution" in text
        assert "recent requests" in text
        # dumped file round-trips through the CLI loader
        path = str(tmp_path / "ops.json")
        eng.dump_ops_snapshot(path)
        text2 = ptop.render(ptop.load_snapshot(path))
        assert "ttft_p99" in text2
        eng.shutdown()

    def test_bundle_sections_and_diagnose(self, telemetry, model,
                                          tmp_path, capsys):
        import diagnose

        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8,
                                       name="e1")
        eng.submit([5, 6, 7], max_new_tokens=3)
        _drain(eng)
        eng.slo.evaluate()      # materialize the lazy SLO engine so the
        # bundle's reports_all() has a live engine to read
        d = str(tmp_path / "bundle")
        assert flight_recorder.dump_debug_bundle(d, reason="test") == d
        assert os.path.exists(
            os.path.join(d, "request_log_tail.jsonl"))
        assert os.path.exists(os.path.join(d, "slo_windows.json"))
        doc = json.load(open(os.path.join(d, "slo_windows.json")))
        assert any(k.startswith("e1") or "rt.ttft" in v
                   for k, v in doc["windows"].items())
        assert doc["slo"]                   # >= 1 live report
        assert diagnose.main(["diagnose", d]) == 0
        out = capsys.readouterr().out
        assert "access-log records" in out
        assert "rolling-window report" in out
        # the bundle dir also renders as a ptop pseudo-snapshot
        text = ptop.render(ptop.load_snapshot(d))
        assert "recent requests" in text
        eng.shutdown()


class TestClusterIntegration:
    def test_router_shed_and_merged_snapshot(self, telemetry, model):
        from paddle_tpu.serving.cluster import (ClusterRouter,
                                                Overloaded, Replica)

        reps = [Replica("r%d" % i, model, max_slots=1, block_size=8,
                        num_blocks=16, prefill_chunk=8)
                for i in range(2)]
        router = ClusterRouter(reps, max_queue=0)
        rng = np.random.RandomState(1)
        crids, shed = [], 0
        for _ in range(6):
            try:
                crids.append(router.submit(
                    rng.randint(0, 64, 5).tolist(), max_new_tokens=3))
            except Overloaded:
                shed += 1
        steps = 0
        while router.step() and steps < 400:
            steps += 1
        for c in crids:
            router.result(c)
        assert shed > 0                 # max_queue=0 must shed
        snap = router.ops_snapshot()
        # router + both replicas contribute windows
        assert set(snap["replicas"]) == {"r0", "r1"}
        assert "router" in snap
        shed_recs = [r for r in snap["requests"]
                     if r["outcome"] == "shed"]
        assert len(shed_recs) == shed
        sig = snap["signals"]
        assert sig["shed_rate_slow"] == pytest.approx(
            shed / (shed + len(crids)))
        stats = router.stats()
        assert stats["replicas"]["r0"]["alive"]
        assert "windows" in stats["replicas"]["r0"]
        router.shutdown()
