"""Serving cluster tier (serving/cluster/): replica health + AOT
warmup, prefix-affinity routing, admission control / load shedding,
seeded replica-kill drain-and-replay, disaggregated prefill/decode
handoff, and the single-timeline Perfetto export."""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import observability as obs
from paddle_tpu.distributed.resilience import faults
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ServingEngine
from paddle_tpu.serving.cluster import (ClusterRouter, DisaggPolicy,
                                        Overloaded, Replica)


@pytest.fixture(scope="module")
def model():
    pt.seed(11)
    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    m = pt.models.GPTForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def telemetry():
    """Enabled, empty registry AND trace ring; off + empty after."""
    obs.registry.reset()
    tracing.reset()
    obs.enable()
    yield obs.registry
    obs.disable()
    obs.registry.reset()
    tracing.reset()


def _ref(m, prompt, max_new):
    out = m.generate(pt.to_tensor(np.asarray([prompt], np.int64)),
                     max_new_tokens=max_new).numpy()
    return out[0].tolist()


def _prompts(m, lens, seed=0):
    rng = np.random.RandomState(seed)
    v = m.config.vocab_size
    return [rng.randint(0, v, n).tolist() for n in lens]


def _mk_replicas(model, n=2, **kw):
    knobs = dict(max_slots=2, block_size=8, num_blocks=32,
                 prefill_chunk=8)
    knobs.update(kw)
    reps = [Replica("r%d" % i, model, **knobs) for i in range(n)]
    for r in reps:
        r.warmup()
    return reps


def _drain(router, cap=500):
    n = 0
    while router.step() and n < cap:
        n += 1
    assert n < cap, "router failed to drain"


# ------------------------------------------------------------------ replica
class TestReplica:
    def test_stats_snapshot(self, model):
        rep = Replica("r0", model, max_slots=2, block_size=8,
                      num_blocks=32, prefill_chunk=8)
        st0 = rep.stats()
        assert st0.total_blocks == 32 and st0.free_blocks == 32
        assert st0.queue_depth == 0 and st0.active_slots == 0
        [p] = _prompts(model, [5])
        rep.submit(p, max_new_tokens=4)
        st1 = rep.stats()
        # submitted but not yet stepped: sits in the waiting queue
        assert st1.queue_depth == 1
        assert st1.can_admit(1)
        assert not st1.can_admit(st1.free_blocks + 1)
        while rep.step():
            pass
        st2 = rep.stats()
        assert st2.queue_depth == 0 and st2.active_slots == 0
        assert st2.free_blocks == st2.total_blocks
        rep.shutdown()

    def test_warmup_pretraces_ragged_jit(self, model):
        """AOT warmup compiles the ragged step exactly once; real
        traffic after warmup pays zero cold compiles and keeps stream
        parity."""
        rep = Replica("r0", model, max_slots=2, block_size=8,
                      num_blocks=32, prefill_chunk=8)
        rep.warmup()
        assert rep.engine.ragged_compiles == 1
        prompts = _prompts(model, [5, 11])
        refs = [_ref(model, p, 6) for p in prompts]
        rids = [rep.submit(p, max_new_tokens=6) for p in prompts]
        while rep.step():
            pass
        assert [rep.engine.result(r) for r in rids] == refs
        assert rep.engine.ragged_compiles == 1, \
            "warmup did not pre-trace the ragged jit"
        rep.shutdown()

    def test_die_drains_descriptors_and_is_idempotent(self, model):
        rep = Replica("r0", model, max_slots=2, block_size=8,
                      num_blocks=32, prefill_chunk=8)
        rep.warmup()
        [p] = _prompts(model, [5])
        rid = rep.submit(p, max_new_tokens=6)
        req = rep.engine._requests[rid]
        while len(req.generated) < 2:
            assert rep.step()
        # a third token is in flight: the dying engine reads it first,
        # so the descriptor holds every token its stream got
        assert req.in_flight == 1
        descs = rep.die()
        assert not rep.alive and not rep.step()
        assert len(descs) == 1 and descs[0].rid == rid
        d = descs[0]
        assert list(d.prompt) == p
        assert len(d.generated) == 3 and d.remaining == 3
        assert [v for k, v in rep.events(rid) if k == "tok"] \
            == list(d.generated)
        assert rep.die() == ()           # idempotent
        rep.shutdown(check_leaks=False)


# ------------------------------------------------------------------- router
class TestRouterParity:
    def test_streams_match_generate_across_replicas(self, model):
        prompts = _prompts(model, [5, 11, 7, 9])
        refs = [_ref(model, p, 6) for p in prompts]
        router = ClusterRouter(_mk_replicas(model))
        crids = [router.submit(p, max_new_tokens=6) for p in prompts]
        _drain(router)
        assert [router.result(c) for c in crids] == refs
        router.shutdown()

    def test_cancel_raises_typed_error(self, model):
        router = ClusterRouter(_mk_replicas(model, n=1))
        [p] = _prompts(model, [5])
        crid = router.submit(p, max_new_tokens=6)
        router.cancel(crid)
        _drain(router)
        with pytest.raises(Exception) as ei:
            router.result(crid)
        assert "cancelled" in str(ei.value)
        router.shutdown()


class TestPrefixAffinity:
    def test_shared_prefix_routes_to_cached_replica(self, model,
                                                    telemetry):
        """Repeated shared-prefix prompts land on the replica whose
        paged prefix cache already holds the blocks — proven by the
        engine's own prefix-hit counter, not just the routing tag."""
        bs = 8
        rng = np.random.RandomState(3)
        v = model.config.vocab_size
        pre = rng.randint(0, v, 2 * bs).tolist()   # two full blocks
        tails = [rng.randint(0, v, 5).tolist() for _ in range(3)]
        prompts = [pre + t for t in tails]
        refs = [_ref(model, p, 4) for p in prompts]
        router = ClusterRouter(_mk_replicas(model, block_size=bs))

        c0 = router.submit(prompts[0], max_new_tokens=4)
        _drain(router)                   # finish -> prefix registered
        outs = [router.result(c0)]
        for p in prompts[1:]:
            c = router.submit(p, max_new_tokens=4)
            _drain(router)
            outs.append(router.result(c))
        assert outs == refs

        snap = telemetry.snapshot()
        # follow-ups routed by affinity, not the least-loaded fallback
        assert snap["counters"].get(
            "cluster.submitted{route=affinity}", 0) >= 2
        assert snap["counters"].get("cluster.affinity_hits", 0) >= 2
        # and the target replica's prefix cache actually hit: both
        # shared blocks restored without recompute, per follow-up
        assert snap["counters"].get(
            "serving.prefix_hit_tokens", 0) >= 2 * 2 * bs
        router.shutdown()


class TestShedding:
    def test_overload_sheds_typed_and_recovers(self, model, telemetry):
        """Past the per-replica queue bound, submit fails fast with the
        typed Overloaded — and admits again once the backlog drains."""
        prompts = _prompts(model, [5, 7, 9, 6, 8])
        router = ClusterRouter(_mk_replicas(model, max_slots=1),
                               max_queue=1)
        crids = [router.submit(p, max_new_tokens=4)
                 for p in prompts[:2]]   # one queued per replica
        with pytest.raises(Overloaded) as ei:
            router.submit(prompts[2], max_new_tokens=4)
        assert ei.value.reason == "overloaded"
        assert "replicas" in ei.value.detail
        snap = telemetry.snapshot()
        assert snap["counters"].get("cluster.shed", 0) == 1

        _drain(router)                   # backlog drains -> admit again
        crids.append(router.submit(prompts[3], max_new_tokens=4))
        _drain(router)
        outs = [router.result(c) for c in crids]
        assert outs == [_ref(model, p, 4) for p in prompts[:2] +
                        [prompts[3]]]
        router.shutdown()

    def test_watermark_blocks_admission_not_queue(self, model):
        """A prompt bigger than free-above-watermark is shed even with
        an empty queue — admission checks blocks, not just depth."""
        router = ClusterRouter(
            _mk_replicas(model, n=1, num_blocks=4, max_seq_len=64))
        [big] = _prompts(model, [40])    # needs 6 blocks of 8, pool: 4
        with pytest.raises(Overloaded):
            router.submit(big, max_new_tokens=4)
        [ok] = _prompts(model, [9])
        c = router.submit(ok, max_new_tokens=4)
        _drain(router)
        assert router.result(c) == _ref(model, ok, 4)
        router.shutdown()


# --------------------------------------------------------------- resilience
class TestReplicaKill:
    def test_seeded_kill_drains_and_replays(self, model, telemetry):
        """Seeded fault plan kills one replica mid-flight; the router
        drains its descriptors and replays on the survivor with exact
        stream parity — greedy replay is invisible to clients."""
        prompts = _prompts(model, [5, 11, 7, 9])
        refs = [_ref(model, p, 6) for p in prompts]
        reps = _mk_replicas(model)
        router = ClusterRouter(reps)
        faults.configure("cluster.replica:kill@5", seed=0)
        try:
            crids = [router.submit(p, max_new_tokens=6)
                     for p in prompts]
            _drain(router)
            outs = [router.result(c) for c in crids]
            assert len(faults.injected()) == 1
        finally:
            faults.reset()
        assert router.num_alive() == 1
        assert outs == refs
        snap = telemetry.snapshot()
        assert snap["counters"].get("cluster.replica_deaths", 0) == 1
        assert snap["counters"].get("cluster.replays", 0) >= 1
        # shedding never applies to replays: every request finished
        assert snap["counters"].get("cluster.shed", 0) == 0
        router.shutdown()                # survivor must not leak blocks

    def test_all_replicas_dead_fails_streams_not_hangs(self, model):
        reps = _mk_replicas(model, n=1)
        router = ClusterRouter(reps)
        [p] = _prompts(model, [5])
        crid = router.submit(p, max_new_tokens=6)
        reps[0].die()
        with pytest.raises(Exception) as ei:
            router.result(crid)
        assert "replica_dead" in str(ei.value)
        router.shutdown(check_leaks=False)


# ------------------------------------------------------------------- disagg
class TestDisagg:
    def test_prefill_decode_split_parity(self, model, telemetry):
        """Prompts prefill on tier 0, decode on tier 1 after the KV
        pages hand off through the paged pool layout — streams stay
        token-identical to generate()."""
        prompts = _prompts(model, [5, 11, 9])
        refs = [_ref(model, p, 6) for p in prompts]
        reps = _mk_replicas(model)
        router = ClusterRouter(reps, disagg=DisaggPolicy.split(reps))
        crids = [router.submit(p, max_new_tokens=6) for p in prompts]
        _drain(router)
        assert [router.result(c) for c in crids] == refs
        snap = telemetry.snapshot()
        assert snap["counters"].get("cluster.handoffs", 0) == \
            len(prompts)
        # decode tier holds the adopted requests' pages; prefill tier
        # released everything at handoff — shutdown checks both
        router.shutdown()

    def test_int8_kv_pages_are_the_wire_format(self, model):
        """kv_quant='int8' handoff ships the quantized pages verbatim;
        results match a single int8 engine bit for bit."""
        prompts = _prompts(model, [5, 11])
        eng = ServingEngine(model, max_slots=2, block_size=8,
                            num_blocks=32, prefill_chunk=8,
                            kv_quant="int8")
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        while eng.step():
            pass
        refs = [eng.result(r) for r in rids]
        eng.shutdown()

        reps = _mk_replicas(model, kv_quant="int8")
        router = ClusterRouter(reps, disagg=DisaggPolicy.split(reps))
        crids = [router.submit(p, max_new_tokens=6) for p in prompts]
        _drain(router)
        assert [router.result(c) for c in crids] == refs
        router.shutdown()


# ------------------------------------------------------------ observability
class TestClusterTimeline:
    def test_one_perfetto_trace_spans_router_and_replicas(
            self, model, telemetry, tmp_path):
        """One chrome-trace export carries the whole cluster story:
        routing, per-replica engine steps, the kill, and the replay —
        a single Perfetto timeline, no per-replica stitching."""
        prompts = _prompts(model, [5, 11, 7, 9])
        router = ClusterRouter(_mk_replicas(model))
        faults.configure("cluster.replica:kill@5", seed=0)
        try:
            crids = [router.submit(p, max_new_tokens=6)
                     for p in prompts]
            _drain(router)
            for c in crids:
                router.result(c)
        finally:
            faults.reset()
        path = str(tmp_path / "cluster_trace.json")
        doc = tracing.export_chrome_trace(path)
        with open(path) as f:
            assert json.load(f) == doc
        names = {ev["name"] for ev in doc["traceEvents"]}
        assert {"cluster.route", "cluster.replay",
                "serving.step", "serving.ragged_step"} <= names
        router.shutdown()
