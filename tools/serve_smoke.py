#!/usr/bin/env python
"""Fast serving smoke: requests through ServingEngine must exactly
reproduce per-request ``generate()`` greedy streams with one step
compile and a fully drained block pool: the engine serves via the
single RAGGED mixed prefill+decode jit (``ragged_compiles == 1``).

``--cluster`` runs the multi-replica arm: two in-process replicas
behind the prefix-affinity router, a seeded fault-plan kill of one
replica mid-flight (``cluster.replica:kill@N``), and asserts the
drained-and-replayed streams still match the single-engine references
token for token.

``--autoscale`` runs the control-plane arm: one replica behind a
router wired to a :class:`ClusterControlPlane` (ManualClock — zero
sleeps), a seeded request ramp that makes the Autoscaler grow the
pool (joining replicas warm up BEFORE taking traffic: exactly one
ragged compile each), a mid-flight ``hang`` fault (the replica goes
SILENT — only the missed-lease scan can find it), eviction inside the
lease budget with token-exact replay, and scale-in back to one
replica on sustained idle.

``--kvtier`` runs the cluster-wide KV cache arm: two ``int8``-KV
replicas behind a router wired to a :class:`ClusterKVStore`. A shared
system prompt served on one replica must be fetched **cross-replica**
through the global prefix index when admission pushes a later request
onto the other replica; after a forced demotion sweep empties both
device caches, a third request must restore the prefix from the
**host-RAM tier** — and every stream stays token-exact against a
tier-off recompute engine.

Importable (``main()`` returns 0/raises) so tests/test_serve_smoke.py
runs all arms inside the tier-1 suite; also runnable standalone:

    JAX_PLATFORMS=cpu python tools/serve_smoke.py \
        [--cluster|--autoscale|--kvtier]
"""
from __future__ import annotations

import os
import sys


def _build(n_prompts=2):
    import numpy as np

    import paddle_tpu as pt

    pt.seed(11)
    cfg = pt.models.gpt_tiny(dropout=0.0, attention_dropout=0.0)
    model = pt.models.GPTForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, n).tolist()
               for n in (5, 11, 7, 9)[:n_prompts]]
    refs = [model.generate(pt.to_tensor(np.asarray([p], np.int64)),
                           max_new_tokens=6).numpy()[0].tolist()
            for p in prompts]
    return pt, model, prompts, refs


def _drain(eng, rids, cap=200):
    steps = 0
    while eng.step():
        steps += 1
        assert steps < cap, "engine failed to drain"
    return [eng.result(r) for r in rids], steps


def main() -> int:
    pt, model, prompts, refs = _build()
    from paddle_tpu.observability.request_log import OUTCOMES

    # this arm ALSO audits the access log, so it runs telemetry-on
    # (restored on exit — the other arms prove the disabled path)
    was_enabled = pt.observability.enabled()
    pt.observability.enable()
    try:
        eng = pt.serving.ServingEngine(model, max_slots=2, block_size=8,
                                       num_blocks=32, prefill_chunk=8)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
        outs, steps = _drain(eng, rids)
        assert outs == refs, "serving stream != generate(): %r vs %r" \
            % (outs, refs)
        assert eng.ragged_compiles == 1, \
            "ragged step compiled %d times" % eng.ragged_compiles

        # ---- access-log integrity: exactly one closed record per
        # submitted request, a legal terminal outcome, and phase
        # segments that never exceed the end-to-end latency
        recs = eng.request_log.tail()
        assert len(recs) == len(rids), \
            "access log has %d records for %d requests" \
            % (len(recs), len(rids))
        assert sorted(r["rid"] for r in recs) == sorted(rids), \
            "access-log rids do not match submitted rids"
        for r in recs:
            assert r["outcome"] in OUTCOMES, \
                "illegal terminal outcome %r" % r["outcome"]
            segs = (r["queue_s"] + r["prefill_s"] + r["decode_s"]
                    + r["preempt_s"])
            assert segs <= r["e2e_s"] + 1e-6, \
                "segments %.6fs exceed e2e %.6fs in %r" \
                % (segs, r["e2e_s"], r)
        eng.shutdown()                   # raises on any block leak
    finally:
        if not was_enabled:
            pt.observability.disable()
    print("serve_smoke: %d requests, %d steps, parity OK, "
          "1 ragged compile, access log intact, pool drained"
          % (len(prompts), steps))
    return 0


def main_cluster() -> int:
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.serving.cluster import ClusterRouter, Replica

    pt, model, prompts, refs = _build(n_prompts=4)
    reps = [Replica("r%d" % i, model, max_slots=2, block_size=8,
                    num_blocks=32, prefill_chunk=8) for i in range(2)]
    for r in reps:
        r.warmup()                       # ragged jit traced pre-traffic
    router = ClusterRouter(reps)

    # the 5th replica step across the cluster kills whichever replica
    # the round-robin lands on, mid-flight — seeded + deterministic
    faults.configure("cluster.replica:kill@5", seed=0)
    try:
        crids = [router.submit(p, max_new_tokens=6) for p in prompts]
        steps = 0
        while router.step():
            steps += 1
            assert steps < 400, "router failed to drain"
        outs = [router.result(c) for c in crids]
    finally:
        faults.reset()
    assert router.num_alive() == 1, "seeded kill did not land"
    assert outs == refs, \
        "replayed streams != generate(): %r vs %r" % (outs, refs)
    for r in reps:
        assert r.engine.ragged_compiles == 1, \
            "replica %s compiled ragged %d times" \
            % (r.name, r.engine.ragged_compiles)
    router.shutdown()                    # raises on survivor block leak
    print("serve_smoke --cluster: %d requests, %d steps, 1 replica "
          "killed, replay parity OK, 1 ragged compile/replica"
          % (len(prompts), steps))
    return 0


def main_autoscale() -> int:
    from paddle_tpu.distributed.resilience import faults
    from paddle_tpu.observability.windows import ManualClock
    from paddle_tpu.serving.cluster import (AutoscaleConfig, Autoscaler,
                                            ClusterControlPlane,
                                            ClusterRouter, Replica)

    pt, model, prompts, refs = _build(n_prompts=4)
    prompts, refs = prompts * 2, refs * 2          # the 8-request ramp
    knobs = dict(max_slots=2, block_size=8, num_blocks=32,
                 prefill_chunk=8)

    clk = ManualClock()
    cp = ClusterControlPlane(lease_timeout=1.0, clock=clk)
    spawned = []

    def spawn(name):
        rep = Replica(name, model, **knobs)
        spawned.append(rep)
        return rep

    first = spawn("r0")
    first.warmup()
    router = ClusterRouter([first], max_queue=8, control_plane=cp)
    scaler = Autoscaler(
        router, spawn,
        AutoscaleConfig(min_replicas=1, max_replicas=3, up_ticks=2,
                        idle_ticks=3, cooldown_ticks=4, queue_hwm=2),
        clock=clk)

    def pump(cap=400):
        steps = 0
        while router.step():
            steps += 1
            scaler.tick()
            clk.advance(0.05)
            assert steps < cap, "router failed to drain"
        return steps

    # the 9th replica step across the cluster hangs whichever replica
    # round-robin lands on — AFTER the queue-pressure scale-out at
    # tick 2, so the victim holds in-flight work and survivors exist
    faults.configure("cluster.replica:hang@9", seed=0)
    try:
        crids = [router.submit(p, max_new_tokens=6) for p in prompts]
        steps = pump()
        hung = [r for r in router.replicas if r.alive and r.hung]
        assert hung, "seeded hang did not land"
        victim = hung[0]
        assert router.num_alive() >= 2, \
            "scale-out must precede the hang (pool=%d)" \
            % router.num_alive()

        # nobody reported the hang: only the lease can find it. Advance
        # the manual clock through the lease budget; the router's scan
        # must evict + drain the zombie within it (survivors keep
        # beating, so ONLY the victim expires).
        for _ in range(64):
            clk.advance(0.1)
            router.step()
            scaler.tick()
            if not victim.alive:
                break
        assert not victim.alive, "missed-beat eviction never fired"
        assert victim.name not in cp.members, \
            "evicted replica still in the epoch"
        steps += pump()                   # drain the replayed work
        outs = [router.result(c) for c in crids]

        # sustained idle: the scaler must walk the pool back to min
        for _ in range(64):
            router.step()
            scaler.tick()
            clk.advance(0.05)
            if router.num_alive() <= 1:
                break
    finally:
        faults.reset()
    assert outs == refs, \
        "post-hang replayed streams != generate(): %r vs %r" \
        % (outs, refs)
    assert len(spawned) >= 2, "autoscaler never scaled out"
    assert router.num_alive() == 1, \
        "idle scale-in left %d replicas" % router.num_alive()
    ev = scaler.last_event or {}
    assert ev.get("kind") == "scale_down", \
        "last scale event should be the idle shrink, got %r" % (ev,)
    for r in spawned:
        assert r.engine.ragged_compiles == 1, \
            "replica %s compiled ragged %d times (join must be warm)" \
            % (r.name, r.engine.ragged_compiles)
    router.shutdown()
    print("serve_smoke --autoscale: %d requests, %d steps, pool "
          "1->%d->%d, hang evicted via missed lease, replay parity "
          "OK, 1 ragged compile/replica"
          % (len(prompts), steps, len(spawned), router.num_alive()))
    return 0


def main_kvtier() -> int:
    """Tier-1 cluster-KV arm: cross-replica prefix fetch through the
    global index, then a host-tier restore after forced demotion, both
    token-exact vs tier-off recompute. Runs telemetry-OFF on purpose:
    the ``ClusterKVStore.counts`` dict must tell the story anyway."""
    import numpy as np

    from paddle_tpu.serving.cluster import ClusterRouter, Replica
    from paddle_tpu.serving.kv_store import (ClusterKVStore,
                                             KVStoreConfig)

    pt, model, _, _ = _build()
    # int8 KV pools: the host spill IS the pool layout, so demote ->
    # promote round trips are bit-exact and streams stay token-exact
    knobs = dict(max_slots=2, block_size=8, num_blocks=24,
                 prefill_chunk=8, kv_quant="int8")
    rng = np.random.RandomState(7)
    shared = rng.randint(0, 200, 32).tolist()   # 4 full blocks
    reqs = [shared + rng.randint(0, 200, n).tolist() for n in (7, 9, 11)]
    junk = rng.randint(0, 200, 20).tolist()

    # tier-off recompute references (same int8 numerics, no cluster)
    ref_eng = pt.serving.ServingEngine(model, **knobs)
    refs = []
    for p in reqs:
        rid = ref_eng.submit(list(p), max_new_tokens=6)
        (out,), _ = _drain(ref_eng, [rid])
        refs.append(out)
    ref_eng.shutdown()

    reps = [Replica("r%d" % i, model, **knobs) for i in range(2)]
    for r in reps:
        r.warmup()
    kv = ClusterKVStore(config=KVStoreConfig(tier="host", host_mb=8))
    router = ClusterRouter(reps, max_queue=1, kv_store=kv)

    def pump(cap=400):
        steps = 0
        while router.step():
            steps += 1
            assert steps < cap, "router failed to drain"
        return steps

    # ---- phase 1: request A plants the shared prefix on r0 and the
    # global index learns the chain
    c0 = router.submit(reqs[0], max_new_tokens=6)
    steps = pump()
    out0 = router.result(c0)

    # ---- phase 2: cross-replica fetch. Saturate r0 (max_queue=1) so
    # the affinity route FAILS admission and request B lands on r1 —
    # whose prefetch must then import the prefix pages from r0
    cj = router.submit(junk, max_new_tokens=6)       # queues on r0
    c1 = router.submit(reqs[1], max_new_tokens=6)    # sheds to r1
    steps += pump()
    out1 = router.result(c1)
    router.result(cj)                                # drain, discard
    c = kv.counts
    assert c["fetches_replica"] >= 1, \
        "no cross-replica prefix fetch happened: %r" % (c,)
    assert c["fetch_tokens"] >= len(shared), \
        "cross-replica fetch moved %d tokens, wanted >= %d" \
        % (c["fetch_tokens"], len(shared))

    # ---- phase 3: forced demotion sweep — every evictable block on
    # both replicas spills through the pump into the host tier; the
    # device caches must come back EMPTY
    for r in reps:
        with r.engine._lock:
            r.engine.manager.pop_evictable(knobs["num_blocks"])
    while kv.pump() > 0:
        pass
    for r in reps:
        assert r.engine.probe_prefix(reqs[2]) == 0, \
            "%s still holds the prefix after demotion" % r.name
    assert kv.counts["demotes"] > 0, "demotion pump spilled nothing"
    assert len(kv.host) > 0, "host tier is empty after the sweep"

    # ---- phase 4: host-tier restore — request C's prefetch promotes
    # the shared prefix back to a device from host RAM
    c2 = router.submit(reqs[2], max_new_tokens=6)
    steps += pump()
    out2 = router.result(c2)
    c = kv.counts
    assert c["fetches_host"] >= 1 and c["promotes"] >= 1, \
        "no host-tier promote happened: %r" % (c,)
    assert c["crc_failures"] == 0, "CRC failures during the smoke"

    assert [out0, out1, out2] == refs, \
        "tiered streams != tier-off recompute: %r vs %r" \
        % ([out0, out1, out2], refs)
    for r in reps:
        assert r.engine.ragged_compiles == 1, \
            "replica %s compiled ragged %d times" \
            % (r.name, r.engine.ragged_compiles)
    router.shutdown()                    # raises on any block leak
    print("serve_smoke --kvtier: %d requests, %d steps, %d tokens "
          "fetched (replica=%d host=%d), %d blocks demoted to host, "
          "token-exact vs recompute, 1 ragged compile/replica"
          % (len(reqs), steps, c["fetch_tokens"],
             c["fetches_replica"], c["fetches_host"], c["demotes"]))
    return 0


if __name__ == "__main__":
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), os.pardir))
    if "--kvtier" in sys.argv:
        sys.exit(main_kvtier())
    if "--autoscale" in sys.argv:
        sys.exit(main_autoscale())
    if "--cluster" in sys.argv:
        sys.exit(main_cluster())
    sys.exit(main())
