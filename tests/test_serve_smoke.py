"""Tier-1 wiring for tools/serve_smoke.py: the serving engine's
parity/compile/leak smoke AND the cluster arm (2 replicas, seeded
replica kill, replay parity) run inside the suite."""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                "tools"))
import serve_smoke  # noqa: E402


def test_serve_smoke_passes():
    assert serve_smoke.main() == 0


def test_serve_smoke_cluster_passes():
    assert serve_smoke.main_cluster() == 0


def test_serve_smoke_autoscale_passes():
    # control-plane arm: SLO/queue-driven scale-out (warm joins, zero
    # cold compiles), seeded mid-flight hang -> missed-lease eviction
    # -> token-exact replay, idle scale-in back to one replica
    assert serve_smoke.main_autoscale() == 0


def test_serve_smoke_kvtier_passes():
    # cluster-wide KV cache arm: cross-replica prefix fetch through
    # the global index, forced demotion sweep, host-tier restore —
    # every stream token-exact vs a tier-off recompute engine
    assert serve_smoke.main_kvtier() == 0
