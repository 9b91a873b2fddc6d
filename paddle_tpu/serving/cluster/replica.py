"""Replica: one :class:`ServingEngine` behind a liveness boundary.

A replica is the unit the cluster router schedules over: it owns one
engine (thread-hosted in-process; nothing here assumes shared memory
beyond the engine handle, so a subprocess host only needs to proxy
these same calls), exposes the engine's thread-safe
:meth:`~paddle_tpu.serving.engine.ServingEngine.stats` health snapshot,
and mediates EVERY engine step through the deterministic fault harness
(:mod:`paddle_tpu.distributed.resilience.faults`, site
``cluster.replica``).

Death is simulated, never real: the fault kinds ``kill`` / ``raise`` /
``drop`` at this site are intercepted *before* :func:`faults.apply`
would ``os._exit`` the whole test process — the replica instead calls
:meth:`ServingEngine.fail_all`, which atomically captures a replayable
descriptor of every in-flight request, ends their streams with
``replica_dead``, and releases all KV pages. The descriptors flow to
the router's ``on_death`` callback, which replays them on survivors.
Generic kinds (``delay``) still go through ``faults.apply``.

The ``hang`` kind is the control-plane flavour of death: the replica
goes silent (stops stepping, therefore stops beating its lease) but
stays ``alive`` — nobody reports the crash. Detection is the router's
job: the lease expires, ``missed()`` names the replica, the router
evicts it and calls :meth:`die` to drain-and-replay. This is the
failure mode the lease substrate exists for; ``kill`` deaths are
self-reporting by comparison.

When the router runs a :class:`ClusterControlPlane`, every productive
step also beats the replica's fenced lease — liveness is a byproduct of
doing work, exactly like the elastic DP trainers.
"""
from __future__ import annotations

import threading
from typing import Callable, Optional, Sequence, Tuple

from ... import observability as _obs
from ...distributed.resilience import faults
from ..engine import (EngineStats, KVHandoff, RequestDescriptor,
                      ServingEngine)

__all__ = ["Replica", "FAULT_SITE"]

# the in-tree injection point for seeded replica kills:
#   PADDLE_TPU_FAULT_PLAN="cluster.replica:kill@7"
# fires on the 7th replica step ACROSS the cluster (the counter is per
# site, not per replica), so single-threaded round-robin stepping makes
# the victim deterministic.
FAULT_SITE = "cluster.replica"

_DEATH_KINDS = ("kill", "raise", "drop")


class Replica:
    """One engine + liveness; the router's scheduling unit."""

    def __init__(self, name: str, model, fault_site: str = FAULT_SITE,
                 **engine_knobs):
        self.name = str(name)
        self.fault_site = fault_site
        # access-log records and window snapshots carry the replica
        # name as their source (explicit name= knob wins)
        engine_knobs.setdefault("name", self.name)
        self.engine = ServingEngine(model, **engine_knobs)
        # router hook: called as on_death(replica, descriptors) from the
        # thread that observed the death, BEFORE step() returns
        self.on_death: Optional[
            Callable[["Replica", Tuple[RequestDescriptor, ...]],
                     None]] = None
        # set by ClusterRouter.add_replica when a control plane runs;
        # step() then beats the fenced lease on every productive pass
        self.control_plane = None
        self._lock = threading.Lock()
        self._alive = True  # guarded by: _lock
        self._hung = False  # guarded by: _lock

    # ------------------------------------------------------------ health
    @property
    def alive(self) -> bool:
        with self._lock:
            return self._alive

    @property
    def hung(self) -> bool:
        with self._lock:
            return self._hung

    def stats(self) -> EngineStats:
        """Thread-safe engine health snapshot (lock-held on the engine
        side, so the router never sees a torn read)."""
        return self.engine.stats()

    def warmup(self) -> None:
        """AOT warmup: pre-trace the step program (the ragged mixed
        prefill+decode jit), so this replica's first real token pays
        no cold compile."""
        self.engine.warmup()

    # ----------------------------------------------------- engine facade
    def submit(self, prompt: Sequence[int], **kw) -> int:
        return self.engine.submit(prompt, **kw)

    def events(self, rid: int):
        return self.engine.events(rid)

    def cancel(self, rid: int, reason: str = "cancelled") -> None:
        self.engine.cancel(rid, reason)

    def take_handoff(self) -> Optional[KVHandoff]:
        return self.engine.take_handoff()

    def adopt_handoff(self, payload: KVHandoff) -> Optional[int]:
        return self.engine.adopt_handoff(payload)

    # ----------------------------------------------------------- driving
    def step(self) -> bool:
        """One engine step, gated on the fault harness. Returns False
        when dead or idle. A death fault makes this replica drain
        in-flight work into descriptors and hand them to ``on_death``
        synchronously — by the time step() returns, the router has
        already replayed them."""
        with self._lock:
            if not self._alive or self._hung:
                return False
        act = faults.check(self.fault_site)
        if act is not None:
            if act.kind in _DEATH_KINDS:
                self.die()
                return False
            if act.kind == "hang":
                # go silent: stop stepping (and therefore beating), but
                # stay alive — the router must DISCOVER this through the
                # missed lease, there is no crash report
                with self._lock:
                    self._hung = True
                return False
            faults.apply(act)
        if self.control_plane is not None:
            self.control_plane.beat(self.name)
        return self.engine.step()

    def die(self) -> Tuple[RequestDescriptor, ...]:
        """Simulate a crash of this replica (idempotent)."""
        with self._lock:
            if not self._alive:
                return ()
            self._alive = False
        descs = self.engine.fail_all("replica_dead")
        if _obs.enabled():
            _obs.registry.counter("cluster.replica_deaths").inc()
        cb = self.on_death
        if cb is not None:
            cb(self, descs)
        return descs

    def retire(self) -> Tuple[RequestDescriptor, ...]:
        """Planned departure (autoscaler scale-in): the same atomic
        drain-and-replay path as :meth:`die` — in-flight work becomes
        descriptors the router replays token-exactly on survivors — but
        NOT counted as a death: the control plane published a clean
        leave, nothing crashed."""
        with self._lock:
            if not self._alive:
                return ()
            self._alive = False
        descs = self.engine.fail_all("replica_dead")
        cb = self.on_death
        if cb is not None:
            cb(self, descs)
        return descs

    def shutdown(self, check_leaks: bool = True) -> None:
        self.engine.shutdown(check_leaks=check_leaks)
