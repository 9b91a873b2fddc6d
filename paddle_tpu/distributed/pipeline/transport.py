"""Pipeline p2p transport: compiled ring transfers + fleet payload channel.

Two distinct consumers share this module:

* The compiled 1F1B step (:mod:`.schedule`) calls :func:`ring_shift`
  INSIDE a traced ``shard_map`` body — a single ring step implemented as
  ``jax.lax.ppermute`` (lowered to XLA ``collective-permute``), so the
  boundary tensor never leaves device HBM.
* The eager FleetExecutor keeps its rpc message bus for CONTROL
  (DATA_IS_READY / DATA_IS_USELESS / STOP) but, when a
  :class:`FleetPayloadTransport` is registered, array payloads ride
  ProcessGroup device p2p instead of being pickled through the store/rpc
  path. The rpc message then carries only a small shape/dtype/seq
  descriptor (:func:`is_payload_descriptor`).

Transport selection (``PADDLE_TPU_PP_TRANSPORT``):

* ``auto`` (default) — device p2p when the process group supports
  compiled collectives (ProcessGroupXLA), host store/rpc otherwise.
* ``device`` — same as auto, and additionally opts the Engine into the
  fully-compiled pipeline step when the staged program is uniform.
* ``host``  — force the host store/rpc path everywhere (debug escape
  hatch; also what the parity tests compare against).
"""
from __future__ import annotations

import threading
from typing import Optional

import jax
import jax.numpy as jnp

from ...config import knobs
from ...core.tensor import Tensor
from ... import observability as _obs

__all__ = [
    "transport_mode", "overlap_bucket_bytes", "ring_shift",
    "FleetPayloadTransport", "set_fleet_transport", "get_fleet_transport",
    "is_payload_descriptor",
]

_PAYLOAD_KEY = "__pp_payload__"


# ------------------------------------------------------------------ knobs
def transport_mode() -> str:
    """``PADDLE_TPU_PP_TRANSPORT``: ``auto`` | ``device`` | ``host``."""
    mode = knobs.get_str("PADDLE_TPU_PP_TRANSPORT").strip().lower()
    return mode if mode in ("auto", "device", "host") else "auto"


def overlap_bucket_bytes() -> int:
    """Gradient-sync bucket size from ``PADDLE_TPU_PP_BUCKET_MB`` (MB)."""
    mb = knobs.get_float("PADDLE_TPU_PP_BUCKET_MB")
    return max(1, int(mb * (1 << 20)))


# ------------------------------------------------- compiled ring transfers
def ring_shift(x: jnp.ndarray, axis_name: str, size: int) -> jnp.ndarray:
    """Move ``x`` one step forward around the ``axis_name`` ring.

    Must be called inside a ``shard_map`` body mapped over ``axis_name``.
    Lowered to XLA ``collective-permute`` via ``lax.ppermute``, which
    has a native transpose, so the step is differentiable.
    """
    perm = [(i, (i + 1) % size) for i in range(size)]
    return jax.lax.ppermute(x, axis_name, perm=perm)


# ---------------------------------------------- fleet payload transport
class FleetPayloadTransport:
    """Carries FleetExecutor message payloads over device p2p.

    The rpc control message keeps its ordering/trace-ctx role but its
    payload becomes a descriptor; the tensor itself moves via
    ``ProcessGroup.send``/``recv`` (compiled pair-mesh collectives on
    ProcessGroupXLA) and stays in device memory end to end.

    Ordering contract: per (src, dst) direction, collectives are
    launched in ``seq`` order on the send side (seq assignment and
    launch are atomic under a per-destination lock) and the receiver
    serialises its recvs per source in the same ``seq`` order via a
    condition variable — rpc delivery order is irrelevant. Distinct
    (src, dst) pairs use distinct pair meshes and may interleave
    freely. Concurrent OPPOSING transfers between the same pair must
    use ``ProcessGroup.sendrecv`` (one fused program) — the fleet
    graph's payload edges are one-directional per pair, which is what
    this transport is specified for.
    """

    def __init__(self, pg, my_rank: int, timeout: float = 300.0):
        self._pg = pg
        self._rank = int(my_rank)
        self._timeout = timeout
        self._maps_lock = threading.Lock()
        self._send_locks = {}        # dst_rank -> Lock
        self._send_seq = {}          # dst_rank -> next seq to assign
        self._recv_cv = {}           # src_rank -> Condition
        self._recv_next = {}         # src_rank -> next seq to accept

    def _send_lock(self, dst: int) -> threading.Lock:
        with self._maps_lock:
            return self._send_locks.setdefault(dst, threading.Lock())

    def _cv(self, src: int) -> threading.Condition:
        with self._maps_lock:
            return self._recv_cv.setdefault(src, threading.Condition())

    def send(self, payload, dst_rank: int, post=None) -> dict:
        """Ship ``payload`` to ``dst_rank``; returns the rpc descriptor.

        ``post`` (descriptor -> None), when given, is invoked while the
        per-destination lock is still held, so the control-message post
        order matches the collective launch order exactly — the
        receiver's single rpc dispatcher then always sees descriptors
        in ``seq`` order and never parks on the ordering condition.
        """
        arr = payload._data if isinstance(payload, Tensor) \
            else jnp.asarray(payload)
        with self._send_lock(dst_rank):
            seq = self._send_seq.get(dst_rank, 0)
            self._send_seq[dst_rank] = seq + 1
            with _obs.span("pp.send", cat="pipeline",
                           args={"transport": "device", "dst": dst_rank,
                                 "seq": seq}):
                self._pg.send(Tensor(arr), dst_rank)
            desc = {_PAYLOAD_KEY: True,
                    "shape": tuple(int(d) for d in arr.shape),
                    "dtype": str(arr.dtype), "seq": seq,
                    "src": self._rank}
            if post is not None:
                post(desc)
        if _obs.enabled():
            nbytes = int(arr.size) * jnp.dtype(arr.dtype).itemsize
            _obs.registry.counter("pipeline.p2p_bytes",
                                  {"transport": "device"}).inc(nbytes)
            _obs.registry.counter("pipeline.p2p_messages",
                                  {"transport": "device"}).inc()
        return desc

    def recv(self, desc: dict):
        """Blocking ordered receive for a payload descriptor."""
        src, seq = int(desc["src"]), int(desc["seq"])
        cv = self._cv(src)
        with cv:
            deadline = self._timeout
            while self._recv_next.get(src, 0) != seq:
                if not cv.wait(timeout=deadline):
                    raise TimeoutError(
                        f"pipeline transport: seq {seq} from rank {src} "
                        f"never became current "
                        f"(next={self._recv_next.get(src, 0)})")
            buf = Tensor(jnp.zeros(desc["shape"], desc["dtype"]))
            with _obs.span("pp.recv", cat="pipeline",
                           args={"transport": "device", "src": src,
                                 "seq": seq}):
                self._pg.recv(buf, src)
            self._recv_next[src] = seq + 1
            cv.notify_all()
        if _obs.enabled():
            arr = buf._data
            nbytes = int(arr.size) * jnp.dtype(arr.dtype).itemsize
            _obs.registry.counter("pipeline.p2p_bytes",
                                  {"transport": "device"}).inc(nbytes)
        return buf._data


def is_payload_descriptor(obj) -> bool:
    return isinstance(obj, dict) and obj.get(_PAYLOAD_KEY) is True


_fleet_transport: Optional[FleetPayloadTransport] = None
_fleet_transport_lock = threading.Lock()


def set_fleet_transport(t: Optional[FleetPayloadTransport]) -> None:
    global _fleet_transport
    with _fleet_transport_lock:
        _fleet_transport = t


def get_fleet_transport() -> Optional[FleetPayloadTransport]:
    return _fleet_transport


def ensure_fleet_transport() -> Optional[FleetPayloadTransport]:
    """Register a :class:`FleetPayloadTransport` over the default
    collective process group, if one exists and the transport knob
    allows device payloads. Idempotent; returns the live transport (or
    None when the store/rpc path must carry payloads — no collective
    group, or ``PADDLE_TPU_PP_TRANSPORT=host``)."""
    global _fleet_transport
    mode = transport_mode()
    if mode == "host":
        return None
    with _fleet_transport_lock:
        if _fleet_transport is not None:
            return _fleet_transport
        try:
            from .. import collective as _coll

            group = _coll._default_group
        except Exception:
            return None
        if group is None:
            return None
        pg = getattr(group, "process_group", None)
        if pg is None or not (hasattr(pg, "send") and hasattr(pg, "recv")):
            return None
        size = pg.size() if callable(getattr(pg, "size", None)) else 0
        if size < 2:
            return None  # single-process group: nothing to ship p2p
        if mode == "auto" and pg.__class__.__name__ != "ProcessGroupXLA":
            # auto engages device payloads only where p2p compiles to
            # device collectives; PADDLE_TPU_PP_TRANSPORT=device opts
            # store-backed groups in explicitly (parity tests)
            return None
        rank = pg.rank() if callable(getattr(pg, "rank", None)) \
            else getattr(pg, "rank", 0)
        _fleet_transport = FleetPayloadTransport(pg, rank)
        return _fleet_transport
