"""Device-native pipeline parallelism.

This package moves pipeline-stage boundary tensors between devices with
compiled collectives instead of the host store/rpc pickle path:

* :mod:`transport` — the p2p layer: ``ring_shift`` (a
  ``jax.lax.ppermute`` ring step inside ``shard_map``),
  the ``PADDLE_TPU_PP_TRANSPORT`` mode knob, and
  :class:`~paddle_tpu.distributed.pipeline.transport.FleetPayloadTransport`
  which carries FleetExecutor message payloads over ProcessGroup device
  p2p while DATA_IS_READY/STOP control stays on the rpc message bus.
* :mod:`schedule` — :class:`CompiledPipeline`: the whole 1F1B
  micro-batch schedule as ONE jit (fixed shapes, zero steady-state
  recompiles, trace-counter-asserted), plus the Engine bridge
  :class:`CompiledStagedTrainStep`.
* :mod:`overlap` — per-layer-bucket gradient synchronisation for
  comm/compute overlap (``PADDLE_TPU_PP_BUCKET_MB``): in-jit
  ``bucket_taps`` whose VJP issues one ``psum`` per bucket during the
  backward pass, and eager ``bucketed_allreduce`` issued per-bucket
  instead of one trailing barrier.
"""
from .transport import (  # noqa: F401
    FleetPayloadTransport,
    ensure_fleet_transport,
    get_fleet_transport,
    is_payload_descriptor,
    overlap_bucket_bytes,
    ring_shift,
    set_fleet_transport,
    transport_mode,
)
from .overlap import bucket_taps, bucketed_allreduce, make_buckets  # noqa: F401
from .schedule import CompiledPipeline, CompiledStagedTrainStep  # noqa: F401

__all__ = [
    "FleetPayloadTransport", "ensure_fleet_transport",
    "get_fleet_transport", "is_payload_descriptor",
    "overlap_bucket_bytes", "ring_shift",
    "set_fleet_transport", "transport_mode",
    "bucket_taps", "bucketed_allreduce", "make_buckets",
    "CompiledPipeline", "CompiledStagedTrainStep",
]
