"""Device management (reference: python/paddle/device/).

TPU-native: devices are jax devices; "gpu"-spelled APIs alias onto the
accelerator so reference-style scripts run unchanged."""
from __future__ import annotations

import contextlib
import os

import jax

__all__ = ["set_device", "get_device", "get_all_devices", "device_count",
           "is_compiled_with_cuda", "is_compiled_with_xpu",
           "is_compiled_with_tpu", "synchronize", "cuda", "get_available_device"]

_current = None


@contextlib.contextmanager
def cpu_children():
    """Processes started inside this block run jax on the CPU. A chip
    belongs to one process at a time, so orchestration children (spawn
    workers, DataLoader workers) must not try to claim it. Children read
    ``JAX_PLATFORMS`` when they import jax during bootstrap, so it is set
    in the inherited environment and restored afterwards."""
    prev = os.environ.get("JAX_PLATFORMS")
    os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        yield
    finally:
        if prev is None:
            os.environ.pop("JAX_PLATFORMS", None)
        else:
            os.environ["JAX_PLATFORMS"] = prev


def _accel_devices():
    # a backend that fails to initialise is an error, never "cpu"
    return jax.devices()


def set_device(device):
    global _current
    _current = device
    return device


def get_device():
    if _current is not None:
        return _current
    devs = _accel_devices()
    if devs and devs[0].platform == "tpu":
        return "tpu:0"
    if devs and devs[0].platform == "gpu":
        return "gpu:0"
    return "cpu"


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in _accel_devices()]


def get_all_devices():
    return get_available_device()


def device_count():
    return len(_accel_devices())


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_tpu() -> bool:
    return any(d.platform == "tpu" for d in _accel_devices())


def synchronize(device=None):
    # jax dispatch is async; block on a trivial transfer
    import jax.numpy as jnp

    jnp.zeros(()).block_until_ready()


class _CudaNamespace:
    """paddle.device.cuda parity shims (map onto the accelerator)."""

    @staticmethod
    def device_count():
        return device_count()

    @staticmethod
    def synchronize(device=None):
        synchronize(device)

    @staticmethod
    def max_memory_allocated(device=None):
        devs = _accel_devices()
        try:
            stats = devs[0].memory_stats()
            return stats.get("peak_bytes_in_use", 0)
        except Exception:
            # CPU / backends without PJRT memory_stats: native counters
            # (native/alloc_stats.cc, analog of phi/core/memory/stats.h)
            from ..core import native as _native

            return _native.stats_peak(0)

    @staticmethod
    def memory_allocated(device=None):
        devs = _accel_devices()
        try:
            stats = devs[0].memory_stats()
            return stats.get("bytes_in_use", 0)
        except Exception:
            from ..core import native as _native

            return _native.stats_allocated(0)

    @staticmethod
    def empty_cache():
        pass


cuda = _CudaNamespace()


def get_cudnn_version():
    return None  # TPU build: no cuDNN


def is_compiled_with_cinn() -> bool:
    return False  # XLA plays CINN's role (SURVEY §2.4.9)


# ---- round-4 parity surface (reference: python/paddle/device/__init__.py)
class XPUPlace:
    def __init__(self, dev_id=0):
        self.dev_id = dev_id

    def __repr__(self):
        return f"Place(xpu:{self.dev_id})"


class IPUPlace:
    def __init__(self, dev_id=0):
        self.dev_id = dev_id

    def __repr__(self):
        return f"Place(ipu:{self.dev_id})"


class Stream:
    """reference: device/__init__.py Stream. XLA on TPU schedules one
    compute stream per core; this object carries the API surface
    (synchronize waits on all dispatched work)."""

    def __init__(self, device=None, priority=2):
        self.device = device
        self.priority = priority

    def synchronize(self):
        import jax

        (jax.device_put(0.0) + 0).block_until_ready()

    def wait_event(self, event):
        event.synchronize()

    def wait_stream(self, stream):
        stream.synchronize()

    def record_event(self, event=None):
        ev = event or Event()
        ev.record(self)
        return ev

    def query(self):
        return True


class Event:
    """reference: device/__init__.py Event."""

    def __init__(self, device=None, enable_timing=False, blocking=False,
                 interprocess=False):
        self.device = device
        self._stream = None

    def record(self, stream=None):
        self._stream = stream or current_stream()

    def query(self):
        return True

    def synchronize(self):
        if self._stream is not None:
            self._stream.synchronize()


_default_stream = Stream()
_stream_stack = []


def current_stream(device=None):
    return _stream_stack[-1] if _stream_stack else _default_stream


def set_stream(stream):
    prev = current_stream()
    _stream_stack.append(stream)
    return prev


class stream_guard:
    """reference: device/__init__.py stream_guard."""

    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        set_stream(self.stream)
        return self.stream

    def __exit__(self, *exc):
        _stream_stack.pop()
        return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_ipu():
    return False


def is_compiled_with_custom_device(device_type=None):
    return False


def is_compiled_with_distribute():
    return True


def get_all_device_type():
    import jax

    return sorted({d.platform for d in jax.devices()})


def get_all_custom_device_type():
    return []


def get_available_custom_device():
    return []


__all__ += ["XPUPlace", "IPUPlace", "Stream", "Event", "current_stream",
            "set_stream", "stream_guard", "is_compiled_with_rocm",
            "is_compiled_with_ipu", "is_compiled_with_custom_device",
            "is_compiled_with_distribute", "get_all_device_type",
            "get_all_custom_device_type", "get_available_custom_device"]
