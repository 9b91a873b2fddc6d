"""paddle_tpu.observability — process-wide telemetry runtime.

The framework's hot paths (Engine.fit, the fused decode, MoE dispatch,
jit caches, the FleetExecutor MessageBus) are instrumented against ONE
thread-safe metrics registry addressable by dotted names, with three
exporters: JSON snapshot, Prometheus text exposition, and counter
annotations merged into profiler chrome traces. Per-compilation XLA
``cost_analysis()`` accounting (FLOPs / bytes) makes MFU derivable from
telemetry instead of hand-computed per bench.

Everything is zero-cost when disabled: instrumented call sites check
``observability.enabled()`` (one module-global read) before any dict
work. Enable with ``PADDLE_TPU_TELEMETRY=1`` in the environment or
``observability.enable()`` at runtime.

Quickstart::

    import paddle_tpu as pt

    pt.observability.enable()
    ...  # train / generate
    snap = pt.observability.snapshot()
    pt.observability.dump_json("/tmp/telemetry.json")
    print(pt.observability.prometheus_text())

Reference analog: fluid/platform/profiler/ (host tracer) +
phi/core/memory/stats.h (allocator stat slots); arXiv:2401.16677 (T3)
motivates the visibility layer — compute/collective overlap cannot be
optimized before it can be measured.
"""
from __future__ import annotations

from .registry import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Stopwatch,
    disable,
    enable,
    enabled,
    registry,
    stopwatch,
)
from .exporters import (  # noqa: F401
    dump_json,
    merge_counters_into_trace,
    prometheus_text,
    snapshot,
)
from . import memory  # noqa: F401
from .memory import sample_device_memory  # noqa: F401
from . import tracing  # noqa: F401
from .tracing import (  # noqa: F401
    Span,
    Tracer,
    activate_context,
    current_context,
    export_chrome_trace,
    merge_chrome_traces,
    span,
    tracer,
)
from . import flight_recorder  # noqa: F401
from .flight_recorder import dump_debug_bundle, install_excepthook  # noqa: F401
from . import health  # noqa: F401
from .xla_cost import (  # noqa: F401
    compiled_costs,
    derive_mfu,
    record_cost_analysis,
    record_memory_analysis,
)
from . import metrics_schema  # noqa: F401
from .metrics_schema import METRICS, MetricSpec  # noqa: F401
from . import windows  # noqa: F401
from .windows import Ewma, ManualClock, RollingCounter  # noqa: F401
from .windows import RollingHistogram, Windows  # noqa: F401
from . import slo  # noqa: F401
from .slo import Objective, SLOEngine  # noqa: F401
from . import request_log  # noqa: F401
from .request_log import RequestLog, RequestTimeline  # noqa: F401
from . import profiler  # noqa: F401
from .profiler import (  # noqa: F401
    StepRecord,
    begin_step,
    disable_profiling,
    enable_profiling,
    profiling_enabled,
)
from . import compile_ledger  # noqa: F401
from . import scopes  # noqa: F401
from .scopes import op_phases  # noqa: F401

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Stopwatch",
    "enable", "disable", "enabled", "registry", "stopwatch",
    "snapshot", "dump_json", "prometheus_text",
    "merge_counters_into_trace", "sample_device_memory",
    "record_cost_analysis", "compiled_costs", "derive_mfu",
    "METRICS", "MetricSpec", "metrics_schema",
    "Span", "Tracer", "tracer", "span", "tracing",
    "current_context", "activate_context", "export_chrome_trace",
    "merge_chrome_traces",
    "flight_recorder", "dump_debug_bundle", "install_excepthook",
    "health",
    "windows", "ManualClock", "RollingCounter", "RollingHistogram",
    "Ewma", "Windows",
    "slo", "Objective", "SLOEngine",
    "request_log", "RequestLog", "RequestTimeline",
    "memory", "record_memory_analysis",
    "profiler", "StepRecord", "begin_step", "profiling_enabled",
    "enable_profiling", "disable_profiling", "compile_ledger",
    "scopes", "op_phases",
]
