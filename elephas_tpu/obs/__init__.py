"""Unified observability layer: span tracing + metrics registry.

Everything here is HOST-side only and allocation-light — no device
syncs, no per-sample storage — so instrumentation can stay on inside
the pipelined serving scheduler's overlap window (the bench guardrail
in ``scripts/lm_bench.py`` pins the traced/untraced gap under 2%).

Two process-global defaults back cross-cutting instrumentation (the
training engines, parameter-server clients, and compile counters all
record through them):

- ``default_tracer()`` — starts as the shared disabled ``NULL_TRACER``
  (every span is a no-op); ``enable_tracing()`` swaps in a live ring.
- ``default_registry()`` — always live (counters/gauges/histograms are
  a few ints each); scrape with ``default_registry().expose_text()``.
- ``default_flight_recorder()`` — bounded anomaly ring (retrace storms,
  heartbeat flaps, rejections, WAL restores); live by default since
  anomalies are rare by construction, swappable for tests via
  ``set_default_flight_recorder()``.

The serving ``InferenceEngine`` instead takes an explicit ``tracer=``
(its clock is injectable and the tracer must share it); it falls back
to the global default when none is passed.

Distributed trace context rides along from ``obs.trace``:
``new_context()``/``activate()``/``current_context()`` are re-exported
here so call sites can root and adopt traces without a second import.
"""

from __future__ import annotations

import time
from typing import Optional

from elephas_tpu.obs.registry import (  # noqa: F401
    Counter,
    Family,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_latency_buckets,
)
from elephas_tpu.obs.trace import (  # noqa: F401
    NULL_TRACER,
    SpanEvent,
    TraceContext,
    Tracer,
    activate,
    current_context,
    new_context,
    new_span_id,
)
from elephas_tpu.obs.flight import (  # noqa: F401
    KINDS,
    NULL_FLIGHT_RECORDER,
    FlightEvent,
    FlightRecorder,
)
from elephas_tpu.obs.health import (  # noqa: F401
    StalenessLedger,
    record_staleness,
    record_unit_dynamics,
    tree_norm,
)
from elephas_tpu.obs.alerts import (  # noqa: F401
    RULE_NAMES,
    AlertEngine,
    AlertRule,
    default_rules,
)
from elephas_tpu.obs.history import (  # noqa: F401
    DEFAULT_SAMPLE_PREFIXES,
    HistoryRing,
    HistorySampler,
)
from elephas_tpu.obs.devprof import (  # noqa: F401
    DeviceProfiler,
    device_memory_snapshot,
    device_seconds_by_part,
    record_device_memory,
)
from elephas_tpu.obs.programs import ProgramReport  # noqa: F401
from elephas_tpu.obs.fleet import (  # noqa: F401
    FleetAggregator,
    ProcessRegistry,
    parse_prometheus_text,
)
from elephas_tpu.obs.load import (  # noqa: F401
    LoadScore,
    LoadSnapshot,
    LoadTracker,
    instant_load,
)
from elephas_tpu.obs.slo import (  # noqa: F401
    GoodputLedger,
    SLOObjective,
    default_objectives,
)
from elephas_tpu.obs.canary import (  # noqa: F401
    CanaryDriver,
    PSCanary,
)
from elephas_tpu.obs.tenancy import (  # noqa: F401
    DEFAULT_TENANT,
    CostLedger,
    merge_tenant_docs,
    tenant_rules,
)
from elephas_tpu.obs.store import (  # noqa: F401
    RECORD_KINDS,
    TelemetryStore,
    iter_records,
    read_store,
    store_dirs,
)
from elephas_tpu.obs.incident import (  # noqa: F401
    IncidentBuilder,
    render_markdown,
)

_tracer: Tracer = NULL_TRACER
_registry = MetricsRegistry()
_flight = FlightRecorder()


def default_tracer() -> Tracer:
    """The process-global tracer (disabled until ``enable_tracing``)."""
    return _tracer


def set_default_tracer(tracer: Optional[Tracer]) -> Tracer:
    """Install ``tracer`` as the global default (None → disabled)."""
    global _tracer
    _tracer = tracer if tracer is not None else NULL_TRACER
    return _tracer


def enable_tracing(capacity: int = 65536, clock=time.monotonic,
                   annotate_device: bool = True) -> Tracer:
    """Swap a live ring in as the global tracer and return it. JAX's
    compile events land on it as ``compile/*`` spans from here on
    (``utils.compiler.install_compile_spans``)."""
    from elephas_tpu.utils.compiler import install_compile_spans

    install_compile_spans()
    return set_default_tracer(
        Tracer(capacity=capacity, clock=clock,
               annotate_device=annotate_device)
    )


def disable_tracing() -> None:
    """Back to the shared no-op tracer (recorded events are dropped)."""
    set_default_tracer(None)


def default_registry() -> MetricsRegistry:
    """The process-global metrics registry (always live)."""
    return _registry


def default_flight_recorder() -> FlightRecorder:
    """The process-global anomaly ring (live by default)."""
    return _flight


def set_default_flight_recorder(
        recorder: Optional[FlightRecorder]) -> FlightRecorder:
    """Install ``recorder`` as the global default (None → disabled)."""
    global _flight
    _flight = recorder if recorder is not None else NULL_FLIGHT_RECORDER
    return _flight
