"""paddle_tpu.observability — production telemetry for the whole stack.

Three stdlib-only parts (no jax, no third-party deps):

* :mod:`~paddle_tpu.observability.metrics` — a process-wide thread-safe
  ``MetricsRegistry`` of labeled Counter/Gauge/Histogram series
  (log2-spaced latency buckets), with ``snapshot()`` plus Prometheus-text
  and one-line-JSON export.
* :mod:`~paddle_tpu.observability.exporter` — an opt-in background
  ``http.server`` thread serving ``/metrics`` and ``/healthz``
  (``PADDLE_TPU_METRICS_PORT`` or ``MetricsExporter(port=...)``), with
  deterministic shutdown.
* :mod:`~paddle_tpu.observability.trace` — ``span()`` context-manager/
  decorator that enters a ``jax.profiler.TraceAnnotation`` (imported
  lazily), so the program's phases are events of the same xplane as the
  device lines; plus the one vocabulary of names (``SCOPES``, ``LOOPS``,
  ``SPANS``) the device work and the host phases are labelled with.

Two request-scoped modules ride on top (lazy-exported below — they load
on first attribute access, keeping ``import paddle_tpu.observability``
as light as before):

* :mod:`~paddle_tpu.observability.flightrecorder` — ``FlightRecorder``
  (the bounded engine-event ring with JSONL/chrome-trace dumps and
  anomaly auto-dump) and ``RequestTrace`` (per-request lifecycle
  timelines behind ``Request.timeline()``).
* :mod:`~paddle_tpu.observability.slo` — ``SLOTracker``/``SLObjective``:
  sliding-window per-class SLO attainment and burn-rate gauges.
* :mod:`~paddle_tpu.observability.watchdog` — ``DeadlockWatchdog``: a
  daemon thread that samples every thread's stack via
  ``sys._current_frames()`` when a progress probe goes stale, dumps
  them through the flight recorder (``auto_dump("stall")``) and bumps
  ``serving_watchdog_stalls_total``.

The serving engine, the decode/train compile caches and ``TrainStep`` are
instrumented out of the box; see the README "Observability" and
"Request-lifecycle observability" sections for the metric name table and
event schema.
"""
import importlib

from paddle_tpu.observability.compilecache import CompileCacheMonitor
from paddle_tpu.observability.exporter import (
    MetricsExporter, start_default_exporter, stop_default_exporter,
)
from paddle_tpu.observability.metrics import (
    Counter, DEFAULT_LATENCY_BUCKETS, Gauge, Histogram, MetricsRegistry,
    get_registry,
)
from paddle_tpu.observability.trace import span

# name -> defining module, resolved on first access (PEP 562)
_LAZY = {
    "DeadlockWatchdog": "paddle_tpu.observability.watchdog",
    "FlightRecorder": "paddle_tpu.observability.flightrecorder",
    "RequestTrace": "paddle_tpu.observability.flightrecorder",
    "SLObjective": "paddle_tpu.observability.slo",
    "SLOTracker": "paddle_tpu.observability.slo",
    "DEFAULT_OBJECTIVES": "paddle_tpu.observability.slo",
}

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "get_registry",
    "DEFAULT_LATENCY_BUCKETS", "MetricsExporter", "start_default_exporter",
    "stop_default_exporter", "span", "CompileCacheMonitor",
] + sorted(_LAZY)


def __getattr__(name):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(
            f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(mod), name)
    globals()[name] = value   # cache: next access skips __getattr__
    return value


def __dir__():
    return sorted(set(globals()) | set(__all__))
