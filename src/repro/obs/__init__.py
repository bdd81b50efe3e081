"""Observability layer: BSP telemetry, span tracing, serving metrics.

Three planes, one package (DESIGN.md §10):

  * ``obs.telemetry`` — on-device per-iteration buffers riding the
    enactor while_loops (frontier size, tier, direction, mixed step,
    overflow, exchange bytes), read-only by construction.
  * ``obs.tracing`` — host-side spans, always on the ``jax.profiler``
    clock, recorded under ``capture()`` and exportable as Chrome
    trace-event JSON (Perfetto), with ``block_until_ready`` fencing.
  * ``obs.metrics`` — streaming log-bucket histograms + counters/gauges
    with Prometheus text exposition for the serving driver.
  * ``obs.log`` — the one logger (``REPRO_LOG`` level knob) the
    scattered print/warnings diagnostics now route through.
"""
from repro.obs import log, metrics, telemetry, tracing
from repro.obs.log import get_logger
from repro.obs.metrics import (Histogram, Metrics, latency_summary,
                               quantile)
from repro.obs.telemetry import (TelemetryBuffer, TelemetryTrace,
                                 distributed_trace, trim)
from repro.obs.tracing import (SpanRegistry, capture, export_chrome_trace,
                               registry, reset, span)

__all__ = [
    "log", "metrics", "telemetry", "tracing",
    "get_logger",
    "Histogram", "Metrics", "latency_summary", "quantile",
    "TelemetryBuffer", "TelemetryTrace", "distributed_trace", "trim",
    "SpanRegistry", "capture", "export_chrome_trace", "registry", "reset",
    "span",
]
