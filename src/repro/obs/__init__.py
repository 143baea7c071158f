"""Observability: tracing + metrics + measured latency (docs/observability.md).

Numpy, the stdlib and ``jax.profiler`` only, and at the bottom of the
layer order: ``core``, ``distributed``, ``serve`` and ``train`` all
import ``obs``, never the reverse. The disabled path records nothing —
pass ``tracer=None`` anywhere and :func:`as_tracer` substitutes the
no-op :data:`NULL` tracer, whose spans are only profiler annotations
(``repro.obs.profile`` reads them back from a profile).
"""
from repro.obs.latency import EmpiricalLatencyModel
from repro.obs.metrics import (
    METRIC_NAMES,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    load_jsonl,
)
from repro.obs.quantiles import WindowedQuantile, windowed_quantile
from repro.obs.trace import (
    NULL,
    SPAN_NAMES,
    NullTracer,
    Tracer,
    as_tracer,
    load_trace,
    span_tree,
)

__all__ = [
    "EmpiricalLatencyModel",
    "METRIC_NAMES",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "load_jsonl",
    "WindowedQuantile",
    "windowed_quantile",
    "NULL",
    "SPAN_NAMES",
    "NullTracer",
    "Tracer",
    "as_tracer",
    "load_trace",
    "span_tree",
]
