"""Observability: tracing, metrics, workload heat, time series, compiles.

Five pieces, all stdlib + numpy only (the compile counter imports JAX when
it is installed):

  * :mod:`repro.obs.trace` — opt-in per-query spans propagated on a
    W3C-style ``traceparent``, recorded in the process-local
    :data:`TRACER`, shipped across the worker RPC in reply headers, and
    assembled into one span tree at the gateway (which also keeps the
    bounded :class:`SlowQueryLog` behind ``GET /debug/slow``), with
    :class:`TraceSampler` head/tail sampling for production rates;
  * :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket
    :class:`LatencyHistogram`\\ s behind a :class:`MetricsRegistry` with
    Prometheus/OpenMetrics text exposition (``GET /metrics``, histogram
    bucket exemplars).  The histogram is also ``QueryStats``' latency
    store, replacing the unbounded sample list;
  * :mod:`repro.obs.heat` — per-worker workload heat (:class:`HeatSketch`:
    count-min keyword counts, space-saving top-K, doc-range histogram),
    merged across workers on the stats wire like the latency histogram
    and consumed by ``ClusterService.load_report()`` / ``GET /debug/heat``;
  * :mod:`repro.obs.timeseries` — :class:`TimeSeriesStore`, a bounded
    ring-buffer history of every registry metric sampled on a daemon
    thread (``GET /debug/timeseries``);
  * :mod:`repro.obs.compiles` — :data:`COMPILES`, the process's count of
    XLA compiles and compile seconds, from ``jax.monitoring`` events.
"""
from .compiles import COMPILES
from .heat import CountMinSketch, HeatShapeError, HeatSketch, SpaceSaving
from .metrics import (
    DEFAULT_BUCKETS_MS,
    BucketMismatchError,
    Counter,
    Gauge,
    Histogram,
    LatencyHistogram,
    MetricsRegistry,
    sanitize_metric_name,
)
from .timeseries import TimeSeriesStore
from .trace import (
    NULL_SPAN,
    TRACER,
    SlowQueryLog,
    Span,
    TraceContext,
    Tracer,
    TraceSampler,
    emit_phases,
    make_traceparent,
    new_span_id,
    new_trace_id,
    parse_traceparent,
)

__all__ = [
    "BucketMismatchError",
    "COMPILES",
    "CountMinSketch",
    "DEFAULT_BUCKETS_MS",
    "Counter",
    "Gauge",
    "HeatShapeError",
    "HeatSketch",
    "Histogram",
    "LatencyHistogram",
    "MetricsRegistry",
    "NULL_SPAN",
    "SlowQueryLog",
    "SpaceSaving",
    "Span",
    "TRACER",
    "TimeSeriesStore",
    "TraceContext",
    "TraceSampler",
    "Tracer",
    "emit_phases",
    "make_traceparent",
    "new_span_id",
    "new_trace_id",
    "parse_traceparent",
    "sanitize_metric_name",
]
