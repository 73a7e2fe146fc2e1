"""repro.serving.observability — tracing + metrics for the serving stack.

Three zero-dependency pieces, threaded through the whole engine:

  * ``tracing`` — per-request and per-batch ``Span`` trees recorded by a
    bounded, clock-injectable ``Tracer``; exported as Chrome-trace/
    Perfetto JSON (``--trace-out``) where the in-flight ring's
    dispatch/retire overlap is *visible* (and ``pipeline_overlaps``
    makes it assertable); also the process's garbage-collection pauses
    (``gc_pause_s``, ``gc`` spans);
  * ``registry`` — labeled Counter/Gauge/Histogram instruments with
    Prometheus text-format and JSON exposition
    (``FoldClient.metrics_text()`` / ``metrics_json()``), the per-replica
    scrape surface a fleet router federates;
  * ``profiler`` + ``httpd`` — the ``jax.profiler`` annotation bridge
    the Tracer writes through (``--jax-profile``) and the optional stdlib
    scrape endpoint (``--metrics-port``).
"""
from repro.serving.observability.httpd import (BackgroundHTTPServer,
                                               MetricsServer, QuietHandler,
                                               parse_hostport)
from repro.serving.observability.profiler import jax_profile
from repro.serving.observability.registry import (FRACTION_BUCKETS,
                                                  LATENCY_BUCKETS,
                                                  PROMETHEUS_CONTENT_TYPE,
                                                  Counter, Gauge, Histogram,
                                                  MetricsRegistry)
from repro.serving.observability.tracing import (PROC_ENGINE, PROC_REQUESTS,
                                                 Span, Tracer, gc_pause_s,
                                                 pipeline_overlaps,
                                                 span_tree,
                                                 validate_chrome_trace)

__all__ = [
    "Span", "Tracer", "span_tree", "gc_pause_s", "pipeline_overlaps",
    "validate_chrome_trace", "PROC_REQUESTS", "PROC_ENGINE",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "LATENCY_BUCKETS", "FRACTION_BUCKETS", "PROMETHEUS_CONTENT_TYPE",
    "MetricsServer", "BackgroundHTTPServer", "QuietHandler",
    "parse_hostport", "jax_profile",
]
