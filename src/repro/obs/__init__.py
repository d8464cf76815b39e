"""Unified runtime observability (docs/observability.md).

One `MetricsRegistry` threaded through the serving engine, plan cache,
sampled loader, trainer, sharded executors and benchmarks; a `SpanTracer`
for nested wall-clock spans with honest-under-async-dispatch close
semantics, each also a profiler annotation on the device trace's clock;
the process tracer (`process_tracer`) that the planner and the compile
listener (`repro.obs.compile`) record in; JSON / Prometheus exporters that
render the same registry; a Chrome/Perfetto trace exporter over the
tracer's records; the wall-clock timing harness (`measure`); and the
persisted perf-baseline layer (`repro.obs.baseline`) behind
`tools/bench_compare.py`'s CI regression gate.
"""
from repro.obs.baseline import (BASELINE_SCHEMA, append_history,
                                compare_rows, load_baseline, make_baseline,
                                row_tolerance, save_baseline,
                                validate_baseline)
from repro.obs.chrome_trace import chrome_trace_doc, write_chrome_trace
from repro.obs.compile import install_compile_listener
from repro.obs.context import run_context
from repro.obs.export import (lint_prometheus, registry_to_json,
                              to_prometheus_text, unescape_label_value,
                              write_metrics)
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               exponential_bounds, pow2_bounds)
from repro.obs.profile import Measurement, measure
from repro.obs.trace import Span, SpanTracer, process_tracer

__all__ = [
    "BASELINE_SCHEMA",
    "Counter",
    "Gauge",
    "Histogram",
    "Measurement",
    "MetricsRegistry",
    "Span",
    "SpanTracer",
    "append_history",
    "chrome_trace_doc",
    "compare_rows",
    "exponential_bounds",
    "install_compile_listener",
    "lint_prometheus",
    "load_baseline",
    "make_baseline",
    "measure",
    "pow2_bounds",
    "process_tracer",
    "registry_to_json",
    "row_tolerance",
    "run_context",
    "save_baseline",
    "to_prometheus_text",
    "unescape_label_value",
    "validate_baseline",
    "write_chrome_trace",
    "write_metrics",
]
