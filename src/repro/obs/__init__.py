"""repro.obs — solver telemetry: spans, metrics, solve reports.

The measurement layer the rest of the stack reports through, off by
default and zero-overhead while off (spans also record while a profiler
is capturing, so a profile of any run carries the program's names):

    import repro.obs as obs

    obs.enable()                      # spans record, metrics count
    p = repro.plan(A, method="pipecg")
    res = p.solve(b)                  # synchronized + timed under a span
    print(p.last_report.summary())    # curve, launches/iter, time, ...
    print(obs.format_metrics())       # plan cache, solves, iterations
    obs.dump_spans("spans.json"); obs.dump_jsonl("metrics.jsonl")

* ``trace``   — host-side span tree; each span also opens a
  ``jax.profiler.TraceAnnotation`` so the same names appear in XLA
  profiles. ``trace_scope`` (``jax.named_scope``, always on) tags
  *traced* code with zero added primitives — the solve loop's jaxpr is
  byte-identical with observability on or off.
* ``metrics`` — process-local counters/gauges/histograms with JSON-lines
  and human-readable sinks; strict no-ops while disabled.
* ``report``  — :class:`SolveReport` built from ``SolveResult`` + plan
  metadata, and :func:`convergence_curve`, the one NaN-trim
  implementation (batched histories return ragged per-row curves).
"""
from __future__ import annotations

from .metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    counter,
    dump_jsonl,
    format_metrics,
    gauge,
    histogram,
    metric_names,
    reset_metrics,
    snapshot,
)
from .report import (  # noqa: F401
    SolveReport,
    comparable_env,
    convergence_curve,
    env_fingerprint,
    iterations_from_history,
    plan_launches_per_iteration,
    solve_report,
)
from .trace import (  # noqa: F401
    Span,
    clear_spans,
    disable,
    dump_spans,
    enable,
    enabled,
    span,
    span_tree,
    spans_to_dicts,
    trace_scope,
    tracing,
)

__all__ = [
    # switch
    "enable", "disable", "enabled", "tracing",
    # spans
    "span", "trace_scope", "Span", "span_tree", "clear_spans",
    "spans_to_dicts", "dump_spans",
    # metrics
    "counter", "gauge", "histogram", "metric_names", "snapshot",
    "reset_metrics", "format_metrics", "dump_jsonl",
    "Counter", "Gauge", "Histogram",
    # report
    "SolveReport", "solve_report", "convergence_curve",
    "iterations_from_history", "env_fingerprint", "comparable_env",
    "plan_launches_per_iteration",
]
