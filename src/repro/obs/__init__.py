"""Unified telemetry layer: metrics, trace spans, path-selection counters,
exporters and SLO reporting — the substrate every serving PR reports
through (ROADMAP item 2).

Zero-dependency inside the repo (imports nothing from ``repro.*``), so
``core``/``kernels``/``robust``/``analytics``/``index``/``launch`` can all
instrument themselves without cycles.

* :mod:`repro.obs.metrics` — process-global registry of counters, gauges
  and streaming log-bucket histograms; true no-ops when disabled.
* :mod:`repro.obs.spans`   — nested ``span()`` context manager forwarding
  to ``jax.profiler.TraceAnnotation``; finished spans kept in memory
  (``finished_spans()``), JAX's trace/lower/compile phases as child spans.
* :mod:`repro.obs.timing`  — ``time_compiled``/``timed_op`` (the one timer
  the CLIs and benchmarks share; compile_s separated from steady-state)
  and ``track_shapes`` jit-recompile tracking.
* :mod:`repro.obs.export`  — JSONL event log + snapshot (+ Prometheus
  text) behind the CLIs' ``--metrics-dir``.
* :mod:`repro.obs.report`  — snapshot → per-op SLO table + span tree
  (rendered by ``python -m repro.launch.obs``).
* :mod:`repro.obs.prof`    — device-level profiling: HLO cost-model stats,
  roofline-utilization and device-memory gauges (``prof.*``), opt-in
  ``jax.profiler`` trace capture (``--profile-dir``), and the post-SPMD
  ``analyze_hlo`` (absorbed from ``launch.hlo_analysis``).
* :mod:`repro.obs.history` — append-only per-commit bench history
  (``results/bench/history.jsonl``) + noise-aware regression detection
  behind ``python -m repro.launch.regress``.
* :mod:`repro.obs.html`    — zero-dependency static HTML dashboard
  (``python -m repro.launch.obs --html``).

Counter semantics under jit: Python-side increments fire at *trace* time,
so path-selection counters (``core.build``, ``analytics.path``, …) count
traced decisions, not per-call volume — exactly what "which path actually
executed / compiled" needs. Per-call volume lives in the ``serve.*``
family recorded by the CLIs around jitted calls.
"""
from .export import (configure, emit_event, metrics_dir, prometheus_text,
                     read_events, read_snapshot, snapshot_dict,
                     write_snapshot)
from .history import (append_history, detect_regression, read_history,
                      regress_report)
from .html import render_html
from .metrics import (REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
                      counter, disable, disabled, enable, enabled, gauge,
                      histogram, parse_key)
from .prof import (analyze_hlo, hw_model, live_memory_stats, profile_op,
                   profiled_op, record_memory_gauges, start_trace,
                   stop_trace, trace)
from .spans import (clear_spans, current_span, event,
                    finished_spans, span)
from .timing import (Stopwatch, reset_shape_tracking, time_compiled,
                     timed_op, track_shapes)

__all__ = [
    "REGISTRY", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "parse_key",
    "enable", "disable", "disabled", "enabled",
    "span", "current_span", "event", "finished_spans", "clear_spans",
    "Stopwatch", "time_compiled", "timed_op", "track_shapes",
    "reset_shape_tracking",
    "configure", "metrics_dir", "emit_event", "write_snapshot",
    "snapshot_dict", "read_snapshot", "read_events", "prometheus_text",
    "profile_op", "profiled_op", "record_memory_gauges",
    "live_memory_stats", "hw_model", "analyze_hlo",
    "start_trace", "stop_trace", "trace",
    "append_history", "read_history", "detect_regression",
    "regress_report", "render_html",
]
