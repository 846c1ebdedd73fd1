"""Run telemetry: metrics registry, recompile/HBM tracking, JSONL events.

The observability spine the perf ROADMAP items report against.
Per-op microbenchmarks have misled in both directions on this codebase
— only in-situ measurement of the real boosting loop is trustworthy —
so every layer here instruments the *actual* hot path and is a strict
no-op when disabled:

- :class:`MetricsRegistry` — label-keyed, thread-safe counters / gauges /
  histograms (`registry` is the process-global instance).
- :mod:`~lightgbm_tpu.obs.jit_tracker` — registered jitted entry points
  (grow / fused-iteration / predict) expose XLA cache-size deltas, so a
  shape-change recompile shows up as a counted event, not a mystery
  stall.
- :func:`device_memory_stats` — HBM gauges via ``device.memory_stats()``
  with explicit ``None`` on backends that lack it (CPU).
- :class:`TelemetryRecorder` — one JSONL event per boosting iteration
  (phase wall times, recompiles, HBM, tree stats, eval results),
  activated by ``lightgbm_tpu.callback.telemetry(path)`` or the
  ``LIGHTGBM_TPU_TELEMETRY=<path>`` env var.
- :mod:`~lightgbm_tpu.obs.export` — the fleet metrics plane: the
  registry rendered as OpenMetrics text on a jax-free stdlib
  ``/metrics`` endpoint (``metrics_port`` / ``--metrics-port``,
  port + rank per process) and the strict parser the fleet scrapers
  and tests read it back with.
- :mod:`~lightgbm_tpu.obs.cost` — in-band XLA cost attribution: each
  registered entry point's first compile per signature records
  flops / bytes / compile wall / cost-model-optimal ms as
  ``{"event": "compile"}`` telemetry (docs/ROOFLINE.md made live).
- :mod:`~lightgbm_tpu.obs.trace` — the distributed tracing plane:
  jax-free spans (``{"event": "span"}``) across the whole
  train -> publish -> serve lifecycle, clock-skew-corrected and
  merged into Perfetto-loadable Chrome trace JSON plus named
  critical paths by ``python -m lightgbm_tpu trace <dir>``. The
  program's own sections (``utils.timer.timed``) are real spans there.
- :mod:`~lightgbm_tpu.obs.scopes` / :mod:`~lightgbm_tpu.obs.xplane` —
  the device side: the round's ops named by layer
  (``jax.named_scope`` from one declared list), :func:`op_scopes` — the
  op -> scope table read back from an entry's executable — and the
  by-scope reading of a device trace (``trace <dir> --xplane``).

See docs/OBSERVABILITY.md for the event schema and workflow.
"""

from .cost import (CostTracked, compile_events_snapshot, device_peaks,
                   drain_compile_events, roofline_optimal_ms)
from .export import (MetricsHTTPServer, ensure_metrics_server,
                     parse_openmetrics, render_openmetrics)
from .jit_tracker import (RecompileWatcher, jit_cache_sizes,
                          jit_declarations, register_jit,
                          total_recompiles)
from .memory import device_memory_stats
from .recorder import (ITERATION_EVENT_KEYS, TelemetryRecorder,
                       UnknownEventError, merge_fleet_summaries,
                       render_fleet_table, render_stats_table,
                       summarize_directory, summarize_events)
from .schemas import (ENV_VARS, EVENT_NAMES, EVENTS, FAULT_EVENT_KINDS,
                      FAULT_KINDS, METRICS, event_keys,
                      fault_event_kinds, injectable_fault_kinds,
                      one_shot_fault_kinds, required_keys)
from .registry import Counter, Gauge, Histogram, MetricsRegistry, registry
from .scopes import DEVICE_SCOPES, op_scopes
from .trace import (SPAN_EVENT_KEYS, current_context, drain_span_events,
                    new_span_id, new_trace_id, record_span,
                    set_current_trace, span, span_events_snapshot)

__all__ = [
    "MetricsRegistry", "Counter", "Gauge", "Histogram", "registry",
    "register_jit", "jit_cache_sizes", "jit_declarations",
    "total_recompiles",
    "RecompileWatcher", "device_memory_stats",
    "TelemetryRecorder", "ITERATION_EVENT_KEYS", "UnknownEventError",
    "EVENTS", "EVENT_NAMES", "METRICS", "ENV_VARS", "FAULT_KINDS",
    "FAULT_EVENT_KINDS", "event_keys", "required_keys",
    "injectable_fault_kinds", "one_shot_fault_kinds",
    "fault_event_kinds",
    "summarize_events", "render_stats_table",
    "summarize_directory", "merge_fleet_summaries",
    "render_fleet_table",
    "render_openmetrics", "parse_openmetrics", "MetricsHTTPServer",
    "ensure_metrics_server",
    "CostTracked", "drain_compile_events", "compile_events_snapshot",
    "device_peaks", "roofline_optimal_ms",
    "SPAN_EVENT_KEYS", "record_span", "span", "drain_span_events",
    "span_events_snapshot", "new_trace_id", "new_span_id",
    "current_context", "set_current_trace",
    "DEVICE_SCOPES", "op_scopes",
]
