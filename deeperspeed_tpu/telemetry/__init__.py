"""Structured, rank-0-aggregated telemetry for DeeperSpeed-TPU.

Four pieces (see README "Observability"):

* :class:`TelemetryRegistry` -- typed scalar/histogram/counter channels with
  a JSONL event sink and a Prometheus-textfile exporter;
* :mod:`hlo_cost` -- HLO ``cost_analysis()`` of the compiled step functions
  -> true FLOPs / bytes-accessed -> per-step MFU/MBU against a TPU
  peak-spec table;
* :mod:`wire` -- the analytic bytes-on-wire model shared with
  ``tools/bench_collectives.py``, fed per-step by the trace-time collective
  footprints ``comm/comm.py`` records into ``CommsLogger``;
* :class:`StallWatchdog` -- heartbeat-tracked progress with a diagnostic
  snapshot (timers, device memory, recent events, thread stacks) on
  deadline;
* :mod:`serving` -- the typed serving-resilience event schema (shed /
  deadline-cancel / degrade / requeue / quarantine) the v2 front end
  narrates its robustness decisions through;
* :mod:`trace` -- request-path span tracing (:class:`Tracer` /
  :class:`TraceContext`), per-request SLO accounting, Chrome-trace export,
  and the :class:`FlightRecorder` postmortem ring; :func:`span`, the one
  primitive every live span goes through (a ``dst:`` event on the
  ``jax.profiler`` timeline, and a ring record when the tracer is on), and
  what is kept whether or not a tracer or a profiler is on:
  :func:`compile_stats` (with the compile phases by interval),
  :func:`step_scopes`, :func:`step_timeline` (one
  record a train step: its clocks, its host phases by wall time, and the
  model's counters), :func:`step_counters` (the newest record's),
  :func:`setup_timeline` (the ``setup/*`` spans and the compile phases:
  the time to the first step by phase),
  :func:`kernel_paths` and :func:`kernel_passes`;
* :mod:`aggregate` -- mergeable registry snapshots + the pool-side
  :class:`MetricsAggregator` (counters sum, histograms merge bucket-wise,
  quantiles interpolate post-merge);
* :mod:`slo` -- the multi-window SLO burn-rate evaluator
  (:class:`SLOBurnEvaluator`) emitting typed alerts and the
  ``slo_pressure`` signal the autoscaler and shed ladder consume.
"""

from .aggregate import (MetricsAggregator, merge_snapshots,
                        snapshot_quantile, snapshot_registry)
from .hlo_cost import (TPU_PEAK_SPECS, compiled_cost, device_peaks, step_cost,
                       utilization)
from .registry import (LATENCY_BUCKETS_S, CounterChannel, HistogramChannel,
                       JsonlSink, PrometheusTextfileSink, ScalarChannel,
                       TelemetryRegistry, get_registry, registry_from_config,
                       set_registry)
from .slo import SLOAlert, SLOBurnEvaluator
from .trace import (FlightRecorder, Span, TraceContext, Tracer, compile_stats,
                    count_kernel_passes, get_tracer, kernel_passes,
                    kernel_paths, set_tracer, setup_timeline, slo_percentiles,
                    span, step_counters, step_scopes, step_timeline,
                    tracer_from_config)
from .watchdog import StallWatchdog
from .wire import plain_wire_bytes, q_bytes, quantized_variant, wire_bytes
from . import serving  # noqa: F401  (typed serving-resilience events)

__all__ = [
    "TelemetryRegistry", "ScalarChannel", "CounterChannel", "HistogramChannel",
    "JsonlSink", "PrometheusTextfileSink", "LATENCY_BUCKETS_S",
    "get_registry", "set_registry", "registry_from_config",
    "Tracer", "TraceContext", "Span", "FlightRecorder", "get_tracer",
    "set_tracer", "tracer_from_config", "slo_percentiles", "span",
    "compile_stats", "step_scopes", "step_counters", "step_timeline",
    "setup_timeline",
    "kernel_paths",
    "kernel_passes", "count_kernel_passes",
    "StallWatchdog", "step_cost", "compiled_cost",
    "utilization", "device_peaks", "TPU_PEAK_SPECS", "wire_bytes", "q_bytes",
    "plain_wire_bytes", "quantized_variant", "serving",
    "MetricsAggregator", "snapshot_registry", "snapshot_quantile",
    "merge_snapshots", "SLOBurnEvaluator", "SLOAlert",
]
