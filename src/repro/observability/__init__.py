"""Observability: task-timeline tracing + unified metrics (SWIFT §4).

SWIFT's engineering loop is *instrument every task, read the task plot*:
per-core tic/toc timestamps rendered as one row per core, one slice per
task, from which load imbalance, dead time and communication stalls are
read off directly (arXiv:1606.02738 §4; first-class tooling in modern
SWIFT, arXiv:2305.13380). This package is that loop for the XLA substrate:

* :mod:`~repro.observability.tracer` — the low-overhead span tracer:
  every span is also a profiler ``TraceAnnotation``; host-scheduled
  ladders fence (``block_until_ready``) so device work is attributed to
  the phase that launched it; free when disabled.
* :mod:`~repro.observability.metrics` — counters/gauges registry absorbing
  the engines' ledgers (transfer bytes, compile counts, bucket events,
  halo volume, bin-occupancy imbalance) behind one API.
* :mod:`~repro.observability.sinks` — Chrome-trace/Perfetto JSON export
  (the task plot) + per-cycle JSONL metrics log, with the minimal schema
  validator CI runs on every traced cycle.
* :mod:`~repro.observability.observer` — the per-run merge point wired in
  by ``SimulationSpec(observe=True)``; feeds measured task costs back into
  :class:`~repro.core.cost_model.CostModel`.
* :mod:`~repro.observability.device_metrics` — the in-program telemetry
  carry (fixed-shape per-rank counter/value rows computed *inside* the
  fused programs, accumulated on device, pulled once per cycle).
* :mod:`~repro.observability.flight` — last-K-cycles flight recorder +
  post-mortem dump bundles, written on any health-sentinel trip.

``python -m repro.observability`` runs one traced Sedov cycle on an
emulated rank mesh and exports + validates ``trace.json`` /
``metrics.jsonl`` (the CI artifact job); ``python -m repro.observability
dump`` produces and validates a flight-recorder bundle (optionally
tripping the NaN sentinel on purpose).

This package must stay importable before jax is configured (its CLI sets
``XLA_FLAGS``), so nothing here imports jax at module scope.
"""

from .costs import RepartitionAdvisor, TaskCostLedger, weighted_imbalance
from .device_metrics import (CELL_COLUMNS, COUNT_COLUMNS, VALUE_COLUMNS,
                             DEVICE_METRICS_VERSION)
from .flight import FlightRecorder, read_bundle, validate_bundle
from .metrics import METRICS_SCHEMA_VERSION, MetricsRegistry
from .observer import ObserveSpec, RunObserver, UMBRELLA_SPANS
from .sinks import (chrome_trace, jsonify, read_metrics_jsonl,
                    upgrade_record, validate_chrome_trace,
                    write_chrome_trace, write_metrics_jsonl)
from .tracer import NULL_TRACER, NullTracer, Span, Tracer

__all__ = [
    "METRICS_SCHEMA_VERSION", "MetricsRegistry",
    "CELL_COLUMNS", "COUNT_COLUMNS", "VALUE_COLUMNS",
    "DEVICE_METRICS_VERSION",
    "RepartitionAdvisor", "TaskCostLedger", "weighted_imbalance",
    "FlightRecorder", "read_bundle", "validate_bundle",
    "ObserveSpec", "RunObserver", "UMBRELLA_SPANS",
    "chrome_trace", "jsonify", "read_metrics_jsonl", "upgrade_record",
    "validate_chrome_trace", "write_chrome_trace", "write_metrics_jsonl",
    "NULL_TRACER", "NullTracer", "Span", "Tracer",
]
