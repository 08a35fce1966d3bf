"""Runtime telemetry: structured metrics rows, span tracing, and
device-side gradient statistics.

Three layers, composable and individually optional:

* :mod:`repro.telemetry.metrics` — :class:`MetricsLogger`: typed
  counter/gauge/histogram channels plus schema-versioned structured
  rows (JSONL sink + in-memory ring buffer);
* :mod:`repro.telemetry.spans` — :class:`SpanTracer`: host-side span
  timers with ``block_until_ready`` fencing, Chrome-trace export
  (Perfetto-viewable), each span a ``jax.profiler`` annotation; and
  the names of the round's device-side scopes;
* :mod:`repro.telemetry.gradstats` — device-side statistics inside the
  jitted round behind ``make_hier_round(..., telemetry=)``: per-level
  parameter divergence, gradient-norm variance, EF residual mass,
  codec compression error.

First consumer: ``repro.autotune.CostAwarePlan.observe`` ingests
``train_round`` rows to compare measured against modeled round walls.
"""
from repro.telemetry.gradstats import (TelemetryConfig, codec_error,
                                       ef_mass, group_divergence,
                                       level_stats, make_grad_observer,
                                       resolve_telemetry)
from repro.telemetry.metrics import (ROW_SCHEMAS, SCHEMA_VERSION,
                                     MetricsLogger, validate_jsonl)
from repro.telemetry.spans import SpanTracer

__all__ = [
    "MetricsLogger", "SpanTracer", "TelemetryConfig", "ROW_SCHEMAS",
    "SCHEMA_VERSION", "validate_jsonl", "resolve_telemetry",
    "group_divergence", "codec_error", "ef_mass", "level_stats",
    "make_grad_observer",
]
