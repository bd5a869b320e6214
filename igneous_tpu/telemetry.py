"""Compat shim over :mod:`igneous_tpu.observability` (ISSUE 5).

This module used to hold the process-local counters/timers itself; that
implementation now lives in ``observability/metrics.py`` alongside the
trace/journal/exporter layers built on top of it. Every public name is
re-exported so ``from igneous_tpu import telemetry`` call sites keep
working unchanged.

Behavior change shipped with the move: ``reset_counters()`` clears the
int counters ONLY — callers that also want timers/gauges/histograms
cleared (the old conflated behavior) must call ``reset_all()``.
"""

from __future__ import annotations

from .observability.metrics import (  # noqa: F401
  StageTimes,
  _stack,
  counters_snapshot,
  emit_counters,
  gauge_max,
  gauge_set,
  gauges_snapshot,
  histograms_snapshot,
  incr,
  observe,
  observe_quiet,
  queue_eta,
  reset_all,
  reset_counters,
  stage,
  task_timing,
  timed_poll_hooks,
  timer_totals,
  timers_snapshot,
)

__all__ = [
  "StageTimes", "counters_snapshot", "emit_counters",
  "gauge_max", "gauge_set", "gauges_snapshot", "histograms_snapshot",
  "incr", "observe", "observe_quiet",
  "queue_eta", "reset_all", "reset_counters", "stage", "task_timing",
  "timed_poll_hooks", "timer_totals", "timers_snapshot",
]
