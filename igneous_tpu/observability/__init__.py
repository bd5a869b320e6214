"""Distributed observability: trace context, spans, journal, exporters.

The subsystem in one picture::

    task factory ──mint──> trace_id in the queue payload
         │                        │
      enqueue              lease / redeliver / DLQ (identity survives)
         │                        │
         └──> worker: task_span + stage spans (pipeline observe() sites,
              storage ops, lease rounds) → per-thread span buffers
                                  │
              Journal.flush ──> <queue>/journal/*.jsonl segments
                                  │            │
         igneous fleet status|trace|top        │ rollup.compact (ISSUE 6)
         Prometheus /metrics        Perfetto   ▼
                                    <journal>/rollup/ windowed records
                                               │
              HealthEngine (stragglers, anomalies, SLO burn, autoscale)
                 │               │                    │
         fleet check|watch   health.* events   health/flags.json
         (exit codes, CI)    + Prom gauges     (LeaseBatcher backs off)

    device plane (ISSUE 7, device.py): executors/pooling emit
    device.compile|execute|h2d|d2h spans + a cumulative utilization
    ledger (recompiles, HBM, busy ratio, per-kernel vox/s) → journal
    "device" records → igneous_device_* gauges, `fleet devices`, watch
    dashboard, recompile-storm/HBM/idle anomalies; profile/request.json
    triggers on-demand jax.profiler captures → <journal>/profiles/

    forward plane (ISSUE 13): replay.py mines the journal into a
    WorkloadModel; sim.py replays it through a deterministic
    discrete-event fleet simulation that EMITS journal format (every
    fleet command works on simulated runs); autoscale.py closes the
    loop — the same policy formula drives the health report, the
    simulator's virtual controller, and `igneous fleet autoscale`

``igneous_tpu.telemetry`` remains as a compat shim over
:mod:`.metrics`; new code should import from here.
"""

from . import (
  autoscale,
  device,
  fleet,
  health,
  journal,
  perfetto,
  prom,
  replay,
  rollup,
  sim,
  trace,
)
from .metrics import (
  StageTimes,
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
  timers_snapshot,
)

__all__ = [
  "autoscale", "device", "fleet", "health", "journal", "perfetto",
  "prom", "replay", "rollup", "sim", "trace",
  "StageTimes", "counters_snapshot", "emit_counters",
  "gauge_max", "gauge_set", "gauges_snapshot", "histograms_snapshot",
  "incr", "observe", "observe_quiet", "queue_eta", "reset_all",
  "reset_counters", "stage",
  "task_timing", "timed_poll_hooks", "timers_snapshot",
]
