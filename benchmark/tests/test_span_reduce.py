"""The program-span reduction: on planes built by hand, on the recorded
v5e trace (which holds no program spans, as a program without them
would), and end to end in a traced CPU run of a tiny cell."""

import os
import shutil
from types import SimpleNamespace as NS

import pytest

from benchmark import span_reduce, trace_reduce
from helpers import make_checkout, run_cell

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _ev(name, start_ms, dur_ms, **stats):
  return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS,
            stats=list(stats.items()))


def _planes():
  """A 100 ms window. The loop thread: acquire [0,5), fetch [5,40),
  dispatch [40,50), finish [50,80), prefetch_wait [85,95); nothing in
  [80,85) and [95,100). The device runs [42,48) and [60,62); an op
  outside the window is clipped away."""
  loop = NS(name="python", events=[
    _ev("bench.window", 0, 100),
    _ev("igneous.lease.acquire", 0, 5),
    _ev("igneous.queue.lease", 1, 3),
    _ev("igneous.lease.round", 5, 75),
    _ev("igneous.lease.round.fetch", 5, 35),
    _ev("igneous.lease.round.dispatch", 40, 10),
    _ev("igneous.device.execute", 41, 8, kernel="pooling.pyramid[average]"),
    _ev("igneous.lease.round.finish", 50, 30),
    _ev("igneous.queue.delete", 78, 2),
    _ev("igneous.lease.prefetch_wait", 85, 10),
  ])
  prefetch = NS(name="python", events=[
    # starts before the window: 10 of its 20 ms lie inside
    _ev("igneous.volume.download", -10, 20, voxels=2_000_000),
    _ev("igneous.volume.get", -10, 4),
    _ev("igneous.volume.decode", -6, 16),
    _ev("igneous.volume.download", 10, 30, voxels=1_000_000),
    _ev("igneous.volume.get", 10, 5),
    _ev("igneous.volume.decode", 15, 25),
    # a nack that releases: one queue interval, not two
    _ev("igneous.queue.nack", 60, 4),
    _ev("igneous.queue.release", 61, 2),
  ])
  host = NS(name="/host:CPU", lines=[loop, prefetch])
  dev = NS(name="/device:TPU:0", lines=[
    NS(name="XLA Modules", events=[
      _ev("jit_pooling_pyramid_average(5)", 42, 6),
      _ev("jit_other(9)", 60, 2)]),
    NS(name="XLA Ops", events=[_ev("fusion.1", 42, 3), _ev("fusion.2", 45, 3),
                               _ev("copy.3", 60, 2), _ev("late", 150, 10)]),
  ])
  return [host, dev]


def test_spans_are_clipped_to_the_window():
  red = span_reduce.reduce_planes(_planes())
  assert red["window_s"] == pytest.approx(0.1)
  # 6 ms of the first get lie outside the window; all of the second's in
  assert red["seconds"]["volume.get"] == pytest.approx(0.005)
  assert red["seconds"]["volume.decode"] == pytest.approx(0.035)
  # the first download counts for the half of it inside the window
  assert red["download_voxels"] == pytest.approx(2_000_000)
  assert span_reduce.seconds_per_gvox(red, "volume.get") == \
      pytest.approx(0.005 / 0.002)
  # lease 3 + delete 2 + (nack holding its release) 4
  assert red["queue_s"] == pytest.approx(0.009)
  assert red["queue_spans"] == 4


def test_idle_goes_to_the_loop_phase():
  red = span_reduce.reduce_planes(_planes())
  idle = {p: round(s * 1e3, 6) for p, s in red["idle_s"].items()}
  # busy [42,48) and [60,62): fetch 35, acquire 5, dispatch 10-6, finish
  # 30-2, prefetch_wait 10, none: [80,85) and [95,100)
  assert idle == {"input_wait": 45.0, "finish": 28.0, "dispatch": 4.0,
                  "acquire": 5.0, "unattributed": 10.0}
  assert span_reduce.idle_share(red, "finish") == pytest.approx(28.0)


def test_idle_phases_add_up_to_the_idle_share():
  planes = _planes()
  red = span_reduce.reduce_planes(planes)
  whole = trace_reduce.reduce_planes(planes)
  idle = whole["window_s"] - whole["busy_s"]
  assert sum(red["idle_s"].values()) == pytest.approx(idle)
  assert sum(span_reduce.idle_share(red, p) for p in span_reduce.PHASES) \
      == pytest.approx(100.0 * idle / whole["window_s"])


def test_innermost_loop_span_takes_the_instant():
  # a fetch nested in a prefetch wait nested in an acquire: each instant
  # goes to the span that started last among those open
  loop = [(0, 100, "acquire"), (10, 90, "input_wait"), (20, 30, "finish")]
  got = span_reduce.attribute_idle([(0, 100)], loop, 0, 120)
  assert got == {"acquire": 20, "input_wait": 70, "finish": 10}
  # idle outside every loop span, and a busy instant in none
  got = span_reduce.attribute_idle([(0, 5), (100, 120)], loop, 0, 120)
  assert got == {"acquire": 5, span_reduce.UNATTRIBUTED: 20}


def test_kernel_found_by_name_and_dispatch_count():
  red = span_reduce.reduce_planes(_planes())
  assert red["modules_s"]["jit_pooling_pyramid_average"] == \
      pytest.approx(0.006)
  assert red["executes"] == {"pooling.pyramid[average]": 1}


def _trace_ctx(checkout, cell, xplane):
  """The context a reader gets for a traced run of ``cell`` whose
  profiler file is ``xplane``."""
  tdir = span_reduce.trace_dir(os.path.join(checkout, "benchmark"), cell)
  os.makedirs(os.path.join(tdir, "plugins", "profile", "1"))
  shutil.copy(xplane, os.path.join(tdir, "plugins", "profile", "1",
                                   "host.xplane.pb"))
  return {"cell": {"name": cell}, "trace": {}, "method": "average",
          "window": {"acked": 4}, "device_kind": "TPU v5 lite",
          "kernel_bytes_per_dispatch": 1 << 30}


NEW_METRICS = ["io.get_s_per_gvox", "io.decode_s_per_gvox",
               "queue.op_s_per_task", "idle.input_wait_share",
               "idle.finish_share", "idle.unattributed_share",
               "kernel.avg_pyramid_roofline"]


def test_no_program_spans_reads_nothing(tmp_path):
  """The recorded v5e trace has device operations and ``bench.*`` spans
  but no program spans, as a program without them writes: every new
  metric reads None, and nothing raises."""
  path = os.path.join(DATA, "small.xplane.pb")
  if not os.path.exists(path):
    pytest.skip("no recorded trace")
  from benchmark.spec import Spec

  checkout = make_checkout(str(tmp_path))
  spec = Spec(checkout, os.path.join(checkout, "benchmark"))
  ctx = _trace_ctx(checkout, "em_ds.jpeg", path)
  assert {m: spec.reader(m).read(ctx) for m in NEW_METRICS} == \
      {m: None for m in NEW_METRICS}
  red = span_reduce.reduce_file(path)
  assert red["devices"] == 1 and not red["seconds"]
  # the untraced run hands no trace: nothing is read
  assert spec.reader("io.get_s_per_gvox").read(dict(ctx, trace=None)) is None


def test_traced_cpu_run_reads_program_spans(tmp_path):
  """A traced run of a tiny cell on JAX's CPU backend: the host-side
  program metrics read; the device ones need a device plane."""
  checkout = make_checkout(str(tmp_path))
  res = run_cell(checkout, "tiny_em.k2", 3_000_000_041, seconds=2, trace=1)
  assert res["correct"], res["checks"]
  metrics = res["metrics"]
  for name in ("io.get_s_per_gvox", "io.decode_s_per_gvox",
               "queue.op_s_per_task"):
    assert metrics[name]["value"] > 0, name
  assert not {"idle.input_wait_share", "idle.finish_share",
              "idle.unattributed_share",
              "kernel.avg_pyramid_roofline"} & set(metrics)
  # the harness's own timing of the same calls covers at least as much
  assert metrics["io.get_s_per_gvox"]["value"] + \
      metrics["io.decode_s_per_gvox"]["value"] <= \
      1.02 * metrics["io.read_s_per_gvox"]["value"]
