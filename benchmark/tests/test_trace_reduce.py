"""The trace reduction, the pyramid bytes and the peaks table: on planes
built by hand, and on a small trace recorded on a TPU v5e
(``data/small.xplane.pb``, made by ``record_small_trace.py``)."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import roofline, trace_reduce

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def _ev(name, start_ms, dur_ms):
  return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def _planes():
  host = NS(name="/host:CPU", lines=[
    NS(name="python", events=[
      _ev("bench.window", 0, 100),
      _ev("bench.download", 0, 30),
      _ev("bench.dispatch", 30, 20),
      _ev("bench.encode_join", 50, 50),
      _ev("not.ours", 0, 100),
    ]),
  ])
  dev = NS(name="/device:TPU:0", lines=[
    NS(name="XLA Modules", events=[_ev("jit_per_shard(77)", 35, 10),
                                   _ev("jit_other(9)", 60, 5)]),
    NS(name="XLA Ops", events=[_ev("fusion.1", 35, 6), _ev("fusion.2", 39, 6),
                               _ev("copy.3", 60, 5), _ev("late", 150, 10)]),
  ])
  return [host, dev]


def test_busy_idle_and_gaps():
  red = trace_reduce.reduce_planes(_planes())
  assert red["window_s"] == pytest.approx(0.1)
  assert red["devices"] == 1
  # union of [35,45) and [60,65); the op after the window is clipped away
  assert red["busy_s"] == pytest.approx(0.015)
  # gaps [0,35) [45,60) [65,100), each given to the span overlapping most
  assert sorted((n, round(s, 6)) for n, s in red["idle_gaps"]) == [
    ("download", 0.035), ("encode_join", 0.015), ("encode_join", 0.035)]
  assert red["ops_s"]["fusion.1"] == pytest.approx(0.006)


def test_modules_under_a_span_are_found_by_time():
  red = trace_reduce.reduce_planes(_planes())
  under = trace_reduce.modules_under(red, "dispatch")
  assert under == {"jit_per_shard": pytest.approx(0.010)}


def test_breakdown_shape():
  out = trace_reduce.breakdown(trace_reduce.reduce_planes(_planes()), top=2)
  assert set(out) == {"device_ops", "idle_gaps"}
  assert len(out["device_ops"]) == 2 and len(out["idle_gaps"]) == 2
  assert all(isinstance(s, float) for _, s in out["device_ops"])


def test_union():
  assert trace_reduce.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [
    (0, 3), (5, 10)]


def test_pyramid_bytes_and_peaks():
  # 4x4x1 u8 cutout, two mips of 2x2x1: 16 + 4 + 1 voxels
  assert roofline.pyramid_bytes((4, 4, 1), (2, 2, 1), 2, 1) == 21
  # odd extents round up, as the pyramid replicates the edge
  assert roofline.mip_shapes((5, 3, 2), (2, 2, 1), 2) == [(3, 2, 2), (2, 1, 2)]
  assert roofline.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
  with pytest.raises(ValueError):
    roofline.peaks("TPU v9 imaginary")
  # 819 MB at 819 GB/s takes 1 ms: 1 ms of device time is the roofline
  assert roofline.bandwidth_share(819e6, 1e-3, "TPU v5 lite") == \
      pytest.approx(100.0)
  assert roofline.bandwidth_share(819e6, 0.0, "TPU v5 lite") is None


def test_recorded_v5e_trace():
  path = os.path.join(DATA, "small.xplane.pb")
  if not os.path.exists(path):
    pytest.skip("no recorded trace")
  red = trace_reduce.reduce_trace(path)
  assert red["devices"] == 1
  assert 0 < red["busy_s"] < red["window_s"]
  spans = [n for n, _, _ in red["host_spans"]]
  assert spans.count("bench.dispatch") == 3
  assert spans.count("bench.download") == 3
  under = trace_reduce.modules_under(red, "dispatch")
  assert len(under) == 1 and sum(under.values()) > 0
  # the sleeps in bench.download leave the device idle
  assert any(n == "download" for n, _ in red["idle_gaps"])
