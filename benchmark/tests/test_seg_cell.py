"""The segmentation cell's per-layer metrics on JAX's CPU backend: a
traced tiny uint64 run reads the plane split and pack from the program's
spans, and both readers report nothing where their spans or modules are
absent, as in a program that lacks them.

    python -m pytest benchmark/tests/test_seg_cell.py -q
"""

import pytest

from helpers import make_checkout, run_cell

SEED = 3_000_000_025


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  return make_checkout(str(tmp_path_factory.mktemp("checkout")))


def test_traced_seg_run_reads_u64_planes(checkout):
  res = run_cell(checkout, "tiny_seg.k2", SEED, seconds=2, trace=1)
  assert res["correct"], res["checks"]
  value = res["metrics"]["executor.u64_planes_s_per_gvox"]["value"]
  assert value > 0
  # no device plane in a CPU trace: no roofline
  assert "kernel.mode_pyramid_roofline" not in res["metrics"]


def _reduced(seconds, executes, modules):
  return {"seconds": seconds, "download_voxels": 2e9, "executes": executes,
          "modules_s": modules}


@pytest.mark.parametrize("reduced,want", [
  (_reduced({"pooling.u64_split": 1.0, "pooling.u64_pack": 0.5}, {}, {}),
   0.75),
  (_reduced({"volume.decode": 1.0}, {}, {}), None),
  (None, None),
])
def test_u64_planes_reader(monkeypatch, checkout, reduced, want):
  from benchmark import span_reduce
  from benchmark.spec import Spec

  monkeypatch.setattr(span_reduce, "for_run", lambda ctx, f: reduced)
  reader = Spec(checkout).reader("executor.u64_planes_s_per_gvox")
  assert reader.read({}) == want


@pytest.mark.parametrize("method,modules,want", [
  ("mode", {"jit_pooling_pyramid_mode": 0.5}, True),
  ("mode", {"jit_pooling_pyramid_average": 0.5}, False),
  ("average", {"jit_pooling_pyramid_mode": 0.5}, False),
])
def test_mode_roofline_reader(monkeypatch, checkout, method, modules, want):
  from benchmark import span_reduce
  from benchmark.spec import Spec

  reduced = _reduced({}, {"pooling.pyramid[mode]": 2}, modules)
  monkeypatch.setattr(span_reduce, "for_run", lambda ctx, f: reduced)
  reader = Spec(checkout).reader("kernel.mode_pyramid_roofline")
  ctx = {"method": method, "kernel_bytes_per_dispatch": 819_000_000,
         "device_kind": "TPU v5 lite"}
  value = reader.read(ctx)
  # two dispatches of 0.819 GB in 0.5 s at 819 GB/s: 0.4%
  assert (value == pytest.approx(0.4)) if want else value is None
