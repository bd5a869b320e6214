"""The harness end to end on JAX's CPU backend at a tiny size: whole runs
with the chip check skipped, the control, the planted faults, and the
refusals (no chip; a directory with nothing but the benchmark).

    python -m pytest benchmark/tests -q
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from helpers import make_checkout, run_cell

RUN = [sys.executable, "benchmark/run.py"]
SEED = 3_000_000_019  # more than 32 signed bits hold


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  return make_checkout(str(tmp_path_factory.mktemp("checkout")))


def _assert_sound(res, metrics):
  assert res["correct"], res["checks"]
  assert res["checks"]["src_chunk_cache_hits"]["value"] == 0
  assert res["failed"] == 0 and res["attempted"] > 0
  assert set(metrics) <= set(res["metrics"]), res["metrics"]
  assert list(res)[-1] == "checks"


@pytest.mark.parametrize("cell", ["tiny_seg.k2", "tiny_em.k2", "tiny_em.k8"])
def test_cell_runs_correct(checkout, cell):
  res = run_cell(checkout, cell, SEED, seconds=2)
  _assert_sound(res, ["vox_per_s_chip", "setup_s"])
  assert res["window"]["compiles"] == 0


def test_traced_run_reads_layer_metrics(checkout):
  res = run_cell(checkout, "tiny_seg.k2", SEED + 1, seconds=2, trace=1)
  # no device plane in a CPU trace: the device metrics are left out
  _assert_sound(res, ["queue.s_per_task", "io.read_s_per_gvox",
                      "io.write_s_per_gvox", "xfer.h2d_bytes_per_vox",
                      "compile.in_window", "compile.setup_s"])
  assert "device.idle_share" not in res["metrics"]
  assert res["metrics"]["xfer.h2d_bytes_per_vox"]["value"] == 8.0
  assert {"busy_s", "window_s"} <= set(res["device"])
  assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_source(checkout):
  a = run_cell(checkout, "tiny_em.k2", 5, seconds=1)
  b = run_cell(checkout, "tiny_em.k2", 5, seconds=1)
  assert a["correct"] and b["correct"]
  gen = __import__("benchmark.spec", fromlist=["Spec"]).Spec(
    checkout, os.path.join(checkout, "benchmark")).generator("em_image")
  assert np.array_equal(gen.generate((64, 64, 4), 5),
                        gen.generate((64, 64, 4), 5))
  assert not np.array_equal(gen.generate((64, 64, 4), 5),
                            gen.generate((64, 64, 4), 6))


@pytest.mark.parametrize("cell", ["tiny_seg.k2", "tiny_em.k2"])
def test_control_is_not_correct(checkout, cell):
  """The reference in lower precision in the program's place: 32-bit
  labels for uint64 mode, bfloat16 for uint8 average."""
  res = run_cell(checkout, cell, SEED + 2, control=True)
  assert not res["correct"]
  assert res["checks"]["mismatched_voxels"]["value"] > 0


def _drop_half(monkeypatch):
  """Half of each round's members are acknowledged unwritten."""
  from igneous_tpu.parallel.lease_batcher import LeaseBatcher

  orig = LeaseBatcher._finish_members

  def finish_members(self, group, finish_one):
    half = len(group) // 2
    return orig(self, group,
                lambda idx, task: None if idx >= half else finish_one(idx, task))

  monkeypatch.setattr(LeaseBatcher, "_finish_members", finish_members)


def _alter_voxel(monkeypatch):
  """One voxel of the first member's first mip altered where the
  pyramid is produced (by 128 levels, which a jpeg round trip keeps)."""
  from igneous_tpu.parallel import batch_runner

  orig = batch_runner.device_pyramid_batch

  def pyramid(executor, imgs, is_u64):
    outs = orig(executor, imgs, is_u64)
    first = np.array(outs[0])
    first.flat[0] ^= 1 if is_u64 else 0x80
    return [first] + list(outs[1:])

  monkeypatch.setattr(batch_runner, "device_pyramid_batch", pyramid)


def _skip_pooling(monkeypatch):
  """The pooling step left undone: each mip strided from the input."""
  from igneous_tpu.parallel import batch_runner

  orig = batch_runner.device_pyramid_batch

  def pyramid(executor, imgs, is_u64):
    outs = orig(executor, imgs, is_u64)
    strided = []
    for m, out in enumerate(outs, start=1):
      step = 2 ** m
      src = np.stack([batch_runner._to_batch_layout(i) for i in imgs])
      strided.append(src[..., ::step, ::step][..., : out.shape[-2],
                                               : out.shape[-1]].copy())
    return strided

  monkeypatch.setattr(batch_runner, "device_pyramid_batch", pyramid)


@pytest.mark.parametrize("fault", [_drop_half, _alter_voxel, _skip_pooling],
                         ids=["half_batch_dropped", "voxel_altered",
                              "pooling_skipped"])
@pytest.mark.parametrize("cell", ["tiny_seg.k2", "tiny_em.k2"])
def test_fault_is_not_correct(checkout, cell, fault, monkeypatch):
  fault(monkeypatch)
  res = run_cell(checkout, cell, SEED + 3, seconds=1)
  assert not res["correct"], res["checks"]


def test_no_chip_exits_without_result():
  """Run from the checkout itself: JAX finds only the CPU."""
  from helpers import ROOT

  env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
  proc = subprocess.run(RUN + ["--workload", "em_ds.jpeg", "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
                        cwd=ROOT, env=env, capture_output=True, text=True,
                        timeout=300)
  assert proc.returncode == 3, proc.stderr[-2000:]
  assert proc.stdout.strip() == ""


def test_bare_benchmark_dir_exits_without_result(tmp_path):
  """A directory holding only BENCHMARK.json and benchmark/: the program
  is missing, so the run fails before any result."""
  make_checkout(str(tmp_path))
  env = dict(os.environ, PYTHONPATH="")
  proc = subprocess.run(RUN + ["--workload", "tiny_seg.k2", "--seed", "1",
                               "--seconds", "1", "--trace", "0"],
                        cwd=str(tmp_path), env=env, capture_output=True,
                        text=True, timeout=300)
  assert proc.returncode != 0
  assert proc.stdout.strip() == ""
  shutil.rmtree(str(tmp_path), ignore_errors=True)


def test_benchmark_json_names_existing_parts():
  from benchmark.spec import Spec

  root = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
  spec = Spec(root)
  for cell in spec.bench["workloads"]:
    spec.config(cell["config"])
    traffic = spec.traffic(cell["traffic"])
    spec.driver(traffic["driver"])
  for metric in spec.bench["end_to_end"] + spec.bench["per_layer"]:
    assert callable(spec.reader(metric["name"]).read)
  assert json.loads(json.dumps(spec.bench)) == spec.bench
