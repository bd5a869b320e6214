"""E2e throughput vs worker-process count.

Measures `igneous-tpu -p W execute --batch K` against real fq:// queues
on shared file:// volumes (tmpfs) for the two production suites:

  img: u8 downsample grid (the codec-bound path)
  seg: u64 skeleton forge (TEASAR-bound)

Emits one JSON line per (suite, workers) plus a markdown table. On a
1-core host the expected result is flat scaling with bounded per-worker
overhead — the datum of interest is that nothing COLLAPSES under
concurrent lease traffic; real scaling numbers need a multi-core host.
CPU workers only: a chip serves one process, so a chip host runs one
device worker with `execute --batch K`.

Run: python tools/worker_scaling.py [workers ...]
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np

ROOT = "/dev/shm/ig_scaling"
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def build_img(path):
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(0)
  # big enough that per-worker interpreter+jax startup (~4 s) does not
  # dominate the wall measurement
  data = rng.integers(0, 255, (1024, 1024, 64)).astype(np.uint8)
  Volume.from_numpy(data, path, chunk_size=(64, 64, 64))
  return int(data.size)


def build_seg(path):
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(0)
  n = 256
  g = np.indices((n, n, n)).astype(np.float32)
  seg = np.zeros((n, n, n), dtype=np.uint64)
  for i in range(24):
    c = rng.integers(n // 8, n - n // 8, 3)
    r = rng.integers(n // 12, n // 5)
    m = ((g[0] - c[0]) ** 2 + (g[1] - c[1]) ** 2 + (g[2] - c[2]) ** 2) < r * r
    seg[m] = i + 1
  Volume.from_numpy(
    seg, path, chunk_size=(128, 128, 128), layer_type="segmentation",
    resolution=(16, 16, 40),
  )
  return int(seg.size)


def make_tasks(suite, path):
  from igneous_tpu import task_creation as tc

  if suite == "img":
    return list(tc.create_downsampling_tasks(
      path, mip=0, num_mips=2, compress=None, memory_target=int(64e6),
    ))
  return list(tc.create_skeletonizing_tasks(
    path, shape=(128, 128, 128), dust_threshold=50,
    teasar_params={"scale": 4, "const": 200},
  ))


def run_suite(suite, workers, batch):
  from igneous_tpu.queues import FileQueue

  base = f"{ROOT}/{suite}_w{workers}"
  shutil.rmtree(base, ignore_errors=True)
  os.makedirs(base)
  vol_path = f"file://{base}/vol"
  voxels = build_img(vol_path) if suite == "img" else build_seg(vol_path)
  tasks = make_tasks(suite, vol_path)
  q = FileQueue(f"fq://{base}/q")
  q.insert(tasks)
  t0 = time.time()
  proc = subprocess.run(
    [sys.executable, "-m", "igneous_tpu.cli", "-p", str(workers),
     "execute", f"fq://{base}/q", "-x", "-q", "--batch", str(batch)],
    env=ENV, capture_output=True, text=True, timeout=3600,
  )
  wall = time.time() - t0
  if proc.returncode != 0:
    raise RuntimeError(proc.stderr[-800:])
  if not q.is_empty():
    raise RuntimeError(f"queue not drained: {suite} w={workers}")
  return {
    "suite": suite, "workers": workers, "batch": batch,
    "tasks": len(tasks), "wall_s": round(wall, 1),
    "voxps": round(voxels / wall, 1),
  }


def main():
  worker_counts = [int(v) for v in sys.argv[1:]] or [1, 2]
  rows = []
  for suite in ("img", "seg"):
    for w in worker_counts:
      r = run_suite(suite, w, batch=4)
      rows.append(r)
      print(json.dumps(r), flush=True)
  print("\n| suite | workers | wall s | vox/s |")
  print("|---|---|---|---|")
  for r in rows:
    print(f"| {r['suite']} | {r['workers']} | {r['wall_s']} "
          f"| {r['voxps']:,.0f} |")
  shutil.rmtree(ROOT, ignore_errors=True)


if __name__ == "__main__":
  main()
