"""512^3 skeleton-forge soak — the EXACT fixture generator, committed.

Round-4's "512^3 / 64-blob-label soak (40.5M fg)" generator was ad-hoc
and lost with the session; round 5's rebuild of "the same" fixture got
73.9M fg voxels of heavily OVERLAPPING blobs (multi-million-voxel merged
complexes) and measured 3124.7 s — a qualitatively harder workload, not
a regression signal. This committed
generator is the canonical soak from round 5 on: grid-placed,
non-overlapping blobs (stable cost, ~31M fg), rng-seeded, printed fg
count — rounds compare on the fg rate (kvox-fg/s) it reports.

Run: JAX_PLATFORMS=cpu python tools/skel_soak.py
"""

from __future__ import annotations

import os
import time

import numpy as np


def build_fixture(n=512, seed=0):
  """4x4x4 grid of 64 blobs, one per 128^3 cell, radius jittered within
  the cell so blobs never overlap or touch task borders."""
  rng = np.random.default_rng(seed)
  g = np.indices((n, n, n)).astype(np.float32)
  seg = np.zeros((n, n, n), dtype=np.uint64)
  lab = 1
  for cx in range(4):
    for cy in range(4):
      for cz in range(4):
        c = np.array([cx, cy, cz]) * 128 + 64 + rng.integers(-8, 9, 3)
        r = int(rng.integers(n // 12, n // 11))  # 42..46 vox
        m = ((g[0] - c[0]) ** 2 + (g[1] - c[1]) ** 2
             + (g[2] - c[2]) ** 2) < r * r
        seg[m] = lab
        lab += 1
  return seg


def main():
  from igneous_tpu import task_creation as tc
  from igneous_tpu.storage import clear_memory_storage
  from igneous_tpu.volume import Volume

  seg = build_fixture()
  fg = int((seg != 0).sum())
  print(f"fg: {fg}", flush=True)
  clear_memory_storage()
  Volume.from_numpy(
    seg, "mem://soak/skel", resolution=(16, 16, 40),
    chunk_size=(128, 128, 128), layer_type="segmentation",
  )
  tasks = list(tc.create_skeletonizing_tasks(
    "mem://soak/skel", shape=(256, 256, 256), dust_threshold=50,
    teasar_params={"scale": 4, "const": 200},
  ))
  print(f"tasks: {len(tasks)}", flush=True)
  t0 = time.time()
  for t in tasks:
    t.execute()
  dt = time.time() - t0
  print(f"SOAK wall: {dt:.1f}s  fg-rate: {fg / dt / 1e3:.1f} kvox-fg/s  "
        f"load={os.getloadavg()}")


if __name__ == "__main__":
  main()
