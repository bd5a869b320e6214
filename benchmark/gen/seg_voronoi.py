"""Dense uint64 segmentation: warped Voronoi cells with ids above 2**32.

Voronoi cells seeded on an (8,8,4)-coarse grid (about 512 seeds per
2048x2048x64 task), sampled through a smooth per-slice sine warp so cell
walls are curved at voxel scale: mixed windows and ties for mode pooling
at every mip. Random ids in [2**32, 2**48); about 2% of the cells are
background (0). The same seed gives the same labels.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np


def generate(shape, seed: int) -> np.ndarray:
  from scipy import ndimage

  rng = np.random.default_rng(seed + 1)
  grid = (8, 8, 4)
  coarse = tuple(-(-s // f) for s, f in zip(shape, grid))
  n = max(8, int(np.prod(coarse)) // 2048)
  seeds = rng.integers(0, coarse, (n, 3))
  ids = np.zeros(coarse, np.int32)
  ids[tuple(seeds.T)] = np.arange(1, n + 1, dtype=np.int32)
  _, idx = ndimage.distance_transform_edt(ids == 0, return_indices=True)
  cells = ids[tuple(idx)]
  table = rng.integers(2**32, 2**48, n + 1, dtype=np.uint64)
  table[0] = 0
  table[rng.integers(1, n + 1, max(1, n // 50))] = 0

  sx, sy, sz = shape
  x = np.arange(sx, dtype=np.float32)[:, None]
  y = np.arange(sy, dtype=np.float32)[None, :]
  period = rng.uniform(48, 96, (2, sz)).astype(np.float32)
  phase = rng.uniform(0, 2 * np.pi, (2, sz)).astype(np.float32)
  out = np.empty(shape, np.uint64, order="F")

  def one_slice(z):
    wx = x + 6 * np.sin(2 * np.pi * y / period[0, z] + phase[0, z])
    wy = y + 6 * np.sin(2 * np.pi * x / period[1, z] + phase[1, z])
    ix = np.clip(wx // grid[0], 0, coarse[0] - 1).astype(np.intp)
    iy = np.clip(wy // grid[1], 0, coarse[1] - 1).astype(np.intp)
    out[:, :, z] = table[cells[ix, iy, z // grid[2]]]

  with cf.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
    list(pool.map(one_slice, range(sz)))
  return out
