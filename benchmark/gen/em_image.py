"""uint8 EM-like image: a 4x4-blurred field of membranes and cytoplasm
plus grain. The same seed gives the same image.

Each z slice draws from its own generator, seeded by (seed, z), so the
slices are made in parallel; the array is built z-major and returned as
its (x, y, z) transpose, which is Fortran-ordered with no copy.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np


def generate(shape, seed: int) -> np.ndarray:
  sx, sy, sz = (int(v) for v in shape)
  out = np.empty((sz, sy, sx), dtype=np.uint8)

  def one_slice(z):
    rng = np.random.default_rng([int(seed), z])
    coarse = rng.integers(0, 224, (sy // 4, sx // 4), dtype=np.uint8)
    grain = np.frombuffer(rng.bytes(sy * sx), dtype=np.uint8)
    np.bitwise_and(grain.reshape(sy, sx), 31, out=out[z])
    out[z] += np.repeat(np.repeat(coarse, 4, axis=0), 4, axis=1)

  with cf.ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
    list(pool.map(one_slice, range(sz)))
  return out.transpose(2, 1, 0)
