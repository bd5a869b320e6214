"""Which op of the lax tiled CCL diverges on a TPU at tile (8,16,128)?

Runs each op of one tile-resolve round on the same inputs on the TPU and
on the host CPU backend of the same process, over the 1856 tiles of a
449x449x128 cutout, and prints one JSON line per op with the count of
differing elements. On a v5e (PR 21) only the reverse segmented cummin
along z and x differed; ccl._TPU_TILES stays at (2,4,8) until a fix
makes this print no difference.

Usage, on the chip: python tools/ccl_tile_repro.py [out.jsonl]
"""
import json
import os
import sys
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke
from igneous_tpu.ops import ccl

tpu, cpu = jax.devices()[0], jax.devices("cpu")[0]
out = open(sys.argv[1], "w") if len(sys.argv) > 1 else sys.stdout


def say(**kw):
  print(json.dumps(kw), file=out, flush=True)


say(tpu=str(tpu), cpu=str(cpu))
lab = chip_smoke.make_labels((449, 449, 128), 0)
zyx = np.ascontiguousarray(ccl._dense_relabel(lab).transpose(2, 1, 0))
tile = (8, 16, 128)
Z, Y, X = zyx.shape
pads = [(-s) % t for s, t in zip(zyx.shape, tile)]
labp = np.pad(zyx, [(0, p) for p in pads])
nz, ny, nx = (s // t for s, t in zip(labp.shape, tile))
tz, ty, tx = tile
labt = (labp.reshape(nz, tz, ny, ty, nx, tx).transpose(0, 2, 4, 1, 3, 5)
        .reshape(-1, tz, ty, tx))
tsize = tz * ty * tx
rng = np.random.default_rng(0)
L = rng.integers(0, tsize, labt.shape).astype(np.int32)
say(tiles=labt.shape)


def cmp(name, fn, *args):
  t0 = time.perf_counter()
  try:
    f = jax.jit(fn)
    a = np.asarray(f(*[jax.device_put(x, tpu) for x in args]))
    b = np.asarray(f(*[jax.device_put(x, cpu) for x in args]))
    d = a != b
    first = [int(v) for v in np.argwhere(d)[0]] if d.any() else None
    say(op=name, differ=int(d.sum()), of=int(d.size), first=first,
        s=round(time.perf_counter() - t0, 2))
  except Exception as e:  # report and go on to the next op
    say(op=name, error=repr(e)[:300])


for axis in (1, 2, 3):
  for rev in (False, True):
    cmp(f"seg_cummin axis{axis} rev{rev}",
        partial(ccl._seg_cummin, axis=axis, reverse=rev), L, labt)
cmp("neighbor_min", partial(ccl._neighbor_min, connectivity=6,
                            axes=(1, 2, 3)), L, labt)
cmp("take_along_axis", lambda f: jnp.take_along_axis(f, f, axis=1),
    L.reshape(-1, tsize))
cmp("tile_resolve relax", partial(ccl._tile_resolve, connectivity=6,
                                  algo="relax"), labt)
cmp("tiled kernel scan", partial(ccl._ccl_tiled_kernel, connectivity=6,
                                 algo="scan", tile=tile), zyx)
