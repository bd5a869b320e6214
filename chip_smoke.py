"""Main-path smoke on the chip, through the CLI's own commands.

    python chip_smoke.py [--seed N]      # one chip: phases P1-P5
    python chip_smoke.py --chips 4       # P1 only, --batch 8 over 4 chips

One process holds the chip and runs every phase through ``igneous_tpu.cli``
(click's ``main(args, standalone_mode=False)``), so all phases share one
JAX compile cache (placed by ``compile_cache.place_jax_cache``). Volumes are
made from ``--seed`` and written as ``file://`` layers under a scratch
directory that is deleted at the end.

  P1 image downsample: uint8 EM-like 2048x2048x128, 4 mips of (2,2,1),
     fq:// queue drained by ``execute --batch 4``; byte-identical to
     the native C++ average pyramid at every mip.
  P2 segmentation downsample: uint64 dense labels 1024x1024x128 with ids
     above 2**32, compressed_segmentation, mode pooling, same flow;
     byte-identical to the native C++ mode pyramid.
  P3 ``image ccl auto`` on a 512x512x64 slab of the P2 labels; its
     partition equals the native two-pass union-find's.
  P4 ``mesh forge`` + ``mesh merge`` on the slab with device emission;
     byte-identical to the host-emit run (IGNEOUS_MESH_EMIT=host).
  P5 ``skeleton forge`` + ``skeleton merge`` on the slab with the device
     EDT; byte-identical to the run with IGNEOUS_EDT_BACKEND=native.

Each phase also requires ``device.execute`` entries in the device ledger
for its kernels, on devices of the expected platform only, and prints one
JSON line. The last line is ``{"ok": true, "device": {...}}``; any failure
exits non-zero before it. With no TPU the script exits non-zero at once.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
from contextlib import contextmanager

import numpy as np

IMG_SHAPE = (2048, 2048, 128)
SEG_SHAPE = (1024, 1024, 128)
# of the P2 labels, read by P3, P4 and P5; depth cut from 128 to 64 to
# fit the chip budget (P4 and P5 run the host forge twice each)
SLAB_SHAPE = (512, 512, 64)
CHUNK = (128, 128, 64)
# destination chunks of the downsampled scales: a task that writes 4 mips
# of (2,2,1) is dest_chunk * 16 wide, so these give 8 tasks per volume
IMG_DEST_CHUNK = (64, 64, 64)
SEG_DEST_CHUNK = (32, 32, 64)
NUM_MIPS = 4
FACTOR = (2, 2, 1)

# device-ledger kernel names each phase must have executed
P1_KERNELS = ("pooling.pyramid[average]",)
P2_KERNELS = ("pooling.pyramid[mode]",)
P3_KERNELS = ("ccl.tiled[scan]",)
P4_KERNELS = ("mc_count_kernel", "mesh.mc_emit")
P5_KERNELS = ("edt.sq_blocked",)


class PhaseError(RuntimeError):
  pass


def _cli(*args) -> None:
  from igneous_tpu.cli import main

  main([str(a) for a in args], standalone_mode=False)


@contextmanager
def _env(**values):
  # lint: allow=IGN105 saves the caller's values of the knobs it pins
  old = {k: os.environ.get(k) for k in values}
  os.environ.update({k: str(v) for k, v in values.items()})
  try:
    yield
  finally:
    for k, v in old.items():
      if v is None:
        os.environ.pop(k, None)
      else:
        os.environ[k] = v


def _check(cond: bool, what: str) -> None:
  if not cond:
    raise PhaseError(what)


def _csv(v) -> str:
  return ",".join(str(int(x)) for x in v)


def _shape(v) -> str:
  return "x".join(str(int(x)) for x in v)


# ---------------------------------------------------------------------------
# data, made from the seed


def make_image(shape, seed: int) -> np.ndarray:
  """uint8 EM-like: 4x4-blurred membranes-and-cytoplasm field plus grain."""
  rng = np.random.default_rng(seed)
  sx, sy, sz = shape
  coarse = rng.integers(0, 224, (sx // 4, sy // 4, sz), dtype=np.uint8)
  img = np.repeat(np.repeat(coarse, 4, axis=0), 4, axis=1)
  img += rng.integers(0, 32, shape, dtype=np.uint8)
  return np.asfortranarray(img)


def make_labels(shape, seed: int) -> np.ndarray:
  """uint64 dense segmentation: Voronoi cells seeded on an (8,8,4)-coarse
  grid, sampled through a smooth per-slice sine warp so that cell walls
  are curved at voxel scale (mixed windows and ties for mode pooling at
  every mip), random ids above 2**32, a few cells left as background."""
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
  for z in range(sz):
    wx = x + 6 * np.sin(2 * np.pi * y / period[0, z] + phase[0, z])
    wy = y + 6 * np.sin(2 * np.pi * x / period[1, z] + phase[1, z])
    ix = np.clip(wx // grid[0], 0, coarse[0] - 1).astype(np.intp)
    iy = np.clip(wy // grid[1], 0, coarse[1] - 1).astype(np.intp)
    out[:, :, z] = table[cells[ix, iy, z // grid[2]]]
  return out


def write_layer(arr, path: str, resolution, chunk, encoding="raw"):
  from igneous_tpu.volume import Volume

  Volume.from_numpy(
    arr, path, resolution=resolution, chunk_size=chunk, encoding=encoding,
  )


def read_mip(path: str, mip: int) -> np.ndarray:
  from igneous_tpu.volume import Volume

  vol = Volume(path, mip=mip)
  return vol.download(vol.meta.bounds(mip))[..., 0]


# ---------------------------------------------------------------------------
# ledger and output checks


def _ledger_reset() -> None:
  from igneous_tpu.observability import device as device_mod

  device_mod.LEDGER.reset()


def _ledger_check(kernels, platform: str) -> dict:
  """device.execute entries for every expected kernel, on devices of
  ``platform`` only; returns the phase's compile/run seconds."""
  from igneous_tpu.observability import device as device_mod

  led = device_mod.LEDGER
  with led.lock:
    stats = {k: dict(v) for k, v in led.kernels.items()}
    devices = sorted(led.device_busy)
  for name in kernels:
    _check(
      stats.get(name, {}).get("executes", 0) > 0,
      f"no device.execute for {name} (ledger kernels: {sorted(stats)})",
    )
  _check(bool(devices), "no device was busy")
  _check(
    all(d.startswith(f"{platform}:") for d in devices),
    f"device work off {platform}: {devices}",
  )
  return {
    "compile_s": sum(v["compile_s"] for v in stats.values()),
    "run_s": sum(v["execute_s"] for v in stats.values()),
    "devices": devices,
  }


def _drain(queue: str, n_tasks: int, batch: int) -> None:
  from igneous_tpu.queues.filequeue import FileQueue

  _cli("execute", queue, "--batch", batch, "--exit-on-empty", "--quiet",
       "--max-deliveries", 2)
  fq = FileQueue(queue)
  _check(fq.completed == n_tasks,
         f"queue completed {fq.completed} of {n_tasks} tasks")
  _check(fq.enqueued == 0, f"{fq.enqueued} tasks left in the queue")
  _check(fq.dlq_count == 0, f"{fq.dlq_count} tasks in the DLQ")


def _enqueued(queue: str) -> int:
  from igneous_tpu.queues.filequeue import FileQueue

  return FileQueue(queue).enqueued


def _tree(root: str) -> dict:
  out = {}
  for dirpath, _dirs, files in os.walk(root):
    for f in files:
      p = os.path.join(dirpath, f)
      with open(p, "rb") as fh:
        out[os.path.relpath(p, root)] = fh.read()
  return out


def _same_partition(a: np.ndarray, b: np.ndarray) -> bool:
  """Equal up to renumbering, background to background."""
  if a.shape != b.shape or not np.array_equal(a == 0, b == 0):
    return False
  _, ia = np.unique(a.ravel(), return_inverse=True)
  _, ib = np.unique(b.ravel(), return_inverse=True)
  pairs = np.unique(ia.astype(np.int64) * (int(ib.max()) + 1) + ib)
  return len(pairs) == ia.max() + 1 == ib.max() + 1


def _line(phase: str, shape, dtype, n_tasks: int, led: dict,
          oracle: str) -> dict:
  return {
    "phase": phase, "shape": _shape(shape), "dtype": dtype,
    "tasks": n_tasks, "compile_s": led["compile_s"], "run_s": led["run_s"],
    "devices": led["devices"], "oracle": oracle,
  }


# ---------------------------------------------------------------------------
# phases


def _downsample_phase(name, root, layer, arr, resolution, encoding, oracle,
                      kernels, platform, batch, chunk, dest_chunk) -> dict:
  """Write ``arr`` as a layer, plan 4 mips onto an fq:// queue, drain it
  with ``execute --batch``, and compare every mip with ``oracle``."""
  path = f"file://{root}/{layer}"
  queue = f"fq://{root}/q_{layer}"
  write_layer(arr, path, resolution, chunk, encoding=encoding)
  _cli("image", "downsample", path, "--queue", queue, "--factor",
       _csv(FACTOR), "--num-mips", NUM_MIPS, "--chunk-size", _csv(dest_chunk))
  n_tasks = _enqueued(queue)
  _check(n_tasks >= 8, f"{name}: planned {n_tasks} tasks, wants at least 8")
  _ledger_reset()
  _drain(queue, n_tasks, batch)
  led = _ledger_check(kernels, platform)
  want = oracle(arr, FACTOR, NUM_MIPS)
  _check(want is not None, "native pooling library unavailable")
  for mip, exp in enumerate(want, start=1):
    _check(np.array_equal(read_mip(path, mip), exp),
           f"{name}: mip {mip} differs from {oracle.__name__}")
  return _line(name, arr.shape, str(arr.dtype), n_tasks, led,
               f"byte-identical to {oracle.__name__}, mips 1-{NUM_MIPS}")


def phase_image_downsample(root: str, img: np.ndarray, platform: str,
                           batch: int = 4, chunk=CHUNK,
                           dest_chunk=IMG_DEST_CHUNK) -> dict:
  from igneous_tpu.ops.oracle import native_downsample_with_averaging

  return _downsample_phase(
    "P1 image downsample", root, "img", img, (4, 4, 40), "raw",
    native_downsample_with_averaging, P1_KERNELS, platform, batch, chunk,
    dest_chunk,
  )


def phase_seg_downsample(root: str, labels: np.ndarray, platform: str,
                         batch: int = 4, chunk=CHUNK,
                         dest_chunk=SEG_DEST_CHUNK) -> dict:
  from igneous_tpu.ops.oracle import native_downsample_segmentation

  return _downsample_phase(
    "P2 segmentation downsample", root, "seg", labels, (8, 8, 40),
    "compressed_segmentation", native_downsample_segmentation, P2_KERNELS,
    platform, batch, chunk, dest_chunk,
  )


def phase_ccl(root: str, slab: np.ndarray, platform: str, chunk=CHUNK,
              shape=(448, 448, 448)) -> dict:
  """Writes the slab layer that P4 and P5 read too."""
  from igneous_tpu.ops.ccl import _ccl_native

  src = f"file://{root}/slab"
  write_layer(slab, src, (8, 8, 40), chunk,
              encoding="compressed_segmentation")
  dest = f"file://{root}/slab_ccl"
  _ledger_reset()
  _cli("image", "ccl", "auto", src, dest, "--shape", _csv(shape))
  led = _ledger_check(P3_KERNELS, platform)
  native = _ccl_native(slab, 6)
  _check(native is not None, "native CCL library unavailable")
  _check(_same_partition(read_mip(dest, 0), native[0]),
         "P3 partition differs from the native union-find")
  return _line("P3 ccl auto", slab.shape, "uint64", _n_tasks(root, shape),
               led, f"same partition as native union-find ({native[1]} comps)")


def _twin_forge(name, root, platform, knob, values, kernels, commands):
  """Run ``commands(out_dir)`` on the slab once per value of ``knob``;
  the first (device) run must show ``kernels`` in the device ledger and
  every run must write byte-identical files."""
  trees = {}
  for value in values:
    if value == values[0]:
      _ledger_reset()
    with _env(**{knob: value}):
      for args in commands(f"{name.split()[1]}_{value}"):
        _cli(*args)
    if value == values[0]:
      led = _ledger_check(kernels, platform)
    trees[value] = _tree(f"{root}/slab/{name.split()[1]}_{value}")
  _check(len(trees[values[0]]) > 0, f"{name}: no files written")
  _check(trees[values[0]] == trees[values[1]],
         f"{name}: {knob}={values[0]} output differs from {values[1]}")
  return led, len(trees[values[1]])


def phase_mesh(root: str, platform: str, shape=(448, 448, 448)) -> dict:
  src = f"file://{root}/slab"
  led, n_files = _twin_forge(
    "P4 mesh", root, platform, "IGNEOUS_MESH_EMIT", ("device", "host"),
    P4_KERNELS, lambda d: [
      ("mesh", "forge", src, "--mesh-dir", d, "--shape", _csv(shape),
       "--simplify-parallel", min(8, os.cpu_count() or 1)),
      ("mesh", "merge", src, "--mesh-dir", d),
    ],
  )
  return _line("P4 mesh forge+merge", _slab_shape(root), "uint64",
               _n_tasks(root, shape), led,
               f"byte-identical to host emit ({n_files} files)")


def phase_skeleton(root: str, platform: str, shape=(512, 512, 512)) -> dict:
  src = f"file://{root}/slab"
  led, n_files = _twin_forge(
    "P5 skel", root, platform, "IGNEOUS_EDT_BACKEND", ("device", "native"),
    P5_KERNELS, lambda d: [
      ("skeleton", "forge", src, "--skel-dir", d, "--shape", _csv(shape)),
      ("skeleton", "merge", src, "--skel-dir", d),
    ],
  )
  return _line("P5 skeleton forge+merge", _slab_shape(root), "uint64",
               _n_tasks(root, shape), led,
               f"byte-identical to native EDT ({n_files} files)")


def run_phases(root: str, seed: int, platform: str, sizes=None) -> None:
  """P1-P5 in order, one JSON line each. ``sizes`` overrides the shapes
  and chunks (a CPU test runs the same phases at a tiny size)."""
  z = dict(img=IMG_SHAPE, seg=SEG_SHAPE, slab=SLAB_SHAPE, chunk=CHUNK,
           img_dest_chunk=IMG_DEST_CHUNK, seg_dest_chunk=SEG_DEST_CHUNK,
           ccl_shape=(448, 448, 448), mesh_shape=(448, 448, 448),
           skel_shape=(512, 512, 512))
  z.update(sizes or {})
  img = make_image(z["img"], seed)
  _timed(lambda: phase_image_downsample(
    root, img, platform, chunk=z["chunk"], dest_chunk=z["img_dest_chunk"]))
  del img
  labels = make_labels(z["seg"], seed)
  _timed(lambda: phase_seg_downsample(
    root, labels, platform, chunk=z["chunk"], dest_chunk=z["seg_dest_chunk"]))
  sx, sy, sz = z["slab"]
  slab = np.asfortranarray(labels[:sx, :sy, :sz])
  del labels
  _timed(lambda: phase_ccl(root, slab, platform, chunk=z["chunk"],
                           shape=z["ccl_shape"]))
  _timed(lambda: phase_mesh(root, platform, shape=z["mesh_shape"]))
  _timed(lambda: phase_skeleton(root, platform, shape=z["skel_shape"]))


def _timed(phase) -> dict:
  t0 = time.perf_counter()
  line = phase()
  line["wall_s"] = time.perf_counter() - t0
  print(json.dumps(line), flush=True)
  return line


def _slab_shape(root: str):
  from igneous_tpu.volume import Volume

  return Volume(f"file://{root}/slab").meta.bounds(0).size3()


def _n_tasks(root: str, shape) -> int:
  return int(np.prod([-(-s // t) for s, t in zip(_slab_shape(root), shape)]))


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--seed", type=int, default=0)
  ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                  help="4: P1 only, --batch 8 over a 4-chip mesh")
  args = ap.parse_args(argv)

  import jax

  devices = jax.devices()
  platform = devices[0].platform
  if platform != "tpu":
    print(f"chip_smoke: no TPU (JAX platform {platform!r})", file=sys.stderr)
    return 2
  if len(devices) < args.chips:
    print(f"chip_smoke: {args.chips} chips asked, {len(devices)} present",
          file=sys.stderr)
    return 2

  from igneous_tpu.compile_cache import place_jax_cache

  print(json.dumps({"jax_cache": place_jax_cache()}), flush=True)
  root = tempfile.mkdtemp(prefix="chip_smoke-")
  t0 = time.perf_counter()
  try:
    if args.chips == 4:
      img = make_image(IMG_SHAPE, args.seed)
      line = _timed(lambda: phase_image_downsample(root, img, platform,
                                                   batch=8))
      _check(len(line["devices"]) == 4,
             f"P1 ran on {line['devices']}, not on all four chips")
    else:
      run_phases(root, args.seed, platform)
  finally:
    shutil.rmtree(root, ignore_errors=True)
  print(json.dumps({"total_wall_s": time.perf_counter() - t0}))
  dev = jax.devices()[0]
  print(json.dumps({"ok": True, "device": {
    "platform": dev.platform, "kind": dev.device_kind,
    "count": len(jax.devices()),
  }}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
