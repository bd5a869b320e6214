"""Ragged paged batching acceptance smoke (ISSUE 12 CI step).

Runs a mixed-shape campaign on an 8-virtual-device CPU mesh and asserts
the paged-batching acceptance criteria end to end:

  * a whole-layer downsample whose grid has FOUR ragged edge cells of
    three distinct shapes: every edge cell rides the paged pyramid
    (``paged_cutouts``, zero solo ``edge_cutouts``), and the stored mips
    are byte-identical to the numpy oracle;
  * a mixed-shape paged CCL fleet byte-identical to solo
    ``connected_components`` on the device backend;
  * fast-path ratio >= 0.95 for the campaign (batched + paged
    deliveries over all deliveries);
  * EXACTLY ONE device.compile span per paged kernel in the journal —
    the one-signature-per-campaign contract;
  * the pad-waste gauge is populated (page slack is measured, not
    hidden).

Usage: python tools/ragged_smoke.py
"""

import os
import sys
import tempfile

# must precede the first jax import: the virtual mesh is a backend flag
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["IGNEOUS_TRACE_SAMPLE"] = "1"
os.environ["IGNEOUS_POOL_HOST"] = "0"       # device pyramid on CPU
os.environ["IGNEOUS_CCL_BACKEND"] = "device"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

PAGED_KERNEL_PREFIXES = ("pooling.paged_pyramid[", "ccl.paged[")


def check_ragged_downsample(rng, tmp):
  from igneous_tpu.ops import oracle
  from igneous_tpu.parallel import batched_downsample, make_mesh
  from igneous_tpu.volume import Volume

  # 641x385 grid at 256x256 cells: 2 full cells + 4 ragged edge cells of
  # 3 distinct shapes (129x256, 256x129, 129x129) — a genuinely
  # mixed-shape campaign for one paged pyramid
  data = rng.integers(0, 255, (641, 385, 64)).astype(np.uint8)
  path = f"file://{tmp}/img"
  Volume.from_numpy(data, path)
  stats = batched_downsample(
    path, num_mips=2, shape=(256, 256, 64), batch_size=8,
    mesh=make_mesh(8), compress=None,
  )
  assert stats["batched_cutouts"] == 2, stats
  assert stats["paged_cutouts"] == 4, stats
  assert stats["edge_cutouts"] == 0, stats
  vol = Volume(path)
  exp = oracle.np_downsample_with_averaging(data, (2, 2, 1), 2)
  for m in (1, 2):
    out = vol.download(vol.meta.bounds(m), mip=m)
    assert np.array_equal(out[..., 0], exp[m - 1]), f"mip {m} differs"
  print("paged downsample: 4 ragged edge cells paged, "
        "mips byte-identical to the oracle")


def check_ragged_ccl(rng):
  from igneous_tpu.ops.ccl import connected_components
  from igneous_tpu.parallel.paged import paged_ccl

  labs = [
    ((rng.random(s) < 0.55) * rng.integers(1, 4, s)).astype(np.uint32)
    for s in [(40, 33, 21), (17, 3, 9), (64, 64, 32)]
  ]
  got = paged_ccl(labs, 6)
  for lab, g in zip(labs, got):
    solo = connected_components(lab, 6)
    assert np.array_equal(g, solo), f"ccl {lab.shape} numbering differs"
  print("paged ccl: byte-identical to solo device CCL (3 ragged shapes)")


def main():
  tmp = tempfile.mkdtemp(prefix="igneous-ragged-smoke-")
  jpath = f"file://{tmp}/journal"

  import jax

  assert jax.device_count() == 8, (
    f"expected the 8-virtual-device mesh, got {jax.device_count()}"
  )

  from igneous_tpu.observability import device as device_mod
  from igneous_tpu.observability import fleet
  from igneous_tpu.observability.journal import Journal

  device_mod.install()
  journal = Journal(jpath, worker_id="ragged-smoke")

  rng = np.random.default_rng(12)
  check_ragged_downsample(rng, tmp)

  # the campaign's fast-path ratio: every delivery rode a batched or
  # paged dispatch, none fell to the solo host path
  fp = dict(device_mod.LEDGER.fastpath)
  total = fp.get("batched", 0) + fp.get("host", 0)
  assert total >= 6, fp
  ratio = fp.get("batched", 0) / total
  assert ratio >= 0.95, f"fastpath_ratio {ratio:.3f} < 0.95 ({fp})"
  print(f"fastpath_ratio {ratio:.3f} (batched {fp.get('batched', 0)} / "
        f"total {total})")

  check_ragged_ccl(rng)

  snap = device_mod.LEDGER.snapshot()
  assert snap["pad_bytes"] > 0, "pad-waste gauge never recorded"
  assert snap["pad_waste_ratio"] is not None
  print(f"pad_waste_ratio {snap['pad_waste_ratio']} "
        f"({snap['pad_bytes']} pad bytes over {snap['real_bytes']} real)")

  assert journal.flush(event="ragged-smoke"), "journal flush wrote nothing"

  records = fleet.load(jpath)
  spans = [r for r in records if r.get("kind") == "span"]
  compiles = {}
  for s in spans:
    if s.get("name") == "device.compile":
      k = s.get("kernel")
      compiles[k] = compiles.get(k, 0) + 1
  paged_kernels = sorted(
    k for k in compiles
    if any(k.startswith(p) for p in PAGED_KERNEL_PREFIXES)
  )
  assert paged_kernels, (
    f"no paged-kernel compile spans in the journal (saw {sorted(compiles)})"
  )
  for k in paged_kernels:
    assert compiles[k] == 1, (
      f"{k} compiled {compiles[k]} times — the whole ragged campaign "
      "must share ONE signature"
    )
  print(f"journal: one device.compile per paged kernel {paged_kernels}")
  print("RAGGED_SMOKE_OK")


if __name__ == "__main__":
  main()
