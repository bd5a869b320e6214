"""Device kernel parity smoke (ISSUE 11 CI step).

Runs each PR-11 device kernel on an 8-virtual-device CPU mesh and
asserts the acceptance criteria end to end:

  * byte identity vs the host/native reference per kernel:
      - ccl.tiled[scan]        vs the native C++ union-find NUMBERING
      - mesh.mc_emit           vs host fancy-indexed triangle emission
      - pooling.fused_pyramid  vs the per-level XLA pyramid walk
      - edt.sq_blocked         bitwise-deterministic across runs with
        background exactly zero, and matching the host envelope to float
        tolerance (host and device order the parabola arithmetic
        differently; EDT's byte-identity contract is per-backend)
  * every kernel's device.execute span landed in the journal;
  * the journal's recompile ledger carries an entry per kernel, with
    recompiles never exceeding distinct signatures.

Usage: python tools/kernel_smoke.py
"""

import os
import sys
import tempfile

# must precede the first jax import: the virtual mesh is a backend flag
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["IGNEOUS_TRACE_SAMPLE"] = "1"

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np

EXPECTED_KERNELS = (
  "ccl.tiled[scan]",
  "edt.sq_blocked",
  "mesh.mc_emit",
  "pooling.fused_pyramid[average]",
)


def check_ccl(rng):
  from igneous_tpu.ops import ccl as ccl_mod

  batch = np.stack([
    ((rng.random((24, 20, 12)) < 0.55)
     * rng.integers(1, 4, (24, 20, 12))).astype(np.uint32)
    for _ in range(8)
  ])
  os.environ["IGNEOUS_CCL_BACKEND"] = "device"
  dev = ccl_mod.connected_components_batch(batch, connectivity=26)
  os.environ["IGNEOUS_CCL_BACKEND"] = "native"
  for k in range(len(batch)):
    nat = ccl_mod.connected_components(batch[k], connectivity=26)
    assert np.array_equal(dev[k], nat), f"ccl chunk {k} numbering differs"
  print("ccl.tiled[scan]: byte-identical to native union-find (8 chunks)")


def check_edt(rng):
  from igneous_tpu.ops import edt as edt_mod

  batch = np.stack([
    ((rng.random((20, 16, 10)) < 0.7)
     * rng.integers(1, 3, (20, 16, 10))).astype(np.uint32)
    for _ in range(8)
  ])
  os.environ["IGNEOUS_EDT_BACKEND"] = "device"
  dev1 = edt_mod.edt_batch(batch, (4.0, 4.0, 40.0))
  dev2 = edt_mod.edt_batch(batch, (4.0, 4.0, 40.0))
  os.environ["IGNEOUS_EDT_BACKEND"] = "numpy"
  for k in range(len(batch)):
    assert np.array_equal(dev1[k], dev2[k]), f"edt chunk {k} nondeterministic"
    assert not dev1[k][batch[k] == 0].any(), f"edt chunk {k} bg nonzero"
    host = edt_mod.edt(batch[k], (4.0, 4.0, 40.0))
    np.testing.assert_allclose(dev1[k], host, rtol=1e-4, atol=1e-3)
  print("edt.sq_blocked: deterministic, zero background, matches host "
        "envelope (8 chunks)")


def check_mesh(rng):
  from igneous_tpu.ops import mesh as mesh_mod

  mask = rng.random((21, 17, 13)) > 0.5
  meshes = {}
  for be in ("host", "device"):
    os.environ["IGNEOUS_MESH_EMIT"] = be
    # twice: the first device call is the fresh-signature compile span;
    # the repeat emits the device.execute span the journal check needs
    meshes[be] = mesh_mod.marching_cubes(mask, anisotropy=(4.0, 4.0, 40.0))
    meshes[be] = mesh_mod.marching_cubes(mask, anisotropy=(4.0, 4.0, 40.0))
  assert np.array_equal(meshes["host"][0], meshes["device"][0]), (
    "mesh vertices differ"
  )
  assert np.array_equal(meshes["host"][1], meshes["device"][1]), (
    "mesh faces differ"
  )
  print(f"mesh.mc_emit: byte-identical to host emission "
        f"({len(meshes['device'][1])} faces)")


def check_pyramid(rng):
  from igneous_tpu.ops import pooling

  img = rng.integers(0, 255, (64, 64, 16)).astype(np.uint8)
  plain = pooling.downsample(img, (2, 2, 1), 3, method="average")
  # twice: first fused call compiles (device.compile span); the repeat
  # emits the device.execute span the journal check needs
  fused = pooling.downsample(
    img, (2, 2, 1), 3, method="average", mip_from=0
  )
  fused = pooling.downsample(
    img, (2, 2, 1), 3, method="average", mip_from=0
  )
  for l in range(3):
    assert np.array_equal(plain[l], fused[l]), f"pyramid mip {l} differs"
  print("pooling.fused_pyramid[average]: byte-identical to the plain walk "
        "(3 mips)")


def main():
  tmp = tempfile.mkdtemp(prefix="igneous-kernel-smoke-")
  jpath = f"file://{tmp}/journal"

  import jax

  assert jax.device_count() == 8, (
    f"expected the 8-virtual-device mesh, got {jax.device_count()}"
  )

  from igneous_tpu.observability import device as device_mod
  from igneous_tpu.observability import fleet
  from igneous_tpu.observability.journal import Journal

  device_mod.install()
  journal = Journal(jpath, worker_id="kernel-smoke")

  rng = np.random.default_rng(11)
  check_ccl(rng)
  check_edt(rng)
  check_mesh(rng)
  check_pyramid(rng)

  assert journal.flush(event="kernel-smoke"), "journal flush wrote nothing"

  records = fleet.load(jpath)
  spans = [r for r in records if r.get("kind") == "span"]
  execs = [s for s in spans if s.get("name") == "device.execute"]
  exec_kernels = {s.get("kernel") for s in execs}
  for kernel in EXPECTED_KERNELS:
    assert kernel in exec_kernels, (
      f"no device.execute span for {kernel} in the journal "
      f"(saw {sorted(exec_kernels)})"
    )

  ledgers = device_mod.device_ledgers(records)
  assert ledgers, "no device ledger records in the journal"
  ledger = next(iter(ledgers.values()))
  kernels = ledger["kernels"]
  for kernel in EXPECTED_KERNELS:
    assert kernel in kernels, (
      f"recompile ledger lacks {kernel} (saw {sorted(kernels)})"
    )
  assert ledger["recompiles"] >= len(EXPECTED_KERNELS)
  assert ledger["recompiles"] <= ledger["distinct_signatures"], (
    "recompiles must count distinct signatures only"
  )
  print(f"journal: {len(execs)} device.execute spans, "
        f"ledger kernels={sorted(kernels)} "
        f"recompiles={ledger['recompiles']}")
  print("KERNEL_SMOKE_OK")


if __name__ == "__main__":
  main()
