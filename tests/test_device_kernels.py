"""Property-style device-vs-native parity sweeps for the PR-11 kernel
suite (tiled CCL, blocked EDT, device mesh emission, fused pyramid).

No hypothesis dependency: seeded generators sweep odd shapes,
anisotropies, connectivities, dtypes, and degenerate volumes, asserting
the contracts the dispatchers promise — byte identity for the integer
kernels (CCL roots/numbering, marching-cubes triangles) and exact
background zeros + documented float agreement for EDT.
"""

import numpy as np
import pytest
from scipy import ndimage

from igneous_tpu.ops import edt as edt_mod
from igneous_tpu.ops import mesh as mesh_mod
from igneous_tpu.ops import pallas_pooling, pooling
from igneous_tpu.ops.ccl import connected_components

# odd/degenerate extents: nothing aligned to tiles, lanes, or buckets
CCL_SHAPES = [(40, 33, 21), (17, 3, 9), (8, 8, 1), (1, 1, 5), (5, 31, 2)]


def _native_or_fail():
  from igneous_tpu.native import ccl_lib

  if ccl_lib() is None:
    pytest.fail("native CCL lib failed to build (toolchain present?)")


def _random_labels(rng, shape, dtype, density=0.55):
  lab = (rng.random(shape) < density) * rng.integers(1, 4, shape)
  lab = lab.astype(dtype)
  if np.issubdtype(np.dtype(dtype), np.unsignedinteger):
    # push a label past 2**32 so uint64 exercises the hi/lo handling
    if np.dtype(dtype).itemsize == 8:
      lab[lab == 3] = np.uint64(2**40 + 7)
  return lab


# ---------------------------------------------------------------------------
# CCL: tiled device kernel vs native two-pass union-find


@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
@pytest.mark.parametrize("connectivity", [6, 18, 26])
def test_ccl_device_native_identity_sweep(
  rng, monkeypatch, connectivity, dtype
):
  """Identical NUMBERING (not just partition) on every backend: the
  4-pass CCL protocol recomputes labels and relies on determinism."""
  _native_or_fail()
  for shape in CCL_SHAPES:
    lab = _random_labels(rng, shape, dtype)
    outs = {}
    for be in ("device", "native"):
      monkeypatch.setenv("IGNEOUS_CCL_BACKEND", be)
      outs[be] = connected_components(lab, connectivity=connectivity)
    assert np.array_equal(outs["device"], outs["native"]), (
      shape, connectivity, dtype,
    )


@pytest.mark.parametrize("algo", ["scan", "relax"])
def test_ccl_device_algos_match_native(rng, monkeypatch, algo):
  _native_or_fail()
  monkeypatch.setenv("IGNEOUS_CCL_DEVICE_ALGO", algo)
  lab = _random_labels(rng, (23, 19, 11), np.uint32)
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "device")
  dev = connected_components(lab, connectivity=26)
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "native")
  nat = connected_components(lab, connectivity=26)
  assert np.array_equal(dev, nat)


def test_ccl_degenerate_volumes(monkeypatch):
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "device")
  for shape in [(6, 5, 4), (1, 1, 1), (2, 2, 7)]:
    # all background
    out, n = connected_components(
      np.zeros(shape, np.uint32), return_N=True
    )
    assert n == 0 and not out.any()
    # one label filling the volume
    out, n = connected_components(
      np.full(shape, 9, np.uint64), return_N=True
    )
    assert n == 1 and (out == 1).all()


def test_ccl_pallas_tile_resolve_parity(rng):
  """The Pallas tile resolve (interpret mode, asked for here) must give
  the identical local roots as the lax resolve — same fixpoint, same
  numbering."""
  import jax

  from igneous_tpu.ops import pallas_ccl
  from igneous_tpu.ops.ccl import _dense_relabel, _tile_resolve

  lab = _random_labels(rng, (16, 16, 8), np.uint32)
  zyx = _dense_relabel(lab).transpose(2, 1, 0)
  # (z, y, x) = (8, 16, 16) cut into (2, 4, 8) tiles
  labt = np.ascontiguousarray(
    zyx.reshape(4, 2, 4, 4, 2, 8).transpose(0, 2, 4, 1, 3, 5)
    .reshape(32, 2, 4, 8)
  )
  lax_roots = np.asarray(jax.jit(
    lambda t: _tile_resolve(t, 6, "scan"))(labt))
  pallas_roots = np.asarray(
    pallas_ccl.tile_resolve(labt, 6, interpret=True))
  fg = labt != 0
  assert np.array_equal(lax_roots[fg], pallas_roots[fg])


def test_ccl_tile_smaller_than_volume_and_larger(rng, monkeypatch):
  """Tile-boundary merge is exercised both when tiles subdivide the
  volume and when one tile covers it (early-exit path)."""
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "device")
  lab = _random_labels(rng, (12, 10, 8), np.uint32)
  exp, _ = ndimage.label(
    lab != 0, structure=ndimage.generate_binary_structure(3, 1)
  )
  outs = []
  for tile in ("1,2,4", "64,64,64"):
    monkeypatch.setenv("IGNEOUS_CCL_TILE", tile)
    out = connected_components(lab * 0 + (lab != 0), connectivity=6)
    outs.append(out)
  assert np.array_equal(outs[0], outs[1])
  # partition agrees with scipy on the binarized volume
  fg = outs[0] != 0
  assert np.array_equal(fg, exp != 0)


# ---------------------------------------------------------------------------
# EDT: blocked device kernel vs native/numpy host paths


@pytest.mark.parametrize(
  "anisotropy", [(1.0, 1.0, 1.0), (4.0, 4.0, 40.0), (16.0, 16.0, 40.0)]
)
def test_edt_device_vs_host_sweep(rng, monkeypatch, anisotropy):
  for shape in [(29, 17, 13), (8, 8, 1), (3, 3, 3)]:
    lab = _random_labels(rng, shape, np.uint32, density=0.7)
    monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "device")
    dev = edt_mod.edt(lab, anisotropy)
    monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "numpy")
    host = edt_mod.edt(lab, anisotropy)
    # background is exactly zero on every backend
    assert not dev[lab == 0].any()
    assert dev.dtype == np.float32
    # device vs host agree to fma-reassociation tolerance (the two
    # backends order the parabola arithmetic differently; ops/edt.py
    # documents the contract as per-backend bitwise determinism)
    np.testing.assert_allclose(dev, host, rtol=1e-4, atol=1e-3)


@pytest.mark.parametrize(
  "anisotropy", [(1.0, 1.0, 1.0), (8.0, 8.0, 40.0), (4.5, 4.5, 33.0)]
)
def test_edt_device_equals_native(rng, monkeypatch, anisotropy):
  """The device EDT rounds each distance once, like the native kernel's
  double arithmetic, so skeletons forged on either field are identical."""
  # dense foreground: long runs, so the y and z passes carry heights
  # that normalizing by w^2 cannot represent exactly
  lab = (rng.random((40, 40, 16)) < 0.97).astype(np.uint8)
  out = {}
  for backend in ("device", "native"):
    monkeypatch.setenv("IGNEOUS_EDT_BACKEND", backend)
    out[backend] = edt_mod.edt(lab, anisotropy, black_border=True)
  assert np.array_equal(out["device"], out["native"])


def test_edt_device_black_border_and_determinism(rng, monkeypatch):
  monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "device")
  lab = _random_labels(rng, (21, 15, 9), np.uint32, density=0.8)
  a = edt_mod.edt(lab, (4.0, 4.0, 40.0), black_border=True)
  b = edt_mod.edt(lab, (4.0, 4.0, 40.0), black_border=True)
  assert np.array_equal(a, b)  # bitwise deterministic
  assert a.shape == lab.shape


def test_edt_batch_matches_solo_device(rng, monkeypatch):
  """edt_batch on the device backend must equal per-chunk solo edt()
  bitwise — same kernel, batched dispatch."""
  monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "device")
  batch = np.stack(
    [_random_labels(rng, (16, 12, 10), np.uint32) for _ in range(3)]
  )
  outs = edt_mod.edt_batch(batch, (4.0, 4.0, 40.0))
  for k in range(len(batch)):
    solo = edt_mod.edt(batch[k], (4.0, 4.0, 40.0))
    np.testing.assert_allclose(outs[k], solo, rtol=1e-5, atol=1e-4)
    assert not outs[k][batch[k] == 0].any()


def test_edt_backend_env_validated(monkeypatch):
  monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "cuda")
  with pytest.raises(ValueError, match="IGNEOUS_EDT_BACKEND"):
    edt_mod.edt(np.ones((2, 2, 2), np.uint32))


# ---------------------------------------------------------------------------
# Mesh: device triangle emission vs host emission, byte identity


MESH_SHAPES = [(16, 16, 16), (13, 9, 21), (5, 5, 5), (33, 17, 8), (2, 2, 2)]


@pytest.mark.parametrize("anisotropy", [(1.0, 1.0, 1.0), (4.0, 4.0, 40.0)])
def test_mesh_device_emit_byte_identity(rng, monkeypatch, anisotropy):
  for shape in MESH_SHAPES:
    mask = rng.random(shape) > 0.5
    meshes = {}
    for be in ("host", "device"):
      monkeypatch.setenv("IGNEOUS_MESH_EMIT", be)
      meshes[be] = mesh_mod.marching_cubes(mask, anisotropy=anisotropy)
    hv, hf = meshes["host"]
    dv, df = meshes["device"]
    assert np.array_equal(hv, dv), shape
    assert np.array_equal(hf, df), shape


def test_mesh_device_emit_sphere_and_empty(monkeypatch):
  x, y, z = np.mgrid[:24, :24, :24]
  sphere = ((x - 12) ** 2 + (y - 12) ** 2 + (z - 12) ** 2) < 81
  for mask in [sphere, np.zeros((7, 7, 7), bool)]:
    meshes = {}
    for be in ("host", "device"):
      monkeypatch.setenv("IGNEOUS_MESH_EMIT", be)
      meshes[be] = mesh_mod.marching_cubes(mask)
    assert np.array_equal(meshes["host"][0], meshes["device"][0])
    assert np.array_equal(meshes["host"][1], meshes["device"][1])


def test_mesh_device_emit_batch_identity(rng, monkeypatch):
  masks = np.stack([
    rng.random((11, 13, 7)) > 0.5,
    np.zeros((11, 13, 7), bool),  # empty member
    rng.random((11, 13, 7)) > 0.8,
  ])
  meshes = {}
  for be in ("host", "device"):
    monkeypatch.setenv("IGNEOUS_MESH_EMIT", be)
    meshes[be] = mesh_mod.marching_cubes_batch(masks)
  for (hv, hf), (dv, df) in zip(meshes["host"], meshes["device"]):
    assert np.array_equal(hv, dv)
    assert np.array_equal(hf, df)


def test_mesh_emit_env_validated(monkeypatch):
  monkeypatch.setenv("IGNEOUS_MESH_EMIT", "gpu")
  mask = np.zeros((5, 5, 5), bool)
  mask[1:4, 1:4, 1:4] = True  # real surface so the emit dispatcher runs
  with pytest.raises(ValueError, match="IGNEOUS_MESH_EMIT"):
    mesh_mod.marching_cubes(mask)


# ---------------------------------------------------------------------------
# Fused pyramid: one pallas dispatch vs iterated pooling vs XLA walk


@pytest.mark.parametrize(
  "method,dtype",
  [("average", np.uint8), ("mode", np.uint32),
   ("average", np.int16), ("mode", np.uint16)],
)
def test_pyramid_fused_parity(rng, method, dtype):
  if not pallas_pooling.available():
    pytest.skip("pallas unavailable")
  for shape in [(64, 64, 8), (33, 17, 5), (100, 70, 3)]:
    img = rng.integers(0, 5, shape).astype(dtype)
    levels = 3
    fused = pallas_pooling.pyramid2x2x1(
      img, levels, method=method, interpret=True
    )
    cur, iters = img, []
    for _ in range(levels):
      cur = pallas_pooling.pool2x2x1(cur, method=method, interpret=True)
      iters.append(cur)
    xla = pooling.downsample(img, (2, 2, 1), levels, method=method)
    for l in range(levels):
      assert fused[l].shape == iters[l].shape, (shape, l)
      assert np.array_equal(fused[l], iters[l]), (shape, dtype, l)
      assert np.array_equal(fused[l], xla[l]), (shape, dtype, l)


def test_downsample_mip_from_identity(rng):
  """mip_from only renames the kernel span and stamps attrs — the mips
  themselves must be bitwise what the plain call produces."""
  img = rng.integers(0, 1000, (45, 31, 12)).astype(np.uint32)
  a = pooling.downsample(img, (2, 2, 1), 3, method="mode")
  b = pooling.downsample(img, (2, 2, 1), 3, method="mode", mip_from=2)
  for x, y in zip(a, b):
    assert np.array_equal(x, y)
  u = rng.integers(0, 2**40, (24, 18, 6)).astype(np.uint64)
  a = pooling.downsample(u, (2, 2, 2), 2, method="mode")
  b = pooling.downsample(u, (2, 2, 2), 2, method="mode", mip_from=1)
  for x, y in zip(a, b):
    assert np.array_equal(x, y)
