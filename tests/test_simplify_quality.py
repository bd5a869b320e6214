"""Quantitative simplification-quality budgets (VERDICT r3 item 6).

The structural tests in test_mesh.py pin corner preservation, open-border
stability, and determinism; these pin the QUANTITATIVE contract on
analytic shapes (the pyfqmr role at reference multires.py:308-359 and the
zmesh simplifier call at reference mesh.py:371-383):

  * the triangle count actually reaches the requested reduction factor
    (within tolerance) when the error budget allows it;
  * no simplified vertex deviates from the analytic surface by more than
    ``max_error`` physical units (+ the half-voxel marching-cubes
    discretization), for both engines (native QEM and the clustering
    fallback);
  * the LOD ladder generate_lods() builds for multires meshes keeps
    shrinking by ~the requested reduction per level.

Failing any budget fails CI.
"""

import numpy as np
import pytest

from igneous_tpu.mesh_io import Mesh, simplify
from igneous_tpu.mesh_multires import generate_lods
from igneous_tpu.ops.mesh import marching_cubes


def _require_native_for_qem(placement):
  if placement != "qem":
    return
  from igneous_tpu.native import simplify_lib

  if simplify_lib() is None:
    pytest.skip("native simplifier unavailable")


def sphere_mesh(r=24.0, n=64):
  g = np.indices((n, n, n)).astype(np.float32) - (n - 1) / 2.0
  mask = (np.sqrt((g**2).sum(0)) < r).astype(np.uint8)
  v, f = marching_cubes(mask)
  center = np.array([(n - 1) / 2.0] * 3, np.float32)
  return Mesh(v, f), center


def cylinder_mesh(r=14.0, h=44, n=48):
  g = np.indices((n, n, n)).astype(np.float32)
  cx = cy = (n - 1) / 2.0
  z0, z1 = (n - h) // 2, (n + h) // 2
  radial = np.sqrt((g[0] - cx) ** 2 + (g[1] - cy) ** 2)
  mask = ((radial < r) & (g[2] >= z0) & (g[2] < z1)).astype(np.uint8)
  v, f = marching_cubes(mask)
  return Mesh(v, f), (cx, cy, float(z0), float(z1), r)


def sphere_deviation(mesh, center, r):
  return np.abs(np.linalg.norm(mesh.vertices - center, axis=1) - r).max()


def cylinder_deviation(mesh, params):
  cx, cy, z0, z1, r = params
  v = mesh.vertices
  radial = np.sqrt((v[:, 0] - cx) ** 2 + (v[:, 1] - cy) ** 2)
  # distance to the capped-cylinder surface (side wall or either cap,
  # accounting for the rim where they meet)
  side = np.abs(radial - r)
  inside_z = np.clip(np.maximum(z0 - v[:, 2], v[:, 2] - (z1 - 1)), 0, None)
  side_dist = np.sqrt(side**2 + inside_z**2)
  cap = np.minimum(np.abs(v[:, 2] - z0), np.abs(v[:, 2] - (z1 - 1)))
  outside_r = np.clip(radial - r, 0, None)
  cap_dist = np.sqrt(cap**2 + outside_r**2)
  return np.minimum(side_dist, cap_dist).max()


# marching cubes tracks the voxelized surface, which sits within ~0.87
# voxel units (half the cell diagonal) of the analytic one
VOXEL_SLOP = 0.9


@pytest.mark.parametrize("placement", ["qem", "centroid"])
def test_sphere_reduction_factor_and_deviation(placement):
  _require_native_for_qem(placement)
  r = 24.0
  mesh, center = sphere_mesh(r=r)
  base = sphere_deviation(mesh, center, r)
  assert base <= VOXEL_SLOP  # sanity: the un-simplified surface is tight

  for factor, max_err in ((4, 2.0), (16, 4.0)):
    out = simplify(
      mesh, reduction_factor=factor, max_error=max_err, placement=placement
    )
    got_factor = len(mesh.faces) / max(len(out.faces), 1)
    # the production engine (QEM) must reach the requested factor within
    # ~30% when the budget allows; the clustering fallback's cell size is
    # capped at max_error, so its landing point is bounded by the budget,
    # not the factor — it must still reduce meaningfully
    floor = factor / 1.3 if placement == "qem" else 1.25
    assert got_factor >= floor, (
      f"{placement} factor {factor}: got {got_factor:.1f}x"
    )
    dev = sphere_deviation(out, center, r)
    assert dev <= max_err + VOXEL_SLOP, (
      f"{placement} factor {factor}: deviation {dev:.2f} > "
      f"{max_err}+{VOXEL_SLOP}"
    )


@pytest.mark.parametrize("placement", ["qem", "centroid"])
def test_cylinder_deviation_budget(placement):
  _require_native_for_qem(placement)
  mesh, params = cylinder_mesh()
  base = cylinder_deviation(mesh, params)
  assert base <= VOXEL_SLOP + 0.5  # rim voxels cut both surfaces

  out = simplify(mesh, reduction_factor=8, max_error=2.0, placement=placement)
  got_factor = len(mesh.faces) / max(len(out.faces), 1)
  assert got_factor >= (8 / 1.3 if placement == "qem" else 1.25)
  dev = cylinder_deviation(out, params)
  assert dev <= 2.0 + VOXEL_SLOP + 0.5, f"{placement}: deviation {dev:.2f}"


def test_error_bound_binds_before_factor():
  """With a tiny error budget the reduction must STOP at the budget, not
  chase the factor: the bound is the contract, the factor is a wish."""
  _require_native_for_qem("qem")
  r = 24.0
  mesh, center = sphere_mesh(r=r)
  out = simplify(mesh, reduction_factor=1000, max_error=0.5, placement="qem")
  dev = sphere_deviation(out, center, r)
  assert dev <= 0.5 + VOXEL_SLOP
  # and it must NOT have collapsed to the 4-face floor chasing 1000x
  assert len(out.faces) > len(mesh.faces) / 200


def test_lod_ladder_shrinks_per_level():
  """generate_lods: each level reduces ~4x until the floor; the table the
  multires manifests advertise must reflect real geometric decimation."""
  mesh, center = sphere_mesh(r=24.0)
  lods = generate_lods(mesh, num_lods=4, reduction=4.0)
  assert len(lods) == 4
  tris = [len(m.faces) for m in lods]
  assert tris[0] == len(mesh.faces)
  for a, b in zip(tris, tris[1:]):
    if a <= 64:  # floor: tiny meshes may stop reducing
      continue
    assert b <= a / 2.0, f"LOD step {a}->{b} reduced less than 2x"
  # every LOD stays glued to the sphere within its implied error scale
  for m in lods[1:]:
    assert sphere_deviation(m, center, 24.0) <= 6.0
