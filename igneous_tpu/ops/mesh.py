"""Device isosurface extraction — the zmesh (marching cubes) equivalent.

Replaces the reference's zmesh C++ mesher for MeshTask
(/root/reference/igneous/tasks/mesh/mesh.py:245 ``Mesher.mesh(data)``).

Two meshers share one TPU-first skeleton (two-pass count/emit, SURVEY.md
§7 "hard parts": kernel 1 computes per-cell cases + triangle counts on
device, O(voxels); the host then touches only the O(surface) slot set):

* ``marching_cubes`` — true 256-case MC, zmesh's algorithm and the
  production default. The case tables are GENERATED at import by walking
  each case's surface loops over the cube's faces (segments per face,
  chained through the shared crossing edges, fan-triangulated), with the
  "separate inside corners" rule on ambiguous faces — a per-face rule, so
  adjacent cells always agree and the surface is watertight by
  construction. No hand-copied 256-entry tables.
* ``marching_tetrahedra`` — 6-tet decomposition with 16-case tables; kept
  as an independent second implementation (its output doubles as a
  cross-check oracle: same voxel volume, same topology, ~2x triangles).

Both emit vertices at cube-edge midpoints (the 0.5 iso-level of the
binary mask, like zmesh on binary masks).
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import knobs

# cube corner i sits at offset (i&1, i>>1&1, i>>2&1)
CORNER_OFFSETS = np.array(
  [[(i >> d) & 1 for d in range(3)] for i in range(8)], dtype=np.float32
)
# 6-tet decomposition sharing the 0-7 diagonal
TETS = np.array(
  [
    (0, 1, 3, 7),
    (0, 3, 2, 7),
    (0, 2, 6, 7),
    (0, 6, 4, 7),
    (0, 4, 5, 7),
    (0, 5, 1, 7),
  ],
  dtype=np.int32,
)


def _build_tables():
  """NTRIS[tet, case] and EDGES[tet, case, tri, vtx, 2] (cube corner pairs).

  Triangles are oriented so normals point from inside (mask=1) to outside.
  """
  ntris = np.zeros((6, 16), dtype=np.int32)
  edges = np.zeros((6, 16, 2, 3, 2), dtype=np.int32)

  for t, tet in enumerate(TETS):
    pts = CORNER_OFFSETS[tet]  # (4, 3) canonical coords
    for case in range(16):
      inside = [j for j in range(4) if (case >> j) & 1]
      outside = [j for j in range(4) if not (case >> j) & 1]
      tris = []  # list of [(a_local, b_local) x3]
      if len(inside) == 1:
        v = inside[0]
        tris.append([(v, outside[0]), (v, outside[1]), (v, outside[2])])
      elif len(inside) == 3:
        v = outside[0]
        tris.append([(inside[0], v), (inside[1], v), (inside[2], v)])
      elif len(inside) == 2:
        i0, i1 = inside
        o0, o1 = outside
        # cut quad in cyclic order
        quad = [(i0, o0), (i1, o0), (i1, o1), (i0, o1)]
        tris.append([quad[0], quad[1], quad[2]])
        tris.append([quad[0], quad[2], quad[3]])

      if not tris:
        continue
      in_centroid = pts[inside].mean(axis=0) if inside else pts.mean(axis=0)
      for k, tri in enumerate(tris):
        mids = np.array([(pts[a] + pts[b]) / 2.0 for a, b in tri])
        n = np.cross(mids[1] - mids[0], mids[2] - mids[0])
        outward = mids.mean(axis=0) - in_centroid
        if np.dot(n, outward) < 0:
          tri = [tri[0], tri[2], tri[1]]
        for v, (a, b) in enumerate(tri):
          edges[t, case, k, v, 0] = tet[a]
          edges[t, case, k, v, 1] = tet[b]
      ntris[t, case] = len(tris)
  return ntris, edges


NTRIS_TABLE, EDGES_TABLE = _build_tables()


def _case_list(mask: jnp.ndarray):
  """mask: (z, y, x) uint8 → list of 6 per-cell case-id arrays (cz, cy, cx).

  Kept as separate per-tet arrays: stacking shifted slices into one big
  array and reshaping it compiles pathologically slowly on XLA CPU, and
  per-tet arrays fuse fine on TPU anyway.
  """
  sz, sy, sx = mask.shape
  cz, cy, cx = sz - 1, sy - 1, sx - 1
  corners = []
  for i in range(8):
    ox, oy, oz = i & 1, (i >> 1) & 1, (i >> 2) & 1
    corners.append(mask[oz : oz + cz, oy : oy + cy, ox : ox + cx].astype(jnp.int32))
  cases = []
  for tet in TETS:
    c = (
      corners[tet[0]]
      + corners[tet[1]] * 2
      + corners[tet[2]] * 4
      + corners[tet[3]] * 8
    )
    cases.append(c)
  return cases


@jax.jit
def _count_kernel(mask: jnp.ndarray):
  """→ (6 per-tet case arrays, 6 per-tet triangle counts, total).

  Triangle count per tet case derives arithmetically from the popcount:
  min(bits, 4 - bits) — no table gather needed on device."""
  cases = _case_list(mask)
  per_tet = []
  total = jnp.int32(0)
  for c in cases:
    b = (c & 1) + ((c >> 1) & 1) + ((c >> 2) & 1) + ((c >> 3) & 1)
    n = jnp.minimum(b, 4 - b)
    per_tet.append(n)
    total = total + jnp.sum(n, dtype=jnp.int32)
  return tuple(cases), tuple(per_tet), total


def _emit_host(cases_np, per_np, shape, real_cells=None) -> np.ndarray:
  """Host-side triangle emission: O(triangles) table lookups in numpy.

  The device pass is O(voxels) (case + count); everything below touches
  only the ~surface-sized slot set, where numpy fancy indexing is faster
  than compiling a device gather program per capacity.

  ``real_cells``: (cx, cy, cz) cell counts of the un-padded mask — cells in
  the shape-bucketing pad ring are dropped (their triangles are artifacts
  of the replicate padding).
  Returns (n, 3, 3) vertex coords in (x, y, z) voxel units.
  """
  sz, sy, sx = shape
  cz, cy, cx = sz - 1, sy - 1, sx - 1
  per = np.stack([p.reshape(-1) for p in per_np], axis=-1)  # (ncells, 6)

  # nonzero keeps allocation proportional to the surface, not the volume
  cell1, tet1 = np.nonzero(per >= 1)
  cell2, tet2 = np.nonzero(per >= 2)
  if real_cells is not None:
    # pad-ring filter on the O(surface) nonzero set only
    rx, ry, rz = real_cells

    def in_real(cell):
      return (
        (cell % cx < rx) & ((cell // cx) % cy < ry)
        & (cell // (cy * cx) < rz)
      )

    k1, k2 = in_real(cell1), in_real(cell2)
    cell1, tet1 = cell1[k1], tet1[k1]
    cell2, tet2 = cell2[k2], tet2[k2]
  cell = np.concatenate([cell1, cell2])
  tet = np.concatenate([tet1, tet2])
  tri = np.concatenate([
    np.zeros(len(cell1), dtype=np.int64),
    np.ones(len(cell2), dtype=np.int64),
  ])

  cases_flat = np.stack([c.reshape(-1) for c in cases_np], axis=-1)  # (ncells, 6)
  case = cases_flat[cell, tet]
  pair = EDGES_TABLE[tet, case, tri]  # (n, 3, 2)
  mid = (CORNER_OFFSETS[pair[..., 0]] + CORNER_OFFSETS[pair[..., 1]]) / 2.0

  base = np.stack(
    [cell % cx, (cell // cx) % cy, cell // (cy * cx)], axis=-1
  ).astype(np.float32)  # xyz
  return base[:, None, :] + mid


def _bucket_shape(orig) -> Tuple[int, int, int]:
  """Power-of-two shape bucket so the count kernel compiles a bounded set
  of variants (and batch members can share one compiled program)."""
  return tuple(max(8, 1 << int(np.ceil(np.log2(s)))) for s in orig)


def _pad_to_bucket(mask: np.ndarray, bucket) -> np.ndarray:
  if tuple(mask.shape) == tuple(bucket):
    return mask
  # replicate padding adds no surface inside the real region; artifact
  # triangles in the pad ring are filtered by cell coordinate
  return np.pad(
    mask, tuple((0, b - s) for b, s in zip(bucket, mask.shape)), mode="edge"
  )


def _weld(tris, anisotropy, offset):
  """(n, 3, 3) half-lattice triangles → welded (verts, faces), physical."""
  from ..mesh_io import drop_degenerate_faces

  lattice = np.round(tris.reshape(-1, 3) * 2.0).astype(np.int64)
  # scalar-key unique: ~5x faster than unique(axis=0)'s void-view row
  # sort. x occupies the top bits so the sort order (and therefore the
  # vertex numbering) is identical to lexicographic row order. 21 bits
  # per axis covers half-lattice coords to 2^21 (volumes to ~1M voxels
  # per side — far beyond any task cutout).
  key = (lattice[:, 0] << 42) | (lattice[:, 1] << 21) | lattice[:, 2]
  ukey, inverse = np.unique(key, return_inverse=True)
  uniq = np.empty((len(ukey), 3), dtype=np.int64)
  uniq[:, 0] = ukey >> 42
  uniq[:, 1] = (ukey >> 21) & 0x1FFFFF
  uniq[:, 2] = ukey & 0x1FFFFF
  vertices = uniq.astype(np.float32) / 2.0
  faces = inverse.reshape(-1, 3).astype(np.uint32)
  faces = drop_degenerate_faces(faces)
  faces = _cancel_coincident_pairs(faces)
  # prune vertices orphaned by the cancellation
  used = np.zeros(len(vertices), dtype=bool)
  used[faces.reshape(-1)] = True
  if not used.all():
    remap = np.cumsum(used) - 1
    vertices = vertices[used]
    faces = remap[faces.astype(np.int64)].astype(np.uint32)
  vertices = (vertices + np.asarray(offset, dtype=np.float32)) * np.asarray(
    anisotropy, dtype=np.float32
  )
  return vertices, faces


def _cancel_coincident_pairs(faces: np.ndarray) -> np.ndarray:
  """Drop pairs of coincident triangles (same vertex triple).

  Marching cubes' fan triangulation can place a diagonal in a cell face's
  plane; when the loop has further vertices on that same face, a whole fan
  triangle can lie IN the shared face and the neighboring cell emits the
  mirrored copy — a zero-volume fin. The pair cancels exactly: removing
  both lowers each boundary edge's face count by 2, so closedness (even
  counts) is preserved. An odd-multiplicity group (fin pair + a real
  surface triangle) keeps one member of the MAJORITY winding — the real
  triangle's orientation appears twice (its own copy plus the matching
  fin half), so the survivor faces outward.
  """
  if len(faces) == 0:
    return faces
  tri = np.sort(faces, axis=1).astype(np.int64)
  if int(tri[:, 2].max()) < (1 << 21):
    # scalar-key grouping (fast path): collision-free while every vertex
    # index fits 21 bits...
    key = (tri[:, 0] << 42) | (tri[:, 1] << 21) | tri[:, 2]
    _, inv, cnt = np.unique(key, return_inverse=True, return_counts=True)
  else:
    # ...multi-million-vertex meshes fall back to exact row grouping
    _, inv, cnt = np.unique(tri, axis=0, return_inverse=True,
                            return_counts=True)
  if (cnt <= 1).all():
    return faces
  keep = cnt[inv] == 1
  # group duplicate rows by one argsort instead of rescanning per group
  dup_ids = np.flatnonzero(~keep)
  order = dup_ids[np.argsort(inv[dup_ids], kind="stable")]
  ginv = inv[order]
  starts = np.flatnonzero(np.concatenate([[True], ginv[1:] != ginv[:-1]]))
  ends = np.concatenate([starts[1:], [len(order)]])
  # winding parity: (a,b,c) is an even permutation of its sorted triple
  perm = np.argsort(faces[order], axis=1)
  even = (
    (perm == (0, 1, 2)).all(axis=1)
    | (perm == (1, 2, 0)).all(axis=1)
    | (perm == (2, 0, 1)).all(axis=1)
  )
  for s, e in zip(starts, ends):
    if (e - s) % 2 == 0:
      continue
    grp_even = even[s:e]
    maj = grp_even if grp_even.sum() * 2 > (e - s) else ~grp_even
    keep[order[s + int(np.flatnonzero(maj)[0])]] = True
  return faces[keep]


_EMPTY_MESH = (
  np.zeros((0, 3), dtype=np.float32), np.zeros((0, 3), dtype=np.uint32)
)

_COUNT_EXECUTOR = None


def _isosurface_batch(
  masks, anisotropy, offsets, executor, batch_size, get_executor, emit_k
):
  """Shared batched count/emit orchestration for both meshers.

  Masks are padded into power-of-two shape buckets and each bucket's
  members run the count pass as ONE shard_map'd device dispatch with the
  mask axis partitioned over the mesh (VERDICT round-1 item 3: the mesh
  forge's per-voxel stage in the batched path). Emission stays host-side
  per mask (O(surface)); ``emit_k(results, k, shape, real_cells)``
  unpacks member k of the kernel outputs into a triangle array.
  """
  if offsets is None:
    offsets = [(0.0, 0.0, 0.0)] * len(masks)
  out = [None] * len(masks)
  groups = {}
  for i, m in enumerate(masks):
    if m.ndim != 3:
      raise ValueError("masks must be 3d")
    groups.setdefault(_bucket_shape(m.shape), []).append(i)

  if executor is None:
    # one module-level executor per kernel: its jit cache covers every
    # shape bucket
    executor = get_executor()

  for bucket, idxs in groups.items():
    # cap group size: an uncapped bucket (e.g. hundreds of labels sharing
    # one shape bucket) would materialize a (K, *bucket) stack at once
    for g0 in range(0, len(idxs), batch_size):
      gidx = idxs[g0 : g0 + batch_size]
      batch = np.stack([
        np.ascontiguousarray(
          _pad_to_bucket(masks[i].astype(np.uint8), bucket).transpose(2, 1, 0)
        )
        for i in gidx
      ])  # (K, z, y, x)
      results = executor(batch)
      totals = results[-1]
      for k, i in enumerate(gidx):
        if int(totals[k]) == 0:
          out[i] = _EMPTY_MESH
          continue
        orig = masks[i].shape
        tris = emit_k(
          results, k, batch.shape[1:],
          (orig[0] - 1, orig[1] - 1, orig[2] - 1),
        )
        if len(tris) == 0:
          out[i] = _EMPTY_MESH
          continue
        out[i] = _weld(tris, anisotropy, offsets[i])
  return out


def _mt_executor():
  global _COUNT_EXECUTOR
  if _COUNT_EXECUTOR is None:
    from ..parallel.executor import BatchKernelExecutor

    _COUNT_EXECUTOR = BatchKernelExecutor(_count_kernel)
  return _COUNT_EXECUTOR


def _mt_emit_k(results, k, shape, real_cells):
  cases_b, per_b, _ = results
  return _emit_host(
    [c[k] for c in cases_b], [p[k] for p in per_b], shape,
    real_cells=real_cells,
  )


def marching_tetrahedra_batch(
  masks, anisotropy=(1.0, 1.0, 1.0), offsets=None, executor=None,
  batch_size: int = 16,
):
  """Batched isosurface extraction: list of binary (x, y, z) masks →
  list of (vertices, faces), identical to per-mask marching_tetrahedra."""
  return _isosurface_batch(
    masks, anisotropy, offsets, executor, batch_size,
    _mt_executor, _mt_emit_k,
  )


def marching_tetrahedra(
  mask: np.ndarray, anisotropy=(1.0, 1.0, 1.0), offset=(0.0, 0.0, 0.0)
) -> Tuple[np.ndarray, np.ndarray]:
  """Binary mask (x, y, z) → (vertices (V,3) float32, faces (F,3) uint32).

  Vertices are in physical units: (voxel_coord + offset) * anisotropy.
  The surface is watertight over the mask's interior; to close a surface
  at the array boundary, pad the mask with a zero shell first (MeshTask
  handles dataset-edge policy).
  """
  if mask.ndim != 3:
    raise ValueError("mask must be 3d")
  orig = mask.shape
  bucket = _bucket_shape(orig)
  mask = _pad_to_bucket(mask, bucket)
  dev = jnp.asarray(
    np.ascontiguousarray(mask.astype(np.uint8).transpose(2, 1, 0))
  )  # (z, y, x)
  cases, per_tet, total = _count_kernel(dev)
  if int(total) == 0:
    return (
      np.zeros((0, 3), dtype=np.float32),
      np.zeros((0, 3), dtype=np.uint32),
    )
  cases_np = [np.asarray(c) for c in cases]
  per_np = [np.asarray(p) for p in per_tet]
  tris = _emit_host(
    cases_np, per_np, dev.shape,
    real_cells=(orig[0] - 1, orig[1] - 1, orig[2] - 1),
  )  # (n, 3, 3) xyz
  if len(tris) == 0:
    return _EMPTY_MESH
  return _weld(tris, anisotropy, offset)


# ---------------------------------------------------------------------------
# marching cubes (256-case), tables generated by surface-loop walking


def _build_mc_tables():
  """Generate the 256-case MC tables programmatically.

  For each corner-insideness case, surface segments are produced per cube
  face (0, 1, or 2 segments from the face's 4 crossing pattern; ambiguous
  faces — diagonal inside corners — always SEPARATE the inside corners, a
  rule that depends only on the shared face so adjacent cells agree and
  the global surface is watertight), chained into closed loops through
  the crossing cube edges (each crossing edge borders exactly two faces),
  and fan-triangulated. Orientation: each loop's Newell normal is made to
  point away from the mean of the loop's inside corner endpoints.

  Returns (ntri[256], tris[256, MAXT, 3] edge ids padded with 0,
  edge_mid[12, 3] midpoint offsets).
  """
  # 12 cube edges as corner pairs (corner i at (i&1, i>>1&1, i>>2&1))
  edge_pairs = []
  for a in range(8):
    for d in range(3):
      if not (a >> d) & 1:
        edge_pairs.append((a, a | (1 << d)))
  edge_id = {p: i for i, p in enumerate(edge_pairs)}  # 12 edges
  edge_mid = np.array(
    [(CORNER_OFFSETS[a] + CORNER_OFFSETS[b]) / 2.0 for a, b in edge_pairs],
    dtype=np.float32,
  )

  # 6 faces: (axis, side) -> 4 corners in cyclic order around the face
  faces = []
  for d in range(3):
    u, v = (d + 1) % 3, (d + 2) % 3
    for s in (0, 1):
      cyc = []
      for bu, bv in ((0, 0), (1, 0), (1, 1), (0, 1)):
        cyc.append((s << d) | (bu << u) | (bv << v))
      faces.append(cyc)

  all_tris = []
  for case in range(256):
    inside = [(case >> i) & 1 for i in range(8)]
    segments = []  # pairs of edge ids
    for cyc in faces:
      cross = [
        k for k in range(4)
        if inside[cyc[k]] != inside[cyc[(k + 1) % 4]]
      ]  # indices into the face cycle: edge (cyc[k], cyc[k+1]) crosses
      def eid(k):
        a, b = cyc[k], cyc[(k + 1) % 4]
        return edge_id[(min(a, b), max(a, b))]
      if len(cross) == 2:
        segments.append((eid(cross[0]), eid(cross[1])))
      elif len(cross) == 4:
        # ambiguous: exactly two diagonal inside corners; cut each inside
        # corner off individually. corner cyc[k] sits between face edges
        # k-1 and k.
        for k in range(4):
          if inside[cyc[k]] and not inside[cyc[(k + 1) % 4]] \
             and not inside[cyc[(k - 1) % 4]]:
            segments.append((eid((k - 1) % 4), eid(k)))

    # chain segments into loops (each crossing edge appears in exactly 2
    # segments -> every vertex has degree 2)
    tris_case = []
    if segments:
      adj = {}
      for a, b in segments:
        adj.setdefault(a, []).append(b)
        adj.setdefault(b, []).append(a)
      unvisited = set(adj)
      loops = []
      while unvisited:
        start = min(unvisited)
        loop = [start]
        unvisited.discard(start)
        prev, cur = None, start
        while True:
          nxt = [x for x in adj[cur] if x != prev]
          # a double edge (two segments between the same pair) closes a
          # 2-loop; guard by preferring unvisited continuation
          nxt = nxt[0] if nxt else adj[cur][0]
          if nxt == start:
            break
          loop.append(nxt)
          unvisited.discard(nxt)
          prev, cur = cur, nxt
        loops.append(loop)

      for loop in loops:
        pts = edge_mid[loop]
        # Newell normal of the (possibly non-planar) loop
        n = np.zeros(3)
        for i in range(len(loop)):
          p0, p1 = pts[i], pts[(i + 1) % len(loop)]
          n += np.cross(p0, p1)
        # inside reference: mean of the loop's inside corner endpoints
        ref = np.zeros(3)
        cnt = 0
        for e in loop:
          a, b = edge_pairs[e]
          c = a if inside[a] else b
          ref += CORNER_OFFSETS[c]
          cnt += 1
        ref /= cnt
        flip = np.dot(n, pts.mean(axis=0) - ref) < 0
        for i in range(1, len(loop) - 1):
          t = (loop[0], loop[i], loop[i + 1])
          tris_case.append((t[0], t[2], t[1]) if flip else t)
    all_tris.append(tris_case)

  maxt = max(len(t) for t in all_tris)
  ntri = np.array([len(t) for t in all_tris], dtype=np.int32)
  tris = np.zeros((256, maxt, 3), dtype=np.int32)
  for case, tc in enumerate(all_tris):
    for k, t in enumerate(tc):
      tris[case, k] = t
  return ntri, tris, edge_mid


MC_NTRI, MC_TRIS, MC_EDGE_MID = _build_mc_tables()


@jax.jit
def _mc_count_kernel(mask: jnp.ndarray):
  """mask (z, y, x) uint8 → (case (cz,cy,cx) int32, ntri, total).

  One 256-entry table gather per cell — constant-table ``take`` lowers to
  a vectorized gather on the VPU."""
  sz, sy, sx = mask.shape
  cz, cy, cx = sz - 1, sy - 1, sx - 1
  case = jnp.zeros((cz, cy, cx), dtype=jnp.int32)
  for i in range(8):
    ox, oy, oz = i & 1, (i >> 1) & 1, (i >> 2) & 1
    case = case + (
      mask[oz : oz + cz, oy : oy + cy, ox : ox + cx].astype(jnp.int32) << i
    )
  ntri = jnp.take(jnp.asarray(MC_NTRI), case)
  return case, ntri, jnp.sum(ntri, dtype=jnp.int32)


def _mc_emit_host(case_np, ntri_np, shape, real_cells=None) -> np.ndarray:
  """Host-side MC triangle emission, O(triangles) numpy fancy indexing.
  Returns (n, 3, 3) vertex coords in (x, y, z) voxel units."""
  sz, sy, sx = shape
  cz, cy, cx = sz - 1, sy - 1, sx - 1
  ntri = np.asarray(ntri_np).reshape(-1)
  case = np.asarray(case_np).reshape(-1)
  cells = np.flatnonzero(ntri)
  if real_cells is not None and len(cells):
    # pad-ring filter on the O(surface) nonzero set only — full-grid
    # coordinate arithmetic per label costs more than the device pass
    rx, ry, rz = real_cells
    in_real = (
      (cells % cx < rx) & ((cells // cx) % cy < ry)
      & (cells // (cy * cx) < rz)
    )
    cells = cells[in_real]
  if len(cells) == 0:
    return np.zeros((0, 3, 3), dtype=np.float32)
  reps = ntri[cells]
  cell = np.repeat(cells, reps)
  # per-triangle index within its cell: arange minus each cell's start
  starts = np.concatenate([[0], np.cumsum(reps)[:-1]])
  k = np.arange(len(cell), dtype=np.int64) - np.repeat(starts, reps)
  edges = MC_TRIS[case[cell], k]  # (n, 3) edge ids
  mid = MC_EDGE_MID[edges]  # (n, 3, 3)
  base = np.stack(
    [cell % cx, (cell // cx) % cy, cell // (cy * cx)], axis=-1
  ).astype(np.float32)
  return base[:, None, :] + mid


def _mesh_emit_backend() -> str:
  """'host' | 'device' triangle emission. The host path is numpy fancy
  indexing (fast on CPU hosts); the device path keeps count+emit on the
  accelerator so MeshTask's forge stage stops round-tripping cases/counts
  through the host. Override with IGNEOUS_MESH_EMIT=host|device."""
  import os

  override = knobs.get_str("IGNEOUS_MESH_EMIT")
  if override:
    if override not in ("host", "device"):
      raise ValueError(
        f"IGNEOUS_MESH_EMIT must be 'host' or 'device': {override!r}"
      )
    return override
  platforms = os.environ.get("JAX_PLATFORMS", "")
  if platforms:
    return "host" if platforms.split(",")[0] == "cpu" else "device"
  return "device" if jax.default_backend() != "cpu" else "host"


def _exclusive_cumsum(nt: jnp.ndarray, row: int = 1024) -> jnp.ndarray:
  """Exclusive prefix sums of int32 ``nt`` in two levels: within
  ``row``-wide rows, then over the row totals. Same integers as one flat
  cumsum, which the TPU compiler takes ~20 s to compile per shape at
  10^6 cells (~1 s this way)."""
  n = nt.shape[0]
  rows = jnp.pad(nt, (0, (-n) % row)).reshape(-1, row)
  inner = jnp.cumsum(rows, axis=1, dtype=jnp.int32)
  totals = inner[:, -1]
  starts = jnp.cumsum(totals, dtype=jnp.int32) - totals
  return (inner - rows + starts[:, None]).reshape(-1)[:n]


@partial(jax.jit, static_argnames=("capacity",))
def _mc_emit_kernel(case: jnp.ndarray, ntri: jnp.ndarray, capacity: int):
  """Device MC triangle emission as a masked gather over ``capacity``
  static slots: exclusive-cumsum triangle offsets per cell, slot→cell via
  searchsorted, then the same MC_TRIS/MC_EDGE_MID table gathers as the
  host path. Slot order IS the host emission order (cells ascending in
  flat (z, y, x) scan order, k ascending within a cell), so after the
  host-side [:total] slice + pad-ring filter the triangle stream — and
  therefore _weld's vertex/face numbering — is byte-identical. Slots
  >= total hold garbage from clamped gathers and are sliced off."""
  cz, cy, cx = ntri.shape
  nt = ntri.reshape(-1)
  ex = _exclusive_cumsum(nt)
  slots = jnp.arange(capacity, dtype=jnp.int32)
  # last cell whose start <= slot: ties (zero-tri cells share a start)
  # resolve to the one cell whose [start, start+ntri) interval holds slot
  cell = (
    jnp.searchsorted(ex, slots, side="right").astype(jnp.int32) - 1
  )
  k = slots - jnp.take(ex, cell)
  k = jnp.minimum(k, jnp.int32(MC_TRIS.shape[1] - 1))  # dead-slot clamp
  cs = jnp.take(case.reshape(-1), cell)
  edges = jnp.asarray(MC_TRIS)[cs, k]  # (capacity, 3)
  # edge -> midpoint as a 12-way select: the same values as indexing
  # MC_EDGE_MID, which the TPU compiler takes ~35 s to compile per shape
  mid = jnp.zeros(edges.shape + (3,), jnp.float32)  # (capacity, 3, 3)
  for e, m in enumerate(MC_EDGE_MID):
    mid = jnp.where((edges == e)[..., None], jnp.asarray(m), mid)
  base = jnp.stack(
    [
      (cell % cx).astype(jnp.float32),
      ((cell // cx) % cy).astype(jnp.float32),
      (cell // (cy * cx)).astype(jnp.float32),
    ],
    axis=-1,
  )
  return base[:, None, :] + mid, cell


def _mc_emit_device(
  case, ntri, total: int, shape, real_cells=None
) -> np.ndarray:
  """Run _mc_emit_kernel as a solo dispatch (one signature per (shape,
  capacity-bucket)) and apply the pad-ring filter on the returned
  per-triangle cell ids."""
  from ..observability import device as device_telemetry

  sz, sy, sx = shape
  cz, cy, cx = sz - 1, sy - 1, sx - 1
  capacity = 1 << max(10, int(total - 1).bit_length())
  kernel = "mesh.mc_emit"
  sig = ((cz, cy, cx), capacity)
  tris, cell = device_telemetry.run_solo(
    kernel, _mc_emit_kernel,
    (jnp.asarray(case), jnp.asarray(ntri), capacity), sig,
    elements=int(total),
    nbytes=int(np.asarray(case).nbytes) + int(np.asarray(ntri).nbytes),
  )
  tris = np.asarray(tris)[:total]
  cell = np.asarray(cell)[:total]
  if real_cells is not None and len(cell):
    rx, ry, rz = real_cells
    in_real = (
      (cell % cx < rx) & ((cell // cx) % cy < ry)
      & (cell // (cy * cx) < rz)
    )
    tris = tris[in_real]
  return tris


def _mc_emit(case, ntri, total: int, shape, real_cells=None) -> np.ndarray:
  """Backend-dispatched MC emission; both paths produce the identical
  triangle stream (order and bits)."""
  if total and _mesh_emit_backend() == "device":
    return _mc_emit_device(case, ntri, total, shape, real_cells)
  return _mc_emit_host(
    np.asarray(case), np.asarray(ntri), shape, real_cells
  )


_MC_COUNT_EXECUTOR = None


def marching_cubes(
  mask: np.ndarray, anisotropy=(1.0, 1.0, 1.0), offset=(0.0, 0.0, 0.0)
) -> Tuple[np.ndarray, np.ndarray]:
  """Binary mask (x, y, z) → (vertices (V,3) float32, faces (F,3) uint32).

  True 256-case marching cubes (zmesh's algorithm; ~half the triangles of
  marching_tetrahedra for the same surface). Vertices in physical units:
  (voxel + offset) * anisotropy. Watertight over the mask interior; pad
  with a zero shell to close surfaces at the array boundary."""
  if mask.ndim != 3:
    raise ValueError("mask must be 3d")
  orig = mask.shape
  bucket = _bucket_shape(orig)
  mask = _pad_to_bucket(mask, bucket)
  dev = jnp.asarray(
    np.ascontiguousarray(mask.astype(np.uint8).transpose(2, 1, 0))
  )
  case, ntri, total = _mc_count_kernel(dev)
  if int(total) == 0:
    return _EMPTY_MESH
  tris = _mc_emit(
    case, ntri, int(total), dev.shape,
    real_cells=(orig[0] - 1, orig[1] - 1, orig[2] - 1),
  )
  if len(tris) == 0:
    return _EMPTY_MESH
  return _weld(tris, anisotropy, offset)


def _mc_executor():
  global _MC_COUNT_EXECUTOR
  if _MC_COUNT_EXECUTOR is None:
    from ..parallel.executor import BatchKernelExecutor

    _MC_COUNT_EXECUTOR = BatchKernelExecutor(_mc_count_kernel)
  return _MC_COUNT_EXECUTOR


def _mc_emit_k(results, k, shape, real_cells):
  case_b, ntri_b, totals = results
  return _mc_emit(
    case_b[k], ntri_b[k], int(np.asarray(totals[k])), shape,
    real_cells=real_cells,
  )


def marching_cubes_batch(
  masks, anisotropy=(1.0, 1.0, 1.0), offsets=None, executor=None,
  batch_size: int = 16,
):
  """Batched marching cubes: list of binary (x, y, z) masks → list of
  (vertices, faces), identical to per-mask marching_cubes. Same
  shard_map'd one-dispatch-per-bucket count pass as
  marching_tetrahedra_batch."""
  return _isosurface_batch(
    masks, anisotropy, offsets, executor, batch_size,
    _mc_executor, _mc_emit_k,
  )
