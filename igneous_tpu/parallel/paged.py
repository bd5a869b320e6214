"""Ragged paged device batching (ISSUE 12).

The batched executors require same-shape cutouts, so boundary chunks and
mixed-shape fleets fall back to the solo host path or pay per-shape
recompiles — exactly the waste `igneous_device_fastpath_ratio` measures.
This module borrows the Ragged Paged Attention idea (PAPERS.md): decompose
every cutout into fixed ``(pz, py, px)`` pages of a dense device batch,
carry each page's valid extent in an int32 sidecar, and run the kernels
over the page batch so ONE compiled signature serves every shape. Page
rounds always dispatch the same page count (filler pages are zero, extent
0), so the jit signature depends on page geometry alone — a whole campaign
of ragged boundary chunks compiles once per kernel (assert via the ISSUE 7
recompile ledger).

Reassembly is bitwise-identical to the solo paths:

- **Pooling pyramid** — pages are picked so every per-mip cumulative
  factor divides the page dims (``pages_compatible``): pooling windows
  never straddle pages and page origins stay window-aligned at every mip.
  Inside the kernel a clamp-gather replicates each axis's last valid row
  into the slack before pooling — the same value `_pad_to_multiple`'s
  edge padding feeds partial windows in the solo path — and the unpacker
  crops each page's output to the ceil-chained local extent, so partial
  windows match the solo bytes and slack lanes never surface.
- **CCL** — pages tile the zero-padded volume and the tile grid divides
  the page (``ccl_page_compatible``), so the per-page tile-local resolve
  equals the solo kernel's tiling; page-local roots are remapped host-side
  to volume-global flat indices and ONE `_merge_tile_roots` stitches both
  in-page tile seams and page seams. Renumbering depends only on the
  partition, which exact CCL makes identical either way.
- **EDT** — line passes are global along each axis, so EDT pages by
  CANONICAL SHAPE instead of spatial pages: every item is zero-padded to
  the fleet's per-axis max rounded up to a pow2 page count. With
  ``black_border=True`` the appended zeros extend the border background
  run without adding label changes, so foreground distances keep their
  exact envelopes (the envelope passes are run-scoped).

When the solo path still wins: single same-shape deliveries (the dense
stacked pyramid is already one signature and has no page slack), CPU
host-pool policy (`IGNEOUS_POOL_HOST` / native CCL / numpy EDT — the host
kernels beat XLA-on-CPU regardless of packing), and cutouts much smaller
than a page (slack > payload; see ``igneous_device_pad_waste_ratio``).

Env knobs: ``IGNEOUS_PAGE_SHAPE=pz,py,px`` (default 32,32,32) and
``IGNEOUS_PAGE_BATCH`` (pages per dispatch round, default 32; rounded up
to a pow2 multiple of the device count).
"""

from __future__ import annotations

import os
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..observability import device as device_telemetry
from ..ops.pooling import (
  _from_device_layout,
  _normalize_factors,
  _pack_u64_planes,
  _pool_once,
  _split_u64_planes,
  _to_device_layout,
)
from .executor import (
  BatchKernelExecutor, LRUCache, compile_program, make_mesh,
)

from .. import tune
from ..analysis import knobs

_DEFAULT_PAGE = (32, 32, 32)


def _next_pow2(n: int) -> int:
  p = 1
  while p < n:
    p <<= 1
  return p


def page_shape() -> Tuple[int, int, int]:
  """The fixed page shape (pz, py, px) in device (z, y, x) axis order.

  The default 32^3 divides evenly by every standard mip factor chain up
  to 5 halvings and by both CCL tile defaults, so all three paged kernels
  share one page geometry."""
  # explicit env > tuned/<device_kind>.json > registry default (ISSUE 19)
  raw = tune.resolve("IGNEOUS_PAGE_SHAPE") or ""
  if not raw:
    return _DEFAULT_PAGE
  parts = tuple(int(v) for v in raw.replace(" ", "").split(","))
  if len(parts) != 3 or any(p <= 0 for p in parts):
    raise ValueError(
      f"IGNEOUS_PAGE_SHAPE must be three positive ints 'pz,py,px': {raw!r}"
    )
  return parts


def page_round_cap(n_devices: int) -> int:
  """Pages per dispatch round: every round sends exactly this many pages
  (zero filler pages, extent 0), so the compiled signature is
  round-count-independent. Pow2 multiple of the device count so the
  executor's own canonical-K rounding is a no-op."""
  want = int(tune.resolve("IGNEOUS_PAGE_BATCH")
             or knobs.KNOBS["IGNEOUS_PAGE_BATCH"].default)
  if want <= 0:
    raise ValueError("IGNEOUS_PAGE_BATCH must be positive")
  cap = max(n_devices, 1)
  while cap < want:
    cap <<= 1
  return cap


def pages_compatible(factors, page: Optional[Tuple[int, int, int]] = None
                     ) -> bool:
  """Can this factor chain pool page-locally? True iff every per-mip
  cumulative factor divides the page dim on its axis — then no pooling
  window ever straddles a page boundary and page origins remain
  window-aligned at every mip."""
  page = page or page_shape()
  cum = [1, 1, 1]
  for (fx, fy, fz) in factors:
    for i, f in enumerate((fz, fy, fx)):
      cum[i] *= int(f)
      if cum[i] <= 0 or page[i] % cum[i]:
        return False
  return True


def ccl_page_compatible(page: Optional[Tuple[int, int, int]] = None) -> bool:
  """True iff the CCL tile grid divides the page, so page boundaries are
  tile-grid boundaries and one host merge stitches both seam kinds."""
  from ..ops.ccl import _tile_shape

  page = page or page_shape()
  return all(p % min(t, p) == 0 for t, p in zip(_tile_shape(), page))


def _ceil_chain(extent, factors):
  """Per-mip extents of one region under the factor chain (z, y, x)."""
  e = tuple(int(v) for v in extent)
  out = []
  for (fx, fy, fz) in factors:
    e = tuple(-(-a // f) for a, f in zip(e, (fz, fy, fx)))
    out.append(e)
  return out


def _mesh_key(mesh):
  return (
    None if mesh is None
    else (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
  )


# ---------------------------------------------------------------------------
# paged pooling pyramid


def _make_page_kernel(factors, method: str, sparse: bool, planes: int):
  """Per-page pyramid kernel: (pages, ext) → per-mip page outputs.

  ``pages``: (c, pz, py, px) — or a (lo, hi) tuple for uint64 plane pairs;
  ``ext``: (3,) int32 valid extent (ez, ey, ex). Before each pooling step a
  clamp-gather overwrites every row past the extent with the last valid
  row (``min(arange, ext-1)`` — always in-bounds, filler pages clamp to
  row 0), reproducing `_pad_to_multiple`'s edge semantics for the partial
  window while keeping the shape fixed. The extent ceil-divides alongside
  the data, so each mip re-clamps against its own valid region; anything
  past it is slack the unpacker crops."""
  factors = tuple(tuple(int(v) for v in f) for f in factors)

  def kernel(tree):
    pages, ext = tree
    cur = pages if planes == 2 else (pages,)
    e = ext.astype(jnp.int32)
    outs = []
    for (fx, fy, fz) in factors:
      clamped = []
      for p in cur:
        for a in range(3):
          idx = jnp.minimum(
            jnp.arange(p.shape[a + 1], dtype=jnp.int32),
            jnp.maximum(e[a] - 1, 0),
          )
          p = jnp.take(p, idx, axis=a + 1)
        clamped.append(p)
      x = tuple(clamped) if planes == 2 else clamped[0]
      x = _pool_once(x, (fx, fy, fz), method, sparse)
      cur = x if planes == 2 else (x,)
      f_zyx = jnp.asarray((fz, fy, fx), jnp.int32)
      e = (e + f_zyx - 1) // f_zyx
      outs.append(x)
    return tuple(outs)

  return kernel


_PAGED_EXECUTORS = {}


def paged_pyramid_executor(
  factors, method: str, sparse: bool, planes: int = 1, mesh=None
) -> BatchKernelExecutor:
  """Cached executor for the paged pyramid kernel. The page geometry and
  work dtype live in the batch signature, so one executor serves every
  campaign; the cache only keys the kernel configuration."""
  factors = tuple(tuple(int(v) for v in f) for f in factors)
  key = (factors, method, bool(sparse), int(planes), _mesh_key(mesh))
  if key not in _PAGED_EXECUTORS:
    _PAGED_EXECUTORS[key] = BatchKernelExecutor(
      _make_page_kernel(factors, method, sparse, planes),
      mesh=mesh,
      name=f"pooling.paged_pyramid[{method}]",
    )
  return _PAGED_EXECUTORS[key]


class PagedPyramid:
  """Incremental paged pyramid over a ragged fleet of cutouts.

  Packs every item (x, y, z[, c]) into fixed pages, dispatches them in
  rounds of exactly ``page_round_cap`` pages, and reassembles per-item
  per-mip outputs bitwise-identical to ``pooling.downsample``. The round
  structure is the lease batcher's straggler-split seam: between rounds a
  flagged host calls :meth:`split_unstarted` to shed every member whose
  page range has not begun, and idle hosts re-lease those members.
  """

  def __init__(
    self,
    imgs: Sequence[np.ndarray],
    factor,
    num_mips: int = 1,
    method: str = "average",
    sparse: bool = False,
    mesh=None,
    page: Optional[Tuple[int, int, int]] = None,
  ):
    if not imgs:
      raise ValueError("need at least one image")
    self.factors = _normalize_factors(factor, num_mips)
    self.page = tuple(page or page_shape())
    if not pages_compatible(self.factors, self.page):
      raise ValueError(
        f"factor chain {self.factors} does not divide page {self.page}; "
        "use the solo path (see pages_compatible)"
      )
    dts = {img.dtype for img in imgs}
    cs = {1 if img.ndim == 3 else img.shape[3] for img in imgs}
    if len(dts) != 1 or len(cs) != 1:
      raise ValueError("paged fleets must share dtype and channel count")
    self._orig_dtype = next(iter(dts))
    self._c = next(iter(cs))
    self.method = method
    self._squeeze = [img.ndim == 3 for img in imgs]

    u64 = method == "mode" and self._orig_dtype.itemsize == 8
    if u64 and self._orig_dtype.kind == "f":
      raise ValueError("mode pooling of floating-point data is not supported")
    self.planes = 2 if u64 else 1

    # mirror pooling.downsample's device dtype rules exactly
    self._planes_in: List[Tuple[np.ndarray, ...]] = []
    self._shapes: List[Tuple[int, int, int]] = []
    for img in imgs:
      work = img.view(np.uint8) if img.dtype == bool else img
      if u64:
        u = work.view(np.uint64) if work.dtype.kind == "i" else work
        lo, hi = _split_u64_planes(u)
        planes = (_to_device_layout(lo), _to_device_layout(hi))
      else:
        if work.dtype.itemsize == 8 and method == "average":
          work = work.astype(np.float32)
        planes = (_to_device_layout(work),)
      self._planes_in.append(planes)
      self._shapes.append(planes[0].shape[1:])  # (Z, Y, X)
    self._work_dtype = self._planes_in[0][0].dtype

    self._executor = paged_pyramid_executor(
      self.factors, method, sparse, self.planes, mesh
    )
    self.cap = page_round_cap(self._executor.n_devices)

    # page table: items packed sequentially so each item's pages are
    # contiguous — a round boundary splits at most one item
    pz, py, px = self.page
    self._entries = []  # (item, (oz, oy, ox), (ez, ey, ex))
    self._left = []
    for i, (Z, Y, X) in enumerate(self._shapes):
      n0 = len(self._entries)
      for oz in range(0, Z, pz):
        for oy in range(0, Y, py):
          for ox in range(0, X, px):
            ext = (min(pz, Z - oz), min(py, Y - oy), min(px, X - ox))
            self._entries.append((i, (oz, oy, ox), ext))
      self._left.append(len(self._entries) - n0)

    self._staged = [
      [
        tuple(
          np.zeros((self._c,) + e, self._work_dtype)
          for _ in range(self.planes)
        )
        for e in _ceil_chain(shape, self.factors)
      ]
      for shape in self._shapes
    ]
    self._next = 0
    self._completed: set = set()
    self._released: set = set()

  @property
  def n_items(self) -> int:
    return len(self._shapes)

  @property
  def pending(self) -> bool:
    return self._next < len(self._entries)

  @property
  def rounds_remaining(self) -> int:
    return -(-(len(self._entries) - self._next) // self.cap)

  def split_unstarted(self) -> List[int]:
    """Straggler split: drop every item NONE of whose pages has been
    dispatched and return their indices. The caller (lease batcher)
    releases those members back to the queue so idle hosts pick up the
    shed page ranges; in-flight items stay here to finish."""
    started = {e[0] for e in self._entries[: self._next]}
    rest = self._entries[self._next:]
    dropped = sorted({e[0] for e in rest} - started)
    if dropped:
      ds = set(dropped)
      self._entries = self._entries[: self._next] + [
        e for e in rest if e[0] not in ds
      ]
      self._released.update(ds)
    return dropped

  def run_round(self) -> List[int]:
    """Dispatch the next round of pages; returns newly-completed item
    indices (whose :meth:`result` is now available)."""
    todo = self._entries[self._next: self._next + self.cap]
    if not todo:
      return []
    self._next += len(todo)
    pz, py, px = self.page
    batch_planes = [
      np.zeros((self.cap, self._c, pz, py, px), self._work_dtype)
      for _ in range(self.planes)
    ]
    exts = np.zeros((self.cap, 3), np.int32)
    itemsize = self._work_dtype.itemsize
    real = 0
    for j, (i, (oz, oy, ox), (ez, ey, ex)) in enumerate(todo):
      for src, dst in zip(self._planes_in[i], batch_planes):
        dst[j][:, :ez, :ey, :ex] = (
          src[:, oz: oz + ez, oy: oy + ey, ox: ox + ex]
        )
      exts[j] = (ez, ey, ex)
      real += ez * ey * ex * self._c * itemsize * self.planes
    # page-pool slack + filler pages: the layer of padding the page
    # packer itself introduces (the pow2 batch layer records separately)
    total = self.cap * pz * py * px * self._c * itemsize * self.planes
    device_telemetry.LEDGER.record_pad_waste(
      padded_bytes=total - real, real_bytes=real
    )
    tree = (
      tuple(batch_planes) if self.planes == 2 else batch_planes[0],
      exts,
    )
    outs = self._executor(
      tree,
      span_attrs={
        "pages": len(todo), "filler_pages": self.cap - len(todo),
      },
    )
    done = []
    for j, (i, (oz, oy, ox), ext) in enumerate(todo):
      F = (1, 1, 1)
      e = ext
      for m, (fx, fy, fz) in enumerate(self.factors):
        f = (fz, fy, fx)
        F = tuple(a * b for a, b in zip(F, f))
        e = tuple(-(-a // b) for a, b in zip(e, f))
        o = (oz // F[0], oy // F[1], ox // F[2])
        mip_out = outs[m] if self.planes == 2 else (outs[m],)
        for pi in range(self.planes):
          self._staged[i][m][pi][
            :,
            o[0]: o[0] + e[0],
            o[1]: o[1] + e[1],
            o[2]: o[2] + e[2],
          ] = np.asarray(mip_out[pi][j])[:, : e[0], : e[1], : e[2]]
      self._left[i] -= 1
      if self._left[i] == 0:
        self._completed.add(i)
        done.append(i)
    return done

  def result(self, i: int) -> List[np.ndarray]:
    """Per-mip outputs for a completed item, formatted exactly as
    ``pooling.downsample`` returns them."""
    if i not in self._completed:
      raise ValueError(f"item {i} is not complete")
    od = self._orig_dtype
    results = []
    for planes in self._staged[i]:
      if self.planes == 2:
        r = _pack_u64_planes(
          _from_device_layout(planes[0]), _from_device_layout(planes[1])
        )
        r = r.view(od) if od.kind == "i" else r.astype(od)
      else:
        r = _from_device_layout(planes[0]).astype(od, copy=False)
      results.append(r[..., 0] if self._squeeze[i] else r)
    return results

  def run(self) -> List[List[np.ndarray]]:
    """Drive every round; returns results for all (unreleased) items."""
    while self.pending:
      self.run_round()
    return [
      self.result(i) for i in range(self.n_items)
      if i not in self._released
    ]


def paged_pyramid(
  imgs: Sequence[np.ndarray],
  factor,
  num_mips: int = 1,
  method: str = "average",
  sparse: bool = False,
  mesh=None,
  page: Optional[Tuple[int, int, int]] = None,
) -> List[List[np.ndarray]]:
  """One-shot paged pyramid: ragged (x, y, z[, c]) cutouts → per-item
  per-mip outputs, bitwise-identical to solo ``pooling.downsample``."""
  return PagedPyramid(
    imgs, factor, num_mips, method=method, sparse=sparse, mesh=mesh,
    page=page,
  ).run()


# ---------------------------------------------------------------------------
# paged CCL


_PAGED_CCL_EXECUTORS = {}


def _paged_ccl_executor(connectivity: int, mesh=None):
  from ..ops.ccl import _ccl_tiled_kernel, _device_algo, _tile_shape

  algo = _device_algo()
  tile = _tile_shape()
  key = (connectivity, algo, tile, _mesh_key(mesh))
  if key not in _PAGED_CCL_EXECUTORS:
    _PAGED_CCL_EXECUTORS[key] = BatchKernelExecutor(
      partial(
        _ccl_tiled_kernel, connectivity=connectivity, algo=algo, tile=tile,
      ),
      mesh=mesh,
      name=f"ccl.paged[{algo}]",
    )
  return _PAGED_CCL_EXECUTORS[key]


def paged_ccl(
  imgs: Sequence[np.ndarray],
  connectivity: int = 6,
  mesh=None,
  page: Optional[Tuple[int, int, int]] = None,
) -> List[np.ndarray]:
  """Ragged device CCL: list of (x, y, z) label volumes → list of
  component volumes numbered exactly as ``connected_components`` numbers
  each alone.

  Every volume is zero-padded (background) to page multiples and cut into
  full-extent pages; the tile-local kernel runs per page, page-local roots
  are remapped to volume-global flat indices, and one `_merge_tile_roots`
  per item stitches in-page tile seams and page seams alike (the tile
  grid divides the page — ``ccl_page_compatible``). Exact CCL both ways
  plus a partition-only renumber ⇒ bitwise-identical outputs."""
  from ..ops.ccl import (
    _dense_relabel, _merge_tile_roots, _roots_to_components, _tile_shape,
    neighbor_offsets,
  )

  neighbor_offsets(connectivity)  # validate before any device work
  page = tuple(page or page_shape())
  if not ccl_page_compatible(page):
    raise ValueError(
      f"CCL tile {_tile_shape()} does not divide page {page}; use the "
      "solo path (see ccl_page_compatible)"
    )
  tile_eff = tuple(min(t, p) for t, p in zip(_tile_shape(), page))
  executor = _paged_ccl_executor(connectivity, mesh)
  cap = page_round_cap(executor.n_devices)
  pz, py, px = page

  vols = []  # (padded labels (Zp,Yp,Xp), (Z,Y,X))
  entries = []  # (item, (oz, oy, ox))
  for img in imgs:
    if img.ndim != 3:
      raise ValueError("labels must be (x, y, z)")
    lab32 = _dense_relabel(np.asarray(img))
    zyx = np.ascontiguousarray(lab32.transpose(2, 1, 0))
    Z, Y, X = zyx.shape
    Zp, Yp, Xp = (-(-s // p) * p for s, p in zip((Z, Y, X), page))
    padded = np.zeros((Zp, Yp, Xp), np.int32)
    padded[:Z, :Y, :X] = zyx
    i = len(vols)
    vols.append((padded, (Z, Y, X)))
    for oz in range(0, Zp, pz):
      for oy in range(0, Yp, py):
        for ox in range(0, Xp, px):
          entries.append((i, (oz, oy, ox)))

  big = np.iinfo(np.int32).max
  roots_vols = [np.full(v[0].shape, big, np.int32) for v in vols]
  page_nbytes = pz * py * px * 4
  real_nbytes = {
    i: int(np.prod(shape)) * 4 for i, (_, shape) in enumerate(vols)
  }
  for r0 in range(0, len(entries), cap):
    todo = entries[r0: r0 + cap]
    batch = np.zeros((cap, pz, py, px), np.int32)
    for j, (i, (oz, oy, ox)) in enumerate(todo):
      batch[j] = vols[i][0][oz: oz + pz, oy: oy + py, ox: ox + px]
    roots = executor(
      batch,
      span_attrs={
        "pages": len(todo), "filler_pages": cap - len(todo),
      },
    )
    for j, (i, (oz, oy, ox)) in enumerate(todo):
      r = np.asarray(roots[j])
      fg = r != big
      if not fg.any():
        continue
      # page-local flat root → volume-global flat root: without this,
      # roots from different pages of one volume collide in the merge
      lz, ly, lx = np.unravel_index(r[fg].astype(np.int64), page)
      dst = roots_vols[i][oz: oz + pz, oy: oy + py, ox: ox + px]
      dst[fg] = np.ravel_multi_index(
        (lz + oz, ly + oy, lx + ox), vols[i][0].shape
      ).astype(np.int32)
  # page padding accounting: pages minus real voxels, plus filler pages
  total = (-(-len(entries) // cap)) * cap * page_nbytes
  real = sum(real_nbytes.values())
  device_telemetry.LEDGER.record_pad_waste(
    padded_bytes=total - real, real_bytes=real
  )

  results = []
  for i, (padded, (Z, Y, X)) in enumerate(vols):
    merged = _merge_tile_roots(
      roots_vols[i], padded, connectivity, tile_eff
    )
    results.append(
      _roots_to_components(merged[:Z, :Y, :X].transpose(2, 1, 0))
    )
  return results


# ---------------------------------------------------------------------------
# paged EDT (canonical-shape pages)


_PAGED_EDT_EXECUTORS = {}


def _paged_edt_executor(anisotropy, mesh=None):
  from ..ops.edt import _edt_sq_kernel, _line_block

  wx, wy, wz = (float(a) for a in anisotropy)
  lb = _line_block()
  key = (wx, wy, wz, lb, _mesh_key(mesh))
  if key not in _PAGED_EDT_EXECUTORS:
    _PAGED_EDT_EXECUTORS[key] = BatchKernelExecutor(
      partial(_edt_sq_kernel, anisotropy=(wx, wy, wz), line_block=lb),
      mesh=mesh,
      name="edt.sq_paged",
    )
  return _PAGED_EDT_EXECUTORS[key]


def paged_edt(
  labels_list: Sequence[np.ndarray],
  anisotropy: Sequence[float] = (1.0, 1.0, 1.0),
  mesh=None,
  page: Optional[Tuple[int, int, int]] = None,
) -> List[np.ndarray]:
  """Ragged device EDT with ``black_border=True`` semantics: list of
  (x, y, z) label volumes → list of float32 distance fields, each
  bitwise-identical to the solo device ``edt(..., black_border=True)``.

  EDT's line passes are global along each axis, so spatial paging is
  impossible; instead items page by CANONICAL SHAPE — zero-padded to the
  fleet's per-axis max (plus the black border) rounded up to a pow2 page
  count, so signatures grow logarithmically with fleet diversity. The
  appended zeros extend the border background run without introducing
  label changes, leaving every foreground voxel's run-scoped envelope —
  and therefore its distance — bit-exact. Only ``black_border=True`` has
  this invariance (an open border would treat the pad as a new boundary),
  which is the skeleton forge's mode; other callers use ``edt_batch``."""
  from ..ops.ccl import _dense_relabel

  if not labels_list:
    return []
  page = tuple(page or page_shape())
  pxyz = (page[2], page[1], page[0])  # page is (pz,py,px); items are xyz
  items = [np.asarray(l) for l in labels_list]
  for it in items:
    if it.ndim != 3:
      raise ValueError("labels must be (x, y, z)")
  canon = tuple(
    _next_pow2(-(-(max(it.shape[a] for it in items) + 2) // p)) * p
    for a, p in zip(range(3), pxyz)
  )
  work = np.zeros((len(items),) + canon, np.int32)
  for k, it in enumerate(items):
    sx, sy, sz = it.shape
    work[k, 1: sx + 1, 1: sy + 1, 1: sz + 1] = _dense_relabel(it)
  real = sum(int(np.prod(it.shape)) * 4 for it in items)
  device_telemetry.LEDGER.record_pad_waste(
    padded_bytes=int(work.nbytes) - real, real_bytes=real
  )
  dev = np.ascontiguousarray(work.transpose(0, 3, 2, 1))  # (K, z, y, x)
  executor = _paged_edt_executor(anisotropy, mesh)
  sq = executor(
    dev, span_attrs={"canonical_shape": "x".join(str(c) for c in canon)}
  )
  outs = []
  for k, it in enumerate(items):
    sx, sy, sz = it.shape
    s = np.asarray(sq[k]).transpose(2, 1, 0)[1: sx + 1, 1: sy + 1, 1: sz + 1]
    o = np.sqrt(s, dtype=np.float32)
    o[it == 0] = 0.0
    outs.append(o)
  return outs


# ---------------------------------------------------------------------------
# pod-mesh entry: paged pyramid over a global page batch


class PagedGlobalRunner:
  """Multi-host paged pyramid (mirrors ChunkExecutor.run_global): runs the
  shard_map'd page kernel over ALREADY-sharded global arrays assembled by
  ``multihost.from_process_local`` from each host's ``page_partition``
  range. Callers read outputs through ``.addressable_shards`` — a host
  only addresses its own chips, so no global gather happens here."""

  def __init__(self, factors, method: str = "average", sparse: bool = False,
               planes: int = 1, mesh=None):
    self.mesh = mesh if mesh is not None else make_mesh()
    self.axis = self.mesh.axis_names[0]
    self.factors = tuple(tuple(int(v) for v in f) for f in factors)
    self.planes = int(planes)
    self.name = f"pooling.paged_pyramid[{method}]"
    self._kernel = _make_page_kernel(
      self.factors, method, sparse, self.planes
    )
    # input signature -> AOT executable
    self._compiled = LRUCache()

  def _make(self, tree):
    """The shard_map'd jit closure for one input structure."""
    batched = jax.vmap(self._kernel)
    out_shape = jax.eval_shape(
      batched,
      jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), tree
      ),
    )
    out_specs = jax.tree.map(lambda _: P(self.axis), out_shape)
    fn = jax.shard_map(
      batched, mesh=self.mesh, in_specs=(P(self.axis),),
      out_specs=out_specs, check_vma=False,
    )
    # lint: allow=IGN201 AOT lower+compile cached by signature at call site
    return jax.jit(fn)

  def __call__(self, pages, exts):
    """pages: global (K, c, pz, py, px) jax.Array (or a (lo, hi) tuple,
    planes=2); exts: global (K, 3) int32. Returns per-mip global arrays."""
    tree = (pages, exts)
    leaves = jax.tree.leaves(tree)
    sig = tuple((tuple(a.shape), str(a.dtype)) for a in leaves)
    if sig not in self._compiled:
      self._compiled[sig] = compile_program(
        self.name, sig, self.mesh, lambda: self._make(tree).lower(tree),
      )
    with device_telemetry.execute_span(
      self.name,
      elements=sum(int(np.prod(a.shape)) for a in leaves),
      mesh=self.mesh,
    ):
      out = self._compiled[sig](tree)
      jax.block_until_ready(out)
    return out
