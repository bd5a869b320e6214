"""Batched downsample driver: many grid cells per device dispatch.

SURVEY.md §5.8's TPU mapping made concrete: instead of one process per
task (the reference's LocalTaskQueue(parallel=N)), one host walks the task
grid, downloads K equal-shaped cutouts with an IO thread pool, runs ONE
shard_map'd pooling program for all K across the chip mesh, and uploads
every mip — IO overlaps device compute via double buffering.

Edge cells (clamped to odd shapes) ride the paged pyramid (parallel.paged,
ISSUE 12): fixed (pz, py, px) pages with per-page extent sidecars keep one
compiled signature for every shape; the per-task solo path remains only
for factor chains the page can't tile.
"""

from __future__ import annotations

import concurrent.futures as cf
from functools import partial
from typing import Optional, Sequence

import numpy as np

from ..lib import Bbox, Vec
from ..observability import trace
from ..volume import Volume
from ..downsample_scales import compute_factors, DEFAULT_FACTOR
from ..task_creation.common import get_bounds
from ..tasks.image import DownsampleTask
from ..ops.pooling import (
  _copy_by_planes,
  _from_device_layout,
  _pack_u64_planes,
  _plane_runs,
  _split_u64_planes,
  _to_device_layout,
)
from .executor import cached_chunk_executor, make_mesh

# single source of truth for the (x,y,z,c) <-> (c,z,y,x) convention
_to_batch_layout = _to_device_layout
_from_batch_layout = _from_device_layout


def _batch_sources(imgs, is_u64_mode: bool):
  """Zero-copy (c,z,y,x) views of K same-shape (x,y,z[,c]) cutouts, one
  list per batch of a dispatch: the (lo, hi) uint32 planes of uint64
  cutouts, else the cutouts themselves."""
  def views(arrs):
    return [(a if a.ndim == 4 else a[..., np.newaxis]).transpose(3, 2, 1, 0)
            for a in arrs]

  if is_u64_mode:
    return [views(planes)
            for planes in zip(*(_split_u64_planes(i) for i in imgs))]
  return [views(imgs)]


def _stack_into(batches, sources) -> int:
  """Write the (K, c, z, y, x) ``batches``, member k of batch b from
  ``sources[b][k]``, run by run of z-planes. A run writes every batch's
  planes of its cutout, so the (lo, hi) copies of a uint64 cutout read
  the source lines the first one just brought in. Returns the runs that
  ran side by side."""
  def stack(index):
    for batch, views in zip(batches, sources):
      batch[index] = views[index[0]][index[1:]]

  return _copy_by_planes(stack, batches[0].shape,
                         sum(int(b.nbytes) for b in batches))


def device_pyramid_batch(executor, imgs, is_u64_mode: bool):
  """K same-shape (x,y,z[,c]) cutouts → per-mip batch arrays via ONE
  ChunkExecutor dispatch. The cutouts are copied into a host batch the
  executor reuses from round to round; uint64 mode rides as (lo, hi)
  uint32 planes and comes back packed into fresh arrays (the encode pool
  owns them after the round). Shared by batched_downsample and the lease
  batcher."""
  sources = _batch_sources(imgs, is_u64_mode)
  nbytes = sum(int(i.nbytes) for i in imgs)

  def fill(batches, reused):
    attrs = {"bytes": nbytes, "reused": int(reused)}
    span = (trace.span("pooling.u64_split", **attrs) if is_u64_mode
            else trace.span("pooling.stack", **attrs))
    with span:
      span.set(workers=_stack_into(batches, sources))

  outs, _ = executor.dispatch_staged(
    (len(imgs),) + sources[0][0].shape,
    np.uint32 if is_u64_mode else np.result_type(*imgs), fill)
  if not is_u64_mode:
    return outs
  span = trace.span("pooling.u64_pack",
                    bytes=sum(int(ol.nbytes + oh.nbytes) for ol, oh in outs))
  with span:
    packed = [_pack_u64_planes(ol, oh) for ol, oh in outs]
    span.set(workers=max(
      (_plane_runs(ol.shape, ol.nbytes + oh.nbytes)[1] for ol, oh in outs),
      default=1))
  return packed


def batched_downsample(
  layer_path: str,
  mip: int = 0,
  num_mips: int = 4,
  shape: Sequence[int] = (256, 256, 64),
  batch_size: int = 8,
  factor: Sequence[int] = DEFAULT_FACTOR,
  sparse: bool = False,
  fill_missing: bool = False,
  compress="gzip",
  mesh=None,
  method: str = "auto",
  bounds: Optional[Bbox] = None,
  drain_flag=None,
) -> dict:
  """Downsample a whole layer with batched device dispatches.

  Creates destination scales (like create_downsampling_tasks), then
  processes the grid in K-cutout batches. Returns run statistics.
  ``bounds`` (at ``mip``) restricts the processed region.
  ``drain_flag`` (anything with ``is_set()``, e.g. lifecycle.StopFlag):
  graceful preemption — the in-flight batch's uploads finish, remaining
  grid cells are skipped and reported via ``stats["drained"]`` so the
  caller can resume with a bounds restriction or a task-queue pass.
  """
  from ..downsample_scales import create_downsample_scales
  from ..ops import pooling

  vol = Volume(layer_path, mip=mip, fill_missing=fill_missing)
  # chunk_size guard: every produced mip must stay chunk-writable
  factors = compute_factors(
    shape, factor, num_mips, chunk_size=vol.meta.chunk_size(mip)
  )
  if not factors:
    raise ValueError(
      f"shape {list(shape)} admits no chunk-aligned downsamples by "
      f"{list(factor)} (chunk {vol.meta.chunk_size(mip).tolist()})"
    )
  create_downsample_scales(vol.meta, mip, shape, factor, num_mips=len(factors))
  vol.commit_info()

  method = pooling.method_for_layer(vol.layer_type, method)
  bounds = get_bounds(vol, bounds, mip, mip)
  shape = Vec(*shape)

  if pooling._host_pool_active():
    # CPU-only host: per-cutout native pooling is the production path
    # (same policy as batched_ccl_faces) — an XLA-CPU batch dispatch is
    # a ~9x pessimization on the most common task type. The cutout
    # stream still pipelines: downloads prefetch and chunk encodes
    # thread while the native kernels pool (ISSUE 3).
    stats = {"batched_cutouts": 0, "edge_cutouts": 0, "dispatches": 0,
             "native_cutouts": 0, "drained": False}
    from ..lib import chunk_bboxes
    from ..pipeline import run_tasks_pipelined

    def native_tasks():
      for gbox in chunk_bboxes(bounds, shape, offset=bounds.minpt, clamp=False):
        if Bbox.intersection(gbox, bounds).empty():
          continue
        yield DownsampleTask(
          layer_path=layer_path, mip=mip, shape=shape.tolist(),
          offset=[int(v) for v in gbox.minpt], fill_missing=fill_missing,
          sparse=sparse, num_mips=len(factors), factor=tuple(factor),
          compress=compress, downsample_method=method,
        )

    run_stats = run_tasks_pipelined(native_tasks(), drain_flag=drain_flag)
    stats["native_cutouts"] = run_stats["executed"]
    stats["drained"] = run_stats["drained"]
    from ..observability import device as device_telemetry

    device_telemetry.LEDGER.record_fastpath(host=run_stats["executed"])
    return stats

  full_boxes = []
  edge_offsets = []  # nominal grid offsets; the per-task path clamps itself
  from ..lib import chunk_bboxes

  for gbox in chunk_bboxes(bounds, shape, offset=bounds.minpt, clamp=False):
    clipped = Bbox.intersection(gbox, bounds)
    if clipped == gbox:
      full_boxes.append(gbox)
    elif not clipped.empty():
      edge_offsets.append(gbox.minpt)

  mesh = mesh if mesh is not None else make_mesh()
  is_u64_mode = method == "mode" and vol.dtype.itemsize == 8
  # shared instance: a fresh ChunkExecutor per call would recompile the
  # pyramid on every lease batch
  executor = cached_chunk_executor(
    mesh, factors=tuple(factors), method=method, sparse=sparse,
    planes=2 if is_u64_mode else 1,
  )
  # the fused walk's span attributes: every device.execute this run emits
  # records which mip range the one-dispatch pyramid produced
  executor.span_attrs = {"mip_from": int(mip), "mip_to": int(mip) + len(factors)}

  stats = {"batched_cutouts": 0, "edge_cutouts": 0, "paged_cutouts": 0,
           "dispatches": 0, "drained": False}

  def draining() -> bool:
    if drain_flag is not None and drain_flag.is_set():
      stats["drained"] = True
    return stats["drained"]

  from ..pipeline import shared_encode_pool, shared_prefetch_pool

  def upload_batch(boxes, mips_out):
    """Route every chunk encode+put through the shared encode pool under
    one ticket — callers overlap it with the next batch's compute and
    only join one batch behind (ISSUE 3: the encode stage was the serial
    tail of every device round)."""
    ticket = shared_encode_pool().ticket()
    for mip_idx, batch_arr in enumerate(mips_out):
      f = Vec(*np.prod(np.asarray(factors[: mip_idx + 1]), axis=0))
      dest_mip = mip + mip_idx + 1
      for k, box in enumerate(boxes):
        mn = box.minpt // f
        arr = _from_batch_layout(batch_arr[k])
        dest_box = Bbox(mn, mn + Vec(*arr.shape[:3]))
        dest_box = Bbox.intersection(dest_box, vol.meta.bounds(dest_mip))
        sl = tuple(slice(0, int(s)) for s in dest_box.size3())
        vol.upload(
          dest_box, arr[sl].astype(vol.dtype, copy=False),
          dest_mip, compress, sink=ticket,
        )
    return ticket

  def run_batch(boxes, imgs):
    mips_out = device_pyramid_batch(executor, imgs, is_u64_mode)
    stats["batched_cutouts"] += len(boxes)
    stats["dispatches"] += 1
    return upload_batch(boxes, mips_out)

  # double buffering: batch i+1's downloads run while batch i computes
  # and uploads (prefetch pool is distinct from the chunk-get pool the
  # downloads fan out to — same-pool nesting would deadlock)
  batches = [
    full_boxes[i : i + batch_size]
    for i in range(0, len(full_boxes), batch_size)
  ]
  io_pool = shared_prefetch_pool()
  pending = (
    [io_pool.submit(vol.download, b) for b in batches[0]]
    if batches else []
  )
  prev_ticket = None
  for i, batch in enumerate(batches):
    if draining():
      break
    imgs = [f.result() for f in pending]
    pending = (
      [io_pool.submit(vol.download, b) for b in batches[i + 1]]
      if i + 1 < len(batches) else []
    )
    # join batch i-1's uploads only now: they overlapped batch i's
    # downloads and this batch's device dispatch
    if prev_ticket is not None:
      prev_ticket.join()
    prev_ticket = run_batch(batch, imgs)
  if prev_ticket is not None:
    prev_ticket.join()
  for f in pending:  # drained mid-stream: settle abandoned downloads
    try:
      f.result()
    except Exception:  # noqa: BLE001 - nothing consumed them
      pass

  # ragged edge cells (ISSUE 12): the paged pyramid packs every clamped
  # cutout into fixed pages, so edges ride the batched device path under
  # the same compiled signature as every other round; the per-task solo
  # path remains only for factor chains no page tiles (pages_compatible)
  from .paged import PagedPyramid, pages_compatible

  if edge_offsets and pages_compatible(tuple(factors)) and not draining():
    from ..tasks.image import downsample_and_upload

    edge_boxes = [
      Bbox.intersection(Bbox(offset, offset + shape), bounds)
      for offset in edge_offsets
    ]
    futs = [io_pool.submit(vol.download, b) for b in edge_boxes]
    imgs = [f.result() for f in futs]
    pyramid = PagedPyramid(
      imgs, tuple(factors), len(factors), method=method, sparse=sparse,
      mesh=mesh,
    )
    ticket = shared_encode_pool().ticket()
    while pyramid.pending and not draining():
      for idx in pyramid.run_round():
        # the solo task's own upload routine, fed the paged mips: chunk
        # bytes stay identical to per-task execution
        downsample_and_upload(
          None, edge_boxes[idx], vol, task_shape=shape.tolist(), mip=mip,
          num_mips=len(factors), factor=tuple(factor), sparse=sparse,
          method=method, compress=compress,
          _mips_out=pyramid.result(idx), sink=ticket,
        )
        stats["paged_cutouts"] += 1
      stats["dispatches"] += 1
    ticket.join()
  else:
    for offset in edge_offsets:
      if draining():
        break
      DownsampleTask(
        layer_path=layer_path,
        mip=mip,
        shape=shape.tolist(),
        offset=[int(v) for v in offset],
        fill_missing=fill_missing,
        sparse=sparse,
        num_mips=len(factors),
        factor=tuple(factor),
        compress=compress,
        downsample_method=method,
      ).execute()
      stats["edge_cutouts"] += 1

  # fast-path eligibility (ISSUE 7): paged edge cutouts ride the batched
  # device program, so only the solo fallback counts as host deliveries
  from ..observability import device as device_telemetry

  device_telemetry.LEDGER.record_fastpath(
    batched=stats["batched_cutouts"] + stats["paged_cutouts"],
    host=stats["edge_cutouts"],
  )
  return stats


# ---------------------------------------------------------------------------
# batched CCL + skeleton forges (VERDICT round-1 item 3: the lease-K →
# one-dispatch pattern generalized beyond downsampling)


def _chunked(items, size):
  return [items[i : i + size] for i in range(0, len(items), size)]


def batched_ccl_faces(
  src_path: str,
  mip: int = 0,
  shape: Sequence[int] = (448, 448, 448),
  batch_size: int = 8,
  threshold_gte=None,
  threshold_lte=None,
  fill_missing: bool = False,
  mesh=None,
) -> dict:
  """CCL pass 1 over a whole layer with batched device dispatches.

  Consumes the same task grid create_ccl_face_tasks builds (identical
  task_nums, offsets, and face outputs — later passes cannot tell the
  difference). Cutouts stream through the PAGED CCL kernel (ISSUE 12) in
  prefetched mixed-shape groups — one compiled signature regardless of
  boundary clamping. When the tile config can't page
  (ccl_page_compatible), the pre-paged per-shape partition remains, with
  single-member shapes on the per-task path.
  """
  from ..ops.ccl import (
    _batch_executor,
    _ccl_backend,
    connected_components_batch,
  )
  from ..storage import CloudFiles
  from ..task_creation.ccl import create_ccl_face_tasks
  from ..tasks.ccl import (
    _offset_components,
    _prep_ccl_image,
    ccl_scratch_path,
    store_ccl_faces,
  )
  tasks = list(create_ccl_face_tasks(
    src_path, mip=mip, shape=shape, threshold_gte=threshold_gte,
    threshold_lte=threshold_lte, fill_missing=fill_missing,
  ))
  stats = {"batched_cutouts": 0, "edge_cutouts": 0, "dispatches": 0}
  if _ccl_backend() == "native":
    # CPU-only host: the native two-pass union-find (per cutout) is the
    # production path — the device kernel on XLA CPU is orders of
    # magnitude slower, so batching it would be a pessimization
    for t in tasks:
      t.execute()
      stats["edge_cutouts"] += 1
    return stats
  files = CloudFiles(src_path)
  scratch = ccl_scratch_path(src_path, mip)

  def prep(task):
    img, cutout, core = _prep_ccl_image(
      src_path, mip, task.shape, task.offset, fill_missing,
      threshold_gte, threshold_lte,
    )
    return task, img, cutout, core

  from .paged import ccl_page_compatible, paged_ccl

  if ccl_page_compatible():
    # ragged paged CCL (ISSUE 12): every cutout — boundary or interior —
    # rides the page kernel under ONE compiled signature, so there is no
    # per-shape partition and no single-member solo fallback
    groups = _chunked(tasks, batch_size)
    with cf.ThreadPoolExecutor(max_workers=8) as io_pool:
      pending = [io_pool.submit(prep, t) for t in groups[0]] if groups else []
      for i, group in enumerate(groups):
        preps = [f.result() for f in pending]
        pending = (
          [io_pool.submit(prep, t) for t in groups[i + 1]]
          if i + 1 < len(groups) else []
        )
        comps = paged_ccl([p[1] for p in preps], 6, mesh=mesh)
        stats["dispatches"] += 1
        for (task, _img, cutout, core), cc in zip(preps, comps):
          cc = _offset_components(cc, task.task_num, task.shape)
          store_ccl_faces(cc, cutout, core, task.task_num, files, scratch)
          stats["batched_cutouts"] += 1
    from ..observability import device as device_telemetry

    device_telemetry.LEDGER.record_fastpath(
      batched=stats["batched_cutouts"], host=stats["edge_cutouts"]
    )
    return stats

  # page-incompatible tile config: the pre-ISSUE-12 per-shape partition —
  # boundary tasks clamped along the same dataset faces batch together;
  # shapes with a single member run the plain task path
  executor = _batch_executor(6, mesh=mesh)
  vol = Volume(src_path, mip=mip)
  bounds = vol.meta.bounds(mip)
  by_shape = {}
  for t in tasks:
    cutout = Bbox.intersection(Bbox(t.offset, t.offset + t.shape + 1), bounds)
    by_shape.setdefault(tuple(cutout.size3()), []).append(t)

  with cf.ThreadPoolExecutor(max_workers=8) as io_pool:
    for shp, members in by_shape.items():
      if len(members) == 1:
        members[0].execute()
        stats["edge_cutouts"] += 1
        continue
      groups = _chunked(members, batch_size)
      # prefetch one group ahead: group i+1 downloads while i computes
      pending = [io_pool.submit(prep, t) for t in groups[0]]
      for i, group in enumerate(groups):
        preps = [f.result() for f in pending]
        pending = (
          [io_pool.submit(prep, t) for t in groups[i + 1]]
          if i + 1 < len(groups) else []
        )
        imgs = np.stack([p[1] for p in preps])
        comps = connected_components_batch(imgs, executor=executor)
        stats["dispatches"] += 1
        for (task, _img, cutout, core), cc in zip(preps, comps):
          cc = _offset_components(cc, task.task_num, task.shape)
          store_ccl_faces(cc, cutout, core, task.task_num, files, scratch)
          stats["batched_cutouts"] += 1
  from ..observability import device as device_telemetry

  device_telemetry.LEDGER.record_fastpath(
    batched=stats["batched_cutouts"], host=stats["edge_cutouts"]
  )
  return stats


def batched_skeleton_forge(
  cloudpath: str,
  mip: int = 0,
  shape: Sequence[int] = (512, 512, 512),
  batch_size: int = 4,
  mesh=None,
  **skeleton_kwargs,
) -> dict:
  """Skeleton forge with the flop-heavy EDT batched across K tasks.

  On the device EDT backend, tasks stream in prefetched MIXED-shape
  groups through the paged canonical-shape EDT (ISSUE 12) — one compiled
  signature, no per-shape partition, no solo fallback. Host backends keep
  the per-shape grouping: label prep on IO threads, all K EDTs as one
  edt_batch call (which runs the native/numpy kernel per cutout), then
  per-task host TEASAR + uploads via SkeletonTask.execute(_prepared,
  _edt_field). Outputs are identical to solo task execution either way.
  """
  from ..ops.edt import edt_batch
  from ..task_creation.skeleton import create_skeletonizing_tasks

  tasks = list(create_skeletonizing_tasks(
    cloudpath, mip=mip, shape=shape, **skeleton_kwargs
  ))
  vol = Volume(cloudpath, mip=mip)
  anis = tuple(float(v) for v in vol.resolution)
  bounds = vol.meta.bounds(mip)
  stats = {"batched_cutouts": 0, "solo_cutouts": 0, "dispatches": 0}

  eligible = []
  by_shape = {}
  for t in tasks:
    core = Bbox.intersection(Bbox(t.offset, t.offset + t.shape), bounds)
    if core.empty():
      continue
    eligible.append(t)
    cutout = Bbox.intersection(Bbox(core.minpt, core.maxpt + 1), bounds)
    by_shape.setdefault(tuple(cutout.size3()), []).append(t)

  def prep(task):
    return task, task.prepare_labels(Volume(
      cloudpath, mip=mip, fill_missing=task.fill_missing, bounded=False
    ))

  from ..ops.edt import _host_backend
  from .paged import paged_edt

  if _host_backend() == "device":
    # ragged paged EDT (ISSUE 12): canonical-shape pages batch every
    # cutout — boundary or interior — through one compiled signature, so
    # mixed shapes need neither a per-shape partition nor solo fallbacks
    groups = _chunked(eligible, batch_size)
    with cf.ThreadPoolExecutor(max_workers=8) as io_pool:
      pending = [io_pool.submit(prep, t) for t in groups[0]] if groups else []
      for i, group in enumerate(groups):
        preps = [f.result() for f in pending]
        pending = (
          [io_pool.submit(prep, t) for t in groups[i + 1]]
          if i + 1 < len(groups) else []
        )
        preps = [(t, p) for t, p in preps if p is not None]
        if not preps:
          continue
        fields = paged_edt([p[0] for _, p in preps], anis, mesh=mesh)
        stats["dispatches"] += 1
        for (task, prepared), field in zip(preps, fields):
          task.execute(_prepared=prepared, _edt_field=field)
          stats["batched_cutouts"] += 1
    from ..observability import device as device_telemetry

    device_telemetry.LEDGER.record_fastpath(
      batched=stats["batched_cutouts"], host=stats["solo_cutouts"]
    )
    return stats

  with cf.ThreadPoolExecutor(max_workers=8) as io_pool:
    for shp, members in by_shape.items():
      if len(members) == 1:
        members[0].execute()
        stats["solo_cutouts"] += 1
        continue
      groups = _chunked(members, batch_size)
      pending = [io_pool.submit(prep, t) for t in groups[0]]
      for i, group in enumerate(groups):
        preps = [f.result() for f in pending]
        pending = (
          [io_pool.submit(prep, t) for t in groups[i + 1]]
          if i + 1 < len(groups) else []
        )
        preps = [(t, p) for t, p in preps if p is not None]
        if not preps:
          continue
        labels_batch = np.stack([p[0] for _, p in preps])
        fields = edt_batch(labels_batch, anis, black_border=True)
        stats["dispatches"] += 1
        for (task, prepared), field in zip(preps, fields):
          task.execute(_prepared=prepared, _edt_field=field)
          stats["batched_cutouts"] += 1
  from ..observability import device as device_telemetry

  device_telemetry.LEDGER.record_fastpath(
    batched=stats["batched_cutouts"], host=stats["solo_cutouts"]
  )
  return stats
