"""Chunked Precomputed volume IO — the data plane of the framework.

Capability-parity target: the subset of CloudVolume the reference pipeline
uses for image IO (download/upload of bbox cutouts at a mip, fill_missing,
bounded clamping, renumbered downloads, chunk-aligned writes, deletion) —
see /root/reference/igneous/tasks/image/image.py:434-517 for the canonical
consumer. Mesh/skeleton sub-clients live in their own modules
(``igneous_tpu.mesh_io``, ``igneous_tpu.skeleton_io``).

Design: pure host IO. Device compute happens in ``igneous_tpu.ops`` on
arrays produced here; this layer stays numpy so the TPU never blocks on
object-store latency (tasks batch many cutouts per device step instead).
"""

from __future__ import annotations

import concurrent.futures as cf
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

from . import chunk_cache, codecs, integrity, telemetry
from .lib import Bbox, Vec, chunk_bboxes, jsonify
from .meta import PrecomputedMetadata
from .observability import trace as _trace
from .storage import CloudFiles, decompress_bytes

IO_THREADS = 8


class VolumeException(Exception):
  pass


class OutOfBoundsError(VolumeException):
  pass


class AlignmentError(VolumeException):
  pass


class EmptyVolumeError(VolumeException):
  pass


def _renumber(img: np.ndarray, preserve_zero: bool = True):
  """fastremap.renumber parity; see ops.remap (single implementation)."""
  from .ops.remap import renumber

  return renumber(img, start=1, preserve_zero=preserve_zero)


def _place(out: np.ndarray, bbox: Bbox, chunk_bbx: Bbox,
           chunk_img: np.ndarray) -> None:
  """Copy the part of ``chunk_img`` (covering ``chunk_bbx``) that lies in
  ``bbox`` into the cutout ``out`` of ``bbox``."""
  isect = Bbox.intersection(chunk_bbx, bbox)
  if isect.empty():
    return
  dst = tuple(
    slice(int(a), int(b))
    for a, b in zip(isect.minpt - bbox.minpt, isect.maxpt - bbox.minpt)
  )
  src = tuple(
    slice(int(a), int(b))
    for a, b in zip(isect.minpt - chunk_bbx.minpt, isect.maxpt - chunk_bbx.minpt)
  )
  out[dst] = chunk_img[src]


class Volume:
  """A Precomputed volume rooted at ``cloudpath`` (file:// or mem://)."""

  def __init__(
    self,
    cloudpath: str,
    mip: int = 0,
    fill_missing: bool = False,
    bounded: bool = True,
    non_aligned_writes: bool = False,
    delete_black_uploads: bool = False,
    background_color: int = 0,
    info: Optional[dict] = None,
    progress: bool = False,
    parallel: int = 1,
  ):
    from .graphene import (
      graphene_client,
      is_graphene,
      watershed_path,
    )

    self.graphene = None
    if is_graphene(cloudpath):
      # proofreading volume: metadata/chunks come from the watershed
      # (supervoxel) layer; the chunk-graph client supplies the
      # supervoxel->root and ->L2 mappings on download
      self.graphene = graphene_client(cloudpath)
      # server-addressed graphene volumes publish the watershed layer
      # location in their /info (data_dir); local doubles embed it in
      # the cloudpath itself
      cloudpath = (
        getattr(self.graphene, "data_dir", None)
        or watershed_path(cloudpath)
      )
    self.meta = PrecomputedMetadata(cloudpath, info=info)
    self.cloudpath = self.meta.cloudpath
    self.cf = self.meta.cf
    self.mip = mip
    self.fill_missing = fill_missing
    self.bounded = bounded
    self.non_aligned_writes = non_aligned_writes
    self.delete_black_uploads = delete_black_uploads
    self.background_color = background_color
    self.progress = progress
    self.parallel = parallel

  # -- constructors ---------------------------------------------------------

  @classmethod
  def create_new_info(cls, *args, **kw) -> dict:
    return PrecomputedMetadata.create_info(*args, **kw)

  @classmethod
  def create(cls, cloudpath: str, info: dict, **kw) -> "Volume":
    meta = PrecomputedMetadata(cloudpath, info=info)
    meta.commit_info()
    meta.refresh_provenance()
    meta.commit_provenance()
    return cls(cloudpath, **kw)

  @classmethod
  def from_numpy(
    cls,
    arr: np.ndarray,
    cloudpath: str,
    resolution: Sequence[int] = (1, 1, 1),
    voxel_offset: Sequence[int] = (0, 0, 0),
    chunk_size: Sequence[int] = (64, 64, 64),
    layer_type: Optional[str] = None,
    encoding: str = "raw",
    encoding_level: Optional[int] = None,
    max_mip: int = 0,
    compress="gzip",
  ) -> "Volume":
    if arr.ndim == 3:
      arr = arr[..., np.newaxis]
    if layer_type is None:
      layer_type = (
        "segmentation" if np.issubdtype(arr.dtype, np.unsignedinteger)
        and arr.dtype.itemsize >= 4 else "image"
      )
    info = cls.create_new_info(
      num_channels=arr.shape[3],
      layer_type=layer_type,
      data_type=np.dtype(arr.dtype).name,
      encoding=encoding,
      resolution=resolution,
      voxel_offset=voxel_offset,
      volume_size=arr.shape[:3],
      chunk_size=chunk_size,
    )
    if max_mip != 0:
      raise NotImplementedError(
        "max_mip: build mips with create_downsampling_tasks after ingest"
      )
    vol = cls.create(cloudpath, info)
    if encoding_level is not None:
      # must precede the upload: the quality knob lives in the scale
      vol.meta.set_encoding(0, None, encoding_level)
      vol.commit_info()
    vol.upload(vol.meta.bounds(0), arr, mip=0, compress=compress)
    return vol

  # -- properties -----------------------------------------------------------

  @property
  def info(self) -> dict:
    return self.meta.info

  @property
  def layer_type(self) -> str:
    return self.meta.layer_type

  @property
  def dtype(self) -> np.dtype:
    return self.meta.dtype

  @property
  def num_channels(self) -> int:
    return self.meta.num_channels

  @property
  def bounds(self) -> Bbox:
    return self.meta.bounds(self.mip)

  @property
  def chunk_size(self) -> Vec:
    return self.meta.chunk_size(self.mip)

  @property
  def resolution(self) -> Vec:
    return self.meta.resolution(self.mip)

  @property
  def voxel_offset(self) -> Vec:
    return self.meta.voxel_offset(self.mip)

  @property
  def volume_size(self) -> Vec:
    return self.meta.volume_size(self.mip)

  @property
  def shape(self) -> Tuple[int, int, int, int]:
    s = self.volume_size
    return (int(s.x), int(s.y), int(s.z), self.num_channels)

  @property
  def encoding(self) -> str:
    return self.meta.encoding(self.mip)

  def mip_bounds(self, mip: int) -> Bbox:
    return self.meta.bounds(mip)

  def mip_chunk_size(self, mip: int) -> Vec:
    return self.meta.chunk_size(mip)

  def mip_resolution(self, mip: int) -> Vec:
    return self.meta.resolution(mip)

  def mip_volume_size(self, mip: int) -> Vec:
    return self.meta.volume_size(mip)

  def mip_voxel_offset(self, mip: int) -> Vec:
    return self.meta.voxel_offset(mip)

  def commit_info(self):
    self.meta.commit_info()

  def refresh_info(self):
    self.meta.refresh_info()

  def commit_provenance(self):
    self.meta.commit_provenance()

  @property
  def provenance(self):
    if self.meta.provenance is None:
      self.meta.refresh_provenance()
    return self.meta.provenance

  # -- download -------------------------------------------------------------

  def _decode_chunk(
    self, data: Optional[bytes], chunk_bbx: Bbox, mip: int,
    writable: bool = True,
  ) -> np.ndarray:
    shape = tuple(int(v) for v in chunk_bbx.size3()) + (self.num_channels,)
    if data is None:
      if not self.fill_missing:
        raise EmptyVolumeError(
          f"Missing chunk {self.meta.chunk_name(mip, chunk_bbx)} in {self.cloudpath}"
        )
      return np.full(shape, self.background_color, dtype=self.dtype)
    return codecs.decode(
      data,
      self.meta.encoding(mip),
      shape,
      self.dtype,
      block_size=self.meta.cseg_block_size(mip),
      writable=writable,
    )

  def _decode_stored(
    self, stored, chunk_bbx: Bbox, mip: int
  ) -> np.ndarray:
    """Decode a (stored bytes, wire method) pair through the shared chunk
    decode cache: a digest hit skips BOTH the inflate and the chunk codec.
    Returns a read-only chunk — every caller copies voxels into its own
    cutout assembly (the ``writable=False`` contract)."""
    data, method = stored
    if data is None:
      return self._decode_chunk(None, chunk_bbx, mip, writable=False)
    encoding = self.meta.encoding(mip)
    # uncompressed raw chunks decode as a zero-copy view; caching those
    # would spend budget to save nothing
    cacheable = chunk_cache.enabled() and (
      method is not None or encoding != "raw"
    )
    if not cacheable:
      return self._guarded_decode(data, method, chunk_bbx, mip)
    bbox_key = (
      tuple(int(v) for v in chunk_bbx.minpt),
      tuple(int(v) for v in chunk_bbx.maxpt),
    )
    key, arr = chunk_cache.lookup(self.cloudpath, mip, bbox_key, data)
    if arr is not None:
      return arr
    # a corrupt chunk raises out of the guarded decode BEFORE
    # chunk_cache.store — no cache tier ever holds bytes that failed
    # to decode, and the digest-keyed lookup above cannot alias a
    # corrupt wire body onto a previously-cached clean decode
    arr = self._guarded_decode(data, method, chunk_bbx, mip)
    return chunk_cache.store(key, arr)

  def _guarded_decode(
    self, data: bytes, method: Optional[str], chunk_bbx: Bbox, mip: int
  ) -> np.ndarray:
    """Inflate + codec-decode with the read-path corruption guard: a
    torn or bit-flipped object at rest surfaces as a typed
    :class:`~igneous_tpu.integrity.CorruptChunkError` (never an opaque
    zlib/codec traceback), ticks ``integrity.corrupt_reads``, and files
    the object reference in the layer's quarantine ledger."""
    import zlib

    try:
      return self._decode_chunk(
        decompress_bytes(data, method), chunk_bbx, mip, writable=False
      )
    except (OSError, EOFError, ValueError, zlib.error) as e:
      key = self.meta.chunk_name(mip, chunk_bbx)
      telemetry.incr("integrity.corrupt_reads")
      reason = f"{type(e).__name__}: {e}"
      integrity.quarantine(self.cloudpath, key, reason)
      raise integrity.CorruptChunkError(self.cloudpath, key, reason) from e

  def download(
    self,
    bbox: Bbox,
    mip: Optional[int] = None,
    renumber: bool = False,
    label: Optional[int] = None,
    parallel: Optional[int] = None,
    agglomerate: bool = False,
    timestamp: Optional[float] = None,
    stop_layer: Optional[int] = None,
  ):
    """Download cutout; returns (x, y, z, c) array (plus mapping if renumber).

    Graphene volumes additionally accept ``agglomerate`` (map supervoxels
    to proofread root ids as of ``timestamp``) and ``stop_layer=2`` (map
    to L2 chunk-graph ids) — the reference's
    ``download(agglomerate, timestamp, stop_layer)`` surface
    (/root/reference/igneous/tasks/skeleton.py:159-164,:337-398).
    """
    if (agglomerate or stop_layer is not None) and self.graphene is None:
      raise ValueError(
        "agglomerate/stop_layer require a graphene:// volume"
      )
    if stop_layer not in (None, 1, 2):
      # pure argument validation: reject before any chunk is fetched
      raise ValueError(
        f"stop_layer={stop_layer!r} unsupported: 1 (supervoxels) and "
        "2 (L2 chunk ids) are the graphene stop layers"
      )
    mip = self.mip if mip is None else mip
    bbox = Bbox(bbox.minpt, bbox.maxpt)
    with _trace.span("volume.download", voxels=int(np.prod(bbox.size3()))):
      return self._download(bbox, mip, renumber, label, parallel,
                            agglomerate, timestamp, stop_layer)

  def _download(self, bbox, mip, renumber, label, parallel, agglomerate,
                timestamp, stop_layer):
    bounds = self.meta.bounds(mip)
    if self.bounded:
      if not bounds.contains_bbox(bbox):
        raise OutOfBoundsError(f"{bbox} is not contained in {bounds}")
      inner = bbox
    else:
      inner = Bbox.intersection(bbox, bounds)

    # Fortran order end to end: decoded chunks are F-order views, the
    # device layout (c,z,y,x) is a zero-copy transpose of an F-order
    # cutout, and raw encode is tobytes("F") — a C-order cutout here would
    # force a full-volume transpose copy on BOTH sides of the compute.
    out = self._cutout(bbox, inner)
    if self.meta.is_sharded(mip):
      from .sharded_image import download_sharded

      renders = download_sharded(self, inner, mip)
      with _trace.span("volume.assemble"):
        for chunk_bbx, chunk_img in renders:
          _place(out, bbox, chunk_bbx, chunk_img)
    else:
      # stored chunks are grid-aligned and clamped to the volume bounds
      chunks = [
        c
        for c in (
          Bbox.intersection(gc, bounds)
          for gc in chunk_bboxes(
            inner,
            self.meta.chunk_size(mip),
            offset=self.meta.voxel_offset(mip),
            clamp=False,
          )
        )
        if not c.empty()
      ]
      keys = [self.meta.chunk_name(mip, c) for c in chunks]
      with _trace.span("volume.get", chunks=len(keys)):
        stored = self._parallel_get_stored(keys, parallel)
      workers = self._decode_workers(stored, mip, parallel)
      decode = _trace.span("volume.decode", chunks=len(keys), workers=workers)
      with decode:
        decode.set(placed=self._decode_into(
          out, bbox, chunks, stored, mip, workers
        ))

    if self.graphene is not None and (agglomerate or stop_layer is not None):
      from .graphene import voxel_chunk_index

      if stop_layer == 2:
        # graph chunks are defined at the watershed BASE resolution:
        # scale mip coordinates by the downsample ratio so L2 identity
        # is mip-invariant
        chunks = voxel_chunk_index(
          bbox.minpt, out.shape[:3], self.graphene.chunk_size,
          scale=self.meta.downsample_ratio(mip),
        )
        mapped = self.graphene.get_l2_ids(
          out[..., 0], chunks, timestamp
        )
      elif stop_layer == 1:
        mapped = out[..., 0].astype(np.uint64, copy=False)  # raw supervoxels
      else:
        mapped = self.graphene.get_roots(out[..., 0], timestamp)
      # root/L2 ids live above 2^40 — NEVER narrow them to the watershed
      # layer's dtype (a uint32 layer would silently wrap ids to garbage)
      out = mapped[..., np.newaxis].astype(np.uint64, copy=False)

    if label is not None:
      out = (out == label).astype(np.uint8)
    if renumber:
      out, mapping = _renumber(out)
      return out, mapping
    return out

  def _cutout(self, bbox: Bbox, inner: Bbox) -> np.ndarray:
    out_shape = tuple(int(v) for v in bbox.size3()) + (self.num_channels,)
    if inner == bbox:
      # the chunk grid covers every output voxel (missing chunks arrive
      # background-filled): skip the background memset
      return np.empty(out_shape, dtype=self.dtype, order="F")
    return np.full(
      out_shape, self.background_color, dtype=self.dtype, order="F"
    )

  def _parallel_get_stored(self, keys: List[str], parallel: Optional[int]):
    # stored-domain reads: (wire bytes, method) pairs, decompressed by
    # the caller AFTER the cache digest gets a chance to skip the work.
    # parallel=1 keeps strict serial semantics; anything wider rides the
    # fixed-width shared pool — spawning a fresh executor per cutout (to
    # honor an exact thread count) showed up as pure thread-start
    # overhead in the e2e profile (ISSUE 3)
    if (parallel or IO_THREADS) <= 1 or len(keys) <= 1:
      return [self.cf.get_stored(k) for k in keys]
    from .pipeline.encoder import shared_io_pool

    return list(shared_io_pool().map(self.cf.get_stored, keys))

  def _decode_workers(self, stored, mip: int, parallel: Optional[int]) -> int:
    """How many of a cutout's chunks decode at once: the shared IO pool's
    width when more than one chunk needs inflate or codec work, else 1.
    Uncompressed raw chunks decode as zero-copy views, which a pool hop
    would only slow down; parallel=1 keeps strict serial semantics."""
    if (parallel or IO_THREADS) <= 1:
      return 1
    raw = self.meta.encoding(mip) == "raw"
    work = sum(
      1 for data, method in stored
      if data is not None and (method is not None or not raw)
    )
    if work <= 1:
      return 1
    from .pipeline import config

    return min(config.io_threads(), len(stored))

  def _decode_into(self, out, bbox: Bbox, chunks, stored, mip: int,
                   workers: int) -> int:
    """Decode each chunk and copy its part of ``bbox`` into ``out`` in the
    same step, so no decoded chunk outlives its copy. PIL's decoders, zlib,
    the cseg decoders and numpy's copy release the GIL, so ``workers`` > 1
    scales with the host's cores; chunks are disjoint, so the threads write
    disjoint regions of ``out``. Returns how many chunks were placed side
    by side: by the IO pool's workers and the calling thread, which takes
    the chunks no worker has started yet rather than wait (0 inline). No
    caller downloads inside the IO pool, so its workers never wait on the
    pool they run on."""
    def place(chunk_bbx, s):
      _place(out, bbox, chunk_bbx, self._decode_stored(s, chunk_bbx, mip))

    if workers <= 1:
      for chunk_bbx, s in zip(chunks, stored):
        place(chunk_bbx, s)
      return 0
    from .pipeline.encoder import shared_io_pool

    # x-neighbours write into the same pages of the F-order cutout: hand
    # the pool z- and y-neighbours first, so its workers first-touch
    # disjoint pages side by side
    order = sorted(zip(chunks, stored), key=lambda cs: tuple(cs[0].minpt))
    pool = shared_io_pool()
    jobs = [(pool.submit(place, c, s), c, s) for c, s in order]
    try:
      # the calling thread places, from the back, what no worker has taken
      for fut, c, s in reversed(jobs):
        if fut.cancel():
          place(c, s)
      for fut, _c, _s in jobs:
        if not fut.cancelled():
          fut.result()
    except BaseException:
      for fut, _c, _s in jobs:
        fut.cancel()
      raise
    return len(jobs)

  def __getitem__(self, slices) -> np.ndarray:
    bbox = self._interpret_slices(slices)
    return self.download(bbox)

  def _interpret_slices(self, slices) -> Bbox:
    if isinstance(slices, Bbox):
      return slices
    if isinstance(slices, (list, tuple)) and all(isinstance(s, slice) for s in slices):
      bounds = self.bounds
      fixed = []
      for i, s in enumerate(slices[:3]):
        start = s.start if s.start is not None else int(bounds.minpt[i])
        stop = s.stop if s.stop is not None else int(bounds.maxpt[i])
        fixed.append(slice(start, stop))
      return Bbox.from_slices(fixed)
    raise TypeError(f"Unsupported index: {slices}")

  def exists(self, bbox: Bbox, mip: Optional[int] = None):
    """Map of chunk key → bool for chunks covering bbox (TouchTask support)."""
    mip = self.mip if mip is None else mip
    bounds = self.meta.bounds(mip)
    chunks = [
      Bbox.intersection(c, bounds)
      for c in chunk_bboxes(
        bbox,
        self.meta.chunk_size(mip),
        offset=self.meta.voxel_offset(mip),
        clamp=False,
      )
    ]
    return {
      self.meta.chunk_name(mip, c): self.cf.exists(self.meta.chunk_name(mip, c))
      for c in chunks
      if not c.empty()
    }

  # -- upload ---------------------------------------------------------------

  def upload(
    self,
    bbox: Bbox,
    img: np.ndarray,
    mip: Optional[int] = None,
    compress: Optional[str] = "gzip",
    parallel: Optional[int] = None,
    sink=None,
  ):
    """``sink`` (pipeline.UploadTicket / SerialSink): when given, chunk
    encode+compress+put runs as submitted closures instead of inline —
    the staged pipeline's parallel encode/upload stage. The caller owns
    joining the sink before treating the upload as durable, and must not
    mutate ``img`` until then. Bytes are identical either way (each
    chunk encodes independently, gzip is mtime=0 deterministic)."""
    mip = self.mip if mip is None else mip
    if img.ndim == 3:
      img = img[..., np.newaxis]
    if tuple(img.shape[:3]) != tuple(int(v) for v in bbox.size3()):
      raise VolumeException(
        f"Image shape {img.shape} does not match bbox {bbox}"
      )
    if img.shape[3] != self.num_channels:
      raise VolumeException(
        f"Image has {img.shape[3]} channels, volume has {self.num_channels}"
      )
    if img.dtype != self.dtype:
      if not np.can_cast(img.dtype, self.dtype, casting="same_kind"):
        raise VolumeException(
          f"Image dtype {img.dtype} is not compatible with volume dtype "
          f"{self.meta.data_type}; cast explicitly."
        )
      img = img.astype(self.dtype)
    bounds = self.meta.bounds(mip)
    if self.bounded and not bounds.contains_bbox(bbox):
      raise OutOfBoundsError(f"{bbox} exceeds bounds {bounds}")

    cs = self.meta.chunk_size(mip)
    offset = self.meta.voxel_offset(mip)
    expanded = bbox.expand_to_chunk_size(cs, offset)
    clamped_expanded = Bbox.intersection(expanded, bounds)
    if clamped_expanded != bbox and not self.non_aligned_writes:
      raise AlignmentError(
        f"{bbox} is not chunk-aligned (chunk {list(map(int, cs))}, "
        f"offset {list(map(int, offset))}) nor clipped to bounds {bounds}"
      )

    if self.meta.is_sharded(mip):
      raise VolumeException(
        "Direct writes to sharded scales are not supported; "
        "use ImageShardTransferTask / make_shard."
      )

    encoding = self.meta.encoding(mip)
    block_size = self.meta.cseg_block_size(mip)
    # per-scale quality knobs (meta.set_encoding; reference
    # task_creation/common.py:215-236 records them in the scale)
    enc_kw = {}
    scale = self.meta.scale(mip)
    if encoding == "jpeg" and "jpeg_quality" in scale:
      enc_kw["jpeg_quality"] = int(scale["jpeg_quality"])
    elif encoding == "png" and "png_level" in scale:
      enc_kw["png_level"] = int(scale["png_level"])
    jobs = []  # (key, cutout): encode deferred so a sink can thread it
    deletes = []
    for gchunk in chunk_bboxes(bbox, cs, offset=offset, clamp=False):
      chunk_bbx = Bbox.intersection(gchunk, bounds)  # stored chunk extent
      if chunk_bbx.empty():
        continue
      isect = Bbox.intersection(chunk_bbx, bbox)
      src = tuple(
        slice(int(a), int(b))
        for a, b in zip(isect.minpt - bbox.minpt, isect.maxpt - bbox.minpt)
      )
      key = self.meta.chunk_name(mip, chunk_bbx)
      if isect == chunk_bbx:
        cutout = img[src]
      else:
        # non-aligned write: read-modify-write the grid-aligned chunk so the
        # stored file keeps its canonical key and untouched voxels survive
        shape = tuple(int(v) for v in chunk_bbx.size3()) + (self.num_channels,)
        data = self.cf.get(key)
        if data is None:
          base = np.full(shape, self.background_color, dtype=self.dtype)
        else:
          base = codecs.decode(
            data, encoding, shape, self.dtype, block_size=block_size
          )
        dst = tuple(
          slice(int(a), int(b))
          for a, b in zip(isect.minpt - chunk_bbx.minpt, isect.maxpt - chunk_bbx.minpt)
        )
        base[dst] = img[src]
        cutout = base
      if self.delete_black_uploads and np.all(cutout == self.background_color):
        deletes.append(key)
        continue
      jobs.append((key, cutout))

    if sink is not None:
      for key, cutout in jobs:
        def encode_and_put(key=key, cutout=cutout):
          self.cf.put(
            key,
            codecs.encode(cutout, encoding, block_size=block_size, **enc_kw),
            compress=compress,
          )
        sink.submit(encode_and_put)
    else:
      puts = [
        (key, codecs.encode(cutout, encoding, block_size=block_size, **enc_kw))
        for key, cutout in jobs
      ]
      self._parallel_put(puts, compress, parallel)
    if deletes:
      self.cf.delete(deletes)
    # decode-cache hygiene: entries under this (path, mip) are doomed
    # (digest keying already keeps late readers correct — a rewritten
    # chunk hashes differently — this frees the memory now). Sink-routed
    # puts may still be in flight; the pipeline runner re-invalidates
    # when the ticket joins.
    chunk_cache.invalidate(self.cloudpath, mip)

  def _parallel_put(self, puts, compress, parallel: Optional[int]):
    # same policy as _parallel_get: parallel=1 is serial, wider requests
    # share the fixed-width pool
    if (parallel or IO_THREADS) <= 1 or len(puts) <= 1:
      for key, data in puts:
        self.cf.put(key, data, compress=compress)
      return
    from .pipeline.encoder import shared_io_pool

    list(shared_io_pool().map(
      lambda kv: self.cf.put(kv[0], kv[1], compress=compress), puts
    ))

  def __setitem__(self, slices, img):
    bbox = self._interpret_slices(slices)
    if np.isscalar(img):
      img = np.full(
        tuple(int(v) for v in bbox.size3()) + (self.num_channels,),
        img,
        dtype=self.dtype,
      )
    self.upload(bbox, np.asarray(img, dtype=self.dtype))

  # -- deletion -------------------------------------------------------------

  def delete(self, bbox: Bbox, mip: Optional[int] = None):
    """Delete all chunk files covering bbox (must be chunk aligned)."""
    mip = self.mip if mip is None else mip
    cs = self.meta.chunk_size(mip)
    offset = self.meta.voxel_offset(mip)
    if bbox != bbox.expand_to_chunk_size(cs, offset).clamp(self.meta.bounds(mip)):
      raise AlignmentError(f"delete bbox {bbox} must be chunk aligned")
    keys = [
      self.meta.chunk_name(mip, c)
      for c in chunk_bboxes(bbox, cs, offset=offset)
    ]
    self.cf.delete(keys)
    chunk_cache.invalidate(self.cloudpath, mip)

  def __repr__(self):
    return (
      f"Volume({self.cloudpath!r}, mip={self.mip}, "
      f"bounds={self.bounds}, dtype={self.meta.data_type})"
    )


CloudVolume = Volume  # familiar alias for users migrating from the reference
