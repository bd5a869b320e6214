"""Plain reference for downsample campaigns.

Independent of the program under test: it imports nothing of
``igneous_tpu``. It reads a Neuroglancer Precomputed layer straight from
disk (``info`` JSON, chunk files named ``x0-x1_y0-y1_z0-z1`` with an
optional ``.gz``), decodes chunks with its own decoders (raw, the
compressed_segmentation spec, jpeg through OpenCV's libjpeg, not the
PIL that the program uses), recomputes the mip pyramid in numpy, and
compares it with the mips the campaign stored.

Pooling semantics (as ``igneous_tpu.ops.pooling`` documents them):

- average: per mip, the integer sum of the window, then round-half-up
  division; each mip is pooled from the previous one;
- mode: the value that occurs most often in the window, ties to the
  earliest window position, positions ordered z-major, then y, then x.

Lossy chunks (jpeg) are compared after the reference mip goes through
a jpeg round trip: encode at the layer's quality in the Precomputed
image layout, decode. Baseline jpeg with the standard quantization
tables and the accurate integer DCT decodes to the same pixels in every
libjpeg build, so equal pooled voxels give equal decoded pixels; any
pooled voxel that differs almost surely moves some pixel of its 8x8 jpeg
block.

``precision="low"`` is the control: 32-bit labels for 64-bit mode
pooling, bfloat16 arithmetic for average pooling.
"""

from __future__ import annotations

import concurrent.futures as cf
import gzip
import json
import os
from typing import List, Sequence, Tuple

import numpy as np

THREADS = min(8, os.cpu_count() or 1)


# ---------------------------------------------------------------------------
# layer access


def read_info(layer_dir: str) -> dict:
  with open(os.path.join(layer_dir, "info")) as f:
    return json.load(f)


def read_object(path: str):
  """Bytes of a stored chunk (``path`` or ``path.gz``), or None."""
  try:
    with open(path, "rb") as f:
      return f.read()
  except FileNotFoundError:
    pass
  try:
    with open(path + ".gz", "rb") as f:
      return gzip.decompress(f.read())
  except FileNotFoundError:
    return None


def chunk_boxes(scale: dict, lo: Sequence[int], hi: Sequence[int]):
  """Stored chunk boxes covering [lo, hi) at one scale, clamped to the
  scale's bounds."""
  cs = scale["chunk_sizes"][0]
  off = scale.get("voxel_offset", [0, 0, 0])
  size = scale["size"]
  ranges = []
  for a in range(3):
    start = off[a] + ((lo[a] - off[a]) // cs[a]) * cs[a]
    axis = []
    for c0 in range(start, hi[a], cs[a]):
      axis.append((c0, min(c0 + cs[a], off[a] + size[a])))
    ranges.append(axis)
  return [
    ((x0, y0, z0), (x1, y1, z1))
    for (z0, z1) in ranges[2] for (y0, y1) in ranges[1]
    for (x0, x1) in ranges[0]
  ]


def chunk_path(layer_dir: str, scale: dict, box) -> str:
  (x0, y0, z0), (x1, y1, z1) = box
  return os.path.join(
    layer_dir, scale["key"], f"{x0}-{x1}_{y0}-{y1}_{z0}-{z1}"
  )


# ---------------------------------------------------------------------------
# decoders


def decode_cseg(data: bytes, shape, dtype, block=(8, 8, 8)) -> np.ndarray:
  """One channel of the compressed_segmentation spec, vectorized over
  blocks that lie whole inside the chunk."""
  x, y, z = (int(v) for v in shape)
  bx, by, bz = (int(v) for v in block)
  gx, gy, gz = -(-x // bx), -(-y // by), -(-z // bz)
  words = np.frombuffer(data, dtype="<u4")
  base = int(words[0])
  n = gx * gy * gz
  hdr = words[base: base + 2 * n].reshape(n, 2).astype(np.int64)
  table = hdr[:, 0] & 0xFFFFFF
  bits = hdr[:, 0] >> 24
  voff = hdr[:, 1]
  is64 = np.dtype(dtype).itemsize == 8
  out = np.zeros((x, y, z), dtype=dtype)
  bidx = np.arange(n)
  bxi, byi, bzi = bidx % gx, (bidx // gx) % gy, bidx // (gx * gy)
  # the block view below is a view only while the chunk tiles into whole
  # blocks; otherwise every block takes the one-block path
  whole = np.full(n, not (x % bx or y % by or z % bz))
  nvox = bx * by * bz
  blocks = out.reshape(gx, bx, gy, by, gz, bz) if whole.all() else None
  for b in np.unique(bits[whole]):
    sel = np.nonzero(whole & (bits == b))[0]
    b = int(b)
    if b == 0:
      idx = np.zeros((len(sel), nvox), dtype=np.int64)
    else:
      per = 32 // b
      pos = np.arange(nvox)
      enc = words[
        base + voff[sel][:, None] + np.arange(-(-nvox * b // 32))[None, :]
      ]
      idx = (
        (enc[:, pos // per] >> ((pos % per) * b).astype(np.uint32))
        & np.uint32((1 << b) - 1)
      ).astype(np.int64)
    tab = base + table[sel][:, None]
    if is64:
      lo = words[tab + 2 * idx].astype(np.uint64)
      hi = words[tab + 2 * idx + 1].astype(np.uint64)
      vals = lo | (hi << np.uint64(32))
    else:
      vals = words[tab + idx]
    vals = vals.reshape(len(sel), bz, by, bx).transpose(0, 3, 2, 1)
    blocks.transpose(0, 2, 4, 1, 3, 5)[bxi[sel], byi[sel], bzi[sel]] = vals
  for k in np.nonzero(~whole)[0]:
    _decode_clipped_block(words, base, hdr[k], int(bxi[k]), int(byi[k]),
                          int(bzi[k]), block, out, is64)
  return out


def _decode_clipped_block(words, base, hdr, bxi, byi, bzi, block, out, is64):
  bx, by, bz = block
  x, y, z = out.shape
  sx, sy, sz = min(bx, x - bxi * bx), min(by, y - byi * by), min(bz, z - bzi * bz)
  nvox = sx * sy * sz
  bits, table, voff = int(hdr[0]) >> 24, int(hdr[0]) & 0xFFFFFF, int(hdr[1])
  if bits == 0:
    idx = np.zeros(nvox, dtype=np.int64)
  else:
    pos = np.arange(nvox) * bits
    enc = words[base + voff: base + voff + -(-nvox * bits // 32)]
    idx = ((enc[pos // 32].astype(np.uint64) >> (pos % 32).astype(np.uint64))
           & np.uint64((1 << bits) - 1)).astype(np.int64)
  if is64:
    vals = (words[base + table + 2 * idx].astype(np.uint64)
            | (words[base + table + 2 * idx + 1].astype(np.uint64) << np.uint64(32)))
  else:
    vals = words[base + table + idx]
  out[bxi * bx: bxi * bx + sx, byi * by: byi * by + sy,
      bzi * bz: bzi * bz + sz] = vals.reshape(sz, sy, sx).transpose(2, 1, 0)


def decode_jpeg(data: bytes, shape) -> np.ndarray:
  """Precomputed image layout: one grayscale image of width x and height
  y*z, the z slices stacked vertically."""
  import cv2

  x, y, z = (int(v) for v in shape)
  plane = cv2.imdecode(np.frombuffer(data, dtype=np.uint8),
                       cv2.IMREAD_GRAYSCALE)
  if plane is None:
    raise ValueError("undecodable jpeg chunk")
  return plane.reshape(z, y, x).transpose(2, 1, 0)


def jpeg_round_trip(img: np.ndarray, quality: int) -> np.ndarray:
  import cv2

  x, y, z = img.shape
  plane = np.ascontiguousarray(img.transpose(2, 1, 0)).reshape(z * y, x)
  ok, data = cv2.imencode(".jpg", plane,
                          [cv2.IMWRITE_JPEG_QUALITY, int(quality)])
  if not ok:
    raise ValueError("jpeg encode failed")
  return decode_jpeg(data.tobytes(), img.shape)


def decode(data: bytes, scale: dict, shape, dtype) -> np.ndarray:
  enc = scale["encoding"]
  if enc == "raw":
    return np.frombuffer(data, dtype=dtype).reshape(shape, order="F")
  if enc == "compressed_segmentation":
    return decode_cseg(data, shape, dtype,
                       scale.get("compressed_segmentation_block_size",
                                 (8, 8, 8)))
  if enc == "jpeg":
    return decode_jpeg(data, shape).astype(dtype, copy=False)
  raise ValueError(f"the reference reads no {enc!r} chunks")


def read_cutout(layer_dir: str, info: dict, mip: int, lo, hi,
                pool: cf.Executor) -> np.ndarray:
  """(x, y, z) voxels of [lo, hi) at ``mip``; a missing chunk raises."""
  scale = info["scales"][mip]
  dtype = np.dtype(info["data_type"])
  out = np.empty(tuple(h - l for l, h in zip(lo, hi)), dtype=dtype)

  def one(box):
    data = read_object(chunk_path(layer_dir, scale, box))
    if data is None:
      raise FileNotFoundError(chunk_path(layer_dir, scale, box))
    shape = tuple(b - a for a, b in zip(*box))
    arr = decode(data, scale, shape, dtype)
    a = [max(box[0][i], lo[i]) for i in range(3)]
    b = [min(box[1][i], hi[i]) for i in range(3)]
    out[a[0] - lo[0]: b[0] - lo[0], a[1] - lo[1]: b[1] - lo[1],
        a[2] - lo[2]: b[2] - lo[2]] = arr[
      a[0] - box[0][0]: b[0] - box[0][0], a[1] - box[0][1]: b[1] - box[0][1],
      a[2] - box[0][2]: b[2] - box[0][2]]

  list(pool.map(one, chunk_boxes(scale, lo, hi)))
  return out


# ---------------------------------------------------------------------------
# pooling


def _window_views(img: np.ndarray, f) -> List[np.ndarray]:
  """The voxels of every window, one strided view per window position,
  positions z-major, then y, then x; odd extents replicate the edge."""
  fx, fy, fz = (int(v) for v in f)
  pads = [(0, (-s) % k) for s, k in zip(img.shape, (fx, fy, fz))]
  if any(p[1] for p in pads):
    img = np.pad(img, pads, mode="edge")
  return [img[ox::fx, oy::fy, oz::fz]
          for oz in range(fz) for oy in range(fy) for ox in range(fx)]


def pool_mode(img: np.ndarray, f) -> np.ndarray:
  views = _window_views(img, f)
  n = len(views)
  counts = [np.ones(views[0].shape, dtype=np.int16) for _ in range(n)]
  for i in range(n):
    for j in range(i + 1, n):
      eq = views[i] == views[j]
      counts[i] += eq
      counts[j] += eq
  # highest count wins, ties to the earliest position: score = count*n - pos
  best = counts[0] * n
  out = views[0].copy()
  for i in range(1, n):
    score = counts[i] * n - i
    take = score > best
    best = np.where(take, score, best)
    out = np.where(take, views[i], out)
  return out


def pool_average(img: np.ndarray, f, low: bool = False) -> np.ndarray:
  views = _window_views(img, f)
  n = len(views)
  if low:
    import ml_dtypes

    bf = ml_dtypes.bfloat16
    acc = views[0].astype(bf)
    for v in views[1:]:
      acc = (acc + v.astype(bf)).astype(bf)
    mean = (acc / bf(n)).astype(np.float32)
    return np.clip(np.rint(mean), 0, np.iinfo(img.dtype).max).astype(img.dtype)
  # exact integer sum: uint16 holds n * 255 for the windows used here
  wide = np.uint16 if n * np.iinfo(img.dtype).max < 2**16 else np.int64
  acc = views[0].astype(wide)
  for v in views[1:]:
    acc += v
  return ((acc + n // 2) // n).astype(img.dtype)


def pyramid(img: np.ndarray, factors, method: str,
            precision: str = "exact") -> List[np.ndarray]:
  low = precision == "low"
  if low and method == "mode" and img.dtype.itemsize == 8:
    img = (img & np.uint64(0xFFFFFFFF)).astype(img.dtype)
  out, cur = [], img
  for f in factors:
    cur = pool_mode(cur, f) if method == "mode" else pool_average(cur, f, low)
    out.append(cur)
  return out


def pyramid_blocked(img: np.ndarray, factors, method: str,
                    precision: str, pool: cf.Executor) -> List[np.ndarray]:
  """``pyramid`` over independent z-slabs in parallel. A slab is as deep
  as the product of the z factors, so no window crosses two slabs."""
  depth = int(np.prod([int(f[2]) for f in factors]))
  z = img.shape[2]
  step = depth * max(1, 8 // depth)
  if z % depth:
    return pyramid(img, factors, method, precision)
  slabs = list(pool.map(
    lambda z0: pyramid(img[:, :, z0: z0 + step], factors, method, precision),
    range(0, z, step),
  ))
  return [np.concatenate([s[m] for s in slabs], axis=2)
          for m in range(len(factors))]


# ---------------------------------------------------------------------------
# comparison


def compare_task(layer_dir: str, offset, shape, factors,
                 method: str) -> Tuple[int, int, int]:
  """(mismatched voxels, compared voxels, missing chunks) of every mip one
  downsample task stored, against the reference pyramid of its mip-0
  cutout as the layer stores it. A missing chunk's voxels all count as
  mismatched."""
  info = read_info(layer_dir)
  lo = [int(v) for v in offset]
  hi = [a + int(s) for a, s in zip(lo, shape)]
  with cf.ThreadPoolExecutor(THREADS) as pool:
    src = read_cutout(layer_dir, info, 0, lo, hi, pool)
    mips = pyramid_blocked(src, factors, method, "exact", pool)
    del src
    dtype = np.dtype(info["data_type"])
    mismatched = compared = missing = 0
    for m, ref in enumerate(mips, start=1):
      scale = info["scales"][m]
      div = np.prod(np.asarray(factors[:m], dtype=np.int64), axis=0)
      mlo = [a // int(d) for a, d in zip(lo, div)]
      size = scale["size"]
      moff = scale.get("voxel_offset", [0, 0, 0])
      mhi = [min(a + s, o + sz) for a, s, o, sz in
             zip(mlo, ref.shape, moff, size)]
      quality = scale.get("jpeg_quality", 85)

      def one(box, ref=ref, scale=scale, mlo=mlo):
        data = read_object(chunk_path(layer_dir, scale, box))
        want = ref[box[0][0] - mlo[0]: box[1][0] - mlo[0],
                   box[0][1] - mlo[1]: box[1][1] - mlo[1],
                   box[0][2] - mlo[2]: box[1][2] - mlo[2]]
        if data is None:  # every voxel of a missing chunk is wrong
          return int(want.size), int(want.size), 1
        got = decode(data, scale, want.shape, dtype)
        if scale["encoding"] == "jpeg":
          want = jpeg_round_trip(np.ascontiguousarray(want), quality)
        return int(np.count_nonzero(got != want)), int(want.size), 0

      for mm, cc, ms in pool.map(one, chunk_boxes(scale, mlo, mhi)):
        mismatched += mm
        compared += cc
        missing += ms
  return mismatched, compared, missing
