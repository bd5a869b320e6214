"""Multi-mip pooling kernels: the tinybrain equivalents, TPU-first.

Reference capabilities replaced here (SURVEY.md §2.3: tinybrain):
2x2x1 / 2x2x2 average pooling, mode (COUNTLESS-style majority) pooling for
segmentation with a sparse variant, min/max pooling, striding, and
multi-mip output in one call (/root/reference/igneous/tasks/image/image.py:37-55).

Design notes (TPU):
  - Layout on device is (c, z, y, x): x is innermost so the 128-lane VPU
    vectorizes along the largest axis.
  - Windows are read with HLO ``reduce-window``s, never with jnp's
    strided indexing, which lowers to a ``gather``. Min, max and the
    average of ≤16-bit integers reduce every window with one; mode, the
    wider averages and striding select each window position with one of
    one-voxel windows at the pooling strides (``_strided``).
  - One jitted program produces the whole mip pyramid: each mip is a
    reshape-into-windows + reduce, which XLA fuses into tight VPU loops —
    no HBM round-trips between mips.
  - The batched executor walks a cutout in z-slabs that no window
    crosses (``slab_depth``), so the window temporaries are one slab's:
    the 2048x2048x64 uint64 task fits one v5e two at a time.
  - Mode pooling counts pairwise equality over the (≤8-voxel) window and
    argmaxes a score that encodes "highest count, ties to the earliest
    window position (z-major, then y, then x)". Equality-only compares mean
    uint32 label bit patterns can be treated as int32 safely.
  - Odd extents are edge-replicated to the next multiple of the factor:
    for factor-2 windows duplicating the partial contents preserves both
    exact averages and majority votes, so border voxels are exact.
  - uint64 labels should be renumbered to ≤32 bits before pooling (the
    tasks do this via renumbered downloads, as the reference does for
    memory reasons at tasks/image/image.py:749-760) and remapped after.

Exact semantics (mirrored by ops.oracle for tests):
  - average on integer dtypes: per-mip sum then round-half-up division.
  - mode: majority value; ties broken by earliest window position of the
    winning value; sparse=True ignores zeros unless the window is all zero.
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import knobs

Factor3 = Tuple[int, int, int]


def method_for_layer(layer_type: str, method="auto") -> str:
  """``method`` accepts the string names, a DownsampleMethods enum member,
  or its integer value."""
  from ..types import DownsampleMethods

  method = DownsampleMethods.to_name(method)
  if method != "auto":
    return method
  return "mode" if layer_type == "segmentation" else "average"


# ---------------------------------------------------------------------------
# device kernels (operate on (c, z, y, x) arrays)


def _pad_to_multiple(x: jnp.ndarray, f: Factor3) -> jnp.ndarray:
  fx, fy, fz = f
  c, sz, sy, sx = x.shape
  pads = (
    (0, 0),
    (0, (-sz) % fz),
    (0, (-sy) % fy),
    (0, (-sx) % fx),
  )
  if any(p[1] for p in pads):
    x = jnp.pad(x, pads, mode="edge")
  return x


def _extremes(dtype) -> Tuple:
  """(lowest, highest) value of ``dtype``: the identities of max and min."""
  if jnp.issubdtype(dtype, jnp.floating):
    return -jnp.inf, jnp.inf
  if dtype == jnp.bool_:
    return False, True
  info = jnp.iinfo(dtype)
  return info.min, info.max


def _strided(x: jnp.ndarray, start, f: Factor3) -> jnp.ndarray:
  """``x[:, dz::fz, dy::fy, dx::fx]`` for ``start`` = (0, dz, dy, dx), as
  one HLO ``reduce-window`` of one-voxel windows at strides ``f`` (over
  the unit-stride slice from ``start``). jnp's strided index lowers to a
  ``gather`` that fetches one z column an output element, and XLA's
  strided ``slice`` moves the lanes slowly: on a v5e the uint64 mode
  pyramid of two 2048x2048x64 cutouts (uint32 planes, slabs 16 deep) took
  132 ms this way, 366 ms as strided slices and 1648 ms as gathers."""
  fx, fy, fz = f
  if any(start):
    x = jax.lax.slice(x, start, x.shape)
  lowest, _ = _extremes(x.dtype)
  return jax.lax.reduce_window(x, jnp.array(lowest, x.dtype), jax.lax.max,
                               (1, 1, 1, 1), (1, fz, fy, fx), "VALID")


def _window_slices(x: jnp.ndarray, f: Factor3) -> list:
  """The n = fz*fy*fx strided slices of each pooling window
  (:func:`_strided`) of the edge-padded input, ordered z-major then y
  then x (position index = dx + fx*(dy + fy*dz))."""
  fx, fy, fz = f
  x = _pad_to_multiple(x, f)
  return [
    _strided(x, (0, dz, dy, dx), f)
    for dz in range(fz)
    for dy in range(fy)
    for dx in range(fx)
  ]


def _pool_average(x: jnp.ndarray, f: Factor3) -> jnp.ndarray:
  """Mean over each window. Integer semantics: round-half-up, exact.

  ≤16-bit integers accumulate in int32 (≤2^20 window sum, exact). 32-bit
  integers split into 16-bit hi/lo planes whose partial sums stay in int32;
  for power-of-two window sizes the rounded division distributes exactly
  across the split (the TPU has no native 64-bit integers). Non-power-of-two
  windows on 32-bit data fall back to float32 (documented approximation).

  The ≤16-bit sums are one reduce-window, not a sum of window slices: on
  a v5e the uint8 5-mip pyramid of two 4096x4096x64 cutouts took 52 ms
  this way, 660 ms as strided slices and 381 ms as jnp's gathers.
  """
  floating = jnp.issubdtype(x.dtype, jnp.floating)
  if not floating and x.dtype.itemsize <= 2:
    fx, fy, fz = f
    n = fx * fy * fz
    window = (1, fz, fy, fx)
    acc = jax.lax.reduce_window(
      _pad_to_multiple(x, f).astype(jnp.int32), jnp.int32(0), jax.lax.add,
      window, window, "VALID")
    return ((acc + n // 2) // n).astype(x.dtype)
  vs = _window_slices(x, f)
  n = len(vs)
  if floating:
    acc = sum(v.astype(jnp.float32) for v in vs)
    return (acc / n).astype(x.dtype)
  if n & (n - 1) == 0:  # power-of-two window on 32-bit integers: exact
    k = n.bit_length() - 1
    lo = sum((v & jnp.uint32(0xFFFF)).astype(jnp.int32) for v in (
      vv.astype(jnp.uint32) for vv in vs))
    hi = sum((v >> jnp.uint32(16)).astype(jnp.int32) for v in (
      vv.astype(jnp.uint32) for vv in vs))
    lo = lo + n // 2
    hi = hi + (lo >> 16)
    lo = lo & jnp.int32(0xFFFF)
    # floor((hi*2^16 + lo) / 2^k) = hi*2^(16-k) + lo>>k exactly for k<=16
    out = (hi << (16 - k)) + (lo >> k)
    return out.astype(jnp.uint32).astype(x.dtype)
  acc = sum(v.astype(jnp.float32) for v in vs)
  return jnp.floor(acc / n + 0.5).astype(x.dtype)


def _pool_mode(x, f: Factor3, sparse: bool):
  """Majority pooling. ``x`` is one array or a tuple of same-shaped planes
  jointly representing each voxel's value (uint64 labels ride as two uint32
  planes — the TPU never touches 64-bit integers).

  Winner = highest occurrence count, ties to the earliest window position;
  sparse ignores zeros unless the whole window is zero."""
  is_tuple = isinstance(x, tuple)
  planes = x if is_tuple else (x,)
  per_plane_slices = [_window_slices(p, f) for p in planes]
  n = len(per_plane_slices[0])
  # vs[i] = tuple of plane values at window position i
  vs = [tuple(ps[i] for ps in per_plane_slices) for i in range(n)]

  def eq(a, b):
    e = None
    for pa, pb in zip(a, b):
      ee = pa == pb
      e = ee if e is None else (e & ee)
    return e

  # pairwise equalities are symmetric: n*(n-1)/2 compares instead of n^2
  pair = {}
  for i in range(n):
    for j in range(i + 1, n):
      pair[(i, j)] = eq(vs[i], vs[j]).astype(jnp.int32)

  best_score = None
  best_val = None
  for i in range(n):
    counts = None
    for j in range(n):
      if i == j:
        continue
      e = pair[(min(i, j), max(i, j))]
      counts = e if counts is None else counts + e
    counts = counts + 1  # self-match
    score = counts * n - i
    if sparse:
      zero = None
      for p in vs[i]:
        z = p == 0
        zero = z if zero is None else (zero & z)
      # all-zero windows keep 0: position 0's value is 0 and survives
      score = jnp.where(zero, jnp.int32(-1), score)
    if best_score is None:
      best_score, best_val = score, vs[i]
    else:
      take = score > best_score
      best_score = jnp.where(take, score, best_score)
      best_val = tuple(
        jnp.where(take, a, b) for a, b in zip(vs[i], best_val)
      )
  return best_val if is_tuple else best_val[0]


def _pool_minmax(x: jnp.ndarray, f: Factor3, op: str) -> jnp.ndarray:
  """Each window's min or max, one reduce-window over the edge-padded
  input: on a v5e the 2x2x1 uint8 pyramid of two 4096x4096x64 cutouts
  took 30 ms this way and 663 ms as a fold of strided slices (as jnp's
  gathers it did not fit)."""
  fx, fy, fz = f
  window = (1, fz, fy, fx)
  lowest, highest = _extremes(x.dtype)
  if op == "min":
    init, reduce = highest, jax.lax.min
  else:
    init, reduce = lowest, jax.lax.max
  return jax.lax.reduce_window(_pad_to_multiple(x, f), jnp.array(init, x.dtype),
                               reduce, window, window, "VALID")


def _pool_striding(x: jnp.ndarray, f: Factor3) -> jnp.ndarray:
  """Each window's first voxel; a partial edge window keeps its own."""
  return _strided(x, (0, 0, 0, 0), f)


def _pool_once(x, f: Factor3, method: str, sparse: bool):
  if method == "mode":
    return _pool_mode(x, f, sparse)
  if isinstance(x, tuple):
    raise ValueError("plane-tuple inputs are only valid for mode pooling")
  if method == "average":
    return _pool_average(x, f)
  if method in ("min", "max"):
    return _pool_minmax(x, f, method)
  if method == "striding":
    return _pool_striding(x, f)
  raise ValueError(f"Unknown downsample method: {method}")


def _pyramid_whole(x, factors: Tuple[Factor3, ...], method: str, sparse: bool):
  outs = []
  for f in factors:
    x = _pool_once(x, f, method, sparse)
    outs.append(x)
  return tuple(outs)


def _pyramid_impl(x, factors: Tuple[Factor3, ...], method: str, sparse: bool,
                  depth: Optional[int] = None):
  """Every mip of ``x`` ((c, z, y, x), or a tuple of such planes for
  mode), walked in z-slabs ``depth`` deep (see :func:`slab_depth`).

  No window crosses a slab whose depth is a multiple of the product of
  the z factors, so each slab's pyramid is written into preallocated
  per-mip buffers at its z offset: the temporaries of the window slices
  and equality maps are then one slab's, not the whole cutout's. A depth
  of None or of the whole z extent is one slab, the whole-cutout program.
  """
  planes = x if isinstance(x, tuple) else (x,)
  z = planes[0].shape[1]
  if depth is None or depth >= z:
    return _pyramid_whole(x, factors, method, sparse)
  step = _z_step(factors)
  if depth % step or z % depth:
    raise ValueError(f"slab depth {depth} must be a multiple of {step} "
                     f"that divides z extent {z}")

  def slab(i):
    s = tuple(jax.lax.dynamic_slice_in_dim(p, i * depth, depth, axis=1)
              for p in planes)
    return s if isinstance(x, tuple) else s[0]

  n = z // depth
  shapes = jax.eval_shape(
    lambda s: _pyramid_whole(s, factors, method, sparse), slab(0))
  bufs = jax.tree.map(
    lambda s: jnp.zeros((s.shape[0], s.shape[1] * n) + s.shape[2:], s.dtype),
    shapes)

  def body(i, bufs):
    outs = _pyramid_whole(slab(i), factors, method, sparse)
    return jax.tree.map(
      lambda b, o: jax.lax.dynamic_update_slice_in_dim(
        b, o, i * o.shape[1], axis=1),
      bufs, outs)

  return jax.lax.fori_loop(0, n, body, bufs)


def _z_step(factors: Sequence[Factor3]) -> int:
  """The depth no window of the chain crosses: the product of the z
  factors."""
  return int(np.prod([int(f[2]) for f in factors]))


# Temporaries of a pyramid pass, as a multiple of the input it pools at
# once: the u8 average widens its input to int32 for the reduce-window
# (5x its input, the most of any method, compiled for a v5e), mode holds
# the pairwise equality maps.
TEMP_PER_INPUT = 6

# Published HBM per chip, for a device that cannot report its memory (one
# described for an ahead-of-time compile, which is not addressable).
_HBM_BYTES = {"TPU v5 lite": 16 * 2**30}


def device_memory_bytes(device) -> Optional[int]:
  """The memory a pyramid may plan against on ``device``: its allocator's
  limit where it reports one, else its kind's published HBM; None for a
  host backend, which reports neither."""
  try:
    stats = device.memory_stats() or {}
  except jax.errors.JaxRuntimeError:  # described, not addressable
    stats = {}
  limit = stats.get("bytes_limit")
  return int(limit) if limit else _HBM_BYTES.get(device.device_kind)


def walk_bytes(z: int, depth: int, factors: Sequence[Factor3],
               plane_bytes: int) -> int:
  """Device bytes a pyramid walked in slabs ``depth`` deep needs: the whole
  input (``plane_bytes`` a z plane, over everything one dispatch pools)
  and every mip, plus one slab's temporaries."""
  mips, vol = 0.0, 1
  for f in factors:
    vol *= int(f[0]) * int(f[1]) * int(f[2])
    mips += 1 / vol
  return int(z * plane_bytes * (1 + mips)
             + TEMP_PER_INPUT * depth * plane_bytes)


def slab_depth(z: int, factors: Sequence[Factor3], plane_bytes: int,
               memory_bytes: Optional[int]) -> int:
  """The z-slab depth the pyramid walks: the deepest multiple of the z
  factors' product that divides ``z`` and whose walk fits
  ``memory_bytes`` (:func:`walk_bytes`); at least that product. The
  whole extent, one slab, when that fits, when memory is unknown, or when
  ``z`` is no multiple of the product (the edge padding in z then needs
  the whole cutout)."""
  step = _z_step(factors)
  if memory_bytes is None or z % step:
    return z
  fits = [d for d in range(step, z + 1, step) if z % d == 0
          and walk_bytes(z, d, factors, plane_bytes) <= memory_bytes]
  return max(fits, default=step)


_jit_pyramid = partial(
  jax.jit, static_argnames=("factors", "method", "sparse")
)(_pyramid_impl)


def _pyramid(x, factors, method, sparse):
  """The jitted pyramid behind device telemetry (ISSUE 7): the solo-task
  device path (``downsample()``) ticks the same recompile ledger and
  emits the same device.compile/device.execute spans as the batched
  executors."""
  return _solo_pyramid(f"pooling.pyramid[{method}]", x, factors, method,
                       sparse)


def _fused_pyramid(x, factors, method, sparse, mip_from: int = 0):
  """The fused multi-mip walk: the SAME single compiled program as
  ``_pyramid`` (the whole mip0→mipN walk is one XLA dispatch with no HBM
  round-trips between mips — it shares ``_jit_pyramid``'s executable
  cache), accounted under its own ``pooling.fused_pyramid[method]``
  kernel with ``mip_from``/``mip_to`` attributes on the device.execute
  span. Callers that walk a varying mip range per invocation (the serve
  tier's ancestor synth) use this so the journal records which levels
  each fused dispatch produced."""
  return _solo_pyramid(
    f"pooling.fused_pyramid[{method}]", x, factors, method, sparse,
    mip_from=int(mip_from), mip_to=int(mip_from) + len(factors),
  )


def _solo_pyramid(kernel, x, factors, method, sparse, **attrs):
  from ..observability import device as device_telemetry

  leaves = x if isinstance(x, tuple) else (x,)
  sig = (tuple((np.shape(a), str(np.asarray(a).dtype)) for a in leaves),
         factors, sparse)
  return device_telemetry.run_solo(
    kernel, _jit_pyramid, (x, factors, method, sparse), sig,
    elements=sum(int(np.size(a)) for a in leaves),
    nbytes=sum(int(np.asarray(a).nbytes) for a in leaves), **attrs,
  )


@lru_cache(maxsize=None)
def pyramid_batched(factors: Tuple[Factor3, ...], method: str, sparse: bool):
  """Compiled batched pyramid: (B, c, z, y, x) → tuple of (B, …) mips.

  The batch axis is how one host feeds many chunks to the device in a
  single program (and how shard_map distributes chunks over a TPU mesh)."""
  return jax.jit(
    jax.vmap(lambda x: _pyramid_impl(x, factors, method, sparse))
  )


# ---------------------------------------------------------------------------
# host-facing API: (x, y, z, c) numpy in/out


def _split_u64_planes(u: np.ndarray):
  """uint64 → (lo, hi) uint32 zero-copy STRIDED VIEWS when the layout
  allows (the one unavoidable copy then happens inside _to_device_layout's
  contiguity fixup). Arithmetic fallback for non-contiguous inputs and
  big-endian hosts (where the word halves are swapped in memory)."""
  import sys

  if sys.byteorder == "little":
    if u.flags["C_CONTIGUOUS"]:
      pairs = u.view(np.uint32).reshape(u.shape + (2,))
      return pairs[..., 0], pairs[..., 1]
    if u.flags["F_CONTIGUOUS"]:
      t = u.T
      pairs = t.view(np.uint32).reshape(t.shape + (2,))
      return pairs[..., 0].T, pairs[..., 1].T
  lo = (u & np.uint64(0xFFFFFFFF)).astype(np.uint32)
  hi = (u >> np.uint64(32)).astype(np.uint32)
  return lo, hi


# a host copy of fewer bytes runs inline on the calling thread. Measured
# on an 8-core host: at 4 MiB the pool's hand-off ate the gain, at 16 MiB
# the pack and the split ran 4x faster in pieces on the pool
INLINE_COPY_BYTES = 16 << 20


def _plane_runs(shape, nbytes: int):
  """How a host copy of ``nbytes`` into an array of ``shape`` (..., z, y,
  x) is cut: ``(runs, workers)``. Each run is a run of whole z-planes
  inside one index of the axes before z, as an index (those indices and a
  slice of z). A copy of ``INLINE_COPY_BYTES`` or more is cut into about
  as many runs as the shared copy pool is wide, which run side by side
  there; a smaller one into one run per index, which run on the calling
  thread (``workers`` 1). Whole planes keep each piece large, so the
  pool's per-piece overhead stays small."""
  if len(shape) < 3:
    return [()], 1
  from ..pipeline import config

  *outer, z = shape[:-2]
  pieces = config._cores() if nbytes >= INLINE_COPY_BYTES else 1
  step = max(1, min(z, -(-z * int(np.prod(outer)) // pieces)))
  runs = [tuple(o) + (slice(z0, min(z0 + step, z)),)
          for o in np.ndindex(*outer) for z0 in range(0, z, step)]
  return runs, max(1, min(len(runs), pieces))


def _copy_by_planes(copy, shape, nbytes: int) -> int:
  """Call ``copy(index)`` for every run ``_plane_runs`` cuts, side by side
  on the shared copy pool where it cut for more than one worker. Returns
  the workers."""
  runs, workers = _plane_runs(shape, nbytes)
  if workers == 1:
    for run in runs:
      copy(run)
  else:
    from ..pipeline.encoder import shared_copy_pool

    list(shared_copy_pool().map(copy, runs))
  return workers


def _pack_u64_planes(lo: np.ndarray, hi: np.ndarray) -> np.ndarray:
  """(lo, hi) uint32 → uint64 by interleaving the two planes into one
  uint32 buffer, then a zero-copy uint64 view.

  C-contiguous planes (batched device outputs) interleave into a C-order
  result: sequential reads, stride-2 writes. Anything else (the
  (x,y,z,c) transpose views of device outputs) interleaves through the
  transposes into an F-order result, so both sides of each copy stay
  sequential — measured 60x faster at 512^3 than astype+shift+or into C
  order (21s → 0.35s), and the F-order result is exactly what raw encode
  (tobytes("F")) wants. Each run of z-planes interleaves both planes
  (``_copy_by_planes``). Arithmetic fallback on big-endian hosts."""
  import sys

  if sys.byteorder == "little":
    c_order = lo.flags["C_CONTIGUOUS"] and hi.flags["C_CONTIGUOUS"]
    src_lo, src_hi = (lo, hi) if c_order else (lo.T, hi.T)
    out = np.empty(src_lo.shape + (2,), dtype=np.uint32)

    def interleave(index):
      pairs = out[index]
      pairs[..., 0] = src_lo[index]
      pairs[..., 1] = src_hi[index]

    _copy_by_planes(interleave, src_lo.shape, out.nbytes)
    packed = out.view(np.uint64)[..., 0]
    return packed if c_order else packed.T
  return lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))


def _to_device_layout(img: np.ndarray) -> np.ndarray:
  if img.ndim == 3:
    img = img[..., np.newaxis]
  return np.ascontiguousarray(img.transpose(3, 2, 1, 0))  # (c,z,y,x)


def _from_device_layout(x) -> np.ndarray:
  return np.asarray(x).transpose(3, 2, 1, 0)  # back to (x,y,z,c)


def _normalize_factors(factor, num_mips: int) -> Tuple[Factor3, ...]:
  """One (fx,fy,fz) triple applied every mip, or a per-mip sequence."""
  arr = np.asarray(factor, dtype=np.int64)
  if arr.ndim == 2:
    if len(arr) < num_mips:
      raise ValueError(f"need {num_mips} per-mip factors, got {len(arr)}")
    return tuple(tuple(int(v) for v in f) for f in arr[:num_mips])
  return tuple(tuple(int(v) for v in arr) for _ in range(num_mips))


def downsample(
  img: np.ndarray,
  factor,
  num_mips: int = 1,
  method: str = "average",
  sparse: bool = False,
  mip_from: Optional[int] = None,
) -> List[np.ndarray]:
  """Pool ``img`` (x,y,z[,c]) iteratively; returns one array per mip.

  ``factor`` is one (fx,fy,fz) triple applied every mip, or a per-mip
  sequence of triples (near-isotropic pyramids).

  ``mip_from``: when given, the device walk runs as the
  ``pooling.fused_pyramid`` kernel and its device.execute spans carry
  ``mip_from``/``mip_to`` attributes (``img`` is a cutout of mip
  ``mip_from``; the results are mips ``mip_from+1 .. mip_from+num_mips``).
  The compiled program — and the numeric output — is identical either way.
  """
  squeeze = img.ndim == 3
  orig_dtype = img.dtype
  if img.dtype == bool:
    img = img.view(np.uint8)
  factors = _normalize_factors(factor, num_mips)
  run_pyramid = (
    _pyramid if mip_from is None
    else partial(_fused_pyramid, mip_from=mip_from)
  )

  if method == "mode" and img.dtype.itemsize == 8:
    # 64-bit labels ride as (lo, hi) uint32 planes: equality distributes
    # over the split, so majority votes are exact and the device stays
    # in its native 32-bit integer width with no renumber pass.
    # int64/float64 go through their uint64 bit pattern (equality-preserving
    # for integers; float mode pooling is not supported).
    if img.dtype.kind == "f":
      raise ValueError("mode pooling of floating-point data is not supported")
    u = img.view(np.uint64) if img.dtype.kind == "i" else img
    lo, hi = _split_u64_planes(u)
    outs = run_pyramid((_to_device_layout(lo), _to_device_layout(hi)),
                       factors, method, sparse)
    results = []
    for ol, oh in outs:
      r = _pack_u64_planes(_from_device_layout(ol), _from_device_layout(oh))
      r = r.view(orig_dtype) if orig_dtype.kind == "i" else r.astype(orig_dtype)
      results.append(r[..., 0] if squeeze else r)
    return results

  work = img
  if img.dtype.itemsize == 8 and method == "average":
    work = img.astype(np.float32)
  x = _to_device_layout(work)
  outs = run_pyramid(x, factors, method, sparse)
  results = []
  for o in outs:
    r = _from_device_layout(o).astype(orig_dtype, copy=False)
    results.append(r[..., 0] if squeeze else r)
  return results


def downsample_with_averaging(img: np.ndarray, factor, num_mips: int = 1):
  return downsample(img, factor, num_mips, method="average")


def downsample_segmentation(
  img: np.ndarray, factor, num_mips: int = 1, sparse: bool = False
):
  return downsample(img, factor, num_mips, method="mode", sparse=sparse)


# ---------------------------------------------------------------------------
# host production path (accelerator-less workers)
#
# The reference's workers are CPU machines running tinybrain's C kernels
# (SURVEY.md §2.3); an igneous_tpu worker on a host with no TPU gets the
# same deal: the oracle-exact native C++ pooling kernels
# (native/csrc/pooling.cpp) threaded across cores, instead of paying the
# XLA CPU backend's overhead on what is a memory-bound stencil. Tasks call
# downsample_auto(); kernel tests keep calling downsample() so device
# coverage is unchanged. Control: IGNEOUS_POOL_HOST=auto(default)|1|0,
# IGNEOUS_POOL_THREADS=0(hardware)|N.


def _backend_is_cpu() -> bool:
  """True when jax would execute on host CPU. Checks JAX_PLATFORMS first so
  a CPU-pinned worker never initializes a backend just to ask."""
  plats = os.environ.get("JAX_PLATFORMS", "")
  if plats:
    return plats.split(",")[0].strip().lower() == "cpu"
  # a backend that fails to initialize raises: quietly pooling on the
  # host would hide a chip that is there but unusable
  return jax.default_backend() == "cpu"


def _host_pool_threads() -> int:
  return knobs.get_int("IGNEOUS_POOL_THREADS")


def _mode_as_u64(img: np.ndarray):
  """Lossless integer→uint64 value mapping for mode pooling (mode only uses
  equality, which any injective mapping preserves; zero maps to zero so
  sparse semantics survive). Returns (u64 array, back-converter)."""
  dt = img.dtype
  if dt == np.uint64:
    return img, lambda r: r
  if dt.kind == "i" and dt.itemsize == 8:
    return img.view(np.uint64), lambda r: r.view(dt)
  if dt.kind == "u" or dt == np.uint8:
    return img.astype(np.uint64), lambda r: r.astype(dt)
  if dt.kind == "i":
    u = np.dtype(f"u{dt.itemsize}")
    return img.view(u).astype(np.uint64), lambda r: r.astype(u).view(dt)
  return None, None


def host_downsample(
  img: np.ndarray,
  factor,
  num_mips: int = 1,
  method: str = "average",
  sparse: bool = False,
  parallel: Optional[int] = None,
) -> Optional[List[np.ndarray]]:
  """`downsample` semantics on the native host kernels; None when this
  (method, dtype) combination has no native path (caller falls back to the
  device kernels). Channels pool independently, matching the device path."""
  from ..native import pooling_lib

  if method not in ("average", "mode", "striding"):
    return None
  if parallel is None:
    parallel = _host_pool_threads()

  squeeze = img.ndim == 3
  if img.ndim == 3:
    img = img[..., np.newaxis]
  if img.ndim != 4:
    return None
  orig_dtype = img.dtype
  if img.dtype == bool:
    img = img.view(np.uint8)
  factors = _normalize_factors(factor, num_mips)

  if method == "striding":
    outs = []
    cur = img
    for fx, fy, fz in factors:
      cur = cur[::fx, ::fy, ::fz]
      outs.append(cur.astype(orig_dtype, copy=False))
    return [o[..., 0] if squeeze else o for o in outs]

  lib = pooling_lib()
  if lib is None:
    return None

  import ctypes

  if method == "average":
    if img.dtype != np.uint8:
      return None

    def run_mip(cur, out, dims, f):
      lib.pool_avg_u8(
        cur.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        *dims, *f, int(parallel),
      )

    work, back = img, lambda r: r
    dtype = np.uint8
  else:  # mode
    work, back = _mode_as_u64(img)
    if work is None:
      return None
    dtype = np.uint64

    def run_mip(cur, out, dims, f):
      lib.pool_mode_u64(
        cur.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        *dims, *f, int(bool(sparse)), int(parallel),
      )

    def run_mip_f(cur, out, dims, f):
      # Fortran-layout variant: exact for any factor (gathers windows in
      # the required dx-fastest tie order with explicit strides)
      lib.pool_mode_u64_f(
        cur.ctypes.data_as(ctypes.c_void_p),
        out.ctypes.data_as(ctypes.c_void_p),
        *dims, *f, int(bool(sparse)), int(parallel),
      )

  # Transposed-call layout trick: a Fortran-ordered (x, y, z) cutout IS a
  # C-ordered (z, y, x) array, so the kernel can run on it directly with
  # reversed dims/factors — no ascontiguousarray transpose-copy (which
  # otherwise dominates the whole pyramid's wall clock). Exact for
  # average at any factor (order-free sum); for mode only at 2x2 windows,
  # where the earliest-position tie-break provably coincides across both
  # traversal orders (see pooling.cpp f122 note + layout tests).
  def mode_transpose_ok(f):
    # average: order-free sum, any factor. mode: only the NON-sparse
    # 2x2x1 case, where the f122 waterfall's winner is provably order-
    # independent; sparse votes and other factors go through the exact
    # Fortran-strided kernel instead.
    if method == "average":
      return True
    return (not sparse) and f == (2, 2, 1)

  nchan = work.shape[3]
  chan_outs: List[List[np.ndarray]] = []
  for c in range(nchan):
    cur = work[..., c]
    outs = []
    for f in factors:
      fx, fy, fz = f
      nx, ny, nz = cur.shape
      oshape = ((nx + fx - 1) // fx, (ny + fy - 1) // fy,
                (nz + fz - 1) // fz)
      f_contig = (
        not cur.flags["C_CONTIGUOUS"] and cur.T.flags["C_CONTIGUOUS"]
      )
      if f_contig and mode_transpose_ok(f):
        out_t = np.empty(oshape[::-1], dtype=dtype)
        run_mip(cur.T, out_t, (nz, ny, nx), (fz, fy, fx))
        out = out_t.T  # logical (x, y, z), Fortran-ordered like the input
      elif f_contig and method == "mode":
        # factors the transpose-equivalence proof does not cover (e.g.
        # volumetric 2x2x2): the dedicated Fortran-strided mode kernel
        out = np.empty(oshape[::-1], dtype=dtype).T
        run_mip_f(cur, out, (nx, ny, nz), (fx, fy, fz))
      else:
        cur = np.ascontiguousarray(cur)
        out = np.empty(oshape, dtype=dtype)
        run_mip(cur, out, (nx, ny, nz), (fx, fy, fz))
      outs.append(out)
      cur = out
    chan_outs.append(outs)

  results = []
  for i in range(len(factors)):
    if nchan == 1:
      r = chan_outs[0][i][..., np.newaxis]  # view, no copy
    else:
      r = np.stack([chan_outs[c][i] for c in range(nchan)], axis=-1)
    r = back(r)
    if r.dtype != orig_dtype:
      r = r.astype(orig_dtype)
    results.append(r[..., 0] if squeeze else r)
  return results


def _host_pool_active() -> bool:
  """True when downsample_auto would try the native host kernels first.
  Exposed so batching policy (parallel/lease_batcher._group_key) can keep
  downsamples solo on accelerator-less workers, where per-cutout native
  pooling IS the fast path and an XLA-CPU batch dispatch is a ~9x
  pessimization."""
  mode = knobs.get_str("IGNEOUS_POOL_HOST").lower()
  return mode != "0" and (mode == "1" or _backend_is_cpu())


def downsample_auto(
  img: np.ndarray,
  factor,
  num_mips: int = 1,
  method: str = "average",
  sparse: bool = False,
  mip_from: Optional[int] = None,
) -> List[np.ndarray]:
  """Production dispatch: native host kernels when jax would run on CPU
  anyway (or when forced), device kernels otherwise. ``mip_from`` labels
  the device walk's spans (see :func:`downsample`); the native host path
  computes the same walk without device telemetry."""
  if _host_pool_active():
    out = host_downsample(img, factor, num_mips, method=method, sparse=sparse)
    if out is not None:
      return out
  return downsample(
    img, factor, num_mips, method=method, sparse=sparse, mip_from=mip_from
  )
