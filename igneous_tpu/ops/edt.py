"""Multilabel anisotropic Euclidean distance transform on device.

The flop-heavy core of skeletonization (kimimaro's bundled ``edt`` C++
library — SURVEY.md §2.3, /root/reference/igneous/tasks/skeleton.py:303-335
runs it inside kimimaro.skeletonize). Semantics (oracle: scipy per label):
for every nonzero voxel, the anisotropic distance to the nearest voxel
center holding a DIFFERENT label (background voxels read 0).

TPU-first formulation: three axis passes of a label-aware min-plus
product over lines,

    out[b, i] = min_j ( keep(b, j, i) + (i - j)^2 w^2 )
    keep(b, j, i) = val[b, j]  if label[b, j] == label[b, i]  else 0

decomposed exactly into two data-parallel pieces per pass (round-2
replacement for the dense (B, n, n) broadcast, which was O(n^4) per axis
and lost to CPU at production sizes):

  1. *Run-edge term* — the best different-label j. Labels form runs along
     the line; the nearest different-label voxel is the one just past i's
     own run boundary, and cost is monotone in |i-j|, so this term is
     (distance to own-run edge)^2 w^2 — two O(n) cumulative scans.
  2. *Same-run lower envelope* — the best same-label j. A same-label j
     beyond an interposed different-label run is always dominated by that
     interposed voxel (|i-k| < |i-j| and val >= 0), so only j inside i's
     OWN run matter. Within a run this is the classic 1D squared-distance
     min-plus, solved by the Felzenszwalb-Huttenlocher parabola envelope:
     O(n) work per line, run here as a lax.scan over line positions
     vectorized across ALL lines at once (B lanes per step). Run
     boundaries reset the envelope via segmented stacks: each run's
     envelope occupies its own monotonically-allocated region of a
     (B, 2n) stack, with a one-slot gap so the +inf top sentinel of a
     finished run survives the next run's first push.

Exactness of the per-axis decomposition for any target set: when line
voxel j already has a different label than i, its in-line contribution is
0 (the voxel itself is a target), which the edge term implements; heights
are normalized by w^2 inside the envelope so float32 intersection
arithmetic stays in a safe magnitude range at any anisotropy.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import knobs

INF = np.float32(1e20)


def _edge_term(lab: jnp.ndarray, w: float) -> jnp.ndarray:
  """(distance to nearest different-label voxel along the line)^2 w^2."""
  B, n = lab.shape
  idx = jnp.arange(n, dtype=jnp.int32)
  chg = lab[:, 1:] != lab[:, :-1]  # change at k means lab[k] != lab[k-1]
  big = np.int32(2 * n)
  # left: start s of i's run = last change position <= i; different voxel
  # at s-1, distance i-s+1. No change to the left -> run starts at 0 -> inf.
  starts = jnp.concatenate(
    [jnp.full((B, 1), -big, jnp.int32), jnp.where(chg, idx[1:], -big)],
    axis=1,
  )
  left = jax.lax.cummax(starts, axis=1)
  dl = jnp.where(left >= 1, (idx[None] - left + 1).astype(jnp.float32), INF)
  # right: first change position k > i; different voxel at k, distance k-i.
  nxt = jnp.concatenate(
    [jnp.where(chg, idx[1:], big), jnp.full((B, 1), big, jnp.int32)],
    axis=1,
  )
  # flip + forward cummin: the TPU compiler takes ~85 s per shape for a
  # reverse cummin over 514-voxel lines, ~2 s for the forward one
  right = jnp.flip(jax.lax.cummin(jnp.flip(nxt, 1), axis=1), 1)
  dr = jnp.where(right <= n - 1, (right - idx[None]).astype(jnp.float32), INF)
  d = jnp.minimum(dl, dr)
  return jnp.where(d >= INF, INF, (d * w) ** 2)


def _take(arr: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
  """Per-lane gather arr[b, idx[b]] for (B, S) arr, (B,) idx."""
  return jnp.take_along_axis(arr, idx[:, None], axis=1)[:, 0]


def _envelope_pass(val: jnp.ndarray, lab: jnp.ndarray, w: float) -> jnp.ndarray:
  """Same-run parabola-envelope min-plus along the last axis.

  val, lab: (B, n). Returns min_j in i's run of val[j] + (i-j)^2 w^2.
  Heights are carried as val/w^2 so envelope intersections stay ~n^2 in
  magnitude regardless of anisotropy (float32-safe); the result is
  rescaled by w^2 on the way out.
  """
  B, n = val.shape
  S = 2 * n + 2  # stack slots: <=1 push per column + 1 gap slot per run
  w2 = np.float32(w * w)
  f = jnp.where(val >= INF, INF, val / w2)  # normalized heights
  chg = jnp.concatenate(
    [
      jnp.ones((B, 1), bool),
      lab[:, 1:] != lab[:, :-1],
    ],
    axis=1,
  )
  finite = f < INF / 2

  qs = jnp.arange(n, dtype=jnp.float32)
  rows = jnp.arange(B)

  def intersect(fq, q, hk, vk):
    # rightmost crossing of parabola (q, fq) with (vk, hk), unit spacing
    return ((fq + q * q) - (hk + vk * vk)) / (2.0 * (q - vk))

  def build(carry, xs):
    v, h, z, k, base = carry
    fq, cq, finq, q = xs
    # run change: open a fresh (empty) envelope region above the old top,
    # leaving one gap slot so the finished run's +inf sentinel survives
    base = jnp.where(cq, k + 2, base)
    k = jnp.where(cq, base - 1, k)

    # pop dominated parabolas: while k >= base and s(q, top) <= z[top]
    def pop_cond(state):
      k_, active = state
      return active.any()

    def pop_body(state):
      k_, active = state
      vk = _take(v, jnp.maximum(k_, 0))
      hk = _take(h, jnp.maximum(k_, 0))
      zk = _take(z, jnp.maximum(k_, 0))
      s = intersect(fq, q, hk, vk)
      pop = active & (s <= zk)
      k_ = jnp.where(pop, k_ - 1, k_)
      active = pop & (k_ >= base)
      return k_, active

    active0 = finq & (k >= base)
    k, _ = jax.lax.while_loop(pop_cond, pop_body, (k, active0))

    # push the new parabola (only finite heights)
    vk = _take(v, jnp.maximum(k, 0))
    hk = _take(h, jnp.maximum(k, 0))
    s = jnp.where(k >= base, intersect(fq, q, hk, vk), -INF)
    pos = jnp.clip(k + 1, 0, S - 2)
    v = v.at[rows, pos].set(jnp.where(finq, q, _take(v, pos)))
    h = h.at[rows, pos].set(jnp.where(finq, fq, _take(h, pos)))
    z = z.at[rows, pos].set(jnp.where(finq, s, _take(z, pos)))
    z = z.at[rows, pos + 1].set(
      jnp.where(finq, INF, _take(z, pos + 1))
    )
    k = jnp.where(finq, k + 1, k)
    return (v, h, z, k, base), base

  v0 = jnp.zeros((B, S), jnp.float32)
  h0 = jnp.full((B, S), INF, jnp.float32)
  z0 = jnp.full((B, S), INF, jnp.float32)
  k0 = jnp.full(B, -1, jnp.int32)
  b0 = jnp.zeros(B, jnp.int32)
  xs = (
    f.T, chg.T, finite.T,
    jnp.broadcast_to(qs[:, None], (n, B)),
  )
  (v, h, z, _, _), bases = jax.lax.scan(build, (v0, h0, z0, k0, b0), xs)
  # bases: (n, B) — the envelope region start for each position's run

  def query(kq, xs):
    baseq, cq, q = xs
    kq = jnp.where(cq, baseq, kq)

    # advance while the next parabola's region starts left of q
    def adv_cond(state):
      kq_, active = state
      return active.any()

    def adv_body(state):
      kq_, active = state
      znext = _take(z, jnp.minimum(kq_ + 1, S - 1))
      step = active & (znext < q)
      kq_ = jnp.where(step, kq_ + 1, kq_)
      return kq_, step

    kq, _ = jax.lax.while_loop(adv_cond, adv_body, (kq, jnp.full(B, True)))
    vk = _take(v, kq)
    hk = _take(h, kq)
    # the normalized heights only pick the parabola; its value at q comes
    # from the un-normalized height, so it is rounded once, as the native
    # kernel's double arithmetic rounds it
    out_q = jnp.where(
      hk >= INF / 2, INF, _take(val, vk.astype(jnp.int32)) + (q - vk) ** 2 * w2
    )
    return kq, out_q

  xs_q = (bases, chg.T, jnp.broadcast_to(qs[:, None], (n, B)))
  _, outs = jax.lax.scan(query, jnp.zeros(B, jnp.int32), xs_q)
  return jnp.where(outs.T >= INF / 2, INF, outs.T)


# lines per envelope block (device path). XLA's CPU backend cannot alias
# the (B, S) v/h/z stack carries of the envelope scan, so every position
# step COPIES them; running the whole volume's B lines at once makes that
# copy ~50MB/step at 128^3 (DRAM-bound). Blocking the lines keeps each
# block's stacks cache-resident — the same total copy volume moves at
# L2/L3 speed instead. Per-line independence makes any blocking bitwise
# identical (the numpy twin blocks the same way via _NP_LINE_BATCH).
# The block size is a tunable (ISSUE 19): IGNEOUS_EDT_LINE_BLOCK >
# tuned/<device_kind>.json > this default.
_DEFAULT_LINE_BLOCK = 256


def _line_block() -> int:
  """Lines per envelope block, via the tuned-knob resolution order."""
  from .. import tune

  spec = tune.resolve("IGNEOUS_EDT_LINE_BLOCK")
  if not spec:
    return _DEFAULT_LINE_BLOCK
  try:
    lb = int(spec)
  except ValueError:
    lb = 0
  if lb < 1:
    raise ValueError(
      f"IGNEOUS_EDT_LINE_BLOCK must be a positive int: {spec!r}"
    )
  return lb


def _axis_pass(
  val: jnp.ndarray, lab: jnp.ndarray, w: float, first: bool,
  line_block: int = _DEFAULT_LINE_BLOCK,
) -> jnp.ndarray:
  """One pass along the LAST axis. val, lab: (..., n)."""
  n = val.shape[-1]
  lead = val.shape[:-1]
  B = int(np.prod(lead)) if lead else 1
  v = val.reshape(B, n)
  l = lab.reshape(B, n)
  out = _edge_term(l, w)
  if not first:
    # the first pass starts from val=INF everywhere, so the same-run
    # envelope could only produce INF — the edge term alone is the answer
    lb = min(int(line_block), B)
    pad = (-B) % lb
    if pad:
      # padded lines are all-background (label 0, val INF): the envelope
      # returns INF for them and they are sliced off below
      v = jnp.pad(v, ((0, pad), (0, 0)), constant_values=INF)
      l = jnp.pad(l, ((0, pad), (0, 0)))
    env = jax.lax.map(
      lambda args: _envelope_pass(args[0], args[1], w),
      (v.reshape(-1, lb, n), l.reshape(-1, lb, n)),
    ).reshape(-1, n)[:B]
    out = jnp.minimum(out, env)
  return out.reshape(*lead, n)


@partial(jax.jit, static_argnames=("anisotropy", "line_block"))
def _edt_sq_kernel(
  labels: jnp.ndarray, anisotropy: Tuple[float, float, float],
  line_block: int = _DEFAULT_LINE_BLOCK,
):
  """labels (z, y, x) int32 → squared EDT float32 (background voxels not
  zeroed: callers do that on the host); three passes.

  Each pass runs along the LAST axis of a layout chosen so consecutive
  transposes fuse into one permutation between passes (in+out transpose
  pairs per pass collapsed: x in (z,y,x), y in (z,x,y), z in (y,x,z) —
  two label transposes and three value transposes total instead of six).
  Values are identical under any layout walk; the envelope itself runs
  blocked over ``line_block``-line chunks (see above — static arg so the
  autotuner can sweep the geometry; any value is bitwise identical)."""
  wx, wy, wz = anisotropy

  # pass along x, native (z, y, x) layout
  val = _axis_pass(
    jnp.full(labels.shape, INF, dtype=jnp.float32), labels, wx, first=True
  )
  # (z, y, x) -> (z, x, y): pass along y
  lab_y = jnp.swapaxes(labels, 1, 2)
  val = _axis_pass(jnp.swapaxes(val, 1, 2), lab_y, wy, first=False,
                   line_block=line_block)
  # (z, x, y) -> (y, x, z): pass along z
  lab_z = jnp.transpose(lab_y, (2, 1, 0))
  val = _axis_pass(jnp.transpose(val, (2, 1, 0)), lab_z, wz, first=False,
                   line_block=line_block)
  # (y, x, z) -> (z, y, x). Background voxels keep their distance to the
  # nearest label: every caller zeroes them on the host, and masking them
  # here took the TPU compiler ~40 s per shape at 66x514x514
  return jnp.transpose(val, (2, 0, 1))


# ---------------------------------------------------------------------------
# numpy twin of the envelope passes — the CPU-backend production path.
#
# XLA's scan cannot alias the (B, 2n) stack carries on the CPU backend, so
# every per-position scatter copies the whole stack (measured ~0.2 Mvox/s
# at 256^3). numpy fancy indexing IS in-place, so the identical algorithm
# runs at memory-bound speed; the device kernel above remains the TPU path
# and the semantics twin for tests.


def _edge_term_np(lab: np.ndarray, w: float) -> np.ndarray:
  B, n = lab.shape
  idx = np.arange(n, dtype=np.int64)
  chg = lab[:, 1:] != lab[:, :-1]
  big = 2 * n
  starts = np.full((B, n), -big, dtype=np.int64)
  starts[:, 1:][chg] = np.broadcast_to(idx[1:], chg.shape)[chg]
  left = np.maximum.accumulate(starts, axis=1)
  dl = np.where(left >= 1, (idx[None] - left + 1).astype(np.float32), INF)
  nxt = np.full((B, n), big, dtype=np.int64)
  nxt[:, :-1][chg] = np.broadcast_to(idx[1:], chg.shape)[chg]
  right = np.minimum.accumulate(nxt[:, ::-1], axis=1)[:, ::-1]
  dr = np.where(right <= n - 1, (right - idx[None]).astype(np.float32), INF)
  d = np.minimum(dl, dr)
  dc = np.where(d >= INF, np.float32(0), d)  # avoid f32 overflow of INF*w
  return np.where(d >= INF, INF, (dc * w) ** 2).astype(np.float32)


def _envelope_pass_np(val: np.ndarray, lab: np.ndarray, w: float) -> np.ndarray:
  # Layouts are position-major — lines (n, B), stacks (S, B) — so every
  # per-step slice is contiguous; the lane-major layout made each column
  # access touch B cache lines and ran ~50x slower.
  B, n = val.shape
  S = 2 * n + 2
  w2 = np.float32(w * w)
  f = np.ascontiguousarray(
    np.where(val >= INF, INF, val / w2).astype(np.float32).T
  )  # (n, B)
  chg = np.empty((n, B), bool)
  chg[0] = True
  chg[1:] = (lab[:, 1:] != lab[:, :-1]).T
  finite = f < INF / 2

  v = np.zeros((S, B), np.float32)
  h = np.full((S, B), INF, np.float32)
  z = np.full((S, B), INF, np.float32)
  k = np.full(B, -1, np.int64)
  base = np.zeros(B, np.int64)
  rows = np.arange(B)
  bases = np.empty((n, B), np.int32)  # S < 2^31 always

  def intersect(fq, q, hk, vk):
    den = 2.0 * (q - vk)
    den = np.where(den == 0, 1.0, den)
    return ((fq + q * q) - (hk + vk * vk)) / den

  for q in range(n):
    cq = chg[q]
    fq = f[q]
    finq = finite[q]
    base[cq] = k[cq] + 2
    k[cq] = base[cq] - 1
    active = finq & (k >= base)
    while active.any():
      ar = rows[active]
      ka = k[active]
      s = intersect(fq[active], q, h[ka, ar], v[ka, ar])
      pop = s <= z[ka, ar]
      k[ar[pop]] -= 1
      active = np.zeros(B, bool)
      active[ar[pop]] = True
      active &= k >= base
    pr = rows[finq]
    kp = k[finq]
    kc = np.maximum(kp, 0)
    s = np.where(
      kp >= base[finq],
      intersect(fq[finq], q, h[kc, pr], v[kc, pr]),
      -INF,
    )
    pos = kp + 1
    v[pos, pr] = q
    h[pos, pr] = fq[finq]
    z[pos, pr] = s
    z[pos + 1, pr] = INF
    k[finq] += 1
    bases[q] = base

  out = np.empty((n, B), np.float32)
  kq = np.zeros(B, np.int64)
  for q in range(n):
    cq = chg[q]
    kq[cq] = bases[q][cq]
    adv = z[np.minimum(kq + 1, S - 1), rows] < q
    while adv.any():
      kq[adv] += 1
      nxt = np.zeros(B, bool)
      nxt[adv] = z[np.minimum(kq[adv] + 1, S - 1), rows[adv]] < q
      adv = nxt
    out[q] = h[kq, rows] + (q - v[kq, rows]) ** 2
  res = np.where(out >= INF / 2, INF, out * w2).astype(np.float32)
  return np.ascontiguousarray(res.T)


# line-batch size for the numpy fallback: bounds transient stack memory
# (the (S, B) stacks would be ~GBs at 512^3 if all lines ran at once)
_NP_LINE_BATCH = 1 << 14


def _axis_pass_np(
  val: np.ndarray, lab: np.ndarray, w: float, first: bool
) -> np.ndarray:
  n = val.shape[-1]
  lead = val.shape[:-1]
  B = int(np.prod(lead)) if lead else 1
  v = np.ascontiguousarray(val).reshape(B, n)
  l = np.ascontiguousarray(lab).reshape(B, n)
  out = _edge_term_np(l, w)
  if not first:
    for lo in range(0, B, _NP_LINE_BATCH):
      hi = min(B, lo + _NP_LINE_BATCH)
      out[lo:hi] = np.minimum(
        out[lo:hi], _envelope_pass_np(v[lo:hi], l[lo:hi], w)
      )
  return out.reshape(*lead, n)


def _edt_sq_numpy(lab32: np.ndarray, anisotropy) -> np.ndarray:
  """(x, y, z) host layout; same three passes as the device kernel."""
  wx, wy, wz = anisotropy
  val = np.full(lab32.shape, INF, dtype=np.float32)
  val = np.moveaxis(
    _axis_pass_np(np.moveaxis(val, 0, 2), np.moveaxis(lab32, 0, 2), wx, True),
    2, 0,
  )
  val = np.swapaxes(
    _axis_pass_np(
      np.swapaxes(val, 1, 2), np.swapaxes(lab32, 1, 2), wy, False
    ), 1, 2,
  )
  val = _axis_pass_np(val, lab32, wz, False)
  return np.where(lab32 == 0, np.float32(0), val)


def _edt_sq_native(labels: np.ndarray, anisotropy, parallel: int = 0):
  """Threaded C++ envelope passes (native/csrc/edt.cpp); None if the
  native toolchain is unavailable. Labels are compared by raw equality so
  no renumber/unique pass is needed at any width."""
  from ..native import edt_lib

  lib = edt_lib()
  if lib is None:
    return None
  import ctypes

  if labels.dtype.itemsize <= 4:
    lab = np.ascontiguousarray(labels)
    if lab.dtype.itemsize < 4:
      lab = lab.astype(np.int32)
    lab = lab.view(np.int32)
    fn = lib.edt_ml_sq32
  else:
    lab = np.ascontiguousarray(labels).view(np.int64)
    fn = lib.edt_ml_sq64
  out = np.empty(lab.shape, dtype=np.float32)
  nx, ny, nz = lab.shape
  fn(
    lab.ctypes.data_as(ctypes.c_void_p), out.ctypes.data_as(ctypes.c_void_p),
    nx, ny, nz, float(anisotropy[0]), float(anisotropy[1]),
    float(anisotropy[2]), int(parallel),
  )
  return out


def _host_backend() -> str:
  """'native' | 'numpy' | 'device' for the current environment."""
  import os

  override = knobs.get_str("IGNEOUS_EDT_BACKEND")
  if override:
    if override not in ("native", "numpy", "device"):
      raise ValueError(
        "IGNEOUS_EDT_BACKEND must be 'native', 'numpy' or 'device': "
        f"{override!r}"
      )
    return override
  platforms = os.environ.get("JAX_PLATFORMS", "")
  if platforms:
    return "native" if platforms.split(",")[0] == "cpu" else "device"
  return "device" if jax.default_backend() != "cpu" else "native"


# executors (and their jit caches) reused per anisotropy: repeat batches
# of the same shape never recompile
_BATCH_EXECUTORS = {}


def batch_edt_executor(anisotropy, mesh=None):
  """Cached BatchKernelExecutor for the squared-EDT kernel, keyed by
  anisotropy + mesh so callers (the lease batcher) can pin dispatches to
  an injected device mesh instead of the full device set."""
  wx, wy, wz = (float(a) for a in anisotropy)
  mesh_key = (
    None if mesh is None
    else (tuple(d.id for d in mesh.devices.flat), mesh.axis_names)
  )
  lb = _line_block()
  key = (wx, wy, wz, lb, mesh_key)
  if key not in _BATCH_EXECUTORS:
    from functools import partial as _partial

    from ..parallel.executor import BatchKernelExecutor

    _BATCH_EXECUTORS[key] = BatchKernelExecutor(
      _partial(_edt_sq_kernel, anisotropy=(wx, wy, wz), line_block=lb),
      mesh=mesh,
      name="edt.sq_blocked",
    )
  return _BATCH_EXECUTORS[key]


def edt_batch(
  labels_batch: np.ndarray,
  anisotropy: Sequence[float] = (1.0, 1.0, 1.0),
  black_border: bool = False,
  executor=None,
):
  """Batched device EDT: (K, x, y, z) → list of K float32 distance fields.

  One shard_map'd dispatch computes all K cutouts' transforms with the
  chunk axis partitioned across the mesh (VERDICT round-1 item 3: the
  skeleton forge's flop-heavy stage in the batched path). Honors the same
  backend dispatch as edt() — on host backends each chunk runs the
  native/numpy path so batched and solo outputs stay bit-identical.
  """
  labels_batch = np.asarray(labels_batch)
  if labels_batch.ndim != 4:
    raise ValueError("labels_batch must be (K, x, y, z)")
  if executor is None and _host_backend() != "device":
    return [
      edt(l, anisotropy, black_border=black_border) for l in labels_batch
    ]
  work = labels_batch
  if black_border:
    work = np.pad(
      labels_batch, ((0, 0), (1, 1), (1, 1), (1, 1)), constant_values=0
    )
  from .ccl import _dense_relabel

  lab32 = _dense_relabel(work)  # shared: handles signed/no-zero inputs
  dev = np.ascontiguousarray(lab32.transpose(0, 3, 2, 1))  # (K, z, y, x)
  wx, wy, wz = (float(a) for a in anisotropy)
  if executor is None:
    executor = batch_edt_executor((wx, wy, wz))
  sq = executor(dev)
  outs = []
  for k in range(len(labels_batch)):
    s = np.asarray(sq[k]).transpose(2, 1, 0)
    if black_border:
      s = s[1:-1, 1:-1, 1:-1]
    o = np.sqrt(s, dtype=np.float32)
    o[labels_batch[k] == 0] = 0.0
    outs.append(o)
  return outs


def edt(
  labels: np.ndarray,
  anisotropy: Sequence[float] = (1.0, 1.0, 1.0),
  black_border: bool = False,
) -> np.ndarray:
  """labels: (x, y, z) integers → float32 distances, same layout.

  black_border treats the array boundary as background (kimimaro uses this
  so skeletons stay inside the cutout). Dispatches to the device kernel on
  accelerator backends and the in-place numpy envelope on the CPU backend
  (override with IGNEOUS_EDT_BACKEND=numpy|device).
  """
  if labels.ndim != 3:
    raise ValueError("labels must be 3d")
  orig_shape = labels.shape
  work = labels
  if black_border:
    work = np.pad(labels, 1, mode="constant", constant_values=0)

  wx, wy, wz = (float(a) for a in anisotropy)
  backend = _host_backend()
  sq = None
  if backend == "native":
    # host paths compare labels by raw equality — no renumber pass needed
    sq = _edt_sq_native(work, (wx, wy, wz))
    if sq is None:
      backend = "numpy"  # no toolchain — numpy twin
  if backend == "numpy":
    sq = _edt_sq_numpy(work, (wx, wy, wz))
  elif backend == "device":
    # compress labels to int32 identity space (values only matter by
    # equality; the device kernel works on 32-bit planes). Shared helper:
    # keeps zero as background even for signed inputs with negatives.
    from ..observability import device as device_telemetry
    from .ccl import _dense_relabel

    lab32 = _dense_relabel(work)
    dev = jnp.asarray(np.ascontiguousarray(lab32.transpose(2, 1, 0)))
    lb = _line_block()
    sq = np.asarray(device_telemetry.run_solo(
      "edt.sq_blocked", _edt_sq_kernel, (dev,), (dev.shape, (wx, wy, wz), lb),
      static=dict(anisotropy=(wx, wy, wz), line_block=lb),
      elements=int(dev.size), nbytes=int(lab32.nbytes),
    )).transpose(2, 1, 0)
  if black_border:
    sq = sq[1:-1, 1:-1, 1:-1]
  out = np.sqrt(sq, dtype=np.float32)
  out[labels == 0] = 0.0
  return out.reshape(orig_shape)
