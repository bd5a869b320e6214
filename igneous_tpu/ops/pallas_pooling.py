"""Pallas TPU kernel for 2x2x1 pooling — the hand-tiled fast path.

The default pooling pyramid (ops/pooling.py) is XLA-fused jnp code; this
module provides an explicitly tiled Pallas version of the hottest single
op (one 2x2x1 average/mode pooling step) for TPU:

  - layout (z-last): pooling runs over the sublane/second-minor dims while
    the lane dimension (z) streams untouched, so every load is contiguous
    in lanes;
  - the grid walks (y-tiles, x-tiles); each program reads a
    (2*TY, 2*TX, Z) VMEM block and writes (TY, TX, Z);
  - the mode variant implements the same earliest-position majority vote
    as ops/pooling._pool_mode via 4 static window slices.

Use ``available()`` / ``pool2x2x1`` with ``interpret=True`` for CPU tests.
The task pipeline keeps the XLA path; no program runs this kernel.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

try:  # pallas is part of jax, but guard exotic builds
  from jax.experimental import pallas as pl

  _PALLAS = True
except Exception:  # pragma: no cover
  _PALLAS = False


def available() -> bool:
  return _PALLAS


def _avg_step(x):
  a = x[0::2, 0::2, :].astype(jnp.int32)
  b = x[0::2, 1::2, :].astype(jnp.int32)
  c = x[1::2, 0::2, :].astype(jnp.int32)
  d = x[1::2, 1::2, :].astype(jnp.int32)
  return ((a + b + c + d + 2) // 4).astype(x.dtype)


def _mode_step(x):
  # earliest-position majority of the 4 window values (y-major window
  # order matches ops/pooling's z-major/y/x ordering for a 2x2x1 factor)
  vs = [
    x[0::2, 0::2, :],
    x[0::2, 1::2, :],
    x[1::2, 0::2, :],
    x[1::2, 1::2, :],
  ]
  best_s = None
  best_v = None
  for i in range(4):
    counts = None
    for j in range(4):
      e = (vs[i] == vs[j]).astype(jnp.int32)
      counts = e if counts is None else counts + e
    score = counts * 4 - i
    if best_s is None:
      best_s, best_v = score, vs[i]
    else:
      take = score > best_s
      best_s = jnp.where(take, score, best_s)
      best_v = jnp.where(take, vs[i], best_v)
  return best_v


def _avg_kernel(x_ref, o_ref):
  o_ref[...] = _avg_step(x_ref[...])


def _mode_kernel(x_ref, o_ref):
  o_ref[...] = _mode_step(x_ref[...])


def _pyramid_kernel(x_ref, *o_refs, method: str):
  # the whole mip walk on one VMEM-resident block: level l+1 pools
  # level l's block without ever leaving VMEM
  cur = x_ref[...]
  step = _avg_step if method == "average" else _mode_step
  for o in o_refs:
    cur = step(cur)
    o[...] = cur


@partial(jax.jit, static_argnames=("method", "ty", "tx", "interpret"))
def _pool_zlast(x, method: str, ty: int, tx: int, interpret: bool):
  """x: (Y, X, Z) with Y, X even, Y % 2ty == 0, X % 2tx == 0, Z % 128 == 0."""
  Y, X, Z = x.shape
  kernel = _avg_kernel if method == "average" else _mode_kernel
  return pl.pallas_call(
    kernel,
    out_shape=jax.ShapeDtypeStruct((Y // 2, X // 2, Z), x.dtype),
    grid=(Y // (2 * ty), X // (2 * tx)),
    in_specs=[
      pl.BlockSpec((2 * ty, 2 * tx, Z), lambda i, j: (i, j, 0)),
    ],
    out_specs=pl.BlockSpec((ty, tx, Z), lambda i, j: (i, j, 0)),
    interpret=interpret,
  )(x)


@partial(
  jax.jit, static_argnames=("method", "levels", "ty", "tx", "interpret")
)
def _pyramid_zlast(x, method: str, levels: int, ty: int, tx: int,
                   interpret: bool):
  """x: (Y, X, Z) with Y % (ty << levels) == 0, X % (tx << levels) == 0,
  Z % 128 == 0. Returns one (Y>>l, X>>l, Z) array per level l=1..levels,
  all produced by a SINGLE pallas_call: each grid program loads one
  (ty<<levels, tx<<levels, Z) block and walks the whole pyramid in VMEM.
  """
  Y, X, Z = x.shape
  by, bx = ty << levels, tx << levels
  out_shape = [
    jax.ShapeDtypeStruct((Y >> (l + 1), X >> (l + 1), Z), x.dtype)
    for l in range(levels)
  ]
  out_specs = [
    pl.BlockSpec((by >> (l + 1), bx >> (l + 1), Z), lambda i, j: (i, j, 0))
    for l in range(levels)
  ]
  return pl.pallas_call(
    partial(_pyramid_kernel, method=method),
    out_shape=out_shape,
    grid=(Y // by, X // bx),
    in_specs=[pl.BlockSpec((by, bx, Z), lambda i, j: (i, j, 0))],
    out_specs=out_specs,
    interpret=interpret,
  )(x)


def pyramid2x2x1(
  img: np.ndarray, num_mips: int = 2, method: str = "average",
  interpret: bool = False,
):
  """Fused multi-mip 2x2x1 pyramid: ONE pallas_call computes every mip.

  img: (x, y, z) numpy; returns a list of num_mips arrays, bitwise what
  L separate pool2x2x1 calls produce. The one-dispatch in-VMEM walk runs
  when x and y are multiples of 2**num_mips — then no mip's extent ever
  goes odd, so every window the cropped outputs read is fully real and
  pad-once (tile alignment only) is exact. Other extents fall back to
  iterated pool2x2x1 calls: an odd INTERMEDIATE extent makes the walks
  genuinely differ (the iterated walk duplicates that mip's own pooled
  edge line; a pad-once walk would fill the same slot by pooling mip-0
  edge replicas), and production chunk shapes are 2**k-aligned anyway.

  VMEM budget: each program holds a (8<<L, 8<<L, Z~128) input block plus
  its mip stack — ~2.8MB at L=3 for int32, comfortably inside the ~16MB
  per-core budget; L>4 callers should drop to ops.pooling's XLA walk.
  Same dtype gates as pool2x2x1.
  """
  if not _PALLAS:
    raise RuntimeError("pallas unavailable in this jax build")
  if num_mips < 1:
    raise ValueError("num_mips must be >= 1")
  if img.shape[0] % (1 << num_mips) or img.shape[1] % (1 << num_mips):
    outs = []
    cur = img
    for _ in range(num_mips):
      cur = pool2x2x1(cur, method=method, interpret=interpret)
      outs.append(cur)
    return outs
  if method == "mode" and img.dtype.itemsize > 4:
    raise ValueError("use ops.pooling for 64-bit labels (hi/lo planes)")
  if method == "average" and (
    np.issubdtype(img.dtype, np.floating) or img.dtype.itemsize > 2
  ):
    raise ValueError(
      "pallas averaging covers <=16-bit integers; use ops.pooling otherwise"
    )
  orig = img.shape
  work = img
  if work.dtype.itemsize <= 2 and method == "mode":
    work = work.astype(np.uint32)

  arr = np.ascontiguousarray(np.transpose(work, (1, 0, 2)))  # (y, x, z)
  ty, tx = 8, 8
  pad_y = (-arr.shape[0]) % (ty << num_mips)
  pad_x = (-arr.shape[1]) % (tx << num_mips)
  pad_z = (-arr.shape[2]) % 128
  if pad_y or pad_x or pad_z:
    arr = np.pad(arr, ((0, pad_y), (0, pad_x), (0, pad_z)), mode="edge")

  outs = _pyramid_zlast(
    jnp.asarray(arr), method, num_mips, ty, tx, interpret
  )
  results = []
  sx, sy, sz = orig
  for o in outs:
    sx, sy = (sx + 1) // 2, (sy + 1) // 2
    r = np.transpose(np.asarray(o), (1, 0, 2))[:sx, :sy, :sz]
    results.append(r.astype(img.dtype, copy=False))
  return results


def pool2x2x1(
  img: np.ndarray, method: str = "average", interpret: bool = False
) -> np.ndarray:
  """One 2x2x1 pooling step via the Pallas kernel.

  img: (x, y, z) numpy. Shapes are padded (edge-replicate, exact for
  factor 2 — see ops/pooling) to even x/y, lane-multiple z, and tile
  multiples.
  """
  if not _PALLAS:
    raise RuntimeError("pallas unavailable in this jax build")
  if method == "mode" and img.dtype.itemsize > 4:
    raise ValueError("use ops.pooling for 64-bit labels (hi/lo planes)")
  if method == "average" and (
    np.issubdtype(img.dtype, np.floating) or img.dtype.itemsize > 2
  ):
    # the kernel accumulates in int32: exact only for <=16-bit integers.
    # Wider dtypes use ops.pooling's hi/lo-split XLA path.
    raise ValueError(
      "pallas averaging covers <=16-bit integers; use ops.pooling otherwise"
    )
  orig = img.shape
  work = img
  if work.dtype.itemsize <= 2 and method == "mode":
    work = work.astype(np.uint32)

  # z-last layout: (y, x, z)
  arr = np.ascontiguousarray(np.transpose(work, (1, 0, 2)))
  ty, tx = 8, 8
  pad_y = (-arr.shape[0]) % (2 * ty)
  pad_x = (-arr.shape[1]) % (2 * tx)
  pad_z = (-arr.shape[2]) % 128
  if pad_y or pad_x or pad_z:
    arr = np.pad(arr, ((0, pad_y), (0, pad_x), (0, pad_z)), mode="edge")

  out = np.asarray(_pool_zlast(jnp.asarray(arr), method, ty, tx, interpret))
  out = np.transpose(out, (1, 0, 2))  # back to (x, y, z)
  out = out[: (orig[0] + 1) // 2, : (orig[1] + 1) // 2, : orig[2]]
  return out.astype(img.dtype, copy=False)
