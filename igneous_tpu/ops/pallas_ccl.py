"""Pallas TPU kernel for the CCL block-local tile resolve.

`ops/ccl.py`'s tiled label-propagation path cuts the volume into
VMEM-sized tiles and resolves each tile locally before one host
boundary-merge pass. This module is a Pallas candidate for that local
resolve. No program path calls it: it does not compile for v5e at an
(8, 16, 128) int32 tile (Mosaic: "Invalid vector register cast"), so
ccl.py resolves with lax (``ccl._tile_resolve``) on every backend.

Per grid program: one (tz, ty, tx) tile lives in VMEM and iterates a
gather-free round — log-doubling segmented cummin along each axis
(Hillis–Steele with run-break flags: rolls + wheres only, no
associative_scan, no pointer gathers) plus one neighbor-min over the
requested connectivity — inside an in-kernel ``while_loop``. That loop
is the real per-tile early exit: each tile stops at ITS OWN fixpoint
instead of the batched-lax path's max-over-tiles round count.

Output contract matches ccl._tile_resolve exactly:
every voxel holds the LOCAL flat index of its tile-component's minimum
voxel (background voxels keep their own index; the caller masks them),
so the two are interchangeable bit-for-bit.

Use ``tile_resolve(..., interpret=True)`` for CPU parity tests.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

try:  # pallas is part of jax, but guard exotic builds
  from jax.experimental import pallas as pl

  _PALLAS = True
except Exception:  # pragma: no cover
  _PALLAS = False


def available() -> bool:
  return _PALLAS


def _seg_cummin_doubling(L, lab, axis, reverse):
  """Segmented cummin along ``axis`` via log-step doubling.

  ok_s[i] tracks "the s-long chain upstream of i stays in one run";
  both the value window and the flag double each step, so ceil(log2(n))
  rolls collapse every contiguous same-label run to its min — the same
  result as ccl._seg_cummin without lax.associative_scan (which Mosaic
  does not lower)."""
  n = L.shape[axis]
  d = -1 if reverse else 1
  coord = jax.lax.broadcasted_iota(jnp.int32, L.shape, axis)
  edge = coord >= 1 if not reverse else coord <= n - 2
  ok = edge & (jnp.roll(lab, d, axis) == lab)
  v = L
  s = 1
  while s < n:
    vs = jnp.roll(v, d * s, axis)
    oks = jnp.roll(ok, d * s, axis)
    v = jnp.where(ok, jnp.minimum(v, vs), v)
    ok = ok & oks  # false flags never wrap into true ones (i >= s holds)
    s *= 2
  return v


def _resolve_kernel(lab_ref, out_ref, *, connectivity: int):
  from .ccl import neighbor_offsets

  lab = lab_ref[0]
  tz, ty, tx = lab.shape
  fg = lab != 0
  big = jnp.iinfo(jnp.int32).max
  L0 = (
    jax.lax.broadcasted_iota(jnp.int32, lab.shape, 0) * (ty * tx)
    + jax.lax.broadcasted_iota(jnp.int32, lab.shape, 1) * tx
    + jax.lax.broadcasted_iota(jnp.int32, lab.shape, 2)
  )

  def nb_min(L):
    m = L
    for off in neighbor_offsets(connectivity):
      nb_L, nb_lab, valid = L, lab, None
      for axis, dd in enumerate(off):
        if dd == 0:
          continue
        nb_L = jnp.roll(nb_L, dd, axis)
        nb_lab = jnp.roll(nb_lab, dd, axis)
        size = lab.shape[axis]
        coord = jax.lax.broadcasted_iota(jnp.int32, lab.shape, axis)
        ok = coord != (0 if dd == 1 else size - 1)
        valid = ok if valid is None else (valid & ok)
      same = valid & (nb_lab == lab)
      m = jnp.minimum(m, jnp.where(same, nb_L, big))
    return m

  def cond(state):
    return state[1]

  def body(state):
    L, _ = state
    Lp = L
    for axis in range(3):
      Lp = jnp.minimum(
        _seg_cummin_doubling(Lp, lab, axis, False),
        _seg_cummin_doubling(Lp, lab, axis, True),
      )
    Lp = jnp.minimum(Lp, nb_min(Lp))
    Lp = jnp.where(fg, jnp.minimum(L, Lp), L)
    return (Lp, jnp.any(Lp != L))

  L, _ = jax.lax.while_loop(cond, body, (L0, jnp.bool_(True)))
  out_ref[0] = L


@partial(jax.jit, static_argnames=("connectivity", "interpret"))
def tile_resolve(
  labt: jnp.ndarray, connectivity: int = 6, interpret: bool = False
) -> jnp.ndarray:
  """labt: (T, tz, ty, tx) int32 tiles → per-voxel local component roots
  (local flat index of the tile-component minimum; background voxels
  keep their own index — the caller masks them)."""
  if not _PALLAS:
    raise RuntimeError("pallas unavailable in this jax build")
  T, tz, ty, tx = labt.shape
  return pl.pallas_call(
    partial(_resolve_kernel, connectivity=connectivity),
    out_shape=jax.ShapeDtypeStruct((T, tz, ty, tx), jnp.int32),
    grid=(T,),
    in_specs=[pl.BlockSpec((1, tz, ty, tx), lambda i: (i, 0, 0, 0))],
    out_specs=pl.BlockSpec((1, tz, ty, tx), lambda i: (i, 0, 0, 0)),
    interpret=interpret,
  )(labt)
