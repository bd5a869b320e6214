"""The yardstick for kernel roofline shares: chip peaks and kernel bytes.

Peaks are published numbers, keyed by ``jax.Device.device_kind``. A device
that is not in the table is an error, never a default.
"""

from __future__ import annotations

from typing import Sequence

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s int8,
# 16 GB of HBM at 819 GB/s per chip.
PEAKS = {
  "TPU v5 lite": {
    "bf16_flop_per_s": 197e12,
    "int8_op_per_s": 393e12,
    "hbm_bytes_per_s": 819e9,
    "hbm_bytes": 16e9,
  },
}


def peaks(device_kind: str) -> dict:
  try:
    return PEAKS[device_kind]
  except KeyError:
    raise ValueError(
      f"no published peaks for device kind {device_kind!r}; add them to "
      f"benchmark/roofline.py with their source"
    ) from None


def mip_shapes(shape: Sequence[int], factor: Sequence[int], num_mips: int):
  """Shapes of mips 1..num_mips of a (x, y, z) cutout; odd extents round
  up, as the pyramid replicates the edge."""
  out = []
  cur = [int(v) for v in shape]
  for _ in range(int(num_mips)):
    cur = [-(-c // int(f)) for c, f in zip(cur, factor)]
    out.append(tuple(cur))
  return out


def pyramid_bytes(shape: Sequence[int], factor: Sequence[int], num_mips: int,
                  itemsize: int) -> int:
  """Least bytes one cutout's pyramid moves through HBM: the input read
  once and every mip written once. ``itemsize`` is the bytes of a voxel
  on the device (8 for uint64 labels, carried as two uint32 planes)."""
  vox = 1
  for s in shape:
    vox *= int(s)
  for m in mip_shapes(shape, factor, num_mips):
    n = 1
    for s in m:
      n *= s
    vox += n
  return vox * int(itemsize)


def bandwidth_share(nbytes: float, device_seconds: float,
                    device_kind: str) -> float | None:
  """Percent of the HBM roofline: the least time for ``nbytes`` at the
  chip's peak bandwidth over the device time the kernel took. None when
  the kernel did not run."""
  if device_seconds <= 0 or nbytes <= 0:
    return None
  least = nbytes / peaks(device_kind)["hbm_bytes_per_s"]
  return 100.0 * least / device_seconds
