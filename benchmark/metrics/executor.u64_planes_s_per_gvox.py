"""Seconds in the program's ``pooling.u64_split`` (uint64 cutouts split
into (lo, hi) uint32 planes and stacked into the batch) and
``pooling.u64_pack`` (the returned mips packed back into uint64) spans,
summed over threads, per billion voxels its ``volume.download`` spans
read (profiler trace). Nothing where the program has neither span."""

from benchmark import span_reduce

SPANS = ("pooling.u64_split", "pooling.u64_pack")


def read(ctx):
  reduced = span_reduce.for_run(ctx, __file__)
  parts = [span_reduce.seconds_per_gvox(reduced, s) for s in SPANS]
  if any(p is None for p in parts):
    return None
  return sum(parts)
