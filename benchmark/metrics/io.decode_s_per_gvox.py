"""Seconds in the program's ``volume.decode`` spans (inflate and codec
decode of a cutout's chunks, with the decoded-chunk cache lookup),
summed over threads, per billion voxels its ``volume.download`` spans
read (profiler trace)."""

from benchmark import span_reduce


def read(ctx):
  return span_reduce.seconds_per_gvox(span_reduce.for_run(ctx, __file__),
                                      "volume.decode")
