"""Seconds in the program's ``volume.get`` spans (the storage get of a
cutout's chunks, on the io pool), summed over threads, per billion
voxels its ``volume.download`` spans read (profiler trace)."""

from benchmark import span_reduce


def read(ctx):
  return span_reduce.seconds_per_gvox(span_reduce.for_run(ctx, __file__),
                                      "volume.get")
