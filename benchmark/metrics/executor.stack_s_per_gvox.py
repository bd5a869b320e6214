"""Seconds in the program's ``pooling.stack`` span (uint8 cutouts copied
into the dispatch's (K, c, z, y, x) batch), summed over threads, per
billion voxels its ``volume.download`` spans read (profiler trace).
Nothing where the program has no such span."""

from benchmark import span_reduce


def read(ctx):
  return span_reduce.seconds_per_gvox(span_reduce.for_run(ctx, __file__),
                                      "pooling.stack")
