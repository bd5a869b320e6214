"""Percent of the traced window in which the device sat idle while the
worker loop waited for input: in ``lease.prefetch_wait`` (the next
round's pre-lease and download) or ``lease.round.fetch`` (the round's
own cutouts). Mean over the cell's chips (profiler trace)."""

from benchmark import span_reduce


def read(ctx):
  return span_reduce.idle_share(span_reduce.for_run(ctx, __file__),
                                "input_wait")
