"""Percent of the traced window in which the device sat idle while the
worker loop finished a round's members (``lease.round.finish``: encode,
upload, join and acknowledgement). Mean over the cell's chips
(profiler trace)."""

from benchmark import span_reduce


def read(ctx):
  return span_reduce.idle_share(span_reduce.for_run(ctx, __file__),
                                "finish")
