"""Seconds in the program's ``queue.*`` spans (lease, renew, delete,
nack, release of the ``fq://`` queue), each thread's union summed over
threads, per acknowledged task (profiler trace)."""

from benchmark import span_reduce


def read(ctx):
  reduced = span_reduce.for_run(ctx, __file__)
  acked = ctx["window"]["acked"]
  if reduced is None or not reduced["queue_spans"] or not acked:
    return None
  return reduced["queue_s"] / acked
