"""Percent of the HBM roofline the average pyramid reached: the least
time for the bytes its dispatches must move (every input voxel read
once, every mip written once) at the chip's peak bandwidth, over the
device time of the programs those dispatches ran (profiler trace).
Bandwidth bounds this kernel."""

from benchmark import roofline
from benchmark.trace_reduce import modules_under


def read(ctx):
  if ctx["method"] != "average":
    return None
  trace = ctx["trace"]
  if trace is None:
    return None
  seconds = sum(modules_under(trace, "dispatch").values())
  dispatches = [s for s in trace["host_spans"] if s[0] == "bench.dispatch"]
  if not dispatches or seconds <= 0:
    return None
  nbytes = len(dispatches) * ctx["kernel_bytes_per_dispatch"]
  return roofline.bandwidth_share(nbytes, seconds, ctx["device_kind"])
