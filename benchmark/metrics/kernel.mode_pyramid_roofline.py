"""Percent of the HBM roofline the mode pyramid reached, found by name:
the least time for the bytes its dispatches must move (every input
voxel read once, every mip written once, 8 bytes a uint64 label) at the
chip's peak bandwidth, over the device time of the compiled programs
named ``jit_pooling_pyramid_mode``. Dispatches are the program's
``device.execute`` spans of that kernel (profiler trace). The bytes
follow from the task's shapes alone, however the program walks them."""

from benchmark import roofline, span_reduce

KERNEL = "pooling.pyramid[mode]"
MODULE = "jit_pooling_pyramid_mode"


def read(ctx):
  if ctx["method"] != "mode":
    return None
  reduced = span_reduce.for_run(ctx, __file__)
  if reduced is None:
    return None
  seconds = sum(s for name, s in reduced["modules_s"].items()
                if name.startswith(MODULE))
  dispatches = reduced["executes"].get(KERNEL, 0)
  if not dispatches or seconds <= 0:
    return None
  nbytes = dispatches * ctx["kernel_bytes_per_dispatch"]
  return roofline.bandwidth_share(nbytes, seconds, ctx["device_kind"])
