"""Campaign throughput: mip-0 voxels of every task acknowledged in the
window, over the window's seconds and the cell's chips (host clock)."""


def read(ctx):
  win = ctx["window"]
  if not win["voxels"] or win["window_s"] <= 0:
    return None
  return win["voxels"] / win["window_s"] / ctx["chips"]
