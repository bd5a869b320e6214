"""Host-to-device bytes in the window (device ledger ``h2d_bytes``) per
mip-0 voxel of the tasks acknowledged."""


def read(ctx):
  win = ctx["window"]
  if not win["voxels"]:
    return None
  return win["h2d_bytes"] / win["voxels"]
