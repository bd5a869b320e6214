"""Seconds in ``Volume.download`` of the source layer (storage get,
inflate, decode), timed by the harness and summed over threads, per
billion voxels read."""


def read(ctx):
  probes = ctx["probes"]
  if probes is None or not probes.download_voxels:
    return None
  return probes.download_s / (probes.download_voxels / 1e9)
