"""The program's ``pipeline.encode_upload.s`` timer over the window
(chunk encode, compress and put on the encode pool, summed over
threads), per billion mip-0 voxels of the tasks acknowledged."""


def read(ctx):
  win = ctx["window"]
  if not win["voxels"] or win["encode_upload_s"] <= 0:
    return None
  return win["encode_upload_s"] / (win["voxels"] / 1e9)
