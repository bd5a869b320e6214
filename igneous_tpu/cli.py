"""The ``igneous-tpu`` command line interface.

Command-tree AND option-level parity with the reference CLI
(/root/reference/igneous_cli/cli.py:185-214; audited programmatically —
every reference command and --option has a counterpart here):
  image {downsample, xfer, create, rm, roi, reorder,
         voxels {count,sum}, contrast {histogram,equalize,clahe},
         ccl {faces,links,calc-labels,relabel,clean,auto}}
  mesh {forge, merge, merge-sharded, xfer, rm, clean,
        spatial-index {create,db}}
  skeleton {forge, merge, merge-sharded, xfer, rm, clean, convert,
            spatial-index {create,db}}
  execute | queue {status,wait,release,rezero,purge,cp,mv,fsck,
                   dlq {ls,retry,purge}}
  fleet {status,trace,top,devices,compact,gc,check,watch}
  profile {capture,ls}
  design {ds-memory, ds-shape, bounds}
  view | license

Heavy imports (jax, task modules) happen inside commands so --help and
queue tooling stay instant.
"""

from __future__ import annotations

import os
import sys

import click

from .analysis import knobs


class Tuple3(click.ParamType):
  """'64,64,64' → (64, 64, 64) (reference cli.py:80-162 param types)."""

  name = "tuple3"

  def convert(self, value, param, ctx):
    if isinstance(value, (tuple, list)):
      return tuple(int(v) for v in value)
    try:
      parts = [int(v) for v in str(value).replace("x", ",").split(",")]
    except ValueError:
      self.fail(f"{value!r} is not an int triple like 64,64,64", param, ctx)
    if len(parts) != 3:
      self.fail(f"{value!r} must have exactly 3 components", param, ctx)
    return tuple(parts)


TUPLE3 = Tuple3()


class Tuple2(click.ParamType):
  """'0,1024' → (0, 1024) (reference cli.py:114-124)."""

  name = "tuple2"

  def convert(self, value, param, ctx):
    if isinstance(value, (tuple, list)):
      return tuple(int(v) for v in value)
    try:
      parts = [int(v) for v in str(value).split(",")]
    except ValueError:
      self.fail(f"{value!r} is not an int pair like 0,1024", param, ctx)
    if len(parts) != 2:
      self.fail(f"{value!r} must have exactly 2 components", param, ctx)
    return tuple(parts)


TUPLE2 = Tuple2()


def range_opts(fn):
  """Shared --xrange/--yrange/--zrange bounds restriction (reference
  cli.py:254-256 et al.)."""
  for opt in (
    click.option("--zrange", type=TUPLE2, default=None,
                 help="Restrict z-bounds (in the bounds mip), e.g. 0,1"),
    click.option("--yrange", type=TUPLE2, default=None,
                 help="Restrict y-bounds (in the bounds mip), e.g. 0,1024"),
    click.option("--xrange", type=TUPLE2, default=None,
                 help="Restrict x-bounds (in the bounds mip), e.g. 0,1024"),
  ):
    fn = opt(fn)
  return fn


def compute_cli_bounds(path, mip, xrange, yrange, zrange):
  """Bbox from the volume bounds at ``mip`` with any provided axis ranges
  overridden (reference cli.py:164-183); None when no range given."""
  if not (xrange or yrange or zrange):
    return None
  from .volume import Volume

  bounds = Volume(path).meta.bounds(mip or 0)
  for axis, rng in enumerate((xrange, yrange, zrange)):
    if rng:
      lo, hi = sorted(rng)
      bounds.minpt[axis] = lo
      bounds.maxpt[axis] = hi
  return bounds


def parse_id_list(value):
  """'5,6,7' → [5, 6, 7]; tolerant of blanks; None/empty → None."""
  if not value:
    return None
  try:
    ids = [int(tok) for tok in str(value).split(",") if tok.strip()]
  except ValueError:
    raise click.UsageError(f"not a comma-separated id list: {value!r}")
  return ids or None


def enqueue(queue_spec: str, tasks, parallel: int = 1):
  from .queues import LocalTaskQueue, TaskQueue

  if queue_spec is None:
    LocalTaskQueue(parallel=parallel).insert(tasks)
  else:
    # batched wire protocol (ISSUE 15): grid iterators know their task
    # count up front, which lets fq:// size its segment shards
    total = None
    if hasattr(tasks, "num_pending"):
      total = tasks.num_pending()
    elif hasattr(tasks, "__len__"):
      try:
        total = len(tasks)
      except TypeError:
        total = None
    TaskQueue(queue_spec).insert_batch(tasks, total=total)


@click.group()
@click.option("-p", "--parallel", default=1, show_default=True,
              help="Worker processes for local execution (0 = all cores).")
@click.version_option(version="0.3.0", prog_name="igneous-tpu")
@click.pass_context
def main(ctx, parallel):
  """igneous-tpu: TPU-native Neuroglancer Precomputed pipelines."""
  from .compile_cache import place_jax_cache

  place_jax_cache()
  ctx.ensure_object(dict)
  # reference semantics: -p 0 means "use the number of cores"
  # (/root/reference/igneous_cli/cli.py:186)
  ctx.obj["parallel"] = parallel if parallel > 0 else (os.cpu_count() or 1)


# ---------------------------------------------------------------------------
# image


@main.group()
def image():
  """Downsample, transfer, ingest, delete image/segmentation layers."""


def _resolve_compress(compress, encoding):
  """'none'/'false' → False; lossy/self-compressed encodings skip the
  second-stage compressor (reference cli.py:283-287)."""
  if isinstance(compress, str) and compress.lower() in ("none", "false"):
    compress = False
  if encoding and str(encoding).lower() in ("jpeg", "jxl", "png", "fpzip",
                                            "zfpc"):
    compress = False
  return compress


@image.command("downsample")
@click.argument("path")
@click.option("--queue", "-q", default=None, help="fq:// queue (local if omitted)")
@click.option("--mip", default=0, show_default=True)
@click.option("--num-mips", default=5, show_default=True)
@click.option("--factor", type=TUPLE3, default=None, help="e.g. 2,2,1")
@click.option("--volumetric", is_flag=True, help="Use 2x2x2 downsampling.")
@click.option("--isotropic", is_flag=True,
              help="Per-mip factors driving the resolution toward isotropy.")
@click.option("--sparse", is_flag=True)
@click.option("--sharded", is_flag=True)
@click.option("--fill-missing", is_flag=True)
@click.option("--chunk-size", type=TUPLE3, default=None)
@click.option("--encoding", default=None)
@click.option("--encoding-level", type=int, default=None,
              help="png level / jpeg quality.")
@click.option("--encoding-effort", type=int, default=None,
              help="(jpeg xl) accepted for parity; jxl is not shipped.")
@click.option("--compress", default="gzip", show_default=True,
              help="Chunk compression: none, gzip, br.")
@click.option("--delete-bg", is_flag=True,
              help="Delete background tiles instead of uploading them.")
@click.option("--bg-color", default=0, show_default=True)
@click.option("--memory", "memory_target", default=int(3.5e9), show_default=True)
@click.option("--method", "downsample_method", default="auto", show_default=True)
@range_opts
@click.option("--batched", is_flag=True,
              help="Run on this host's device mesh now (K cutouts per "
                   "shard_map dispatch, double-buffered IO) instead of "
                   "enqueuing per-cutout tasks.")
@click.option("--batch-size", default=8, show_default=True,
              help="Cutouts per device dispatch with --batched.")
@click.option("--shape", type=TUPLE3, default=(256, 256, 64),
              show_default=True, help="Cutout shape with --batched.")
@click.pass_context
def image_downsample(ctx, path, queue, mip, num_mips, factor, volumetric,
                     isotropic, sparse, sharded, fill_missing, chunk_size,
                     encoding, encoding_level, encoding_effort, compress,
                     delete_bg, bg_color, memory_target, downsample_method,
                     xrange, yrange, zrange, batched, batch_size, shape):
  """Build the downsample pyramid of PATH."""
  from . import task_creation as tc

  if isotropic:
    if factor is not None or volumetric:
      raise click.UsageError("--isotropic excludes --factor/--volumetric")
    if sharded or batched:
      raise click.UsageError(
        "--isotropic plans per-mip factors, which only the unsharded "
        "task factory supports"
      )
    factor = "isotropic"
  elif volumetric:
    if factor is not None:
      raise click.UsageError("--volumetric and --factor are exclusive")
    factor = (2, 2, 2)
  compress = _resolve_compress(compress, encoding)
  bounds = compute_cli_bounds(path, mip, xrange, yrange, zrange)
  if batched:
    if sharded or queue:
      raise click.UsageError("--batched runs unsharded on this host (no -q)")
    if factor == "isotropic":
      raise click.UsageError("--batched uses one fixed --factor")
    if encoding or chunk_size:
      raise click.UsageError(
        "--batched downsamples in place; --encoding/--chunk-size apply "
        "only to the task factories"
      )
    from .parallel.batch_runner import batched_downsample

    stats = batched_downsample(
      path, mip=mip, num_mips=num_mips, shape=shape,
      batch_size=batch_size, factor=factor or (2, 2, 1), sparse=sparse,
      fill_missing=fill_missing, method=downsample_method, bounds=bounds,
    )
    click.echo(
      f"batched: {stats['batched_cutouts']} cutouts in "
      f"{stats['dispatches']} dispatches, {stats['edge_cutouts']} edge "
      f"cutouts via the task path"
    )
    return
  if sharded:
    tasks = tc.create_image_shard_downsample_tasks(
      path, mip=mip, fill_missing=fill_missing, sparse=sparse,
      chunk_size=chunk_size, encoding=encoding,
      encoding_level=encoding_level, encoding_effort=encoding_effort,
      factor=factor or (2, 2, 1), memory_target=memory_target,
      downsample_method=downsample_method, bounds=bounds, bounds_mip=mip,
      num_mips=num_mips,
    )
  else:
    tasks = tc.create_downsampling_tasks(
      path, mip=mip, num_mips=num_mips, fill_missing=fill_missing,
      sparse=sparse, chunk_size=chunk_size, encoding=encoding,
      encoding_level=encoding_level, encoding_effort=encoding_effort,
      delete_black_uploads=delete_bg, background_color=bg_color,
      compress=compress, factor=factor, memory_target=memory_target,
      downsample_method=downsample_method, bounds=bounds, bounds_mip=mip,
    )
  enqueue(queue, tasks, ctx.obj["parallel"])


@image.command("xfer")
@click.argument("src")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--chunk-size", type=TUPLE3, default=None)
@click.option("--shape", type=TUPLE3, default=None,
              help="(overrides --memory) Task shape in voxels.")
@click.option("--translate", type=TUPLE3, default=(0, 0, 0))
@click.option("--fill-missing", is_flag=True)
@click.option("--sharded", is_flag=True)
@click.option("--encoding", default=None)
@click.option("--encoding-level", type=int, default=None,
              help="png level / jpeg quality.")
@click.option("--encoding-effort", type=int, default=None,
              help="(jpeg xl) accepted for parity; jxl is not shipped.")
@click.option("--compress", default="gzip", show_default=True,
              help="Chunk compression: none, gzip, br.")
@click.option("--downsample/--skip-downsample", "do_downsample", default=True,
              show_default=True,
              help="Produce downsamples from transfer tiles.")
@click.option("--max-mips", default=5, show_default=True,
              help="Maximum number of additional pyramid levels.")
@click.option("--num-mips", default=None, type=int,
              help="Deprecated alias for --max-mips.")
@click.option("--memory", "memory_target", default=int(3.5e9),
              show_default=True)
@click.option("--sparse", is_flag=True)
@click.option("--volumetric", is_flag=True, help="Use 2x2x2 downsampling.")
@click.option("--method", "--downsample-method", "downsample_method",
              default="auto", show_default=True)
@click.option("--delete-bg", is_flag=True)
@click.option("--bg-color", default=0, show_default=True)
@click.option("--dest-voxel-offset", type=TUPLE3, default=None,
              help="Set the new volume's global origin.")
@click.option("--clean-info", is_flag=True,
              help="Scrub mesh/skeleton fields from the new info.")
@click.option("--no-src-update", is_flag=True,
              help="Skip the source provenance note.")
@click.option("--truncate-scales/--no-truncate-scales", default=True,
              show_default=True,
              help="Drop source scales above --mip in the new info.")
@click.option("--use-https-src", is_flag=True,
              help="Parity flag: implies --no-src-update (no https "
                   "backend in this build).")
@range_opts
@click.option("--bounds-mip", default=None, type=int,
              help="Mip the ranges are specified in [default: --mip].")
@click.option("--cutout", is_flag=True,
              help="Restrict a newly created volume to the given bounds.")
@click.pass_context
def image_xfer(ctx, src, dest, queue, mip, chunk_size, shape, translate,
               fill_missing, sharded, encoding, encoding_level,
               encoding_effort, compress, do_downsample, max_mips, num_mips,
               memory_target, sparse, volumetric, downsample_method,
               delete_bg, bg_color, dest_voxel_offset, clean_info,
               no_src_update, truncate_scales, use_https_src, xrange, yrange,
               zrange, bounds_mip, cutout):
  """Transfer/rechunk/re-encode SRC into DEST."""
  from . import task_creation as tc

  compress = _resolve_compress(compress, encoding)
  bounds_mip = mip if bounds_mip is None else bounds_mip
  bounds = compute_cli_bounds(src, bounds_mip, xrange, yrange, zrange)
  factor = (2, 2, 2) if volumetric else None
  if num_mips is not None:
    max_mips = num_mips
  if not do_downsample:
    max_mips = 0
  if sharded:
    tasks = tc.create_image_shard_transfer_tasks(
      src, dest, mip=mip, chunk_size=chunk_size, encoding=encoding,
      encoding_level=encoding_level, encoding_effort=encoding_effort,
      translate=translate, fill_missing=fill_missing,
      dest_voxel_offset=dest_voxel_offset, bounds=bounds,
      bounds_mip=bounds_mip, uncompressed_shard_bytesize=memory_target,
      cutout=cutout, clean_info=clean_info, truncate_scales=truncate_scales,
    )
  else:
    tasks = tc.create_transfer_tasks(
      src, dest, chunk_size=chunk_size, shape=shape, mip=mip,
      translate=translate, fill_missing=fill_missing, encoding=encoding,
      encoding_level=encoding_level, encoding_effort=encoding_effort,
      compress=compress, num_mips=max_mips, memory_target=memory_target,
      sparse=sparse, factor=factor, downsample_method=downsample_method,
      delete_black_uploads=delete_bg, background_color=bg_color,
      dest_voxel_offset=dest_voxel_offset, clean_info=clean_info,
      no_src_update=no_src_update, truncate_scales=truncate_scales,
      use_https_for_source=use_https_src, bounds=bounds,
      bounds_mip=bounds_mip, cutout=cutout,
    )
  enqueue(queue, tasks, ctx.obj["parallel"])


@image.command("infer")
@click.argument("src")
@click.argument("dest")
@click.option("--model", "model_path", required=True,
              help="Cloudpath of a saved model (model.json + params.npz).")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=None,
              help="Task shape in voxels (snapped up to chunk multiples).")
@click.option("--halo", type=TUPLE3, default=None,
              help="Context voxels per face [default: the model overlap].")
@click.option("--batch-size", default=4, show_default=True,
              help="Patches per device dispatch group.")
@click.option("--postprocess",
              type=click.Choice(["none", "quantize", "argmax"]),
              default="none", show_default=True,
              help="none: float32 channels; quantize: uint8 [0,1]*255; "
                   "argmax: uint8 channel argmax (segmentation).")
@click.option("--fill-missing", is_flag=True)
@click.option("--compress", default="gzip", show_default=True)
@click.option("--chunk-size", type=TUPLE3, default=None,
              help="Destination chunk size [default: source's].")
@range_opts
@click.option("--bounds-mip", default=None, type=int,
              help="Mip the ranges are specified in [default: --mip].")
@click.pass_context
def image_infer(ctx, src, dest, model_path, queue, mip, shape, halo,
                batch_size, postprocess, fill_missing, compress,
                chunk_size, xrange, yrange, zrange, bounds_mip):
  """Run conv-net inference over SRC into DEST (halo'd cutout →
  jitted JAX apply → overlap blend → Precomputed output)."""
  from . import task_creation as tc

  bounds_mip = mip if bounds_mip is None else bounds_mip
  bounds = compute_cli_bounds(src, bounds_mip, xrange, yrange, zrange)
  tasks = tc.create_inference_tasks(
    src, dest, model_path, mip=mip, shape=shape, halo=halo,
    bounds=bounds, bounds_mip=bounds_mip, fill_missing=fill_missing,
    batch_size=batch_size, postprocess=postprocess, compress=compress,
    chunk_size=chunk_size,
  )
  enqueue(queue, tasks, ctx.obj["parallel"])


@image.command("create")
@click.argument("src")
@click.argument("dest")
@click.option("--resolution", type=TUPLE3, default=(1, 1, 1), show_default=True)
@click.option("--offset", type=TUPLE3, default=(0, 0, 0), show_default=True)
@click.option("--chunk-size", type=TUPLE3, default=(64, 64, 64), show_default=True)
@click.option("--layer-type", default=None,
              type=click.Choice(["image", "segmentation"]))
@click.option("--seg", is_flag=True,
              help="Shorthand for --layer-type segmentation.")
@click.option("--encoding", default="raw", show_default=True)
@click.option("--encoding-level", type=int, default=None,
              help="png level / jpeg quality.")
@click.option("--encoding-effort", type=int, default=None,
              help="(jpeg xl) accepted for parity; jxl is not shipped.")
@click.option("--compress", default="gzip", show_default=True,
              help="Chunk compression: none, gzip, br.")
@click.option("--h5-dataset", default="main", show_default=True,
              help="Which h5 dataset to access (hdf5 imports only).")
def image_create(src, dest, resolution, offset, chunk_size, layer_type, seg,
                 encoding, encoding_level, encoding_effort, compress,
                 h5_dataset):
  """Ingest an array file (npy/npy.gz/h5/nrrd/nii/nii.gz) as a Precomputed
  layer (reference `igneous image create`, cli.py:1852-1923; ckl needs
  the crackle library and fails with instructions)."""
  from .formats import load_volume_file
  from .volume import Volume

  if seg:
    layer_type = "segmentation"
  try:
    arr = load_volume_file(src, h5_dataset=h5_dataset)
  except (ValueError, OSError) as e:  # OSError: corrupt gzip members
    raise click.UsageError(str(e))
  Volume.from_numpy(
    arr, dest, resolution=resolution, voxel_offset=offset,
    chunk_size=chunk_size, layer_type=layer_type, encoding=encoding,
    encoding_level=encoding_level,
    compress=_resolve_compress(compress, encoding),
  )
  click.echo(f"Created {dest} from {src} {arr.shape} {arr.dtype}")


@image.command("rm")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--num-mips", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=None,
              help="Task shape in voxels.")
@range_opts
@click.pass_context
def image_rm(ctx, path, queue, mip, num_mips, shape, xrange, yrange, zrange):
  """Delete image chunks at mip (… mip+num-mips)."""
  from . import task_creation as tc

  bounds = compute_cli_bounds(path, mip, xrange, yrange, zrange)
  enqueue(queue, tc.create_deletion_tasks(
    path, mip=mip, num_mips=num_mips, shape=shape, bounds=bounds,
    bounds_mip=mip,
  ), ctx.obj["parallel"])


# -- image contrast ----------------------------------------------------------


@image.group("contrast")
def image_contrast():
  """Luminance histograms, contrast stretch, CLAHE."""


@image_contrast.command("histogram")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--coverage", default=0.01, show_default=True)
@click.option("--fill-missing", is_flag=True)
@range_opts
@click.option("--bounds-mip", default=None, type=int,
              help="Mip the ranges are specified in [default: --mip].")
@click.pass_context
def contrast_histogram(ctx, path, queue, mip, coverage, fill_missing,
                       xrange, yrange, zrange, bounds_mip):
  """Phase 1: per-z luminance histograms."""
  from . import task_creation as tc

  bounds_mip = mip if bounds_mip is None else bounds_mip
  bounds = compute_cli_bounds(path, bounds_mip, xrange, yrange, zrange)
  enqueue(queue, tc.create_luminance_levels_tasks(
    path, mip=mip, coverage_factor=coverage, fill_missing=fill_missing,
    bounds=bounds, bounds_mip=bounds_mip,
  ), ctx.obj["parallel"])


@image_contrast.command("equalize")
@click.argument("src")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--clip-fraction", default=0.01, show_default=True)
@click.option("--shape", type=TUPLE3, default=None)
@click.option("--fill-missing", is_flag=True)
@click.option("--minval", type=int, default=0, show_default=True,
              help="Stretch floor value.")
@click.option("--maxval", type=int, default=255, show_default=True,
              help="Stretch ceiling value.")
@click.option("--translate", type=TUPLE3, default=(0, 0, 0))
@range_opts
@click.option("--bounds-mip", default=None, type=int,
              help="Mip the ranges are specified in [default: --mip].")
@click.pass_context
def contrast_equalize(ctx, src, dest, queue, mip, clip_fraction, shape,
                      fill_missing, minval, maxval, translate, xrange,
                      yrange, zrange, bounds_mip):
  """Phase 2: histogram stretch using phase-1 levels."""
  from . import task_creation as tc

  bounds_mip = mip if bounds_mip is None else bounds_mip
  bounds = compute_cli_bounds(src, bounds_mip, xrange, yrange, zrange)
  enqueue(queue, tc.create_contrast_normalization_tasks(
    src, dest, mip=mip, clip_fraction=clip_fraction, shape=shape,
    fill_missing=fill_missing, minval=minval, maxval=maxval,
    translate=translate, bounds=bounds, bounds_mip=bounds_mip,
  ), ctx.obj["parallel"])


class Tuple2Or1(click.ParamType):
  """'8' or '8,8' → (8, 8)."""

  name = "tuple2or1"

  def convert(self, value, param, ctx):
    if isinstance(value, (tuple, list)):
      return tuple(int(v) for v in value)
    try:
      parts = [int(v) for v in str(value).split(",")]
    except ValueError:
      self.fail(f"{value!r} is not like 8 or 8,8", param, ctx)
    if len(parts) == 1:
      parts = parts * 2
    if len(parts) != 2:
      self.fail(f"{value!r} must have 1 or 2 components", param, ctx)
    return tuple(parts)


@image_contrast.command("clahe")
@click.argument("src")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--clip-limit", default=40.0, show_default=True)
@click.option("--tile-grid-size", "--tile-grid", "tile_grid",
              type=Tuple2Or1(), default=(8, 8), show_default=True,
              help="Size of the adaptive grid.")
@click.option("--shape", type=TUPLE3, default=(2048, 2048, 64), show_default=True)
@click.option("--fill-missing", is_flag=True)
@range_opts
@click.option("--bounds-mip", default=None, type=int,
              help="Mip the ranges are specified in [default: --mip].")
@click.pass_context
def contrast_clahe(ctx, src, dest, queue, mip, clip_limit, tile_grid, shape,
                   fill_missing, xrange, yrange, zrange, bounds_mip):
  from . import task_creation as tc

  bounds_mip = mip if bounds_mip is None else bounds_mip
  bounds = compute_cli_bounds(src, bounds_mip, xrange, yrange, zrange)
  enqueue(queue, tc.create_clahe_tasks(
    src, dest, mip=mip, clip_limit=clip_limit, tile_grid_size=tile_grid,
    shape=shape, fill_missing=fill_missing, bounds=bounds,
    bounds_mip=bounds_mip,
  ), ctx.obj["parallel"])


# -- image voxels ------------------------------------------------------------


@image.group("voxels")
def image_voxels():
  """Voxel statistics."""


@image_voxels.command("count")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(512, 512, 512), show_default=True)
@click.option("--fill-missing", is_flag=True)
@click.pass_context
def voxels_count(ctx, path, queue, mip, shape, fill_missing):
  """Census phase; run `voxels sum` afterwards."""
  from . import task_creation as tc

  enqueue(queue, tc.create_voxel_counting_tasks(
    path, mip=mip, shape=shape, fill_missing=fill_missing,
  ), ctx.obj["parallel"])


@image_voxels.command("sum")
@click.argument("path")
@click.option("--mip", default=0, show_default=True)
@click.option("--compress", default="gzip", show_default=True,
              help="Compression for the stored voxel_counts.im.")
@click.option("-o", "--output", default=None,
              help="Also write the IntMap file locally at this path.")
def voxels_sum(path, mip, compress, output):
  """Reduce census files into voxel_counts.im."""
  from . import task_creation as tc

  compress = _resolve_compress(compress, None) or None
  totals = tc.accumulate_voxel_counts(
    path, mip, compress=compress, additional_output=output,
  )
  click.echo(f"labels: {len(totals)}")


@image.command("roi")
@click.argument("path")
@click.option("--threshold", default=0.0, show_default=True)
@click.option("--dust", default=100, show_default=True,
              help="Suppress components smaller than this many voxels.")
@click.option("--suppress-faint", default=0, show_default=True,
              help="Voxels at or below this value become background.")
@click.option("--max-axial-len", default=512, show_default=True,
              help="Downsample in memory until XY fits this square.")
@click.option("--z-step", type=int, default=None,
              help="Evaluate ROIs per z-slab of this depth.")
@click.option("--progress", is_flag=True)
def image_roi(path, threshold, dust, suppress_faint, max_axial_len, z_step,
              progress):
  """Detect tissue regions of interest at the coarsest mip."""
  from . import task_creation as tc

  rois = tc.compute_rois(
    path, threshold=threshold, dust_threshold=dust,
    suppress_faint_voxels=suppress_faint, max_axial_length=max_axial_len,
    z_step=z_step, progress=progress,
  )
  for roi in rois:
    click.echo(str(roi))
  click.echo(f"{len(rois)} ROI detected. info file updated.")


@image.command("reorder")
@click.argument("src")
@click.argument("dest")
@click.argument("mapping_json", type=click.Path(exists=True), required=False)
@click.option("--mapping-file", type=click.Path(exists=True), default=None,
              help="JSON file of {dest_z: src_z} (reference flag form).")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--fill-missing", is_flag=True)
@click.option("--encoding", default=None)
@click.option("--encoding-level", type=int, default=None)
@click.option("--encoding-effort", type=int, default=None,
              help="(jpeg xl) accepted for parity; jxl is not shipped.")
@click.option("--compress", default="gzip", show_default=True)
@click.option("--delete-bg", is_flag=True)
@click.option("--bg-color", default=0, show_default=True)
@click.pass_context
def image_reorder(ctx, src, dest, mapping_json, mapping_file, queue, mip,
                  fill_missing, encoding, encoding_level, encoding_effort,
                  compress, delete_bg, bg_color):
  """Shuffle z-slices per a {dest_z: src_z} JSON mapping."""
  import json as json_mod

  from . import task_creation as tc

  path = mapping_json or mapping_file
  if not path:
    raise click.UsageError("provide MAPPING_JSON or --mapping-file")
  with open(path) as f:
    mapping = json_mod.load(f)
  enqueue(queue, tc.create_reordering_tasks(
    src, dest, mapping, mip=mip, fill_missing=fill_missing,
    encoding=encoding, encoding_level=encoding_level,
    compress=_resolve_compress(compress, encoding),
    delete_black_uploads=delete_bg, background_color=bg_color,
  ), ctx.obj["parallel"])


# -- image ccl ---------------------------------------------------------------


@image.group("ccl")
def image_ccl():
  """Whole-image connected components labeling (4-pass)."""


_CCL_OPTS = [
  click.option("--mip", default=0, show_default=True),
  click.option("--shape", type=TUPLE3, default=(448, 448, 448), show_default=True),
  click.option("--threshold-gte", type=float, default=None),
  click.option("--threshold-lte", type=float, default=None),
  click.option("--fill-missing", is_flag=True),
  click.option("--dust", "dust_threshold", default=0, show_default=True,
               help="Delete objects smaller than this many voxels "
                    "within a cutout."),
]


def ccl_opts(fn):
  for opt in reversed(_CCL_OPTS):
    fn = opt(fn)
  return fn


@image_ccl.command("faces")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@ccl_opts
@click.pass_context
def ccl_faces(ctx, path, queue, mip, shape, threshold_gte, threshold_lte,
              fill_missing, dust_threshold):
  from . import task_creation as tc

  enqueue(queue, tc.create_ccl_face_tasks(
    path, mip, shape, fill_missing, threshold_gte, threshold_lte,
    dust_threshold=dust_threshold,
  ), ctx.obj["parallel"])


@image_ccl.command("links")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@ccl_opts
@click.pass_context
def ccl_links(ctx, path, queue, mip, shape, threshold_gte, threshold_lte,
              fill_missing, dust_threshold):
  from . import task_creation as tc

  enqueue(queue, tc.create_ccl_equivalence_tasks(
    path, mip, shape, fill_missing, threshold_gte, threshold_lte,
    dust_threshold=dust_threshold,
  ), ctx.obj["parallel"])


@image_ccl.command("calc-labels")
@click.argument("path")
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(448, 448, 448),
              show_default=True,
              help="Accepted for parity; the stored equivalence files "
                   "already determine the task grid.")
def ccl_calc_labels(path, mip, shape):
  """Single-machine global union-find (pass 3)."""
  from . import task_creation as tc

  max_label = tc.create_relabeling(path, mip, shape)
  click.echo(f"max_label: {max_label}")


@image_ccl.command("relabel")
@click.argument("path")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@ccl_opts
@click.option("--encoding", default="compressed_segmentation", show_default=True)
@click.option("--chunk-size", type=TUPLE3, default=None,
              help="Chunk size of the destination layer.")
@click.pass_context
def ccl_relabel(ctx, path, dest, queue, mip, shape, threshold_gte,
                threshold_lte, fill_missing, dust_threshold, encoding,
                chunk_size):
  from . import task_creation as tc

  enqueue(queue, tc.create_ccl_relabel_tasks(
    path, dest, mip, shape, fill_missing, threshold_gte, threshold_lte,
    encoding=encoding, chunk_size=chunk_size, dust_threshold=dust_threshold,
  ), ctx.obj["parallel"])


@image_ccl.command("clean")
@click.argument("path")
@click.option("--mip", default=0, show_default=True)
def ccl_clean(path, mip):
  from . import task_creation as tc

  tc.clean_ccl_files(path, mip)


@image_ccl.command("auto")
@click.argument("path")
@click.argument("dest")
@click.option("--queue", "-q", default=None,
              help="Lease-based queue to drain each pass through "
                   "(local execution if omitted).")
@ccl_opts
@click.option("--encoding", default="compressed_segmentation", show_default=True)
@click.option("--chunk-size", type=TUPLE3, default=None,
              help="Chunk size of the destination layer.")
@click.option("--clean/--no-clean", default=True, show_default=True,
              help="Delete scratch files afterwards.")
@click.pass_context
def ccl_auto_cmd(ctx, path, dest, queue, mip, shape, threshold_gte,
                 threshold_lte, fill_missing, dust_threshold, encoding,
                 chunk_size, clean):
  """All four passes locally (reference cli.py:799-852)."""
  from . import task_creation as tc
  from .queues import LocalTaskQueue, TaskQueue

  tq = (
    TaskQueue(queue) if queue
    else LocalTaskQueue(parallel=ctx.obj["parallel"], progress=False)
  )
  max_label = tc.ccl_auto(
    path, dest, mip=mip, shape=shape, queue=tq,
    threshold_gte=threshold_gte, threshold_lte=threshold_lte,
    fill_missing=fill_missing, encoding=encoding, chunk_size=chunk_size,
    clean=clean, dust_threshold=dust_threshold,
  )
  click.echo(f"components: {max_label}")


# ---------------------------------------------------------------------------
# mesh


@main.group()
def mesh():
  """Mesh forging and management."""


@mesh.command("forge")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(448, 448, 448), show_default=True)
@click.option("--simplify/--skip-simplify", "simplify", default=True,
              show_default=True, help="Enable mesh simplification.")
@click.option("--simplify-factor", default=100, show_default=True)
@click.option("--max-error", default=40, show_default=True)
@click.option("--mesh-dir", "--dir", "mesh_dir", default=None,
              help="Write meshes into this directory instead of the one "
                   "in the info file.")
@click.option("--dust-threshold", "--dust", "dust_threshold", type=int,
              default=None,
              help="Skip objects smaller than this many voxels.")
@click.option("--dust-global/--dust-local", default=False, show_default=True,
              help="Dust by global voxel counts (requires a voxels census).")
@click.option("--fill-missing", is_flag=True)
@click.option("--fill-holes", type=int, default=0, show_default=True,
              help="0: off 1: fill cavities 2: also fix borders "
                   "3: also morphological closing.")
@click.option("--compress", default="gzip", show_default=True,
              help="Fragment file compression: none, gzip, br.")
@click.option("--sharded", is_flag=True)
@click.option("--spatial-index/--no-spatial-index", default=True, show_default=True)
@click.option("--closed-edge/--open-edge", "closed_edge", default=True,
              show_default=True,
              help="Close meshes against the dataset boundary.")
@click.option("--labels", "--obj-ids", "obj_ids", default=None,
              help="comma-separated: mesh only these labels")
@click.option("--exclude-labels", "--exclude-obj-ids", "exclude_obj_ids",
              default=None,
              help="comma-separated: never mesh these labels")
@click.option("--mesher", default="cubes", show_default=True,
              type=click.Choice(["cubes", "tetrahedra"]))
@click.option("--simplify-parallel", default=1, show_default=True,
              help="threads for per-label simplification inside each task")
@click.pass_context
def mesh_forge(ctx, path, queue, mip, shape, simplify, simplify_factor,
               max_error, mesh_dir, dust_threshold, dust_global,
               fill_missing, fill_holes, compress, sharded, spatial_index,
               closed_edge, obj_ids, exclude_obj_ids, mesher,
               simplify_parallel):
  from . import task_creation as tc

  compress = _resolve_compress(compress, None) or None
  enqueue(queue, tc.create_meshing_tasks(
    path, mip=mip, shape=shape,
    simplification=simplify,
    simplification_factor=simplify_factor,
    max_simplification_error=max_error,
    mesh_dir=mesh_dir, dust_threshold=dust_threshold,
    dust_global=dust_global,
    fill_missing=fill_missing, fill_holes=fill_holes, sharded=sharded,
    spatial_index=spatial_index,
    closed_dataset_edges=closed_edge,
    object_ids=parse_id_list(obj_ids),
    exclude_object_ids=parse_id_list(exclude_obj_ids),
    mesher=mesher, parallel=simplify_parallel, compress=compress,
  ), ctx.obj["parallel"])


@mesh.command("merge")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--magnitude", default=2, show_default=True)
@click.option("--mesh-dir", "--dir", "mesh_dir", default=None)
@click.option("--nlod", default=0, show_default=True,
              help="(multires) Extra levels of detail; 0 = legacy "
                   "manifests.")
@click.option("--vqb", default=16, show_default=True,
              help="(multires) Vertex quantization bits: 10 or 16.")
@click.option("--min-chunk-size", type=TUPLE3, default=(256, 256, 256),
              show_default=True,
              help="(multires) Minimum finest-LOD fragment cell (voxels).")
@click.pass_context
def mesh_merge(ctx, path, queue, magnitude, mesh_dir, nlod, vqb,
               min_chunk_size):
  """Write legacy manifests — or unsharded multires with --nlod > 0
  (stage 2, reference cli.py:1073-1103)."""
  from . import task_creation as tc

  if nlod > 0:
    tasks = tc.create_unsharded_multires_mesh_tasks(
      path, magnitude=magnitude, mesh_dir=mesh_dir, num_lods=nlod + 1,
      vertex_quantization_bits=vqb, min_chunk_size=min_chunk_size,
    )
  else:
    tasks = tc.create_mesh_manifest_tasks(
      path, magnitude=magnitude, mesh_dir=mesh_dir)
  enqueue(queue, tasks, ctx.obj["parallel"])


@mesh.command("merge-sharded")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mesh-dir", "--dir", "mesh_dir", default=None)
@click.option("--num-lods", "num_lods", default=None, type=int,
              help="Total levels of detail [default: 2].")
@click.option("--nlod", default=None, type=int,
              help="Reference-style: EXTRA levels of detail "
                   "(total = nlod + 1).")
@click.option("--vqb", default=16, show_default=True,
              help="Vertex quantization bits: 10 or 16.")
@click.option("--min-chunk-size", type=TUPLE3, default=(256, 256, 256),
              show_default=True,
              help="Minimum finest-LOD fragment cell (voxels).")
@click.option("--compress-level", default=7, show_default=True,
              help="Draco compression level (recorded; this build's "
                   "encoder is fixed sequential-method).")
@click.option("--shard-index-bytes", default=2**13, show_default=True)
@click.option("--minishard-index-bytes", default=2**15, show_default=True)
@click.option("--minishard-index-encoding", default="gzip", show_default=True)
@click.option("--min-shards", default=1, show_default=True)
@click.option("--max-labels-per-shard", default=1000, show_default=True)
@click.option("--spatial-index-db", default=None,
              help="Query labels from this sqlite db (mesh spatial-index "
                   "db) instead of listing .spatial files.")
@click.pass_context
def mesh_merge_sharded(ctx, path, queue, mesh_dir, num_lods, nlod, vqb,
                       min_chunk_size, compress_level, shard_index_bytes,
                       minishard_index_bytes, minishard_index_encoding,
                       min_shards, max_labels_per_shard, spatial_index_db):
  """Sharded multires merge (reference cli.py:1105-1155)."""
  from . import task_creation as tc

  if num_lods is None:
    num_lods = (nlod + 1) if nlod is not None else 2
  enqueue(queue, tc.create_sharded_multires_mesh_tasks(
    path, mesh_dir=mesh_dir, num_lods=num_lods,
    vertex_quantization_bits=vqb, min_chunk_size=min_chunk_size,
    draco_compression_level=compress_level,
    shard_index_bytes=shard_index_bytes,
    minishard_index_bytes=minishard_index_bytes,
    minishard_index_encoding=minishard_index_encoding,
    min_shards=min_shards, max_labels_per_shard=max_labels_per_shard,
    spatial_index_db=spatial_index_db,
  ), ctx.obj["parallel"])


@mesh.group("spatial-index")
def mesh_spatial_index():
  """Mesh spatial-index maintenance."""


@mesh_spatial_index.command("create")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(448, 448, 448), show_default=True)
@click.option("--mesh-dir", default=None)
@click.option("--fill-missing", is_flag=True)
@click.pass_context
def mesh_spatial_index_create(ctx, path, queue, mip, shape, mesh_dir,
                              fill_missing):
  from . import task_creation as tc
  from .tasks.mesh import mesh_dir_for
  from .volume import Volume

  mdir = mesh_dir_for(Volume(path), mesh_dir)
  enqueue(queue, tc.create_spatial_index_tasks(
    path, mdir, mip=mip, shape=shape, fill_missing=fill_missing,
  ), ctx.obj["parallel"])


@mesh_spatial_index.command("db")
@click.argument("path")
@click.argument("db_path", type=click.Path())
@click.option("--mesh-dir", default=None)
@click.option("--progress", is_flag=True)
@click.option("--allow-missing", is_flag=True,
              help="Tolerate missing index files.")
def mesh_spatial_index_db(path, db_path, mesh_dir, progress, allow_missing):
  """Materialize the spatial index into a sqlite database."""
  from .spatial_index import SpatialIndex
  from .tasks.mesh import mesh_dir_for
  from .volume import Volume

  vol = Volume(path)
  mdir = mesh_dir_for(vol, mesh_dir)
  n = SpatialIndex(vol.cf, mdir).to_sqlite(
    db_path, progress=progress, allow_missing=allow_missing,
  )
  click.echo(f"wrote {n} rows to {db_path}")


@mesh.command("clean")
@click.argument("path")
@click.option("--mesh-dir", default=None)
def mesh_clean(path, mesh_dir):
  """Delete stage-1 intermediates (fragment files, .frags containers,
  .spatial cells), keeping manifests and multires outputs."""
  from .tasks.mesh import mesh_dir_for
  from .volume import Volume

  vol = Volume(path)
  mdir = mesh_dir_for(vol, mesh_dir)
  doomed = [
    k for k in vol.cf.list(f"{mdir}/")
    if k.endswith(".frags") or k.endswith(".spatial")
    or len(k.split("/")[-1].split(":")) == 3  # label:0:bbox fragments
  ]
  vol.cf.delete(doomed)
  click.echo(f"deleted {len(doomed)} intermediate files")


@mesh.command("xfer")
@click.argument("src")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@click.option("--mesh-dir", "--dir", "mesh_dir", default=None)
@click.option("--magnitude", default=1, show_default=True)
@click.option("--sharded", is_flag=True,
              help="Convert unsharded meshes to sharded multires at the "
                   "destination.")
@click.option("--nlod", default=0, show_default=True,
              help="(--sharded) Extra levels of detail.")
@click.option("--mip", type=int, default=None,
              help="Accepted for parity; the multires info records the "
                   "source mip.")
@click.pass_context
def mesh_xfer(ctx, src, dest, queue, mesh_dir, magnitude, sharded, nlod, mip):
  from . import task_creation as tc

  if sharded:
    tasks = tc.create_sharded_multires_mesh_from_unsharded_tasks(
      src, dest_cloudpath=dest, mesh_dir=mesh_dir, num_lods=nlod + 1,
    )
  else:
    tasks = tc.create_mesh_transfer_tasks(
      src, dest, mesh_dir=mesh_dir, magnitude=magnitude)
  enqueue(queue, tasks, ctx.obj["parallel"])


@mesh.command("rm")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mesh-dir", "--dir", "mesh_dir", default=None)
@click.option("--magnitude", default=1, show_default=True)
@click.pass_context
def mesh_rm(ctx, path, queue, mesh_dir, magnitude):
  from . import task_creation as tc

  enqueue(queue, tc.create_mesh_deletion_tasks(
    path, magnitude=magnitude, mesh_dir=mesh_dir), ctx.obj["parallel"])


# ---------------------------------------------------------------------------
# skeleton


@main.group()
def skeleton():
  """Skeleton forging and management."""


@skeleton.command("forge")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(512, 512, 512), show_default=True)
@click.option("--scale", default=4.0, show_default=True, help="TEASAR scale")
@click.option("--const", default=500.0, show_default=True, help="TEASAR const (nm)")
@click.option("--max-paths", type=float, default=None,
              help="Abort an object after tracing this many paths.")
@click.option("--dust-threshold", default=1000, show_default=True)
@click.option("--dust-global/--dust-local", default=False, show_default=True,
              help="dust by global voxel counts (requires a voxels census)")
@click.option("--fill-missing", is_flag=True)
@click.option("--fill-holes", type=int, default=0, show_default=True,
              help="0: off 1: fill cavities 2: +close box sides "
                   "3: +morphological closing")
@click.option("--sharded", is_flag=True)
@click.option("--skel-dir", default=None)
@click.option("--spatial-index/--skip-spatial-index", default=True,
              show_default=True)
@click.option("--fix-borders/--no-fix-borders", default=True, show_default=True)
@click.option("--fix-branching/--no-fix-branching", default=True,
              show_default=True,
              help="regrow the path field from the whole tree before each "
                   "branch so junctions attach on-center")
@click.option("--fix-avocados", is_flag=True,
              help="absorb nucleus labels engulfed by a soma and "
                   "re-EDT the solid cell body")
@click.option("--fix-autapses", is_flag=True,
              help="(graphene) constrain TEASAR to the chunk graph so "
                   "self-contacts are severed")
@click.option("--soma-detect", default=1100.0, show_default=True,
              help="soma candidate EDT threshold (physical units)")
@click.option("--soma-accept", default=3500.0, show_default=True,
              help="soma acceptance EDT threshold (physical units)")
@click.option("--soma-scale", default=2.0, show_default=True)
@click.option("--soma-const", default=300.0, show_default=True)
@click.option("--labels", default=None,
              help="comma-separated: skeletonize only these labels")
@click.option("--cross-section", type=int, default=0, show_default=True,
              help="Compute per-vertex cross sectional area; the value is "
                   "the normal-vector smoothing window (0 = off).")
@click.option("--cross-section-label-repair-sec", type=int, default=-1,
              show_default=True,
              help="Per-label time budget for contact repair: 0 off, "
                   "-1 unlimited.")
@click.option("--output", "-o", default=None,
              help="Write stage-1 fragments to this path instead.")
@click.option("--timestamp", type=int, default=None,
              help="(graphene) proofreading state at this UNIX time.")
@click.option("--root-ids", default=None,
              help="(graphene) materialized root-id layer to read instead "
                   "of querying the server.")
@click.option("--progress", is_flag=True,
              help="Accepted for parity; local queues already show a "
                   "progress bar.")
@click.pass_context
def skeleton_forge(ctx, path, queue, mip, shape, scale, const, max_paths,
                   dust_threshold, dust_global, fill_missing, fill_holes,
                   sharded, skel_dir, spatial_index, fix_borders,
                   fix_branching, fix_avocados, fix_autapses, soma_detect,
                   soma_accept, soma_scale, soma_const, labels,
                   cross_section, cross_section_label_repair_sec, output,
                   timestamp, root_ids, progress):
  from . import task_creation as tc

  enqueue(queue, tc.create_skeletonizing_tasks(
    path, mip=mip, shape=shape,
    teasar_params={
      "scale": scale, "const": const,
      "soma_detection_threshold": soma_detect,
      "soma_acceptance_threshold": soma_accept,
      "soma_invalidation_scale": soma_scale,
      "soma_invalidation_const": soma_const,
      "max_paths": max_paths,
    },
    dust_threshold=dust_threshold, dust_global=dust_global,
    fill_missing=fill_missing, fill_holes=fill_holes,
    sharded=sharded, skel_dir=skel_dir, spatial_index=spatial_index,
    fix_borders=fix_borders,
    fix_branching=fix_branching, fix_avocados=fix_avocados,
    fix_autapses=fix_autapses,
    object_ids=parse_id_list(labels),
    cross_sectional_area=(cross_section > 0),
    csa_smoothing_window=max(int(cross_section), 1),
    csa_repair_sec_per_label=cross_section_label_repair_sec,
    frag_path=output, timestamp=timestamp, root_ids_cloudpath=root_ids,
  ), ctx.obj["parallel"])


@skeleton.command("merge")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--magnitude", default=1, show_default=True)
@click.option("--skel-dir", default=None)
@click.option("--dust-threshold", "--min-cable-length", "dust_threshold",
              default=4000.0, show_default=True,
              help="Skip objects shorter than this physical path length.")
@click.option("--tick-threshold", default=6000.0, show_default=True)
@click.option("--delete-fragments", is_flag=True)
@click.option("--max-cable-length", type=float, default=None,
              help="skip postprocessing (not upload) for merged skeletons "
                   "longer than this (nm) — bounds the cost of merge-error "
                   "monsters")
@click.pass_context
def skeleton_merge(ctx, path, queue, magnitude, skel_dir, dust_threshold,
                   tick_threshold, delete_fragments, max_cable_length):
  from . import task_creation as tc

  enqueue(queue, tc.create_unsharded_skeleton_merge_tasks(
    path, magnitude=magnitude, skel_dir=skel_dir,
    dust_threshold=dust_threshold, tick_threshold=tick_threshold,
    delete_fragments=delete_fragments, max_cable_length=max_cable_length,
  ), ctx.obj["parallel"])


@skeleton.command("merge-sharded")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--skel-dir", default=None)
@click.option("--dust-threshold", "--min-cable-length", "dust_threshold",
              default=4000.0, show_default=True,
              help="Skip objects shorter than this physical path length.")
@click.option("--tick-threshold", default=6000.0, show_default=True)
@click.option("--max-cable-length", type=float, default=None,
              help="skip postprocessing for merged skeletons longer than "
                   "this (nm)")
@click.option("--shard-index-bytes", default=2**13, show_default=True)
@click.option("--minishard-index-bytes", default=2**15, show_default=True)
@click.option("--minishard-index-encoding", default="gzip", show_default=True)
@click.option("--data-encoding", default="gzip", show_default=True,
              help="Shard data compression: gzip or raw.")
@click.option("--min-shards", default=1, show_default=True)
@click.option("--max-labels-per-shard", default=2000, show_default=True)
@click.option("--spatial-index-db", default=None,
              help="Query labels from this sqlite db instead of listing "
                   ".spatial files.")
@click.pass_context
def skeleton_merge_sharded(ctx, path, queue, skel_dir, dust_threshold,
                           tick_threshold, max_cable_length,
                           shard_index_bytes, minishard_index_bytes,
                           minishard_index_encoding, data_encoding,
                           min_shards, max_labels_per_shard,
                           spatial_index_db):
  from . import task_creation as tc

  enqueue(queue, tc.create_sharded_skeleton_merge_tasks(
    path, skel_dir=skel_dir, dust_threshold=dust_threshold,
    tick_threshold=tick_threshold, max_cable_length=max_cable_length,
    shard_index_bytes=shard_index_bytes,
    minishard_index_bytes=minishard_index_bytes,
    minishard_index_encoding=minishard_index_encoding,
    data_encoding=data_encoding, min_shards=min_shards,
    max_labels_per_shard=max_labels_per_shard,
    spatial_index_db=spatial_index_db,
  ), ctx.obj["parallel"])


@skeleton.command("convert")
@click.argument("path")
@click.argument("out_dir", type=click.Path())
@click.option("--skel-dir", default=None)
@click.option("--labels", default=None, help="comma-separated label ids")
def skeleton_convert(path, out_dir, skel_dir, labels):
  """Export finished skeletons as SWC files
  (reference `igneous skeleton convert`)."""
  import os

  from .skeleton_io import Skeleton, to_swc
  from .tasks.skeleton import skel_dir_for
  from .volume import Volume

  vol = Volume(path)
  sdir = skel_dir_for(vol, skel_dir)
  attrs = (vol.cf.get_json(f"{sdir}/info") or {}).get("vertex_attributes")
  os.makedirs(out_dir, exist_ok=True)
  ids = parse_id_list(labels)
  wanted = set(ids) if ids else None
  n = 0
  for key in vol.cf.list(f"{sdir}/"):
    name = key.split("/")[-1]
    if not name.isdigit():
      continue
    label = int(name)
    if wanted is not None and label not in wanted:
      continue
    s = Skeleton.from_precomputed(vol.cf.get(key), vertex_attributes=attrs)
    with open(os.path.join(out_dir, f"{label}.swc"), "w") as f:
      f.write(to_swc(s, label=label))
    n += 1
  click.echo(f"wrote {n} swc files to {out_dir}")


@skeleton.group("spatial-index")
def skeleton_spatial_index():
  """Skeleton spatial-index maintenance."""


@skeleton_spatial_index.command("create")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--mip", default=0, show_default=True)
@click.option("--shape", type=TUPLE3, default=(512, 512, 512), show_default=True)
@click.option("--skel-dir", default=None)
@click.option("--fill-missing", is_flag=True)
@click.pass_context
def skeleton_spatial_index_create(ctx, path, queue, mip, shape, skel_dir,
                                  fill_missing):
  """Rebuild the skeleton spatial index."""
  from . import task_creation as tc
  from .tasks.skeleton import skel_dir_for
  from .volume import Volume

  sdir = skel_dir_for(Volume(path), skel_dir)
  enqueue(queue, tc.create_spatial_index_tasks(
    path, sdir, mip=mip, shape=shape, fill_missing=fill_missing,
  ), ctx.obj["parallel"])


@skeleton_spatial_index.command("db")
@click.argument("path")
@click.argument("db_path", type=click.Path())
@click.option("--skel-dir", default=None)
@click.option("--progress", is_flag=True)
@click.option("--allow-missing", is_flag=True,
              help="Tolerate missing index files.")
def skeleton_spatial_index_db(path, db_path, skel_dir, progress,
                              allow_missing):
  """Materialize the skeleton spatial index into a sqlite database
  (reference `igneous skeleton spatial-index db`, cli.py:1565-1586)."""
  from .spatial_index import SpatialIndex
  from .tasks.skeleton import skel_dir_for
  from .volume import Volume

  vol = Volume(path)
  sdir = skel_dir_for(vol, skel_dir)
  n = SpatialIndex(vol.cf, sdir).to_sqlite(
    db_path, progress=progress, allow_missing=allow_missing,
  )
  click.echo(f"wrote {n} rows to {db_path}")


@skeleton.command("clean")
@click.argument("path")
@click.option("--skel-dir", default=None)
def skeleton_clean(path, skel_dir):
  """Delete stage-1 intermediates (.sk fragments, .frags containers,
  .spatial cells), keeping the merged skeletons."""
  from .tasks.skeleton import skel_dir_for
  from .volume import Volume

  vol = Volume(path)
  sdir = skel_dir_for(vol, skel_dir)
  doomed = [
    k for k in vol.cf.list(f"{sdir}/")
    if k.endswith(".sk") or k.endswith(".frags") or k.endswith(".spatial")
  ]
  vol.cf.delete(doomed)
  click.echo(f"deleted {len(doomed)} intermediate files")


@skeleton.command("xfer")
@click.argument("src")
@click.argument("dest")
@click.option("--queue", "-q", default=None)
@click.option("--skel-dir", "--dir", "skel_dir", default=None)
@click.option("--magnitude", default=1, show_default=True)
@click.option("--sharded", is_flag=True,
              help="Convert unsharded skeletons to sharded format at the "
                   "destination.")
@click.pass_context
def skeleton_xfer(ctx, src, dest, queue, skel_dir, magnitude, sharded):
  from . import task_creation as tc

  if sharded:
    enqueue(queue, tc.create_sharded_from_unsharded_skeleton_merge_tasks(
      src, dest_cloudpath=dest, skel_dir=skel_dir,
    ), ctx.obj["parallel"])
    return
  enqueue(queue, tc.create_skeleton_transfer_tasks(
    src, dest, skel_dir=skel_dir, magnitude=magnitude), ctx.obj["parallel"])


@skeleton.command("rm")
@click.argument("path")
@click.option("--queue", "-q", default=None)
@click.option("--skel-dir", "--dir", "skel_dir", default=None)
@click.option("--magnitude", default=1, show_default=True)
@click.pass_context
def skeleton_rm(ctx, path, queue, skel_dir, magnitude):
  from . import task_creation as tc

  enqueue(queue, tc.create_skeleton_deletion_tasks(
    path, magnitude=magnitude, skel_dir=skel_dir), ctx.obj["parallel"])


# ---------------------------------------------------------------------------
# execute / queue / design


@main.command("execute")
@click.argument("queue_spec", required=False)
@click.option("--aws-region", default=None,
              help="AWS region of the SQS queue [default: $SQS_REGION_NAME].")
@click.option("--lease-sec", default=None, type=int,
              help="Visibility timeout [default: $LEASE_SECONDS or 600].")
@click.option("--tally/--no-tally", default=True, show_default=True,
              help="Tally completed fq:// tasks.")
@click.option("-n", "--num-tasks", "num_tasks", default=None, type=int,
              help="Stop after N tasks.")
@click.option("-x", "--exit-on-empty", is_flag=True)
@click.option("--min-sec", default=-1.0, show_default=True,
              help="Keep polling at least this long (<0: forever).")
@click.option("-q", "--quiet", is_flag=True,
              help="Suppress per-task status messages.")
@click.option("--time", "timing", is_flag=True,
              help="Log per-task wall time + stage breakdown as JSON lines.")
@click.option("--batch", "batch_size", default=1, show_default=True, type=int,
              help="Lease up to K compatible tasks per round and run their "
                   "device stage as ONE mesh dispatch (SURVEY §5.8). Each "
                   "lease still completes/recycles independently.")
@click.option("--max-deliveries", default=None, type=int,
              help="Quarantine a task in the queue's dlq/ after this many "
                   "deliveries instead of recycling it forever "
                   "[default: infinite retry].")
@click.option("--task-deadline", "task_deadline", default=None, type=float,
              help="Per-task wall-clock deadline in seconds; an overrun "
                   "counts as a failed delivery (recorded, then DLQ once "
                   "--max-deliveries is exhausted).")
@click.option("--heartbeat-sec", "heartbeat_sec", default=None, type=float,
              help="Renew held leases at this interval so long tasks "
                   "outlive a short --lease-sec without double execution "
                   "[default: $IGNEOUS_HEARTBEAT_SEC or lease/3; 0 "
                   "disables].")
@click.option("--drain-sentinel", default=None,
              help="Preemption watcher: drain gracefully (finish the "
                   "in-flight task, release the rest, exit 83) when this "
                   "file appears [default: $IGNEOUS_PREEMPT_SENTINEL; "
                   "SIGTERM/SIGINT and $IGNEOUS_PREEMPT_URL drain too].")
@click.option("--pipeline/--no-pipeline", "pipeline", default=None,
              help="Staged execution pipeline (ISSUE 3): thread each "
                   "task's chunk encode/uploads and prefetch batched "
                   "rounds' cutouts; byte-identical output, joined before "
                   "every lease delete [default: $IGNEOUS_PIPELINE].")
@click.option("--metrics-port", "metrics_port", default=None, type=int,
              help="Serve Prometheus text metrics on this port "
                   "(/metrics; 0 picks a free port) "
                   "[default: $IGNEOUS_METRICS_PORT; unset disables].")
@click.option("--journal", "journal_path", default=None,
              help="Where to append fleet journal segments (span batches "
                   "merged by `igneous fleet`) [default: $IGNEOUS_JOURNAL, "
                   "else <queue>/journal/ for fq:// queues].")
@click.pass_context
def execute(ctx, queue_spec, aws_region, lease_sec, tally, num_tasks,
            exit_on_empty, min_sec, quiet, timing, batch_size,
            max_deliveries, task_deadline, heartbeat_sec, drain_sentinel,
            pipeline, metrics_port, journal_path):
  """Worker poll loop: lease → run → delete
  (reference cli.py:888-964 semantics). QUEUE_SPEC falls back to the
  QUEUE_URL env var and --lease-sec to LEASE_SECONDS, so container CMDs
  stay declarative (secrets.py).

  Lifecycle: SIGTERM/SIGINT (or the preemption watcher) request a
  graceful drain — the in-flight task finishes, still-leased batch
  members are released, a final counters JSON line flushes, and the
  worker exits 83 so schedulers can tell "preempted" from "failed"."""
  import sys as sys_mod

  from . import lifecycle, secrets

  queue_spec = queue_spec or secrets.queue_url()
  if not queue_spec:
    raise click.UsageError("provide QUEUE_SPEC or set $QUEUE_URL")
  if lease_sec is None:
    lease_sec = secrets.lease_seconds()
  if aws_region:
    os.environ["SQS_REGION_NAME"] = aws_region
  if pipeline is not None:
    # env (not a param thread) so spawned workers inherit the choice
    knobs.set_env("IGNEOUS_PIPELINE", "1" if pipeline else "off")
  if journal_path is not None:
    knobs.set_env("IGNEOUS_JOURNAL", journal_path)  # children inherit too
  if metrics_port is not None:
    # multi-process workers each need their own port: 0 lets the OS pick
    knobs.set_env(
      "IGNEOUS_METRICS_PORT", 0 if ctx.obj["parallel"] > 1 else metrics_port
    )
  parallel = ctx.obj["parallel"]
  if parallel > 1:
    import multiprocessing as mp
    import time as time_mod

    # divide cores among workers for native kernel threading (same
    # oversubscription hygiene as the reference's cv2.setNumThreads(0))
    knobs.setdefault_env(
      "IGNEOUS_POOL_THREADS", max(1, (os.cpu_count() or 1) // parallel)
    )
    ctx_mp = mp.get_context("spawn")
    procs = [
      ctx_mp.Process(
        target=_execute_worker,
        args=(queue_spec, lease_sec, num_tasks, exit_on_empty, min_sec,
              timing, quiet, tally, batch_size, max_deliveries,
              task_deadline, heartbeat_sec, drain_sentinel),
      )
      for _ in range(parallel)
    ]
    for p in procs:
      p.start()
    # forward a drain request to every child (k8s signals pid 1 only);
    # each child runs its own graceful drain and exits 83
    flag = lifecycle.StopFlag()
    restore = lifecycle.install_signal_handlers(flag)
    try:
      while any(p.is_alive() for p in procs):
        if flag.is_set():
          for p in procs:
            if p.is_alive():
              p.terminate()  # SIGTERM → the child's own drain path
          break
        time_mod.sleep(0.2)
      for p in procs:
        p.join()
    finally:
      restore()
    failed = [
      p.exitcode for p in procs
      if p.exitcode not in (0, lifecycle.EXIT_PREEMPTED)
    ]
    if failed:
      raise click.ClickException(f"worker exit codes {failed}")
    if flag.is_set() or any(
      p.exitcode == lifecycle.EXIT_PREEMPTED for p in procs
    ):
      sys_mod.exit(lifecycle.EXIT_PREEMPTED)
    return
  _execute_worker(queue_spec, lease_sec, num_tasks, exit_on_empty, min_sec,
                  timing, quiet, tally, batch_size, max_deliveries,
                  task_deadline, heartbeat_sec, drain_sentinel)


def _execute_worker(queue_spec, lease_sec, num_tasks, exit_on_empty, min_sec,
                    timing=False, quiet=False, tally=True, batch_size=1,
                    max_deliveries=None, task_deadline=None,
                    heartbeat_sec=None, drain_sentinel=None):
  import sys as sys_mod
  import time

  import igneous_tpu.tasks  # noqa: F401  register all task classes
  from . import lifecycle, telemetry
  from .observability import journal as journal_mod
  from .observability import prom
  from .observability.device import require_worker_backend
  from .queues import TaskQueue

  require_worker_backend()
  flag = lifecycle.StopFlag()
  restore = lifecycle.install_signal_handlers(flag)
  watcher = lifecycle.PreemptionWatcher(flag, sentinel=drain_sentinel)
  watcher.start()

  tq = TaskQueue(queue_spec, max_deliveries=max_deliveries)

  # observability (ISSUE 5): journal segments + /metrics endpoint + an
  # atexit last-will so even a crashing worker leaves its final
  # counters line and span batch behind
  jpath = journal_mod.journal_path_for(tq, queue_spec)
  if jpath:
    journal_mod.set_active(journal_mod.Journal(jpath))
    # device telemetry plane (ISSUE 7): the utilization ledger rides
    # every journal flush and the profiler trigger poll rides the
    # between-tasks maybe_flush cadence
    from .observability import device as device_mod

    device_mod.install()
  journal_mod.install_last_will({"queue": queue_spec})
  # worker-liveness gauge (ISSUE 6): present while this process answers
  # scrapes; goes stale in Prometheus the moment the worker dies — the
  # health plane's per-worker "up" signal
  telemetry.gauge_set("worker.up", 1.0)
  bound_port = prom.start_http_server()
  if bound_port is not None and not quiet:
    click.echo(f"metrics: http://0.0.0.0:{bound_port}/metrics")

  start = time.time()

  def drained() -> bool:
    # "empty" only means nothing is leasable right now; with a delivery
    # budget the worker must outlive failed leases so every task ends
    # COMPLETED or DEAD-LETTERED, not stranded mid-recycle (the poison
    # task would otherwise need a second worker run to reach the DLQ)
    if max_deliveries is None:
      return True
    try:
      return tq.enqueued == 0
    except (NotImplementedError, AttributeError):
      return True

  def stop_fn(executed: int, empty: bool) -> bool:
    if num_tasks is not None and 0 <= num_tasks <= executed:
      return True
    if min_sec == 0 and (executed >= 1 or empty):
      # reference special value: run at most a single task (cli.py:892)
      return True
    if empty and exit_on_empty and drained():
      return True
    if empty and 0 <= min_sec <= (time.time() - start) and drained():
      return True
    return False

  try:
    if batch_size > 1:
      from .parallel.lease_batcher import poll_batched

      # honor --num-tasks / the min_sec==0 single-task special exactly:
      # the lease loop must not lease past the remaining budget
      task_budget = None
      if num_tasks is not None and num_tasks >= 0:
        task_budget = num_tasks
      if min_sec == 0:
        task_budget = 1 if task_budget is None else min(task_budget, 1)
      executed, stats = poll_batched(
        tq, batch_size=batch_size, lease_seconds=lease_sec,
        verbose=not quiet, stop_fn=stop_fn, task_budget=task_budget,
        timing=timing,  # per-ROUND JSON lines (tasks share dispatches)
        task_deadline_seconds=task_deadline,
        heartbeat_seconds=heartbeat_sec, drain_flag=flag,
      )
      if not quiet:
        click.echo(
          f"executed {executed} tasks "
          f"({stats['batched']} batched in "
          f"{sum(stats['dispatches'].values())} dispatches, "
          f"{stats['solo']} solo, {stats['failed']} failed, "
          f"{stats['released']} released)"
        )
    else:
      before_fn = after_fn = None
      if timing:
        from .telemetry import timed_poll_hooks

        before_fn, after_fn = timed_poll_hooks()

      executed = tq.poll(
        lease_seconds=lease_sec, verbose=not quiet, stop_fn=stop_fn,
        before_fn=before_fn, after_fn=after_fn, tally=tally,
        task_deadline_seconds=task_deadline,
        heartbeat_seconds=heartbeat_sec, drain_flag=flag,
      )
      if not quiet:
        click.echo(f"executed {executed} tasks")
  except BaseException:
    # crashing worker (satellite): the final counters line + journal
    # batch land NOW, with the real event name — not at teardown
    journal_mod.fire_last_will("crash", {"queue": queue_spec})
    raise
  finally:
    # write-envelope durability (ISSUE 16): buffered manifest records
    # land with the same urgency as the journal's last-will batch — an
    # audit must see digests for everything this worker uploaded
    from . import integrity as integrity_mod

    integrity_mod.flush_all(swallow=True)
    watcher.stop()
    restore()
  if flag.is_set():
    # last will: the counters line survives the pod for kubectl logs,
    # and the journal's final segment survives it in the bucket
    journal_mod.fire_last_will(
      "drain", {"reason": flag.reason, "executed": executed}
    )
    sys_mod.exit(lifecycle.EXIT_PREEMPTED)
  # clean exit: flush the journal without the counters line (stdout
  # contract unchanged for healthy drains)
  journal_mod.disarm_last_will()


# ---------------------------------------------------------------------------
# integrity audit (ISSUE 16)


def _audit_round(path, mips, report_dir, queue_spec, parallel,
                 check_digest, require_present, lease_sec, drain_sec):
  """One audit pass: fan the grid out, drain it, collect findings."""
  from . import integrity
  from .task_creation.audit import create_integrity_audit_tasks, load_findings

  integrity.flush_all()
  for mip in mips:
    tasks = create_integrity_audit_tasks(
      path, mip=mip, report_dir=report_dir,
      check_digest=check_digest, require_present=require_present,
    )
    enqueue(queue_spec, tasks, parallel)
  if queue_spec is not None:
    _drain_inline(queue_spec, lease_sec, drain_sec)
  return load_findings(report_dir)


def _drain_inline(queue_spec, lease_sec, deadline_sec):
  """Lease→execute→delete the queue to empty from this process (the
  audit CLI doubles as a worker so `--queue fq://…` needs no separate
  fleet; external workers leasing the same ranges just finish sooner)."""
  import time as time_mod

  from .queues import TaskQueue

  tq = TaskQueue(queue_spec)
  deadline = time_mod.monotonic() + deadline_sec

  def stop_fn(executed, empty):
    return (empty and tq.enqueued == 0) or time_mod.monotonic() > deadline

  tq.poll(lease_seconds=lease_sec, verbose=False, stop_fn=stop_fn,
          max_backoff_window=0.25)
  if tq.enqueued > 0:
    raise click.ClickException(
      f"audit queue failed to drain within {deadline_sec:.0f}s "
      f"({tq.enqueued} tasks left)"
    )


@main.command("audit")
@click.argument("path")
@click.option("--queue", "-q", "queue_spec", default=None,
              help="fq:// queue to fan the audit grid through (range "
                   "leases); runs locally if omitted.")
@click.option("--mip", "mips", multiple=True, type=int,
              help="Mip level(s) to audit. Default: every mip the "
                   "layer's recorded downsample campaign produced.")
@click.option("--report-dir", default=None,
              help="Findings/report location "
                   "[default: <path>/integrity/audit]")
@click.option("--out", default=None, type=click.Path(),
              help="Also write the completeness report JSON to this "
                   "local file.")
@click.option("--heal", is_flag=True,
              help="Re-enqueue the producing task for each damaged "
                   "cell and loop audit→repair→re-audit to convergence.")
@click.option("--max-rounds", default=5, show_default=True,
              help="Heal convergence bound.")
@click.option("--no-digest", is_flag=True,
              help="Skip manifest digest checks (presence+decode only).")
@click.option("--allow-missing", is_flag=True,
              help="Missing chunks are not findings (sparse campaigns "
                   "with delete_black_uploads).")
@click.option("--lease-sec", default=60.0, show_default=True)
@click.option("--drain-sec", default=600.0, show_default=True,
              help="Deadline for each queued round to drain.")
@click.pass_context
def audit(ctx, path, queue_spec, mips, report_dir, out, heal, max_rounds,
          no_digest, allow_missing, lease_sec, drain_sec):
  """Verify a campaign's outputs: presence, decode, manifest digests.

  Replays the expected chunk grid of PATH against the write envelope
  (ISSUE 16) and reports every missing, undecodable, or
  digest-mismatched chunk. Exit 0 = complete and intact; exit 2 =
  findings remain (each is named on stdout). With --heal, findings
  re-enqueue the producing DownsampleTask for exactly the damaged
  cells and the audit loops until clean or --max-rounds.
  """
  import json as json_mod
  import time as time_mod

  from . import chunk_cache, integrity
  from .observability import trace
  from .task_creation.audit import (
    downsample_provenance,
    downsample_repair_tasks,
  )
  from .volume import Volume

  parallel = ctx.obj["parallel"]
  path = path.rstrip("/")
  report_dir = report_dir or f"{path}/{integrity.INTEGRITY_PREFIX}/audit"
  vol = Volume(path, mip=0)
  prov = downsample_provenance(vol)
  if mips:
    mips = sorted(set(int(m) for m in mips))
  elif prov is not None:
    src = int(prov["mip"])
    mips = list(range(src + 1, src + int(prov["num_mips"]) + 1))
  else:
    raise click.ClickException(
      "no recorded downsample campaign in provenance: pass --mip "
      "explicitly to name the levels to audit"
    )

  findings, totals = _audit_round(
    path, mips, report_dir, queue_spec, parallel,
    not no_digest, not allow_missing, lease_sec, drain_sec,
  )
  rounds = 1
  repaired = 0
  while findings and heal and rounds <= max_rounds:
    tasks, unhealable = downsample_repair_tasks(path, findings, prov)
    if unhealable:
      for f in unhealable:
        click.echo(f"UNHEALABLE {f['kind']} mip={f['mip']} {f['key']}")
      break
    if not tasks:
      break
    click.echo(
      f"heal round {rounds}: {len(findings)} findings -> "
      f"{len(tasks)} repair tasks"
    )
    # repairs carry the audit's trace lineage through the queue, the
    # same way any enqueued campaign does
    with trace.activate(trace.SpanContext(trace.new_id(), None, True)):
      enqueue(queue_spec, tasks, parallel)
    if queue_spec is not None:
      _drain_inline(queue_spec, lease_sec, drain_sec)
    repaired += len(tasks)
    # repaired chunks re-enter reads fresh: drop any decoded chunks the
    # damaged bytes may have neighbored
    for mip in mips:
      chunk_cache.invalidate(path, mip)
    findings, totals = _audit_round(
      path, mips, report_dir, queue_spec, parallel,
      not no_digest, not allow_missing, lease_sec, drain_sec,
    )
    rounds += 1

  report = {
    "layer": path,
    "mips": list(mips),
    "rounds": rounds,
    "repair_tasks": repaired,
    "chunks_checked": totals["chunks"],
    "unmanifested": totals["unmanifested"],
    "findings": findings,
    "complete": not findings,
    "ts": time_mod.time(),
  }
  from .storage import CloudFiles

  CloudFiles(report_dir).put_json("report.json", report)
  if out:
    with open(out, "w") as f:
      json_mod.dump(report, f, indent=2, sort_keys=True)

  for f in findings:
    click.echo(f"CORRUPT {f['kind']} mip={f['mip']} {f['key']}")
  click.echo(
    f"audited {totals['chunks']} chunks across mips {list(mips)}: "
    + ("complete and intact"
       if not findings else f"{len(findings)} findings")
    + (f" ({repaired} repair tasks over {rounds} rounds)" if repaired else "")
  )
  if findings:
    raise SystemExit(2)


@main.group("queue")
def queue_group():
  """Queue inspection and maintenance (reference cli.py:1998-2054)."""


@queue_group.command("status")
@click.argument("queue_spec")
@click.option("--eta", is_flag=True, help="Sample throughput and estimate ETA.")
@click.option("--sample-sec", default=10.0, show_default=True,
              help="Live-sampling window for --eta; skipped entirely when "
                   "journal segments provide the throughput.")
def queue_status(queue_spec, eta, sample_sec):
  from .queues import TaskQueue

  tq = TaskQueue(queue_spec)
  click.echo(f"inserted: {tq.inserted}")
  click.echo(f"enqueued: {tq.enqueued}")
  click.echo(f"leased: {tq.leased}")
  click.echo(f"completed: {tq.completed}")
  if hasattr(tq, "queue_files"):
    # control-plane objects, not tasks: O(shards) for batch-inserted
    # campaigns — the scale-out signal (ISSUE 15)
    click.echo(f"queue files: {tq.queue_files}")
  if hasattr(tq, "dlq_count"):
    click.echo(f"dead-lettered: {tq.dlq_count}")
  if hasattr(tq, "stale_leases"):
    # zombie pressure: leases past expiry that no worker has recycled yet
    click.echo(f"stale leases: {tq.stale_leases}")
  if hasattr(tq, "lease_ages"):
    ages = tq.lease_ages()
    if ages:
      click.echo(f"lease_expiry_sec (min/max): {ages[0]:.0f}/{ages[-1]:.0f}")
  if eta:
    from .observability import journal as journal_mod
    from .telemetry import queue_eta

    # journal-derived throughput when the fleet left segments behind
    # (no sampling sleep); live two-sample estimate otherwise
    stats = queue_eta(
      tq, sample_seconds=sample_sec,
      journal_path=journal_mod.journal_path_for(tq, queue_spec),
    )
    click.echo(f"tasks/sec: {stats['tasks_per_sec']} ({stats['source']})")
    click.echo(f"eta_sec: {stats['eta_sec']}")


@queue_group.command("wait")
@click.argument("queue_spec")
@click.option("--interval", "--rate", "interval", default=5.0,
              show_default=True, help="seconds between checks")
@click.option("--timeout", default=None, type=float,
              help="give up after this many seconds")
@click.option("--aws-region", default=None,
              help="AWS region of the SQS queue.")
def queue_wait(queue_spec, interval, timeout, aws_region):
  """Block until the queue is empty (reference `igneous queue wait`,
  cli.py:1974). Uses the backend's own emptiness semantics — for sqs://
  that includes the eventual-consistency double-confirmation."""
  import time as _time

  from .queues import TaskQueue

  if aws_region:
    os.environ["SQS_REGION_NAME"] = aws_region
  q = TaskQueue(queue_spec)
  deadline = None if timeout is None else _time.monotonic() + timeout
  while True:
    if q.is_empty():
      click.echo("queue empty")
      return
    now = _time.monotonic()
    if deadline is not None and now >= deadline:
      raise click.ClickException(f"queue not empty after {timeout}s")
    # never sleep past the deadline (a long --interval must not make the
    # command overshoot --timeout)
    _time.sleep(interval if deadline is None else min(interval, deadline - now))


@queue_group.command("release")
@click.argument("queue_spec")
@click.option("--reset-deliveries", is_flag=True,
              help="Also zero delivery counts for tasks still in rotation "
                   "so a --max-deliveries budget starts fresh (re-arm "
                   "after a bad deploy burned deliveries on healthy "
                   "tasks). fq:// only; DLQ'd tasks keep their counts.")
def queue_release(queue_spec, reset_deliveries):
  """Drop all leases (crashed workers' tasks return immediately)."""
  from .queues import TaskQueue

  tq = TaskQueue(queue_spec)
  tq.release_all()
  if reset_deliveries:
    if not hasattr(tq, "reset_deliveries"):
      raise click.UsageError("--reset-deliveries supports fq:// queues only")
    n = tq.reset_deliveries()
    click.echo(f"reset delivery counts for {n} tasks")


@queue_group.command("purge")
@click.argument("queue_spec")
def queue_purge(queue_spec):
  from .queues import TaskQueue

  TaskQueue(queue_spec).purge()


@queue_group.command("rezero")
@click.argument("queue_spec")
def queue_rezero(queue_spec):
  from .queues import TaskQueue

  TaskQueue(queue_spec).rezero()


@queue_group.command("fsck")
@click.argument("queue_spec")
@click.option("--repair", is_flag=True,
              help="Quarantine malformed tasks, recycle bad leases.")
def queue_fsck(queue_spec, repair):
  """Audit queue consistency (malformed tasks, bad leases, counter drift)."""
  import json as json_mod

  from .queues import TaskQueue

  tq = TaskQueue(queue_spec)
  if not hasattr(tq, "fsck"):
    raise click.UsageError("fsck supports fq:// queues only")
  click.echo(json_mod.dumps(tq.fsck(repair=repair), indent=2))


@queue_group.group("dlq")
def dlq_group():
  """Dead-letter queue: inspect, requeue, or drop quarantined tasks.

  Tasks land here when a worker runs with --max-deliveries N and a task
  fails (raises, overruns its deadline, or loses its worker) on every
  delivery. fq:// queues only — SQS deployments use a RedrivePolicy."""


def _require_dlq(queue_spec):
  from .queues import TaskQueue

  tq = TaskQueue(queue_spec)
  if not hasattr(tq, "dlq_ls"):
    raise click.UsageError("queue dlq supports fq:// queues only")
  return tq


@dlq_group.command("ls")
@click.argument("queue_spec")
def dlq_ls(queue_spec):
  """One JSON line per quarantined task: payload, delivery count, and
  the recorded failure reasons (newest last)."""
  import json as json_mod

  for rec in _require_dlq(queue_spec).dlq_ls():
    click.echo(json_mod.dumps(rec))


@dlq_group.command("retry")
@click.argument("queue_spec")
@click.option("--name", "names", multiple=True,
              help="Specific task file(s); default: all.")
def dlq_retry(queue_spec, names):
  """Return quarantined tasks to rotation with a fresh delivery budget."""
  n = _require_dlq(queue_spec).dlq_retry(list(names) or None)
  click.echo(f"requeued {n} tasks")


@dlq_group.command("purge")
@click.argument("queue_spec")
def dlq_purge(queue_spec):
  """Drop all quarantined tasks. Irreversible."""
  n = _require_dlq(queue_spec).dlq_purge()
  click.echo(f"purged {n} tasks")


@queue_group.command("cp")
@click.argument("src")
@click.argument("dest")
def queue_cp(src, dest):
  """Copy pending tasks between queues."""
  from .queues import copy_queue

  click.echo(f"copied {copy_queue(src, dest)} tasks")


@queue_group.command("mv")
@click.argument("src")
@click.argument("dest")
def queue_mv(src, dest):
  """Move pending tasks between queues."""
  from .queues import move_queue

  click.echo(f"moved {move_queue(src, dest)} tasks")


# ---------------------------------------------------------------------------
# fleet observability (ISSUE 5)


@main.group("fleet")
def fleet_group():
  """Fleet observability: merge worker journal segments from the bucket.

  Workers running `igneous execute` append span/counter batches as JSONL
  segments under <queue>/journal/ (or $IGNEOUS_JOURNAL). These commands
  aggregate them AFTER the fact — no live connection to any worker."""


def _journal_location(queue_spec, journal_path):
  from .observability import journal as journal_mod
  from .queues import TaskQueue

  path = journal_path or knobs.get_str("IGNEOUS_JOURNAL")
  if path is None and queue_spec:
    path = journal_mod.journal_path_for(TaskQueue(queue_spec), queue_spec)
  if not path:
    raise click.UsageError(
      "no journal location: pass --journal, set $IGNEOUS_JOURNAL, or give "
      "an fq:// queue spec (whose journal/ sidecar is implied)"
    )
  return path


def _fleet_records(queue_spec, journal_path, effective=True):
  """Journal records for the fleet commands. ``effective`` reads rollup
  compactions + uncovered raw segments (O(windows) — status/top/check);
  ``effective=False`` reads every raw segment (`fleet trace` needs the
  per-span detail rollups summarize away). Byte-compatible: with no
  rollups present the two views are identical."""
  from .observability import fleet

  path = _journal_location(queue_spec, journal_path)
  records = fleet.load_effective(path) if effective else fleet.load(path)
  if not records:
    raise click.ClickException(f"no journal segments under {path}")
  return records


def _queue_depth_stats(queue_spec):
  """Best-effort depth snapshot for the health engine (None without a
  queue spec — health still runs, minus backlog-driven detectors)."""
  if not queue_spec:
    return None
  from .queues import TaskQueue

  try:
    tq = TaskQueue(queue_spec)
  except Exception as e:
    raise click.UsageError(f"cannot open queue {queue_spec}: {e}")
  if hasattr(tq, "depth_snapshot"):
    return tq.depth_snapshot()
  return {"backlog": getattr(tq, "backlog", None) or tq.enqueued}


def _journal_opts(fn):
  for opt in (
    click.option("--journal", "journal_path", default=None,
                 help="Journal path override [default: $IGNEOUS_JOURNAL or "
                      "<queue>/journal/]."),
    click.option("--queue", "-q", "queue_spec", default=None,
                 help="Queue whose journal/ sidecar to read "
                      "[default: $QUEUE_URL]."),
  ):
    fn = opt(fn)
  return fn


@fleet_group.command("status")
@_journal_opts
@click.option("--json", "as_json", is_flag=True, help="Machine-readable.")
def fleet_status(queue_spec, journal_path, as_json):
  """Per-stage fleet aggregates: p50/p95 stage times, stall ratio,
  throughput, zombie/DLQ tallies — merged across every worker."""
  import json as json_mod

  from . import secrets
  from .observability import fleet

  st = fleet.status(_fleet_records(queue_spec or secrets.queue_url(),
                                   journal_path))
  if as_json:
    click.echo(json_mod.dumps(st, indent=2))
    return
  click.echo(f"workers: {len(st['workers'])} ({', '.join(st['workers'])})")
  click.echo(f"window: {st['window_sec']}s")
  click.echo(
    f"tasks: {st['tasks']} ({st['tasks_failed']} failed spans, "
    f"{st['tasks_failed_counter']} failure counters)"
  )
  if st["tasks_per_sec"] is not None:
    click.echo(f"tasks/sec: {st['tasks_per_sec']}")
  if st["stall_ratio"] is not None:
    click.echo(f"stall ratio: {st['stall_ratio']}")
  click.echo(f"zombie fences: {st['zombie_fences']}  "
             f"dlq promoted: {st['dlq_promoted']}")
  surv = {
    k: v for k, v in st["counters"].items()
    if k.startswith(("speculation.", "steal."))
  }
  if surv:
    click.echo("campaign survival: " + "  ".join(
      f"{k.split('.', 1)[1] if k.startswith('speculation.') else k} {v}"
      for k, v in sorted(surv.items())
    ))
  click.echo("stage                                count   total_s  "
             "p50_ms   p95_ms")
  for name, s in st["stages"].items():
    click.echo(
      f"{name:<36} {s['count']:>6} {s['total_s']:>9} "
      f"{s['p50_ms']:>8} {s['p95_ms']:>8}"
    )


@fleet_group.command("trace")
@click.argument("trace_id")
@_journal_opts
@click.option("-o", "--out", "out_path", default=None,
              help="Also write a Perfetto/Chrome trace JSON here "
                   "(open at ui.perfetto.dev).")
def fleet_trace(trace_id, queue_spec, journal_path, out_path):
  """One task's merged lineage: enqueue wait, every delivery (retries
  included), and the pipeline stage spans inside each, across workers."""
  from . import secrets
  from .observability import fleet, perfetto

  records = _fleet_records(queue_spec or secrets.queue_url(), journal_path,
                           effective=False)
  spans = fleet.trace_records(records, trace_id)
  if not spans:
    raise click.ClickException(f"no spans recorded for trace {trace_id}")
  for line in fleet.render_trace(spans):
    click.echo(line)
  if out_path:
    n = perfetto.dump(records, out_path, trace_id=trace_id)
    click.echo(f"wrote {n} events to {out_path}")


@fleet_group.command("top")
@_journal_opts
@click.option("-n", "top_n", default=10, show_default=True)
def fleet_top(queue_spec, journal_path, top_n):
  """Slowest task executions by trace (feed one to `fleet trace`)."""
  from . import secrets
  from .observability import fleet

  records = _fleet_records(queue_spec or secrets.queue_url(), journal_path)
  rows = fleet.slowest_tasks(records, n=top_n)
  if not rows:
    raise click.ClickException("no task spans in the journal")
  click.echo("dur_s     task                       attempt  trace_id")
  for r in rows:
    err = f"  ERROR={r['error']}" if r.get("error") else ""
    click.echo(
      f"{r['dur_s']:>8.3f}  {r['task']:<25} {str(r['attempt'] or '-'):>7}"
      f"  {r['trace_id']}  @{r['worker']}{err}"
    )


@fleet_group.command("devices")
@_journal_opts
@click.option("--json", "as_json", is_flag=True, help="Machine-readable.")
def fleet_devices(queue_spec, journal_path, as_json):
  """Merged per-device utilization table (ISSUE 7): busy seconds/ratio,
  dispatches, recompiles, HBM peak per worker x device, per-kernel
  vox/s, and the batched-vs-host fast-path tally — from the cumulative
  device ledgers each worker flushes into the journal."""
  from . import secrets
  from .observability import device as device_mod

  records = _fleet_records(queue_spec or secrets.queue_url(), journal_path)
  ledgers = device_mod.device_ledgers(records)
  if as_json:
    click.echo(device_mod.report_json(ledgers))
    return
  for line in device_mod.render_devices(ledgers):
    click.echo(line)


@fleet_group.command("compact")
@_journal_opts
@click.option("--window-sec", "window", default=None, type=float,
              help="Rollup window width [default: $IGNEOUS_ROLLUP_WINDOW_SEC "
                   "or 60].")
@click.option("--min-segments", default=2, show_default=True, type=int,
              help="Skip when fewer uncovered raw segments exist.")
def fleet_compact(queue_spec, journal_path, window, min_segments):
  """Fold raw journal segments into windowed rollups (ISSUE 6).

  After compaction, `fleet status|top|check|watch` and `queue status
  --eta` read O(windows) instead of O(all segments), and the covered raw
  segments become GC-able via `fleet gc`. Workers self-compact their own
  segments every $IGNEOUS_ROLLUP_EVERY flushes; this command is the
  admin/cron sweep for whatever they left behind."""
  import json as json_mod

  from . import secrets
  from .observability import rollup

  path = _journal_location(queue_spec or secrets.queue_url(), journal_path)
  res = rollup.compact(path, window=window, min_segments=min_segments)
  click.echo(json_mod.dumps(res))


@fleet_group.command("gc")
@_journal_opts
@click.option("--retain-sec", default=None, type=float,
              help="Keep covered raw segments at least this long "
                   "[default: $IGNEOUS_JOURNAL_RETAIN or 3600]. This is "
                   "the `fleet trace` debuggability horizon: rollups keep "
                   "aggregates forever, per-span detail only lives in "
                   "raw segments.")
def fleet_gc(queue_spec, journal_path, retain_sec):
  """Delete raw journal segments already folded into rollups."""
  import json as json_mod

  from . import secrets
  from .observability import rollup

  path = _journal_location(queue_spec or secrets.queue_url(), journal_path)
  click.echo(json_mod.dumps(rollup.gc(path, retain=retain_sec)))


def _health_opts(fn):
  for opt in (
    click.option("--window-sec", "window_sec", default=None, type=float,
                 help="Analysis window [default: $IGNEOUS_HEALTH_WINDOW_SEC "
                      "or 600]."),
    click.option("--stall-sec", "stall_sec", default=None, type=float,
                 help="Flag a worker whose journal went silent this long "
                      "with backlog remaining [default: "
                      "$IGNEOUS_HEALTH_STALL_SEC or 120]."),
    click.option("--straggler-ratio", "straggler_ratio", default=None,
                 type=float,
                 help="Flag a worker at p95 >= ratio x fleet median "
                      "[default: $IGNEOUS_HEALTH_STRAGGLER_RATIO or 3]."),
    click.option("--horizon-sec", "horizon_sec", default=None, type=float,
                 help="Autoscaler target: drain the backlog within this "
                      "many seconds [default: $IGNEOUS_AUTOSCALE_HORIZON_SEC "
                      "or 600]."),
  ):
    fn = opt(fn)
  return fn


def _evaluate_health(queue_spec, journal_path, window_sec, stall_sec,
                     straggler_ratio, horizon_sec):
  from .observability import fleet, health

  path = _journal_location(queue_spec, journal_path)
  records = fleet.load_effective(path)
  if not records:
    raise click.ClickException(f"no journal segments under {path}")
  cfg = health.HealthConfig.from_env(
    window_sec=window_sec, stall_sec=stall_sec,
    straggler_ratio=straggler_ratio, horizon_sec=horizon_sec,
  )
  queue_stats = _queue_depth_stats(queue_spec)
  report = health.HealthEngine(cfg).evaluate(records, queue_stats)
  return path, report, queue_stats


@fleet_group.command("check")
@_journal_opts
@_health_opts
@click.option("--json", "as_json", is_flag=True, help="Machine-readable.")
@click.option("--out", "out_path", default=None,
              help="Also write the full report JSON here (CI artifact).")
@click.option("--emit-events/--no-emit-events", default=True,
              show_default=True,
              help="Append structured health.* events to the journal.")
@click.option("--flags/--no-flags", "write_flags", default=True,
              show_default=True,
              help="Publish <journal>/health/flags.json so flagged "
                   "workers surrender pre-leases (LeaseBatcher polls it).")
@click.option("--textfile", default=None,
              help="Write the Prometheus textfile (incl. "
                   "igneous_fleet_desired_workers / igneous_slo_burn / "
                   "igneous_fleet_stragglers) here for the node-exporter "
                   "collector [default: $IGNEOUS_METRICS_TEXTFILE].")
def fleet_check(queue_spec, journal_path, window_sec, stall_sec,
                straggler_ratio, horizon_sec, as_json, out_path,
                emit_events, write_flags, textfile):
  """One health evaluation, exit-code-bearing (CI/cron gate).

  Exit 0 = healthy; exit 2 = stragglers/anomalies/SLO burn detected —
  the output names each one. Also publishes the autoscaler signal and
  straggler flags unless told otherwise."""
  import json as json_mod
  import sys as sys_mod

  from . import secrets
  from .observability import health, journal as journal_mod, prom

  path, report, _ = _evaluate_health(
    queue_spec or secrets.queue_url(), journal_path,
    window_sec, stall_sec, straggler_ratio, horizon_sec,
  )
  health.publish_gauges(report)
  if textfile or knobs.get_str("IGNEOUS_METRICS_TEXTFILE"):
    prom.write_textfile(textfile)
  if emit_events:
    health.emit_events(
      report,
      journal_mod.Journal(path, worker_id=health.default_checker_id()),
    )
  if write_flags:
    health.write_flags(path, report)
  if out_path:
    with open(out_path, "w") as f:
      f.write(health.report_json(report))
  if as_json:
    click.echo(health.report_json(report))
  else:
    for line in health.check_lines(report):
      click.echo(line)
  if not report["healthy"]:
    sys_mod.exit(2)


@fleet_group.command("watch")
@_journal_opts
@_health_opts
@click.option("--interval", default=5.0, show_default=True,
              help="Seconds between refreshes.")
@click.option("--iterations", default=None, type=int,
              help="Render N frames then exit [default: until Ctrl-C].")
@click.option("--no-clear", is_flag=True,
              help="Append frames instead of redrawing in place.")
@click.option("--once", is_flag=True,
              help="Render exactly one frame and exit (same as "
                   "--iterations 1).")
@click.option("--json", "as_json", is_flag=True,
              help="Emit each frame as one JSON object (report + queue "
                   "snapshot) instead of the ANSI dashboard — for "
                   "dashboards and the simulator's live-vs-predicted "
                   "comparison. Implies --no-clear.")
def fleet_watch(queue_spec, journal_path, window_sec, stall_sec,
                straggler_ratio, horizon_sec, interval, iterations,
                no_clear, once, as_json):
  """Live fleet dashboard over the journal rollups: status, per-worker
  table, stragglers, alerts, autoscale — refreshed in place."""
  import json as json_mod
  import time as time_mod

  from . import secrets
  from .observability import health

  queue_spec = queue_spec or secrets.queue_url()
  if once:
    iterations = 1
  n = 0
  while True:
    report = queue_stats = None
    try:
      _path, report, queue_stats = _evaluate_health(
        queue_spec, journal_path,
        window_sec, stall_sec, straggler_ratio, horizon_sec,
      )
      lines = health.render_dashboard(report, queue_stats)
    except click.ClickException as e:
      lines = [f"fleet watch: {e.message} (waiting...)"]
    if as_json:
      click.echo(json_mod.dumps({
        "report": report, "queue": queue_stats,
        "error": None if report is not None else lines[0],
      }))
    else:
      if not no_clear:
        click.echo("\x1b[2J\x1b[H", nl=False)
      for line in lines:
        click.echo(line)
    n += 1
    if iterations is not None and n >= iterations:
      return
    time_mod.sleep(max(interval, 0.0))


def _parse_kv_spec(spec, caster=float):
  out = {}
  for part in (spec or "").split(","):
    part = part.strip()
    if not part:
      continue
    if "=" not in part:
      raise click.UsageError(f"expected key=value, got {part!r}")
    k, v = part.split("=", 1)
    try:
      out[k.strip()] = caster(v)
    except ValueError:
      raise click.UsageError(f"bad value in {part!r}")
  return out


def _load_or_mine_model(mine_path, model_path, window_sec=None):
  from .observability import replay

  if model_path:
    import json as json_mod

    with open(model_path) as f:
      return replay.WorkloadModel.from_dict(json_mod.load(f))
  model = replay.mine_journal(mine_path, window_sec=window_sec)
  if not model.task_types:
    raise click.ClickException(
      f"no task spans to mine under {mine_path} — run a journaled "
      "campaign first, or pass --model"
    )
  return model


def _autoscale_policy_opts(fn):
  for opt in (
    click.option("--step-max", default=None, type=int,
                 help="Max workers added/removed per action "
                      "[default: $IGNEOUS_AUTOSCALE_STEP_MAX or uncapped]."),
    click.option("--cooldown-sec", default=None, type=float,
                 help="Min seconds between scale actions "
                      "[default: $IGNEOUS_AUTOSCALE_COOLDOWN_SEC or 60]."),
    click.option("--hysteresis", default=None, type=float,
                 help="Dead band around current size "
                      "[default: $IGNEOUS_AUTOSCALE_HYSTERESIS or 0.2]."),
    click.option("--horizon-sec", "as_horizon_sec", default=None, type=float,
                 help="Drain the backlog within this many seconds "
                      "[default: $IGNEOUS_AUTOSCALE_HORIZON_SEC or 600]."),
    click.option("--max-workers", default=None, type=int,
                 help="Fleet ceiling [default: $IGNEOUS_AUTOSCALE_MAX "
                      "or 1000]."),
    click.option("--min-workers", default=None, type=int,
                 help="Fleet floor [default: $IGNEOUS_AUTOSCALE_MIN or 1]."),
  ):
    fn = opt(fn)
  return fn


def _policy_from_opts(min_workers, max_workers, as_horizon_sec, hysteresis,
                      cooldown_sec, step_max):
  from .observability import autoscale

  return autoscale.AutoscalePolicy.from_env(
    min_workers=min_workers, max_workers=max_workers,
    horizon_sec=as_horizon_sec, hysteresis=hysteresis,
    cooldown_sec=cooldown_sec, step_max=step_max,
  )


@fleet_group.command("simulate")
@_journal_opts
@click.option("--from-journal", "mine_path", default=None,
              help="Journal to mine the workload model from [default: the "
                   "--journal/--queue location].")
@click.option("--model", "model_path", default=None,
              help="Load a saved workload_model.json instead of mining.")
@click.option("--save-model", "save_model_path", default=None,
              help="Write the mined model JSON here (commit it, diff it, "
                   "re-simulate it months later).")
@click.option("--workers", default=4, show_default=True, type=int)
@click.option("--tasks", default=None, type=int,
              help="Scale the campaign to N total tasks (mined mix "
                   "proportions kept) [default: replay the mined counts].")
@click.option("--seed", default=0, show_default=True, type=int)
@click.option("--batch-size", default=None, type=int,
              help="Members per lease round [default: $IGNEOUS_SIM_BATCH "
                   "or 1].")
@click.option("--fail-scale", default=None, type=float,
              help="Multiply mined failure probabilities (what-if on "
                   "fault rates) [default: $IGNEOUS_SIM_FAIL_SCALE or 1].")
@click.option("--policy", "policy_mode",
              type=click.Choice(["fixed", "auto"]), default="fixed",
              show_default=True,
              help="fixed = N workers for the whole run; auto = a virtual "
                   "autoscale controller (the SAME PolicyLoop `fleet "
                   "autoscale` runs) sizes the fleet as it goes.")
@_autoscale_policy_opts
@click.option("--chaos", "chaos_spec", default=None,
              help="Fault injection, e.g. "
                   "'preempt=1,kill=1,stragglers=2,stall=1'. Keys: "
                   "preempt, preempt_at, kill, kill_at, stragglers, "
                   "straggler_factor, stall, stall_at.")
@click.option("--speculate", "sim_speculate", is_flag=True, default=False,
              help="Model cross-host straggler speculation (duplicate-issue "
                   "with first-ack-wins fencing) [default: "
                   "$IGNEOUS_SIM_SPECULATE].")
@click.option("--steal", "sim_steal", is_flag=True, default=False,
              help="Model idle-worker work stealing (unstarted tails carved "
                   "off long-held rounds) [default: $IGNEOUS_SIM_STEAL].")
@click.option("--what-if", "what_if_spec", default=None,
              help="Comma-separated alternative worker counts to forecast "
                   "alongside the base run, e.g. '1,8,32'.")
@click.option("--cost-per-worker-hour", default=0.0, show_default=True,
              type=float, help="Price forecasts in $ (0 = no cost column).")
@click.option("--emit-journal", "emit_path", default=None,
              help="Write the simulated run AS journal segments here — "
                   "`igneous fleet status|watch|top|trace` and the "
                   "Perfetto exporter work on it unchanged.")
@click.option("--base-ts", default=0.0, show_default=True, type=float,
              help="Timestamp anchor for --emit-journal (0 keeps output "
                   "bit-identical across same-seed reruns; pass a unix "
                   "time to overlay simulated history on live dashboards).")
@click.option("--json", "as_json", is_flag=True, help="Machine-readable.")
@click.option("--out", "out_path", default=None,
              help="Also write the full forecast JSON here (CI artifact).")
def fleet_simulate(queue_spec, journal_path, mine_path, model_path,
                   save_model_path, workers, tasks, seed, batch_size,
                   fail_scale, policy_mode, min_workers, max_workers,
                   as_horizon_sec, hysteresis, cooldown_sec, step_max,
                   chaos_spec, sim_speculate, sim_steal, what_if_spec,
                   cost_per_worker_hour, emit_path, base_ts, as_json,
                   out_path):
  """Forecast a campaign on virtual workers from mined journal history.

  Mines per-task-type empirical distributions (durations with their
  straggler tails, retry probabilities, lease-round overhead, worker
  speed spread) out of a real journal, then replays the campaign through
  a deterministic discrete-event simulation of the queue semantics —
  leases, redeliveries, DLQ, pre-lease rounds, preemption/kill/straggler
  chaos, and optionally the autoscale policy loop itself. Same seed,
  same model, same config => bit-identical forecast AND journal bytes."""
  import json as json_mod

  from . import secrets
  from .observability import replay, sim as sim_mod

  queue_spec = queue_spec or secrets.queue_url()
  if not model_path:
    mine_path = mine_path or _journal_location(queue_spec, journal_path)
  model = _load_or_mine_model(mine_path, model_path)
  if save_model_path:
    with open(save_model_path, "w") as f:
      json_mod.dump(model.to_dict(), f)

  chaos = sim_mod.ChaosSpec(**{
    k: (int(v) if k in ("preempt", "kill", "stragglers", "stall") else v)
    for k, v in _parse_kv_spec(chaos_spec).items()
  }) if chaos_spec else sim_mod.ChaosSpec()
  policy = _policy_from_opts(min_workers, max_workers, as_horizon_sec,
                             hysteresis, cooldown_sec, step_max)
  cfg = sim_mod.SimConfig.from_env(
    workers=workers, seed=seed, tasks=tasks, batch_size=batch_size,
    fail_scale=fail_scale, base_ts=base_ts,
    cost_per_worker_hour=cost_per_worker_hour,
    speculate=1 if sim_speculate else None,
    steal=1 if sim_steal else None,
  )
  cfg.chaos = chaos
  cfg.autoscale = policy_mode == "auto"
  cfg.policy = policy

  results = sim_mod.simulate(model, cfg, journal_path=emit_path)
  alternatives = []
  if what_if_spec:
    counts = [int(x) for x in what_if_spec.split(",") if x.strip()]
    alternatives = sim_mod.what_if(model, cfg, counts)

  payload = {
    "model": model.summary(),
    "config": {
      "workers": cfg.workers, "seed": cfg.seed,
      "batch_size": cfg.batch_size, "policy": policy_mode,
      "fail_scale": cfg.fail_scale, "tasks": results["tasks"],
    },
    "forecast": results,
    "what_if": alternatives,
  }
  if out_path:
    with open(out_path, "w") as f:
      json_mod.dump(payload, f, indent=2)
  if as_json:
    click.echo(json_mod.dumps(payload, indent=2))
    return

  ms = model.summary()
  click.echo(
    f"model: {ms['tasks_seen']} tasks mined across "
    f"{len(ms['task_types'])} type(s); round overhead p50 "
    f"{ms['round_overhead_p50_ms']}ms"
  )
  for name, t in ms["task_types"].items():
    click.echo(
      f"  {name:<30} n={t['count']:<6} p50 {t['p50_ms']}ms  "
      f"p95 {t['p95_ms']}ms  fail {t['fail_prob'] * 100:.1f}%"
    )
  r = results
  mode = "autoscaled" if cfg.autoscale else "fixed"
  click.echo(
    f"forecast ({mode}, {r['workers']} worker(s), seed {r['seed']}): "
    f"{r['tasks']} tasks in {r['makespan_sec']}s "
    f"({r['tasks_per_sec']}/s, utilization "
    f"{r['utilization'] * 100:.0f}%)"
  )
  click.echo(
    f"  completed {r['completed']}  dlq {r['dlq']}  retries "
    f"{r['failed_deliveries']}  lease recycles {r['lease_recycles']}  "
    f"released {r['released']}"
    + (f"  cost ${r['cost_usd']}" if r["cost_usd"] is not None else "")
  )
  spec, steals = r.get("speculation") or {}, r.get("steals") or {}
  if spec.get("issued") or steals.get("claims"):
    click.echo(
      f"  campaign survival: speculated {spec.get('issued', 0)} "
      f"(won {spec.get('won', 0)}, fenced {spec.get('fenced', 0)})  "
      f"steals {steals.get('claims', 0)} "
      f"({steals.get('tasks', 0)} task(s))"
    )
  if r["scale_events"]:
    click.echo(f"  scale events: {len(r['scale_events'])} "
               f"(peak {r['peak_workers']} workers)")
  if not r["completed_all"]:
    click.echo("  WARNING: campaign did not complete "
               f"(timed_out={r['timed_out']})")
  if alternatives:
    click.echo("what-if:")
    click.echo(f"  {'workers':>8}  {'makespan_s':>11}  {'delta':>8}  "
               f"{'dlq':>5}  {'util':>6}  cost")
    for alt in alternatives:
      delta = alt["makespan_sec"] - r["makespan_sec"]
      cost = f"${alt['cost_usd']}" if alt["cost_usd"] is not None else "-"
      click.echo(
        f"  {alt['workers']:>8}  {alt['makespan_sec']:>11}  "
        f"{delta:>+8.1f}  {alt['dlq']:>5}  "
        f"{alt['utilization'] * 100:>5.0f}%  {cost}"
      )
  if emit_path:
    click.echo(
      f"emitted {results['journal_segments']} journal segment(s) to "
      f"{emit_path} (try: igneous fleet status --journal {emit_path})"
    )


@fleet_group.command("autoscale")
@_journal_opts
@_autoscale_policy_opts
@click.option("--actuator", "actuator_kind",
              type=click.Choice(["local", "textfile", "command"]),
              default="local", show_default=True,
              help="local = spawn/drain real `igneous execute` "
                   "subprocesses; textfile = atomically publish the "
                   "target for an external reconciler; command = shell "
                   "out to a template with {n}.")
@click.option("--target-file", default=None,
              help="Path for --actuator textfile.")
@click.option("--scale-command", default=None,
              help="Template for --actuator command, e.g. "
                   "'kubectl scale --replicas={n} deploy/igneous-worker'.")
@click.option("--worker-arg", "worker_args", multiple=True,
              help="Extra args for spawned workers (local actuator), "
                   "repeatable.")
@click.option("--interval", default=None, type=float,
              help="Seconds between controller ticks "
                   "[default: $IGNEOUS_AUTOSCALE_INTERVAL_SEC or 15].")
@click.option("--iterations", default=None, type=int,
              help="Tick N times then exit [default: until drained or "
                   "Ctrl-C].")
@click.option("--drain-exit/--no-drain-exit", default=True,
              show_default=True,
              help="Exit once the backlog is empty and the pool is at "
                   "the policy floor (batch-campaign mode). "
                   "--no-drain-exit runs as a service.")
@click.option("--validate/--no-validate", default=True, show_default=True,
              help="Before touching the fleet, replay the mined journal "
                   "through the simulator under THIS policy and abort if "
                   "the simulated campaign fails to complete.")
@click.option("--json", "as_json", is_flag=True,
              help="One JSON object per controller decision.")
def fleet_autoscale(queue_spec, journal_path, min_workers, max_workers,
                    as_horizon_sec, hysteresis, cooldown_sec, step_max,
                    actuator_kind, target_file, scale_command, worker_args,
                    interval, iterations, drain_exit, validate, as_json):
  """Closed-loop fleet autoscaler: act on the HealthEngine's
  desired_workers signal.

  Each tick reads the journal + live queue depth, runs the SAME policy
  formula the health report and the simulator use, damps it (hysteresis,
  cooldown, step cap), and actuates. Scale-down is always graceful
  SIGTERM drain; nothing is ever killed."""
  import json as json_mod
  import time as time_mod

  from . import secrets
  from .observability import autoscale, sim as sim_mod
  from .queues import TaskQueue

  queue_spec = queue_spec or secrets.queue_url()
  if not queue_spec:
    raise click.UsageError("fleet autoscale needs a queue (-q or "
                           "$QUEUE_URL): backlog drives the policy")
  path = _journal_location(queue_spec, journal_path)
  policy = _policy_from_opts(min_workers, max_workers, as_horizon_sec,
                             hysteresis, cooldown_sec, step_max)

  if validate:
    from .observability import replay

    try:
      model = replay.mine_journal(path)
    except Exception:
      model = None
    if model and model.task_types:
      cfg = sim_mod.SimConfig.from_env(workers=policy.min_workers)
      cfg.autoscale = True
      cfg.policy = policy
      forecast = sim_mod.simulate(model, cfg)
      if not forecast["completed_all"]:
        raise click.ClickException(
          "policy validation failed: the simulated campaign did not "
          f"complete (dlq={forecast['dlq']}, "
          f"timed_out={forecast['timed_out']}). Loosen the policy or "
          "pass --no-validate."
        )
      click.echo(
        f"policy validated in simulation: {forecast['tasks']} tasks in "
        f"{forecast['makespan_sec']}s, peak {forecast['peak_workers']} "
        f"worker(s), {len(forecast['scale_events'])} scale event(s)",
        err=True,
      )
    else:
      click.echo("policy validation skipped: no task history to mine yet",
                 err=True)

  if actuator_kind == "local":
    actuator = autoscale.LocalPoolActuator(
      queue_spec, worker_args=list(worker_args),
    )
  elif actuator_kind == "textfile":
    if not target_file:
      raise click.UsageError("--actuator textfile needs --target-file")
    actuator = autoscale.TextfileActuator(target_file)
  else:
    if not scale_command:
      raise click.UsageError("--actuator command needs --scale-command")
    actuator = autoscale.CommandActuator(scale_command)

  controller = autoscale.AutoscaleController(
    path, TaskQueue(queue_spec), actuator,
    policy=policy, interval_sec=interval,
  )
  n = 0
  try:
    while True:
      decision = controller.step()
      if as_json:
        click.echo(json_mod.dumps(decision))
      else:
        click.echo(
          f"[{time_mod.strftime('%H:%M:%S')}] backlog "
          f"{decision['backlog']}  rate {decision['per_worker_rate']}/s"
          f"/worker  {decision['current']} -> {decision['target']} "
          f"({decision['reason']})"
        )
      n += 1
      actuator.reap()
      if (
        drain_exit and decision["backlog"] <= 0
        and actuator.current() <= policy.min_workers
        and n > 1
      ):
        break
      if iterations is not None and n >= iterations:
        break
      time_mod.sleep(controller.interval_sec)
  finally:
    actuator.shutdown()
  summary = {
    "ticks": n,
    "actions": sum(1 for d in controller.history if d["actuated"]),
  }
  if isinstance(actuator, autoscale.LocalPoolActuator):
    summary["spawned"] = actuator.stats["spawned"]
    summary["drained"] = actuator.stats["drained"]
    summary["exits"] = actuator.stats["exits"]
  click.echo(json_mod.dumps(summary))


# closed-loop campaign driver (ISSUE 17)


@main.group("campaign")
def campaign_group():
  """Closed-loop campaign survival: autoscale + speculation + stealing.

  One driver process per campaign: each tick sizes the fleet from the
  journal (the `fleet autoscale` loop), publishes straggler flags, and
  twins the unfinished tails of range leases held by flagged or
  journal-projected-slow workers (first ack wins, losers are zombie-
  fenced, completions never double-count)."""


@campaign_group.command("run")
@_journal_opts
@_autoscale_policy_opts
@click.option("--tick-sec", default=None, type=float,
              help="Seconds between driver ticks "
                   "[default: $IGNEOUS_CAMPAIGN_TICK_SEC or 5].")
@click.option("--max-wall-sec", default=None, type=float,
              help="Abort (gracefully) after this much wall clock "
                   "[default: $IGNEOUS_CAMPAIGN_MAX_WALL_SEC; 0 = never].")
@click.option("--iterations", default=None, type=int,
              help="Tick N times then exit [default: until drained].")
@click.option("--speculate/--no-speculate", "speculate", default=None,
              help="Twin the tails of flagged/slow holders' range leases "
                   "[default: $IGNEOUS_CAMPAIGN_SPECULATE or on].")
@click.option("--steal/--no-steal", "steal", default=None,
              help="Let idle workers claim unstarted sub-ranges off "
                   "long-held range leases [default: $IGNEOUS_STEAL "
                   "or off].")
@click.option("--worker-arg", "worker_args", multiple=True,
              help="Extra args for spawned workers, repeatable "
                   "(e.g. --worker-arg=--batch-size=4).")
@click.option("--actuator", "actuator_kind",
              type=click.Choice(["local", "textfile", "command"]),
              default="local", show_default=True,
              help="How scale actions reach the fleet (see fleet "
                   "autoscale).")
@click.option("--target-file", default=None,
              help="Path for --actuator textfile.")
@click.option("--scale-command", default=None,
              help="Template for --actuator command with {n}.")
@click.option("--json", "as_json", is_flag=True,
              help="One JSON object per tick + a summary object.")
def campaign_run(queue_spec, journal_path, min_workers, max_workers,
                 as_horizon_sec, hysteresis, cooldown_sec, step_max,
                 tick_sec, max_wall_sec, iterations, speculate, steal,
                 worker_args, actuator_kind, target_file, scale_command,
                 as_json):
  """Run a campaign to completion on a hostile fleet.

  Glues the survival layer into one loop: autoscale sizes the fleet,
  health flags route queue depth away from stragglers, speculation
  twins their unfinished tails, and (with --steal) idle workers carve
  unstarted sub-ranges off long-held leases. Exits when the queue is
  drained — no backlog, no outstanding leases, pool at the floor."""
  import json as json_mod
  import time as time_mod

  from . import secrets
  from .observability import autoscale, campaign as campaign_mod
  from .queues import TaskQueue

  queue_spec = queue_spec or secrets.queue_url()
  if not queue_spec:
    raise click.UsageError("campaign run needs a queue (-q or $QUEUE_URL)")
  path = _journal_location(queue_spec, journal_path)
  policy = _policy_from_opts(min_workers, max_workers, as_horizon_sec,
                             hysteresis, cooldown_sec, step_max)
  worker_env = {}
  if steal is not None:
    # ship the steal knob into every worker this driver spawns; the
    # driver process itself never steals (it holds no leases)
    knobs.set_env("IGNEOUS_STEAL", "1" if steal else "0")
    worker_env["IGNEOUS_STEAL"] = "1" if steal else "0"
  if actuator_kind == "local":
    actuator = autoscale.LocalPoolActuator(
      queue_spec, worker_args=list(worker_args), env=worker_env or None,
    )
  elif actuator_kind == "textfile":
    if not target_file:
      raise click.UsageError("--actuator textfile needs --target-file")
    actuator = autoscale.TextfileActuator(target_file)
  else:
    if not scale_command:
      raise click.UsageError("--actuator command needs --scale-command")
    actuator = autoscale.CommandActuator(scale_command)

  runner = campaign_mod.CampaignRunner(
    path, TaskQueue(queue_spec), actuator,
    policy=policy, tick_sec=tick_sec, speculate=speculate,
    max_wall_sec=max_wall_sec,
  )

  def narrate(sleep_sec):
    d = runner.history[-1]
    if as_json:
      click.echo(json_mod.dumps(d))
    else:
      extras = ""
      if d["speculated"]:
        extras += f"  speculated {d['speculated']}"
      if d["flagged"]:
        extras += f"  flagged {','.join(d['flagged'])}"
      click.echo(
        f"[{time_mod.strftime('%H:%M:%S')}] backlog {d['backlog']}  "
        f"workers {d['current']} -> {d['target']} ({d['reason']})"
        + extras
      )
    time_mod.sleep(sleep_sec)

  summary = runner.run(iterations=iterations, sleep_fn=narrate)
  click.echo(json_mod.dumps(summary if as_json else {
    k: v for k, v in summary.items() if k != "fleet_status"
  }))
  if summary["timed_out"] or summary["queue"].get("enqueued", 0) > 0:
    raise SystemExit(3)


# on-demand profiler capture (ISSUE 7)


@main.group("profile")
def profile_group():
  """On-demand ``jax.profiler`` capture across the fleet.

  ``capture`` publishes <journal>/profile/request.json; every worker
  polls it on the journal cadence (the PR 6 straggler-flag pattern) and
  runs one bounded profiler trace, uploading the TensorBoard-format
  artifacts under <journal>/profiles/. No worker restart, no always-on
  profiling cost."""


@profile_group.command("capture")
@_journal_opts
@click.option("--duration", default=5.0, show_default=True, type=float,
              help="Seconds of device activity to capture.")
@click.option("--worker", "workers", multiple=True,
              help="Restrict the trigger to these worker ids "
                   "[default: every worker captures once].")
@click.option("--wait", default=0.0, show_default=True, type=float,
              help="Poll up to this many seconds for artifacts to land "
                   "before returning (0 = fire and forget).")
@click.option("--local", is_flag=True,
              help="Capture in THIS process instead of publishing a "
                   "worker trigger (debugging a driver-side workload).")
def profile_capture(queue_spec, journal_path, duration, workers, wait,
                    local):
  """Trigger a bounded profiler capture on fleet workers."""
  import time as time_mod

  from . import secrets
  from .observability import device as device_mod
  from .observability import journal as journal_mod

  path = _journal_location(queue_spec or secrets.queue_url(), journal_path)
  if local:
    j = journal_mod.Journal(path, worker_id=f"profile-cli-{os.getpid()}")
    device_mod._capture_blocking(duration, j, "manual", None)
    for key in device_mod.list_profiles(path):
      click.echo(key)
    return
  req = device_mod.write_profile_request(
    path, duration_sec=duration, workers=list(workers) or None,
  )
  click.echo(f"published capture request {req['id']} "
             f"({duration}s) at {path}/{device_mod.PROFILE_REQUEST_KEY}")
  if wait <= 0:
    return
  deadline = time_mod.monotonic() + wait
  prefix = f"{device_mod.PROFILE_ARTIFACT_PREFIX}"
  while time_mod.monotonic() < deadline:
    found = [
      k for k in device_mod.list_profiles(path) if req["id"] in k
    ]
    if found:
      click.echo(f"{len(found)} artifact file(s):")
      for key in found:
        click.echo(f"  {prefix}{key}" if not key.startswith(prefix) else
                   f"  {key}")
      return
    time_mod.sleep(1.0)
  raise click.ClickException(
    f"no artifacts for request {req['id']} within {wait}s (are workers "
    "running with a journal?)"
  )


@profile_group.command("ls")
@_journal_opts
def profile_ls(queue_spec, journal_path):
  """List captured profile artifacts under <journal>/profiles/."""
  from . import secrets
  from .observability import device as device_mod

  path = _journal_location(queue_spec or secrets.queue_url(), journal_path)
  keys = device_mod.list_profiles(path)
  if not keys:
    click.echo("no profile artifacts")
    return
  for key in keys:
    click.echo(key)


@main.group()
def design():
  """Capacity planning math (reference cli.py `design`)."""


@design.command("ds-memory")
@click.argument("path")
@click.option("--memory", default=int(3.5e9), show_default=True)
@click.option("--mip", default=0, show_default=True)
@click.option("--factor", type=TUPLE3, default=(2, 2, 1), show_default=True)
@click.option("--max-mips", default=None, type=int,
              help="Cap the downsample count even if memory allows more.")
@click.option("--verbose", is_flag=True)
def design_ds_memory(path, memory, mip, factor, max_mips, verbose):
  """Optimal task shape + mip count for a byte budget
  (reference cli.py ds-memory)."""
  from .downsample_scales import (
    downsample_shape_from_memory_target,
    num_mips_from_memory_target,
  )
  from .volume import Volume

  vol = Volume(path, mip=mip)
  cs = vol.meta.chunk_size(mip)
  mips = num_mips_from_memory_target(
    memory, vol.dtype.itemsize, cs, factor, vol.num_channels
  )
  if max_mips is not None:
    mips = min(mips, max_mips)
  shape = downsample_shape_from_memory_target(
    vol.dtype.itemsize, int(cs.x), int(cs.y), int(cs.z), factor, memory,
    max_mips=max_mips, num_channels=vol.num_channels,
  )
  if verbose:
    click.echo(f"Data width: {vol.dtype.itemsize} B")
    click.echo(f"Chunk size: {int(cs.x)},{int(cs.y)},{int(cs.z)}")
    click.echo(f"Memory limit: {memory:.2e} B")
    click.echo(f"Optimized shape: {','.join(str(int(v)) for v in shape)}")
    click.echo(f"Downsamples: {mips}")
  else:
    click.echo(f"mips achievable in {memory:.2e} bytes: {mips}")
    click.echo(",".join(str(int(v)) for v in shape))


@design.command("ds-shape")
@click.argument("path")
@click.option("--memory", default=int(3.5e9), show_default=True)
@click.option("--mip", default=0, show_default=True)
@click.option("--factor", type=TUPLE3, default=(2, 2, 1), show_default=True)
@click.option("--num-mips", default=None, type=int)
def design_ds_shape(path, memory, mip, factor, num_mips):
  """Optimal task shape for a byte budget."""
  from .downsample_scales import downsample_shape_from_memory_target
  from .volume import Volume

  vol = Volume(path, mip=mip)
  cs = vol.meta.chunk_size(mip)
  shape = downsample_shape_from_memory_target(
    vol.dtype.itemsize, int(cs.x), int(cs.y), int(cs.z), factor, memory,
    max_mips=num_mips, num_channels=vol.num_channels,
  )
  click.echo(",".join(str(int(v)) for v in shape))


@design.command("bounds")
@click.argument("path")
@click.option("--mip", default=0, show_default=True)
def design_bounds(path, mip):
  """Reverse-engineer bounds from stored chunk filenames
  (reference cli.py:1628-1649 repair tool)."""
  from .lib import Bbox
  from .volume import Volume

  vol = Volume(path)
  boxes = []
  for key in vol.cf.list(f"{vol.meta.key(mip)}/"):
    try:
      boxes.append(Bbox.from_filename(key))
    except ValueError:
      continue
  if not boxes:
    click.echo("no chunks found")
    return
  total = Bbox.expand(*boxes)
  click.echo(f"chunks: {len(boxes)}")
  click.echo(f"bounds: {total}")
  click.echo(f"info bounds: {vol.meta.bounds(mip)}")


@main.command("view")
@click.argument("path")
@click.option("--port", default=1337, show_default=True)
@click.option("--browser/--no-browser", default=True, show_default=True,
              help="Open the link in the system browser.")
@click.option("--ng", default=None,
              help="Alternative Neuroglancer deployment URL.")
@click.option("--pos", type=TUPLE3, default=None,
              help="Open the view centered at this voxel position.")
@click.option("--name", default=None, help="Custom layer name.")
@click.option("--indirect", is_flag=True,
              help="Parity flag: the reference routes through CloudVolume "
                   "for private buckets; this build always serves the "
                   "local file server, which covers that case.")
def view_cmd(path, port, browser, ng, pos, name, indirect):
  """Serve PATH locally and print a Neuroglancer link
  (reference cli.py:1735-1850)."""
  import socket

  from .view import serve

  # skip to a free port like the reference (cli.py:1748-1754)
  for _ in range(10):
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as sock:
      if sock.connect_ex(("localhost", port)) != 0:
        break
    port += 1
  serve(
    path, port=port, block=True, browser=browser, ng_url=ng, position=pos,
    layer_name=name,
  )


@main.command("serve")
@click.argument("paths", nargs=-1, required=True)
@click.option("--port", default=8080, show_default=True)
@click.option("--host", default="0.0.0.0", show_default=True)
@click.option("--ram-mb", default=None, type=float,
              help="RAM cache budget (env IGNEOUS_SERVE_RAM_MB; default 256).")
@click.option("--ssd-dir", default=None,
              help="Local-SSD spill directory (env IGNEOUS_SERVE_SSD_DIR; "
                   "default off). Entries survive restarts.")
@click.option("--ssd-mb", default=None, type=float,
              help="SSD spill budget (env IGNEOUS_SERVE_SSD_MB; default 4096).")
@click.option("--synth/--no-synth", default=None,
              help="Synthesize missing mips on the fly from the parent "
                   "scale (env IGNEOUS_SERVE_SYNTH_MIPS; default on).")
@click.option("--writeback/--no-writeback", default=None,
              help="Write synthesized mips back to storage "
                   "(env IGNEOUS_SERVE_WRITEBACK; default off).")
@click.option("--cache-control", default=None,
              help="Cache-Control header for CDN fronting "
                   "(env IGNEOUS_SERVE_CACHE_CONTROL; "
                   "default 'public, max-age=300').")
@click.option("--journal", default=None,
              help="Journal cloudpath for request traces "
                   "(env IGNEOUS_JOURNAL).")
@click.option("--metrics-port", default=None, type=int,
              help="Prometheus /metrics port (also served inline at "
                   "/metrics on the main port; 0 auto-assigns).")
@click.option("--peers", default=None,
              help="Comma-separated replica base URLs: static federation "
                   "ring membership (env IGNEOUS_SERVE_FLEET_PEERS).")
@click.option("--peers-file", default=None,
              help="Shared membership directory cloudpath: replicas "
                   "heartbeat + discover the ring here "
                   "(env IGNEOUS_SERVE_FLEET_MEMBERSHIP).")
@click.option("--self-url", default=None,
              help="This replica's advertised base URL (env "
                   "IGNEOUS_SERVE_FLEET_SELF; default derived from the "
                   "bound host/port).")
@click.option("--prewarm/--no-prewarm", default=None,
              help="Telemetry-driven prefetch of predicted-hot chunks "
                   "mined from journal traces (env IGNEOUS_SERVE_PREWARM; "
                   "default off).")
def serve_cmd(paths, port, host, ram_mb, ssd_dir, ssd_mb, synth, writeback,
              cache_control, journal, metrics_port, peers, peers_file,
              self_url, prewarm):
  """Serve one or more Precomputed layers over HTTP (ISSUE 9).

  PATHS are cloudpaths, optionally named: ``name=gs://bucket/layer``.
  A single unnamed path also serves at the root (view parity); multiple
  layers serve under ``/<name>/``. The hot path hands stored bytes to
  clients without decoding (Content-Encoding negotiation), a multi-tier
  cache (RAM -> local SSD -> CDN via strong ETags) absorbs re-reads,
  concurrent misses for one chunk coalesce into a single backend fetch,
  and missing mips are synthesized through the device downsample
  kernels. SIGTERM drains gracefully and exits 0.
  """
  import json as json_mod
  import os as os_mod
  import signal as signal_mod
  import socket as socket_mod

  from .observability import journal as journal_mod
  from .observability import prom
  from .serve import Federation, ServeApp, ServeConfig, ServeServer

  layers = {}
  for spec in paths:
    if "=" in spec.split("://")[0]:
      name, _, cloudpath = spec.partition("=")
    else:
      cloudpath = spec
      name = cloudpath.rstrip("/").split("/")[-1] or "layer"
    if name in layers:
      raise click.UsageError(f"duplicate layer name: {name!r}")
    layers[name] = cloudpath
  default_layer = next(iter(layers)) if len(layers) == 1 else None

  jpath = journal if journal is not None else os_mod.environ.get(
    journal_mod.PATH_ENV
  )
  if jpath:
    worker_id = f"serve-{socket_mod.gethostname().split('.')[0]}-{os_mod.getpid()}"
    journal_mod.set_active(journal_mod.Journal(jpath, worker_id=worker_id))
  journal_mod.install_last_will({"role": "serve"})

  config = ServeConfig.from_env(
    ram_mb=ram_mb, ssd_dir=ssd_dir, ssd_mb=ssd_mb, synth_mips=synth,
    writeback=writeback, cache_control=cache_control,
  )
  federation = Federation.from_env(peers=peers, membership_dir=peers_file)
  app = ServeApp(layers, config=config, default_layer=default_layer,
                 federation=federation, prewarm=prewarm)
  server = ServeServer(app, host=host, port=port,
                       drain_timeout=config.drain_sec)
  bound_metrics = None
  if metrics_port is not None:
    bound_metrics = prom.start_http_server(metrics_port)
    if bound_metrics is not None:
      click.echo(f"metrics: http://0.0.0.0:{bound_metrics}/metrics")
  # the advertised URL needs the BOUND port (--port 0 auto-assigns),
  # so federation activates only after the listening socket exists
  if federation.configured:
    from .analysis import knobs as knobs_mod

    adv = self_url or knobs_mod.get_str("IGNEOUS_SERVE_FLEET_SELF")
    if not adv:
      adv_host = host
      if adv_host in ("0.0.0.0", "::", ""):
        adv_host = socket_mod.gethostname().split(".")[0]
      adv = f"http://{adv_host}:{server.server_address[1]}"
    federation.activate(adv)
  # machine-parsable readiness line (the CI smoke and orchestration
  # scripts wait on this rather than polling ports — it carries every
  # BOUND port so N auto-assigned replicas can boot on one host)
  click.echo(json_mod.dumps({
    "event": "serve.listening", "port": server.server_address[1],
    "host": host, "layers": sorted(layers),
    "metrics_port": bound_metrics,
    "self_url": federation.self_url if federation.configured else None,
  }), nl=True)

  def _on_signal(_signum, _frame):
    server.request_shutdown()

  signal_mod.signal(signal_mod.SIGTERM, _on_signal)
  signal_mod.signal(signal_mod.SIGINT, _on_signal)
  server.join()


@main.command("lint")
@click.option("--root", default=".", show_default=True,
              help="Repo root to analyze.")
@click.option("--knobs-md", is_flag=True,
              help="Print the generated README knob table.")
@click.option("--write", is_flag=True,
              help="With --knobs-md: rewrite README.md in place.")
@click.option("--baseline", default=None,
              help="Baseline file (repo-relative; default "
                   "tools/lint_baseline.json).")
@click.option("--update-baseline", is_flag=True,
              help="Accept current findings as the new baseline "
                   "(env-knobs/telemetry passes refuse).")
@click.option("--select", multiple=True,
              help="Run only these passes (repeatable).")
@click.option("--json", "as_json", is_flag=True,
              help="Machine-readable findings output.")
def lint_cmd(root, knobs_md, write, baseline, update_baseline, select,
             as_json):
  """Project-native static analysis (see README 'Static analysis')."""
  from igneous_tpu.analysis import runner

  for pid in select:
    if pid not in runner.PASS_IDS:
      raise click.BadParameter(
        f"unknown pass {pid!r}; choose from {', '.join(runner.PASS_IDS)}"
      )
  rc = runner.main(
    root, knobs_md=knobs_md, write=write, baseline_path=baseline,
    update_baseline=update_baseline, select=list(select) or None,
    as_json=as_json, echo=click.echo,
  )
  if rc:
    raise SystemExit(rc)


@main.command("tune")
@click.option("--out", default=None,
              help="Config root to write tuned/<device_kind>.json under "
                   "(default: IGNEOUS_TUNE_CONFIG).")
@click.option("--budget", "budget_sec", type=float, default=None,
              help="Wall-clock budget for the whole sweep in seconds "
                   "(default: IGNEOUS_TUNE_BUDGET_SEC; unset = unbounded).")
@click.option("--repeats", type=int, default=None,
              help="Timed runs per candidate, best-of "
                   "(default: IGNEOUS_TUNE_REPEATS).")
@click.option("--size", type=int, default=48, show_default=True,
              help="Edge length of the seeded sweep workloads.")
@click.option("--knob", "only", multiple=True,
              help="Sweep only these knobs (repeatable; default: all).")
@click.option("--json", "as_json", is_flag=True,
              help="Print the full tuned config as JSON.")
def tune_cmd(out, budget_sec, repeats, size, only, as_json):
  """Autotune kernel knobs for this device kind (see README
  'Compile cache & autotuner').

  Sweeps Pallas CCL tile shapes, EDT line-block geometry, and page
  shape/batch on seeded workloads; every candidate must be
  byte-identical to the registry default. Winners are persisted as
  tuned/<device_kind>.json and picked up automatically (resolution:
  explicit env > tuned config > registry default).
  """
  import json

  from igneous_tpu import tune as tune_mod
  from igneous_tpu.analysis import knobs as knobs_mod
  for name in only:
    if name not in tune_mod.TUNABLE:
      raise click.BadParameter(
        f"unknown tunable {name!r}; choose from "
        f"{', '.join(tune_mod.TUNABLE)}"
      )
  pinned = [n for n in (only or tune_mod.TUNABLE) if knobs_mod.raw(n)]
  if pinned:
    raise click.ClickException(
      f"refusing to tune while {', '.join(pinned)} is pinned in the "
      "environment — explicit env always outranks tuned configs, so the "
      "sweep could never take effect; unset it first"
    )
  config = tune_mod.run(
    out=out, budget_sec=budget_sec, repeats=repeats, size=size,
    only=list(only) or None, log=click.echo,
  )
  if as_json:
    click.echo(json.dumps(config, indent=2, sort_keys=True))
    return
  if config["knobs"]:
    click.echo(f"tuned {len(config['knobs'])} knob(s): "
               + ", ".join(f"{k}={v}" for k, v in config["knobs"].items()))
  else:
    click.echo("registry defaults already optimal; nothing tuned")
  ratio = config.get("tune_best_vs_default_ratio")
  if ratio is not None:
    click.echo(f"tune_best_vs_default_ratio: {ratio}")
  if config.get("written_to"):
    click.echo(f"wrote {config['written_to']}")
  else:
    click.echo("no config root resolvable (pass --out or set "
               "IGNEOUS_TUNE_CONFIG); config not persisted")


@main.command("license")
def license_cmd():
  click.echo("igneous-tpu is licensed under the BSD 3-Clause license.")


if __name__ == "__main__":
  main()
