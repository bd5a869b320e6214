"""Compile-only checks of the main-path kernels for a described v5e chip.

Nothing runs: each test lowers one kernel at the width chip_smoke.py
drives it and compiles it with the TPU compiler for ``v5e:2x2`` devices
that are described, not attached. The compiler refuses here what it would
refuse on the chip (Mosaic lowering, VMEM, HBM fit), at no chip time.
The topology is described inside a fixture (never at import) and these
tests stay in this one file: only one process may load libtpu.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

FACTORS4 = ((2, 2, 1),) * 4


@pytest.fixture(scope="module")
def topo():
  from jax.experimental import topologies

  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  try:
    return topologies.get_topology_desc(
      platform="tpu", topology_name="v5e:2x2"
    )
  except Exception as e:
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
  return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
  return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
  compiled = fn.lower(*args, **static).compile()
  assert compiled.as_text()
  return compiled


def test_average_pyramid_u8(one_chip):
  from igneous_tpu.ops.pooling import _jit_pyramid

  x = _spec((1, 64, 2048, 2048), jnp.uint8, one_chip)
  _compile(_jit_pyramid, x, FACTORS4, "average", False)


def test_mode_pyramid_u32_planes(one_chip):
  from igneous_tpu.ops.pooling import _jit_pyramid

  plane = _spec((1, 64, 1024, 1024), jnp.uint32, one_chip)
  _compile(_jit_pyramid, (plane, plane), FACTORS4, "mode", False)


@pytest.mark.parametrize("chips,k,method", [
  (1, 4, "average"),  # chip_smoke P1: execute --batch 4 on one chip
  (4, 8, "average"),  # chip_smoke --chips 4: --batch 8 over a 2x2 mesh
  (1, 4, "mode"),     # chip_smoke P2: uint64 labels as u32 plane pairs
])
def test_batched_chunk_pyramid(topo, chips, k, method):
  """The lease batcher's ChunkExecutor program, sharded over the mesh."""
  from igneous_tpu.parallel.executor import ChunkExecutor

  mesh = Mesh(np.asarray(topo.devices[:chips]), ("chunks",))
  planes = 2 if method == "mode" else 1
  ex = ChunkExecutor(mesh, factors=FACTORS4, method=method, planes=planes)
  shape, dtype = (
    ((k, 1, 64, 1024, 1024), jnp.uint8) if planes == 1
    else ((k, 1, 64, 512, 512), jnp.uint32)
  )
  arr = _spec(shape, dtype, NamedSharding(mesh, P("chunks")))
  compiled = _compile(ex._fn, (arr,) * planes)
  if chips > 1:
    assert "all-reduce" in compiled.as_text()  # the nonzero psum


def test_default_u64_task_batch_of_two_fits_one_chip(topo):
  """The planner's default uint64 segmentation task (2048x2048x64, 4 mips
  at 2,2,1) at --batch 2 on one chip: the pyramid walks z-slabs, so its
  temporaries are one slab's and the program fits with room to spare."""
  from igneous_tpu.parallel.executor import ChunkExecutor

  mesh = Mesh(np.asarray(topo.devices[:1]), ("chunks",))
  ex = ChunkExecutor(mesh, factors=FACTORS4, method="mode", planes=2)
  plane = _spec((2, 1, 64, 2048, 2048), jnp.uint32,
                NamedSharding(mesh, P("chunks")))
  assert ex.slab_depth(plane.shape, [plane.dtype] * 2) < 64
  mem = _compile(ex._fn, (plane, plane)).memory_analysis()
  total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
           + mem.temp_size_in_bytes)
  assert total < 12e9, total


@pytest.mark.parametrize("method,planes,shape,dtype,mips", [
  ("average", 1, (2, 1, 64, 4096, 4096), jnp.uint8, 5),  # em_ds.jpeg
  ("mode", 2, (2, 1, 64, 2048, 2048), jnp.uint32, 4),    # seg_ds.dense
])
def test_benchmark_pyramids_select_windows_without_gathers(
    topo, method, planes, shape, dtype, mips):
  """The benchmark cells' ChunkExecutor programs at --batch 2 on one chip
  read their pooling windows without gathers (mode through reduce-windows
  of one-voxel windows at the pooling strides, the uint8 average through
  one reduce-window sum)."""
  from igneous_tpu.parallel.executor import ChunkExecutor

  mesh = Mesh(np.asarray(topo.devices[:1]), ("chunks",))
  ex = ChunkExecutor(mesh, factors=((2, 2, 1),) * mips, method=method,
                     planes=planes)
  arr = _spec(shape, dtype, NamedSharding(mesh, P("chunks")))
  hlo = _compile(ex._fn, (arr,) * planes).as_text()
  gathers = hlo.count(" gather(")
  assert gathers == 0, gathers


def test_paged_pyramid_default_page_batch(one_chip):
  from igneous_tpu.parallel.paged import (
    _make_page_kernel, page_round_cap, page_shape,
  )

  kernel = jax.jit(jax.vmap(_make_page_kernel(FACTORS4, "average", False, 1)))
  n = page_round_cap(1)
  pages = _spec((n, 1) + tuple(page_shape()), jnp.uint8, one_chip)
  exts = _spec((n, 3), jnp.int32, one_chip)
  _compile(kernel, (pages, exts))


def test_ccl_tiled_default(one_chip):
  from igneous_tpu.ops.ccl import _ccl_tiled_kernel, _tile_shape

  labels = _spec((64, 256, 256), jnp.int32, one_chip)
  _compile(_ccl_tiled_kernel, labels, connectivity=6, algo="scan",
           tile=_tile_shape())


def test_edt(one_chip):
  from igneous_tpu.ops.edt import _DEFAULT_LINE_BLOCK, _edt_sq_kernel

  labels = _spec((66, 130, 130), jnp.int32, one_chip)
  _compile(_edt_sq_kernel, labels, anisotropy=(8.0, 8.0, 40.0),
           line_block=_DEFAULT_LINE_BLOCK)


def test_mc_count(one_chip):
  from igneous_tpu.ops.mesh import _mc_count_kernel

  _compile(_mc_count_kernel, _spec((256, 256, 256), jnp.uint8, one_chip))


def test_mc_emit(one_chip):
  """A P4 label's signature; before PR 21 it took ~35 s to compile."""
  from igneous_tpu.ops.mesh import _mc_emit_kernel

  cells = _spec((63, 127, 127), jnp.int32, one_chip)
  _compile(_mc_emit_kernel, cells, cells, capacity=1 << 16)
