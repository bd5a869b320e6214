"""The pyramid walked in z-slabs.

``ops.pooling._pyramid_impl`` with a slab depth against the whole-cutout
program and the plain reference (``benchmark/reference.py``, exact), the
rule that picks the depth, and uint64 labels through ``ChunkExecutor``
and one ``execute --batch 2`` lease round.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from igneous_tpu.ops import oracle, pooling
from igneous_tpu.ops.pooling import (
  _from_device_layout, _pack_u64_planes, _pyramid_impl, _split_u64_planes,
  _to_device_layout, slab_depth,
)
from igneous_tpu.parallel import executor as executor_mod
from igneous_tpu.parallel.batch_runner import device_pyramid_batch
from igneous_tpu.parallel.executor import ChunkExecutor, make_mesh

F221 = ((2, 2, 1),) * 4
F222 = ((2, 2, 2),) * 2
XY = (20, 12)  # odd extents at deeper mips: the edge is replicated


def _labels(shape, seed):
  """uint64 labels from a small pool, so windows tie: ids >= 2**32, ids
  that differ from another only in the high word, and zeros."""
  rng = np.random.default_rng(seed)
  base = rng.integers(2**32, 2**48, 3, dtype=np.uint64)
  pool = np.concatenate([base, base ^ np.uint64(1 << 40), [0]])
  return pool[rng.integers(0, len(pool), shape)].astype(np.uint64)


def _image(shape, seed):
  return np.random.default_rng(seed).integers(0, 256, shape).astype(np.uint8)


def _program(img, factors, method, sparse, depth):
  """Mips of an (x, y, z) cutout, as (x, y, z) arrays of its dtype."""
  if img.dtype == np.uint64:
    lo, hi = _split_u64_planes(img)
    x = (jnp.asarray(_to_device_layout(lo)), jnp.asarray(_to_device_layout(hi)))
  else:
    x = jnp.asarray(_to_device_layout(img))
  outs = jax.jit(
    lambda x: _pyramid_impl(x, factors, method, sparse, depth))(x)
  if img.dtype == np.uint64:
    return [_pack_u64_planes(_from_device_layout(ol),
                             _from_device_layout(oh))[..., 0]
            for ol, oh in outs]
  return [_from_device_layout(o)[..., 0] for o in outs]


def _expected(img, factors, method, sparse):
  if sparse:  # the reference has no sparse vote: the numpy oracle's
    return oracle.np_downsample_segmentation(img, factors[0], len(factors),
                                             sparse=True)
  return reference.pyramid(img, factors, method)


def _assert_same(got, want):
  assert len(got) == len(want)
  for g, w in zip(got, want):
    assert g.dtype == w.dtype and g.shape == w.shape
    np.testing.assert_array_equal(g, w)


WALKS = [
  (F221, 8, 1), (F221, 8, 2), (F221, 8, 4),
  (F222, 8, 4), (F222, 16, 4), (F222, 16, 8),
]


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
@pytest.mark.parametrize("factors,z,depth", WALKS,
                         ids=[f"{f[0][2]}z{len(f)}-z{z}-d{d}"
                              for f, z, d in WALKS])
def test_u64_mode_slab_walk(factors, z, depth, sparse):
  img = _labels(XY + (z,), seed=z * 31 + depth)
  want = _expected(img, factors, "mode", sparse)
  whole = _program(img, factors, "mode", sparse, None)
  _assert_same(whole, want)
  _assert_same(_program(img, factors, "mode", sparse, depth), whole)


@pytest.mark.parametrize("factors,z,depth", WALKS,
                         ids=[f"{f[0][2]}z{len(f)}-z{z}-d{d}"
                              for f, z, d in WALKS])
def test_u8_average_slab_walk(factors, z, depth):
  img = _image(XY + (z,), seed=z * 17 + depth)
  want = reference.pyramid(img, factors, "average")
  _assert_same(_program(img, factors, "average", False, None), want)
  _assert_same(_program(img, factors, "average", False, depth), want)


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_z_not_a_multiple_of_the_z_factors_walks_one_slab(sparse):
  """z = 6 under (2,2,2)x2: the z edge is replicated, which needs the
  whole cutout, so the rule gives one slab."""
  img = _labels(XY + (6,), seed=6)
  depth = slab_depth(6, F222, plane_bytes=1 << 20, memory_bytes=1 << 20)
  assert depth == 6
  _assert_same(_program(img, F222, "mode", sparse, depth),
               _expected(img, F222, "mode", sparse))


WINDOW_SHAPES = [(1, 5, 7, 9), (2, 4, 8, 16), (1, 3, 5, 11)]
WINDOW_FACTORS = [(2, 2, 1), (2, 2, 2), (3, 3, 1), (1, 2, 3)]


def _edge_padded(x, f):
  """(c, z, y, x) ``x`` edge-replicated to a multiple of ``f`` (x, y, z)."""
  fx, fy, fz = f
  _, z, y, w = x.shape
  return np.pad(x, [(0, 0), (0, -z % fz), (0, -y % fy), (0, -w % fx)],
                mode="edge")


def _with_extremes(shape, dtype, seed):
  """Values of ``dtype`` drawn from its extremes and a few between (NaN
  and infinities for floats)."""
  if np.dtype(dtype) == np.bool_:
    pool = np.array([False, True])
  elif np.issubdtype(dtype, np.floating):
    pool = np.array([-np.inf, -1.5, -0.0, 0, 2.25, np.inf, np.nan], dtype)
  else:
    info = np.iinfo(dtype)
    pool = np.array([info.min, info.min + 1, 0, 7, info.max - 1, info.max],
                    dtype)
  return pool[np.random.default_rng(seed).integers(0, len(pool), shape)]


@pytest.mark.parametrize("dtype",
                         [np.uint8, np.int16, np.uint32, np.float32, np.bool_],
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("f", WINDOW_FACTORS,
                         ids=lambda f: "x".join(map(str, f)))
@pytest.mark.parametrize("shape", WINDOW_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_window_slices_select_the_strided_views(shape, f, dtype):
  """Each window position is the numpy strided view of the edge-padded
  input, in z-major, then y, then x order, the dtype's extremes (and NaN)
  included."""
  x = _with_extremes(shape, dtype, sum(shape))
  fx, fy, fz = f
  padded = _edge_padded(x, f)
  got = jax.jit(lambda a: pooling._window_slices(a, f))(jnp.asarray(x))
  want = [padded[:, dz::fz, dy::fy, dx::fx]
          for dz in range(fz) for dy in range(fy) for dx in range(fx)]
  assert len(got) == len(want)
  for g, w in zip(got, want):
    assert g.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(g), w)


@pytest.mark.parametrize("dtype", [np.uint8, np.uint16, np.int16],
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("f", WINDOW_FACTORS,
                         ids=lambda f: "x".join(map(str, f)))
@pytest.mark.parametrize("shape", WINDOW_SHAPES,
                         ids=lambda s: "x".join(map(str, s)))
def test_narrow_int_average_is_the_rounded_window_mean(shape, f, dtype):
  """The reduce-window sum of ≤16-bit integers: each window of the
  edge-padded input, its mean rounded half up."""
  info = np.iinfo(dtype)
  x = np.random.default_rng(sum(shape) + 1).integers(
    info.min, info.max, shape, endpoint=True).astype(dtype)
  fx, fy, fz = f
  padded = _edge_padded(x, f).astype(np.int64)
  c, z, y, w = padded.shape
  sums = padded.reshape(c, z // fz, fz, y // fy, fy, w // fx, fx).sum(
    axis=(2, 4, 6))
  n = fx * fy * fz
  want = ((sums + n // 2) // n).astype(dtype)
  got = jax.jit(lambda a: pooling._pool_average(a, f))(jnp.asarray(x))
  assert got.dtype == want.dtype
  np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("op", ["min", "max"])
@pytest.mark.parametrize("dtype", [np.uint8, np.int16, np.uint32, np.float32],
                         ids=lambda d: np.dtype(d).name)
@pytest.mark.parametrize("f", WINDOW_FACTORS,
                         ids=lambda f: "x".join(map(str, f)))
@pytest.mark.parametrize("shape", WINDOW_SHAPES[:2],
                         ids=lambda s: "x".join(map(str, s)))
def test_minmax_is_each_windows_extreme(shape, f, dtype, op):
  """The min or max of each window of the edge-padded input, extremes of
  the dtype included; a NaN in a window makes it NaN, as in numpy."""
  x = _with_extremes(shape, dtype, sum(shape) + 2)
  fx, fy, fz = f
  padded = _edge_padded(x, f)
  c, z, y, w = padded.shape
  windows = padded.reshape(c, z // fz, fz, y // fy, fy, w // fx, fx)
  want = getattr(windows, op)(axis=(2, 4, 6))
  got = jax.jit(lambda a: pooling._pool_minmax(a, f, op))(jnp.asarray(x))
  assert got.dtype == want.dtype
  np.testing.assert_array_equal(np.asarray(got), want)


@pytest.mark.parametrize("dtype", [np.uint8, np.int16, np.uint32, np.float32],
                         ids=lambda d: np.dtype(d).name)
def test_pool_striding_takes_each_windows_first_voxel(dtype):
  """Partial edge windows keep their own first voxel: no padding."""
  x = _with_extremes((2, 5, 7, 9), dtype, 3)
  got = jax.jit(lambda a: pooling._pool_striding(a, (3, 2, 2)))(
    jnp.asarray(x))
  assert got.dtype == x.dtype
  np.testing.assert_array_equal(np.asarray(got), x[:, ::2, ::2, ::3])


def test_illegal_depth_is_refused():
  x = jnp.zeros((1, 8, 4, 4), jnp.uint8)
  with pytest.raises(ValueError, match="slab depth"):
    _pyramid_impl(x, F222, "average", False, 2)  # crosses a z window
  with pytest.raises(ValueError, match="slab depth"):
    _pyramid_impl(x, F221, "average", False, 3)  # does not divide z


# one v5e: its published HBM, as a described chip reports it, and the
# capacity its compiler plans against
V5E_MEMORY = [pooling._HBM_BYTES["TPU v5 lite"], int(15.75 * 2**30)]


@pytest.mark.parametrize("memory", V5E_MEMORY)
def test_depth_rule_walks_the_default_u64_task_on_one_v5e(memory):
  """The planner's default uint64 task (2048x2048x64, 4 mips at 2,2,1)
  at --batch 2: both tasks' (lo, hi) planes on one chip."""
  plane_bytes = 2 * 2048 * 2048 * 2 * 4
  depth = slab_depth(64, F221, plane_bytes, memory)
  assert depth == 16
  assert pooling.walk_bytes(64, depth, F221, plane_bytes) <= memory
  assert pooling.walk_bytes(64, 2 * depth, F221, plane_bytes) > memory
  assert slab_depth(64, F221, plane_bytes, None) == 64  # memory unknown


@pytest.mark.parametrize("memory", V5E_MEMORY)
def test_depth_rule_keeps_the_default_jpeg_task_whole(memory):
  """The default uint8 task (4096x4096x64, 5 mips) at --batch 2 fits one
  chip whole: one slab, the whole-cutout program."""
  plane_bytes = 2 * 4096 * 4096
  assert slab_depth(64, ((2, 2, 1),) * 5, plane_bytes, memory) == 64


def test_depth_rule_is_legal_for_every_chain():
  rng = np.random.default_rng(5)
  for _ in range(400):
    n = int(rng.integers(1, 5))
    factors = tuple(tuple(int(v) for v in rng.integers(1, 4, 3))
                    for _ in range(n))
    z = int(rng.integers(1, 130))
    depth = slab_depth(z, factors, int(rng.integers(1, 1 << 20)),
                       int(rng.integers(1, 1 << 30)))
    step = int(np.prod([f[2] for f in factors]))
    assert depth == z or (depth % step == 0 and z % depth == 0), (
      factors, z, depth)


def test_host_backend_reports_no_memory():
  assert pooling.device_memory_bytes(jax.devices()[0]) is None


@pytest.fixture
def small_memory(monkeypatch):
  """Device memory small enough that a test-sized task walks in slabs of
  2 z planes. A fresh executor cache keeps programs compiled under other
  memory out of the test."""
  plane_bytes = 32 * 32 * 8  # one task's (lo, hi) z plane per device
  memory = pooling.walk_bytes(8, 2, F221[:3], plane_bytes)
  monkeypatch.setattr(executor_mod, "device_memory_bytes",
                      lambda device: memory)
  monkeypatch.setattr(executor_mod, "_CHUNK_EXECUTOR_CACHE", {})


def test_chunk_executor_u64_planes_batch_of_two(small_memory):
  imgs = [_labels((32, 32, 8), seed=s) for s in (1, 2)]
  ex = ChunkExecutor(make_mesh(2), factors=F221[:3], method="mode", planes=2)
  outs = device_pyramid_batch(ex, imgs, True)
  for k, img in enumerate(imgs):
    want = reference.pyramid(img, F221[:3], "mode")
    _assert_same([_from_device_layout(o[k])[..., 0] for o in outs], want)
  lo = np.zeros((2, 1, 8, 32, 32), np.uint32)
  assert ex._walk_attrs((lo, lo)) == {"slabs": 4, "slab_depth": 2}


def test_lease_round_of_u64_tasks_is_batched(tmp_path, monkeypatch,
                                             small_memory):
  from igneous_tpu.downsample_scales import create_downsample_scales
  from igneous_tpu.parallel.lease_batcher import poll_batched
  from igneous_tpu.queues import FileQueue
  from igneous_tpu.tasks.image import DownsampleTask
  from igneous_tpu.volume import Volume

  monkeypatch.setenv("IGNEOUS_POOL_HOST", "0")
  shape = (32, 32, 8)
  data = _labels((64, 32, 8), seed=9)
  path = f"file://{tmp_path}/seg"
  vol = Volume.from_numpy(data, path, chunk_size=(8, 8, 8),
                          layer_type="segmentation",
                          encoding="compressed_segmentation")
  create_downsample_scales(vol.meta, 0, shape, (2, 2, 1), num_mips=2)
  vol.commit_info()
  q = FileQueue(f"fq://{tmp_path}/q")
  q.insert_batch([
    DownsampleTask(layer_path=path, mip=0, shape=shape, offset=(x, 0, 0),
                   num_mips=2, factor=(2, 2, 1))
    for x in (0, 32)
  ])
  executed, stats = poll_batched(q, batch_size=2, mesh=make_mesh(2),
                                 stop_fn=lambda executed, empty: empty)
  assert executed == 2
  assert stats["batched"] == 2 and stats["group_fallbacks"] == 0
  assert stats["failed"] == 0 and stats["solo"] == 0
  want = reference.pyramid(data, F221[:2], "mode")
  for mip in (1, 2):
    mv = Volume(path, mip=mip)
    np.testing.assert_array_equal(mv.download(mv.mip_bounds(mip))[..., 0],
                                  want[mip - 1])
