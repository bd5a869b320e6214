"""Host staging of the batched dispatch.

The copies that feed a ``ChunkExecutor`` dispatch and drain it run in
runs of whole z-planes (``ops.pooling._copy_by_planes``), side by side
on the shared copy pool above ``INLINE_COPY_BYTES`` and inline below it;
the input batches come from the executor's free list. These tests hold
the pooled copies to the serial ones byte for byte, the dispatch to the
exact reference pyramid across rounds that reuse one batch, and the free
list to one batch per caller in flight.
"""

import sys
import threading

import numpy as np
import pytest

from benchmark import reference
from igneous_tpu.ops import pooling
from igneous_tpu.ops.pooling import (
  INLINE_COPY_BYTES,
  _pack_u64_planes,
  _plane_runs,
  _split_u64_planes,
  _to_device_layout,
)
from igneous_tpu.parallel import batch_runner
from igneous_tpu.parallel.batch_runner import device_pyramid_batch
from igneous_tpu.parallel.executor import ChunkExecutor, make_mesh
from igneous_tpu.pipeline import config
from igneous_tpu.pipeline.encoder import (
  shared_copy_pool,
  shared_io_pool,
  shared_prefetch_pool,
)

F221 = ((2, 2, 1),) * 2
# (y, x) planes of a little over INLINE_COPY_BYTES / 13 bytes of packed
# uint64 output: 13 z-planes of one task cross the threshold, and 13
# z-planes split unevenly into runs for any pool width but 13 and 1
ABOVE_YX = (256, INLINE_COPY_BYTES // (13 * 256 * 8) + 8)
BELOW_YX = (16, 24)


def _serial_pack(lo, hi):
  """The pack as one thread wrote it before it ran in pieces."""
  if lo.flags["C_CONTIGUOUS"] and hi.flags["C_CONTIGUOUS"]:
    out = np.empty(lo.shape + (2,), dtype=np.uint32)
    out[..., 0] = lo
    out[..., 1] = hi
    return out.view(np.uint64)[..., 0]
  out = np.empty((2,) + lo.shape, dtype=np.uint32, order="F")
  out[0] = lo
  out[1] = hi
  return out.T.view(np.uint64)[..., 0].T


def _planes(shape, seed):
  rng = np.random.default_rng(seed)
  return (rng.integers(0, 2**32, shape, dtype=np.uint32),
          rng.integers(0, 2**32, shape, dtype=np.uint32))


def _labels(shape, seed):
  """uint64 labels from a small pool: ids >= 2**32, ids that differ from
  another only in the high word, and zeros."""
  rng = np.random.default_rng(seed)
  base = rng.integers(2**32, 2**48, 3, dtype=np.uint64)
  pool = np.concatenate([base, base ^ np.uint64(1 << 40), [0]])
  return np.asfortranarray(
    pool[rng.integers(0, len(pool), shape)].astype(np.uint64))


def _assert_identical(got, want):
  assert got.dtype == want.dtype and got.shape == want.shape
  assert got.strides == want.strides
  assert got.tobytes("A") == want.tobytes("A")


def _expected_workers(shape, nbytes):
  if nbytes < INLINE_COPY_BYTES:
    return 1
  return min(config._cores(), len(_plane_runs(shape, nbytes)[0]))


@pytest.mark.skipif(sys.byteorder != "little", reason="plane views")
@pytest.mark.parametrize("k", [1, 2])
@pytest.mark.parametrize("yx", [BELOW_YX, ABOVE_YX], ids=["inline", "pool"])
@pytest.mark.parametrize("order", ["C", "F", "strided"])
def test_pooled_pack_matches_serial_pack(k, yx, order):
  z = 13
  lo, hi = _planes((k, 1, z) + yx, seed=k)
  if order == "F":  # the (x,y,z,c) views the solo path packs
    lo, hi = lo[0].transpose(3, 2, 1, 0), hi[0].transpose(3, 2, 1, 0)
  elif order == "strided":  # neither C nor F contiguous
    lo, hi = lo[..., ::2], hi[..., ::2]
  got = _pack_u64_planes(lo, hi)
  _assert_identical(got, _serial_pack(lo, hi))
  assert got.flags["C_CONTIGUOUS"] == (order == "C")


def test_plane_runs_cover_every_plane_once():
  shape = (2, 1, 13) + ABOVE_YX
  runs, workers = _plane_runs(shape, INLINE_COPY_BYTES)
  assert workers == _expected_workers(shape, INLINE_COPY_BYTES)
  seen = np.zeros(shape[:3], int)
  for index in runs:
    assert len(index) == 3 and index[2].stop > index[2].start
    seen[index] += 1
  assert (seen == 1).all()
  runs, workers = _plane_runs(shape, INLINE_COPY_BYTES - 1)
  assert workers == 1 and runs == [(0, 0, slice(0, 13)), (1, 0, slice(0, 13))]


@pytest.mark.parametrize("yx", [BELOW_YX, ABOVE_YX], ids=["inline", "pool"])
@pytest.mark.parametrize("channel", [False, True], ids=["xyz", "xyzc"])
def test_split_into_staging_matches_split_planes(yx, channel):
  shape = yx[::-1] + (13,) + ((1,) if channel else ())
  imgs = [_labels(shape, seed=s) for s in (3, 4)]
  imgs[1] = np.ascontiguousarray(imgs[1])  # C-order cutouts split too
  batch = (len(imgs), 1, 13) + yx
  lo, hi = (np.full(batch, 0xDEADBEEF, np.uint32) for _ in range(2))
  workers = batch_runner._stack_into(
    (lo, hi), batch_runner._batch_sources(imgs, True))
  assert workers == _expected_workers(batch, sum(i.nbytes for i in imgs))
  for b, p in ((lo, 0), (hi, 1)):
    want = np.stack([_to_device_layout(_split_u64_planes(i)[p])
                     for i in imgs])
    np.testing.assert_array_equal(b, want)


@pytest.mark.parametrize("yx", [BELOW_YX, (512, 2600)],
                         ids=["inline", "pool"])
def test_stack_into_staging_matches_np_stack(yx):
  rng = np.random.default_rng(5)
  imgs = [np.asfortranarray(rng.integers(0, 256, yx[::-1] + (13,))
                            .astype(np.uint8)) for _ in range(2)]
  batch = np.full((2, 1, 13) + yx, 7, np.uint8)
  workers = batch_runner._stack_into(
    (batch,), batch_runner._batch_sources(imgs, False))
  assert workers == _expected_workers(batch.shape, batch.nbytes)
  np.testing.assert_array_equal(
    batch, np.stack([_to_device_layout(i) for i in imgs]))


def _mips(outs, k):
  return [pooling._from_device_layout(o[k])[..., 0] for o in outs]


@pytest.mark.parametrize("planes", [1, 2])
def test_rounds_through_one_executor_reuse_its_batch(planes):
  if planes == 2:
    ex = ChunkExecutor(make_mesh(2), factors=F221, method="mode", planes=2)
    make = lambda seed: _labels((32, 24, 8), seed)  # noqa: E731
    method = "mode"
  else:
    ex = ChunkExecutor(make_mesh(2), factors=F221, method="average")
    make = lambda seed: np.asfortranarray(  # noqa: E731
      np.random.default_rng(seed).integers(0, 256, (32, 24, 8))
      .astype(np.uint8))
    method = "average"
  rounds = [[make(10 * r + k) for k in range(2)] for r in range(2)]
  first = device_pyramid_batch(ex, rounds[0], planes == 2)
  kept = [np.array(m) for m in first]
  ((key, (staged,)),) = [(k, v) for k, v in ex._staging._d.items()]
  second = device_pyramid_batch(ex, rounds[1], planes == 2)
  # round 2 refilled round 1's batch, and round 1's mips did not move
  assert len(ex._staging[key]) == 1 and ex._staging[key][0] is staged
  for m, k in zip(first, kept):
    np.testing.assert_array_equal(m, k)
  for imgs, outs in zip(rounds, (first, second)):
    for k, img in enumerate(imgs):
      want = reference.pyramid(img, F221, method)
      for got, w in zip(_mips(outs, k), want):
        assert got.dtype == w.dtype
        np.testing.assert_array_equal(got, w)


def test_failed_dispatch_keeps_its_batch_off_the_free_list():
  ex = ChunkExecutor(make_mesh(1), factors=F221, method="average")

  def fill(arrays, reused):
    raise RuntimeError("fill failed")

  with pytest.raises(RuntimeError):
    ex.dispatch_staged((1, 1, 8, 16, 16), np.uint8, fill)
  assert len(ex._staging) == 0


class _Gated(ChunkExecutor):
  """Dispatches only once ``barrier`` has as many callers in flight."""

  barrier = None

  def __call__(self, batch):
    arrs = batch if isinstance(batch, tuple) else (batch,)
    self.seen.append(arrs[0])
    self.barrier.wait(timeout=60)
    return super().__call__(batch)


@pytest.mark.parametrize("planes", [1, 2])
def test_two_threads_dispatch_on_one_executor(planes):
  method = "mode" if planes == 2 else "average"
  ex = _Gated(make_mesh(1), factors=F221, method=method, planes=planes)
  ex.seen = []
  ex.barrier = threading.Barrier(1)
  if planes == 2:
    imgs = [[_labels((32, 24, 8), s)] for s in (1, 2, 3)]
  else:
    imgs = [[np.random.default_rng(s).integers(0, 256, (32, 24, 8))
             .astype(np.uint8)] for s in (1, 2, 3)]
  device_pyramid_batch(ex, imgs[0], planes == 2)  # compiles, stages one
  ex.barrier = threading.Barrier(2)
  results = {}

  def run(i):
    results[i] = device_pyramid_batch(ex, imgs[i], planes == 2)

  threads = [threading.Thread(target=run, args=(i,)) for i in (1, 2)]
  for t in threads:
    t.start()
  for t in threads:
    t.join(timeout=120)
    assert not t.is_alive()
  # both in flight at once, each on its own batch: one reused, one new
  assert ex.seen[1] is not ex.seen[2]
  assert any(s is ex.seen[0] for s in ex.seen[1:])
  for i in (1, 2):
    want = reference.pyramid(imgs[i][0], F221, method)
    for got, w in zip(_mips(results[i], 0), want):
      np.testing.assert_array_equal(got, w)


class _Checking(ChunkExecutor):
  """A dispatch that checks its batch still holds what its fill wrote."""

  def __call__(self, batch):
    arrs = batch if isinstance(batch, tuple) else (batch,)
    mark = int(arrs[0].flat[0])
    threading.Event().wait(0.0005)
    for a in arrs:
      assert (a == mark).all(), "a batch was shared between callers"
    return [], 0


def test_free_list_stress_gives_each_caller_its_own_batch():
  ex = _Checking(make_mesh(1), factors=F221, method="average")
  interval = sys.getswitchinterval()
  errors = []

  def run(i):
    try:
      for j in range(40):
        ex.dispatch_staged((1, 1, 4, 8, 8), np.uint8,
                           lambda arrs, reused, m=(i * 40 + j) % 251:
                           arrs[0].fill(m))
    except Exception as e:  # noqa: BLE001 - reported below
      errors.append(e)

  sys.setswitchinterval(1e-6)
  try:
    threads = [threading.Thread(target=run, args=(i,))
               for i in range(2 * config._cores() + 1)]
    for t in threads:
      t.start()
    for t in threads:
      t.join(timeout=120)
      assert not t.is_alive()
  finally:
    sys.setswitchinterval(interval)
  assert not errors, errors[0]
  (free,) = ex._staging._d.values()
  assert 1 <= len(free) <= len(threads)
  assert len({id(a[0]) for a in free}) == len(free)


def _copy_threads():
  return sum(t.name.startswith("ig-pipeline-copy")
             for t in threading.enumerate())


def test_copy_pool_is_persistent_and_its_own():
  pool = shared_copy_pool()
  assert pool is shared_copy_pool()
  assert pool is not shared_io_pool() and pool is not shared_prefetch_pool()
  lo, hi = _planes((2, 1, 13) + ABOVE_YX, seed=9)
  counts = []
  for _ in range(6):
    _pack_u64_planes(lo, hi)
    counts.append(_copy_threads())
  assert counts[-1] <= config._cores()
  assert counts[-1] == counts[-3]
