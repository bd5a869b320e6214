"""A cutout's chunks decode and land in the cutout side by side on the
shared IO pool.

``Volume.download`` fans the decode of a multi-chunk compressed cutout out
to ``shared_io_pool()``, and each worker copies the chunk it decoded into
its place in the cutout; ``parallel=1``, single chunks and uncompressed
raw chunks stay serial. These tests hold the pooled decode-and-place to
the serial one byte for byte, F-order, for boxes inside and past the
bounds and for several channels; and check that corruption, the
decoded-chunk cache (never aliased into a cutout), ``fill_missing`` and
the ``workers`` and ``placed`` stats of the ``volume.decode`` span behave
as on the serial path, and that downloads run from the prefetch pool
finish with a narrow IO pool.
"""

import concurrent.futures as cf
import os
import sys

import numpy as np
import pytest

from igneous_tpu import chunk_cache, integrity, telemetry
from igneous_tpu.lib import Bbox
from igneous_tpu.observability import trace
from igneous_tpu.pipeline import encoder
from igneous_tpu.storage import clear_memory_storage
from igneous_tpu.volume import Volume

# 3 x 2 x 2 chunks of 32 x 32 x 16; the last chunk along x and y is
# clamped at the volume's edge
SIZE = (80, 50, 32)
CHUNK = (32, 32, 16)


@pytest.fixture(autouse=True)
def _clean():
  telemetry.reset_all()
  chunk_cache.clear()
  trace.reset()
  integrity.flush_all(swallow=True)
  yield
  integrity.flush_all(swallow=True)
  chunk_cache.clear()
  trace.reset()
  clear_memory_storage()


def _image(rng, dtype):
  if dtype == np.uint8:
    # smooth enough that jpeg keeps some structure, with grain on top
    x = np.arange(SIZE[0])[:, None, None]
    y = np.arange(SIZE[1])[None, :, None]
    z = np.arange(SIZE[2])[None, None, :]
    field = 100 + 60 * np.sin(x / 9.0) * np.cos(y / 7.0) + z
    grain = rng.integers(0, 20, SIZE)
    return np.clip(field + grain, 0, 255).astype(np.uint8)
  # segmentation: blocks of labels above 2**32, so both words matter
  labels = rng.integers(1, 40, (SIZE[0] // 8 + 1, SIZE[1] // 8 + 1,
                                SIZE[2] // 8 + 1)).astype(np.uint64)
  img = labels.repeat(8, 0).repeat(8, 1).repeat(8, 2)[
    :SIZE[0], :SIZE[1], :SIZE[2]]
  return img + np.uint64(2**40)


# (encoding, wire compression, dtype, channels)
CODECS = {
  "jpeg_u8": ("jpeg", None, np.uint8, 1),
  "gzip_raw_u8": ("raw", "gzip", np.uint8, 1),
  "gzip_raw_u8x3": ("raw", "gzip", np.uint8, 3),
  "gzip_raw_u64": ("raw", "gzip", np.uint64, 1),
  "cseg_u64": ("compressed_segmentation", None, np.uint64, 1),
}


def _volume(tmp_path, rng, name):
  encoding, compress, dtype, channels = CODECS[name]
  data = _image(rng, dtype)
  if channels > 1:
    data = np.stack([data, ~data, data // 3], axis=-1)
  path = f"file://{tmp_path}/{name}"
  Volume.from_numpy(data, path, chunk_size=CHUNK, encoding=encoding,
                    compress=compress)
  return path, data


def _decode_spans(fn):
  """Run ``fn`` under a sampled trace context; returns (its result, the
  ``volume.decode`` span records it journaled)."""
  trace.reset()
  with trace.activate(trace.SpanContext("d" * 16, "root", True)):
    out = fn()
  spans = [s for s in trace.drain_spans() if s["name"] == "volume.decode"]
  return out, spans


def _slices(box, origin):
  return tuple(slice(int(a), int(b)) for a, b in zip(
    np.array(box.minpt) - np.array(origin),
    np.array(box.maxpt) - np.array(origin)))


@pytest.mark.parametrize("name", sorted(CODECS))
@pytest.mark.parametrize(
  "region", ["whole", "edge", "missing", "unbounded", "cached"])
def test_pooled_decode_equals_serial(tmp_path, rng, name, region):
  path, data = _volume(tmp_path, rng, name)
  unbounded = region == "unbounded"
  vol = Volume(path, fill_missing=(region == "missing"),
               bounded=not unbounded, background_color=7 if unbounded else 0)
  bounds = vol.meta.bounds(0)
  if region in ("whole", "cached"):
    bbox = bounds
  elif unbounded:
    # past the bounds on both sides of x and z: background outside, the
    # layer's chunks inside
    bbox = Bbox((-5, 10, -3), tuple(int(v) + 6 for v in bounds.maxpt))
  else:
    # off the chunk grid, reaching into the clamped edge chunks
    bbox = Bbox((5, 3, 2), tuple(int(v) for v in bounds.maxpt))
  if region == "missing":
    chunk = Bbox((32, 32, 16), (64, 50, 32))
    key = vol.meta.chunk_name(0, chunk)
    vol.cf.delete([key])

  chunk_cache.clear()
  if region == "cached":
    # both reads below are served from the chunk cache this one fills
    vol.download(bbox, parallel=1)
  serial, serial_spans = _decode_spans(lambda: vol.download(bbox, parallel=1))
  if region != "cached":
    chunk_cache.clear()
  pooled, pooled_spans = _decode_spans(lambda: vol.download(bbox))

  assert pooled.dtype == serial.dtype and pooled.shape == serial.shape
  assert pooled.tobytes("A") == serial.tobytes("A")
  assert serial.flags.f_contiguous and pooled.flags.f_contiguous
  assert [(s["workers"], s["placed"]) for s in serial_spans] == [(1, 0)]
  (span,) = pooled_spans
  assert span["chunks"] == 12 and span["workers"] > 1
  assert span["placed"] == 12
  inner = Bbox.intersection(bbox, bounds)
  if region == "missing":
    assert (pooled[_slices(chunk, bbox.minpt)] == 0).all()
  elif CODECS[name][0] != "jpeg":
    # lossless codecs: the cutout is the source itself
    want = data[_slices(inner, (0, 0, 0))]
    assert np.array_equal(pooled[_slices(inner, bbox.minpt)],
                          want.reshape(want.shape[:3] + (-1,)))
  if unbounded:
    outside = np.ones(pooled.shape[:3], dtype=bool)
    outside[_slices(inner, bbox.minpt)] = False
    assert outside.any() and (pooled[outside] == 7).all()
  if region == "cached":
    assert telemetry.counters_snapshot()["chunk_cache.hits"] == 24
    # no cutout aliases a cached chunk: writing into the ones served from
    # the cache leaves the next read of the box as it was
    want = serial.tobytes("A")
    serial[...] = 3
    pooled[...] = 3
    assert vol.download(bbox, parallel=1).tobytes("A") == want
    assert vol.download(bbox).tobytes("A") == want


def test_corrupt_chunk_among_many_raises_and_quarantines_its_key(
    tmp_path, rng):
  path, _data = _volume(tmp_path, rng, "gzip_raw_u8")
  vol = Volume(path)
  bad = vol.meta.chunk_name(0, Bbox((32, 32, 0), (64, 50, 16)))
  stored = os.path.join(tmp_path, "gzip_raw_u8", bad + ".gz")
  with open(stored, "rb") as f:
    raw = f.read()
  with open(stored, "wb") as f:
    f.write(raw[: len(raw) // 2])  # torn
  chunk_cache.clear()

  (span,) = _decode_spans(lambda: pytest.raises(
    integrity.CorruptChunkError, vol.download, vol.meta.bounds(0)))[1]
  assert span["workers"] > 1 and span["error"] == "CorruptChunkError"
  quarantined = integrity.load_quarantine(path)
  assert [q["key"] for q in quarantined] == [bad]
  assert telemetry.counters_snapshot()["integrity.corrupt_reads"] == 1


def _cache_counts(vol, parallel):
  chunk_cache.clear()
  telemetry.reset_counters()
  bounds = vol.meta.bounds(0)
  first = vol.download(bounds, parallel=parallel)
  second = vol.download(bounds, parallel=parallel)
  counts = telemetry.counters_snapshot()
  return first, second, (counts.get("chunk_cache.hits", 0),
                         counts.get("chunk_cache.misses", 0))


def test_chunk_cache_counts_match_serial(tmp_path, rng, monkeypatch):
  monkeypatch.setenv("IGNEOUS_CHUNK_CACHE", "on")
  path, _data = _volume(tmp_path, rng, "jpeg_u8")
  vol = Volume(path)
  s1, s2, serial = _cache_counts(vol, parallel=1)
  # switch threads often, so a lost update to the cache or its counters
  # would show in the counts
  interval = sys.getswitchinterval()
  sys.setswitchinterval(1e-6)
  try:
    p1, p2, pooled = _cache_counts(vol, parallel=None)
  finally:
    sys.setswitchinterval(interval)
  # the second read of each pair is served from the cache, chunk for chunk
  assert serial == pooled == (12, 12)
  for a in (s1, s2, p1, p2):
    assert np.array_equal(a, s1)


@pytest.mark.parametrize("case,workers", [
  ("raw_uncompressed", 1),
  ("single_chunk", 1),
  ("serial_parallel_1", 1),
  ("gzip_raw", "many"),
])
def test_workers_stat(tmp_path, rng, case, workers):
  compress = None if case == "raw_uncompressed" else "gzip"
  data = _image(rng, np.uint8)
  path = f"file://{tmp_path}/{case}"
  vol = Volume.from_numpy(data, path, chunk_size=CHUNK, compress=compress)
  bbox = vol.meta.bounds(0)
  parallel = None
  if case == "single_chunk":
    bbox = Bbox((0, 0, 0), CHUNK)
  elif case == "serial_parallel_1":
    parallel = 1
  out, (span,) = _decode_spans(lambda: vol.download(bbox, parallel=parallel))
  sl = tuple(slice(int(a), int(b)) for a, b in zip(bbox.minpt, bbox.maxpt))
  assert np.array_equal(out[..., 0], data[sl])
  assert out.flags.f_contiguous
  if workers == "many":
    assert 1 < span["workers"] <= span["chunks"]
    # every chunk lands in the cutout on the pool worker that decoded it
    assert span["placed"] == span["chunks"]
  else:
    assert span["workers"] == workers and span["placed"] == 0


def test_prefetch_pool_downloads_finish_on_a_narrow_io_pool(
    tmp_path, rng, monkeypatch):
  """The worker loop's shape: three cutouts downloaded at once from the
  prefetch pool, each fanning its gets and decodes out to an IO pool of
  width 2. All three finish, with the serial voxels."""
  monkeypatch.setenv("IGNEOUS_PIPELINE_IO_THREADS", "2")
  narrow = cf.ThreadPoolExecutor(max_workers=2,
                                 thread_name_prefix="ig-pipeline-io")
  prefetch = cf.ThreadPoolExecutor(max_workers=3,
                                   thread_name_prefix="ig-pipeline-prefetch")
  monkeypatch.setattr(encoder, "_SHARED_IO", narrow)
  monkeypatch.setattr(encoder, "_SHARED_PREFETCH", prefetch)
  try:
    vols = []
    for name in ("jpeg_u8", "gzip_raw_u64", "cseg_u64"):
      path, _data = _volume(tmp_path, rng, name)
      vols.append(Volume(path))
    serial = [v.download(v.meta.bounds(0), parallel=1) for v in vols]
    chunk_cache.clear()
    futs = [encoder.shared_prefetch_pool().submit(v.download, v.meta.bounds(0))
            for v in vols]
    _done, not_done = cf.wait(futs, timeout=120)
    assert not not_done, "downloads from the prefetch pool did not finish"
    for fut, want in zip(futs, serial):
      assert fut.result().tobytes("A") == want.tobytes("A")
  finally:
    narrow.shutdown(wait=False, cancel_futures=True)
    prefetch.shutdown(wait=False, cancel_futures=True)
