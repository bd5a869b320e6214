"""Benchmark: downsample mip0→4 throughput on the chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "vox/s", "vs_baseline": N, "detail": {...}}

Headline metric: sustained device-kernel throughput of the real pooling
pyramid (average uint8 and mode/COUNTLESS uint64, mip 0→4) on chunk
batches resident in HBM — kernel-vs-kernel against the numpy oracle
credited with perfect 8-core scaling. This mirrors how the reference's
tinybrain numbers are kernel-level (SURVEY.md §6).

detail.e2e_* reports the full pipeline (mem:// volumes, LocalTaskQueue,
codecs, host↔device transfers).

Runs in one process on the chip: with no TPU it exits non-zero and
prints no number.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np

from igneous_tpu.analysis import knobs

QUICK = bool(int(os.environ.get("BENCH_QUICK", "0")))
_REPO_DIR = os.path.dirname(os.path.abspath(__file__))

IMG_SHAPE = (512, 512, 64) if QUICK else (1024, 1024, 128)
SEG_SHAPE = (256, 256, 64) if QUICK else (512, 512, 256)
NUM_MIPS = 4
KERNEL_ITERS = 3 if QUICK else 10


# ---------------------------------------------------------------------------
# data


def make_data():
  rng = np.random.default_rng(0)
  img = rng.integers(0, 255, size=IMG_SHAPE).astype(np.uint8)
  blocks = rng.integers(1, 2**40, size=(16, 16, 16)).astype(np.uint64)
  reps = [s // 16 for s in SEG_SHAPE]
  seg = np.kron(blocks, np.ones(reps, dtype=np.uint64))
  seg[rng.random(SEG_SHAPE) < 0.02] = 0
  return img, seg


# ---------------------------------------------------------------------------
# kernel-level (device-resident)


def bench_device_kernels(img, seg):
  import jax
  import jax.numpy as jnp
  from functools import partial

  from igneous_tpu.ops.pooling import _pyramid_impl, _to_device_layout

  factors = tuple([(2, 2, 1)] * NUM_MIPS)

  # Timing materializes a scalar that depends on every output; the salt
  # defeats any duplicate-dispatch caching.
  @partial(jax.jit, static_argnames=())
  def step(xi, lo, hi, salt):
    o_avg = _pyramid_impl(xi + salt.astype(xi.dtype), factors, "average", False)
    o_mode = _pyramid_impl(
      (lo ^ salt.astype(lo.dtype), hi), factors, "mode", False
    )
    chk = jnp.sum(o_avg[-1].astype(jnp.int32))
    for om in o_mode[-1]:
      chk = chk + jnp.sum(om.astype(jnp.int32))
    return chk

  xi = jax.device_put(_to_device_layout(img))
  lo = jax.device_put(_to_device_layout((seg & np.uint64(0xFFFFFFFF)).astype(np.uint32)))
  hi = jax.device_put(_to_device_layout((seg >> np.uint64(32)).astype(np.uint32)))

  float(step(xi, lo, hi, jnp.uint32(0)))  # compile + settle transfers

  t0 = time.perf_counter()
  for i in range(KERNEL_ITERS):
    float(step(xi, lo, hi, jnp.uint32(i + 1)))
  dt = (time.perf_counter() - t0) / KERNEL_ITERS
  return (img.size + seg.size) / dt


BEST_OF_N = 2 if QUICK else 3


RAW_SAMPLES: dict = {}  # callsite key -> every sample taken this run


def _best_of(once, n=BEST_OF_N, record=None):
  """Best-of-N throughput sampling. A single sample taken in a contended
  scheduler window can underreport by orders of magnitude (the round-3
  artifact recorded 46x below the real rate); the max over N samples is
  the least-contended estimate of what the kernels actually sustain.
  ``record`` keeps the raw samples (RAW_SAMPLES, emitted in the artifact)
  so cross-round comparisons can use min/median too — r01/r02 artifacts
  were single-sample and are comparable on median, not max."""
  samples = [once() for _ in range(n)]
  if record is not None:
    RAW_SAMPLES.setdefault(record, []).extend(samples)
  return max(samples)


def _sample_stats():
  return {
    key: {
      "n": len(s),
      "min": round(min(s), 1),
      "median": round(float(np.median(s)), 1),
      "max": round(max(s), 1),
    }
    for key, s in RAW_SAMPLES.items()
  }


def bench_cpu_kernels(img, seg):
  """Single-core CPU comparator rate (best-of-N). Prefers the native C++
  pooling kernels (oracle-verified semantics twins — the closest in-image
  stand-in for tinybrain, which a zero-egress build cannot vendor);
  falls back to the numpy oracles when no toolchain exists."""
  from igneous_tpu.native import pooling_lib
  from igneous_tpu.ops import oracle

  pooling_lib()  # build/load outside the timed region (g++ on first use)
  if (
    oracle.native_downsample_with_averaging(
      img[:64, :64, :16], (2, 2, 1), 1, parallel=1
    ) is not None
    and oracle.native_downsample_segmentation(
      seg[:64, :64, :16], (2, 2, 1), 1, parallel=1
    ) is not None
  ):
    def once():
      t0 = time.perf_counter()
      oracle.native_downsample_with_averaging(img, (2, 2, 1), NUM_MIPS, parallel=1)
      oracle.native_downsample_segmentation(seg, (2, 2, 1), NUM_MIPS, parallel=1)
      return (img.size + seg.size) / (time.perf_counter() - t0)
    return _best_of(once, BEST_OF_N, record="cpu_1core"), "native-C++ pooling x8-core credit"

  def once():
    t0 = time.perf_counter()
    oracle.np_downsample_with_averaging(img, (2, 2, 1), NUM_MIPS)
    oracle.np_downsample_segmentation(seg, (2, 2, 1), NUM_MIPS)
    return (img.size + seg.size) / (time.perf_counter() - t0)
  return _best_of(once, BEST_OF_N, record="cpu_1core"), "numpy-oracle kernels x8-core credit"


# ---------------------------------------------------------------------------
# end-to-end pipeline (includes storage codecs + transfers)


def _build_volumes(img, seg):
  from igneous_tpu.volume import Volume

  Volume.from_numpy(
    img, "mem://bench/img", chunk_size=(128, 128, 64), layer_type="image"
  )
  Volume.from_numpy(
    seg, "mem://bench/seg", chunk_size=(128, 128, 64), layer_type="segmentation"
  )


def _run_pipeline(path, sparse=False):
  from igneous_tpu import task_creation as tc
  from igneous_tpu.queues import LocalTaskQueue

  tasks = tc.create_downsampling_tasks(
    path, mip=0, num_mips=NUM_MIPS, sparse=sparse, compress=None,
    memory_target=int(1e9),
  )
  LocalTaskQueue(parallel=1, progress=False).insert(tasks)


def _timed_e2e(img, seg):
  from igneous_tpu.storage import clear_memory_storage

  clear_memory_storage()
  _build_volumes(img, seg)
  _run_pipeline("mem://bench/img")  # warmup compiles
  _run_pipeline("mem://bench/seg")
  clear_memory_storage()
  _build_volumes(img, seg)
  t0 = time.perf_counter()
  _run_pipeline("mem://bench/img")
  _run_pipeline("mem://bench/seg")
  dt = time.perf_counter() - t0
  return (img.size + seg.size) / dt


def bench_e2e(img, seg):
  """(serial_rate, pipeline_rate): the same task stream with the staged
  pipeline off (strict per-task serial — the pre-ISSUE-3 path, r05's
  e2e_pipeline_voxps comparable) and on (the ISSUE 3 subsystem)."""
  os.environ["IGNEOUS_PIPELINE"] = "off"
  try:
    serial = _timed_e2e(img, seg)
  finally:
    os.environ.pop("IGNEOUS_PIPELINE", None)
  pipelined = _timed_e2e(img, seg)
  return serial, pipelined


def bench_trace_overhead(img, seg):
  """(trace_overhead_pct, per-stage span summary) — ISSUE 5 acceptance:
  tracing at default sampling must cost <2% of e2e_pipeline wall time.
  Measures the SAME pipelined e2e stream with IGNEOUS_TRACE_SAMPLE=0
  (spans never allocate) vs =1 (every span records); the span batch from
  the traced run doubles as the per-stage summary BENCH reports."""
  from igneous_tpu.observability import trace as trace_mod

  prev = knobs.raw("IGNEOUS_TRACE_SAMPLE")

  def restore():
    if prev is None:
      os.environ.pop("IGNEOUS_TRACE_SAMPLE", None)
    else:
      os.environ["IGNEOUS_TRACE_SAMPLE"] = prev

  # interleaved pairs: on a shared 1-core host, run-to-run drift
  # (several %) exceeds the overhead being measured; back-to-back off/on
  # pairs + a median over 5 ratios keeps the recorded number honest
  off_rates, on_rates = [], []
  try:
    os.environ["IGNEOUS_TRACE_SAMPLE"] = "1"
    _timed_e2e(img, seg)  # discarded: pools/codecs/compiles all warm
    for _ in range(5):
      os.environ["IGNEOUS_TRACE_SAMPLE"] = "0"
      off_rates.append(_timed_e2e(img, seg))
      os.environ["IGNEOUS_TRACE_SAMPLE"] = "1"
      trace_mod.reset()  # only the LAST traced run's spans feed the summary
      on_rates.append(_timed_e2e(img, seg))
  finally:
    restore()
  # median of PAIRED ratios: each off/on pair ran back-to-back, so the
  # ratio cancels drift that max-of-runs would fold into the overhead
  ratios = sorted(
    off / on - 1.0 for off, on in zip(off_rates, on_rates) if on
  )
  overhead_pct = ratios[len(ratios) // 2] * 100.0 if ratios else None

  spans = trace_mod.drain_spans()
  by_name = {}
  for rec in spans:
    s = by_name.setdefault(rec["name"], {"count": 0, "total_s": 0.0})
    s["count"] += 1
    s["total_s"] += rec.get("dur", 0.0)
  summary = {
    name: {"count": s["count"], "total_s": round(s["total_s"], 4)}
    for name, s in sorted(by_name.items())
  }
  return (
    round(overhead_pct, 2) if overhead_pct is not None
    else _skip("no successful traced/untraced rate pairs"),
    summary,
  )


def bench_integrity_overhead(img, seg):
  """integrity_overhead_pct — ISSUE 16 acceptance: the checksummed
  write envelope (blake2b digest per put + batched manifest flushes)
  must cost <=5% of e2e_pipeline wall time on the clean path. Same
  interleaved-pair methodology as bench_trace_overhead: back-to-back
  off/on runs, median of paired ratios."""
  from igneous_tpu import integrity

  prev = knobs.raw("IGNEOUS_INTEGRITY")

  def restore():
    if prev is None:
      os.environ.pop("IGNEOUS_INTEGRITY", None)
    else:
      os.environ["IGNEOUS_INTEGRITY"] = prev

  off_rates, on_rates = [], []
  try:
    os.environ["IGNEOUS_INTEGRITY"] = "1"
    _timed_e2e(img, seg)  # discarded: pools/codecs/compiles all warm
    for _ in range(5):
      os.environ["IGNEOUS_INTEGRITY"] = "off"
      off_rates.append(_timed_e2e(img, seg))
      os.environ["IGNEOUS_INTEGRITY"] = "1"
      on_rates.append(_timed_e2e(img, seg))
  finally:
    restore()
    integrity.flush_all(swallow=True)
  ratios = sorted(
    off / on - 1.0 for off, on in zip(off_rates, on_rates) if on
  )
  if not ratios:
    return _skip("no successful envelope-on/off rate pairs")
  return round(ratios[len(ratios) // 2] * 100.0, 2)


def _run_batched(img, seg, mesh=None):
  from igneous_tpu.parallel.batch_runner import batched_downsample
  from igneous_tpu.storage import clear_memory_storage

  def run():
    batched_downsample(
      "mem://bench/img", mip=0, num_mips=NUM_MIPS,
      shape=(512, 512, 64), compress=None, mesh=mesh,
    )
    batched_downsample(
      "mem://bench/seg", mip=0, num_mips=NUM_MIPS,
      shape=(256, 256, 64), compress=None, mesh=mesh,
    )

  clear_memory_storage()
  _build_volumes(img, seg)
  run()  # warmup compiles
  clear_memory_storage()
  _build_volumes(img, seg)
  t0 = time.perf_counter()
  run()
  dt = time.perf_counter() - t0
  return (img.size + seg.size) / dt


def bench_e2e_batched(img, seg):
  """The production TPU path: K-cutout device dispatches with
  double-buffered download/upload (parallel/batch_runner.py) instead of
  one task at a time. Returns (host_path_rate, device_path_rate_or_None,
  path_label): the host rate keeps cross-round continuity; the device
  rate exercises the device-pool batched path whenever ANY mesh exists
  (virtual CPU devices included)."""
  host_rate = _run_batched(img, seg)

  import jax

  device_rate, label = None, "host-native (no mesh available)"
  if jax.device_count() > 1 or jax.default_backend() == "tpu":
    from igneous_tpu.parallel.executor import make_mesh

    os.environ["IGNEOUS_POOL_HOST"] = "0"  # pin the device pool path
    try:
      device_rate = _run_batched(img, seg, mesh=make_mesh())
    finally:
      os.environ.pop("IGNEOUS_POOL_HOST", None)
    label = (
      f"device-pool over {jax.device_count()} "
      f"{jax.default_backend()} device(s)"
    )
  return host_rate, device_rate, label


def measure_inflate_MBps(seg):
  """gunzip bandwidth of one stored chunk — the storage-codec wall that
  bounds any serial e2e rate on gzip-ingested layers (on an N-core host
  the pipeline can hide up to (N-1)/N of it behind compute)."""
  import gzip

  raw = np.ascontiguousarray(seg[:128, :128, :64]).tobytes()
  gz = gzip.compress(raw, compresslevel=6, mtime=0)
  rates = []
  for _ in range(3):
    t0 = time.perf_counter()
    gzip.decompress(gz)
    rates.append(len(raw) / (time.perf_counter() - t0) / 1e6)
  return round(max(rates), 1)


def bench_codecs(img, seg):
  """Per-codec bandwidth table (ISSUE 4 satellite): MB/s of DECODED bytes
  through each chunk codec + wire compressor, on one stored-chunk-sized
  cutout of the bench fixtures. ``cseg``/``compresso`` run their
  production path (native C++ where a toolchain exists, bulk-NumPy
  otherwise); ``zstd`` is None when the codec doesn't ship."""
  from igneous_tpu import codecs
  from igneous_tpu.storage import compress_bytes, decompress_bytes

  chunk = np.asfortranarray(seg[:128, :128, :64, np.newaxis])
  u8chunk = np.asfortranarray(img[:128, :128, :64, np.newaxis])
  out = {}

  def rate(nbytes, fn, n=3):
    best = min(_timed(fn) for _ in range(n))
    return round(nbytes / best / 1e6, 1)

  def _timed(fn):
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0

  raw_bytes = codecs.encode(chunk, "raw")
  out["raw_encode_MBps"] = rate(chunk.nbytes, lambda: codecs.encode(chunk, "raw"))
  out["raw_decode_MBps"] = rate(
    chunk.nbytes,
    lambda: codecs.decode(raw_bytes, "raw", chunk.shape, chunk.dtype, writable=False),
  )
  cs = codecs.encode(chunk, "compressed_segmentation")
  out["cseg_encode_MBps"] = rate(
    chunk.nbytes, lambda: codecs.encode(chunk, "compressed_segmentation")
  )
  out["cseg_decode_MBps"] = rate(
    chunk.nbytes,
    lambda: codecs.decode(cs, "compressed_segmentation", chunk.shape, chunk.dtype),
  )
  cp = codecs.encode(chunk, "compresso")
  out["compresso_encode_MBps"] = rate(
    chunk.nbytes, lambda: codecs.encode(chunk, "compresso")
  )
  out["compresso_decode_MBps"] = rate(
    chunk.nbytes, lambda: codecs.decode(cp, "compresso", chunk.shape, chunk.dtype)
  )
  # wire compressors measured over the raw u8 image chunk (the EM-image
  # common case; segmentation normally rides cseg/compresso underneath)
  u8raw = codecs.encode(u8chunk, "raw")
  gz = compress_bytes(u8raw, "gzip")
  out["gzip_deflate_MBps"] = rate(len(u8raw), lambda: compress_bytes(u8raw, "gzip"))
  out["gzip_inflate_MBps"] = rate(len(u8raw), lambda: decompress_bytes(gz, "gzip"))
  try:
    zs = compress_bytes(u8raw, "zstd")
    out["zstd_deflate_MBps"] = rate(len(u8raw), lambda: compress_bytes(u8raw, "zstd"))
    out["zstd_inflate_MBps"] = rate(len(u8raw), lambda: decompress_bytes(zs, "zstd"))
  except ImportError:
    out["zstd_deflate_MBps"] = _skip("zstandard not installed")
    out["zstd_inflate_MBps"] = _skip("zstandard not installed")
  return out


def bench_cseg_speedup():
  """Fast cseg paths vs the per-block loop reference (ISSUE 4 tentpole
  acceptance), on two fixtures: ``uniform`` — 16^3-celled segmentation
  (the realistic connectomics case: blocks interior to one object
  dominate, F-ordered like a download cutout); ``mixed`` — the same chunk
  with 2%% salt noise so nearly every block takes the sort path (worst
  case). ``fast`` is the production compress/decompress (native C++ here
  when a toolchain exists); ``numpy`` pins the pure bulk-NumPy fallback
  (IGNEOUS_TPU_NO_NATIVE honored per call)."""
  from igneous_tpu import cseg

  rng = np.random.default_rng(7)
  cells = rng.integers(1, 2**40, size=(8, 8, 4)).astype(np.uint64)
  uniform = np.asfortranarray(np.kron(cells, np.ones((16, 16, 16), np.uint64)))
  mixed = uniform.copy(order="F")
  mixed[rng.random(mixed.shape) < 0.02] = 0
  out = {}
  for name, labels in (("uniform", uniform), ("mixed", mixed)):
    shape4 = labels.shape + (1,)
    t0 = time.perf_counter()
    cseg._encode_channel_loop(labels, (8, 8, 8))
    enc_loop = time.perf_counter() - t0
    data = cseg.compress(labels)
    t0 = time.perf_counter()
    cseg._decompress_loop(data, shape4, np.uint64)
    dec_loop = time.perf_counter() - t0

    def best(fn, n=3):
      best_t = 1e9
      for _ in range(n):
        t0 = time.perf_counter()
        fn()
        best_t = min(best_t, time.perf_counter() - t0)
      return best_t

    enc_fast = best(lambda: cseg.compress(labels))
    dec_fast = best(lambda: cseg.decompress(data, shape4, np.uint64))
    os.environ["IGNEOUS_TPU_NO_NATIVE"] = "1"
    try:
      enc_np = best(lambda: cseg.compress(labels))
      dec_np = best(lambda: cseg.decompress(data, shape4, np.uint64))
    finally:
      os.environ.pop("IGNEOUS_TPU_NO_NATIVE", None)
    out[name] = {
      "encode_loop_ms": round(enc_loop * 1e3, 1),
      "decode_loop_ms": round(dec_loop * 1e3, 1),
      "fast_encode_speedup": round(enc_loop / enc_fast, 1),
      "fast_decode_speedup": round(dec_loop / dec_fast, 1),
      "numpy_encode_speedup": round(enc_loop / enc_np, 1),
      "numpy_decode_speedup": round(dec_loop / dec_np, 1),
    }
  return out


def bench_transfer_passthrough(seg):
  """Aligned same-geometry transfer throughput (ISSUE 4 tentpole): the
  compressed-domain passthrough (stored bytes move verbatim) vs the same
  transfer forced down the decode/re-encode path. Returns
  (passthrough_voxps, decode_voxps)."""
  from igneous_tpu import chunk_cache
  from igneous_tpu.storage import clear_memory_storage
  from igneous_tpu.tasks.image import TransferTask
  from igneous_tpu.volume import Volume

  sub = np.ascontiguousarray(seg[:256, :256, :128])
  clear_memory_storage()
  src = Volume.from_numpy(
    sub, "mem://bench/xfer_src", chunk_size=(128, 128, 64),
    layer_type="segmentation", encoding="compressed_segmentation",
  )

  def run_transfer(dest_path):
    chunk_cache.clear()
    task = TransferTask(
      src_path="mem://bench/xfer_src", dest_path=dest_path, mip=0,
      shape=sub.shape, offset=(0, 0, 0), skip_downsamples=True,
    )
    Volume.create(
      dest_path, Volume("mem://bench/xfer_src").info,
    )
    t0 = time.perf_counter()
    task.execute()
    return sub.size / (time.perf_counter() - t0)

  passthrough = max(run_transfer(f"mem://bench/xfer_pt{i}") for i in range(2))
  os.environ["IGNEOUS_TRANSFER_PASSTHROUGH"] = "off"
  try:
    decode = max(run_transfer(f"mem://bench/xfer_dec{i}") for i in range(2))
  finally:
    os.environ.pop("IGNEOUS_TRANSFER_PASSTHROUGH", None)
  clear_memory_storage()
  return round(passthrough, 1), round(decode, 1)


def bench_serve(seg):
  """Serving-tier latency/throughput over a seeded mem:// layer
  (ISSUE 9): hot-hit p50, overall p99, requests/sec over a keep-alive
  connection, and the coalescing dedupe ratio under a 16-client
  thundering herd on one cold chunk."""
  import http.client
  import threading

  from igneous_tpu.observability import metrics
  from igneous_tpu.serve import ServeApp, ServeConfig, ServeServer
  from igneous_tpu.volume import Volume

  sub = np.ascontiguousarray(seg[:128, :128, :64])
  vol = Volume.from_numpy(
    sub, "mem://bench/serve_layer", chunk_size=(64, 64, 32),
    layer_type="segmentation", encoding="compressed_segmentation",
  )
  del vol
  app = ServeApp(
    {"layer": "mem://bench/serve_layer"}, default_layer="layer",
    config=ServeConfig(ram_mb=64.0, synth_mips=False),
  )
  srv = ServeServer(app, host="127.0.0.1", port=0)
  port = srv.server_address[1]
  chunk_url = "/1_1_1/0-64_0-64_0-32"
  try:
    conn = http.client.HTTPConnection("127.0.0.1", port)
    lat = []
    n_requests = 300
    conn.request("GET", chunk_url)  # cold: populate the RAM tier
    conn.getresponse().read()
    t_all = time.perf_counter()
    for _ in range(n_requests):
      t0 = time.perf_counter()
      conn.request("GET", chunk_url, headers={"Accept-Encoding": "gzip"})
      conn.getresponse().read()
      lat.append(time.perf_counter() - t0)
    wall = time.perf_counter() - t_all
    conn.close()
    lat.sort()
    p50 = lat[len(lat) // 2]
    p99 = lat[min(int(len(lat) * 0.99), len(lat) - 1)]

    # thundering herd on one cold chunk: dedupe ratio = clients per
    # backend fetch the coalescer achieved
    app._cache.invalidate("layer")
    before = metrics.counters_snapshot()
    herd = 16
    barrier = threading.Barrier(herd)

    def hammer():
      c = http.client.HTTPConnection("127.0.0.1", port)
      barrier.wait()
      c.request("GET", chunk_url)
      c.getresponse().read()
      c.close()

    threads = [threading.Thread(target=hammer) for _ in range(herd)]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    after = metrics.counters_snapshot()
    leaders = after.get("serve.coalesce.leaders", 0) - before.get(
      "serve.coalesce.leaders", 0
    )
    waiters = after.get("serve.coalesce.waiters", 0) - before.get(
      "serve.coalesce.waiters", 0
    )
    dedupe = (leaders + waiters) / max(leaders, 1)
  finally:
    srv.shutdown()
  return {
    "serve_hot_hit_p50_ms": round(p50 * 1e3, 3),
    "serve_p99_ms": round(p99 * 1e3, 3),
    "serve_req_per_sec": round(n_requests / wall, 1),
    "serve_coalesce_dedupe_ratio": round(dedupe, 2),
  }


def bench_serve_fleet(seg):
  """Federated serving tier (ISSUE 18): three in-process replicas over
  one seeded mem:// layer joined by a static consistent-hash ring. A
  seeded zipfian herd (the stationary request mix of a million-user
  viewer population) runs round-robin across the replicas; reports
  fleet req/s, the peer-hit ratio (cold fills answered by the chunk's
  ring owner instead of origin), and the shed rate once one replica's
  admission gate is squeezed to a token-bucket far below offered load."""
  import http.client
  import threading

  from igneous_tpu.observability import metrics
  from igneous_tpu.serve import (
    Federation, QosGate, ServeApp, ServeConfig, ServeServer,
  )
  from igneous_tpu.volume import Volume

  sub = np.ascontiguousarray(seg[:128, :128, :64])
  Volume.from_numpy(
    sub, "mem://bench/serve_fleet_layer", chunk_size=(32, 32, 32),
    layer_type="segmentation",
  )
  replicas = []
  try:
    for _ in range(3):
      app = ServeApp(
        {"layer": "mem://bench/serve_fleet_layer"}, default_layer="layer",
        config=ServeConfig(ram_mb=64.0, synth_mips=False),
      )
      replicas.append(ServeServer(app, host="127.0.0.1", port=0))
    urls = [
      f"http://127.0.0.1:{srv.server_address[1]}" for srv in replicas
    ]
    for srv, url in zip(replicas, urls):
      fed = Federation(peers=urls, timeout_ms=5000.0, retry_sec=30.0)
      fed.activate(url)
      srv.app.federation = fed
    ports = [srv.server_address[1] for srv in replicas]

    rng = np.random.default_rng(7)
    keys = [
      f"1_1_1/{x}-{x+32}_{y}-{y+32}_{z}-{z+32}"
      for x in range(0, 128, 32)
      for y in range(0, 128, 32)
      for z in range(0, 64, 32)
    ]
    ranks = np.arange(1, len(keys) + 1, dtype=np.float64)
    pop = 1.0 / ranks ** 1.1
    pop /= pop.sum()
    n_requests = 150 if QUICK else 600
    clients = 8
    draws = rng.choice(len(keys), size=n_requests, p=pop)
    requests = [keys[d] for d in draws]
    per_client = [requests[i::clients] for i in range(clients)]
    barrier = threading.Barrier(clients)

    def viewer(ci):
      conns = {}
      barrier.wait()
      for j, key in enumerate(per_client[ci]):
        port = ports[(ci + j) % len(ports)]
        conn = conns.get(port)
        if conn is None:
          conn = conns[port] = http.client.HTTPConnection(
            "127.0.0.1", port
          )
        conn.request("GET", f"/{key}", headers={"Accept-Encoding": "gzip"})
        conn.getresponse().read()
      for conn in conns.values():
        conn.close()

    before = metrics.counters_snapshot()
    threads = [
      threading.Thread(target=viewer, args=(ci,)) for ci in range(clients)
    ]
    t_all = time.perf_counter()
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    wall = time.perf_counter() - t_all
    after = metrics.counters_snapshot()
    peer_hits = after.get("serve.peer.hits", 0) - before.get(
      "serve.peer.hits", 0
    )
    fetches = after.get("serve.fetch", 0) - before.get("serve.fetch", 0)
    peer_hit_ratio = peer_hits / max(peer_hits + fetches, 1)

    # overload one replica: token bucket at ~2 rps vs a 200-request blast
    shed_app = replicas[0].app
    shed_app._qos = QosGate(rps=2.0, burst_sec=1.0, layer_names=["layer"])
    sheds = 0
    blast = 200
    conn = http.client.HTTPConnection("127.0.0.1", ports[0])
    for _ in range(blast):
      conn.request("GET", f"/{keys[0]}")
      resp = conn.getresponse()
      resp.read()
      if resp.status == 503:
        sheds += 1
    conn.close()
  finally:
    for srv in replicas:
      srv.shutdown()
  return {
    "serve_fleet_req_per_sec": round(n_requests / wall, 1),
    "serve_fleet_peer_hit_ratio": round(peer_hit_ratio, 3),
    "serve_fleet_shed_rate": round(sheds / blast, 3),
  }


def measure_transfer_MBps():
  import jax

  x = np.zeros(16 * 1024 * 1024, dtype=np.uint8)
  t0 = time.perf_counter()
  xd = jax.device_put(x)
  xd.block_until_ready()
  up = 16.0 / (time.perf_counter() - t0)
  t0 = time.perf_counter()
  np.asarray(xd)
  down = 16.0 / (time.perf_counter() - t0)
  return round(up, 1), round(down, 1)


def bench_mesh_kernel():
  """BASELINE config 3: marching-cubes count pass (the production
  mesher), BATCHED — K masks per shard_map dispatch (the per-voxel device
  stage; emission is O(surface) host work)."""
  from igneous_tpu.ops.mesh import _mc_count_kernel as _count_kernel
  from igneous_tpu.parallel.executor import BatchKernelExecutor

  n = 64 if QUICK else 128
  K = 4 if QUICK else 8
  g = np.indices((n, n, n)).astype(np.float32) - (n - 1) / 2
  mask = (np.sqrt((g**2).sum(0)) < n // 3).astype(np.uint8)
  batch = np.stack([mask.transpose(2, 1, 0)] * K)
  ex = BatchKernelExecutor(_count_kernel)

  ex(batch)  # compile
  t0 = time.perf_counter()
  iters = 2 if QUICK else 4
  for _ in range(iters):
    ex(batch)
  dt = (time.perf_counter() - t0) / iters
  return batch.size / dt


def bench_ccl_kernel(algo: str = "scan", force_device: bool = False):
  """BASELINE config 4: block CCL, BATCHED — K cutouts per shard_map
  dispatch (+ host renumber per chunk). ``algo`` selects the device
  kernel variant (scan = pointer jumps, relax = gather-free) so TPU runs
  record the ROADMAP hardware A/B.

  ``force_device`` (ISSUE 10 satellite): on the CPU fallback,
  connected_components_batch short-circuits to the native per-cutout
  union-find and silently IGNORES the algo knob — every "relax" number
  recorded through r05 was either null or the native path remeasured.
  Pinning IGNEOUS_CCL_BACKEND=device makes the relax kernel itself run
  (on the XLA CPU device). It is ~100x slower than native there, so the
  forced measurement uses the reduced block — vox/s normalizes size."""
  from igneous_tpu.ops.ccl import connected_components_batch

  os.environ["IGNEOUS_CCL_DEVICE_ALGO"] = algo
  if force_device:
    os.environ["IGNEOUS_CCL_BACKEND"] = "device"
  try:
    n = 64 if (QUICK or force_device) else 128
    K = 4 if (QUICK or force_device) else 8
    rng = np.random.default_rng(0)
    lab = (rng.integers(0, 3, (K, n, n, n)) * 7).astype(np.uint32)
    connected_components_batch(lab)  # compile
    t0 = time.perf_counter()
    connected_components_batch(lab)
    dt = time.perf_counter() - t0
    return lab.size / dt
  finally:
    os.environ.pop("IGNEOUS_CCL_DEVICE_ALGO", None)
    if force_device:
      os.environ.pop("IGNEOUS_CCL_BACKEND", None)


def bench_infer():
  """ISSUE 10 headline: end-to-end InferenceTask campaign — halo'd
  download → batched jitted conv apply → overlap blend → Precomputed
  write — through the staged pipeline on mem:// storage, with a tiny
  fixed-seed conv net so the number tracks the machinery, not the model.
  Returns (voxels written per second, device busy ratio over the timed
  window, engine stats) — the busy ratio is the PR 7 ledger delta, i.e.
  the fraction of the campaign the device actually computed."""
  from igneous_tpu import task_creation as tc
  from igneous_tpu.infer import ModelSpec, init_params, save_model
  from igneous_tpu.observability.device import LEDGER
  from igneous_tpu.pipeline import run_tasks_pipelined
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(0)
  n = 128 if QUICK else 256
  nz = 32 if QUICK else 64
  data = rng.integers(0, 255, (n, n, nz, 1)).astype(np.uint8)
  src = "mem://bench/infer-src"
  model_path = "mem://bench/infer-model"
  Volume.from_numpy(data, src, chunk_size=(64, 64, 32), layer_type="image")
  spec = ModelSpec(
    "convnet3d", in_channels=1, out_channels=2,
    patch_shape=(64, 64, 32), overlap=(16, 16, 8), hidden=(8,),
  )
  save_model(model_path, spec, init_params(spec, seed=0))

  def campaign(dest):
    return list(tc.create_inference_tasks(
      src, dest, model_path, shape=(128, 128, 32), batch_size=4,
    ))

  # warm run: jit compile + model load land outside the timed window,
  # matching the steady state of a long campaign
  run_tasks_pipelined(campaign("mem://bench/infer-warm"))

  busy0 = LEDGER.busy_seconds()
  t0 = time.perf_counter()
  run_tasks_pipelined(campaign("mem://bench/infer-out"))
  wall = time.perf_counter() - t0
  busy = LEDGER.busy_seconds() - busy0
  voxels = int(np.prod(data.shape[:3]))
  return voxels / wall, (busy / wall if wall > 0 else None)


def bench_pool_ab_cpu(img):
  """CPU-device A/B of the 2x2x1 average-pool step (ISSUE 7 satellite):
  the batched XLA device path (ChunkExecutor over every virtual device)
  vs the native threaded host path, same voxels each side. Replaces the
  perpetual {"skipped": "tpu-only"} entry whenever >=2 (virtual) devices
  exist — the number behind the IGNEOUS_POOL_HOST=auto dispatch policy."""
  import jax

  from igneous_tpu.ops import oracle, pooling
  from igneous_tpu.parallel.executor import cached_chunk_executor, make_mesh

  n = jax.device_count()
  if n < 2:
    return None
  chunk = np.ascontiguousarray(img[:256, :256, :64])
  mesh = make_mesh()
  ex = cached_chunk_executor(mesh, factors=((2, 2, 1),), method="average")
  batch = np.stack([pooling._to_device_layout(chunk)] * n)
  iters = 2 if QUICK else 5

  ex(batch)  # compile + settle
  t0 = time.perf_counter()
  for _ in range(iters):
    ex(batch)
  device_rate = batch.size * iters / (time.perf_counter() - t0)

  host_fn = lambda: pooling.host_downsample(  # noqa: E731
    chunk, (2, 2, 1), 1, method="average", parallel=0
  )
  label = "native-threaded host pooling"
  if host_fn() is None:
    host_fn = lambda: oracle.np_downsample_with_averaging(  # noqa: E731
      chunk, (2, 2, 1), 1
    )
    label = "numpy-oracle host pooling"
  t0 = time.perf_counter()
  for _ in range(iters):
    for _k in range(n):  # same voxel count as the n-chunk device batch
      host_fn()
  host_rate = chunk.size * n * iters / (time.perf_counter() - t0)
  return {
    "device_voxps": round(device_rate, 1),
    "host_voxps": round(host_rate, 1),
    "device_vs_host": round(device_rate / host_rate, 3),
    "devices": n,
    "mode": f"cpu-device A/B: sharded XLA pyramid over {n} virtual "
            f"device(s) vs {label}",
  }


def bench_edt_kernel():
  """BASELINE config 5's device core: multilabel anisotropic EDT,
  BATCHED — K cutouts per shard_map dispatch."""
  from igneous_tpu.ops.edt import edt_batch

  n = 64 if QUICK else 128
  K = 4 if QUICK else 8
  rng = np.random.default_rng(0)
  lab = (rng.integers(0, 3, (K, n, n, n)) * 9).astype(np.uint32)
  edt_batch(lab, (4, 4, 40))  # compile
  t0 = time.perf_counter()
  edt_batch(lab, (4, 4, 40))
  dt = time.perf_counter() - t0
  return lab.size / dt


def bench_edt_device_kernel():
  """The device EDT kernel itself (blocked envelope scans, ISSUE 11
  tentpole 2), pinned to the device backend — without the pin, CPU
  fallback runs route edt_batch to the native/numpy host kernels and the
  device restructure would go unmeasured (the same silent-substitution
  trap ccl_relax fell into through r05). Reduced block: the XLA-CPU
  device is ~10x slower than native here; vox/s normalizes size."""
  from igneous_tpu.ops.edt import edt_batch

  os.environ["IGNEOUS_EDT_BACKEND"] = "device"
  try:
    n = 64 if QUICK else 64
    K = 4
    rng = np.random.default_rng(0)
    lab = (rng.integers(0, 3, (K, n, n, n)) * 9).astype(np.uint32)
    edt_batch(lab, (4, 4, 40))  # compile
    t0 = time.perf_counter()
    edt_batch(lab, (4, 4, 40))
    return lab.size / (time.perf_counter() - t0)
  finally:
    os.environ.pop("IGNEOUS_EDT_BACKEND", None)


def bench_mesh_extract_kernel():
  """Device mesh extraction (ISSUE 11 tentpole 3): count AND triangle
  emission on device (IGNEOUS_MESH_EMIT=device), solo marching_cubes on
  a half-dense random mask — the worst case for emission volume. The
  existing mesh_count_kernel_voxps times only the count pass."""
  from igneous_tpu.ops.mesh import marching_cubes

  os.environ["IGNEOUS_MESH_EMIT"] = "device"
  try:
    n = 64 if QUICK else 128
    rng = np.random.default_rng(0)
    mask = rng.random((n, n, n)) > 0.5
    marching_cubes(mask)  # compile both kernels
    iters = 2 if QUICK else 3
    t0 = time.perf_counter()
    for _ in range(iters):
      marching_cubes(mask)
    dt = (time.perf_counter() - t0) / iters
    return mask.size / dt
  finally:
    os.environ.pop("IGNEOUS_MESH_EMIT", None)


def bench_pyramid_fused(img):
  """The fused multi-mip walk (ISSUE 11 tentpole 4): mip0→3 in ONE
  compiled device program via pooling.downsample(mip_from=0), device
  kernels pinned (IGNEOUS_POOL_HOST=0) so CPU-fallback runs measure the
  fused XLA walk rather than the native per-mip host loop."""
  from igneous_tpu.ops import pooling

  chunk = np.ascontiguousarray(img[:256, :256, :64])
  os.environ["IGNEOUS_POOL_HOST"] = "0"
  try:
    pooling.downsample(chunk, (2, 2, 1), 3, method="average", mip_from=0)
    iters = 2 if QUICK else 5
    t0 = time.perf_counter()
    for _ in range(iters):
      pooling.downsample(chunk, (2, 2, 1), 3, method="average", mip_from=0)
    dt = (time.perf_counter() - t0) / iters
    return chunk.size / dt
  finally:
    os.environ.pop("IGNEOUS_POOL_HOST", None)


def bench_ragged():
  """Ragged paged batching (ISSUE 12): one mixed-shape boundary-cell
  fleet through the paged pyramid (ONE compiled signature + page slack)
  vs the same fleet through solo per-cutout downsample (a compile per
  distinct shape). Both run cold on purpose — the per-shape recompile
  tax is exactly what paging removes, so warmed-cache rates would
  measure the wrong thing. Device path pinned (IGNEOUS_POOL_HOST=0) so
  CPU-fallback rounds measure the paged kernel rather than the native
  host loop. Returns (batched_voxps, solo_voxps, pad_waste_pct)."""
  from igneous_tpu.observability import device as device_mod
  from igneous_tpu.ops import pooling
  from igneous_tpu.parallel.paged import paged_pyramid

  os.environ["IGNEOUS_POOL_HOST"] = "0"
  try:
    rng = np.random.default_rng(0)
    shapes = [(129, 256, 64), (256, 129, 64), (129, 129, 64),
              (65, 97, 33), (193, 65, 64)]
    if QUICK:
      shapes = shapes[:3]
    imgs = [rng.integers(0, 255, s).astype(np.uint8) for s in shapes]
    total = sum(i.size for i in imgs)

    led = device_mod.LEDGER
    pad0, real0 = led.pad_bytes, led.real_bytes
    t0 = time.perf_counter()
    paged_pyramid(imgs, (2, 2, 1), 2, method="average")
    batched = total / (time.perf_counter() - t0)
    pad = led.pad_bytes - pad0
    real = led.real_bytes - real0
    pad_waste_pct = (
      round(100.0 * pad / (pad + real), 2) if (pad + real) else None
    )

    t0 = time.perf_counter()
    for img in imgs:
      pooling.downsample(img, (2, 2, 1), 2, method="average")
    solo = total / (time.perf_counter() - t0)
    return batched, solo, pad_waste_pct
  finally:
    os.environ.pop("IGNEOUS_POOL_HOST", None)


_CACHE_BENCH_CHILD = r"""
import json, os, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
from igneous_tpu.parallel import paged
from igneous_tpu.observability import device as dev
rng = np.random.default_rng(19)
imgs = [rng.integers(0, 255, s).astype(np.uint8)
        for s in [(48, 41, 25), (24, 24, 24), (43, 16, 9)]]
paged.paged_pyramid(imgs, (2, 2, 1), num_mips=2)
led = dev.LEDGER
print(json.dumps({
  "compile_s": sum(k["compile_s"] for k in led.kernels.values()),
  "cc": dict(led.compile_cache),
}))
"""


def bench_compile_cache():
  """Persistent compile cache (ISSUE 19): the same paged workload in two
  FRESH interpreters sharing one file:// cache. The cold child pays the
  XLA compiles and publishes executables; the warm child fetches. cold_s
  is the cold child's measured compile seconds, warm_s what the warm
  child paid instead (fetch + any residual compiles) — their ratio is
  the per-worker startup tax the cache removes fleet-wide. Returns
  (cold_s, warm_s) or None when the children fail (e.g. no
  serialize_executable support on this backend)."""
  import tempfile

  tmp = tempfile.mkdtemp(prefix="igneous-bench-cc-")

  def child():
    env = dict(os.environ)
    env.update({
      "JAX_PLATFORMS": "cpu",
      "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
      "IGNEOUS_COMPILE_CACHE": f"file://{tmp}/cache",
    })
    proc = subprocess.run(
      [sys.executable, "-c", _CACHE_BENCH_CHILD, _REPO_DIR],
      env=env, cwd=_REPO_DIR, capture_output=True, text=True,
      timeout=600,
    )
    if proc.returncode != 0:
      return None
    return json.loads(proc.stdout.strip().splitlines()[-1])

  cold = child()
  warm = child()
  if not cold or not warm:
    return None
  if not cold["cc"].get("puts") or not warm["cc"].get("hits"):
    return None  # the cache never engaged; a ratio would be fiction
  cold_s = cold["compile_s"]
  warm_s = warm["compile_s"] + warm["cc"].get("fetch_s", 0.0)
  return cold_s, warm_s


def bench_tune():
  """Autotuner (ISSUE 19): a budget-bounded `igneous tune` sweep on the
  live backend — best-vs-default ratio across the tunable knobs (1.0 =
  registry defaults already optimal; every candidate byte-identity
  checked inside the sweep). None when the sweep fails."""
  import tempfile

  from igneous_tpu import tune as tune_mod

  try:
    config = tune_mod.run(
      out=f"file://{tempfile.mkdtemp(prefix='igneous-bench-tune-')}",
      budget_sec=60.0 if QUICK else 180.0, repeats=2, size=32,
    )
  except Exception:
    return None
  return config.get("tune_best_vs_default_ratio")


def bench_host_kernels(img, seg):
  """The production path on an accelerator-less host: the native C++
  pooling kernels threaded across every core — exactly what
  ops.pooling.downsample_auto dispatches to when no TPU is attached (the
  same deal as the reference's tinybrain-on-CPU workers). None when the
  native lib is unavailable."""
  from igneous_tpu.ops import pooling

  warm = pooling.host_downsample(
    img, (2, 2, 1), NUM_MIPS, method="average", parallel=0
  )
  if warm is None:
    return None

  def once():
    t0 = time.perf_counter()
    pooling.host_downsample(img, (2, 2, 1), NUM_MIPS, method="average", parallel=0)
    pooling.host_downsample(seg, (2, 2, 1), NUM_MIPS, method="mode", parallel=0)
    return (img.size + seg.size) / (time.perf_counter() - t0)

  return _best_of(once, BEST_OF_N, record="host_kernel")


def bench_forge_pipelines():
  """e2e forge rates on a small blobby segmentation (BASELINE configs
  3/5 pipeline-level): mesh forge (sharded, device count pass + host
  emit/weld/QEM) and skeleton forge with exact cross-sections."""
  from igneous_tpu.volume import Volume
  from igneous_tpu import task_creation as tc
  from igneous_tpu.queues import LocalTaskQueue
  from igneous_tpu.storage import clear_memory_storage

  rng = np.random.default_rng(0)
  n = 64 if QUICK else 128
  g = np.indices((n, n, n)).astype(np.float32)
  seg = np.zeros((n, n, n), dtype=np.uint64)
  for i in range(8):
    c = rng.integers(n // 8, n - n // 8, 3)
    r = rng.integers(n // 12, n // 5)
    seg[((g[0] - c[0]) ** 2 + (g[1] - c[1]) ** 2 + (g[2] - c[2]) ** 2) < r * r] = i + 1
  clear_memory_storage()
  Volume.from_numpy(
    seg, "mem://bench/forge", resolution=(16, 16, 40),
    chunk_size=(n, n, n), layer_type="segmentation",
  )
  tq = LocalTaskQueue(parallel=1, progress=False)

  t0 = time.perf_counter()
  tq.insert(tc.create_meshing_tasks(
    "mem://bench/forge", shape=(n, n, n), sharded=True, spatial_index=True,
  ))
  mesh_dt = time.perf_counter() - t0

  t0 = time.perf_counter()
  tq.insert(tc.create_skeletonizing_tasks(
    "mem://bench/forge", shape=(n, n, n), dust_threshold=50,
    teasar_params={"scale": 4, "const": 200},
    cross_sectional_area=True, csa_smoothing_window=2,
  ))
  skel_dt = time.perf_counter() - t0
  clear_memory_storage()
  return round(seg.size / mesh_dt, 1), round(seg.size / skel_dt, 1)


def bench_queue():
  """Queue scale-out (ISSUE 15): the control-plane rates a 10M-task
  campaign lives or dies on, measured on a 100k-task fq:// queue —
  batched segment enqueue vs the classic one-file-per-task layout,
  range-lease acquisition throughput, and the `queue status` depth read
  (O(shards): task counts ride in segment file names)."""
  import shutil
  import tempfile

  from igneous_tpu.queues import FileQueue, PrintTask, serialize

  n = 20_000 if QUICK else 100_000
  n_classic = 1_000 if QUICK else 2_000
  payload = serialize(PrintTask("bench"))
  root = tempfile.mkdtemp(prefix="bench_queue_")
  try:
    cq = FileQueue(f"fq://{root}/classic")
    t0 = time.perf_counter()
    cq.insert(payload for _ in range(n_classic))
    classic_rate = n_classic / (time.perf_counter() - t0)

    q = FileQueue(f"fq://{root}/batched")
    t0 = time.perf_counter()
    q.insert_batch((payload for _ in range(n)), total=n)
    enqueue_rate = n / (time.perf_counter() - t0)

    t0 = time.perf_counter()
    for _ in range(3):
      snap = q.depth_snapshot()
    status_ms = (time.perf_counter() - t0) / 3 * 1e3
    assert snap["enqueued"] == n, snap

    target = min(n, 4_096 if QUICK else 20_480)
    leased = 0
    t0 = time.perf_counter()
    while leased < target:
      got = q.lease_batch(600, max_tasks=1024)
      if not got:
        break
      leased += len(got)
    lease_rate = leased / (time.perf_counter() - t0)
  finally:
    shutil.rmtree(root, ignore_errors=True)
  return (
    round(enqueue_rate, 1), round(lease_rate, 1), round(status_ms, 3),
    round(classic_rate, 1),
  )


def bench_campaign_survival():
  """Campaign survival (ISSUE 17): end-to-end voxel throughput of a
  range-leased downsample campaign under the closed-loop driver — a
  clean run vs a hostile one where a live range holder is frozen
  mid-lease (SIGSTOP) and its tail is rescued by straggler speculation
  before the zombie wakes into the fence. Identical task grids and
  fleet policy, so hostile/clean is the measured price of the storm
  WITH survival on. Returns (hostile_voxps, clean_voxps, spec_issued)."""
  import shutil
  import signal
  import tempfile

  from igneous_tpu import task_creation as tc
  from igneous_tpu.observability import autoscale, campaign, fleet, health
  from igneous_tpu.observability import journal as journal_mod
  from igneous_tpu.queues import FileQueue
  from igneous_tpu.tasks import SleepTask
  from igneous_tpu.volume import Volume

  edge = 96 if QUICK else 128
  img = np.random.default_rng(17).integers(
    0, 255, (edge, edge, 64)
  ).astype(np.uint8)
  n_sleeps = 8 if QUICK else 16

  def run_campaign(root, hostile):
    layer = f"file://{root}/layer"
    Volume.from_numpy(img, layer, chunk_size=(32, 32, 32), compress="gzip")
    tasks = list(tc.create_downsampling_tasks(
      layer, mip=0, num_mips=1, memory_target=int(6e5), compress="gzip",
    ))
    # interleaved SleepTasks stretch the campaign across enough driver
    # ticks for the freeze to land mid-range (same trick as the soak)
    tasks += [SleepTask(seconds=0.4) for _ in range(n_sleeps)]
    spec = f"fq://{root}/q"
    prev_shards = knobs.raw("IGNEOUS_QUEUE_SHARDS")
    os.environ["IGNEOUS_QUEUE_SHARDS"] = "3"
    try:
      q = FileQueue(spec, max_deliveries=25)
      n_tasks = q.insert_batch(tasks, total=len(tasks))
    finally:
      if prev_shards is None:
        os.environ.pop("IGNEOUS_QUEUE_SHARDS", None)
      else:
        os.environ["IGNEOUS_QUEUE_SHARDS"] = prev_shards
    jpath = journal_mod.journal_path_for(q, spec)
    env = {
      "JAX_PLATFORMS": "cpu",
      "PYTHONPATH": (
        _REPO_DIR + os.pathsep + os.environ["PYTHONPATH"]
        if os.environ.get("PYTHONPATH") else _REPO_DIR
      ),
      "IGNEOUS_JOURNAL_FLUSH_SEC": "0.2",
      "IGNEOUS_STEAL": "1",
      "IGNEOUS_STEAL_MIN_HELD_SEC": "1.0",
      "IGNEOUS_SPECULATE_MIN_HELD_SEC": "0",
    }
    actuator = autoscale.LocalPoolActuator(
      spec, worker_args=["--lease-sec", "20", "--batch", "4"],
      env=env, grace_sec=60.0,
    )
    runner = campaign.CampaignRunner(
      jpath, q, actuator,
      policy=autoscale.AutoscalePolicy(
        min_workers=2, max_workers=3, horizon_sec=5.0,
        hysteresis=0.2, cooldown_sec=1.0, step_max=2,
      ),
      health_config=health.HealthConfig(stall_sec=3.0),
      tick_sec=1.0, speculate=True, max_wall_sec=120.0,
    )
    state = {"tick": 0, "stalled": 0, "stopped": None, "resume_at": 0}

    def chaos_sleep(dt):
      state["tick"] += 1
      actuator.reap()
      procs = [p for p in actuator.procs if p.poll() is None]
      if procs and not state["stalled"]:
        holders = set()
        for r in q.range_leases():
          h = r.get("holder") or ""
          if not r.get("expired") and "-" in h:
            try:
              holders.add(int(h.rsplit("-", 1)[1]))
            except ValueError:
              pass
        victims = [p for p in procs if p.pid in holders]
        if victims:
          victims[0].send_signal(signal.SIGSTOP)
          state.update(stalled=1, stopped=victims[0],
                       resume_at=state["tick"] + 6)
      if state["stopped"] is not None and state["tick"] >= state["resume_at"]:
        state["stopped"].send_signal(signal.SIGCONT)
        state["stopped"] = None
      time.sleep(dt)

    prev_spec = knobs.raw("IGNEOUS_SPECULATE_MIN_HELD_SEC")
    os.environ["IGNEOUS_SPECULATE_MIN_HELD_SEC"] = "0"
    try:
      runner.run(sleep_fn=chaos_sleep if hostile else time.sleep)
    finally:
      if state["stopped"] is not None:
        state["stopped"].send_signal(signal.SIGCONT)
      if prev_spec is None:
        os.environ.pop("IGNEOUS_SPECULATE_MIN_HELD_SEC", None)
      else:
        os.environ["IGNEOUS_SPECULATE_MIN_HELD_SEC"] = prev_spec
    assert q.completed == n_tasks, (
      f"completions drifted: tally={q.completed} tasks={n_tasks}"
    )
    if hostile:
      assert state["stalled"], "freeze never landed: hostile == clean"
    records = fleet.load_effective(jpath)
    task_spans = [
      r for r in records
      if r.get("kind") == "span" and r.get("name") == "task"
    ]
    # completions-tally mtime is the instant the last FIRST-resolution
    # landed; the waking zombie's fenced acks never touch it
    makespan = (
      os.path.getmtime(os.path.join(q.path, "completions"))
      - min(r["ts"] for r in task_spans)
    )
    counters = fleet.status(records)["counters"]
    return img.size / max(makespan, 1e-9), counters

  root = tempfile.mkdtemp(prefix="bench_campaign_")
  try:
    clean_rate, _ = run_campaign(os.path.join(root, "clean"), hostile=False)
    hostile_rate, counters = run_campaign(
      os.path.join(root, "hostile"), hostile=True
    )
  finally:
    shutil.rmtree(root, ignore_errors=True)
  return (
    round(hostile_rate, 1), round(clean_rate, 1),
    int(counters.get("speculation.issued", 0)),
  )


def _skip(reason: str) -> dict:
  """Explicit not-run marker (ISSUE 6 satellite): a gated metric records
  WHY it has no number, so the BENCH trajectory distinguishes "skipped
  on this platform" from "measured zero" — a silent null poisoned the
  pool_ab/ccl_relax history for five rounds."""
  return {"skipped": reason}


def _null_check(result: dict):
  """Self-check (ISSUE 11 satellite): no metric in the artifact may be a
  bare null. Every gated metric must carry a ``{"skipped": reason}``
  marker instead — a bare null is indistinguishable from "measured zero"
  or "crashed silently" in the BENCH trajectory. Offending paths are
  rewritten to explicit markers and reported under detail.null_check so
  the regression is loud in the artifact itself, not just absent."""
  offenders = []

  def walk(node, path):
    if isinstance(node, dict):
      for k, v in node.items():
        if v is None:
          offenders.append(f"{path}.{k}" if path else str(k))
          node[k] = _skip("bare null caught by self-check")
        else:
          walk(v, f"{path}.{k}" if path else str(k))
    elif isinstance(node, list):
      for i, v in enumerate(node):
        if v is None:
          offenders.append(f"{path}[{i}]")
          node[i] = _skip("bare null caught by self-check")
        else:
          walk(v, f"{path}[{i}]")

  walk(result, "")
  result.setdefault("detail", {})["null_check"] = (
    "ok" if not offenders else {"bare_nulls_rewritten": offenders}
  )
  return result


def run_bench():
  import jax

  from igneous_tpu.compile_cache import place_jax_cache

  # never report CPU numbers as device numbers
  backend = jax.default_backend()
  if backend != "tpu":
    sys.exit(f"bench.py needs a TPU; JAX backend is {backend!r}")
  place_jax_cache()
  platform = backend
  img, seg = make_data()
  dev_kernel = bench_device_kernels(img, seg)
  host_kernel = None if platform == "tpu" else bench_host_kernels(img, seg)
  cpu1, baseline_kind = bench_cpu_kernels(img, seg)

  # Consistency guard (round-3 postmortem): on the CPU-fallback path the
  # headline (threaded native pooling) and cpu_1core (the same kernels,
  # one core) are measured seconds apart in the same process. The headline
  # dropping below cpu_1core/4 is physically impossible without external
  # interference — the r03 artifact recorded exactly that (21.5M headline
  # vs 1.09G cpu_1core) and poisoned the round's official signal. Discard
  # and re-measure instead of publishing a contended sample.
  guard_retries = 0
  while (
    host_kernel is not None
    and host_kernel < cpu1 / 4
    and guard_retries < 3
  ):
    guard_retries += 1
    time.sleep(3)  # let whatever is contending drain
    host_kernel = bench_host_kernels(img, seg)

  cpu8 = cpu1 * 8.0
  e2e_serial, e2e = bench_e2e(img, seg)
  trace_overhead_pct, stage_spans = bench_trace_overhead(img, seg)
  integrity_overhead_pct = bench_integrity_overhead(img, seg)
  e2e_batched, e2e_batched_device, batched_path = bench_e2e_batched(img, seg)
  inflate = measure_inflate_MBps(seg)
  up, down = measure_transfer_MBps()
  mesh_rate = bench_mesh_kernel()
  ccl_rate = bench_ccl_kernel("scan")
  # ISSUE 10 satellite: on the CPU fallback the batch entry point ignores
  # the algo knob (native short-circuit) — force the device backend so
  # the relax kernel itself is what gets timed
  ccl_relax_rate = bench_ccl_kernel(
    "relax", force_device=(platform != "tpu")
  )
  infer_e2e_rate, infer_busy_ratio = bench_infer()
  # the Pallas pooling kernel does not compile for v5e, so only the
  # device-vs-host A/B runs here
  pool_ab = bench_pool_ab_cpu(img)
  if pool_ab is None:
    pool_ab = _skip("single-device host: no device path to A/B")
  edt_rate = bench_edt_kernel()
  edt_device_rate = bench_edt_device_kernel()
  mesh_extract_rate = bench_mesh_extract_kernel()
  pyramid_fused_rate = bench_pyramid_fused(img)
  ragged_batched_rate, ragged_solo_rate, pad_waste_pct = bench_ragged()
  cache_pair = bench_compile_cache()
  tune_ratio = bench_tune()
  mesh_forge_rate, skel_forge_rate = bench_forge_pipelines()
  codec_tbl = bench_codecs(img, seg)
  cseg_speedup = bench_cseg_speedup()
  (queue_enqueue_rate, queue_lease_rate,
   queue_status_ms, queue_classic_rate) = bench_queue()
  (campaign_hostile_rate, campaign_clean_rate,
   campaign_spec_issued) = bench_campaign_survival()
  xfer_passthrough, xfer_decode = bench_transfer_passthrough(seg)
  serve_stats = bench_serve(seg)
  serve_fleet_stats = bench_serve_fleet(seg)

  # Headline = the framework's production kernel path on this platform:
  # device pyramid on TPU; on the CPU fallback, the native threaded host
  # path that downsample_auto actually dispatches to here (the XLA-CPU
  # device-kernel rate stays in detail for reference).
  headline = dev_kernel if host_kernel is None else host_kernel
  result = {
    "metric": "downsample_kernel_mip0to4_voxels_per_sec",
    "value": round(headline, 1),
    "unit": "vox/s",
    "vs_baseline": round(headline / cpu8, 3),
    # vs_baseline divides by an 8-CORE credit regardless of how many
    # cores this host actually has; on a 1-core host that reads as a 60x
    # miss when the per-core truth is parity, so both numbers are kept.
    "vs_baseline_per_core": round(headline / cpu1, 3),
    "vs_baseline_note": (
      "vs_baseline uses an 8-core-credit denominator (cpu_1core x 8) on "
      f"a {len(os.sched_getaffinity(0))}-core host; vs_baseline_per_core "
      "divides by the measured single-core rate"
    ),
    "detail": {
      "img_shape": list(IMG_SHAPE),
      "seg_shape": list(SEG_SHAPE),
      "device_kernel_voxps": round(dev_kernel, 1),
      "host_native_kernel_voxps": (
        round(host_kernel, 1) if host_kernel is not None
        else _skip(
          "tpu platform: device pyramid is the production path"
          if platform == "tpu"
          else "native pooling library unavailable on this host"
        )
      ),
      # the baseline credits the reference with 8 cores; on a smaller
      # fallback host the per-core ratio is the informative comparison
      "host_cores": len(os.sched_getaffinity(0)),
      "load_avg": [round(x, 2) for x in os.getloadavg()],
      "best_of_n": BEST_OF_N,
      "raw_samples": _sample_stats(),
      "guard_retries": guard_retries,
      "cpu_1core_kernel_voxps": round(cpu1, 1),
      "cpu8_baseline_voxps": round(cpu8, 1),
      # e2e_pipeline = the production path (staged pipeline ON);
      # e2e_serial = the same stream strictly per-task serial (what
      # r01-r05 measured under this key's name)
      "e2e_pipeline_voxps": round(e2e, 1),
      "e2e_serial_voxps": round(e2e_serial, 1),
      "pipeline_speedup": round(e2e / e2e_serial, 3),
      "pipeline_threads_active": __import__(
        "igneous_tpu.pipeline.config", fromlist=["config"]
      ).use_threads(),
      "inflate_MBps": inflate,
      # ISSUE 5: span recording cost at default sampling (negative =
      # measurement noise on a shared host) + where the traced run's
      # wall time went, by span name
      "trace_overhead_pct": trace_overhead_pct,
      "stage_spans": stage_spans,
      # ISSUE 16: clean-path cost of the checksummed write envelope
      # (digest per put + manifest flushes) vs IGNEOUS_INTEGRITY=off;
      # acceptance gate is <=5% (negative = host drift noise)
      "integrity_overhead_pct": integrity_overhead_pct,
      "e2e_batched_voxps": round(e2e_batched, 1),
      "e2e_batched_device_voxps": (
        round(e2e_batched_device, 1) if e2e_batched_device
        else _skip("no device mesh/pool available for the batched path")
      ),
      "e2e_batched_path": batched_path,
      "transfer_MBps_up_down": [up, down],
      "mesh_count_kernel_voxps": round(mesh_rate, 1),
      "mesh_forge_e2e_voxps": mesh_forge_rate,
      "skeleton_forge_csa_e2e_voxps": skel_forge_rate,
      "ccl_kernel_voxps": round(ccl_rate, 1),
      "ccl_relax_kernel_voxps": (
        round(ccl_relax_rate, 1) if ccl_relax_rate is not None
        else _skip("relax kernel produced no measurement")
      ),
      # ISSUE 10: conv-net inference as a first-class workload — e2e
      # voxels/s through the staged pipeline and the fraction of the
      # campaign the device spent computing (ledger busy delta / wall)
      "infer_e2e_voxps": round(infer_e2e_rate, 1),
      "infer_device_busy_ratio": (
        round(infer_busy_ratio, 4) if infer_busy_ratio is not None
        else _skip("zero-wall inference window")
      ),
      # ISSUE 4: compressed-domain fast paths
      "codec_MBps": codec_tbl,
      "cseg_vs_loop": cseg_speedup,
      # ISSUE 15: batched queue wire protocol + range leases — segment
      # enqueue and range-lease acquisition rates on a 100k-task fq://
      # campaign, the classic per-task enqueue for the speedup
      # denominator, and the depth read (O(shards), not O(tasks))
      "queue_enqueue_tasks_per_sec": queue_enqueue_rate,
      "queue_lease_tasks_per_sec": queue_lease_rate,
      "queue_status_ms_100k": queue_status_ms,
      "queue_classic_enqueue_tasks_per_sec": queue_classic_rate,
      "queue_enqueue_speedup": (
        round(queue_enqueue_rate / queue_classic_rate, 1)
        if queue_classic_rate else _skip("classic enqueue measured zero")
      ),
      # ISSUE 17: campaign survival — identical range-leased downsample
      # campaigns under the closed-loop driver, clean vs hostile (a
      # range holder frozen mid-lease, tail rescued by speculation);
      # the ratio is the storm's measured throughput tax with survival on
      "campaign_hostile_voxps": campaign_hostile_rate,
      "campaign_clean_voxps": campaign_clean_rate,
      "campaign_survival_retention": (
        round(campaign_hostile_rate / campaign_clean_rate, 3)
        if campaign_clean_rate
        else _skip("clean campaign measured zero")
      ),
      "campaign_speculation_issued": campaign_spec_issued,
      "transfer_passthrough_voxps": xfer_passthrough,
      "transfer_decode_voxps": xfer_decode,
      "transfer_passthrough_speedup": (
        round(xfer_passthrough / xfer_decode, 2) if xfer_decode
        else _skip("decode-path transfer rate unavailable")
      ),
      "edt_kernel_voxps": round(edt_rate, 1),
      # ISSUE 11: the device kernel suite measured AS device kernels —
      # backend pins keep CPU-fallback rounds from silently substituting
      # the host paths (see each bench's docstring)
      "edt_device_kernel_voxps": round(edt_device_rate, 1),
      "mesh_extract_kernel_voxps": round(mesh_extract_rate, 1),
      "pyramid_fused_voxps": round(pyramid_fused_rate, 1),
      # ISSUE 12: a mixed-shape ragged fleet, paged (ONE compiled
      # signature for the whole campaign) vs solo per-cutout (a compile
      # per distinct shape) — both cold, because the recompile tax is
      # the thing being removed — plus the page slack the campaign paid
      "ragged_batched_voxps": round(ragged_batched_rate, 1),
      "ragged_solo_voxps": round(ragged_solo_rate, 1),
      "pad_waste_pct": (
        pad_waste_pct if pad_waste_pct is not None
        else _skip("no pad-waste bytes recorded during the paged run")
      ),
      # ISSUE 19: the per-worker startup tax the persistent compile
      # cache removes — the same paged workload in two fresh
      # interpreters sharing a file:// cache, compile seconds paid cold
      # vs fetch seconds paid warm
      "compile_cache_cold_s": (
        round(cache_pair[0], 4) if cache_pair
        else _skip("compile cache children failed or cache never engaged")
      ),
      "compile_cache_warm_s": (
        round(cache_pair[1], 4) if cache_pair
        else _skip("compile cache children failed or cache never engaged")
      ),
      "compile_cache_speedup": (
        round(cache_pair[0] / cache_pair[1], 2)
        if cache_pair and cache_pair[1] > 0
        else _skip("warm child paid ~zero; ratio undefined")
      ),
      # ISSUE 19: budget-bounded autotune sweep on this backend — <1.0
      # means a candidate beat the registry defaults (byte-identity
      # asserted per candidate inside the sweep)
      "tune_best_vs_default_ratio": (
        tune_ratio if tune_ratio is not None
        else _skip("tune sweep failed or measured nothing")
      ),
      "pool_ab": pool_ab,
      # ISSUE 9: interactive serving tier — hot-path latency, sustained
      # keep-alive throughput, and herd-coalescing effectiveness
      **serve_stats,
      **serve_fleet_stats,
      # ISSUE 7: the device telemetry plane's own view of this bench run
      # — per-kernel compile/execute seconds + vox/s, per-device busy
      # seconds, recompile count, transfer bytes, utilization ratio
      "device_telemetry": _device_telemetry(),
      "baseline": baseline_kind + " (reference stack not installed here)",
      "platform": platform,
      "device": _device_name(),
    },
  }
  print(json.dumps(_null_check(result)))


def _device_telemetry():
  from igneous_tpu.observability import device as device_mod

  snap = device_mod.LEDGER.snapshot()
  return snap if snap is not None else _skip("no device dispatches ran")


def _device_name():
  try:
    import jax

    return str(jax.devices()[0])
  except Exception:
    return "unknown"


if __name__ == "__main__":
  run_bench()
