"""Program spans on the profiler's clock.

Every ``trace.span`` is also a ``jax.profiler`` annotation named
``igneous.<span>``, sampled or not. These tests drive one
``execute --batch 2`` lease round (``poll_batched`` over an ``fq://``
queue, two small downsample tasks on the batched device path) under the
profiler, read the ``.xplane.pb`` back, and check the spans the
benchmark's program-span metrics read: the round's phases, the download's
split, the dispatch's kernel name; and that sampling decides only the
journal, never the annotations.
"""

import glob

import numpy as np
import pytest

from igneous_tpu.downsample_scales import create_downsample_scales
from igneous_tpu.observability import device as device_mod
from igneous_tpu.observability import metrics, trace
from igneous_tpu.parallel import make_mesh
from igneous_tpu.parallel.executor import ChunkExecutor, program_name
from igneous_tpu.parallel.lease_batcher import poll_batched
from igneous_tpu.queues import FileQueue
from igneous_tpu.tasks.image import DownsampleTask
from igneous_tpu.volume import Volume

SHAPE = (64, 64, 16)
TASK_VOXELS = SHAPE[0] * SHAPE[1] * SHAPE[2]


def _tasks(path):
  return [
    DownsampleTask(layer_path=path, mip=0, shape=SHAPE, offset=(x, 0, 0),
                   num_mips=1, factor=(2, 2, 1))
    for x in (0, SHAPE[0])
  ]


def _events(logdir):
  """``[(line_index, name, start_ns, end_ns, stats)]`` of every
  ``igneous.*`` host event, names without the prefix."""
  from jax.profiler import ProfileData

  (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
  out = []
  for plane in ProfileData.from_file(path).planes:
    if not plane.name.startswith("/host:"):
      continue
    for i, line in enumerate(plane.lines):
      for ev in line.events:
        if ev.name.startswith(trace.PROFILER_PREFIX):
          start = int(ev.start_ns)
          out.append((i, ev.name[len(trace.PROFILER_PREFIX):], start,
                      start + int(ev.duration_ns), dict(ev.stats)))
  return out


def _round(root, name, monkeypatch_ctx, sample):
  """One lease round of two tasks under the profiler; returns (events,
  journal span records, batcher stats)."""
  import jax

  monkeypatch_ctx.setenv("IGNEOUS_TRACE_SAMPLE", sample)
  data = np.random.default_rng(7).integers(
    0, 255, (2 * SHAPE[0], SHAPE[1], SHAPE[2])).astype(np.uint8)
  path = f"file://{root}/{name}"
  vol = Volume.from_numpy(data, path, chunk_size=(32, 32, 16))
  create_downsample_scales(vol.meta, 0, SHAPE, (2, 2, 1), num_mips=1)
  vol.commit_info()
  q = FileQueue(f"fq://{root}/{name}-q")
  q.insert_batch(_tasks(path))  # one segment: a range lease of two
  # a profiler capture left running by another test would refuse ours
  assert not device_mod._PROFILE_STATE["active"]
  trace.reset()
  logdir = f"{root}/{name}-trace"
  jax.profiler.start_trace(logdir)
  try:
    executed, stats = poll_batched(
      q, batch_size=2, mesh=make_mesh(2),
      stop_fn=lambda executed, empty: empty,
    )
  finally:
    jax.profiler.stop_trace()
  assert executed == 2 and stats["batched"] == 2
  return _events(logdir), trace.drain_spans(), stats


@pytest.fixture(scope="module")
def rounds(tmp_path_factory):
  root = tmp_path_factory.mktemp("spans")
  with pytest.MonkeyPatch.context() as mp:
    # the batched device path, not the native host pooling
    mp.setenv("IGNEOUS_POOL_HOST", "0")
    sampled = _round(root, "sampled", mp, "1")
    unsampled = _round(root, "unsampled", mp, "0")
  return {"sampled": sampled, "unsampled": unsampled}


def _named(events, name):
  return sorted((e for e in events if e[1] == name), key=lambda e: e[2])


def _inside(inner, outer):
  return (inner[0] == outer[0] and outer[2] <= inner[2]
          and inner[3] <= outer[3])


@pytest.mark.parametrize("mode", ["sampled", "unsampled"])
def test_lease_round_holds_its_phases_in_order(rounds, mode):
  events = rounds[mode][0]
  (rnd,) = _named(events, "lease.round")
  phases = [e for e in events if e[1].startswith("lease.round.")
            and _inside(e, rnd)]
  assert [e[1] for e in sorted(phases, key=lambda e: e[2])] == [
    "lease.round.fetch", "lease.round.dispatch", "lease.round.finish"]
  # the loop acquires the round's leases before the round runs (and
  # polls the emptied queue once more after it)
  acquires = _named(events, "lease.acquire")
  assert acquires[0][3] <= rnd[2] <= acquires[-1][2]
  assert _named(events, "queue.lease") and len(
    _named(events, "queue.delete")) == 2


@pytest.mark.parametrize("mode", ["sampled", "unsampled"])
def test_volume_download_holds_get_and_decode(rounds, mode):
  events = rounds[mode][0]
  downloads = _named(events, "volume.download")
  assert len(downloads) == 2
  # one decode span per download, on the downloading thread, however
  # many pool threads decode its chunks
  assert len(_named(events, "volume.decode")) == len(downloads)
  for dl in downloads:
    assert dl[4]["voxels"] == TASK_VOXELS
    parts = [e for e in sorted(events, key=lambda e: e[2])
             if e[1].startswith("volume.") and e is not dl
             and _inside(e, dl)]
    # each chunk is copied into the cutout by the pool worker that
    # decoded it, inside the decode span: no serial assembly follows
    assert [e[1] for e in parts] == ["volume.get", "volume.decode"]
    # four gzip chunks a cutout: the decode fans out
    stats = parts[1][4]
    assert stats["chunks"] == 4 and stats["workers"] > 1
    assert stats["placed"] == 4


def test_device_execute_carries_kernel(rounds):
  events = rounds["sampled"][0]
  kernels = [e[4].get("kernel") for e in _named(events, "device.execute")]
  assert kernels == ["pooling.pyramid[average]"]
  (dispatch,) = _named(events, "lease.round.dispatch")
  (execute,) = _named(events, "device.execute")
  assert _inside(execute, dispatch)
  h2d = _named(events, "device.h2d")
  assert h2d and h2d[0][4]["bytes"] > 0 and h2d[0][4]["kernel"] == kernels[0]


def test_average_pyramid_walks_one_slab_on_the_host(rounds):
  """The host backend reports no memory: the whole cutout is one slab."""
  (execute,) = _named(rounds["sampled"][0], "device.execute")
  assert (execute[4]["slabs"], execute[4]["slab_depth"]) == (1, SHAPE[2])


@pytest.fixture(scope="module")
def u64_round(tmp_path_factory):
  """One lease round of two uint64 segmentation tasks, with the device's
  memory taken as small enough that the pyramid walks 4 slabs of 4."""
  import jax

  from igneous_tpu.ops import pooling
  from igneous_tpu.parallel import executor as executor_mod

  root = tmp_path_factory.mktemp("u64spans")
  rng = np.random.default_rng(11)
  ids = rng.integers(2**32, 2**48, 6, dtype=np.uint64)
  data = ids[rng.integers(0, 6, (2 * SHAPE[0], SHAPE[1], SHAPE[2]))]
  path = f"file://{root}/seg"
  vol = Volume.from_numpy(data, path, chunk_size=(32, 32, 16),
                          layer_type="segmentation")
  create_downsample_scales(vol.meta, 0, SHAPE, (2, 2, 1), num_mips=1)
  vol.commit_info()
  q = FileQueue(f"fq://{root}/q")
  q.insert_batch(_tasks(path))
  plane_bytes = SHAPE[0] * SHAPE[1] * 8  # a task's (lo, hi) z plane a chip
  with pytest.MonkeyPatch.context() as mp:
    mp.setenv("IGNEOUS_POOL_HOST", "0")
    memory = pooling.walk_bytes(SHAPE[2], 4, ((2, 2, 1),), plane_bytes)
    mp.setattr(executor_mod, "device_memory_bytes", lambda device: memory)
    mp.setattr(executor_mod, "_CHUNK_EXECUTOR_CACHE", {})
    assert not device_mod._PROFILE_STATE["active"]
    logdir = f"{root}/trace"
    jax.profiler.start_trace(logdir)
    try:
      executed, stats = poll_batched(
        q, batch_size=2, mesh=make_mesh(2),
        stop_fn=lambda executed, empty: empty,
      )
    finally:
      jax.profiler.stop_trace()
  assert executed == 2 and stats["batched"] == 2
  assert stats["group_fallbacks"] == 0
  return _events(logdir)


@pytest.mark.parametrize("mode", ["sampled", "unsampled"])
def test_uint8_batch_stack_is_its_own_span(rounds, mode):
  events = rounds[mode][0]
  (dispatch,) = _named(events, "lease.round.dispatch")
  (stack,) = _named(events, "pooling.stack")
  (execute,) = _named(events, "device.execute")
  assert _inside(stack, dispatch) and stack[3] <= execute[2]
  assert stack[4]["bytes"] == 2 * TASK_VOXELS  # two uint8 cutouts
  assert stack[4]["workers"] == 1  # below the copy pool's threshold
  # the unsampled round runs second, on the executor the first one used
  assert stack[4]["reused"] == 1 or mode == "sampled"


def test_u64_planes_split_and_pack_carry_bytes(u64_round):
  (dispatch,) = _named(u64_round, "lease.round.dispatch")
  (split,) = _named(u64_round, "pooling.u64_split")
  (pack,) = _named(u64_round, "pooling.u64_pack")
  (execute,) = _named(u64_round, "device.execute")
  assert _inside(split, dispatch) and _inside(pack, dispatch)
  assert split[3] <= execute[2] and execute[3] <= pack[2]
  assert split[4]["bytes"] == 2 * TASK_VOXELS * 8  # two uint64 cutouts
  assert pack[4]["bytes"] == 2 * TASK_VOXELS // 4 * 8  # their mip 1


def test_u64_planes_split_and_pack_carry_workers_and_reused(u64_round):
  (split,) = _named(u64_round, "pooling.u64_split")
  (pack,) = _named(u64_round, "pooling.u64_pack")
  # tiny cutouts copy inline; a fresh executor has no batch to reuse
  assert (split[4]["workers"], split[4]["reused"]) == (1, 0)
  assert pack[4]["workers"] == 1


@pytest.fixture(scope="module")
def staged_rounds(tmp_path_factory):
  """Two dispatches on each of two fresh executors, uint8 average and
  uint64 mode, under the profiler."""
  import jax

  from igneous_tpu.parallel.batch_runner import device_pyramid_batch

  rng = np.random.default_rng(5)
  logdir = str(tmp_path_factory.mktemp("staged"))
  assert not device_mod._PROFILE_STATE["active"]
  jax.profiler.start_trace(logdir)
  try:
    for method, planes, dtype in (("average", 1, np.uint8),
                                  ("mode", 2, np.uint64)):
      ex = ChunkExecutor(make_mesh(2), factors=((2, 2, 1),), method=method,
                         planes=planes)
      for _ in range(2):
        imgs = [rng.integers(0, 200, SHAPE).astype(dtype) for _ in range(2)]
        device_pyramid_batch(ex, imgs, planes == 2)
  finally:
    jax.profiler.stop_trace()
  return _events(logdir)


@pytest.mark.parametrize("name", ["pooling.stack", "pooling.u64_split"])
def test_staged_batch_is_reused_from_the_second_round(staged_rounds, name):
  assert [e[4]["reused"] for e in _named(staged_rounds, name)] == [0, 1]


def test_device_execute_carries_slab_walk(u64_round):
  (execute,) = _named(u64_round, "device.execute")
  assert execute[4]["kernel"] == "pooling.pyramid[mode]"
  assert (execute[4]["slabs"], execute[4]["slab_depth"]) == (4, 4)


def test_sampling_off_keeps_annotations_and_journals_nothing(rounds):
  events, spans, _stats = rounds["unsampled"]
  assert spans == []
  names = {e[1] for e in events}
  assert {"lease.round", "lease.acquire", "device.execute",
          "volume.download", "pipeline.encode.join"} <= names


def test_lease_journal_records_keep_names_and_attrs(rounds):
  """The journal sees the lease spans as the fleet simulator and replay
  read them; the loop's phases reach the profiler only."""
  spans = rounds["sampled"][1]
  worker = [s for s in spans if s["trace"] == trace.worker_trace_id()]
  (acquire,) = [s for s in worker if s["name"] == "lease.acquire"]
  (rnd,) = [s for s in worker if s["name"] == "lease.round"]
  assert acquire["members"] == 2 and acquire["range_sizes"] == [2]
  assert acquire["parent"] is None and acquire["dur"] >= 0
  assert {k: rnd[k] for k in ("members", "executed", "failed")} == {
    "members": 2, "executed": 2, "failed": 0}
  assert not [s for s in spans if s["name"].startswith("lease.round.")
              or s["name"] in ("lease.prefetch", "lease.prefetch_wait")]
  assert [s["kernel"] for s in worker if s["name"] == "device.execute"] == [
    "pooling.pyramid[average]"]


def test_span_set_and_drop():
  ctx = trace.SpanContext("c" * 16, "root", True)
  trace.reset()
  with trace.activate(ctx):
    kept = trace.span("lease.acquire", members=1)
    with kept as sid:
      kept.set(members=3)
    dropped = trace.span("lease.acquire")
    with dropped:
      dropped.drop()
  (rec,) = trace.drain_spans()
  assert rec["span"] == sid and rec["parent"] == "root"
  assert rec["members"] == 3


def test_encode_upload_timer_counts_without_sampling(tmp_path, monkeypatch):
  from igneous_tpu.pipeline.encoder import EncodePool

  monkeypatch.setenv("IGNEOUS_TRACE_SAMPLE", "0")
  before = metrics.timer_totals().get("pipeline.encode_upload.s",
                                      {"count": 0})["count"]
  pool = EncodePool(threads=2)
  try:
    ticket = pool.ticket()
    for _ in range(3):
      ticket.submit(lambda: None)
    ticket.join()
  finally:
    pool.shutdown()
  after = metrics.timer_totals()["pipeline.encode_upload.s"]["count"]
  assert after - before == 3


def test_pyramid_program_is_named_after_its_kernel():
  import jax

  ex = ChunkExecutor(make_mesh(2), factors=((2, 2, 1),), method="average")
  assert program_name(ex.name) == "pooling_pyramid_average"
  x = jax.device_put(np.zeros((2, 1, 4, 8, 8), np.uint8))
  assert "@jit_pooling_pyramid_average" in ex._fn.lower((x,)).as_text()
