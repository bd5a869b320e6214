"""ISSUE 7: device telemetry plane — kernel spans, recompile ledger,
HBM/utilization accounting, profiler trigger, fleet merge.

Satellite coverage checklist:
  * kernel spans nest under task traces on the CPU backend;
  * the recompile counter fires exactly once per new compiled signature;
  * HBM gauges no-op gracefully where memory_stats() is absent;
  * the flags-file profiler trigger round-trips (request → worker poll →
    capture → artifacts + journal marker, exactly once);
  * `igneous fleet devices` merges per-worker journal ledgers;
plus the health-engine device anomalies.
"""

import time

import numpy as np
import pytest

from igneous_tpu import task_creation as tc
from igneous_tpu import telemetry
from igneous_tpu.cli import main as cli_main
from igneous_tpu.observability import (
  device as device_mod,
  fleet,
  health,
  journal as journal_mod,
  perfetto,
  prom,
  rollup,
  trace,
)
from igneous_tpu.ops.oracle import (
  np_downsample_segmentation, np_downsample_with_averaging,
)
from igneous_tpu.parallel.executor import (
  BatchKernelExecutor, ChunkExecutor, make_mesh,
)
from igneous_tpu.pipeline import run_tasks_pipelined
from igneous_tpu.volume import Volume


@pytest.fixture(autouse=True)
def _clean_device_plane():
  telemetry.reset_all()
  trace.reset()
  journal_mod.set_active(None)
  device_mod.reset()
  yield
  telemetry.reset_all()
  trace.reset()
  journal_mod.set_active(None)
  device_mod.reset()


# -- recompile ledger ---------------------------------------------------------


def _xyz_oracle(chunks, oracle):
  """Mip 1 of each (c=1, z, y, x) chunk under ``oracle`` (an xyz numpy
  pyramid), back in (k, 1, z, y, x) order."""
  return np.stack([
    oracle(c[0].transpose(2, 1, 0), (2, 2, 1), 1)[0].transpose(2, 1, 0)[None]
    for c in chunks
  ])


# variant 0 -> 1 changes the shape, 1 -> 2 only the dtype: both are new
# signatures
_DTYPES = (np.uint8, np.uint8, np.uint16)


def _u8_chunks(k, y=8, dtype=np.uint8):
  rng = np.random.default_rng(k * 100 + y)
  return rng.integers(0, 255, (k, 1, 4, y, 8)).astype(dtype)


def _run_batch(variant, executors):
  ex = executors.setdefault(
    "batch", BatchKernelExecutor(lambda x: x * 2, name="tkern")
  )
  x = np.ones((4, 4, 4 * min(variant + 1, 2)),
              (np.float32, np.float32, np.float64)[variant])
  return "tkern", ex(x), x * 2


def _run_chunk(variant, executors):
  ex = executors.setdefault("chunk", ChunkExecutor(
    make_mesh(2), factors=((2, 2, 1),), method="average"))
  chunks = _u8_chunks(2, 8 * min(variant + 1, 2), _DTYPES[variant])
  (mip,), _ = ex(chunks)
  return ex.name, mip, _xyz_oracle(chunks, np_downsample_with_averaging)


def _run_chunk_planes2(variant, executors):
  ex = executors.setdefault("chunk_planes2", ChunkExecutor(
    make_mesh(2), factors=((2, 2, 1),), method="mode", planes=2))
  # the planes are always a uint32 pair: variant 2 changes the shape again
  rng = np.random.default_rng(variant)
  labels = rng.integers(0, 4, (2, 1, 4, 8 * (variant + 1), 8)).astype(
    np.uint64) * np.uint64(2**40 + 3)
  lo = (labels & np.uint64(0xFFFFFFFF)).astype(np.uint32)
  hi = (labels >> np.uint64(32)).astype(np.uint32)
  ((mlo, mhi),), _ = ex((lo, hi))
  got = mlo.astype(np.uint64) | (mhi.astype(np.uint64) << np.uint64(32))
  return ex.name, got, _xyz_oracle(labels, np_downsample_segmentation)


def _run_global(variant, executors):
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P

  mesh = make_mesh(2)
  ex = executors.setdefault("global", ChunkExecutor(
    mesh, factors=((2, 2, 1),), method="average"))
  chunks = _u8_chunks(2 * min(variant + 1, 2), dtype=_DTYPES[variant])
  placed = jax.device_put(chunks, NamedSharding(mesh, P(mesh.axis_names[0])))
  (mip,), _ = ex.run_global(placed)
  return ex.name, np.asarray(mip), _xyz_oracle(
    chunks, np_downsample_with_averaging)


def _run_paged_global(variant, executors):
  import jax
  from jax.sharding import NamedSharding, PartitionSpec as P

  from igneous_tpu.parallel.paged import PagedGlobalRunner

  mesh = make_mesh(2)
  runner = executors.setdefault("paged_global", PagedGlobalRunner(
    ((2, 2, 1),), method="average", mesh=mesh))
  pages = _u8_chunks(2 * min(variant + 1, 2), dtype=_DTYPES[variant])
  exts = np.full((len(pages), 3), (4, 8, 8), np.int32)
  rows = NamedSharding(mesh, P(mesh.axis_names[0]))
  (mip,) = runner(jax.device_put(pages, rows), jax.device_put(exts, rows))
  return runner.name, np.asarray(mip), _xyz_oracle(
    pages, np_downsample_with_averaging)


def _run_paged(variant, executors):
  from igneous_tpu.parallel.paged import PagedPyramid

  rng = np.random.default_rng(variant)
  imgs = [rng.integers(0, 255, s).astype(_DTYPES[variant])
          for s in [(20, 9, 5), (8, 8, 8)]]
  p = PagedPyramid(imgs, (2, 2, 1), 1, method="average",
                   page=(8, 8, 8 * min(variant + 1, 2)))
  got = np.concatenate([mips[0].ravel() for mips in p.run()])
  exp = np.concatenate([
    np_downsample_with_averaging(img, (2, 2, 1), 1)[0].ravel()
    for img in imgs
  ])
  return "pooling.paged_pyramid[average]", got, exp


@pytest.mark.parametrize("run", [
  _run_batch, _run_chunk, _run_chunk_planes2, _run_global,
  _run_paged_global, _run_paged,
], ids=["batch", "chunk", "chunk_planes2", "run_global", "paged_global",
        "paged"])
def test_recompile_counter_fires_once_per_new_signature(run, monkeypatch):
  """Every device executor compiles through one route: a new input
  signature ticks ``device.recompiles`` and the kernel's compiles once,
  with compile time counted apart from execute time; a repeat signature
  only executes."""
  from igneous_tpu.parallel import paged

  # fresh executors: a compiled signature held from an earlier test
  # would skip the compile this test counts
  monkeypatch.setattr(paged, "_PAGED_EXECUTORS", {})
  executors = {}
  ticks, executes = [], [0]
  for variant in (0, 0, 1, 1, 2, 2):
    name, got, exp = run(variant, executors)
    np.testing.assert_array_equal(np.asarray(got), exp)
    k = device_mod.LEDGER.snapshot()["kernels"][name]
    ticks.append(
      (telemetry.counters_snapshot()["device.recompiles"], k["compiles"])
    )
    executes.append(k["executes"])
  assert ticks == [(1, 1), (1, 1), (2, 2), (2, 2), (3, 3), (3, 3)]
  assert executes == list(range(7))  # every call executes exactly once
  assert device_mod.LEDGER.snapshot()["distinct_signatures"] == 3
  assert k["compile_s"] > 0 and k["execute_s"] > 0


def test_ledger_tracks_transfers_devices_and_utilization():
  ex = BatchKernelExecutor(lambda x: x * 2, name="scale")
  batch = np.ones((8, 16, 16), np.float32)
  ex(batch)
  snap = device_mod.LEDGER.snapshot()
  assert snap["h2d_bytes"] >= batch.nbytes
  assert snap["d2h_bytes"] > 0
  assert snap["dispatches"] == 1
  # 8 virtual CPU devices (conftest): every mesh member accrues busy time
  assert len(snap["devices"]) == 8
  assert 0 < snap["busy_ratio"] <= 1.0
  assert snap["kernels"]["scale"]["vox_per_sec"] > 0


# -- kernel spans nest under task traces (CPU backend) ------------------------


def test_device_spans_nest_under_task_trace(tmp_path, monkeypatch):
  monkeypatch.setenv("IGNEOUS_POOL_HOST", "0")  # device pyramid path
  path = f"file://{tmp_path}/img"
  rng = np.random.default_rng(3)
  data = rng.integers(0, 255, (256, 256, 64)).astype(np.uint8)
  Volume.from_numpy(data, path, chunk_size=(64, 64, 64), layer_type="image")
  # 4 equal-shaped tasks: the first dispatch compiles the signature, the
  # remaining three hit the cache and emit pure device.execute spans
  tasks = list(tc.create_downsampling_tasks(
    path, mip=0, num_mips=1, memory_target=2 * 1024 * 1024
  ))
  assert len(tasks) == 4 and trace.trace_of(tasks[0])
  stats = run_tasks_pipelined(tasks)
  assert stats["failed"] == 0 and stats["executed"] == len(tasks)

  spans = trace.drain_spans()
  task_ids = {trace.trace_of(t)["trace_id"] for t in tasks}
  dev_spans = [s for s in spans if s["name"] == "device.execute"]
  assert dev_spans, "device pyramid must emit device.execute spans"
  for s in dev_spans:
    # nested: the span belongs to the task's trace AND parents into its
    # execution span tree (not a detached root)
    assert s["trace"] in task_ids
    assert s.get("parent")
    assert s.get("device", "").startswith("cpu:")
  compile_spans = [s for s in spans if s["name"] == "device.compile"]
  assert compile_spans, "first signature must record a compile span"


def test_device_spans_fall_back_to_worker_trace_without_task_ctx():
  ex = BatchKernelExecutor(lambda x: x + 1, name="rootkern")
  ex(np.ones((4, 4), np.float32))
  spans = [s for s in trace.drain_spans()
           if s["name"].startswith("device.")]
  assert spans
  assert all(s["trace"] == trace.worker_trace_id() for s in spans)


def test_device_spans_render_on_perfetto_device_tracks():
  ex = BatchKernelExecutor(lambda x: x + 1, name="trackkern")
  ex(np.ones((4, 4), np.float32))
  recs = [dict(s, kind="span", worker="w0") for s in trace.drain_spans()]
  doc = perfetto.chrome_trace(recs)
  names = [
    e["args"]["name"] for e in doc["traceEvents"]
    if e.get("ph") == "M" and e["name"] == "thread_name"
  ]
  assert any(n.startswith("device cpu:") for n in names)
  dev_events = [
    e for e in doc["traceEvents"]
    if e.get("ph") == "X" and e["name"].startswith("device.")
  ]
  assert dev_events and all(e["tid"] >= 10_000 for e in dev_events)


# -- HBM gauges ---------------------------------------------------------------


def test_hbm_gauges_noop_gracefully_on_cpu():
  # XLA CPU devices answer memory_stats() with None: the sample must
  # return empty, set no gauges, and raise nothing
  assert device_mod.LEDGER.sample_hbm() == {}
  device_mod.publish_gauges()  # utilization may set a gauge; hbm must not
  gauges = telemetry.gauges_snapshot()
  assert not any(k.startswith("device.hbm") for k in gauges)


def test_hbm_highwater_keeps_peak_across_samples(monkeypatch):
  class FakeDev:
    platform, id = "tpu", 0

    def __init__(self, stats):
      self._stats = stats

    def memory_stats(self):
      return self._stats

  import jax

  monkeypatch.setattr(
    jax, "local_devices",
    lambda: [FakeDev({"bytes_in_use": 10, "peak_bytes_in_use": 90,
                      "bytes_limit": 100})],
  )
  device_mod.LEDGER.sample_hbm()
  monkeypatch.setattr(
    jax, "local_devices",
    lambda: [FakeDev({"bytes_in_use": 5, "peak_bytes_in_use": 40,
                      "bytes_limit": 100})],
  )
  out = device_mod.LEDGER.sample_hbm()
  # the ledger's high-water never regresses even when the backend's does
  assert device_mod.LEDGER.hbm["tpu:0"]["peak_bytes_in_use"] == 90
  assert out["tpu:0"]["peak_bytes_in_use"] == 90
  assert telemetry.gauges_snapshot()["device.hbm.peak_bytes"] == 90.0


# -- fast-path eligibility ----------------------------------------------------


def test_fastpath_ratio_gauge_and_counters():
  device_mod.LEDGER.record_fastpath(batched=3)
  device_mod.LEDGER.record_fastpath(host=1)
  counters = telemetry.counters_snapshot()
  assert counters["device.fastpath.batched"] == 3
  assert counters["device.fastpath.host"] == 1
  assert telemetry.gauges_snapshot()["device.fastpath_ratio"] == 0.75


# -- prometheus ---------------------------------------------------------------


def test_prom_renders_igneous_device_metrics():
  ex = BatchKernelExecutor(lambda x: x + 1, name="promkern")
  ex(np.ones((4, 4), np.float32))
  device_mod.publish_gauges()
  text = prom.render()
  assert "igneous_device_recompiles_total 1" in text
  assert "igneous_device_busy_ratio" in text
  assert "igneous_device_execute_s_seconds_count" in text


# -- journal + fleet merge ----------------------------------------------------


def _device_record(worker, ts, **kw):
  rec = {
    "kind": "device", "worker": worker, "ts": ts,
    "t_start": ts - 60.0, "wall_s": 60.0,
    "busy_s": kw.pop("busy_s", 6.0),
    "busy_ratio": kw.pop("busy_ratio", 0.1),
    "dispatches": kw.pop("dispatches", 5),
    "recompiles": kw.pop("recompiles", 1),
    "distinct_signatures": 1,
    "kernels": {"pooling.pyramid[average]": {
      "compiles": 1, "compile_s": 0.2, "executes": 5, "execute_s": 6.0,
      "elements": 6_000_000, "bytes": 6_000_000,
      "vox_per_sec": 1_000_000.0, "bytes_per_sec": 1_000_000.0,
    }},
    "devices": {"cpu:0": 6.0},
    "fastpath": kw.pop("fastpath", {"batched": 4, "host": 1}),
    "h2d_bytes": 100, "d2h_bytes": 50,
  }
  rec.update(kw)
  return rec


def test_journal_flush_carries_device_record(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  j = journal_mod.Journal(jpath, worker_id="w-dev")
  journal_mod.set_active(j)
  device_mod.install()
  try:
    ex = BatchKernelExecutor(lambda x: x + 1, name="jkern")
    ex(np.ones((4, 4), np.float32))
    assert j.flush(event="test")
  finally:
    journal_mod.set_active(None)
  recs = fleet.load(jpath)
  devrecs = [r for r in recs if r.get("kind") == "device"]
  assert len(devrecs) == 1
  assert devrecs[0]["worker"] == "w-dev"
  assert devrecs[0]["kernels"]["jkern"]["executes"] == 1
  # idle flush on the same journal: the ledger did not change, so the
  # new segment carries no second device record
  journal_mod.set_active(j)
  try:
    j.flush(event="idle")
  finally:
    journal_mod.set_active(None)
  recs = fleet.load(jpath)
  assert len([r for r in recs if r.get("kind") == "device"]) == 1


def test_fleet_devices_merges_ledgers(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  now = time.time()
  j1 = journal_mod.Journal(jpath, worker_id="w1")
  # w1 writes two cumulative snapshots: the merge must keep the newest
  j1.write_records([_device_record("w1", now - 30, dispatches=2)])
  j1.write_records([_device_record("w1", now, dispatches=9)])
  j2 = journal_mod.Journal(jpath, worker_id="w2")
  j2.write_records([_device_record("w2", now, dispatches=4)])

  ledgers = device_mod.device_ledgers(fleet.load(jpath))
  assert set(ledgers) == {"w1", "w2"}
  assert ledgers["w1"]["dispatches"] == 9
  lines = device_mod.render_devices(ledgers)
  text = "\n".join(lines)
  assert "w1" in text and "w2" in text and "cpu:0" in text
  assert "fast path: 8/10 deliveries batched" in text

  from click.testing import CliRunner

  res = CliRunner().invoke(
    cli_main, ["fleet", "devices", "--journal", jpath]
  )
  assert res.exit_code == 0, res.output
  assert "pooling.pyramid[average]" in res.output
  res = CliRunner().invoke(
    cli_main, ["fleet", "devices", "--journal", jpath, "--json"]
  )
  assert res.exit_code == 0
  import json

  doc = json.loads(res.output)
  assert doc["summary"]["workers"] == 2
  assert doc["summary"]["dispatches"] == 13


def test_rollup_compaction_preserves_device_ledgers(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  now = time.time()
  j1 = journal_mod.Journal(jpath, worker_id="w1")
  j1.write_records([_device_record("w1", now - 30, dispatches=2)])
  j1.write_records([_device_record("w1", now, dispatches=7)])
  res = rollup.compact(jpath)
  assert res["segments_compacted"] == 2
  ledgers = device_mod.device_ledgers(fleet.load_effective(jpath))
  assert ledgers["w1"]["dispatches"] == 7  # latest survives compaction


# -- health engine device anomalies ------------------------------------------


def _task_span(worker, ts, dur=0.5):
  return {"kind": "span", "worker": worker, "name": "task", "ts": ts,
          "dur": dur, "trace": trace.new_id(), "span": trace.new_id(),
          "parent": None}


def test_health_recompile_storm_anomaly():
  now = time.time()
  records = [
    _task_span("w1", now - 30),
    _device_record("w1", now - 60, recompiles=2),
    _device_record("w1", now, recompiles=44),  # 42 in 60s = 42/min
  ]
  report = health.HealthEngine().evaluate(records, {"backlog": 0}, now=now)
  kinds = [a["kind"] for a in report["anomalies"]]
  assert "recompile_storm" in kinds
  storm = next(a for a in report["anomalies"]
               if a["kind"] == "recompile_storm")
  assert storm["worker"] == "w1" and storm["recompiles"] == 42
  # startup compiles below the floor never read as a storm
  records = [
    _task_span("w1", now - 30),
    _device_record("w1", now, recompiles=5),
  ]
  report = health.HealthEngine().evaluate(records, {"backlog": 0}, now=now)
  assert "recompile_storm" not in [a["kind"] for a in report["anomalies"]]


def test_health_hbm_high_water_anomaly():
  now = time.time()
  records = [_device_record(
    "w1", now,
    hbm={"tpu:0": {"bytes_in_use": 80, "peak_bytes_in_use": 95,
                   "bytes_limit": 100}},
  )]
  report = health.HealthEngine().evaluate(records, {"backlog": 0}, now=now)
  hw = [a for a in report["anomalies"] if a["kind"] == "hbm_high_water"]
  assert hw and hw[0]["device"] == "tpu:0" and hw[0]["peak_frac"] == 0.95
  assert report["devices"]["hbm_peak_frac"] == 0.95


def test_health_device_idle_while_backlogged():
  now = time.time()
  records = [
    _task_span("w1", now - 10),
    _device_record("w1", now, busy_ratio=0.01),
  ]
  report = health.HealthEngine().evaluate(records, {"backlog": 50}, now=now)
  idle = [a for a in report["anomalies"] if a["kind"] == "device_idle"]
  assert idle and idle[0]["worker"] == "w1"
  # no backlog: an idle device is a finished campaign, not an anomaly
  report = health.HealthEngine().evaluate(records, {"backlog": 0}, now=now)
  assert not [a for a in report["anomalies"] if a["kind"] == "device_idle"]
  # busy device with backlog: healthy overlap, no anomaly
  records[1] = _device_record("w1", now, busy_ratio=0.8)
  report = health.HealthEngine().evaluate(records, {"backlog": 50}, now=now)
  assert not [a for a in report["anomalies"] if a["kind"] == "device_idle"]


def test_watch_dashboard_shows_device_line():
  now = time.time()
  records = [
    _task_span("w1", now - 10),
    _device_record("w1", now, busy_ratio=0.25),
  ]
  report = health.HealthEngine().evaluate(records, {"backlog": 0}, now=now)
  lines = health.render_dashboard(report)
  devline = [ln for ln in lines if ln.startswith("devices:")]
  assert devline and "busy 25.0%" in devline[0]
  assert "fastpath 4/5 batched" in devline[0]


# -- profiler: flags-file trigger + hardened context manager ------------------


def _wait_capture_done(timeout=30.0):
  deadline = time.monotonic() + timeout
  while time.monotonic() < deadline:
    if not device_mod._PROFILE_STATE["active"]:
      return
    time.sleep(0.05)
  raise AssertionError("profiler capture thread never finished")


def test_profile_flags_trigger_roundtrip(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  j = journal_mod.Journal(jpath, worker_id="w-prof")
  req = device_mod.write_profile_request(jpath, duration_sec=0.1)
  assert device_mod.read_profile_request(jpath)["id"] == req["id"]

  assert device_mod.poll_profile_trigger(j) is True
  _wait_capture_done()
  artifacts = device_mod.list_profiles(jpath)
  assert artifacts, "capture must upload artifacts under profiles/"
  assert all(a.startswith(f"profiles/w-prof-{req['id']}/")
             for a in artifacts)
  # the journal carries the capture marker with the request id
  markers = [
    r for r in fleet.load(jpath)
    if r.get("kind") == "span" and r.get("name") == "device.profile"
  ]
  assert markers and markers[0]["request_id"] == req["id"]
  assert markers[0]["artifacts"] == len(artifacts)
  # one-shot: the same request never triggers twice on this worker
  assert device_mod.poll_profile_trigger(j) is False


def test_profile_request_restricted_to_named_workers(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  j = journal_mod.Journal(jpath, worker_id="w-other")
  device_mod.write_profile_request(
    jpath, duration_sec=0.1, workers=["w-target"]
  )
  assert device_mod.poll_profile_trigger(j) is False


def test_stale_profile_request_ignored(tmp_path):
  jpath = f"file://{tmp_path}/journal"
  from igneous_tpu.storage import CloudFiles

  CloudFiles(jpath).put_json(device_mod.PROFILE_REQUEST_KEY, {
    "id": "old", "ts": time.time() - 10_000, "duration_sec": 0.1,
  })
  assert device_mod.read_profile_request(jpath) is None


