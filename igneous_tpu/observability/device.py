"""Device telemetry plane: kernel spans, recompile/HBM accounting,
utilization ledger, on-demand profiler capture (ISSUE 7).

PRs 5-6 made the *fleet* observable; the device itself stayed a black
box — "the pipeline is I/O-dominated and the TPU mostly idles" was
folklore, not a number. This module is the instrument:

* **Kernel spans** — the executors (``parallel/executor.py``), the
  single-task device pyramid (``ops/pooling.downsample``), and thereby
  every pipeline compute stage emit ``device.compile`` vs
  ``device.execute`` spans (timed through ``block_until_ready``) plus
  ``device.h2d``/``device.d2h`` transfer spans with byte counts. Spans
  nest under whatever task/stage trace context is active on the calling
  thread (PR 5), so ``fleet trace`` and the Perfetto export show the
  device work inside the task that caused it — on its own per-device
  track. Each is a ``trace.span``, so a profiler trace also shows it as
  ``igneous.device.<phase>`` with its ``kernel`` (and ``bytes``) beside
  the device's own operations.
* **Recompile / shape-churn ledger** — distinct compiled signatures
  per kernel are counted; ``device.recompiles`` increments exactly once
  per NEW signature (the ragged-batching baseline number), and the
  fast-path eligibility gauge tracks batched vs fell-to-host deliveries.
* **HBM + utilization accounting** — per-kernel peak-memory watermarks
  and live-buffer gauges from ``Device.memory_stats()`` (graceful no-op
  on backends without them — XLA CPU returns None), and a per-worker
  utilization ledger: device-busy seconds / wall seconds, per-kernel
  vox/s and bytes/s. The ledger is CUMULATIVE and flushes into the
  journal as ``{"kind": "device"}`` records (latest-per-worker is
  lossless, so rollups keep only that), surfaces as ``igneous_device_*``
  Prometheus gauges, the ``igneous fleet devices`` CLI, the ``fleet
  watch`` dashboard, and three new HealthEngine anomalies (recompile
  storm, HBM high-water, device idle-while-backlogged).
* **On-demand profiler capture** — ``igneous profile capture`` publishes
  ``<journal>/profile/request.json``; workers poll it (same pattern as
  the PR 6 straggler flags) and run a bounded ``jax.profiler`` trace,
  uploading the artifacts next to the journal under ``profiles/``.
  ``IGNEOUS_PROFILE_EVERY`` additionally samples every Nth device
  dispatch into ``IGNEOUS_PROFILE_DIR`` with zero flag-file traffic.

Everything here must be safe on accelerator-less hosts and cost nothing
when idle: ledger updates are a dict update under one lock, span records
only allocate when a sampled trace context is active (or on the worker
trace), and the profiler is inert unless explicitly triggered.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
from typing import Dict, Iterator, List, Optional

from . import metrics, trace

from ..analysis import knobs

PROFILE_DIR_ENV = "IGNEOUS_PROFILE_DIR"
PROFILE_EVERY_ENV = "IGNEOUS_PROFILE_EVERY"
PROFILE_REQUEST_KEY = "profile/request.json"
PROFILE_ARTIFACT_PREFIX = "profiles/"
# how often a worker re-reads <journal>/profile/request.json (one small
# object GET, piggybacked on the journal maybe_flush cadence — the same
# deal as LeaseBatcher's straggler-flag poll)
PROFILE_POLL_SEC = 15.0
# a capture request older than this is history, not a trigger: a worker
# booting days later must not burn minutes profiling for nobody
PROFILE_REQUEST_TTL_SEC = 600.0


# ---------------------------------------------------------------------------
# utilization ledger


class DeviceLedger:
  """Process-wide cumulative accounting of device work.

  One instance per worker process (module singleton). All totals are
  monotonic since ``t_start`` so the journal's latest-per-worker record
  is a complete summary — rollup compaction keeps exactly that.
  """

  def __init__(self):
    self.lock = threading.Lock()
    self.reset()

  def reset(self) -> None:
    with self.lock:
      self.t_start = time.time()  # guarded-by: self.lock
      self._t0 = time.monotonic()  # guarded-by: self.lock
      # kernel -> cumulative stats
      self.kernels: Dict[str, dict] = {}  # guarded-by: self.lock
      # (kernel, signature-repr) seen-set: the recompile ledger
      self._signatures: set = set()  # guarded-by: self.lock
      # device label -> cumulative busy seconds
      self.device_busy: Dict[str, float] = {}  # guarded-by: self.lock
      self.h2d_bytes = 0  # guarded-by: self.lock
      self.d2h_bytes = 0  # guarded-by: self.lock
      self.h2d_seconds = 0.0  # guarded-by: self.lock
      self.d2h_seconds = 0.0  # guarded-by: self.lock
      self.recompiles = 0  # guarded-by: self.lock
      self.dispatches = 0  # guarded-by: self.lock
      self.fastpath = {"batched": 0, "host": 0}  # guarded-by: self.lock
      # padding-byte accounting across every batched dispatch (pow2
      # batch rounding, page-pool filler slots, infer group fill)
      self.pad_bytes = 0  # guarded-by: self.lock
      self.real_bytes = 0  # guarded-by: self.lock
      # device label -> last sampled memory stats (+ peak high-water)
      self.hbm: Dict[str, dict] = {}  # guarded-by: self.lock
      # anything recorded since the last journal flush? An idle worker
      # must not grow a segment per flush interval forever
      self._dirty = False  # guarded-by: self.lock

  def _kernel_locked(self, name: str) -> dict:
    k = self.kernels.get(name)
    if k is None:
      k = self.kernels[name] = {
        "compiles": 0, "compile_s": 0.0,
        "executes": 0, "execute_s": 0.0,
        "elements": 0, "bytes": 0,
      }
    return k

  # -- write side -----------------------------------------------------------

  def note_signature(self, kernel: str, signature) -> bool:
    """True exactly once per (kernel, signature): the recompile tick.
    Counter contract (ISSUE 7 acceptance): ``device.recompiles``
    increments ONLY when a shape/dtype signature is first compiled."""
    key = (kernel, repr(signature))
    with self.lock:
      if key in self._signatures:
        return False
      self._signatures.add(key)
      self.recompiles += 1
    metrics.incr("device.recompiles")
    return True

  def record_compile(self, kernel: str, seconds: float) -> None:
    with self.lock:
      k = self._kernel_locked(kernel)
      k["compiles"] += 1
      k["compile_s"] += float(seconds)
      self._dirty = True

  def record_execute(self, kernel: str, seconds: float,
                     elements: int = 0, nbytes: int = 0,
                     devices: Optional[List[str]] = None) -> None:
    """One device dispatch: ``seconds`` of wall time in which the listed
    devices were busy (the program is sharded across all of them, so
    each is attributed the full interval)."""
    seconds = float(seconds)
    with self.lock:
      k = self._kernel_locked(kernel)
      k["executes"] += 1
      k["execute_s"] += seconds
      k["elements"] += int(elements)
      k["bytes"] += int(nbytes)
      self.dispatches += 1
      self._dirty = True
      for dev in devices or ("device",):
        self.device_busy[dev] = self.device_busy.get(dev, 0.0) + seconds

  def record_transfer(self, direction: str, nbytes: int,
                      seconds: float) -> None:
    with self.lock:
      self._dirty = True
      if direction == "h2d":
        self.h2d_bytes += int(nbytes)
        self.h2d_seconds += float(seconds)
      else:
        self.d2h_bytes += int(nbytes)
        self.d2h_seconds += float(seconds)

  def record_fastpath(self, batched: int = 0, host: int = 0) -> None:
    """Fast-path eligibility accounting: ``batched`` deliveries rode a
    batched device dispatch, ``host`` fell to the per-task host path
    (ragged shape, single-member group, accelerator-less worker)."""
    with self.lock:
      self.fastpath["batched"] += int(batched)
      self.fastpath["host"] += int(host)
      self._dirty = True
      b, h = self.fastpath["batched"], self.fastpath["host"]
    if batched:
      metrics.incr("device.fastpath.batched", int(batched))
    if host:
      metrics.incr("device.fastpath.host", int(host))
    if b + h:
      metrics.gauge_set("device.fastpath_ratio", b / (b + h))

  def record_pad_waste(self, padded_bytes: int = 0,
                       real_bytes: int = 0) -> None:
    """Padding-byte accounting for batched dispatches: ``padded_bytes``
    were filler (pow2 batch rounding, page-pool slack, dispatch-group
    fill), ``real_bytes`` carried cutout data. The exported gauge is the
    cumulative padded/real ratio — the waste the ragged paged packer
    exists to eliminate. Padding layers can nest (a paged round's filler
    pages also ride the executor's own pow2 rounding), so the totals are
    additive bookkeeping of every layer's slack, not disjoint memory."""
    with self.lock:
      self.pad_bytes += int(padded_bytes)
      self.real_bytes += int(real_bytes)
      self._dirty = True
      p, r = self.pad_bytes, self.real_bytes
    if r:
      metrics.gauge_set("device.pad_waste_ratio", p / r)

  def sample_hbm(self) -> Dict[str, dict]:
    """Poll ``Device.memory_stats()`` on every local device; a backend
    without them (XLA CPU) simply contributes nothing — the gauges
    no-op instead of erroring (ISSUE 7 acceptance)."""
    try:
      import jax

      devices = jax.local_devices()
    except Exception:
      return {}
    out = {}
    for dev in devices:
      try:
        stats = dev.memory_stats()
      except Exception:
        stats = None
      if not stats:
        continue
      label = f"{dev.platform}:{dev.id}"
      rec = {
        "bytes_in_use": int(stats.get("bytes_in_use", 0)),
        "peak_bytes_in_use": int(
          stats.get("peak_bytes_in_use", stats.get("bytes_in_use", 0))
        ),
      }
      limit = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
      if limit:
        rec["bytes_limit"] = int(limit)
      out[label] = rec
    if out:
      with self.lock:
        for label, rec in out.items():
          prev = self.hbm.get(label) or {}
          rec["peak_bytes_in_use"] = max(
            rec["peak_bytes_in_use"], prev.get("peak_bytes_in_use", 0)
          )
          self.hbm[label] = rec
      worst = max(out.values(), key=lambda r: r["peak_bytes_in_use"])
      metrics.gauge_set("device.hbm.bytes_in_use", worst["bytes_in_use"])
      metrics.gauge_max("device.hbm.peak_bytes", worst["peak_bytes_in_use"])
      if worst.get("bytes_limit"):
        # the PrometheusRule divides peak by this for the high-water alert
        metrics.gauge_set("device.hbm.bytes_limit", worst["bytes_limit"])
    return out

  # -- read side ------------------------------------------------------------

  def busy_seconds(self) -> float:
    with self.lock:
      return max(self.device_busy.values(), default=0.0)

  def utilization(self) -> Optional[float]:
    """device-busy seconds / wall seconds since ledger start, using the
    busiest device (the program shards across all of them, so the
    busiest one bounds what overlap could still hide). None before any
    dispatch — "no device work" and "device idle" are different facts."""
    wall = time.monotonic() - self._t0
    if wall <= 0 or not self.device_busy:
      return None
    return min(self.busy_seconds() / wall, 1.0)

  def snapshot(self) -> Optional[dict]:
    """The journal/Prometheus view; None when no device work happened
    (accelerator-less workers write no device records at all)."""
    with self.lock:
      if not self.dispatches and not self.fastpath["host"] \
         and not self.h2d_bytes:
        return None
      wall = max(time.monotonic() - self._t0, 1e-9)
      kernels = {}
      for name, k in self.kernels.items():
        kernels[name] = {
          **{key: (round(v, 4) if isinstance(v, float) else v)
             for key, v in k.items()},
          "vox_per_sec": (
            round(k["elements"] / k["execute_s"], 1)
            if k["execute_s"] > 0 else None
          ),
          "bytes_per_sec": (
            round(k["bytes"] / k["execute_s"], 1)
            if k["execute_s"] > 0 and k["bytes"] else None
          ),
        }
      busy = max(self.device_busy.values(), default=0.0)
      snap = {
        "ts": time.time(),
        "t_start": self.t_start,
        "wall_s": round(wall, 3),
        "busy_s": round(busy, 4),
        "busy_ratio": round(min(busy / wall, 1.0), 4),
        "dispatches": self.dispatches,
        "recompiles": self.recompiles,
        "distinct_signatures": len(self._signatures),
        "kernels": kernels,
        "devices": {
          dev: round(s, 4) for dev, s in sorted(self.device_busy.items())
        },
        "fastpath": dict(self.fastpath),
        "pad_bytes": self.pad_bytes,
        "real_bytes": self.real_bytes,
        "pad_waste_ratio": (
          round(self.pad_bytes / self.real_bytes, 4)
          if self.real_bytes else None
        ),
        "h2d_bytes": self.h2d_bytes,
        "d2h_bytes": self.d2h_bytes,
        "h2d_MBps": (
          round(self.h2d_bytes / self.h2d_seconds / 1e6, 1)
          if self.h2d_seconds > 0 else None
        ),
        "d2h_MBps": (
          round(self.d2h_bytes / self.d2h_seconds / 1e6, 1)
          if self.d2h_seconds > 0 else None
        ),
      }
      if self.hbm:
        snap["hbm"] = {dev: dict(rec) for dev, rec in self.hbm.items()}
      return snap


LEDGER = DeviceLedger()


def reset() -> None:
  """Testing hook: fresh ledger + profiler trigger state."""
  LEDGER.reset()
  _PROFILE_STATE.update(cache=(0.0, None), served=set(), active=False)


def publish_gauges() -> None:
  """Ledger → ``igneous_device_*`` gauges (rendered by prom.render):
  busy ratio, dispatch/recompile tallies (the counters register at
  record time), and the HBM watermarks sampled fresh."""
  util = LEDGER.utilization()
  if util is not None:
    metrics.gauge_set("device.busy_ratio", util)
  LEDGER.sample_hbm()


# ---------------------------------------------------------------------------
# span emission — called by the executors around each device phase


def require_worker_backend() -> None:
  """Fail a worker that should hold an accelerator but landed on the CPU.

  With ``JAX_PLATFORMS`` unset (or naming tpu), a process that cannot get
  the chip, such as a second worker on a one-chip host, falls back to
  JAX's CPU backend with only a warning and would then take every host
  path. A worker pinned with ``JAX_PLATFORMS=cpu`` is a CPU worker by
  choice and passes."""
  plats = os.environ.get("JAX_PLATFORMS", "")
  if plats.split(",")[0].strip().lower() == "cpu":
    return
  import jax

  if jax.default_backend() == "cpu":
    raise RuntimeError(
      "device worker has no accelerator (JAX fell back to the CPU): a "
      "chip serves one process, so run one worker per chip with "
      "`execute --batch K`, or pin CPU workers with JAX_PLATFORMS=cpu"
    )


def _devices_of(mesh=None) -> List[str]:
  if mesh is not None:
    try:
      return [f"{d.platform}:{d.id}" for d in mesh.devices.flat]
    except Exception:
      pass
  try:  # un-meshed dispatch runs on the default device
    import jax

    d = jax.devices()[0]
    return [f"{d.platform}:{d.id}"]
  except Exception:
    return ["device"]


@contextlib.contextmanager
def compile_span(kernel: str, devices: List[str]) -> Iterator[None]:
  """Time one XLA compilation (lower+compile) and account it to the
  ledger."""
  sp = trace.span("device.compile", worker=True, kernel=kernel,
                  device=devices[0] if devices else None)
  t0 = time.perf_counter()
  try:
    with sp:
      yield
  finally:
    dt = time.perf_counter() - t0
    LEDGER.record_compile(kernel, dt)
    metrics.observe_quiet("device.compile.s", dt)


@contextlib.contextmanager
def execute_span(kernel: str, elements: int = 0, nbytes: int = 0,
                 mesh=None, **attrs) -> Iterator[None]:
  """Time one device dispatch. The caller must block on the result
  INSIDE the context (``jax.block_until_ready``) — dispatch is async and
  an unblocked timing would measure enqueue, not execution.

  Extra keyword ``attrs`` ride onto the emitted ``device.execute`` span
  verbatim (e.g. the fused pyramid kernel's ``mip_from``/``mip_to``)."""
  devices = _devices_of(mesh)
  sp = trace.span("device.execute", worker=True, kernel=kernel,
                  elements=elements, device=devices[0] if devices else None,
                  devices=len(devices), **attrs)
  t0 = time.perf_counter()
  try:
    with sp:
      yield
  finally:
    dt = time.perf_counter() - t0
    LEDGER.record_execute(kernel, dt, elements=elements, nbytes=nbytes,
                          devices=devices)
    metrics.observe_quiet("device.execute.s", dt)
    maybe_sample_profile()


def run_solo(kernel: str, fn, args, signature, static=None,
             elements: int = 0, nbytes: int = 0, **attrs):
  """One un-meshed dispatch of the jitted ``fn(*args, **static)`` on the
  default device. A fresh ``signature`` first compiles ahead of time
  inside a compile span (which fills jit's own cache); every call then
  runs inside an execute span, blocked on its result."""
  import jax

  static = static or {}
  if LEDGER.note_signature(kernel, signature):
    with compile_span(kernel, _devices_of()):
      fn.lower(*args, **static).compile()
  with execute_span(kernel, elements=elements, nbytes=nbytes, **attrs):
    out = fn(*args, **static)
    jax.block_until_ready(out)
  return out


@contextlib.contextmanager
def transfer_span(direction: str, nbytes: int, kernel: str = "",
                  mesh=None) -> Iterator[None]:
  """Time one host<->device transfer (``direction`` is "h2d" or "d2h")
  with its byte count."""
  devices = _devices_of(mesh)
  sp = trace.span(f"device.{direction}", worker=True, kernel=kernel or None,
                  bytes=int(nbytes), device=devices[0] if devices else None)
  t0 = time.perf_counter()
  try:
    with sp:
      yield
  finally:
    dt = time.perf_counter() - t0
    LEDGER.record_transfer(direction, nbytes, dt)
    metrics.observe_quiet(f"device.{direction}.s", dt)


def nbytes_of(tree) -> int:
  """Total bytes across a pytree of arrays (transfer span byte counts)."""
  try:
    import jax

    return sum(int(getattr(l, "nbytes", 0)) for l in jax.tree.leaves(tree))
  except Exception:
    return 0


def elements_of(tree) -> int:
  try:
    import jax

    return sum(int(getattr(l, "size", 0)) for l in jax.tree.leaves(tree))
  except Exception:
    return 0


# ---------------------------------------------------------------------------
# journal integration


def journal_records() -> List[dict]:
  """The journal flush hook (registered via
  ``journal.register_record_provider``): one cumulative ``device``
  record per flush — only when the ledger changed since the last one
  (an idle worker must not mint a fresh segment every interval)."""
  with LEDGER.lock:
    dirty = LEDGER._dirty
    LEDGER._dirty = False
  if not dirty:
    return []
  publish_gauges()  # refreshes HBM watermarks before the snapshot
  snap = LEDGER.snapshot()
  if snap is None:
    return []
  snap["kind"] = "device"
  return [snap]


def install() -> None:
  """Wire the device plane into an active journal-bearing worker:
  ledger records ride every journal flush, and the profiler trigger is
  polled on the same cadence. Idempotent."""
  from . import journal as journal_mod

  journal_mod.register_record_provider(journal_records)
  journal_mod.register_poll_hook(poll_profile_trigger)


# ---------------------------------------------------------------------------
# on-demand profiler capture


_PROFILE_STATE = {
  "cache": (0.0, None),  # (checked_at_monotonic, request-or-None)
  "served": set(),       # request ids this process already captured
  "active": False,       # a capture thread is running
}
_PROFILE_LOCK = threading.Lock()


def write_profile_request(journal_path: str, duration_sec: float = 5.0,
                          workers: Optional[List[str]] = None,
                          request_id: Optional[str] = None) -> dict:
  """Publish a capture request where workers can see it (the ``igneous
  profile capture`` CLI). ``workers`` restricts the trigger; None means
  every worker that polls the flag captures once."""
  from ..storage import CloudFiles

  req = {
    "id": request_id or trace.new_id(),
    "ts": time.time(),
    "duration_sec": float(duration_sec),
    "workers": list(workers) if workers else None,
  }
  CloudFiles(journal_path).put_json(PROFILE_REQUEST_KEY, req)
  return req


def read_profile_request(journal_path: str) -> Optional[dict]:
  from ..storage import CloudFiles

  try:
    req = CloudFiles(journal_path).get_json(PROFILE_REQUEST_KEY)
  except Exception:
    return None
  if not req or not req.get("id"):
    return None
  if time.time() - float(req.get("ts") or 0) > PROFILE_REQUEST_TTL_SEC:
    return None
  return req


def poll_profile_trigger(journal=None) -> bool:
  """Worker-side poll (TTL-cached, piggybacked on the journal flush
  cadence): when a fresh capture request names this worker (or no one
  in particular), run one bounded profiler capture in the background.
  Returns True when a capture was started."""
  j = journal
  if j is None:
    from . import journal as journal_mod

    j = journal_mod.get_active()
  if j is None:
    return False
  now = time.monotonic()
  checked_at, req = _PROFILE_STATE["cache"]
  if now - checked_at > PROFILE_POLL_SEC:
    req = read_profile_request(j.cloudpath)
    _PROFILE_STATE["cache"] = (now, req)
  if req is None:
    return False
  if req["id"] in _PROFILE_STATE["served"]:
    return False
  targets = req.get("workers")
  if targets and j.worker_id not in targets:
    return False
  _PROFILE_STATE["served"].add(req["id"])
  return start_capture(
    duration_sec=float(req.get("duration_sec") or 5.0),
    journal=j, request_id=req["id"],
  )


def start_capture(duration_sec: float, journal=None,
                  request_id: str = "manual",
                  logdir: Optional[str] = None) -> bool:
  """Run one bounded ``jax.profiler`` capture on a background thread
  (the worker keeps executing — profiling the device plane must not
  idle it) and upload the artifacts next to the journal. Returns False
  when a capture is already running or the profiler is unavailable.

  The thread is deliberately NON-daemon: the XLA profiler leaves
  thread-local state behind, and an unjoined profiler thread at
  interpreter exit segfaults in TSL teardown (reproduced on jaxlib
  0.4.36 CPU: daemon capture thread + normal exit → SIGSEGV with no
  Python frame). Non-daemon means threading's shutdown joins it before
  the interpreter tears down — which also guarantees a draining
  worker's capture artifacts land instead of dying with the pod."""
  with _PROFILE_LOCK:
    if _PROFILE_STATE["active"]:
      return False
    _PROFILE_STATE["active"] = True

  def run():
    try:
      _capture_blocking(duration_sec, journal, request_id, logdir)
    finally:
      _PROFILE_STATE["active"] = False

  threading.Thread(target=run, daemon=False, name="ig-profile").start()
  return True


def _capture_blocking(duration_sec, journal, request_id, logdir):
  import tempfile

  from . import metrics as metrics_mod

  try:
    import jax
  except Exception:
    return
  base = logdir or knobs.get_str(PROFILE_DIR_ENV)
  tmp = None
  if not base:
    tmp = tempfile.mkdtemp(prefix="igneous-profile-")
    base = tmp
  worker = journal.worker_id if journal is not None else "local"
  capture_dir = os.path.join(base, f"{worker}-{request_id}")
  try:
    jax.profiler.start_trace(capture_dir)
  except Exception:
    metrics_mod.incr("device.profile.start_failed")
    return
  try:
    time.sleep(max(float(duration_sec), 0.0))
  finally:
    try:
      jax.profiler.stop_trace()
    except Exception:
      metrics_mod.incr("device.profile.stop_failed")
      return
  metrics_mod.incr("device.profile.captures")
  trace.event("device.profile", request_id=request_id, dir=capture_dir,
              duration_sec=float(duration_sec))
  if journal is not None:
    uploaded = _upload_artifacts(journal.cloudpath, capture_dir,
                                 f"{PROFILE_ARTIFACT_PREFIX}{worker}-{request_id}/")
    journal.write_records([{
      "kind": "span", "name": "device.profile", "ts": time.time(),
      "dur": float(duration_sec), "trace": trace.worker_trace_id(),
      "span": trace.new_id(), "parent": None,
      "request_id": request_id, "artifacts": uploaded,
    }], event="profile")


def _upload_artifacts(journal_path: str, local_dir: str,
                      prefix: str) -> int:
  """Copy the profiler's local artifact tree under
  ``<journal>/profiles/`` via CloudFiles; returns files uploaded."""
  from ..storage import CloudFiles

  cf = CloudFiles(journal_path)
  n = 0
  for root, _dirs, files in os.walk(local_dir):
    for fname in files:
      full = os.path.join(root, fname)
      rel = os.path.relpath(full, local_dir)
      try:
        with open(full, "rb") as f:
          cf.put(prefix + rel.replace(os.sep, "/"), f.read(), compress=None)
        n += 1
      except Exception:
        metrics.incr("device.profile.upload_failed")
  return n


def list_profiles(journal_path: str) -> List[str]:
  from ..storage import CloudFiles

  try:
    return sorted(CloudFiles(journal_path).list(PROFILE_ARTIFACT_PREFIX))
  except Exception:
    return []


_SAMPLE_COUNT = [0]


def maybe_sample_profile() -> None:
  """Sampled capture: with ``IGNEOUS_PROFILE_DIR`` set and
  ``IGNEOUS_PROFILE_EVERY=N`` (N>0), every Nth device dispatch starts a
  short capture. Inert by default — two env reads per dispatch, nothing
  else."""
  if not knobs.get_str(PROFILE_DIR_ENV):
    return
  every = knobs.get_int(PROFILE_EVERY_ENV)
  if every <= 0:
    return
  _SAMPLE_COUNT[0] += 1
  if _SAMPLE_COUNT[0] % every:
    return
  start_capture(
    duration_sec=knobs.get_float("IGNEOUS_PROFILE_SEC"),
    request_id=f"sample-{_SAMPLE_COUNT[0]}",
  )


# ---------------------------------------------------------------------------
# fleet read side — merged per-device table


def device_ledgers(records) -> Dict[str, dict]:
  """Latest cumulative device record per worker from merged journal
  records (raw segments or rollups — both carry them verbatim)."""
  out: Dict[str, dict] = {}
  for rec in records:
    if rec.get("kind") != "device":
      continue
    worker = rec.get("worker", "local")
    prev = out.get(worker)
    if prev is None or rec.get("ts", 0) >= prev.get("ts", 0):
      out[worker] = rec
  return out


def _fmt_bytes(n) -> str:
  if n is None:
    return "-"
  n = float(n)
  for unit in ("B", "KB", "MB", "GB", "TB"):
    if abs(n) < 1024 or unit == "TB":
      return f"{n:.0f}{unit}" if unit == "B" else f"{n:.1f}{unit}"
    n /= 1024
  return f"{n:.1f}TB"


def render_devices(ledgers: Dict[str, dict]) -> List[str]:
  """The ``igneous fleet devices`` table: one row per worker x device
  with busy ratio + HBM, then per-kernel vox/s rows."""
  if not ledgers:
    return ["no device records in the journal (no worker dispatched "
            "device work, or the device plane is disabled)"]
  lines = [
    f"{'worker':<28}{'device':<14}{'busy_s':>9}{'busy%':>7}"
    f"{'disp':>6}{'recomp':>7}{'hbm_peak':>10}"
  ]
  for worker in sorted(ledgers):
    rec = ledgers[worker]
    devices = rec.get("devices") or {}
    hbm = rec.get("hbm") or {}
    ratio = rec.get("busy_ratio")
    for i, (dev, busy) in enumerate(sorted(devices.items())):
      peak = (hbm.get(dev) or {}).get("peak_bytes_in_use")
      pct = (
        f"{ratio * 100:.1f}%" if ratio is not None and i == 0 else ""
      )
      lines.append(
        f"{worker if i == 0 else '':<28}{dev:<14}{busy:>9.2f}"
        f"{pct:>7}"
        f"{rec.get('dispatches', 0) if i == 0 else '':>6}"
        f"{rec.get('recompiles', 0) if i == 0 else '':>7}"
        f"{_fmt_bytes(peak):>10}"
      )
    if not devices:
      lines.append(f"{worker:<28}{'-':<14}{0.0:>9.2f}{'':>7}"
                   f"{rec.get('dispatches', 0):>6}"
                   f"{rec.get('recompiles', 0):>7}{'-':>10}")
  lines.append("")
  lines.append(f"{'worker':<28}{'kernel':<22}{'execs':>6}{'exec_s':>9}"
               f"{'vox/s':>14}{'compiles':>9}")
  for worker in sorted(ledgers):
    for i, (kname, k) in enumerate(
      sorted((ledgers[worker].get("kernels") or {}).items())
    ):
      vox = k.get("vox_per_sec")
      lines.append(
        f"{worker if i == 0 else '':<28}{kname:<22}{k.get('executes', 0):>6}"
        f"{k.get('execute_s', 0.0):>9.3f}"
        f"{(f'{vox:,.0f}' if vox else '-'):>14}{k.get('compiles', 0):>9}"
      )
  fp = {"batched": 0, "host": 0}
  for rec in ledgers.values():
    for key in fp:
      fp[key] += int((rec.get("fastpath") or {}).get(key, 0))
  total = fp["batched"] + fp["host"]
  if total:
    lines.append("")
    lines.append(
      f"fast path: {fp['batched']}/{total} deliveries batched "
      f"({fp['batched'] / total:.1%}), {fp['host']} fell to host"
    )
  pad = sum(int(rec.get("pad_bytes") or 0) for rec in ledgers.values())
  real = sum(int(rec.get("real_bytes") or 0) for rec in ledgers.values())
  if real:
    lines.append(
      f"pad waste: {_fmt_bytes(pad)} padding over {_fmt_bytes(real)} real "
      f"bytes ({pad / real:.1%})"
    )
  return lines


def fleet_summary(ledgers: Dict[str, dict]) -> Optional[dict]:
  """Compact cross-worker rollup for the health report / watch
  dashboard: fleet busy ratio (busiest device per worker, averaged),
  total recompiles/dispatches, worst HBM fraction."""
  if not ledgers:
    return None
  ratios = [
    r["busy_ratio"] for r in ledgers.values()
    if r.get("busy_ratio") is not None
  ]
  hbm_frac = None
  for rec in ledgers.values():
    for dev_stats in (rec.get("hbm") or {}).values():
      limit = dev_stats.get("bytes_limit")
      if limit:
        frac = dev_stats.get("peak_bytes_in_use", 0) / limit
        hbm_frac = frac if hbm_frac is None else max(hbm_frac, frac)
  fp = {"batched": 0, "host": 0}
  for rec in ledgers.values():
    for key in fp:
      fp[key] += int((rec.get("fastpath") or {}).get(key, 0))
  pad = sum(int(rec.get("pad_bytes") or 0) for rec in ledgers.values())
  real = sum(int(rec.get("real_bytes") or 0) for rec in ledgers.values())
  return {
    "workers": len(ledgers),
    "pad_waste_ratio": round(pad / real, 4) if real else None,
    "busy_ratio": (
      round(sum(ratios) / len(ratios), 4) if ratios else None
    ),
    "dispatches": sum(r.get("dispatches", 0) for r in ledgers.values()),
    "recompiles": sum(r.get("recompiles", 0) for r in ledgers.values()),
    "hbm_peak_frac": round(hbm_frac, 4) if hbm_frac is not None else None,
    "fastpath": fp,
  }


def report_json(ledgers: Dict[str, dict]) -> str:
  return json.dumps(
    {"summary": fleet_summary(ledgers), "workers": ledgers},
    indent=2,
  )
