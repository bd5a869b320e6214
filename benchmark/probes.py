"""The harness's own timing and trace spans around calls into the program.

``QueueProbe`` is the queue handed to the worker loop: the program's
queue, with the window's lease deadline, per-call timing, the credit of
every acknowledged task, and a hook run after every lease and every
acknowledgement. ``CallProbes`` wraps a few program entry
points for the traced run only: each wrapped call runs inside a
``jax.profiler.TraceAnnotation`` named ``bench.<what>`` (what the
trace reduction attributes idle gaps to), and source downloads are
timed. The end-to-end run installs no ``CallProbes``.
"""

from __future__ import annotations

import contextlib
import threading
import time


def ledger_totals() -> dict:
  """Compiles, compile seconds, executes per kernel and host-to-device
  bytes from the program's device ledger, since the process started."""
  from igneous_tpu.observability import device

  led = device.LEDGER
  with led.lock:
    return {
      "compiles": sum(k["compiles"] for k in led.kernels.values()),
      "compile_s": sum(k["compile_s"] for k in led.kernels.values()),
      "executes": {n: k["executes"] for n, k in led.kernels.items()},
      "h2d_bytes": led.h2d_bytes,
    }


def timer_seconds(name: str) -> float:
  from igneous_tpu.observability import metrics

  return float(metrics.timer_totals().get(name, {}).get("sum", 0.0))


def counter(name: str) -> int:
  from igneous_tpu.observability import metrics

  return int(metrics.counters_snapshot().get(name, 0))


def annotation(name: str, on: bool):
  if not on:
    return contextlib.nullcontext()
  import jax

  return jax.profiler.TraceAnnotation(f"bench.{name}")


class QueueProbe:
  def __init__(self, inner, task_voxels, deadline=None, on_change=None,
               annotate=False):
    self._inner = inner
    self._task_voxels = task_voxels
    self.deadline = deadline
    self._on_change = on_change
    self._annotate = annotate
    self._lock = threading.Lock()
    self._held = {}  # id(token) -> (token, task)
    self.seconds = 0.0
    self.leased = 0
    self.acked = 0
    self.acked_voxels = 0
    self.acked_tasks = []

  def _timed(self, fn, *args, **kw):
    t0 = time.perf_counter()
    try:
      with annotation("queue", self._annotate):
        return fn(*args, **kw)
    finally:
      dt = time.perf_counter() - t0
      with self._lock:
        self.seconds += dt

  def lease_batch(self, seconds: float = 600, max_tasks: int = 1):
    if self.deadline is not None and time.perf_counter() >= self.deadline:
      return []
    got = self._timed(self._inner.lease_batch, seconds, max_tasks=max_tasks)
    with self._lock:
      self.leased += len(got)
      for task, token in got:
        self._held[id(token)] = (token, task)
    if got and self._on_change is not None:
      self._on_change()
    return got

  def delete(self, lease_id):
    done = self._timed(self._inner.delete, lease_id)
    if done is not False:
      with self._lock:
        _token, task = self._held.pop(id(lease_id), (None, None))
        if task is not None:
          self.acked += 1
          self.acked_voxels += self._task_voxels(task)
          self.acked_tasks.append(task)
      if self._on_change is not None and task is not None:
        self._on_change()
    return done

  def renew(self, *args, **kw):
    return self._timed(self._inner.renew, *args, **kw)

  def nack(self, *args, **kw):
    return self._timed(self._inner.nack, *args, **kw)

  def release(self, *args, **kw):
    return self._timed(self._inner.release, *args, **kw)

  def __getattr__(self, name):
    return getattr(self._inner, name)


class CallProbes:
  """Wraps ``Volume.download`` (timed for ``src_path``), the pyramid
  executor's dispatch and the encode pool's join while installed."""

  def __init__(self, src_path: str):
    self.src_path = src_path.rstrip("/")
    self._lock = threading.Lock()
    self.download_s = 0.0
    self.download_voxels = 0
    self._saved = []

  def _patch(self, owner, attr, make):
    orig = getattr(owner, attr)
    self._saved.append((owner, attr, orig))
    setattr(owner, attr, make(orig))

  def install(self) -> "CallProbes":
    from igneous_tpu.parallel.executor import ChunkExecutor
    from igneous_tpu.pipeline.encoder import UploadTicket
    from igneous_tpu.volume import Volume

    probes = self

    def download(orig):
      def wrapped(vol, bbox, *args, **kw):
        if vol.cloudpath.rstrip("/") != probes.src_path:
          return orig(vol, bbox, *args, **kw)
        t0 = time.perf_counter()
        with annotation("download", True):
          out = orig(vol, bbox, *args, **kw)
        dt = time.perf_counter() - t0
        with probes._lock:
          probes.download_s += dt
          probes.download_voxels += int(
            out.shape[0] * out.shape[1] * out.shape[2]
          )
        return out
      return wrapped

    def spanned(name):
      def make(orig):
        def wrapped(*args, **kw):
          with annotation(name, True):
            return orig(*args, **kw)
        return wrapped
      return make

    self._patch(Volume, "download", download)
    self._patch(ChunkExecutor, "__call__", spanned("dispatch"))
    self._patch(UploadTicket, "join", spanned("encode_join"))
    return self

  def uninstall(self) -> None:
    while self._saved:
      owner, attr, orig = self._saved.pop()
      setattr(owner, attr, orig)
