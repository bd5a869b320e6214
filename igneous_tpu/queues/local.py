"""In-process / multi-process task execution.

LocalTaskQueue semantics mirror the reference's
``LocalTaskQueue(parallel=N)`` (/root/reference/README.md:69-81): inserting
tasks executes them immediately, optionally across N spawned worker
processes. Spawn (not fork) is used for the same reason the reference CLI
does (/root/reference/igneous_cli/cli.py:920-922): forking a process with
live thread pools / device handles deadlocks; with JAX in the picture fork
is outright unsafe.
"""

from __future__ import annotations

import functools
import multiprocessing as mp
import os
from typing import Iterable, Optional

from tqdm import tqdm

from .registry import deserialize, serialize


def _execute_payload(payload: str):
  # runs in a spawned worker: re-import the task universe first
  import igneous_tpu.tasks  # noqa: F401  (registers all task classes)

  from ..observability import trace

  task = deserialize(payload)
  with trace.task_span(task, queue="LocalTaskQueue"):
    task.execute()
  return True


def _execute_payload_contained(payload: str, max_deliveries: int):
  """Spawned-worker body with the containment contract: retry up to
  ``max_deliveries`` attempts, then report the failure instead of
  killing the whole pool. Returns (payload, error_or_None)."""
  from .filequeue import failure_reason

  last = None
  for _ in range(max(int(max_deliveries), 1)):
    try:
      _execute_payload(payload)
      return payload, None
    except Exception as e:  # noqa: BLE001 - recorded as a dead letter
      last = failure_reason(e)
  return payload, last


def _worker_init(pool_threads: int):
  """Spawned-worker setup: N process-parallel workers each get 1/N of the
  cores for their native kernel threading (same oversubscription hygiene as
  the reference's cv2.setNumThreads(0),
  /root/reference/igneous/tasks/image/image.py:177-180)."""
  os.environ.setdefault("IGNEOUS_POOL_THREADS", str(pool_threads))


def _in_worker(fn, payload):
  """Pool-worker call: a worker that landed on the CPU backend fails
  here, and the failure reaches the parent with the result."""
  from ..observability.device import require_worker_backend

  require_worker_backend()
  return fn(payload)


class LocalTaskQueue:
  """Executes tasks on insert; parallel > 1 uses a spawn process pool.

  ``max_deliveries`` opts into the same failure containment the lease
  queues have: each task gets that many attempts, and tasks that still
  fail are collected in ``self.dead_letters`` (payload + failure reason)
  instead of aborting the whole insert. The default (None) keeps the
  historical fail-fast behavior — the first exception propagates."""

  def __init__(self, parallel: int = 1, progress: bool = True,
               max_deliveries: Optional[int] = None, drain_flag=None):
    """``drain_flag`` (anything with ``is_set()``): graceful preemption —
    the in-flight task finishes, remaining tasks are left unexecuted
    (mirrors the lease queues' drain contract for local runs)."""
    self.parallel = max(int(parallel), 1)
    self.progress = progress
    self.inserted = 0
    self.completed = 0
    self.max_deliveries = (
      None if not max_deliveries or int(max_deliveries) <= 0
      else int(max_deliveries)
    )
    self.dead_letters: list = []
    self.drain_flag = drain_flag
    self.drained = False

  def _draining(self) -> bool:
    if self.drain_flag is not None and self.drain_flag.is_set():
      self.drained = True
    return self.drained

  @property
  def backlog(self) -> int:
    """Tasks inserted but not completed or dead-lettered (insert()
    executes inline, so this is nonzero only mid-insert — kept for
    backend-uniform health plumbing, ISSUE 6)."""
    return max(self.inserted - self.completed - len(self.dead_letters), 0)

  def depth_snapshot(self) -> dict:
    return {
      "inserted": self.inserted,
      "enqueued": self.backlog,
      "leased": 0,
      "completed": self.completed,
      "backlog": self.backlog,
      "dlq": len(self.dead_letters),
    }

  def renew(self, lease_id, seconds: float = 600):
    """No-op: local tasks execute in-process with no visibility timeout;
    exists so the shared heartbeat/lifecycle plumbing is backend-uniform."""
    return lease_id

  def _record_dead_letter(self, payload: str, error: str):
    from .. import telemetry

    self.dead_letters.append({"payload": payload, "error": error})
    telemetry.incr("dlq.promoted")

  def insert(self, tasks: Iterable, total: Optional[int] = None):
    if self.parallel == 1:
      from ..pipeline import config as pipeline_config

      # a task STREAM on one process is exactly what the staged pipeline
      # exists for: download(i+1) overlaps compute(i) overlaps
      # encode/upload(i-1), byte-identical to this serial loop
      # (IGNEOUS_PIPELINE=off restores strict serial execution)
      if pipeline_config.enabled(default=True):
        return self._insert_pipelined(tasks, total)
    payloads = (serialize(t) for t in self._iter(tasks))
    bar = tqdm(
      total=total, desc="Tasks", disable=(not self.progress), unit="task"
    )
    if self.parallel == 1:
      for payload in payloads:
        if self._draining():
          break
        self.inserted += 1
        if self.max_deliveries is None:
          _execute_payload(payload)
        else:
          _p, err = _execute_payload_contained(payload, self.max_deliveries)
          if err is not None:
            self._record_dead_letter(payload, err)
            bar.update(1)
            continue
        self.completed += 1
        bar.update(1)
    else:
      ctx = mp.get_context("spawn")
      threads = max(1, (os.cpu_count() or 1) // self.parallel)
      with ctx.Pool(
        self.parallel, initializer=_worker_init, initargs=(threads,)
      ) as pool:
        if self.max_deliveries is None:
          for _ in pool.imap_unordered(
            functools.partial(_in_worker, _execute_payload), payloads,
            chunksize=1,
          ):
            self.inserted += 1
            self.completed += 1
            bar.update(1)
            if self._draining():
              break  # pool __exit__ terminates; unconsumed payloads stay
        else:
          runner = functools.partial(
            _execute_payload_contained, max_deliveries=self.max_deliveries
          )
          for payload, err in pool.imap_unordered(
            functools.partial(_in_worker, runner), payloads, chunksize=1
          ):
            self.inserted += 1
            if err is not None:
              self._record_dead_letter(payload, err)
            else:
              self.completed += 1
            bar.update(1)
            if self._draining():
              break
    bar.close()

  def _insert_pipelined(self, tasks: Iterable, total: Optional[int] = None):
    """parallel=1 insert through the staged pipeline (ISSUE 3).

    Semantics preserved from the serial loop: tasks round-trip through
    serialize/deserialize, ``inserted``/``completed`` tally the same
    way, drain stops admission and finishes in-flight work, fail-fast
    raises the first failure (after in-flight uploads join — a task is
    never abandoned mid-write), and ``max_deliveries`` retries failures
    solo before dead-lettering them."""
    from ..pipeline import run_tasks_pipelined
    from .filequeue import failure_reason

    bar = tqdm(
      total=total, desc="Tasks", disable=(not self.progress), unit="task"
    )

    def stream():
      for t in self._iter(tasks):
        payload = serialize(t)
        self.inserted += 1
        yield deserialize(payload)

    def on_complete(task):
      self.completed += 1
      bar.update(1)

    on_error = None
    if self.max_deliveries is not None:
      def on_error(task, exc):
        payload = serialize(task)
        if self.max_deliveries <= 1:
          self._record_dead_letter(payload, failure_reason(exc))
          bar.update(1)
          return
        # the pipelined attempt spent one delivery; the rest run solo
        _p, err = _execute_payload_contained(payload, self.max_deliveries - 1)
        if err is not None:
          self._record_dead_letter(payload, err)
        else:
          self.completed += 1
        bar.update(1)

    try:
      stats = run_tasks_pipelined(
        stream(),
        drain_flag=self.drain_flag,
        on_error=on_error,
        on_complete=on_complete,
      )
      if stats["drained"]:
        self.drained = True
    finally:
      bar.close()

  insert_all = insert
  # batched wire protocol (ISSUE 15): local execution has no wire, so the
  # batch entry point IS the streaming insert
  insert_batch = insert

  @staticmethod
  def _iter(tasks):
    if hasattr(tasks, "__iter__") and not isinstance(tasks, (str, bytes, dict)):
      return iter(tasks)
    return iter([tasks])

  def wait(self, *args, **kw):
    return self

  def __enter__(self):
    return self

  def __exit__(self, *exc):
    return False


class MockTaskQueue:
  """Serial immediate execution without serialization (debugging)."""

  def __init__(self, *args, **kw):
    pass

  def insert(self, tasks, *args, **kw):
    for task in LocalTaskQueue._iter(tasks):
      task = deserialize(serialize(task))
      task.execute()

  insert_all = insert
  insert_batch = insert

  def wait(self, *args, **kw):
    return self
