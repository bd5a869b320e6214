"""Parallel chunk encode/upload with deterministic bytes.

The e2e profile shows chunk encode+compress
+put as a serial tail on every task: each produced mip's chunks were
encoded and written one after another on the compute thread. This module
moves that tail onto a persistent thread pool.

Determinism: each chunk is encoded and compressed INDEPENDENTLY (codecs
encode + gzip mtime=0), so the byte content of every stored object is a
pure function of its voxels — thread scheduling can only reorder WHICH
object lands first, never what lands. The chaos soak's byte-identity
contract therefore survives any pool width, which is the property the
containment tests pin.

Completion safety: work is grouped under *tickets*. A task joins its
ticket before reporting success — a lease is never deleted (nor a
LocalTaskQueue task counted complete) while one of its chunks is still
in flight, and a failed put re-raises at the join, landing in the same
retry/nack path a synchronous upload failure would. Puts themselves are
atomic at the backend (tmp+rename / single dict store), so a fault or
preemption mid-pipeline leaves either the complete object or nothing —
no partial uploads, no orphaned tmp files beyond what the backend
already cleans.
"""

from __future__ import annotations

import concurrent.futures as cf
import threading
import time
from typing import Callable, List, Optional

from .. import telemetry
from ..observability import trace
from . import config


class UploadTicket:
  """Tracks the in-flight uploads of ONE task (or lease-batch member)."""

  def __init__(self, pool: "EncodePool"):
    self._pool = pool
    self._lock = threading.Lock()
    self._futures: List[cf.Future] = []  # guarded-by: self._lock

  def submit(self, fn: Callable[[], None]) -> None:
    # every chunk's encode+put is timed, sampled or not; a sampled trace
    # context is carried onto the pool thread, so the work (and its
    # storage spans) stays attributed to the task that produced it
    ctx = trace.current()
    if ctx is not None and not ctx.sampled:
      ctx = None

    def timed():
      with trace.activate(ctx):
        t0 = time.perf_counter()
        try:
          fn()
        finally:
          telemetry.observe(
            "pipeline.encode_upload.s", time.perf_counter() - t0
          )

    fut = self._pool._submit(timed)
    with self._lock:
      self._futures.append(fut)

  def join(self) -> None:
    """Wait for every upload in this ticket; re-raise the FIRST failure
    (after letting the rest finish, so no thread still writes while the
    caller unwinds — a retried task would race its own previous self)."""
    with self._lock:
      futures, self._futures = self._futures, []
    first_error = None
    with trace.span("pipeline.encode.join", chunks=len(futures)):
      for fut in futures:
        try:
          fut.result()
        except BaseException as e:  # noqa: BLE001 - re-raised below
          if first_error is None:
            first_error = e
    if first_error is not None:
      raise first_error

  def pending(self) -> int:
    with self._lock:
      return sum(1 for f in self._futures if not f.done())


class EncodePool:
  """Persistent encode/upload worker pool.

  One pool per process (``shared_encode_pool``): thread churn is exactly
  the overhead the pipeline exists to remove, and deflate/puts from
  different tasks coexist safely because objects are independent.
  Its threads run side by side only while the codec lets go of the GIL,
  as zlib's deflate and OpenCV's jpeg encode do.
  """

  def __init__(self, threads: Optional[int] = None):
    self.threads = threads or config.encode_threads()
    self._ex = cf.ThreadPoolExecutor(
      max_workers=self.threads, thread_name_prefix="ig-pipeline-encode"
    )

  def _submit(self, fn) -> cf.Future:
    telemetry.incr("pipeline.upload.submitted")
    return self._ex.submit(fn)

  def ticket(self) -> UploadTicket:
    return UploadTicket(self)

  def shutdown(self) -> None:
    self._ex.shutdown(wait=True)


class SerialSink:
  """The sink a synchronous caller gets: submit == run now. Keeps the
  upload code path IDENTICAL between pipelined and serial execution —
  one implementation, one set of bytes."""

  def submit(self, fn: Callable[[], None]) -> None:
    fn()

  def join(self) -> None:
    pass


_SHARED: Optional[EncodePool] = None
_SHARED_LOCK = threading.Lock()


def shared_encode_pool() -> EncodePool:
  global _SHARED
  with _SHARED_LOCK:
    if _SHARED is None:
      _SHARED = EncodePool()
    return _SHARED


_SHARED_IO: Optional[cf.ThreadPoolExecutor] = None
_SHARED_PREFETCH: Optional[cf.ThreadPoolExecutor] = None
_SHARED_COPY: Optional[cf.ThreadPoolExecutor] = None


def shared_io_pool() -> cf.ThreadPoolExecutor:
  """Persistent fine-grained chunk get/put pool. Replaces the per-call
  ThreadPoolExecutor spawning that showed up as pure thread-start
  overhead in the e2e profile."""
  global _SHARED_IO
  with _SHARED_LOCK:
    if _SHARED_IO is None:
      _SHARED_IO = cf.ThreadPoolExecutor(
        max_workers=config.io_threads(), thread_name_prefix="ig-pipeline-io"
      )
    return _SHARED_IO


def shared_prefetch_pool() -> cf.ThreadPoolExecutor:
  """Task-level download closures (whole cutouts). DISTINCT from
  shared_io_pool on purpose: a cutout download fans its chunk gets out
  to the io pool, so running both tiers on one pool can fill every
  worker with outer downloads waiting on their own sub-gets — a classic
  same-pool deadlock."""
  global _SHARED_PREFETCH
  with _SHARED_LOCK:
    if _SHARED_PREFETCH is None:
      _SHARED_PREFETCH = cf.ThreadPoolExecutor(
        max_workers=max(config.io_threads(), config.prefetch_depth()),
        thread_name_prefix="ig-pipeline-prefetch",
      )
    return _SHARED_PREFETCH


def shared_copy_pool() -> cf.ThreadPoolExecutor:
  """Persistent pool for host memory copies cut into pieces (the batched
  dispatch's staging, ``ops.pooling._copy_by_planes``), one thread a
  core: numpy lets go of the GIL while it copies. DISTINCT from
  shared_io_pool, where the next round's prefetch decodes while this
  round's batch is copied, so the copy never queues behind it; and from
  shared_prefetch_pool for the same-pool deadlock that pool's docstring
  gives."""
  global _SHARED_COPY
  with _SHARED_LOCK:
    if _SHARED_COPY is None:
      _SHARED_COPY = cf.ThreadPoolExecutor(
        max_workers=config._cores(), thread_name_prefix="ig-pipeline-copy"
      )
    return _SHARED_COPY
