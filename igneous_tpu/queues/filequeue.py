"""Lease-based filesystem task queue (``fq://``).

Behavioral parity with the reference's FileQueue (python-task-queue,
described at /root/reference/README.md:69-81): at-least-once delivery with a
visibility timeout — a leased task that is not deleted within its lease
returns to the pool; workers pick a random task among the first 100 to
avoid lease contention; completions are tallied 1 byte per task.

All state is plain files, so any shared POSIX filesystem (NFS, /mnt
volumes) works as the control plane across machines.

Failure containment (ISSUE 1): each task carries persisted attempt
metadata (``meta/<name>``: delivery count + recent failure reasons).
With ``max_deliveries`` configured, a task that keeps failing — by
raising, overrunning its deadline, or losing its worker — moves to the
``dlq/`` sidecar instead of re-entering rotation, where ``igneous queue
dlq ls|retry|purge`` can inspect, requeue, or drop it. The default
(``max_deliveries=None``) preserves the historical infinite-retry
at-least-once semantics.

Queue scale-out (ISSUE 15): the classic layout is one file + meta per
task, which goes quadratic-ish on listings at the tens-of-millions-of-
tasks campaigns the paper's grid sizes imply. ``insert_batch`` instead
writes **sharded metadata segments** — ``seg_<segid>_<count>.jsonl``
files holding up to ``IGNEOUS_QUEUE_SEG_TASKS`` tasks each (one line
``<index>\\t<payload>`` per task), sized so a batch lands in about
``IGNEOUS_QUEUE_SHARDS`` appends — and ``lease_batch`` leases a whole
segment as ONE :class:`~.ranges.RangeLease`. Depth reads stay
O(segments): task counts ride in the file names, completion tallies stay
1-byte-per-task counter files, and delivery counts key on the segment id
(stable across ack rewrites and splits). Per-task semantics survive
through sub-task accounting — see :mod:`.ranges`. Classic per-task files
and segments coexist freely in one queue directory, so pre-ISSUE-15
layouts keep reading.

Campaign survival (ISSUE 17): two sidecar protocols keep a hostile
fleet's tail from holding a campaign hostage, both dormant (zero reads,
zero writes) until first use — queues that never speculate or steal
read byte-for-byte unchanged.

* **Straggler speculation** (``spec/`` sidecar): :meth:`speculate_lease`
  double-issues the unfinished tail of a held range lease as a twin
  segment. First RESOLUTION wins: completing an index creates a
  per-index ``O_EXCL`` marker, and only the marker creator tallies the
  completion — the loser's late ack shrinks its lease *without*
  tallying, so completions never double-count. Exactly one of
  ``speculation.won`` (twin resolved first) / ``speculation.fenced``
  (original resolved first) increments per issued index, making
  ``won + fenced == issued`` an end-of-campaign invariant.
* **Work stealing** (``steal/`` sidecar): an idle worker claims a
  long-held range with :meth:`steal_claim` (``O_EXCL`` claim file =
  deterministic winner among racing thieves); the holder's next
  heartbeat renewal services the claim by releasing the unstarted tail
  of its range back to the pool through the expiry-fenced range-release
  seam, then removes the claim.

While a speculation pair is live, ``enqueued``/``backlog`` transiently
count both copies and ``fsck`` counter drift dips negative by the
twinned index count; both read exact again once the pair resolves.
"""

from __future__ import annotations

import json
import os
import random
import re
import time
import uuid
from typing import Dict, Iterable, List, Optional, Tuple

from ..observability import trace
from .ranges import RangeLease, RangeSub
from .registry import RegisteredTask, deserialize, serialize

LEASE_SEP = "--"
CONTENTION_WINDOW = 100
MAX_RECORDED_FAILURES = 5  # per-task failure-reason ring (meta file bound)

SEG_PREFIX = "seg_"
SEG_SUFFIX = ".jsonl"
# defaults mirrored by the knobs registry (analysis/knobs.py)
DEFAULT_QUEUE_SHARDS = 16
DEFAULT_SEG_TASKS = 1024
DEFAULT_RECYCLE_SEC = 5.0
DEFAULT_SPECULATE_MIN_TASKS = 1
DEFAULT_SPECULATE_MAX_TWINS = 4
DEFAULT_SPECULATE_MIN_HELD_SEC = 0.0
DEFAULT_STEAL_MIN_TASKS = 2
DEFAULT_STEAL_MIN_HELD_SEC = 2.0
DEFAULT_STEAL_FRACTION = 0.5
DEFAULT_STEAL_CLAIM_TTL_SEC = 300.0

# mid-range failures / DLQ expansions carve per-index classic files
_CARVE_RE = re.compile(r"^task_([0-9a-f]+)_(\d+)\.json$")


def seg_parse(name: str) -> Optional[Tuple[str, int]]:
  """``seg_<segid>_<count>.jsonl`` → (segid, count); None for classic
  per-task file names. The count in the NAME is the task count in the
  file (maintained across ack rewrites), so depth reads never open
  segment files."""
  if not name.startswith(SEG_PREFIX) or not name.endswith(SEG_SUFFIX):
    return None
  parts = name[len(SEG_PREFIX):-len(SEG_SUFFIX)].rsplit("_", 1)
  if len(parts) != 2:
    return None
  try:
    return parts[0], int(parts[1])
  except ValueError:
    return None


def seg_name(segid: str, count: int) -> str:
  return f"{SEG_PREFIX}{segid}_{int(count)}{SEG_SUFFIX}"


def _name_tasks(name: str) -> int:
  """Tasks a queue/lease file name represents (lease prefixes allowed)."""
  parsed = seg_parse(name.split(LEASE_SEP, 1)[-1])
  return parsed[1] if parsed else 1


def _seg_content(entries) -> str:
  return "".join(f"{int(i)}\t{p}\n" for i, p in entries)


class TaskDeadlineError(Exception):
  """A task overran its per-delivery wall-clock deadline (poll_loop)."""


class StaleLeaseError(Exception):
  """The lease behind a renew/delete no longer belongs to this worker —
  it expired, or the queue re-issued the task to someone else. A worker
  seeing this is a *zombie* for that task: it must stop acting on it
  (the work itself is safe to discard — tasks are idempotent and the
  current owner will complete it)."""


def iter_tasks(tasks):
  """Normalize an insert() argument to an iterator of single tasks.
  Strings/bytes/dicts are single payloads, not collections — shared by
  every queue backend so a payload-dict never gets iterated as keys."""
  if hasattr(tasks, "__iter__") and not isinstance(tasks, (str, bytes, dict)):
    return iter(tasks)
  return iter([tasks])


def failure_reason(exc: BaseException) -> str:
  """One-line failure record shared by every containment path (poll_loop,
  the lease batcher, LocalTaskQueue) so DLQ entries read uniformly."""
  msg = str(exc)
  return f"{type(exc).__name__}: {msg}" if msg else type(exc).__name__


def run_with_deadline(fn, deadline_seconds: Optional[float]):
  """Run ``fn()`` with a wall-clock deadline. On overrun, raises
  TaskDeadlineError so the caller's failure bookkeeping (nack → DLQ)
  takes over. The overrunning call keeps executing on an abandoned
  daemon thread — it cannot be killed safely — which is sound here
  because tasks are idempotent and the lease it held stays failed."""
  if not deadline_seconds or deadline_seconds <= 0:
    return fn()
  import threading

  result = {}

  def body():
    try:
      result["value"] = fn()
    except BaseException as e:  # noqa: BLE001 - relayed to the caller
      result["error"] = e

  t = threading.Thread(target=body, daemon=True)
  t.start()
  t.join(deadline_seconds)
  if t.is_alive():
    raise TaskDeadlineError(
      f"task exceeded its {deadline_seconds:.1f}s deadline"
    )
  if "error" in result:
    raise result["error"]
  return result.get("value")


def poll_loop(
  queue,
  lease_seconds: float = 600,
  verbose: bool = False,
  stop_fn=None,
  max_backoff_window: float = 30.0,
  before_fn=None,
  after_fn=None,
  task_deadline_seconds: Optional[float] = None,
  heartbeat_seconds: Optional[float] = None,
  drain_flag=None,
):
  """Shared worker loop: lease→execute→delete until stop_fn says stop or
  the queue drains (stop_fn=None polls forever, sleeping with bounded
  backoff when empty). Used by every queue backend (fq://, sqs://) so
  execution semantics — at-least-once, recycle-on-failure — are uniform.

  Failure containment: an exception (or ``task_deadline_seconds``
  overrun) records its reason with the task via ``queue.nack`` when the
  backend supports it — feeding the same bookkeeping that promotes
  repeat offenders to the DLQ — and otherwise leaves the lease to
  recycle on its visibility timeout, exactly as before.

  Lifecycle (ISSUE 2): a heartbeat thread renews the held lease every
  ``heartbeat_seconds`` (default lease/3, env IGNEOUS_HEARTBEAT_SEC;
  <= 0 disables) so long tasks outlive a short ``--lease-sec`` without
  being double-executed. ``drain_flag`` (anything with ``is_set()``,
  e.g. lifecycle.StopFlag) requests graceful shutdown: the in-flight
  task finishes, no new lease is taken."""
  from .. import telemetry
  from ..observability import journal as journal_mod
  from .heartbeat import LeaseHeartbeat

  def draining() -> bool:
    return drain_flag is not None and drain_flag.is_set()

  def attempt_of(lease_id) -> Optional[int]:
    # fq:// persists delivery counts; SQS reports ApproximateReceiveCount
    try:
      if hasattr(queue, "delivery_count"):
        return int(queue.delivery_count(lease_id))
      if getattr(queue, "last_receive_count", 0):
        return int(queue.last_receive_count)
    except Exception:
      pass
    return None

  def idle(seconds: float):
    # wake early when a drain request lands mid-backoff
    if drain_flag is not None and hasattr(drain_flag, "wait"):
      drain_flag.wait(seconds)
    else:
      time.sleep(seconds)

  backoff = 1.0
  executed = 0
  hb = LeaseHeartbeat(queue, lease_seconds, interval=heartbeat_seconds)
  try:
   with hb:
    while True:
      # interval/drain-requested journal flush between tasks: the poll
      # loop IS the worker's main thread, so batches land without a
      # dedicated flusher thread
      journal_mod.maybe_flush_active()
      if draining():
        return executed
      if stop_fn is not None and stop_fn(executed=executed, empty=False):
        return executed
      leased = queue.lease(lease_seconds)
      if leased is None:
        if stop_fn is not None and stop_fn(executed=executed, empty=True):
          return executed
        if draining():
          return executed
        idle(backoff + random.random())
        backoff = min(backoff * 2, max_backoff_window)
        continue
      backoff = 1.0
      task, lease_id = leased
      key = hb.track(lease_id)
      if isinstance(lease_id, RangeSub):
        # stealing only carves UNSTARTED members; this one is in flight
        lease_id.mark_started()
      if verbose:
        print(f"Executing {task!r}")
      try:
        if before_fn:
          before_fn(task)
        # IGNEOUS_PIPELINE=1 opts the solo worker loop into tier-A
        # pipelining: the task's chunk encodes+puts thread on the shared
        # pool, joined before the lease delete below — completion
        # semantics are unchanged (execute_with_sink falls back to plain
        # execute() when the task has no stage plan or pipelining is off)
        from ..pipeline import execute_with_sink

        # the task span wraps this delivery: stage/storage spans on this
        # thread (and pool threads the upload ticket propagates to)
        # parent under it, attributed to the payload's trace
        with trace.task_span(
          task, attempt=attempt_of(lease_id), queue=type(queue).__name__
        ):
          run_with_deadline(
            lambda: execute_with_sink(task), task_deadline_seconds
          )
        if after_fn:
          after_fn(task)
      except Exception as e:
        # leave the lease in place: the task recycles after the timeout
        # (at-least-once semantics; matches reference behavior on failure).
        # nack records the reason and quarantines exhausted tasks.
        if verbose:
          import traceback

          traceback.print_exc()
        telemetry.incr("tasks.failed")
        current = hb.untrack(key)
        if hasattr(queue, "nack"):
          queue.nack(current, failure_reason(e))
        continue
      # untrack returns the CURRENT lease token (heartbeat renewals
      # re-timestamp fq:// lease names); delete is fenced against stale
      # tokens, so a zombie's late ack can never complete a re-issued task
      queue.delete(hb.untrack(key))
      executed += 1
  finally:
    # whatever ends the loop — drain, stop_fn, an unhandled error — the
    # pending span batch must not die with the worker
    journal_mod.flush_active(
      event="drain" if draining() else "poll_exit"
    )


class FileQueue:
  def __init__(self, path: str, max_deliveries: Optional[int] = None,
               worker_id: Optional[str] = None):
    """``max_deliveries``: after this many deliveries (leases), a task
    that fails again is quarantined in ``dlq/`` instead of recycling.
    None (default) keeps the historical infinite-retry behavior.

    ``worker_id`` names this consumer in segment lease metadata (the
    ``holder`` field speculation/steal planners target). Defaults to the
    journal's worker id so HealthEngine flags — which name journal
    workers — map straight onto lease holders; pass the same id given to
    :class:`~..observability.journal.Journal` when overriding one."""
    if path.startswith("fq://"):
      path = path[len("fq://"):]
    self.path = os.path.abspath(os.path.expanduser(path))
    self.queue_dir = os.path.join(self.path, "queue")
    self.lease_dir = os.path.join(self.path, "leased")
    self.dlq_dir = os.path.join(self.path, "dlq")
    self.meta_dir = os.path.join(self.path, "meta")
    # survival sidecars (ISSUE 17): created lazily on first use, so a
    # queue that never speculates/steals keeps its pre-ISSUE-17 layout
    self.spec_dir = os.path.join(self.path, "spec")
    self.steal_dir = os.path.join(self.path, "steal")
    self._worker_id = worker_id
    self.max_deliveries = (
      None if not max_deliveries or int(max_deliveries) <= 0
      else int(max_deliveries)
    )
    os.makedirs(self.queue_dir, exist_ok=True)
    os.makedirs(self.lease_dir, exist_ok=True)
    os.makedirs(self.dlq_dir, exist_ok=True)
    os.makedirs(self.meta_dir, exist_ok=True)
    # cached per-shard pending index (lease picks from here instead of a
    # full listdir+sort per acquisition) and the recycle-scan throttle
    self._pending_cache: Optional[List[str]] = None
    self._last_recycle = 0.0

  @property
  def worker_id(self) -> str:
    if self._worker_id is None:
      from ..observability.journal import default_worker_id

      self._worker_id = default_worker_id()
    return self._worker_id

  # -- per-task attempt metadata --------------------------------------------

  def _meta_path(self, name: str) -> str:
    return os.path.join(self.meta_dir, name)

  @staticmethod
  def _meta_key(name_or_lease: str) -> str:
    """Meta file key for a queue/lease/dlq name. Segments key on the
    SEGID (``seg_<segid>``) so ack rewrites — which change the count in
    the file name — never orphan the delivery-count record."""
    name = str(name_or_lease).split(LEASE_SEP, 1)[-1]
    parsed = seg_parse(name)
    return f"{SEG_PREFIX}{parsed[0]}" if parsed else name

  def _read_meta(self, name: str) -> dict:
    try:
      with open(self._meta_path(name)) as f:
        return json.load(f)
    except (FileNotFoundError, ValueError):
      return {"deliveries": 0, "failures": []}

  def _write_meta(self, name: str, meta: dict):
    tmp = os.path.join(self.path, f".tmp-meta-{uuid.uuid4().hex}")
    try:
      with open(tmp, "w") as f:
        json.dump(meta, f)
      os.replace(tmp, self._meta_path(name))
    except BaseException:
      # same turd-free contract as storage put(): a failed write must not
      # leave .tmp-* files accumulating next to the counters
      try:
        os.remove(tmp)
      except FileNotFoundError:
        pass
      raise

  def _drop_meta(self, name: str):
    try:
      os.remove(self._meta_path(name))
    except FileNotFoundError:
      pass

  def _record_failure(self, name: str, reason: str) -> dict:
    meta = self._read_meta(name)
    meta.setdefault("failures", []).append({
      "time": time.time(), "error": str(reason)[:2000],
    })
    meta["failures"] = meta["failures"][-MAX_RECORDED_FAILURES:]
    self._write_meta(name, meta)
    return meta

  def delivery_count(self, name_or_lease) -> int:
    """Deliveries so far for a task (by queue filename, lease id, or
    range-member handle) — the fq:// analogue of SQS's
    ApproximateReceiveCount. Range members report the shared segment's
    delivery count until a failure splits them out solo."""
    if isinstance(name_or_lease, RangeSub):
      key = f"{SEG_PREFIX}{name_or_lease.parent.segid}"
    else:
      key = self._meta_key(name_or_lease)
    return int(self._read_meta(key).get("deliveries", 0))

  def _exhausted(self, name: str) -> bool:
    return (
      self.max_deliveries is not None
      and self.delivery_count(name) >= self.max_deliveries
    )

  # -- dead-letter queue ----------------------------------------------------

  def _quarantine_to_dlq(self, src_path: str, name: str, reason: str):
    """Move a task file into dlq/ (terminal until an operator intervenes).
    The meta file stays: it holds the delivery count + failure reasons
    that `dlq ls` reports."""
    self._record_failure(name, reason)
    try:
      os.rename(src_path, os.path.join(self.dlq_dir, name))
    except FileNotFoundError:
      return  # another worker moved it first
    from .. import telemetry

    telemetry.incr("dlq.promoted")

  @property
  def dlq_count(self) -> int:
    return len(os.listdir(self.dlq_dir))

  def dlq_ls(self) -> List[dict]:
    """One record per quarantined task: name, payload (JSON string),
    delivery count, and the recorded failure reasons (newest last)."""
    out = []
    for name in sorted(os.listdir(self.dlq_dir)):
      try:
        with open(os.path.join(self.dlq_dir, name)) as f:
          payload = f.read()
      except FileNotFoundError:
        continue
      meta = self._read_meta(name)
      out.append({
        "name": name,
        "payload": payload,
        "deliveries": int(meta.get("deliveries", 0)),
        "failures": meta.get("failures", []),
      })
    return out

  def dlq_retry(self, names: Optional[Iterable[str]] = None) -> int:
    """Return quarantined tasks to rotation (all, or just ``names``),
    resetting their delivery counts so they get a fresh budget."""
    if names is None:
      names = sorted(os.listdir(self.dlq_dir))
    n = 0
    for name in names:
      src = os.path.join(self.dlq_dir, name)
      try:
        os.rename(src, os.path.join(self.queue_dir, name))
      except FileNotFoundError:
        continue
      meta = self._read_meta(name)
      meta["deliveries"] = 0
      self._write_meta(name, meta)
      n += 1
    self._pending_cache = None
    return n

  def dlq_purge(self) -> int:
    """Drop all quarantined tasks (and their metadata). Irreversible."""
    n = 0
    for name in list(os.listdir(self.dlq_dir)):
      try:
        os.remove(os.path.join(self.dlq_dir, name))
        n += 1
      except FileNotFoundError:
        continue
      finally:
        self._drop_meta(name)
    return n

  # -- counters -------------------------------------------------------------

  def _tally(self, counter: str, n: int = 1):
    with open(os.path.join(self.path, counter), "ab") as f:
      f.write(b"\x01" * n)

  def _count(self, counter: str) -> int:
    try:
      return os.path.getsize(os.path.join(self.path, counter))
    except FileNotFoundError:
      return 0

  @property
  def inserted(self) -> int:
    return self._count("insertions")

  @property
  def completed(self) -> int:
    return self._count("completions")

  @property
  def speculation_won(self) -> int:
    """Crash-safe count of pair indices the TWIN resolved first."""
    return self._count("speculation_won")

  @property
  def speculation_fenced(self) -> int:
    """Crash-safe count of pair indices the ORIGINAL resolved first."""
    return self._count("speculation_fenced")

  @property
  def enqueued(self) -> int:
    """Tasks in rotation (queued + leased). O(segments) — segment task
    counts ride in the file names, so no segment file is ever opened."""
    return (
      sum(_name_tasks(n) for n in os.listdir(self.queue_dir))
      + sum(_name_tasks(n) for n in os.listdir(self.lease_dir))
    )

  @property
  def leased(self) -> int:
    return sum(_name_tasks(n) for n in os.listdir(self.lease_dir))

  @property
  def queue_files(self) -> int:
    """Control-plane objects backing the pending pool — O(shards) per
    batch-inserted campaign, vs O(tasks) for the classic layout (the
    `queue status`/smoke-gate scalability signal)."""
    return len(os.listdir(self.queue_dir))

  def lease_ages(self) -> List[float]:
    """Seconds until each outstanding lease expires (negative = overdue,
    will recycle on the next poll)."""
    now = time.time()
    out = []
    for name in os.listdir(self.lease_dir):
      try:
        out.append(float(name.split(LEASE_SEP, 1)[0]) - now)
      except ValueError:
        continue
    return sorted(out)

  @property
  def stale_leases(self) -> int:
    """Leases past expiry that no poll has recycled yet — the queue's
    zombie pressure: each one is a worker that died, hung, or stopped
    heartbeating (`igneous queue status` surfaces this)."""
    return sum(1 for age in self.lease_ages() if age < 0)

  @property
  def backlog(self) -> int:
    """Work remaining (queued + leased, DLQ excluded) — the autoscaler's
    demand signal (ISSUE 6)."""
    return self.enqueued

  def depth_snapshot(self) -> dict:
    """One consistent-ish read of every depth the health plane consumes
    (listing races are possible; each field is individually truthful)."""
    leased = self.leased
    return {
      "inserted": self.inserted,
      "enqueued": self.enqueued,
      "leased": leased,
      "completed": self.completed,
      "backlog": self.backlog,
      "dlq": self.dlq_count,
      "stale_leases": self.stale_leases,
    }

  def reset_deliveries(self) -> int:
    """Zero the delivery count of every task still in rotation (queued or
    leased) so a ``max_deliveries`` budget starts fresh — the operator
    re-arm after a bad deploy burned deliveries on healthy tasks. DLQ'd
    tasks keep their counts (``dlq retry`` already grants fresh budgets)."""
    n = 0
    quarantined = set(os.listdir(self.dlq_dir))
    for name in list(os.listdir(self.meta_dir)):
      if name in quarantined:
        continue
      meta = self._read_meta(name)
      if not meta.get("deliveries"):
        continue
      meta["deliveries"] = 0
      self._write_meta(name, meta)
      n += 1
    return n

  def fsck(self, repair: bool = False) -> dict:
    """Consistency audit: undeserializable task files (the same check
    lease() applies), unparseable lease names, counter drift. With
    repair=True, malformed files move to ``<queue>/quarantine/`` and
    bad-name leases with VALID payloads recycle into the queue (corrupt
    ones are quarantined too)."""
    problems = {"malformed_tasks": [], "bad_lease_names": [],
                "counter_drift": (self.inserted - self.completed
                                  - self.enqueued - self.dlq_count)}
    quarantine_dir = os.path.join(self.path, "quarantine")

    def payload_ok(path: str):
      """None if a worker raced us; else (valid, contents)."""
      try:
        with open(path) as f:
          contents = f.read()
      except FileNotFoundError:
        return None  # leased/recycled mid-scan: healthy, skip
      try:
        deserialize(contents)  # exactly what lease() will do
        return (True, contents)
      except Exception:
        return (False, contents)

    def quarantine(path: str, name: str):
      os.makedirs(quarantine_dir, exist_ok=True)
      try:
        os.rename(path, os.path.join(quarantine_dir, name))
      except FileNotFoundError:
        pass

    def segment_ok(path: str, count: int):
      """None if raced; else whether every line deserializes AND the
      task count in the name matches the file (depth reads trust it)."""
      try:
        entries = self._read_segment(path)
      except FileNotFoundError:
        return None
      except Exception:
        return False
      if len(entries) != count:
        return False
      try:
        for _i, p in entries:
          deserialize(p)
      except Exception:
        return False
      return True

    for name in list(os.listdir(self.queue_dir)):
      path = os.path.join(self.queue_dir, name)
      parsed = seg_parse(name)
      if parsed is not None:
        ok = segment_ok(path, parsed[1])
        if ok is None or ok:
          continue
      else:
        result = payload_ok(path)
        if result is None or result[0]:
          continue
      problems["malformed_tasks"].append(name)
      if repair:
        quarantine(path, name)

    for name in list(os.listdir(self.lease_dir)):
      try:
        float(name.split(LEASE_SEP, 1)[0])
        continue  # well-formed lease
      except ValueError:
        pass
      problems["bad_lease_names"].append(name)
      if repair:
        path = os.path.join(self.lease_dir, name)
        result = payload_ok(path)
        if result is not None and result[0]:
          try:
            os.rename(path, os.path.join(self.queue_dir, name))
          except FileNotFoundError:
            pass
        elif result is not None:
          quarantine(path, name)
    return problems

  def is_empty(self) -> bool:
    return self.enqueued == 0

  def rezero(self):
    for counter in ("insertions", "completions"):
      try:
        os.remove(os.path.join(self.path, counter))
      except FileNotFoundError:
        pass

  # -- segment I/O ----------------------------------------------------------

  def _write_file(self, dirpath: str, name: str, content: str):
    """tmp-write + atomic rename with the same turd-free contract as
    insert()/_write_meta."""
    tmp = os.path.join(self.path, f".tmp-{uuid.uuid4().hex}")
    try:
      with open(tmp, "w") as f:
        f.write(content)
      os.replace(tmp, os.path.join(dirpath, name))
    except BaseException:
      try:
        os.remove(tmp)
      except FileNotFoundError:
        pass
      raise

  @staticmethod
  def _read_segment(path: str) -> List[Tuple[int, str]]:
    """Segment file → [(task_index, payload)] (payloads are single-line
    JSON, so one line per task). Raises FileNotFoundError on lease races
    like every other read here; malformed lines raise ValueError for
    fsck to catch."""
    entries = []
    with open(path) as f:
      for line in f:
        line = line.rstrip("\n")
        if not line:
          continue
        idx, payload = line.split("\t", 1)
        entries.append((int(idx), payload))
    return entries

  def _copy_meta(self, src_segid: str, dst_segid: str):
    """Splits inherit the parent segment's attempt record, so per-task
    DLQ attribution survives any number of lease splits. Speculation
    pair membership (ISSUE 17) rides along too — a twin tail split off
    at the batch cap (or a stolen/released remainder) must keep routing
    its acks through first-resolution marker arbitration, else the two
    copies of an index would BOTH tally. Holder identity does not copy:
    the split lands pending, owned by whoever leases it next."""
    meta = self._read_meta(f"{SEG_PREFIX}{src_segid}")
    meta.pop("holder", None)
    meta.pop("leased_at", None)
    if not meta.get("spec") and self._spec_active():
      spec = self._spec_of(src_segid)   # heals a clobbered orig meta
      if spec is not None:
        meta["spec"] = spec
    if meta.get("deliveries") or meta.get("failures") or meta.get("spec"):
      self._write_meta(f"{SEG_PREFIX}{dst_segid}", meta)
    spec = meta.get("spec")
    if isinstance(spec, dict) and spec.get("pair"):
      # lineage marker: until this descendant drains, the pair's done
      # markers must survive — a GC that only tracked the two original
      # segids would collect them and let a lingering copy re-tally
      try:
        fd = os.open(
          self._spec_path(f"side_{spec['pair']}_{dst_segid}"),
          os.O_CREAT | os.O_WRONLY,
        )
        os.close(fd)
      except OSError:
        pass

  # -- producer -------------------------------------------------------------

  def insert(self, tasks: Iterable, total: Optional[int] = None):
    """Classic one-file-per-task insert (kept verbatim for layout
    compatibility; batched producers should call :meth:`insert_batch`)."""
    del total
    n = 0
    for task in self._iter(tasks):
      payload = serialize(task)
      name = f"{uuid.uuid4().hex}.json"
      tmp = os.path.join(self.path, f".tmp-{name}")
      try:
        with open(tmp, "w") as f:
          f.write(payload)
        os.replace(tmp, os.path.join(self.queue_dir, name))
      except BaseException:
        try:
          os.remove(tmp)
        except FileNotFoundError:
          pass
        raise
      n += 1
    self._tally("insertions", n)
    self._pending_cache = None
    return n

  def insert_batch(self, tasks: Iterable, total: Optional[int] = None):
    """Batched wire protocol (ISSUE 15): tasks land in segment files of
    up to ``IGNEOUS_QUEUE_SEG_TASKS`` tasks each — one append per
    segment instead of one file + meta per task. ``total`` (when the
    producer knows it, e.g. a regular grid's task count) sizes segments
    so the batch spreads across ~``IGNEOUS_QUEUE_SHARDS`` files for
    lease-contention spread; unknown totals stream at the per-segment
    cap. ``IGNEOUS_QUEUE_SEG_TASKS=0`` falls back to the classic
    per-task layout."""
    from ..analysis import knobs

    seg_cap = knobs.get_int("IGNEOUS_QUEUE_SEG_TASKS")
    seg_cap = DEFAULT_SEG_TASKS if seg_cap is None else int(seg_cap)
    if seg_cap <= 0:
      return self.insert(tasks, total=total)
    shards = knobs.get_int("IGNEOUS_QUEUE_SHARDS")
    shards = max(int(shards or DEFAULT_QUEUE_SHARDS), 1)
    if total:
      seg_size = min(max(-(-int(total) // shards), 1), seg_cap)
    else:
      seg_size = seg_cap
    base = self.inserted   # global task indices continue across batches
    n = 0
    chunk: List[Tuple[int, str]] = []

    def flush():
      nonlocal chunk
      if chunk:
        self._write_file(
          self.queue_dir, seg_name(uuid.uuid4().hex, len(chunk)),
          _seg_content(chunk),
        )
        chunk = []

    for task in self._iter(tasks):
      payload = task if isinstance(task, str) else serialize(task)
      chunk.append((base + n, payload))
      n += 1
      if len(chunk) >= seg_size:
        flush()
    flush()
    self._tally("insertions", n)
    self._pending_cache = None
    return n

  insert_all = insert

  _iter = staticmethod(lambda tasks: iter_tasks(tasks))

  # -- consumer -------------------------------------------------------------

  def _recycle_expired(self, force: bool = False) -> int:
    """Return expired leases to rotation. Throttled to one lease-dir scan
    per ``IGNEOUS_QUEUE_RECYCLE_SEC`` (0 = scan on every call) — the full
    scan dominated small-task lease latency. ``force=True`` bypasses the
    throttle (used when the pending pool looks drained, so an
    emptied-but-expired queue never reads as done). Returns the number of
    files returned to the pool."""
    from ..analysis import knobs

    now = time.time()
    if not force:
      interval = knobs.get_float("IGNEOUS_QUEUE_RECYCLE_SEC")
      interval = DEFAULT_RECYCLE_SEC if interval is None else float(interval)
      if interval > 0 and now - self._last_recycle < interval:
        return 0
    self._last_recycle = now
    n = 0
    for name in os.listdir(self.lease_dir):
      try:
        deadline = float(name.split(LEASE_SEP, 1)[0])
      except ValueError:
        continue
      if deadline >= now:
        continue
      orig = name.split(LEASE_SEP, 1)[1]
      src = os.path.join(self.lease_dir, name)
      if self._exhausted(orig):
        # the worker that held this lease died (or never acked): the
        # lease expiring IS the failure signal for its final delivery
        reason = (
          f"lease expired after delivery {self.delivery_count(orig)} "
          f"(worker lost or task hung)"
        )
        parsed = seg_parse(orig)
        if parsed:
          self._expire_segment_to_dlq(src, parsed[0], reason)
        else:
          self._quarantine_to_dlq(src, orig, reason)
        continue
      try:
        os.rename(src, os.path.join(self.queue_dir, orig))
      except FileNotFoundError:
        continue  # another worker recycled it first
      n += 1
      if self._pending_cache is not None:
        self._pending_cache.append(orig)
    if os.path.isdir(self.spec_dir) or os.path.isdir(self.steal_dir):
      self._survival_gc(now)
    return n

  def _expire_segment_to_dlq(self, src: str, segid: str, reason: str):
    """A segment that exhausted its delivery budget quarantines
    per-index: every surviving member becomes its own ``dlq/`` entry
    (``task_<segid>_<idx>.json``) carrying the shared attempt record, so
    `dlq ls|retry` keep their per-task granularity. Deterministic names
    make a racing double-expansion idempotent; dlq files land before the
    lease file is removed, so a crash mid-expansion re-runs cleanly."""
    from .. import telemetry

    try:
      entries = self._read_segment(src)
    except FileNotFoundError:
      return  # another worker expanded it first
    seg_meta = self._read_meta(f"{SEG_PREFIX}{segid}")
    spec = seg_meta.get("spec") if self._spec_active() else None
    for idx, payload in entries:
      if spec and self._spec_resolved(spec["pair"], idx):
        # the pair's other copy already completed (and tallied) this
        # index — dropping it is the resolution, not a quarantine
        self._spec_collapse(None, None, 1)
        continue
      name = f"task_{segid}_{idx}.json"
      meta = self._read_meta(name)
      meta["deliveries"] = max(
        int(meta.get("deliveries", 0)), int(seg_meta.get("deliveries", 0))
      )
      meta["failures"] = (
        seg_meta.get("failures", []) + meta.get("failures", [])
      )[-MAX_RECORDED_FAILURES:]
      if spec:
        meta["spec"] = spec
      self._write_meta(name, meta)
      self._record_failure(name, reason)
      self._write_file(self.dlq_dir, name, payload)
      telemetry.incr("dlq.promoted")
    try:
      os.remove(src)
    except FileNotFoundError:
      pass
    self._drop_meta(f"{SEG_PREFIX}{segid}")

  def _pop_pending(self) -> Optional[str]:
    """Pick a pending name from the cached per-shard index — the random-
    within-window contention dodge of the classic lease(), without the
    listdir+sort per acquisition. The cache is reverse-sorted so the
    window sits at the tail for O(1) pops."""
    cache = self._pending_cache
    if not cache:
      return None
    window = min(len(cache), CONTENTION_WINDOW)
    return cache.pop(len(cache) - 1 - random.randrange(window))

  def _lease_one(self, name: str, seconds: float, cap: int):
    """Acquire one pending file (rename = the mutex). A classic per-task
    file leases whole; a segment leases as a :class:`RangeLease`, split
    at ``cap`` members — the remainder returns to the pool under a new
    segid (attempt meta copied) BEFORE the lease shrinks, so a crash
    between duplicates deliveries but never loses tasks. Returns a list
    of (task, token) pairs, None when the rename race was lost, or []
    when the file held only speculation-resolved indices (already
    completed by the pair's other copy) and collapsed to nothing."""
    deadline = time.time() + seconds
    lease_name = f"{deadline:.3f}{LEASE_SEP}{name}"
    src = os.path.join(self.queue_dir, name)
    dst = os.path.join(self.lease_dir, lease_name)
    try:
      os.rename(src, dst)
    except FileNotFoundError:
      return None  # lost the race; caller tries another
    parsed = seg_parse(name)
    if parsed is None:
      spec = self._spec_of_name(name) if self._spec_active() else None
      if spec is not None:
        carve = _CARVE_RE.match(name)
        if carve and self._spec_resolved(spec["pair"], int(carve.group(2))):
          # the pair's other copy already completed (and tallied) this
          # index — drop the duplicate instead of delivering it
          self._spec_collapse(dst, name, 1)
          return []
      meta = self._read_meta(name)
      meta["deliveries"] = int(meta.get("deliveries", 0)) + 1
      self._write_meta(name, meta)
      with open(dst) as f:
        return [(deserialize(f.read()), lease_name)]
    segid = parsed[0]
    entries = self._read_segment(dst)
    if self._spec_active():
      spec = self._spec_of(segid)
      if spec is not None:
        live = [
          (i, p) for i, p in entries
          if not self._spec_resolved(spec["pair"], i)
        ]
        if len(live) != len(entries):
          self._spec_collapse(None, None, len(entries) - len(live))
          if not live:
            try:
              os.remove(dst)
            except FileNotFoundError:
              pass
            self._drop_meta(f"{SEG_PREFIX}{segid}")
            return []
          new_lease = f"{deadline:.3f}{LEASE_SEP}{seg_name(segid, len(live))}"
          self._write_file(self.lease_dir, new_lease, _seg_content(live))
          try:
            os.remove(dst)
          except FileNotFoundError:
            pass
          lease_name, entries = new_lease, live
          dst = os.path.join(self.lease_dir, lease_name)
    cap = max(int(cap), 1)
    if len(entries) > cap:
      keep, rest = entries[:cap], entries[cap:]
      rest_segid = uuid.uuid4().hex
      self._copy_meta(segid, rest_segid)
      rest_name = seg_name(rest_segid, len(rest))
      self._write_file(self.queue_dir, rest_name, _seg_content(rest))
      if self._pending_cache is not None:
        # next pop likely continues the contiguous run on this worker
        self._pending_cache.append(rest_name)
      lease_name_new = f"{deadline:.3f}{LEASE_SEP}{seg_name(segid, len(keep))}"
      self._write_file(self.lease_dir, lease_name_new, _seg_content(keep))
      try:
        os.remove(dst)
      except FileNotFoundError:
        pass
      lease_name, entries = lease_name_new, keep
    meta = self._read_meta(f"{SEG_PREFIX}{segid}")
    meta["deliveries"] = int(meta.get("deliveries", 0)) + 1
    # holder identity: what speculation targets (flagged worker -> its
    # leases) and stealing filters (a thief never claims its own range)
    meta["holder"] = self.worker_id
    meta["leased_at"] = round(time.time(), 3)
    self._write_meta(f"{SEG_PREFIX}{segid}", meta)
    rl = RangeLease(self, lease_name, segid, dict(entries), deadline)
    return [(deserialize(p), RangeSub(rl, i)) for i, p in entries]

  def lease_batch(self, seconds: float = 600, max_tasks: int = 1):
    """Lease up to ``max_tasks`` tasks in one call. Segments come back as
    range members — (task, :class:`RangeSub`) pairs sharing one
    underlying lease — classic files as (task, lease_id) pairs; the two
    mix freely in one result. Returns [] when the queue is drained."""
    with trace.span("queue.lease"):
      return self._lease_batch(seconds, max_tasks)

  def _lease_batch(self, seconds, max_tasks):
    self._recycle_expired()
    out: List[Tuple[RegisteredTask, object]] = []
    refreshed = False
    races = 0
    while len(out) < max_tasks and races < 10:
      name = self._pop_pending()
      if name is None:
        if refreshed:
          break
        # cache drained: force a recycle pass (the throttle must not make
        # an emptied-but-expired queue look drained), then re-list once
        self._recycle_expired(force=True)
        self._pending_cache = sorted(os.listdir(self.queue_dir), reverse=True)
        refreshed = True
        continue
      got = self._lease_one(name, seconds, max_tasks - len(out))
      if got is None:
        races += 1
        continue
      out.extend(got)
    return out

  def lease(self, seconds: float = 600) -> Optional[Tuple[RegisteredTask, str]]:
    """Returns (task, lease_id) or None if the queue is drained. On a
    segmented queue the single task splits off its segment, so solo
    pollers interoperate with batch producers."""
    got = self.lease_batch(seconds, max_tasks=1)
    return got[0] if got else None

  def _lease_deadline(self, lease_id: str) -> Optional[float]:
    try:
      return float(str(lease_id).split(LEASE_SEP, 1)[0])
    except ValueError:
      return None

  def renew(self, lease_id, seconds: float = 600):
    """Extend a held lease's visibility timeout (the fq:// analogue of
    SQS ChangeMessageVisibility) by re-timestamping the lease name.
    Returns the NEW lease token — the old one is dead; callers (normally
    a LeaseHeartbeat) must use the returned token from here on. A range
    member renews its parent's ONE lease and returns the same handle:
    RangeSub tokens are stable across renewals (rotation is internal).

    Zombie fencing: renewal is refused (StaleLeaseError + ``zombie.renew``
    counter) once the lease has expired or the task was re-issued — a
    stalled worker that wakes up cannot re-acquire what it lost."""
    with trace.span("queue.renew"):
      return self._renew(lease_id, seconds)

  def _renew(self, lease_id, seconds):
    from .. import telemetry

    if isinstance(lease_id, RangeSub):
      self._range_renew(lease_id.parent, seconds)
      return lease_id
    deadline = self._lease_deadline(lease_id)
    orig = str(lease_id).split(LEASE_SEP, 1)[-1]
    if deadline is None or deadline < time.time():
      telemetry.incr("zombie.renew")
      raise StaleLeaseError(
        f"lease for {orig!r} already expired; the task is due for re-issue"
      )
    new_id = f"{time.time() + seconds:.3f}{LEASE_SEP}{orig}"
    try:
      os.rename(
        os.path.join(self.lease_dir, lease_id),
        os.path.join(self.lease_dir, new_id),
      )
    except FileNotFoundError:
      telemetry.incr("zombie.renew")
      raise StaleLeaseError(
        f"lease for {orig!r} was re-issued (or completed) by another worker"
      ) from None
    return new_id

  def delete(self, lease_id) -> bool:
    """Complete a task. Zombie-fenced: the delete (and its completion
    tally) only lands while the lease token is current — a worker that
    stalled past its lease and woke after the task was re-issued gets
    False + a ``zombie.delete`` counter instead of double-completing
    (the acceptance invariant: completions tally == task count). A range
    member acks its sub-range: the parent lease shrinks by one index."""
    with trace.span("queue.delete"):
      return self._delete(lease_id)

  def _delete(self, lease_id):
    from .. import telemetry

    if isinstance(lease_id, RangeSub):
      return self._range_ack(lease_id.parent, lease_id.index)
    deadline = self._lease_deadline(lease_id)
    if deadline is not None and deadline < time.time():
      telemetry.incr("zombie.delete")
      return False
    orig = str(lease_id).split(LEASE_SEP, 1)[-1]
    try:
      os.remove(os.path.join(self.lease_dir, lease_id))
    except FileNotFoundError:
      telemetry.incr("zombie.delete")
      return False
    spec = self._spec_of_name(orig) if self._spec_active() else None
    self._drop_meta(orig)
    if spec is not None:
      # a speculated index carved out as a classic task: the O_EXCL
      # marker arbitrates the tally exactly as in _range_ack_many
      carve = _CARVE_RE.match(orig)
      idx = int(carve.group(2)) if carve else None
      if idx is not None:
        if not self._spec_mark_first(spec["pair"], idx):
          self._spec_wasted(spec, 1)
          return False  # pair's other copy completed (and tallied) it
        self._spec_account_first(spec, 1)
    self._tally("completions")
    return True

  def nack(self, lease_id, reason: str = "", requeue: bool = False):
    """Record a failed delivery. The failure reason persists with the
    task's metadata; once ``max_deliveries`` is exhausted the task moves
    to ``dlq/``. Otherwise the lease is left to recycle on its visibility
    timeout (at-least-once semantics unchanged) unless ``requeue=True``
    returns it to rotation immediately. A range member nack SPLITS the
    lease: only the failed index retries (or dead-letters).

    A nack whose lease was already re-issued (or completed) is dropped
    with a ``zombie.nack`` counter — recording it would resurrect meta
    for a task this worker no longer owns."""
    with trace.span("queue.nack"):
      return self._nack(lease_id, reason, requeue)

  def _nack(self, lease_id, reason, requeue):
    if isinstance(lease_id, RangeSub):
      return self._range_nack(
        lease_id.parent, lease_id.index, reason, requeue=requeue
      )
    orig = lease_id.split(LEASE_SEP, 1)[-1]
    src = os.path.join(self.lease_dir, lease_id)
    if not os.path.exists(src):
      from .. import telemetry

      telemetry.incr("zombie.nack")
      return
    if self._exhausted(orig):
      self._quarantine_to_dlq(src, orig, reason)  # records the reason
    else:
      self._record_failure(orig, reason)
      if requeue:
        self.release(lease_id)

  def release(self, lease_id):
    """Return a lease to rotation immediately (undelivered). A range
    member releases just its index back as a fresh one-task segment."""
    with trace.span("queue.release"):
      return self._release(lease_id)

  def _release(self, lease_id):
    if isinstance(lease_id, RangeSub):
      return self._range_release(lease_id.parent, [lease_id.index])
    orig = lease_id.split(LEASE_SEP, 1)[1]
    try:
      os.rename(
        os.path.join(self.lease_dir, lease_id),
        os.path.join(self.queue_dir, orig),
      )
    except FileNotFoundError:
      return
    if self._pending_cache is not None:
      self._pending_cache.append(orig)

  def release_all(self):
    for name in list(os.listdir(self.lease_dir)):
      if LEASE_SEP in name:
        self.release(name)
    self._pending_cache = None

  # -- batched completion ----------------------------------------------------

  def ack_batch(self, tokens) -> List[bool]:
    """Complete many tasks at once. Range members sharing a parent lease
    collapse into ONE lease-file rewrite; classic tokens delete one by
    one. Results align positionally with ``tokens`` (False = zombie-
    fenced, exactly as the scalar ops report it)."""
    tokens = list(tokens)
    results = [False] * len(tokens)
    by_parent: Dict[int, Tuple[RangeLease, List[Tuple[int, int]]]] = {}
    for pos, tok in enumerate(tokens):
      if isinstance(tok, RangeSub):
        by_parent.setdefault(id(tok.parent), (tok.parent, []))[1].append(
          (pos, tok.index)
        )
      else:
        results[pos] = self.delete(tok)
    for parent, members in by_parent.values():
      acked = self._range_ack_many(parent, [i for _, i in members])
      for pos, i in members:
        results[pos] = bool(acked.get(int(i)))
    return results

  def nack_batch(self, tokens, reason: str = "", requeue: bool = False):
    """Fail many deliveries with one call (per-token semantics identical
    to scalar ``nack``: range members split, exhausted tasks DLQ)."""
    for tok in tokens:
      self.nack(tok, reason, requeue=requeue)

  # -- range-lease mechanics (handles live in .ranges) -----------------------

  def _range_rewrite(self, rl: RangeLease, new_entries: Dict[int, str],
                     new_deadline: Optional[float] = None) -> bool:
    """Swap the range's lease file for one holding ``new_entries``
    (removed outright when empty). Write-new-then-remove-old: a crash in
    between re-delivers, never loses. False = the old lease file was
    gone (expired + re-issued, or completed elsewhere) — the new file is
    withdrawn and the caller is a zombie for this range. Caller holds
    ``rl.lock``."""
    deadline = rl.deadline if new_deadline is None else float(new_deadline)
    old = os.path.join(self.lease_dir, rl.token)
    if not new_entries:
      try:
        os.remove(old)
      except FileNotFoundError:
        return False
      rl.entries = {}
      return True
    new_token = f"{deadline:.3f}{LEASE_SEP}{seg_name(rl.segid, len(new_entries))}"
    if new_token == rl.token:
      rl.entries = dict(new_entries)
      return True
    self._write_file(
      self.lease_dir, new_token, _seg_content(sorted(new_entries.items()))
    )
    try:
      os.remove(old)
    except FileNotFoundError:
      try:
        os.remove(os.path.join(self.lease_dir, new_token))
      except FileNotFoundError:
        pass
      return False
    rl.token = new_token
    rl.entries = dict(new_entries)
    rl.deadline = deadline
    return True

  def _range_ack_many(self, rl: RangeLease, indices) -> Dict[int, bool]:
    """Complete several members of one range with a single rewrite."""
    from .. import telemetry

    todo = [int(i) for i in indices]
    with rl.lock:
      if rl.deadline < time.time():
        telemetry.incr("zombie.delete", len(todo))
        return {i: False for i in todo}
      hit = sorted({i for i in todo if i in rl.entries})
      miss = [i for i in todo if i not in rl.entries]
      if miss:
        telemetry.incr("zombie.delete", len(miss))
      if not hit:
        return {i: False for i in todo}
      remaining = {i: p for i, p in rl.entries.items() if i not in set(hit)}
      if not self._range_rewrite(rl, remaining):
        telemetry.incr("zombie.delete", len(hit))
        return {i: False for i in todo}
      # first-RESOLUTION-wins (ISSUE 17): with a live speculation pair,
      # the per-index O_EXCL marker — attempted only AFTER the rewrite
      # proved this worker still owns its copy — arbitrates the tally.
      # Exactly one side creates each marker (and tallies); the loser's
      # ack shrank its lease above but tallies nothing.
      spec = self._spec_of(rl.segid) if self._spec_active() else None
      if spec is None:
        first = hit
      else:
        first = [i for i in hit if self._spec_mark_first(spec["pair"], i)]
        if first:
          self._spec_account_first(spec, len(first))
        if len(first) != len(hit):
          self._spec_wasted(spec, len(hit) - len(first))
      if first:
        self._tally("completions", len(first))
      if not remaining:
        self._drop_meta(f"{SEG_PREFIX}{rl.segid}")
      hitset = set(hit)
      return {i: i in hitset for i in todo}

  def _range_ack(self, rl: RangeLease, index: int) -> bool:
    return self._range_ack_many(rl, [index])[int(index)]

  def _range_nack(self, rl: RangeLease, index: int, reason: str = "",
                  requeue: bool = False):
    """Mid-range failure: carve the failed index out as a classic
    single-task lease (``task_<segid>_<idx>.json``) inheriting the
    range's attempt record, shrink the range, then hand the carve to the
    classic nack machinery — so reason recording, DLQ promotion, and
    retry budgets apply to ONLY the failed index while the rest of the
    range proceeds untouched."""
    from .. import telemetry

    index = int(index)
    with rl.lock:
      if rl.deadline < time.time() or index not in rl.entries:
        telemetry.incr("zombie.nack")
        return
      carve = f"task_{rl.segid}_{index}.json"
      seg_meta = self._read_meta(f"{SEG_PREFIX}{rl.segid}")
      meta = self._read_meta(carve)
      meta["deliveries"] = max(
        int(meta.get("deliveries", 0)), int(seg_meta.get("deliveries", 0))
      )
      meta["failures"] = (
        seg_meta.get("failures", []) + meta.get("failures", [])
      )[-MAX_RECORDED_FAILURES:]
      if seg_meta.get("spec"):
        # pair membership rides along: the carve's eventual ack must
        # still go through first-resolution marker arbitration
        meta["spec"] = seg_meta["spec"]
      self._write_meta(carve, meta)
      carve_lease = f"{rl.deadline:.3f}{LEASE_SEP}{carve}"
      self._write_file(self.lease_dir, carve_lease, rl.entries[index])
      remaining = {i: p for i, p in rl.entries.items() if i != index}
      if not self._range_rewrite(rl, remaining):
        # the whole range is being redelivered; withdraw the carve so the
        # index isn't duplicated
        try:
          os.remove(os.path.join(self.lease_dir, carve_lease))
        except FileNotFoundError:
          pass
        telemetry.incr("zombie.nack")
        return
    return self.nack(carve_lease, reason, requeue=requeue)

  def _range_release(self, rl: RangeLease, indices=None) -> int:
    """Return members (all surviving ones when ``indices`` is None) to
    the pool immediately as a fresh segment under a new segid (attempt
    meta copied, deliveries kept — matching classic release)."""
    with rl.lock:
      if indices is None:
        chosen = sorted(rl.entries)
      else:
        chosen = sorted({int(i) for i in indices} & set(rl.entries))
      if not chosen or rl.deadline < time.time():
        return 0  # expired: the recycler owns these now
      released = {i: rl.entries[i] for i in chosen}
      new_segid = uuid.uuid4().hex
      self._copy_meta(rl.segid, new_segid)
      new_name = seg_name(new_segid, len(released))
      self._write_file(
        self.queue_dir, new_name, _seg_content(sorted(released.items()))
      )
      remaining = {i: p for i, p in rl.entries.items() if i not in set(chosen)}
      if not self._range_rewrite(rl, remaining):
        try:
          os.remove(os.path.join(self.queue_dir, new_name))
        except FileNotFoundError:
          pass
        return 0
      if self._pending_cache is not None:
        self._pending_cache.append(new_name)
      return len(released)

  def _range_renew(self, rl: RangeLease, seconds: float) -> str:
    """Extend the range's ONE lease. Internally the token rotates (the
    deadline rides in the file name) but RangeSub handles stay valid.
    Freshness guard: when the deadline already covers ~the requested
    extension, this is a no-op — K heartbeat-tracked members cost one
    rename per beat, not K."""
    from .. import telemetry

    with rl.lock:
      now = time.time()
      if not rl.entries:
        # fully completed: a heartbeat racing the final ack — not a zombie
        raise StaleLeaseError(
          f"range {rl.segid!r} fully completed; nothing left to renew"
        )
      if rl.deadline < now:
        telemetry.incr("zombie.renew")
        raise StaleLeaseError(
          f"range lease {rl.segid!r} already expired; due for re-issue"
        )
      # work stealing (ISSUE 17): the heartbeat IS the holder's claim
      # inbox — service a pending claim before the freshness guard can
      # short-circuit, so a thief never waits past one renewal interval
      self._steal_service(rl)
      if rl.deadline >= now + float(seconds) * 0.9:
        return rl.token
      new_deadline = now + float(seconds)
      new_token = (
        f"{new_deadline:.3f}{LEASE_SEP}{seg_name(rl.segid, len(rl.entries))}"
      )
      try:
        os.rename(
          os.path.join(self.lease_dir, rl.token),
          os.path.join(self.lease_dir, new_token),
        )
      except FileNotFoundError:
        telemetry.incr("zombie.renew")
        raise StaleLeaseError(
          f"range lease {rl.segid!r} was re-issued by another worker"
        ) from None
      rl.token = new_token
      rl.deadline = new_deadline
      return rl.token

  # -- campaign survival: straggler speculation + work stealing (ISSUE 17) ---

  def _spec_active(self) -> bool:
    """One stat call gates every speculation hook: the ``spec/`` sidecar
    only exists once something speculated, so queues that never do read
    byte-for-byte as before ISSUE 17."""
    return os.path.isdir(self.spec_dir)

  def _spec_path(self, name: str) -> str:
    return os.path.join(self.spec_dir, name)

  def _spec_of(self, segid: str) -> Optional[dict]:
    """Pair membership of a segment: ``{"pair": …, "side": "orig"|"twin"}``
    from its attempt meta. The ORIG side gets a pair-file fallback:
    ``speculate_lease`` (driver process) stamping ``meta["spec"]`` can
    race the holder's own meta read-modify-write (a lease split's
    delivery bump, a failure record) and lose — but the pair file is
    NAMED after the orig segid, so its existence alone proves
    membership no matter which write landed last."""
    spec = self._read_meta(f"{SEG_PREFIX}{segid}").get("spec")
    if isinstance(spec, dict) and "pair" in spec:
      return spec
    if os.path.exists(self._spec_path(f"pair_{segid}.json")):
      return {"pair": segid, "side": "orig"}
    return None

  def _spec_of_name(self, name: str) -> Optional[dict]:
    """Pair membership of a classic queue/lease/dlq file name (carves
    inherit it into their own meta; plain per-task files never have
    any)."""
    spec = self._read_meta(self._meta_key(name)).get("spec")
    return spec if isinstance(spec, dict) and "pair" in spec else None

  def _spec_resolved(self, pairid: str, index: int) -> bool:
    return os.path.exists(self._spec_path(f"done_{pairid}_{int(index)}"))

  def _spec_mark_first(self, pairid: str, index: int) -> bool:
    """Atomically claim first resolution of (pair, index). The O_EXCL
    create is the ONE commitment point for the completion tally: the
    creator tallies, everyone else is fenced."""
    try:
      fd = os.open(
        self._spec_path(f"done_{pairid}_{int(index)}"),
        os.O_CREAT | os.O_EXCL | os.O_WRONLY,
      )
    except FileExistsError:
      return False
    os.close(fd)
    return True

  def _spec_account_first(self, spec: dict, n: int):
    """Exactly one of won/fenced per issued index, settled at first
    resolution — the twin resolving first means speculation paid off,
    the original resolving first means the twin's copy is now waste.
    Besides the in-process telemetry counter (journal-flushed, LOSSY
    when the acking worker is SIGKILLed before its next flush) the
    resolution appends to a crash-safe queue tally, committed in the
    same breath as the done marker — the campaign driver reconciles
    the journal ledger from these after the pool is down."""
    from .. import telemetry

    if spec.get("side") == "twin":
      telemetry.incr("speculation.won", n)
      self._tally("speculation_won", n)
    else:
      telemetry.incr("speculation.fenced", n)
      self._tally("speculation_fenced", n)

  def _spec_wasted(self, spec: dict, n: int):
    """A duplicate ack: the loser executed work the winner already
    tallied. ``speculation.wasted_ms`` accumulates the pair-open window
    per duplicate — the wall-clock bound on the duplicated effort."""
    from .. import telemetry

    telemetry.incr("speculation.duplicate_ack", n)
    pair = self._read_pair(spec["pair"])
    if pair and pair.get("ts"):
      window_ms = int(max(0.0, time.time() - float(pair["ts"])) * 1000)
      telemetry.incr("speculation.wasted_ms", window_ms * n)

  def _spec_collapse(self, lease_path: Optional[str],
                     meta_name: Optional[str], n: int):
    """Drop an already-resolved duplicate copy at lease/expiry time. No
    tally — the winner tallied at resolution; this is how a fenced
    twin's leftover copies drain out of rotation."""
    from .. import telemetry

    if lease_path is not None:
      try:
        os.remove(lease_path)
      except FileNotFoundError:
        pass
    if meta_name is not None:
      self._drop_meta(meta_name)
    telemetry.incr("speculation.deduped", n)

  def _read_pair(self, pairid: str) -> Optional[dict]:
    try:
      with open(self._spec_path(f"pair_{pairid}.json")) as f:
        return json.load(f)
    except (FileNotFoundError, ValueError):
      return None

  def range_leases(self) -> List[dict]:
    """Live range leases with holder identity — the planner's view for
    speculation targeting and steal candidate selection."""
    now = time.time()
    spec_on = self._spec_active()
    out = []
    for name in os.listdir(self.lease_dir):
      try:
        deadline = float(name.split(LEASE_SEP, 1)[0])
      except ValueError:
        continue
      parsed = seg_parse(name.split(LEASE_SEP, 1)[-1])
      if parsed is None:
        continue
      segid, count = parsed
      meta = self._read_meta(f"{SEG_PREFIX}{segid}")
      paired = bool(meta.get("spec")) or (
        # pair-file fallback: a clobbered orig meta must not make this
        # lease look stealable/re-speculatable (see _spec_of)
        spec_on
        and os.path.exists(self._spec_path(f"pair_{segid}.json"))
      )
      out.append({
        "lease": name, "segid": segid, "count": count,
        "deadline": deadline, "expired": deadline < now,
        "holder": meta.get("holder"),
        "leased_at": meta.get("leased_at"),
        "spec": paired,
      })
    return out

  def speculate_lease(self, lease_name: str) -> int:
    """Double-issue the unfinished tail of one held range lease as a
    speculative TWIN segment: fresh segid, fresh delivery budget, the
    SAME global task indices. The twin enters normal rotation; whichever
    copy resolves an index first tallies it (see ``_range_ack_many``)
    and the loser's copy is fenced. One live pair per segment —
    re-speculation waits until the pair resolves and GCs. Returns the
    number of indices twinned (0 when the target is not a range lease,
    is already paired, is below ``IGNEOUS_SPECULATE_MIN_TASKS``, or
    rotated away since it was listed)."""
    from .. import telemetry
    from ..analysis import knobs

    orig = str(lease_name).split(LEASE_SEP, 1)[-1]
    parsed = seg_parse(orig)
    if parsed is None:
      return 0
    segid = parsed[0]
    key = f"{SEG_PREFIX}{segid}"
    meta = self._read_meta(key)
    if meta.get("spec"):
      return 0
    v = knobs.get_int("IGNEOUS_SPECULATE_MIN_TASKS")
    min_tasks = DEFAULT_SPECULATE_MIN_TASKS if v is None else int(v)
    try:
      entries = self._read_segment(os.path.join(self.lease_dir, lease_name))
    except FileNotFoundError:
      return 0  # rotated or completed since the listing; next sweep
    if len(entries) < max(min_tasks, 1):
      return 0
    os.makedirs(self.spec_dir, exist_ok=True)
    # the pair file is the mutex: an O_EXCL loss means a racing driver
    # just speculated this segment
    try:
      fd = os.open(
        self._spec_path(f"pair_{segid}.json"),
        os.O_CREAT | os.O_EXCL | os.O_WRONLY,
      )
    except FileExistsError:
      return 0
    twin = uuid.uuid4().hex
    with os.fdopen(fd, "w") as f:
      json.dump({
        "pair": segid, "orig": segid, "twin": twin,
        "indices": [int(i) for i, _ in entries],
        "ts": round(time.time(), 3), "holder": meta.get("holder"),
      }, f)
    self._write_meta(
      f"{SEG_PREFIX}{twin}",
      {"deliveries": 0, "failures": [],
       "spec": {"pair": segid, "side": "twin"}},
    )
    meta["spec"] = {"pair": segid, "side": "orig"}
    self._write_meta(key, meta)
    # the twin entering rotation is the commit point
    twin_name = seg_name(twin, len(entries))
    self._write_file(self.queue_dir, twin_name, _seg_content(entries))
    if self._pending_cache is not None:
      self._pending_cache.append(twin_name)
    telemetry.incr("speculation.issued", len(entries))
    return len(entries)

  def speculate_flagged(self, workers, max_twins: Optional[int] = None) -> int:
    """Driver entry point: twin the tails of every unexpired, unpaired
    range lease held by a flagged worker — biggest ranges first, capped
    at ``max_twins`` new pairs per sweep (IGNEOUS_SPECULATE_MAX_TWINS).
    Returns the total number of indices twinned."""
    from ..analysis import knobs

    workers = {str(w) for w in workers}
    if not workers:
      return 0
    if max_twins is None:
      v = knobs.get_int("IGNEOUS_SPECULATE_MAX_TWINS")
      max_twins = DEFAULT_SPECULATE_MAX_TWINS if v is None else int(v)
    held = knobs.get_float("IGNEOUS_SPECULATE_MIN_HELD_SEC")
    min_held = DEFAULT_SPECULATE_MIN_HELD_SEC if held is None else float(held)
    now = time.time()
    cands = [
      r for r in self.range_leases()
      if not r["expired"] and not r["spec"] and r["holder"] in workers
      and now - float(r["leased_at"] or now) >= min_held
    ]
    cands.sort(key=lambda r: (-r["count"], r["lease"]))
    issued = twins = 0
    for r in cands:
      if twins >= max_twins:
        break
      n = self.speculate_lease(r["lease"])
      if n:
        issued += n
        twins += 1
    return issued

  def steal_claim(self, thief: Optional[str] = None) -> Optional[str]:
    """Thief entry point: claim the biggest long-held foreign range so
    its holder's next heartbeat renewal releases the unstarted tail back
    to the pool, where the thief (or any idle worker) leases it. One
    claim file per segment; O_EXCL creation makes racing thieves
    converge on distinct targets deterministically. Returns the claimed
    segid, or None when nothing qualifies."""
    from .. import telemetry
    from ..analysis import knobs

    thief = thief or self.worker_id
    v = knobs.get_int("IGNEOUS_STEAL_MIN_TASKS")
    min_tasks = DEFAULT_STEAL_MIN_TASKS if v is None else int(v)
    held = knobs.get_float("IGNEOUS_STEAL_MIN_HELD_SEC")
    min_held = DEFAULT_STEAL_MIN_HELD_SEC if held is None else float(held)
    now = time.time()
    cands = [
      r for r in self.range_leases()
      if not r["expired"] and r["count"] >= max(min_tasks, 1)
      and r["holder"] not in (None, thief)
      and now - float(r["leased_at"] or now) >= min_held
    ]
    cands.sort(key=lambda r: (-r["count"], r["lease"]))
    for r in cands:
      os.makedirs(self.steal_dir, exist_ok=True)
      try:
        fd = os.open(
          os.path.join(self.steal_dir, f"{r['segid']}.claim"),
          os.O_CREAT | os.O_EXCL | os.O_WRONLY,
        )
      except FileExistsError:
        continue  # another thief got this range; try the next
      with os.fdopen(fd, "w") as f:
        json.dump({"thief": thief, "ts": round(now, 3)}, f)
      telemetry.incr("steal.claims")
      return r["segid"]
    return None

  def _steal_service(self, rl: RangeLease) -> int:
    """Holder side, under ``rl.lock`` from ``_range_renew``: a pending
    claim releases ``IGNEOUS_STEAL_FRACTION`` of the UNSTARTED tail
    through the expiry-fenced range-release seam, always keeping at
    least one member so the holder's in-flight work keeps its lease.
    Too-small grants deny the claim (file removed) rather than starve
    the thief silently."""
    if not os.path.isdir(self.steal_dir):
      return 0
    claim = os.path.join(self.steal_dir, f"{rl.segid}.claim")
    if not os.path.exists(claim):
      return 0
    from .. import telemetry
    from ..analysis import knobs

    frac = knobs.get_float("IGNEOUS_STEAL_FRACTION")
    frac = DEFAULT_STEAL_FRACTION if frac is None else float(frac)
    v = knobs.get_int("IGNEOUS_STEAL_MIN_TASKS")
    min_tasks = DEFAULT_STEAL_MIN_TASKS if v is None else int(v)
    unstarted = sorted(set(rl.entries) - rl.started)
    grant_n = min(
      int(len(unstarted) * max(min(frac, 1.0), 0.0)),
      len(rl.entries) - 1,
    )
    granted = 0
    if grant_n >= 1 and len(unstarted) >= max(min_tasks, 1):
      granted = self._range_release(rl, unstarted[-grant_n:])
    try:
      os.remove(claim)
    except FileNotFoundError:
      pass
    if granted:
      telemetry.incr("steal.granted")
      telemetry.incr("steal.tasks", granted)
    else:
      telemetry.incr("steal.denied")
    return granted

  def _survival_gc(self, now: float):
    """Recycle-pass housekeeping for the survival sidecars: TTL-expired
    steal claims recycle (so a re-leased range can be claimed again),
    DLQ carves whose index the pair's other copy completed are pruned
    as stale duplicates, and fully-resolved pairs drop their markers +
    pair file — but only once NOTHING on disk references either segid,
    because any lingering copy must keep deduping against the markers."""
    from .. import telemetry
    from ..analysis import knobs

    if os.path.isdir(self.steal_dir):
      ttl = knobs.get_float("IGNEOUS_STEAL_CLAIM_TTL_SEC")
      ttl = DEFAULT_STEAL_CLAIM_TTL_SEC if ttl is None else float(ttl)
      for name in os.listdir(self.steal_dir):
        if not name.endswith(".claim"):
          continue
        path = os.path.join(self.steal_dir, name)
        try:
          with open(path) as f:
            ts = float(json.load(f).get("ts") or 0)
        except (FileNotFoundError, ValueError, TypeError):
          ts = 0.0
        if now - ts > max(ttl, 0.0):
          try:
            os.remove(path)
            telemetry.incr("steal.expired_claims")
          except FileNotFoundError:
            pass
    if not self._spec_active():
      return
    names = os.listdir(self.spec_dir)
    pairs = [n for n in names if n.startswith("pair_")]
    if not pairs:
      return
    markers = {n for n in names if n.startswith("done_")}
    qlive = os.listdir(self.queue_dir) + os.listdir(self.lease_dir)

    for pname in pairs:
      try:
        with open(self._spec_path(pname)) as f:
          pair = json.load(f)
      except (FileNotFoundError, ValueError):
        continue
      pid = pair.get("pair")
      # descendants (lease splits, stolen/released tails) carry the
      # pair under fresh segids; their side_ lineage markers make them
      # visible here so the pair outlives every circulating copy
      side_pref = f"side_{pid}_"
      lineage = [n[len(side_pref):] for n in names if n.startswith(side_pref)]
      sides = tuple(
        [pair.get("orig", ""), pair.get("twin", "")] + lineage
      )
      # stale DLQ duplicates: the other copy completed this index AFTER
      # it was quarantined — zero-DLQ-leakage means pruning them
      for n in os.listdir(self.dlq_dir):
        m = _CARVE_RE.match(n)
        if not m or m.group(1) not in sides:
          continue
        if f"done_{pid}_{int(m.group(2))}" in markers:
          try:
            os.remove(os.path.join(self.dlq_dir, n))
          except FileNotFoundError:
            continue
          self._drop_meta(n)
          telemetry.incr("speculation.dlq_pruned")
      idxs = pair.get("indices", [])
      done = [f"done_{pid}_{i}" for i in idxs]
      if not all(d in markers for d in done):
        continue

      def referenced(segid: str, listing) -> bool:
        seg_pref = f"{SEG_PREFIX}{segid}_"
        carve_pref = f"task_{segid}_"
        return any(seg_pref in n or carve_pref in n for n in listing)

      dlq_live = os.listdir(self.dlq_dir)
      if any(
        referenced(s, qlive) or referenced(s, dlq_live) for s in sides
      ):
        continue
      for d in done:
        try:
          os.remove(self._spec_path(d))
        except FileNotFoundError:
          pass
      for n in names:
        if n.startswith(side_pref):
          try:
            os.remove(self._spec_path(n))
          except FileNotFoundError:
            pass
      try:
        os.remove(self._spec_path(pname))
      except FileNotFoundError:
        pass
      for s in sides:
        self._drop_meta(f"{SEG_PREFIX}{s}")

  def purge(self):
    for d in (self.queue_dir, self.lease_dir, self.dlq_dir, self.meta_dir,
              self.spec_dir, self.steal_dir):
      if not os.path.isdir(d):
        continue
      for name in list(os.listdir(d)):
        try:
          os.remove(os.path.join(d, name))
        except FileNotFoundError:
          pass
    self._pending_cache = None
    self.rezero()

  # -- worker loop ----------------------------------------------------------

  def poll(
    self,
    lease_seconds: float = 600,
    verbose: bool = False,
    tally: bool = True,
    stop_fn=None,
    max_backoff_window: float = 30.0,
    before_fn=None,
    after_fn=None,
    task_deadline_seconds: Optional[float] = None,
    heartbeat_seconds: Optional[float] = None,
    drain_flag=None,
  ):
    """Lease→execute→delete until stop_fn says stop or the queue drains
    (stop_fn=None polls forever, sleeping with bounded backoff when empty)."""
    del tally  # completions are always tallied; kept for API familiarity
    return poll_loop(
      self, lease_seconds, verbose, stop_fn, max_backoff_window,
      before_fn, after_fn, task_deadline_seconds,
      heartbeat_seconds, drain_flag,
    )

  def __len__(self):
    return self.enqueued
