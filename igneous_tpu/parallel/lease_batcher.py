"""Queue-leased batched execution — SURVEY.md §5.8's north star made real.

The reference's worker loop (`igneous execute`, reference
igneous_cli/cli.py:888-964) runs one task per lease. On a TPU host that
wastes the chip: each task's device stage (a pooling pyramid, an EDT, a
block CCL) occupies a sliver of the mesh while download/upload dominate
wall clock. This module teaches the worker loop to lease up to K tasks,
group the compatible ones (same type + same device-stage signature), and
run each group's device stage as ONE shard_map'd dispatch across the
mesh — while every lease still completes independently:

  * a member whose host stage fails keeps its lease and recycles alone
    after the visibility timeout (at-least-once, exactly like the solo
    poll loop in queues/filequeue.py:36-80);
  * a failed group dispatch falls back to running the incomplete
    members solo within the same round, so one poisoned member can't
    repeatedly drag K-1 healthy leases into recycling;
  * outputs are byte-identical to solo execution — the group handlers
    feed the batched device results back through the SAME completion
    code paths the solo tasks use (downsample_and_upload(_mips_out=...),
    SkeletonTask.execute(_prepared=..., _edt_field=...), the CCL
    store_ccl_faces helpers).

Batchable today: DownsampleTask (pooling pyramid), SkeletonTask (EDT),
CCLFacesTask (block CCL), MeshTask (marching-cubes count pass). Anything
else — or any member whose cutout clamps to a different shape — executes
solo within the same lease round.
"""

from __future__ import annotations

import random
import time
from collections import defaultdict
from typing import Optional

import numpy as np

from ..lib import Bbox
from ..observability import device as device_telemetry
from ..observability import journal as journal_mod
from ..observability import trace
from ..queues.filequeue import failure_reason, run_with_deadline


def _cutout_key(task):
  """Cache key for a prefetched downsample cutout download."""
  return (
    task.src_path, int(task.mip),
    tuple(int(v) for v in task.offset),
    tuple(int(v) for v in task.shape),
  )


def _range_sizes(tokens):
  """Contiguous-range composition of a lease round: member counts per
  shared RangeLease, largest first (classic tokens excluded). None when
  the round had no range members — the journal attr only appears for
  range-leased rounds, which is what replay.py mines."""
  from ..queues.ranges import RangeSub

  sizes = {}
  for tok in tokens:
    if isinstance(tok, RangeSub):
      sizes[id(tok.parent)] = sizes.get(id(tok.parent), 0) + 1
  return sorted(sizes.values(), reverse=True) if sizes else None


def _group_key(task, volmeta_cache):
  """Hashable device-stage signature, or None when the task must run solo.

  Tasks whose device stage depends only on (cutout shape, dtype, kernel
  params) batch together; the offset is the batch dimension. Keys embed
  the PREDICTED cutout shape so boundary tasks clamped along the same
  dataset faces still group, while ragged members fall out to solo."""
  from ..tasks.ccl import CCLFacesTask
  from ..tasks.image import DownsampleTask
  from ..tasks.mesh import MeshTask
  from ..tasks.skeleton import SkeletonTask

  def bounds_of(path, mip, fill_missing=False):
    key = (path, mip)
    if key not in volmeta_cache:
      from ..volume import Volume

      volmeta_cache[key] = Volume(
        path, mip=mip, fill_missing=fill_missing, bounded=False
      ).meta.bounds(mip)
    return volmeta_cache[key]

  if type(task) is DownsampleTask:
    from ..ops.pooling import _host_pool_active

    if _host_pool_active():
      # accelerator-less host: per-cutout native pooling IS the fast
      # path (same policy as the CCL native check below); an XLA-CPU
      # batch dispatch would be a ~9x pessimization
      return None
    bounds = bounds_of(task.src_path, task.mip, task.fill_missing)
    box = Bbox.intersection(
      Bbox(task.offset, task.offset + task.shape), bounds
    )
    if box.empty():
      return None
    if box != Bbox(task.offset, task.offset + task.shape):
      # clamped edge cutout (ISSUE 12): the paged pyramid batches it
      # with its full-shape siblings when the factor chain pages; chains
      # that must resolve against destination metadata (factor/num_mips
      # unset) stay solo — the handler can't predict their geometry here
      from ..ops.pooling import _normalize_factors
      from .paged import pages_compatible

      if task.factor is None or task.num_mips is None:
        return None
      if not pages_compatible(
        _normalize_factors(task.factor, int(task.num_mips))
      ):
        return None
    return (
      "downsample", task.src_path, int(task.mip),
      tuple(int(v) for v in task.shape),
      None if task.factor is None else tuple(int(v) for v in task.factor),
      task.num_mips, bool(task.sparse), bool(task.fill_missing),
      task.downsample_method, task.compress,
      bool(task.delete_black_uploads), int(task.background_color),
    )

  if type(task) is SkeletonTask:
    bounds = bounds_of(task.cloudpath, task.mip, task.fill_missing)
    core = Bbox.intersection(
      Bbox(task.offset, task.offset + task.shape), bounds
    )
    if core.empty():
      return None  # solo path no-ops it cheaply
    cutout = Bbox.intersection(Bbox(core.minpt, core.maxpt + 1), bounds)
    from ..ops.edt import _host_backend

    # paged EDT (ISSUE 12) runs every shape through one canonical-shape
    # signature, so shape need not partition the group on device hosts
    shape_part = (
      ("paged",) if _host_backend() == "device"
      else tuple(int(v) for v in cutout.size3())
    )
    return (
      "skeleton", task.cloudpath, int(task.mip),
      shape_part, bool(task.fill_missing),
    )

  if type(task) is CCLFacesTask:
    from ..ops.ccl import _ccl_backend

    if _ccl_backend() == "native":
      # CPU-only host: per-cutout native union-find IS the fast path
      # (same policy as ops.ccl.connected_components_batch)
      return None
    bounds = bounds_of(task.src_path, task.mip, task.fill_missing)
    cutout = Bbox.intersection(
      Bbox(task.offset, task.offset + task.shape + 1), bounds
    )
    if cutout.empty():
      return None
    from .paged import ccl_page_compatible

    # paged CCL (ISSUE 12): one page-batch signature covers ragged
    # cutouts, so shape only partitions when pages can't tile the tile
    shape_part = (
      ("paged",) if ccl_page_compatible()
      else tuple(int(v) for v in cutout.size3())
    )
    return (
      "ccl_faces", task.src_path, int(task.mip),
      shape_part,
      task.threshold_gte, task.threshold_lte,
      int(task.dust_threshold), bool(task.fill_missing),
    )

  if type(task) is MeshTask:
    # mesh cutouts need not share shapes: the count pass batches per
    # per-label mask bucket, which already spans tasks (see
    # _run_mesh_group); the kernel and resolution must agree though
    return ("mesh", task.layer_path, int(task.mip), task.mesher)

  return None


class LeaseBatcher:
  """Worker loop that leases up to ``batch_size`` tasks per round and
  runs compatible device stages as single mesh dispatches."""

  def __init__(
    self,
    queue,
    batch_size: int = 8,
    lease_seconds: float = 600,
    mesh=None,
    verbose: bool = False,
    timing: bool = False,
    task_deadline_seconds: Optional[float] = None,
    heartbeat_seconds: Optional[float] = None,
    drain_flag=None,
  ):
    self.queue = queue
    self.batch_size = int(batch_size)
    self.lease_seconds = lease_seconds
    self.mesh = mesh
    self.verbose = verbose
    # per-member wall-clock deadline for the solo/completion stages —
    # shares queues.filequeue.run_with_deadline with the solo poll loop
    self.task_deadline_seconds = task_deadline_seconds
    # lease renewal while a round executes (a K-member round holds K
    # leases across ONE long device dispatch — without renewal, short
    # --lease-sec would re-issue the whole round mid-flight)
    self.heartbeat_seconds = heartbeat_seconds
    # graceful preemption: finish the member in flight, release the rest
    self.drain_flag = drain_flag
    # --time equivalent for batched rounds: per-task stage timing makes
    # no sense when K tasks share one dispatch, so emit one JSON line
    # per lease ROUND instead (wall, members, dispatches delta)
    self.timing = timing
    self.stats = {
      "executed": 0, "batched": 0, "solo": 0, "failed": 0,
      "group_fallbacks": 0, "released": 0, "prefetched_rounds": 0,
      "prefetched_cutouts": 0,
      # ISSUE 6: rounds where the health plane's straggler flag made
      # this worker surrender/skip round-(i+1) pre-leasing
      "straggler_surrenders": 0, "straggler_prefetch_skips": 0,
      # ISSUE 12: members whose unstarted page ranges a flagged worker
      # shed back to the queue mid-campaign (healthy hosts re-lease them)
      "paged_splits": 0,
      # ISSUE 17: steal claims this worker filed while the queue looked
      # empty (the claimed holder's next heartbeat releases the tail)
      "steal_claims": 0,
      "dispatches": defaultdict(int),
    }
    # straggler-flag poll cache: (checked_at_monotonic, flagged)
    self._flag_cache = (0.0, False)
    self._completed_in_group = set()
    self._hb = None
    # next-round pipelining (ISSUE 3): while round i's device dispatch
    # and completions run, a background thread leases round i+1's
    # members and downloads their groupable cutouts, so the chip never
    # waits on the queue or the object store between rounds
    self._next_round = None   # cf.Future -> list[(task, lease_id)]
    self._img_cache = {}      # download-prefetch results, keyed by
                              # (src_path, mip, offset, shape)

  def _draining(self) -> bool:
    return self.drain_flag is not None and self.drain_flag.is_set()

  # how often a worker re-reads <journal>/health/flags.json (one small
  # object GET; anything the health checker wrote since last poll takes
  # effect within this many seconds)
  FLAG_POLL_SEC = 15.0

  def _straggler_flagged(self) -> bool:
    """True when the fleet health plane flagged THIS worker (ISSUE 6):
    `igneous fleet check` publishes a straggler report next to the
    journal, and a flagged worker stops pre-leasing round i+1 — queue
    depth goes to healthy workers instead of a lease this worker will
    be slow (or too dead) to serve."""
    j = journal_mod.get_active()
    if j is None:
      return False
    now = time.monotonic()
    checked_at, flagged = self._flag_cache
    if now - checked_at < self.FLAG_POLL_SEC:
      return flagged
    try:
      from ..observability import health

      flagged = j.worker_id in health.flagged_workers(j.cloudpath)
    except Exception:
      flagged = False
    self._flag_cache = (now, flagged)
    return flagged

  def _current_id(self, lease_id):
    """The member's CURRENT lease token (heartbeat renewals re-timestamp
    fq:// tokens) — and stop renewing it: every caller is about to
    delete, nack, or release the lease."""
    return self._hb.untrack(lease_id) if self._hb is not None else lease_id

  def _release_members(self, members):
    """Drain path: hand still-leased members straight back to the queue
    instead of letting their leases age out on a dead pod."""
    from .. import telemetry

    for _task, lease_id in members:
      try:
        self.queue.release(self._current_id(lease_id))
      except Exception:
        continue  # worst case the lease ages out, as before
      self.stats["released"] += 1
      telemetry.incr("drain.released")

  # -- poll loop ------------------------------------------------------------

  def poll(
    self,
    stop_fn=None,
    max_backoff_window: float = 30.0,
    task_budget: Optional[int] = None,
  ) -> int:
    """Lease K → group → dispatch → complete each lease independently.
    Same stop_fn/backoff contract as queues.filequeue.poll_loop.
    ``task_budget`` caps TOTAL executed tasks: the lease loop never takes
    more leases than the remaining budget, so ``--num-tasks N`` means N
    even when N < batch_size (stop_fn alone is only consulted between
    rounds and would overshoot by up to batch_size-1)."""
    from ..queues.heartbeat import LeaseHeartbeat

    # ONE heartbeat spans the whole poll loop, not one per round: round
    # i+1's pre-leased members must keep renewing WHILE round i executes,
    # or a round longer than lease_seconds would expire them and re-issue
    # the tasks to other workers — the duplicate-execution window the
    # heartbeats exist to close
    self._hb = LeaseHeartbeat(
      self.queue, self.lease_seconds, interval=self.heartbeat_seconds
    )
    self._hb.start()
    try:
      return self._poll_inner(stop_fn, max_backoff_window, task_budget)
    finally:
      self._hb.stop()
      self._hb = None

  def _poll_inner(self, stop_fn, max_backoff_window, task_budget) -> int:
    backoff = 1.0
    while True:
      if self._draining():
        self._surrender_prefetch()
        return self.stats["executed"]
      if stop_fn is not None and stop_fn(
        executed=self.stats["executed"], empty=False
      ):
        self._surrender_prefetch()
        return self.stats["executed"]
      cap = self.batch_size
      if task_budget is not None:
        cap = min(cap, task_budget - self.stats["executed"])
        if cap <= 0:
          self._surrender_prefetch()
          return self.stats["executed"]
      if self._next_round is not None and self._straggler_flagged():
        # flagged mid-flight: round i+1's pre-leased members go straight
        # back to the queue instead of waiting on this slow worker
        self._surrender_prefetch()
        self.stats["straggler_surrenders"] += 1
      members = self._take_prefetched()
      if len(members) > cap:
        # the budget shrank between prefetch and now: surplus goes back
        self._release_members(members[cap:])
        members = members[:cap]
      synced = []
      acquire = trace.span("lease.acquire", worker=True)
      with acquire:
        while len(members) < cap and not self._draining():
          got = self._lease_many(cap - len(members))
          if not got:
            break
          for leased in got:
            members.append(leased)
            self._hb.track(leased[1])
            synced.append(leased[1])
        if synced:
          # per-round queue-interaction cost: the workload miner folds
          # these into the round-overhead distribution the fleet
          # simulator replays, so batched campaigns simulate queue time,
          # not just compute. range_sizes (when present) records the
          # round's contiguous-range composition for range-lease replay.
          acquire.set(members=len(synced))
          sizes = _range_sizes(synced)
          if sizes:
            acquire.set(range_sizes=sizes)
        else:
          acquire.drop()  # an empty poll is no round's overhead
      if self._draining():
        # preempted between lease and dispatch: nothing ran, so every
        # member goes straight back (_release_members untracks each
        # lease from the heartbeat as it releases)
        self._release_members(members)
        return self.stats["executed"]
      if not members:
        if stop_fn is not None and stop_fn(
          executed=self.stats["executed"], empty=True
        ):
          return self.stats["executed"]
        if self._try_steal():
          # a claim is filed: the holder's next heartbeat releases the
          # unstarted tail back to the queue — re-poll soon, don't back
          # off, or the released tasks sit idle for the backoff window
          time.sleep(1.0 + random.random())
          continue
        time.sleep(backoff + random.random())
        backoff = min(backoff * 2, max_backoff_window)
        continue
      backoff = 1.0
      # pipeline the NEXT round while this one dispatches/completes; the
      # prefetch is fenced off every (path, mip) this round writes
      if len(members) == cap and (
        task_budget is None
        or task_budget - self.stats["executed"] - len(members) > 0
      ):
        if self._straggler_flagged():
          # health plane flagged this worker: run what we hold, but
          # don't pre-lease more — healthy workers take round i+1
          self.stats["straggler_prefetch_skips"] += 1
        else:
          next_cap = self.batch_size
          if task_budget is not None:
            next_cap = min(
              next_cap, task_budget - self.stats["executed"] - len(members)
            )
          from ..pipeline import shared_prefetch_pool

          self._next_round = shared_prefetch_pool().submit(
            self._prelease_and_prefetch, next_cap,
            self._round_write_set(members),
          )
      if self.timing:
        import json

        before = dict(self.stats, dispatches=dict(self.stats["dispatches"]))
        t0 = time.perf_counter()
        self.run_round(members)
        print(json.dumps({
          "round_members": len(members),
          "wall_s": round(time.perf_counter() - t0, 3),
          "executed": self.stats["executed"] - before["executed"],
          "failed": self.stats["failed"] - before["failed"],
          "dispatches": {
            k: v - before["dispatches"].get(k, 0)
            for k, v in self.stats["dispatches"].items()
            if v - before["dispatches"].get(k, 0)
          },
        }))
      else:
        self.run_round(members)
      # round boundary: the round's spans (one lease.round + K member
      # task spans) flush as one journal segment
      journal_mod.maybe_flush_active(event="round")

  def _try_steal(self) -> bool:
    """Idle-worker pull half of work stealing (ISSUE 17): the queue
    looks empty, but long-held range leases may still pin unstarted
    work — claim the biggest one so its holder's next heartbeat renewal
    releases the unstarted tail back to the pool. Opt-in
    (IGNEOUS_STEAL); queues without the protocol are skipped."""
    from ..analysis import knobs

    steal_claim = getattr(self.queue, "steal_claim", None)
    if steal_claim is None or not knobs.get_bool("IGNEOUS_STEAL"):
      return False
    try:
      seg = steal_claim()
    except Exception:
      return False
    if seg is None:
      return False
    self.stats["steal_claims"] += 1
    return True

  @staticmethod
  def _mark_started(lease_id):
    """Fence this member off work stealing: only UNSTARTED members are
    carved off a claimed range (queues/ranges.py). Classic string
    tokens have no mark and need none — stealing is range-only."""
    mark = getattr(lease_id, "mark_started", None)
    if mark is not None:
      mark()

  def _lease_many(self, n: int):
    """One queue interaction for up to ``n`` leases: the batched wire
    protocol (ISSUE 15) when the backend has it — fq:// segments arrive
    as RangeSub members sharing ONE underlying lease, which the round's
    delete/nack/release/renew calls consume natively — else the classic
    scalar lease loop."""
    lease_batch = getattr(self.queue, "lease_batch", None)
    if lease_batch is not None:
      return lease_batch(self.lease_seconds, max_tasks=n)
    out = []
    while len(out) < n and not self._draining():
      leased = self.queue.lease(self.lease_seconds)
      if leased is None:
        break
      out.append(leased)
    return out

  # -- next-round pipelining ------------------------------------------------

  def _take_prefetched(self):
    fut, self._next_round = self._next_round, None
    if fut is None:
      return []
    with trace.span("lease.prefetch_wait"):
      return fut.result()

  def _surrender_prefetch(self):
    """Drain/stop path: pre-leased members of a round that will never
    run go straight back to the queue."""
    try:
      self._release_members(self._take_prefetched())
    finally:
      self._img_cache.clear()

  def _round_write_set(self, members):
    """Conservative (path, mip) image-chunk write set for a round's
    members, or None when a member's writes are unknowable (an arbitrary
    task type may write any layer). Fences the next round's cutout
    prefetch off chunks this round is still producing."""
    from ..tasks.ccl import CCLFacesTask
    from ..tasks.image import TransferTask
    from ..tasks.mesh import MeshTask
    from ..tasks.skeleton import SkeletonTask

    writes = set()
    for task, _lease_id in members:
      if isinstance(task, TransferTask):  # DownsampleTask included
        if not task.skip_first:
          writes.add((task.dest_path, int(task.mip)))
        if task.skip_downsamples:
          continue
        if task.num_mips is None:
          return None  # pyramid depth resolves from dest metadata
        writes.update(
          (task.dest_path, int(task.mip) + m)
          for m in range(1, int(task.num_mips) + 1)
        )
      elif type(task) in (SkeletonTask, CCLFacesTask, MeshTask):
        # these write frag/scratch artifacts, never the image chunks a
        # downsample cutout prefetch reads
        continue
      else:
        return None
    return writes

  def _invalidate_cache(self, writes):
    """Drop prefetched cutouts whose (path, mip) a round wrote — a stale
    image must never feed a later round's dispatch. ``writes=None``
    (unknowable write set) drops everything. The shared chunk decode
    cache follows the same fence (its digest keys keep late readers
    correct regardless; this frees doomed entries at the round edge)."""
    from .. import chunk_cache

    if writes is None:
      self._img_cache.clear()
      chunk_cache.clear()
      return
    if not writes:
      return
    for ckey in [k for k in self._img_cache if (k[0], k[1]) in writes]:
      self._img_cache.pop(ckey, None)
    chunk_cache.invalidate_writes(writes)

  def _prelease_and_prefetch(self, cap: int, busy_writes=frozenset()):
    """Background half of the round pipeline: lease round i+1's members
    and download the cutouts its downsample groups will need, while
    round i owns the device. ``busy_writes`` is the running round's
    (path, mip) write set: cutouts intersecting it are NOT downloaded
    (their chunks are still changing under round i's uploads — the
    round's own fetch reads them fresh after the writes land), and stale
    cache leftovers matching it are dropped. Download failures are
    dropped silently — the round's own download retries and surfaces the
    real error."""
    with trace.span("lease.prefetch"):
      return self._prelease_and_prefetch_inner(cap, busy_writes)

  def _prelease_and_prefetch_inner(self, cap, busy_writes):
    members = []
    while len(members) < cap and not self._draining():
      got = self._lease_many(cap - len(members))
      if not got:
        break
      if self._draining():
        # the drain raced our leases: members the dying round just
        # released (or fresh tasks) must go straight back UNCOUNTED —
        # keeping them would double-account the same task as both a
        # round release and a surrendered prefetch
        for leased in got:
          try:
            self.queue.release(leased[1])
          except Exception:
            pass
        break
      for leased in got:
        members.append(leased)
        if self._hb is not None:
          # renew from the moment of pre-lease: round i may run longer
          # than lease_seconds, and an expired pre-lease re-delivers the
          # task to another worker while we still hold it
          self._hb.track(leased[1])
    if not members:
      return members
    self.stats["prefetched_rounds"] += 1
    self._invalidate_cache(busy_writes)
    # bound the cache: entries a round never consumed (handler fell back
    # solo, say) must not accumulate; insertion order evicts oldest
    while len(self._img_cache) > 2 * max(cap, 1):
      self._img_cache.pop(next(iter(self._img_cache)), None)
    volmeta_cache = {}
    vols = {}
    from .. import telemetry
    from ..volume import Volume

    for task, _lease_id in members:
      if self._draining():
        break
      try:
        key = _group_key(task, volmeta_cache)
      except Exception:
        continue
      if key is None or key[0] != "downsample":
        continue
      ckey = _cutout_key(task)
      if ckey in self._img_cache:
        continue
      if busy_writes is None or (ckey[0], ckey[1]) in busy_writes:
        continue  # round i is still writing this (path, mip)
      vkey = (task.src_path, int(task.mip), bool(task.fill_missing))
      try:
        if vkey not in vols:
          vols[vkey] = Volume(
            task.src_path, mip=task.mip, fill_missing=task.fill_missing
          )
        self._img_cache[ckey] = vols[vkey].download(
          Bbox(task.offset, task.offset + task.shape)
        )
        self.stats["prefetched_cutouts"] += 1
        telemetry.incr("pipeline.lease.prefetched_cutouts")
      except Exception:
        continue
    return members

  def run_round(self, members):
    """Execute one lease round: group, dispatch groups, solo the rest.

    All K leases are heartbeat-renewed for the duration of the round; a
    drain request releases every member not yet started (groups not yet
    dispatched, solo members not yet executing) back to the queue."""
    from ..queues.heartbeat import LeaseHeartbeat

    owns_hb = self._hb is None  # direct callers outside poll()
    if owns_hb:
      self._hb = LeaseHeartbeat(
        self.queue, self.lease_seconds, interval=self.heartbeat_seconds
      )
      self._hb.start()
    for _task, lease_id in members:
      self._hb.track(lease_id)  # idempotent for pre-leased members
    before_exec = self.stats["executed"]
    before_fail = self.stats["failed"]
    # worker-scoped span: one lease round (group dispatch + member
    # completions) under the process's own trace id
    round_span = trace.span("lease.round", worker=True, members=len(members))
    try:
      with round_span:
        try:
          self._run_round_inner(members)
        finally:
          round_span.set(
            executed=self.stats["executed"] - before_exec,
            failed=self.stats["failed"] - before_fail,
          )
    finally:
      # cutouts this round's writes made stale must never feed a later
      # round from the prefetch cache (a member re-leased after failure,
      # say, whose cutout lingered unconsumed)
      self._invalidate_cache(self._round_write_set(members))
      if owns_hb:
        self._hb.stop()
        self._hb = None

  def _run_round_inner(self, members):
    volmeta_cache = {}
    groups = defaultdict(list)
    solo = []
    for task, lease_id in members:
      try:
        key = _group_key(task, volmeta_cache)
      except Exception:
        key = None  # unreadable metadata: the solo path surfaces it
      if key is None:
        solo.append((task, lease_id))
      else:
        groups[key].append((task, lease_id))

    for key, group in groups.items():
      if self._draining():
        self._release_members(group)
        continue
      if len(group) == 1:
        solo.extend(group)
        continue
      handler = {
        "downsample": self._run_downsample_group,
        "skeleton": self._run_skeleton_group,
        "ccl_faces": self._run_ccl_group,
        "mesh": self._run_mesh_group,
      }[key[0]]
      self._completed_in_group = set()
      for _task, lease_id in group:
        self._mark_started(lease_id)  # group dispatch begins now
      try:
        handler(key, group)
      except Exception:
        # group-stage failure (one member's corrupt chunk poisoning the
        # shared download/dispatch, say): don't let it drag K-1 healthy
        # leases into recycling — rerun the incomplete members solo
        # within the same round, so only genuinely bad leases recycle.
        # Tasks are idempotent (at-least-once), so a member whose work
        # finished but whose completion raised is safe to rerun.
        if self.verbose:
          import traceback

          traceback.print_exc()
        self.stats["group_fallbacks"] += 1
        solo.extend(
          m for m in group if m[1] not in self._completed_in_group
        )

    for i, (task, lease_id) in enumerate(solo):
      if self._draining():
        self._release_members(solo[i:])
        return
      if self.verbose:
        print(f"Executing (solo) {task!r}")
      self._mark_started(lease_id)
      try:
        with trace.task_span(
          task, attempt=self._attempt_of(lease_id), mode="batch-solo"
        ):
          run_with_deadline(task.execute, self.task_deadline_seconds)
      except Exception as e:
        self._record_failure(lease_id, e)
        continue
      self.queue.delete(self._current_id(lease_id))
      self.stats["executed"] += 1
      self.stats["solo"] += 1
      # per-delivery fast-path eligibility (ISSUE 7): this delivery fell
      # off the batched device path (ragged shape, singleton group,
      # host-pool policy) — the ledger's ratio is the ragged-batching
      # roadmap item's baseline
      device_telemetry.LEDGER.record_fastpath(host=1)

  # -- completion plumbing --------------------------------------------------

  def _record_failure(self, lease_id, exc):
    """One bookkeeping path for every failed member — solo execution,
    group completion, deadline overrun: the reason is recorded with the
    task (queue.nack), so the batcher's group→solo degradation and the
    DLQ promotion share the same persisted evidence."""
    if self.verbose:
      import traceback

      traceback.print_exc()
    from .. import telemetry

    telemetry.incr("tasks.failed")
    self.stats["failed"] += 1
    if hasattr(self.queue, "nack"):
      self.queue.nack(self._current_id(lease_id), failure_reason(exc))

  def _complete(self, lease_id):
    self.queue.delete(self._current_id(lease_id))
    self.stats["executed"] += 1
    self.stats["batched"] += 1
    device_telemetry.LEDGER.record_fastpath(batched=1)
    # group membership tracks the ORIGINAL token (what handlers hold)
    self._completed_in_group.add(lease_id)

  def _attempt_of(self, lease_id):
    try:
      if hasattr(self.queue, "delivery_count"):
        return int(self.queue.delivery_count(lease_id))
    except Exception:
      pass
    return None

  def _finish_members(self, group, finish_one):
    """Run each member's host completion; a failure keeps that member's
    lease only."""
    with trace.span("lease.round.finish", members=len(group)):
      for idx, (task, lease_id) in enumerate(group):
        try:
          # the member's completion span: its share of the batched round
          # (the shared device dispatch is the round's own lease.round
          # span)
          with trace.task_span(
            task, attempt=self._attempt_of(lease_id), mode="batched"
          ):
            run_with_deadline(
              lambda: finish_one(idx, task), self.task_deadline_seconds
            )
        except Exception as e:
          self._record_failure(lease_id, e)
          continue
        self._complete(lease_id)

  # -- group handlers -------------------------------------------------------

  def _run_downsample_group(self, key, group):
    """K downsample cutouts → one ChunkExecutor pyramid dispatch for the
    full-shape members plus one paged-pyramid campaign for the clamped
    edge members (ISSUE 12: one compiled signature regardless of edge
    geometry); uploads go back through downsample_and_upload so chunk
    bytes match solo. Between paged rounds a straggler-flagged worker
    sheds members whose page ranges haven't started back to the queue,
    so idle hosts pick up the remainder of the campaign."""
    from ..ops import pooling
    from ..tasks.image import _resolve_factors, downsample_and_upload
    from ..volume import Volume
    from .batch_runner import _from_batch_layout, device_pyramid_batch
    from .executor import cached_chunk_executor, make_mesh

    t0 = group[0][0]
    src = Volume(t0.src_path, mip=t0.mip, fill_missing=t0.fill_missing)
    dest = Volume(
      t0.dest_path, mip=t0.mip, fill_missing=t0.fill_missing,
      delete_black_uploads=t0.delete_black_uploads,
      background_color=t0.background_color,
    )
    factors = _resolve_factors(dest, t0.mip, t0.shape, t0.num_mips, t0.factor)
    if not factors:
      # nothing to produce; solo semantics are a clean no-op per task
      for _task, lease_id in group:
        self._complete(lease_id)
      return
    method = pooling.method_for_layer(dest.layer_type, t0.downsample_method)
    bounds = src.meta.bounds(t0.mip)
    boxes = [
      Bbox.intersection(Bbox(t.offset, t.offset + t.shape), bounds)
      for t, _ in group
    ]
    nominal = tuple(int(v) for v in t0.shape)  # key-shared across members
    full_idx = [
      k for k, b in enumerate(boxes)
      if tuple(int(v) for v in b.size3()) == nominal
    ]
    ragged_idx = [k for k in range(len(boxes)) if k not in full_idx]

    def fetch(pair):
      k, task = pair
      img = self._img_cache.pop(_cutout_key(task), None)
      if img is not None and (
        tuple(int(v) for v in img.shape[:3])
        == tuple(int(v) for v in boxes[k].size3())
      ):
        return img
      return src.download(boxes[k])

    from ..pipeline import shared_prefetch_pool

    with trace.span("lease.round.fetch", members=len(group)):
      imgs = list(shared_prefetch_pool().map(
        fetch, list(enumerate(t for t, _ in group))
      ))
    mesh = self.mesh if self.mesh is not None else make_mesh()

    mips_out = None
    full_pos = {k: j for j, k in enumerate(full_idx)}
    pyramid = None
    ragged_pos = {k: j for j, k in enumerate(ragged_idx)}
    released = set()
    with trace.span("lease.round.dispatch", members=len(group)):
      if full_idx:
        is_u64 = method == "mode" and dest.dtype.itemsize == 8
        executor = cached_chunk_executor(
          mesh, factors=tuple(factors), method=method, sparse=t0.sparse,
          planes=2 if is_u64 else 1,
        )
        mips_out = device_pyramid_batch(
          executor, [imgs[k] for k in full_idx], is_u64
        )
        self.stats["dispatches"]["downsample"] += 1

      if ragged_idx:
        from .paged import PagedPyramid

        pyramid = PagedPyramid(
          [imgs[k] for k in ragged_idx], tuple(factors), len(factors),
          method=method, sparse=t0.sparse, mesh=mesh,
        )
        while pyramid.pending:
          if self._straggler_flagged():
            shed = pyramid.split_unstarted()
            if shed:
              self._release_members([group[ragged_idx[j]] for j in shed])
              self.stats["paged_splits"] += len(shed)
              for j in shed:
                k = ragged_idx[j]
                released.add(k)
                # the lease is back in the queue for a healthy worker: the
                # group-fallback path must not ALSO rerun it solo here
                self._completed_in_group.add(group[k][1])
          if not pyramid.pending:
            break
          pyramid.run_round()
          self.stats["dispatches"]["downsample_paged"] += 1

    to_finish = [m for k, m in enumerate(group) if k not in released]
    idx_map = [k for k in range(len(group)) if k not in released]

    def finish(j, task):
      k = idx_map[j]
      # the member's chunk encodes+puts thread on the shared pool; the
      # join keeps the completion contract (delete only after every
      # byte landed) inside the member's own deadline window
      from ..pipeline import SerialSink, config as pcfg, shared_encode_pool

      sink = (
        shared_encode_pool().ticket() if pcfg.use_threads() else SerialSink()
      )
      mips = (
        pyramid.result(ragged_pos[k]) if k in ragged_pos
        else [_from_batch_layout(np.asarray(m[full_pos[k]])) for m in mips_out]
      )
      downsample_and_upload(
        None, boxes[k], dest,
        task_shape=task.shape, mip=task.mip, num_mips=task.num_mips,
        factor=task.factor, sparse=task.sparse,
        method=task.downsample_method, compress=task.compress,
        _mips_out=mips,
        sink=sink,
      )
      sink.join()

    self._finish_members(to_finish, finish)

  def _run_skeleton_group(self, key, group):
    """K skeleton cutouts → one batched EDT dispatch; TEASAR and uploads
    run through SkeletonTask.execute(_prepared, _edt_field)."""
    from ..ops.edt import _host_backend, edt_batch
    from ..volume import Volume

    t0 = group[0][0]
    vol = Volume(
      t0.cloudpath, mip=t0.mip, fill_missing=t0.fill_missing, bounded=False
    )
    anis = tuple(float(v) for v in vol.resolution)

    def prep(task):
      return task.prepare_labels(Volume(
        t0.cloudpath, mip=t0.mip, fill_missing=task.fill_missing,
        bounded=False,
      ))

    from ..pipeline import shared_prefetch_pool

    with trace.span("lease.round.fetch", members=len(group)):
      preps = list(shared_prefetch_pool().map(prep, [t for t, _ in group]))

    live = [i for i, p in enumerate(preps) if p is not None]
    fields = {}
    with trace.span("lease.round.dispatch", members=len(group)):
      if live and _host_backend() == "device":
        # device hosts group ragged shapes under one key: the
        # paged EDT relabels every member into one canonical-shape page
        # batch, so the whole group rides a single compiled signature
        from .paged import paged_edt

        edts = paged_edt([preps[i][0] for i in live], anis, mesh=self.mesh)
        self.stats["dispatches"]["skeleton"] += 1
        fields = {i: f for i, f in zip(live, edts)}
      elif live:
        labels_batch = np.stack([preps[i][0] for i in live])
        # host backend: shapes partition the group key, so the stack is
        # rectangular; no executor pin — edt_batch's host fallback keeps
        # batched EDTs bit-identical to solo on accelerator-less hosts
        edts = edt_batch(labels_batch, anis, black_border=True,
                         executor=None)
        self.stats["dispatches"]["skeleton"] += 1
        fields = {i: f for i, f in zip(live, edts)}

    def finish(k, task):
      if preps[k] is None:
        return  # empty core: solo execute() is the same clean no-op
      task.execute(_prepared=preps[k], _edt_field=fields[k])

    self._finish_members(group, finish)

  def _run_ccl_group(self, key, group):
    """K CCL cutouts → one batched block-CCL dispatch; face planes are
    stored by the same helpers CCLFacesTask.execute uses."""
    from ..ops.ccl import _batch_executor, connected_components_batch
    from ..storage import CloudFiles
    from ..tasks.ccl import (
      _offset_components,
      _prep_ccl_image,
      ccl_scratch_path,
      store_ccl_faces,
    )

    t0 = group[0][0]
    files = CloudFiles(t0.src_path)
    scratch = ccl_scratch_path(t0.src_path, t0.mip)

    def prep(task):
      return _prep_ccl_image(
        task.src_path, task.mip, task.shape, task.offset,
        task.fill_missing, task.threshold_gte, task.threshold_lte,
        task.dust_threshold,
      )

    from ..pipeline import shared_prefetch_pool

    with trace.span("lease.round.fetch", members=len(group)):
      preps = list(shared_prefetch_pool().map(prep, [t for t, _ in group]))

    from .paged import ccl_page_compatible

    with trace.span("lease.round.dispatch", members=len(group)):
      if ccl_page_compatible():
        # page-compatible tile: ragged cutouts share the group key,
        # and the paged CCL runs them all through one
        # fixed-page-batch signature
        from .paged import paged_ccl

        comps = paged_ccl([p[0] for p in preps], 6, mesh=self.mesh)
      else:
        imgs = np.stack([p[0] for p in preps])
        comps = connected_components_batch(
          imgs, executor=_batch_executor(6, mesh=self.mesh)
        )
    self.stats["dispatches"]["ccl_faces"] += 1

    def finish(k, task):
      _img, cutout, core = preps[k]
      cc = _offset_components(comps[k], task.task_num, task.shape)
      store_ccl_faces(cc, cutout, core, task.task_num, files, scratch)

    self._finish_members(group, finish)

  def _run_mesh_group(self, key, group):
    """K mesh cutouts → the marching-cubes count pass batches across ALL
    tasks' labels per mask-shape bucket (one dispatch per bucket instead
    of per task); emit/weld/simplify/upload stay per task."""
    from ..tasks.mesh import execute_mesh_tasks_batched

    with trace.span("lease.round.dispatch", members=len(group)):
      dispatches = execute_mesh_tasks_batched(
        [t for t, _ in group], mesh=self.mesh,
      )
    self.stats["dispatches"]["mesh"] += dispatches

    def finish(k, task):
      if getattr(task, "_batch_error", None) is not None:
        err = task._batch_error
        task._batch_error = None
        raise err

    self._finish_members(group, finish)


def poll_batched(
  queue,
  batch_size: int = 8,
  lease_seconds: float = 600,
  verbose: bool = False,
  stop_fn=None,
  max_backoff_window: float = 30.0,
  mesh=None,
  task_budget: Optional[int] = None,
  timing: bool = False,
  task_deadline_seconds: Optional[float] = None,
  heartbeat_seconds: Optional[float] = None,
  drain_flag=None,
):
  """Functional entry point mirroring queues.filequeue.poll_loop."""
  batcher = LeaseBatcher(
    queue, batch_size=batch_size, lease_seconds=lease_seconds,
    mesh=mesh, verbose=verbose, timing=timing,
    task_deadline_seconds=task_deadline_seconds,
    heartbeat_seconds=heartbeat_seconds, drain_flag=drain_flag,
  )
  executed = batcher.poll(
    stop_fn=stop_fn, max_backoff_window=max_backoff_window,
    task_budget=task_budget,
  )
  return executed, batcher.stats
