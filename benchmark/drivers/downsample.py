"""Downsample campaign through the worker loop of ``igneous execute --batch K``.

Set-up:
- the source layer, made by the configuration's generator from the seed
  and written through the program's ``Volume.upload``; it holds the
  traffic's ``task_grid`` of tasks, two rounds' worth or more, and is
  cached per (configuration, size, seed);
- the ``DownsampleTask``s that ``create_downsampling_tasks`` plans with
  the configuration's settings, checked against the task shape and mip
  count the configuration states;
- an ``fq://`` queue of passes over those tasks in planning order, in
  one segment, so that the loop leases them in that order and no round
  shares a chunk with the round before it or the one after it;
- one warm-up round of the first round's tasks through the same loop,
  which compiles (or loads) every program the window runs; its output is
  then deleted, so that every stored mip the check reads was written in
  the window.

Window: ``parallel.lease_batcher.poll_batched`` over a ``QueueProbe``
that leases nothing once ``seconds`` have passed; the window closes when
the loop has acknowledged the last task it leased. In a campaign every
task reads chunks nobody decoded before. Here a round's chunks come back
two rounds later, so the source layer is dropped from the decoded-chunk
cache at every lease and acknowledgement, and the window counts the
cache's hits, which the check holds to 0.
"""

from __future__ import annotations

import concurrent.futures as cf
import gc
import math
import os
import shutil
import time

import numpy as np

from benchmark import reference
from benchmark.probes import (
  CallProbes, QueueProbe, annotation, counter, ledger_totals, timer_seconds,
)
from benchmark.source import cached_layer

PYRAMID_KERNEL = "pooling.pyramid[{}]"
# above any rate a downsample worker reaches: the queue outlasts the window
MAX_VOX_PER_S_CHIP = 4e9
# source layers kept on disk, the newest first: a check's second set
# reuses the seeds of its first
KEEP_LAYERS = 8


class Campaign:
  def __init__(self, ctx):
    self.ctx = ctx
    self.cfg = ctx.config
    self.traffic = ctx.traffic
    self.grid = [int(v) for v in self.traffic["task_grid"]]
    self.batch = int(self.traffic["batch"])
    self.task_shape = [int(v) for v in self.cfg["task_shape"]]
    self.factor = [int(v) for v in self.cfg["factor"]]
    self.mips = int(self.cfg["task_mips"])
    self.n_tasks = int(np.prod(self.grid))
    self.run_dir = os.path.join(ctx.cache_dir, "run", ctx.cell["name"])
    self.layer = None
    self.tasks = []
    self.acked_tasks = []
    self.probes = None

  @property
  def method(self) -> str:
    m = self.cfg["downsample_method"]
    if m != "auto":
      return m
    return "mode" if self.cfg["layer_type"] == "segmentation" else "average"

  @property
  def task_voxels(self) -> int:
    return int(np.prod(self.task_shape))

  def kernel_bytes_per_dispatch(self) -> int:
    from benchmark.roofline import pyramid_bytes

    itemsize = np.dtype(self.cfg["data_type"]).itemsize
    return self.batch * pyramid_bytes(
      self.task_shape, self.factor, self.mips, itemsize
    )

  # -- set-up ---------------------------------------------------------------

  def setup(self) -> dict:
    split = {}
    t0 = time.perf_counter()
    self.layer, built = self._source()
    split["generate_s"] = time.perf_counter() - t0
    split["generated"] = built
    t0 = time.perf_counter()
    self._plan()
    split["plan_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    self._warm_up()
    split["warmup_s"] = time.perf_counter() - t0
    return split

  def _source(self):
    volume = [s * g for s, g in zip(self.task_shape, self.grid)]
    key = "{}_{}_{}".format(
      self.ctx.config_name, "x".join(map(str, volume)), self.ctx.seed
    )
    gen = self.ctx.spec.generator(self.cfg["generator"])
    cfg = self.cfg

    def build(layer_dir):
      from igneous_tpu.pipeline import shared_encode_pool
      from igneous_tpu.volume import Volume

      arr = gen.generate(tuple(volume), self.ctx.seed)
      info = Volume.create_new_info(
        num_channels=1, layer_type=cfg["layer_type"],
        data_type=cfg["data_type"], encoding=cfg["encoding"],
        resolution=cfg["resolution"], voxel_offset=(0, 0, 0),
        volume_size=volume, chunk_size=cfg["chunk_size"],
      )
      vol = Volume.create(f"file://{layer_dir}", info)
      if cfg.get("encoding_level") is not None:
        vol.meta.set_encoding(0, None, cfg["encoding_level"])
        vol.commit_info()
      # chunks encode in parallel on the program's encode pool
      ticket = shared_encode_pool().ticket()
      vol.upload(vol.meta.bounds(0), arr, mip=0,
                 compress=cfg["source_compress"] or None, sink=ticket)
      ticket.join()

    return cached_layer(os.path.join(self.ctx.cache_dir, "src"), key, build,
                        KEEP_LAYERS)

  def _plan(self) -> None:
    from igneous_tpu import task_creation as tc

    cfg = self.cfg
    self.tasks = list(tc.create_downsampling_tasks(
      f"file://{self.layer}", mip=0, num_mips=cfg["num_mips"],
      factor=tuple(self.factor), memory_target=cfg["memory_target"],
      compress=cfg["compress"], downsample_method=cfg["downsample_method"],
    ))
    shapes = {tuple(int(v) for v in t.shape) for t in self.tasks}
    mips = {int(t.num_mips) for t in self.tasks}
    if (len(self.tasks) != self.n_tasks or shapes != {tuple(self.task_shape)}
        or mips != {self.mips}):
      raise RuntimeError(
        f"planner gave {len(self.tasks)} tasks of shapes {sorted(shapes)} "
        f"with mips {sorted(mips)}; the configuration states "
        f"{self.n_tasks} x {self.task_shape} with {self.mips} mips"
      )
    if self.n_tasks % self.batch or self.n_tasks < 2 * self.batch:
      raise ValueError("task_grid must hold two or more whole rounds")
    shutil.rmtree(self.run_dir, ignore_errors=True)
    os.makedirs(self.run_dir)

  def _queue(self, name: str, tasks: list):
    """An ``fq://`` queue holding ``tasks`` in one segment: the queue
    leases a segment's tasks in order."""
    from igneous_tpu.queues import TaskQueue
    from igneous_tpu.queues.filequeue import DEFAULT_SEG_TASKS

    if len(tasks) > DEFAULT_SEG_TASKS:
      raise ValueError(f"{len(tasks)} tasks do not fit one queue segment")
    q = TaskQueue("fq://" + os.path.join(self.run_dir, name))
    q.insert_batch(tasks)
    return q

  def _mesh(self):
    from igneous_tpu.parallel.executor import make_mesh

    return make_mesh(self.ctx.chips)

  def _poll(self, probe):
    from igneous_tpu.parallel.lease_batcher import poll_batched

    return poll_batched(
      probe, batch_size=self.batch, mesh=self._mesh(),
      stop_fn=lambda executed, empty: empty,
    )

  def _warm_up(self) -> None:
    probe = QueueProbe(self._queue("warm", self.tasks[:self.batch]),
                       lambda t: 0)
    executed, stats = self._poll(probe)
    if executed != self.batch or stats["failed"] or not stats["batched"]:
      raise RuntimeError(f"warm-up round: {executed} executed, stats {stats}")
    self._drop_outputs()

  def _drop_outputs(self) -> None:
    info = reference.read_info(self.layer)
    for scale in info["scales"][1:]:
      shutil.rmtree(os.path.join(self.layer, scale["key"]),
                    ignore_errors=True)

  def _passes(self, seconds: float) -> int:
    """Passes over the source that outlast the window, as many as one
    queue segment holds."""
    from igneous_tpu.queues.filequeue import DEFAULT_SEG_TASKS

    rate = MAX_VOX_PER_S_CHIP * self.ctx.chips
    need = math.ceil(seconds * rate / (self.n_tasks * self.task_voxels)) + 2
    return min(need, DEFAULT_SEG_TASKS // self.n_tasks)

  # -- window ---------------------------------------------------------------

  def window(self, seconds: float, traced: bool) -> dict:
    from igneous_tpu import chunk_cache

    queue = self._queue("window", self.tasks * self._passes(seconds))
    src = f"file://{self.layer}"
    probe = QueueProbe(
      queue, lambda t: int(np.prod([int(v) for v in t.shape])),
      on_change=lambda: chunk_cache.invalidate(src, 0), annotate=traced,
    )
    if traced:
      self.probes = CallProbes(src).install()
    before = ledger_totals()
    enc0 = timer_seconds("pipeline.encode_upload.s")
    hits0 = counter("chunk_cache.hits")
    try:
      with annotation("window", traced):
        t_open = time.perf_counter()
        probe.deadline = t_open + seconds
        self._poll(probe)
        t_close = time.perf_counter()
    finally:
      if self.probes is not None:
        self.probes.uninstall()
    after = ledger_totals()
    kernel = PYRAMID_KERNEL.format(self.method)
    self.acked_tasks = list(probe.acked_tasks)
    return {
      "window_s": t_close - t_open,
      "voxels": probe.acked_voxels,
      "attempted": probe.leased,
      "acked": probe.acked,
      "failed": probe.leased - probe.acked,
      "queue_s": probe.seconds,
      "dispatches": after["executes"].get(kernel, 0)
      - before["executes"].get(kernel, 0),
      "compiles": after["compiles"] - before["compiles"],
      "h2d_bytes": after["h2d_bytes"] - before["h2d_bytes"],
      "encode_upload_s": timer_seconds("pipeline.encode_upload.s") - enc0,
      "src_cache_hits": counter("chunk_cache.hits") - hits0,
    }

  def release(self) -> None:
    from igneous_tpu import chunk_cache

    chunk_cache.clear()
    gc.collect()

  # -- correctness ----------------------------------------------------------

  def plant_control(self) -> None:
    """The control: the reference, computed in the precision below the
    configuration's (``reference.pyramid``'s "low"), stores every task's
    mips where the program would."""
    from igneous_tpu.lib import Bbox
    from igneous_tpu.volume import Volume

    factors = [self.factor] * self.mips
    info = reference.read_info(self.layer)
    for task in self.tasks:
      lo = [int(v) for v in task.offset]
      hi = [a + s for a, s in zip(lo, self.task_shape)]
      with cf.ThreadPoolExecutor(reference.THREADS) as pool:
        src = reference.read_cutout(self.layer, info, 0, lo, hi, pool)
        mips = reference.pyramid_blocked(src, factors, self.method, "low",
                                         pool)
      div = np.ones(3, dtype=np.int64)
      for m, arr in enumerate(mips, start=1):
        div = div * np.asarray(self.factor)
        mlo = [a // int(d) for a, d in zip(lo, div)]
        vol = Volume(f"file://{self.layer}", mip=m)
        box = Bbox(mlo, [a + s for a, s in zip(mlo, arr.shape)])
        box = Bbox.intersection(box, vol.meta.bounds(m))
        sl = tuple(slice(0, int(s)) for s in box.size3())
        vol.upload(box, arr[sl], mip=m, compress=task.compress)
    self.acked_tasks = list(self.tasks)

  def check(self) -> dict:
    """Every mip of every task of one round the window acknowledged,
    against the reference. The round is drawn from the seed among those
    acknowledged, so that the check stays shorter than the window."""
    factors = [self.factor] * self.mips
    round_of = {self._offset(t): i // self.batch
                for i, t in enumerate(self.tasks)}
    done = {self._offset(t) for t in self.acked_tasks}
    rounds = sorted({round_of[o] for o in done})
    pick = []
    if rounds:
      chosen = rounds[np.random.default_rng(self.ctx.seed).integers(
        len(rounds))]
      pick = sorted(o for o in done if round_of[o] == chosen)
    mismatched = compared = missing = 0
    for offset in pick:
      mm, cc, ms = reference.compare_task(
        self.layer, offset, self.task_shape, factors, self.method
      )
      mismatched += mm
      compared += cc
      missing += ms
    return {
      "tasks_compared": len(pick),
      "voxels_compared": compared,
      "mismatched_voxels": mismatched,
      "missing_chunks": missing,
    }

  @staticmethod
  def _offset(task) -> tuple:
    return tuple(int(v) for v in task.offset)

  def close(self) -> None:
    shutil.rmtree(self.run_dir, ignore_errors=True)
