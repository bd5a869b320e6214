"""Profiler trace -> the program's own spans: seconds per span, voxels
read, device idle by worker-loop phase, a kernel's device time by name.

The program marks its work with ``jax.profiler`` annotations named
``igneous.<span>`` (host events on the ``/host:CPU`` plane, whatever the
trace sampling), so this module needs no hook in the program, only the
traced run's ``.xplane.pb``. It is found from the cell's name
(``benchmark/.cache/run/<cell>.trace``) and read once per file.

Everything is clipped to the ``bench.window`` span:

- ``seconds``: per span name, the clipped seconds summed over threads;
- ``queue_s``: the ``queue.*`` spans' seconds, each thread's union (a
  nack that releases counts once);
- ``download_voxels``: the ``voxels`` stat of every ``volume.download``,
  times the share of the span inside the window;
- ``idle_s``: the device's idle time (the window less the union of its
  operation intervals, as ``trace_reduce`` computes busy time), mean
  over devices, by the worker loop's phase at each idle instant: the
  innermost loop span open then (``LOOP_PHASES``), or ``unattributed``;
- ``modules_s``: device seconds per compiled program, summed over
  devices; ``executes``: ``device.execute`` spans per ``kernel`` stat.

A trace from a program without these spans yields empty tables, and the
metrics that read them report nothing.
"""

from __future__ import annotations

import os
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from benchmark import trace_reduce

PREFIX = "igneous."
QUEUE = "queue."
DOWNLOAD = "volume.download"
EXECUTE = "device.execute"
# the worker loop's spans (``parallel/lease_batcher.py``) and the phase
# each one's idle time belongs to
LOOP_PHASES = {
  "lease.acquire": "acquire",
  "lease.prefetch_wait": "input_wait",
  "lease.round.fetch": "input_wait",
  "lease.round.dispatch": "dispatch",
  "lease.round.finish": "finish",
}
UNATTRIBUTED = "unattributed"
PHASES = ("input_wait", "finish", "dispatch", "acquire", UNATTRIBUTED)

Span = Tuple[object, str, int, int, dict]  # thread, name, start, end, stats

_MEMO: Dict[tuple, dict] = {}


def trace_dir(bench_dir: str, cell: str) -> str:
  return os.path.join(bench_dir, ".cache", "run", cell + ".trace")


def for_run(ctx: dict, reader_file: str) -> Optional[dict]:
  """The reduction of the traced run a metric reader is handed, or None
  on an untraced run. ``reader_file`` is the reader's ``__file__``: the
  trace lies under the benchmark directory that holds the reader."""
  if ctx.get("trace") is None:
    return None
  bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(reader_file)))
  path = trace_reduce.find_xplane(trace_dir(bench_dir, ctx["cell"]["name"]))
  return reduce_file(path) if path else None


def reduce_file(path: str) -> dict:
  key = (path, os.path.getmtime(path), os.path.getsize(path))
  if key not in _MEMO:
    from jax.profiler import ProfileData

    _MEMO.clear()
    _MEMO[key] = reduce_planes(ProfileData.from_file(path).planes)
  return _MEMO[key]


def _stats(ev) -> dict:
  return dict(getattr(ev, "stats", None) or ())


def _read(planes):
  spans: List[Span] = []
  windows = []
  devices = []
  for plane in planes:
    if plane.name.startswith("/host:"):
      for i, line in enumerate(plane.lines):
        for ev in line.events:
          name = ev.name
          if name.startswith(PREFIX):
            a = int(ev.start_ns)
            spans.append(((plane.name, i), name[len(PREFIX):], a,
                          a + int(ev.duration_ns), _stats(ev)))
          elif name == trace_reduce.WINDOW:
            a = int(ev.start_ns)
            windows.append((a, a + int(ev.duration_ns)))
    elif trace_reduce._is_device_plane(plane.name):
      lines = {line.name: line for line in plane.lines}
      if trace_reduce.OPS_LINE not in lines:
        continue
      modules = lines.get(trace_reduce.MODULES_LINE)
      devices.append((
        list(trace_reduce._events(lines[trace_reduce.OPS_LINE])),
        list(trace_reduce._events(modules)) if modules is not None else [],
      ))
  return spans, windows, devices


def _window(spans, windows, devices) -> Tuple[int, int]:
  if windows:
    return max(windows, key=lambda w: w[1] - w[0])
  starts = [s[2] for s in spans] + [a for ops, _ in devices for _, a, _ in ops]
  ends = [s[3] for s in spans] + [b for ops, _ in devices for _, _, b in ops]
  return (min(starts), max(ends)) if starts else (0, 0)


def _idle(busy: List[Tuple[int, int]], lo: int, hi: int):
  out, cursor = [], lo
  for a, b in busy:
    if a > cursor:
      out.append((cursor, a))
    cursor = max(cursor, b)
  if hi > cursor:
    out.append((cursor, hi))
  return out


def attribute_idle(idle: List[Tuple[int, int]], loop: List[tuple],
                   lo: int, hi: int) -> Dict[str, int]:
  """Nanoseconds of ``idle`` (sorted, disjoint) per phase. ``loop`` holds
  ``(start, end, phase)`` intervals; each idle instant goes to the
  innermost one open then (the latest started), or to ``unattributed``."""
  cuts = sorted({lo, hi} | {t for a, b, _ in loop for t in (a, b)
                            if lo < t < hi})
  out: Dict[str, int] = defaultdict(int)
  j = 0
  for p, q in zip(cuts, cuts[1:]):
    open_ = [(a, b, ph) for a, b, ph in loop if a <= p and q <= b]
    phase = max(open_, key=lambda s: (s[0], -s[1]))[2] if open_ \
        else UNATTRIBUTED
    while j < len(idle) and idle[j][1] <= p:
      j += 1
    k = j
    while k < len(idle) and idle[k][0] < q:
      out[phase] += max(0, min(idle[k][1], q) - max(idle[k][0], p))
      k += 1
  return dict(out)


def reduce_planes(planes) -> dict:
  """``planes`` as ``trace_reduce.reduce_planes`` takes them; host events
  may carry ``stats``, (key, value) pairs."""
  spans, windows, devices = _read(planes)
  lo, hi = _window(spans, windows, devices)
  seconds: Dict[str, float] = defaultdict(float)
  queue_by_thread = defaultdict(list)
  voxels = 0.0
  loop = []
  executes: Dict[str, int] = defaultdict(int)
  for thread, name, a, b, stats in spans:
    iv = trace_reduce._clip((a, b), lo, hi)
    if iv is None:
      continue
    seconds[name] += (iv[1] - iv[0]) / 1e9
    if name.startswith(QUEUE):
      queue_by_thread[thread].append(iv)
    elif name == DOWNLOAD and b > a:
      voxels += float(stats.get("voxels", 0)) * (iv[1] - iv[0]) / (b - a)
    elif name in LOOP_PHASES:
      loop.append((iv[0], iv[1], LOOP_PHASES[name]))
    elif name == EXECUTE and "kernel" in stats:
      executes[str(stats["kernel"])] += 1

  idle_ns: Dict[str, float] = defaultdict(float)
  modules_s: Dict[str, float] = defaultdict(float)
  for ops, modules in devices:
    busy = trace_reduce.union([iv for iv in (
      trace_reduce._clip((a, b), lo, hi) for _, a, b in ops) if iv])
    for phase, ns in attribute_idle(_idle(busy, lo, hi), loop,
                                    lo, hi).items():
      idle_ns[phase] += ns / len(devices)
    for mod, a, b in modules:
      iv = trace_reduce._clip((a, b), lo, hi)
      if iv is not None:
        modules_s[trace_reduce._module_name(mod)] += (iv[1] - iv[0]) / 1e9

  return {
    "window_s": (hi - lo) / 1e9,
    "devices": len(devices),
    "seconds": dict(seconds),
    "queue_spans": sum(len(v) for v in queue_by_thread.values()),
    "queue_s": sum(trace_reduce._total(trace_reduce.union(v))
                   for v in queue_by_thread.values()) / 1e9,
    "download_voxels": voxels,
    "loop_spans": len(loop),
    "idle_s": {p: idle_ns.get(p, 0.0) / 1e9 for p in PHASES},
    "modules_s": dict(modules_s),
    "executes": dict(executes),
  }


def seconds_per_gvox(reduced: Optional[dict], span: str) -> Optional[float]:
  """Seconds in ``span`` per billion voxels ``volume.download`` read."""
  if reduced is None or reduced["download_voxels"] <= 0 \
     or span not in reduced["seconds"]:
    return None
  return reduced["seconds"][span] / (reduced["download_voxels"] / 1e9)


def idle_share(reduced: Optional[dict], *phases: str) -> Optional[float]:
  """Percent of the window the device sat idle in ``phases``; None
  without a device plane or without the loop's spans."""
  if reduced is None or not reduced["devices"] or not reduced["loop_spans"] \
     or reduced["window_s"] <= 0:
    return None
  return 100.0 * sum(reduced["idle_s"][p] for p in phases) \
      / reduced["window_s"]
