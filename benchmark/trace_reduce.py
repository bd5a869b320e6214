"""Profiler trace -> device busy time, kernel device time, idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing but
``jax.profiler.ProfileData``:

- device planes are the ``/device:<accelerator>:<n>`` planes; an operation
  is an event on their ``XLA Ops`` line, and a compiled program an event
  on their ``XLA Modules`` line;
- host spans are the harness's own ``jax.profiler.TraceAnnotation``
  events, named ``bench.<what>``, on the ``/host:CPU`` plane;
- the window is the ``bench.window`` host span.

Busy time is the union of a device's operation intervals inside the
window; idle share is 1 minus busy over the window, averaged over the
devices. Each idle gap (no operation on any device) is attributed to the
harness span that overlaps it most, or to ``host.other``.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
SPAN_PREFIX = "bench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

# gaps shorter than this lie between back-to-back operations
MIN_GAP_NS = 10_000

Interval = Tuple[int, int]


def find_xplane(logdir: str) -> Optional[str]:
  paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True)
  return max(paths, key=os.path.getmtime) if paths else None


def _is_device_plane(name: str) -> bool:
  return name.startswith("/device:") and not name.startswith("/device:CPU")


def _op_name(name: str) -> str:
  """``%fusion.2 = u8[...] fusion(...), ...`` -> ``fusion.2``."""
  return name.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
  """``jit_foo(123)`` -> ``jit_foo``: the id differs per compilation."""
  return re.sub(r"\(\d+\)$", "", name)


def _clip(iv: Interval, lo: int, hi: int) -> Optional[Interval]:
  a, b = max(iv[0], lo), min(iv[1], hi)
  return (a, b) if b > a else None


def union(intervals: List[Interval]) -> List[Interval]:
  out: List[list] = []
  for a, b in sorted(intervals):
    if out and a <= out[-1][1]:
      out[-1][1] = max(out[-1][1], b)
    else:
      out.append([a, b])
  return [(a, b) for a, b in out]


def _total(intervals: List[Interval]) -> int:
  return sum(b - a for a, b in intervals)


def _overlap(a: Interval, b: Interval) -> int:
  return max(0, min(a[1], b[1]) - max(a[0], b[0]))


def _events(line):
  for ev in line.events:
    start = int(ev.start_ns)
    yield ev.name, start, start + int(ev.duration_ns)


def reduce_trace(path: str) -> dict:
  """Reduce one trace file; see the module docstring."""
  from jax.profiler import ProfileData

  return reduce_planes(ProfileData.from_file(path).planes)


def reduce_planes(planes) -> dict:
  """``planes``: objects with ``name`` and ``lines``; lines with ``name``
  and ``events``; events with ``name``, ``start_ns``, ``duration_ns``."""
  spans: List[Tuple[str, int, int]] = []
  devices = []
  for plane in planes:
    if plane.name.startswith("/host:"):
      for line in plane.lines:
        for name, a, b in _events(line):
          if name.startswith(SPAN_PREFIX):
            spans.append((name, a, b))
    elif _is_device_plane(plane.name):
      lines = {line.name: line for line in plane.lines}
      if OPS_LINE not in lines:
        continue
      devices.append((
        plane.name,
        list(_events(lines[OPS_LINE])),
        list(_events(lines[MODULES_LINE])) if MODULES_LINE in lines else [],
      ))
  windows = [(a, b) for name, a, b in spans if name == WINDOW]
  if windows:
    lo, hi = max(windows, key=lambda w: w[1] - w[0])
  else:
    ends = [b for _, _, b in spans] + [
      b for _, ops, _ in devices for _, _, b in ops
    ]
    starts = [a for _, a, _ in spans] + [
      a for _, ops, _ in devices for _, a, _ in ops
    ]
    lo, hi = (min(starts), max(ends)) if starts else (0, 0)
  window_ns = hi - lo
  host = [(n, a, b) for n, a, b in spans if n != WINDOW]

  busy_ns = []
  all_busy: List[Interval] = []
  ops_s: Dict[str, float] = defaultdict(float)
  modules_s: Dict[str, float] = defaultdict(float)
  module_events: List[Tuple[str, int, int]] = []
  for _name, ops, modules in devices:
    clipped = []
    for op, a, b in ops:
      iv = _clip((a, b), lo, hi)
      if iv is not None:
        clipped.append(iv)
        ops_s[_op_name(op)] += (iv[1] - iv[0]) / 1e9
    u = union(clipped)
    busy_ns.append(_total(u))
    all_busy.extend(u)
    for mod, a, b in modules:
      iv = _clip((a, b), lo, hi)
      if iv is not None:
        modules_s[_module_name(mod)] += (iv[1] - iv[0]) / 1e9
        module_events.append((_module_name(mod), iv[0], iv[1]))

  gaps = []
  cursor = lo
  for a, b in union(all_busy) + [(hi, hi)]:
    if a - cursor >= MIN_GAP_NS:
      gaps.append((cursor, a))
    cursor = max(cursor, b)
  idle_gaps = []
  for gap in gaps:
    best, label = 0, "host.other"
    for name, a, b in host:
      ov = _overlap(gap, (a, b))
      if ov > best:
        best, label = ov, name[len(SPAN_PREFIX):]
    idle_gaps.append((label, (gap[1] - gap[0]) / 1e9))

  return {
    "window_s": window_ns / 1e9,
    "devices": len(devices),
    "busy_s": (sum(busy_ns) / len(busy_ns) / 1e9) if busy_ns else 0.0,
    "ops_s": dict(ops_s),
    "modules_s": dict(modules_s),
    "module_events": module_events,
    "host_spans": host,
    "idle_gaps": sorted(idle_gaps, key=lambda g: -g[1]),
  }


def modules_under(reduced: dict, span: str) -> Dict[str, float]:
  """Device seconds, summed over devices, of every compiled program that
  ran at least once inside a ``bench.<span>`` host span: the programs a
  harness call site dispatched, found by time and not by name."""
  marks = [(a, b) for n, a, b in reduced["host_spans"]
           if n == SPAN_PREFIX + span]
  names = {
    mod for mod, a, b in reduced["module_events"]
    if any(_overlap((a, b), m) > 0 for m in marks)
  }
  return {n: s for n, s in reduced["modules_s"].items() if n in names}


def breakdown(reduced: dict, top: int = 10) -> dict:
  """The result line's ``breakdown``: the device operations that took
  most time and the longest idle gaps, each as ``[name, seconds]``."""
  ops = sorted(reduced["ops_s"].items(), key=lambda kv: -kv[1])[:top]
  return {
    "device_ops": [[name, sec] for name, sec in ops],
    "idle_gaps": [[name, sec] for name, sec in reduced["idle_gaps"][:top]],
  }
