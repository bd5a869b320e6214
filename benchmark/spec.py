"""Finds the benchmark's parts by the names ``BENCHMARK.json`` gives them.

Each part is a file of its own, so a later change adds files and edits
none:

- a configuration: the ``file`` its ``configs`` entry names;
- a cell's traffic: ``benchmark/workloads/<traffic>.json``;
- a metric's reader: ``benchmark/metrics/<metric>.py``, with
  ``read(ctx) -> float | None``;
- a campaign driver: ``benchmark/drivers/<driver>.py``, named by the
  traffic's ``driver`` key;
- a source generator: ``benchmark/gen/<generator>.py``, named by the
  configuration's ``generator`` key, with ``generate(shape, seed)``.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))


def _load_module(path: str, prefix: str):
  if not os.path.isfile(path):
    raise FileNotFoundError(path)
  name = prefix + re.sub(r"\W", "_", os.path.basename(path)[:-3])
  spec = importlib.util.spec_from_file_location(name, path)
  module = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(module)
  return module


class Spec:
  def __init__(self, root: str, bench_dir: str = BENCH_DIR):
    self.root = root
    self.dir = bench_dir
    with open(os.path.join(root, "BENCHMARK.json")) as f:
      self.bench = json.load(f)

  def cell(self, name: str) -> dict:
    for cell in self.bench["workloads"]:
      if cell["name"] == name:
        return cell
    known = [c["name"] for c in self.bench["workloads"]]
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (known: {known})")

  def config(self, name: str) -> dict:
    for entry in self.bench["configs"]:
      if entry["name"] == name:
        with open(os.path.join(self.root, entry["file"])) as f:
          return json.load(f)
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")

  def traffic(self, name: str) -> dict:
    with open(os.path.join(self.dir, "workloads", f"{name}.json")) as f:
      return json.load(f)

  def metrics(self, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: the end-to-end ones
    with the profiler off, the per-layer ones with it on."""
    entries = self.bench["per_layer" if trace else "end_to_end"]
    return [m for m in entries
            if "workloads" not in m or cell in m["workloads"]]

  def reader(self, metric: str):
    return _load_module(
      os.path.join(self.dir, "metrics", f"{metric}.py"), "bench_metric_"
    )

  def driver(self, kind: str):
    return _load_module(
      os.path.join(self.dir, "drivers", f"{kind}.py"), "bench_driver_"
    )

  def generator(self, name: str):
    return _load_module(
      os.path.join(self.dir, "gen", f"{name}.py"), "bench_gen_"
    )
