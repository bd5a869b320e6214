"""A scratch checkout of the benchmark with tiny cells, for CPU tests."""

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

# the real configurations, cut to sizes a test run holds: 32x32x16
# chunks and a memory target that makes the planner pick small tasks
TINY = {
  "tiny_seg": ("seg_u64_cseg", dict(chunk_size=[32, 32, 16],
                                    memory_target=4000000,
                                    task_shape=[128, 128, 16], task_mips=2)),
  "tiny_em": ("em_u8_jpeg", dict(chunk_size=[32, 32, 16],
                                 memory_target=4000000,
                                 task_shape=[256, 256, 16], task_mips=3)),
}
CELLS = [
  ("tiny_seg.k2", "tiny_seg", "ds_k2", 1),
  ("tiny_em.k2", "tiny_em", "ds_k2", 1),
  ("tiny_em.k8", "tiny_em", "ds_k8", 4),
]


def make_checkout(dst: str) -> str:
  """``dst`` gets BENCHMARK.json and benchmark/, plus the tiny cells."""
  os.makedirs(dst, exist_ok=True)
  shutil.copytree(BENCH, os.path.join(dst, "benchmark"),
                  ignore=shutil.ignore_patterns(".cache", "__pycache__"))
  shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
  with open(os.path.join(dst, "BENCHMARK.json")) as f:
    bench = json.load(f)
  for name, (base, changes) in TINY.items():
    with open(os.path.join(BENCH, "configs", base + ".json")) as f:
      cfg = json.load(f)
    cfg.update(name=name, **changes)
    path = f"benchmark/configs/{name}.json"
    with open(os.path.join(dst, path), "w") as f:
      json.dump(cfg, f)
    bench["configs"].append(dict(name=name, source="test", file=path,
                                 reduced=[], why="test"))
  for name, config, traffic, chips in CELLS:
    bench["workloads"].append(dict(name=name, config=config, traffic=traffic,
                                   chips=chips, why="test"))
  for metric in bench["per_layer"]:
    metric["workloads"] += [c[0] for c in CELLS]
  with open(os.path.join(dst, "BENCHMARK.json"), "w") as f:
    json.dump(bench, f, indent=1)
  return dst


def run_cell(checkout: str, cell: str, seed: int, seconds: float = 2.0,
             trace: int = 0, control: bool = False) -> dict:
  from benchmark import run

  argv = ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
          "--trace", str(trace)] + (["--control"] if control else [])
  return run.run(argv, require_chip=False, root=checkout,
                 bench_dir=os.path.join(checkout, "benchmark"))
