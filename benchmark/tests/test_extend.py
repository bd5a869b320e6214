"""A later change adds a configuration, a cell and a metric as new files
(plus their entries in BENCHMARK.json); the harness finds and runs all
three with no other file edited."""

import hashlib
import json
import os

from helpers import make_checkout, run_cell

READER = '''"""Tasks acknowledged per second of the window."""


def read(ctx):
  win = ctx["window"]
  return win["acked"] / win["window_s"] if win["window_s"] > 0 else None
'''


def _digests(root):
  out = {}
  for dirpath, _dirs, files in os.walk(os.path.join(root, "benchmark")):
    if ".cache" in dirpath or "__pycache__" in dirpath:
      continue
    for f in files:
      with open(os.path.join(dirpath, f), "rb") as fh:
        out[os.path.relpath(os.path.join(dirpath, f), root)] = (
          hashlib.sha256(fh.read()).hexdigest())
  return out


def test_new_config_cell_and_metric_as_files(tmp_path):
  root = make_checkout(str(tmp_path))
  before = _digests(root)
  bench_dir = os.path.join(root, "benchmark")

  with open(os.path.join(bench_dir, "configs", "tiny_em.json")) as f:
    cfg = json.load(f)
  cfg.update(name="tiny_em_q95", encoding_level=95)
  with open(os.path.join(bench_dir, "configs", "tiny_em_q95.json"), "w") as f:
    json.dump(cfg, f)
  with open(os.path.join(bench_dir, "workloads", "ds_k4_test.json"), "w") as f:
    json.dump({"driver": "downsample", "task_grid": [4, 2, 1], "batch": 4},
              f)
  with open(os.path.join(bench_dir, "metrics", "tasks_per_s.py"), "w") as f:
    f.write(READER)

  with open(os.path.join(root, "BENCHMARK.json")) as f:
    bench = json.load(f)
  bench["configs"].append(dict(name="tiny_em_q95", source="test",
                               file="benchmark/configs/tiny_em_q95.json",
                               reduced=[], why="test"))
  bench["workloads"].append(dict(name="tiny_em_q95.k4", config="tiny_em_q95",
                                 traffic="ds_k4_test", chips=1, why="test"))
  bench["end_to_end"].append(dict(name="tasks_per_s", unit="tasks/s",
                                  better="higher", bound=0.25,
                                  source="host_clock",
                                  workloads=["tiny_em_q95.k4"]))
  with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
    json.dump(bench, f)

  res = run_cell(root, "tiny_em_q95.k4", 11, seconds=1)
  assert res["correct"], res["checks"]
  assert res["metrics"]["tasks_per_s"]["value"] > 0
  assert {"vox_per_s_chip", "setup_s"} <= set(res["metrics"])
  after = _digests(root)
  assert {k: v for k, v in after.items() if k in before} == before
  assert set(after) - set(before) == {
    "benchmark/configs/tiny_em_q95.json",
    "benchmark/workloads/ds_k4_test.json",
    "benchmark/metrics/tasks_per_s.py",
  }
