"""Device telemetry plane smoke (ISSUE 7 CI step).

Runs a real pipelined downsample workload through `igneous execute`
on a virtual 8-device CPU mesh (batched device dispatches via
IGNEOUS_POOL_HOST=0) with a pre-published profiler capture request,
then asserts the acceptance criteria end to end:

  * device.execute AND device.compile spans landed in the journal;
  * the journal carries a cumulative per-worker device ledger with a
    busy ratio and per-kernel vox/s;
  * igneous_device_recompiles_total counted distinct signatures only
    (recompiles <= distinct signatures, both >= 1);
  * `igneous fleet devices` exits 0 and prints the merged table;
  * the flags-file profiler trigger produced capture artifacts under
    <journal>/profiles/ (optionally copied out for the CI artifact).

Usage: python tools/device_smoke.py [--size 128] [--profile-out DIR]
"""

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def worker_env(tmp):
  env = dict(os.environ)
  env.update({
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "IGNEOUS_POOL_HOST": "0",        # device pyramid, not native host pool
    "IGNEOUS_PIPELINE": "1",
    "IGNEOUS_PIPELINE_THREADS": "1",
    "IGNEOUS_JOURNAL_FLUSH_SEC": "2",
  })
  return env


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--size", type=int, default=256)
  ap.add_argument("--profile-out", default=None,
                  help="Copy captured profile artifacts here (CI upload).")
  args = ap.parse_args()

  tmp = tempfile.mkdtemp(prefix="igneous-device-smoke-")
  path = f"file://{tmp}/img"
  qdir = f"{tmp}/q"
  qspec = f"fq://{qdir}"
  jpath = f"file://{qdir}/journal"

  from igneous_tpu import task_creation as tc
  from igneous_tpu.analysis import discovery
  from igneous_tpu.observability import device as device_mod
  from igneous_tpu.queues import FileQueue
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(7)
  n = args.size
  data = rng.integers(0, 255, (n, n, 64)).astype(np.uint8)
  Volume.from_numpy(data, path, chunk_size=(32, 32, 32),
                    layer_type="image")
  tasks = list(tc.create_downsampling_tasks(
    path, mip=0, num_mips=1, memory_target=2 * 1024 * 1024
  ))
  assert len(tasks) >= 4, f"want a few tasks, got {len(tasks)}"
  FileQueue(qspec).insert(tasks)

  # publish the capture trigger BEFORE the worker starts: its first
  # journal poll must pick it up (the PR 6 flags-file pattern)
  req = device_mod.write_profile_request(jpath, duration_sec=1.0)
  print(f"profile request {req['id']} published")

  proc = subprocess.run(
    [sys.executable, "-m", "igneous_tpu", "execute", qspec,
     "--batch", "4", "--exit-on-empty", "--min-sec", "10", "-q",
     "--lease-sec", "60"],
    env=worker_env(tmp), cwd=REPO, capture_output=True, text=True,
    timeout=600,
  )
  sys.stdout.write(proc.stdout)
  sys.stderr.write(proc.stderr)
  assert proc.returncode == 0, f"worker failed rc={proc.returncode}"

  from igneous_tpu.observability import fleet

  records = fleet.load(jpath)
  spans = [r for r in records if r.get("kind") == "span"]
  execs = [s for s in spans if s.get("name") == "device.execute"]
  compiles = [s for s in spans if s.get("name") == "device.compile"]
  assert execs, "no device.execute spans in the journal"
  assert compiles, "no device.compile spans in the journal"
  assert all(s.get("device") for s in execs), "spans lack device attr"

  ledgers = device_mod.device_ledgers(records)
  assert ledgers, "no device ledger records in the journal"
  ledger = next(iter(ledgers.values()))
  assert ledger["busy_ratio"] is not None and ledger["dispatches"] >= 1
  assert ledger["recompiles"] >= 1
  assert ledger["recompiles"] <= ledger["distinct_signatures"] + 0, (
    "recompiles must count distinct signatures only"
  )
  kernels = ledger["kernels"]
  assert any(k.get("vox_per_sec") for k in kernels.values()), (
    "per-kernel vox/s missing from the ledger"
  )
  print(f"ledger: busy_ratio={ledger['busy_ratio']} "
        f"dispatches={ledger['dispatches']} "
        f"recompiles={ledger['recompiles']} kernels={sorted(kernels)}")

  proc = subprocess.run(
    [sys.executable, "-m", "igneous_tpu", "fleet", "devices",
     "--journal", jpath],
    env=worker_env(tmp), cwd=REPO, capture_output=True, text=True,
    timeout=120,
  )
  sys.stdout.write(proc.stdout)
  assert proc.returncode == 0, (
    f"igneous fleet devices exited {proc.returncode}: {proc.stderr}"
  )
  assert "busy_s" in proc.stdout

  artifacts = device_mod.list_profiles(jpath)
  assert artifacts, "profiler trigger produced no artifacts"
  print(f"profile artifacts: {len(artifacts)}")
  if args.profile_out:
    os.makedirs(args.profile_out, exist_ok=True)
    src_root = os.path.join(qdir, "journal", "profiles")
    for full in discovery.walk_files(src_root):
      rel = os.path.relpath(full, src_root)
      dest = os.path.join(args.profile_out, rel)
      os.makedirs(os.path.dirname(dest), exist_ok=True)
      shutil.copyfile(full, dest)
    print(f"copied artifacts to {args.profile_out}")

  print("DEVICE_SMOKE_OK")


if __name__ == "__main__":
  main()
