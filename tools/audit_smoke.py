#!/usr/bin/env python
"""Integrity audit smoke (ISSUE 16 CI step).

Runs a real downsample campaign through `igneous execute` on a virtual
8-device CPU mesh (so manifests are written by the same worker path
production uses), then damages the layer at rest with three distinct
fault shapes — a torn write (truncation), a flipped bit, and a deleted
object — and asserts the audit plane end to end:

  * `igneous audit` exits 2 and NAMES each of the three damaged chunks
    on stdout (CORRUPT <kind> mip=<m> <key> lines);
  * `igneous audit --heal` re-runs the producing tasks for exactly the
    damaged cells through an fq:// range-lease queue and exits 0;
  * a follow-up plain audit confirms convergence (exit 0);
  * the machine-readable completeness reports land where CI can upload
    them as artifacts (--report-out).

Usage: python tools/audit_smoke.py [--size 128] [--report-out DIR]
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


def worker_env():
  env = dict(os.environ)
  env.update({
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "IGNEOUS_POOL_HOST": "0",
    "IGNEOUS_PIPELINE": "1",
    "IGNEOUS_PIPELINE_THREADS": "1",
  })
  return env


def run(argv, timeout=600):
  proc = subprocess.run(
    [sys.executable, "-m", "igneous_tpu"] + argv,
    env=worker_env(), cwd=REPO, capture_output=True, text=True,
    timeout=timeout,
  )
  sys.stdout.write(proc.stdout)
  sys.stderr.write(proc.stderr)
  return proc


def produced_chunks(layer_dir, mip0_dir):
  """Chunk files of every produced (non-source) mip, sorted for a
  deterministic corruption target set."""
  out = []
  for entry in sorted(os.listdir(layer_dir)):
    full = os.path.join(layer_dir, entry)
    if not os.path.isdir(full) or entry == mip0_dir:
      continue
    if entry in ("integrity",):
      continue
    for name in sorted(os.listdir(full)):
      if "-" in name:  # bbox-named chunk, not a sidecar
        out.append(os.path.join(full, name))
  return out


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--size", type=int, default=256)
  ap.add_argument("--report-out", default=None,
                  help="Copy audit completeness reports here (CI upload).")
  args = ap.parse_args()

  tmp = tempfile.mkdtemp(prefix="igneous-audit-smoke-")
  path = f"file://{tmp}/img"
  layer_dir = os.path.join(tmp, "img")
  qspec = f"fq://{tmp}/q"
  auditq = f"fq://{tmp}/auditq"

  from igneous_tpu import task_creation as tc
  from igneous_tpu.queues import FileQueue
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(11)
  n = args.size
  data = rng.integers(0, 255, (n, n, 64)).astype(np.uint8)
  vol = Volume.from_numpy(data, path, chunk_size=(32, 32, 32),
                          compress="gzip", layer_type="image")
  mip0_dir = vol.meta.key(0)
  # memory_target sized so the default 256x256x64 volume plans the full
  # 2-mip pyramid ([128,128,64] task shape) across a 4-task grid
  tasks = list(tc.create_downsampling_tasks(
    path, mip=0, num_mips=2, memory_target=4 * 1024 * 1024,
    compress="gzip",
  ))
  assert len(tasks) >= 4, f"want a fan-out of tasks, got {len(tasks)}"
  FileQueue(qspec).insert(tasks)

  proc = run(["execute", qspec, "--batch", "4", "--exit-on-empty",
              "--min-sec", "10", "-q", "--lease-sec", "60"])
  assert proc.returncode == 0, f"campaign worker failed rc={proc.returncode}"

  # a clean campaign must audit clean before we break anything
  proc = run(["audit", path, "--queue", auditq])
  assert proc.returncode == 0, (
    f"clean-campaign audit exited {proc.returncode}: {proc.stdout}"
  )

  chunks = produced_chunks(layer_dir, mip0_dir)
  assert len(chunks) >= 3, f"need >=3 produced chunks, got {len(chunks)}"
  targets = [chunks[0], chunks[len(chunks) // 2], chunks[-1]]
  assert len(set(targets)) == 3

  def logical_key(full):
    rel = os.path.relpath(full, layer_dir)
    for ext in (".gz", ".zstd", ".br"):
      if rel.endswith(ext):
        return rel[: -len(ext)]
    return rel

  torn, flipped, deleted = targets
  with open(torn, "r+b") as f:
    f.truncate(max(1, os.path.getsize(torn) // 2))
  raw = open(flipped, "rb").read()
  i = len(raw) // 2
  with open(flipped, "wb") as f:
    f.write(raw[:i] + bytes([raw[i] ^ 0x10]) + raw[i + 1:])
  os.remove(deleted)
  injected = {logical_key(t) for t in targets}
  print(f"injected 3 faults: {sorted(injected)}")

  report1 = os.path.join(tmp, "audit-findings.json")
  proc = run(["audit", path, "--queue", auditq, "--out", report1])
  assert proc.returncode == 2, (
    f"audit over damaged layer exited {proc.returncode}, want 2"
  )
  named = {
    line.split()[-1]
    for line in proc.stdout.splitlines() if line.startswith("CORRUPT ")
  }
  assert named == injected, (
    f"audit must name exactly the injected faults: "
    f"missed={sorted(injected - named)} extra={sorted(named - injected)}"
  )
  rep = json.load(open(report1))
  assert not rep["complete"] and len(rep["findings"]) == 3, rep

  report2 = os.path.join(tmp, "audit-healed.json")
  proc = run(["audit", path, "--queue", auditq, "--heal", "--out", report2])
  assert proc.returncode == 0, (
    f"audit --heal exited {proc.returncode}: {proc.stdout}"
  )
  assert "complete and intact" in proc.stdout
  rep = json.load(open(report2))
  assert rep["complete"] and rep["repair_tasks"] >= 1, rep

  # convergence: a fresh audit of the healed layer is clean
  proc = run(["audit", path, "--queue", auditq])
  assert proc.returncode == 0, f"post-heal audit exited {proc.returncode}"

  if args.report_out:
    os.makedirs(args.report_out, exist_ok=True)
    for rpt in (report1, report2):
      shutil.copyfile(
        rpt, os.path.join(args.report_out, os.path.basename(rpt))
      )
    print(f"copied reports to {args.report_out}")

  shutil.rmtree(tmp, ignore_errors=True)
  print("AUDIT_SMOKE_OK")


if __name__ == "__main__":
  main()
