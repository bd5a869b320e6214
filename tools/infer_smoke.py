"""Inference subsystem smoke (ISSUE 10 CI step).

Runs a 2-task InferenceTask campaign through `igneous execute` twice on a
virtual 8-device CPU mesh — once strictly serial, once through the staged
pipeline — and asserts the acceptance criteria end to end:

  * both runs exit 0 and write the SAME output bytes (the inference
    byte-determinism contract: pipelined == serial, chunk for chunk);
  * device.execute spans for the inference kernel landed in the journal
    (the conv apply really ran through the batched device path);
  * the journal's device ledger shows nonzero busy time, and the
    fast-path tally counted the campaign's patches;
  * `igneous fleet devices` exits 0 and shows the busy column.

Usage: python tools/infer_smoke.py [--size 128]
"""

import argparse
import os
import subprocess
import sys
import tempfile

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from igneous_tpu.analysis import discovery  # noqa: E402


def worker_env(pipeline: str):
  env = dict(os.environ)
  env.update({
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "IGNEOUS_PIPELINE": pipeline,
    "IGNEOUS_PIPELINE_THREADS": "1",
    "IGNEOUS_JOURNAL_FLUSH_SEC": "2",
  })
  return env


def layer_bytes(root):
  out = {}
  for full in discovery.walk_files(root):
    fname = os.path.basename(full)
    if "provenance" in fname or ".tmp." in fname:
      continue
    with open(full, "rb") as f:
      out[os.path.relpath(full, root)] = f.read()
  return out


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--size", type=int, default=128)
  args = ap.parse_args()

  tmp = tempfile.mkdtemp(prefix="igneous-infer-smoke-")
  src = f"file://{tmp}/src"
  model_path = f"file://{tmp}/model"
  qdir = f"{tmp}/q"
  jpath = f"file://{qdir}/journal"

  from igneous_tpu import task_creation as tc
  from igneous_tpu.infer import ModelSpec, init_params, save_model
  from igneous_tpu.observability import fleet
  from igneous_tpu.observability import device as device_mod
  from igneous_tpu.queues import FileQueue
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(10)
  n = args.size
  data = rng.integers(0, 255, (n, n, 32)).astype(np.uint8)
  Volume.from_numpy(data, src, chunk_size=(32, 32, 32), layer_type="image")

  spec = ModelSpec(
    "convnet3d", in_channels=1, out_channels=2,
    patch_shape=(32, 32, 16), overlap=(8, 8, 4), hidden=(4,),
  )
  save_model(model_path, spec, init_params(spec, seed=3))

  # task shape = half the volume -> exactly a 2-task campaign
  task_shape = (n // 2, n, 32)
  runs = {}
  for mode, pipeline in (("serial", "off"), ("pipelined", "1")):
    dest = f"file://{tmp}/out_{mode}"
    tasks = list(tc.create_inference_tasks(
      src, dest, model_path, shape=task_shape, batch_size=4,
    ))
    assert len(tasks) == 2, f"want a 2-task campaign, got {len(tasks)}"
    qspec = f"fq://{qdir}_{mode}"
    FileQueue(qspec).insert(tasks)
    proc = subprocess.run(
      [sys.executable, "-m", "igneous_tpu", "execute", qspec,
       "--batch", "2", "--exit-on-empty", "-q", "--lease-sec", "120",
       "--journal", jpath],
      env=worker_env(pipeline), cwd=REPO, capture_output=True, text=True,
      timeout=600,
    )
    sys.stdout.write(proc.stdout)
    sys.stderr.write(proc.stderr)
    assert proc.returncode == 0, f"{mode} worker rc={proc.returncode}"
    runs[mode] = layer_bytes(f"{tmp}/out_{mode}")

  serial, pipelined = runs["serial"], runs["pipelined"]
  assert serial, "serial run produced no output objects"
  assert set(serial) == set(pipelined), (
    "pipelined run wrote a different object set"
  )
  diff = [k for k in serial if serial[k] != pipelined[k]]
  assert not diff, f"byte mismatch pipelined vs serial: {diff}"
  print(f"byte identity: {len(serial)} objects identical")

  records = fleet.load(jpath)
  spans = [r for r in records if r.get("kind") == "span"]
  execs = [
    s for s in spans
    if s.get("name") == "device.execute"
    and str(s.get("kernel", "")).startswith("infer.")
  ]
  assert execs, "no inference device.execute spans in the journal"

  ledgers = device_mod.device_ledgers(records)
  assert ledgers, "no device ledger records in the journal"
  ledger = next(iter(ledgers.values()))
  assert ledger["busy_s"] and ledger["busy_s"] > 0, (
    f"device busy time not recorded: {ledger}"
  )
  fastpath = ledger.get("fastpath") or {}
  assert fastpath.get("batched", 0) > 0, (
    f"fast-path tally missing inference patches: {fastpath}"
  )
  print(f"ledger: busy_s={ledger['busy_s']} fastpath={fastpath}")

  proc = subprocess.run(
    [sys.executable, "-m", "igneous_tpu", "fleet", "devices",
     "--journal", jpath],
    env=worker_env("1"), cwd=REPO, capture_output=True, text=True,
    timeout=120,
  )
  sys.stdout.write(proc.stdout)
  assert proc.returncode == 0, (
    f"igneous fleet devices exited {proc.returncode}: {proc.stderr}"
  )
  assert "busy_s" in proc.stdout
  print("INFER_SMOKE_OK")


if __name__ == "__main__":
  main()
