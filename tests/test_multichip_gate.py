"""CI guard for the multi-chip driver gate.

Round 5 shipped with MULTICHIP red: a dispatch-policy change routed
batched downsamples to the native host path on accelerator-less hosts,
and ``dryrun_multichip``'s child env pinned only the EDT/CCL backends —
so the gate's ``batched_cutouts > 0`` assertion fired. The fix pins
``IGNEOUS_POOL_HOST=0`` next to the other pins (``__graft_entry__.py``);
THIS test is the part that keeps it fixed: a cut-down ``_dryrun_impl``
equivalent on a 2-virtual-device CPU mesh runs on every CI push, so a
future dispatch-policy change breaks a test here instead of silently
breaking the driver artifact after snapshot.

The check runs in a CPU-pinned subprocess for the same reason the real
dryrun does: virtual host devices need XLA_FLAGS set before jax boots.
"""

import json
import os
import subprocess
import sys

REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_CHILD_SRC = r"""
import json
import numpy as np

from igneous_tpu.ops.oracle import np_downsample_with_averaging
from igneous_tpu.parallel import make_mesh
from igneous_tpu.parallel.batch_runner import batched_downsample
from igneous_tpu.parallel.lease_batcher import poll_batched
from igneous_tpu.volume import Volume

import jax
assert jax.default_backend() == "cpu", jax.default_backend()
assert jax.device_count() >= 2, jax.device_count()
mesh = make_mesh(2)

rng = np.random.default_rng(5)
img = rng.integers(0, 255, (32, 32, 8)).astype(np.uint8)
Volume.from_numpy(img, "mem://gate/img", chunk_size=(8, 8, 8))
st = batched_downsample(
  "mem://gate/img", num_mips=1, shape=(16, 16, 8),
  batch_size=2, mesh=mesh, compress=None,
)
v1 = Volume("mem://gate/img", mip=1)
got = v1.download(v1.bounds)[..., 0]
exp = np_downsample_with_averaging(img, (2, 2, 1), 1)[0]
assert np.array_equal(got, exp), "batched pipeline output != oracle"

# queue-leased --batch worker over the same mesh (the other section the
# r5 regression silently skipped)
import tempfile

from igneous_tpu.downsample_scales import create_downsample_scales
from igneous_tpu.queues import FileQueue
from igneous_tpu.tasks.image import DownsampleTask

img2 = rng.integers(0, 255, (32, 32, 8)).astype(np.uint8)
Volume.from_numpy(img2, "mem://gate/lease", chunk_size=(8, 8, 8))
vol2 = Volume("mem://gate/lease")
create_downsample_scales(vol2.meta, 0, (16, 16, 8), (2, 2, 1), num_mips=1)
vol2.commit_info()
with tempfile.TemporaryDirectory() as qdir:
  q = FileQueue(f"fq://{qdir}")
  q.insert([
    DownsampleTask(
      layer_path="mem://gate/lease", mip=0, shape=(16, 16, 8),
      offset=(x, y, 0), num_mips=1, factor=(2, 2, 1),
    )
    for x in range(0, 32, 16) for y in range(0, 32, 16)
  ])
  executed, lease_stats = poll_batched(
    q, batch_size=2, lease_seconds=600, mesh=mesh,
    stop_fn=lambda executed, empty: empty,
  )
  assert executed == 4 and q.is_empty(), (executed, q.enqueued)

print("GATE_RESULT " + json.dumps({
  "batched_cutouts": st["batched_cutouts"],
  "dispatches": st["dispatches"],
  "lease_executed": executed,
  "lease_downsample_dispatches": lease_stats["dispatches"].get("downsample", 0),
}))
"""


def test_multichip_gate_batched_device_path():
  from __graft_entry__ import _cpu_env

  env = _cpu_env(2)
  # the SAME pins the real dryrun_multichip child uses — this test exists
  # to fail when those pins and the dispatch policy drift apart
  env["IGNEOUS_EDT_BACKEND"] = "device"
  env["IGNEOUS_CCL_BACKEND"] = "device"
  env["IGNEOUS_POOL_HOST"] = "0"
  proc = subprocess.run(
    [sys.executable, "-c", _CHILD_SRC],
    env=env, cwd=REPO_DIR, capture_output=True, text=True, timeout=420,
  )
  assert proc.returncode == 0, (
    f"gate child failed rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
  )
  line = [l for l in proc.stdout.splitlines() if l.startswith("GATE_RESULT ")]
  assert line, proc.stdout
  result = json.loads(line[-1].split(" ", 1)[1])
  # the exact assertions MULTICHIP_r05 failed on
  assert result["batched_cutouts"] > 0, result
  assert result["dispatches"] >= 1, result
  assert result["lease_downsample_dispatches"] >= 1, result
