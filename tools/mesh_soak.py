"""512^3 mesh-forge soak — committed generator (same rationale as
tools/skel_soak.py: round-4's ad-hoc fixture was lost with its session,
so cross-round wall numbers start fresh at round 5). Shares skel_soak's grid-placed non-overlapping blob field;
runs 8 sharded MeshTasks (shape 256^3, spatial index) and reports the
fg rate.

Run: JAX_PLATFORMS=cpu python tools/mesh_soak.py
"""

from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from skel_soak import build_fixture  # noqa: E402


def main():
  from igneous_tpu import task_creation as tc
  from igneous_tpu.storage import clear_memory_storage
  from igneous_tpu.volume import Volume

  seg = build_fixture()
  fg = int((seg != 0).sum())
  print(f"fg: {fg}", flush=True)
  clear_memory_storage()
  Volume.from_numpy(
    seg, "mem://soak/mesh", resolution=(16, 16, 40),
    chunk_size=(128, 128, 128), layer_type="segmentation",
  )
  tasks = list(tc.create_meshing_tasks(
    "mem://soak/mesh", mip=0, shape=(256, 256, 256), sharded=True,
    spatial_index=True,
  ))
  print(f"tasks: {len(tasks)}", flush=True)
  t0 = time.time()
  for t in tasks:
    t.execute()
  dt = time.time() - t0
  print(f"SOAK wall: {dt:.1f}s  fg-rate: {fg / dt / 1e3:.1f} kvox-fg/s  "
        f"load={os.getloadavg()}")


if __name__ == "__main__":
  main()
