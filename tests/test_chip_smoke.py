"""chip_smoke.py's phases at a tiny size on the 8 virtual CPU devices.

The script itself refuses the CPU; this drives the same phase functions
with the device paths pinned (the CPU defaults are the host kernels), so
a change that breaks a phase or its oracle fails here before a chip run.
"""

import json

import pytest

import chip_smoke
from igneous_tpu.observability.device import require_worker_backend

TINY = dict(
  img=(256, 256, 16), seg=(256, 256, 16), slab=(64, 64, 16),
  chunk=(32, 32, 8), img_dest_chunk=(8, 8, 8), seg_dest_chunk=(8, 8, 8),
  ccl_shape=(40, 40, 16), mesh_shape=(40, 40, 16), skel_shape=(64, 64, 16),
)


def test_phases_tiny_on_virtual_devices(tmp_path, monkeypatch, capsys):
  monkeypatch.setenv("IGNEOUS_POOL_HOST", "0")
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "device")
  chip_smoke.run_phases(str(tmp_path), 0, "cpu", sizes=TINY)
  lines = [
    json.loads(l) for l in capsys.readouterr().out.splitlines()
    if l.startswith('{"phase"')
  ]
  assert [l["phase"].split()[0] for l in lines] == [
    "P1", "P2", "P3", "P4", "P5",
  ]


def test_unpinned_worker_on_cpu_fails_loudly(monkeypatch):
  require_worker_backend()  # JAX_PLATFORMS=cpu: a CPU worker by choice
  monkeypatch.delenv("JAX_PLATFORMS")
  with pytest.raises(RuntimeError, match="no accelerator"):
    require_worker_backend()
