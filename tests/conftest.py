import os

# Tests run on a virtual 8-device CPU mesh so multi-chip sharding logic is
# exercised without TPU hardware; only chip_smoke.py and benchmark/run.py
# run on the chip. The env vars are set before jax is imported and are
# inherited by subprocesses (LocalTaskQueue spawn workers, CLI children).
# JAX's persistent compilation cache stays off, so CPU executables never
# land in the checkout's .jax_cache.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
  os.environ["XLA_FLAGS"] = (
    xla_flags + " --xla_force_host_platform_device_count=8"
  ).strip()

import numpy as np
import pytest


@pytest.fixture
def rng():
  return np.random.default_rng(seed=42)
