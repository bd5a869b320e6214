"""The benchmark's own tests run on JAX's CPU backend with 8 virtual
devices, and import the program from the checkout."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# the batched device path on XLA-CPU, not the native host pooling
os.environ.setdefault("IGNEOUS_POOL_HOST", "0")
if "--xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""):
  os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
  ).strip()

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
  os.path.abspath(__file__))))
if ROOT not in sys.path:
  sys.path.insert(0, ROOT)
