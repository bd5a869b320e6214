"""Each cell's pyramid dispatch, compiled for a described TPU v5e at the
cell's real shapes: the ``ChunkExecutor`` program the window runs, on one
chip or over the 2x2 mesh. Nothing runs; this catches what the chip's
compiler refuses (memory included) before any chip time is spent, and
prints ``memory_analysis()`` per cell.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_v5e_compile.py -s
"""

import json
import os

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


@pytest.fixture(scope="module")
def topo():
  os.environ.setdefault("TPU_LOG_DIR", "disabled")
  import jax
  from jax.experimental import topologies

  jax.config.update("jax_enable_compilation_cache", False)
  try:
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
  except Exception as e:  # no TPU compiler here
    pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _cells():
  with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    bench = json.load(f)
  out = []
  for cell in bench["workloads"]:
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
      cfg = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads",
                           cell["traffic"] + ".json")) as f:
      traffic = json.load(f)
    if traffic["driver"] == "downsample":
      out.append((cell["name"], cell["chips"], cfg, traffic["batch"]))
  return out


@pytest.mark.parametrize("name,chips,cfg,batch", _cells(),
                         ids=[c[0] for c in _cells()])
def test_pyramid_compiles_for_v5e(topo, name, chips, cfg, batch):
  import jax
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

  from igneous_tpu.parallel.executor import ChunkExecutor

  mesh = Mesh(np.asarray(topo.devices[:chips]), ("chunks",))
  method = cfg["downsample_method"]
  if method == "auto":
    method = "mode" if cfg["layer_type"] == "segmentation" else "average"
  u64 = method == "mode" and np.dtype(cfg["data_type"]).itemsize == 8
  ex = ChunkExecutor(mesh, factors=[tuple(cfg["factor"])] * cfg["task_mips"],
                     method=method, planes=2 if u64 else 1)
  x, y, z = cfg["task_shape"]
  dtype = np.uint32 if u64 else np.dtype(cfg["data_type"])
  spec = jax.ShapeDtypeStruct((batch, 1, z, y, x), dtype,
                              sharding=NamedSharding(mesh, P("chunks")))
  xs = (spec, spec) if u64 else (spec,)
  compiled = ex._fn.lower(xs).compile()
  mem = compiled.memory_analysis()
  line = {
    "cell": name, "chips": chips,
    "argument_bytes": mem.argument_size_in_bytes,
    "output_bytes": mem.output_size_in_bytes,
    "temp_bytes": mem.temp_size_in_bytes,
  }
  print(json.dumps(line))
  total = line["argument_bytes"] + line["output_bytes"] + line["temp_bytes"]
  assert total < 16e9


def test_default_seg_task_does_not_fit_one_v5e(topo):
  """The cell left out of BENCHMARK.json: the planner's default uint64
  task (2048x2048x64, 4 mips) at --batch 2 needs more HBM than one v5e
  has. When this starts to fail, the program fits and the cell
  seg_ds.dense (configs/seg_u64_cseg.json) can come back."""
  import jax
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

  from igneous_tpu.parallel.executor import ChunkExecutor

  with open(os.path.join(ROOT, "benchmark", "configs",
                         "seg_u64_cseg.json")) as f:
    cfg = json.load(f)
  mesh = Mesh(np.asarray(topo.devices[:1]), ("chunks",))
  ex = ChunkExecutor(mesh, factors=[tuple(cfg["factor"])] * cfg["task_mips"],
                     method="mode", planes=2)
  x, y, z = cfg["task_shape"]
  spec = jax.ShapeDtypeStruct((2, 1, z, y, x), np.uint32,
                              sharding=NamedSharding(mesh, P("chunks")))
  with pytest.raises(Exception, match="RESOURCE_EXHAUSTED"):
    ex._fn.lower((spec, spec)).compile()
