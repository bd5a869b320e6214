"""JAX's persistent compile cache, the executors' bounded in-process
caches, and the autotuner's tuned-config resolution.

The executors compile ahead of time and JAX's cache persists each
executable under the program's module name, so a second process with
the same program and signature loads it instead of compiling.
"""

import json
import os
import subprocess
import sys

import pytest

from igneous_tpu import compile_cache as cc
from igneous_tpu import tune
from igneous_tpu.parallel.executor import LRUCache


@pytest.fixture
def tune_root(tmp_path, monkeypatch):
  root = f"file://{tmp_path}/tuned"
  monkeypatch.setenv(tune.CONFIG_ENV, root)
  tune.reset_cache()
  yield root
  tune.reset_cache()


# -- bounded in-memory caches ------------------------------------------------

def test_lru_cache_evicts_oldest(monkeypatch):
  monkeypatch.setenv("IGNEOUS_EXECUTOR_CACHE_CAP", "2")
  cache = LRUCache()
  cache["a"] = 1
  cache["b"] = 2
  _ = cache["a"]  # refresh a
  cache["c"] = 3  # evicts b (oldest)
  assert "a" in cache and "c" in cache and "b" not in cache
  assert len(cache) == 2


def test_lru_cache_default_cap():
  cache = LRUCache()
  for i in range(100):
    cache[i] = i
  assert len(cache) == 64


# -- tuned-config resolution --------------------------------------------------

def _write_tuned(root, knobs_dict):
  path = os.path.join(
    root[len("file://"):], tune.TUNED_PREFIX,
    f"{tune.device_kind()}.json",
  )
  os.makedirs(os.path.dirname(path), exist_ok=True)
  with open(path, "w") as f:
    json.dump({"version": 1, "knobs": knobs_dict}, f)
  tune.reset_cache()


def test_tuned_config_applies_and_env_wins(tune_root, monkeypatch):
  monkeypatch.delenv("IGNEOUS_EDT_LINE_BLOCK", raising=False)
  _write_tuned(tune_root, {"IGNEOUS_EDT_LINE_BLOCK": "128"})
  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") == "128"
  from igneous_tpu.ops.edt import _line_block

  assert _line_block() == 128
  # explicit env always outranks the tuned config
  monkeypatch.setenv("IGNEOUS_EDT_LINE_BLOCK", "64")
  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") == "64"
  assert _line_block() == 64


@pytest.mark.parametrize("source", ["env", "tuned"])
def test_unverified_ccl_tile_refused_on_tpu(tune_root, monkeypatch, source):
  """(8,16,128) gave wrong components on a v5e: on a TPU no tile outside
  ccl._TPU_TILES is taken, from the env or a tuned config, and the tuner
  sweeps none; the CPU keeps taking it."""
  import jax

  from igneous_tpu.ops import ccl

  monkeypatch.delenv("IGNEOUS_CCL_TILE", raising=False)
  if source == "env":
    monkeypatch.setenv("IGNEOUS_CCL_TILE", "8,16,128")
  else:
    _write_tuned(tune_root, {"IGNEOUS_CCL_TILE": "8,16,128"})
  assert ccl._tile_shape() == (8, 16, 128)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with pytest.raises(ValueError, match="not verified on TPU"):
    ccl._tile_shape()
  assert tune.candidates("tpu")["IGNEOUS_CCL_TILE"] == [""]


def test_tune_config_root_precedence(tune_root, monkeypatch):
  """The tuned root is IGNEOUS_TUNE_CONFIG; unset, there is no tuned
  layer."""
  _write_tuned(tune_root, {"IGNEOUS_PAGE_BATCH": "9"})
  monkeypatch.delenv("IGNEOUS_PAGE_BATCH", raising=False)
  assert tune.config_root() == tune_root
  assert tune.resolve("IGNEOUS_PAGE_BATCH") == "9"
  monkeypatch.delenv(tune.CONFIG_ENV)
  tune.reset_cache()
  assert tune.config_root() is None
  assert tune.resolve("IGNEOUS_PAGE_BATCH") is None


def test_bad_tuned_config_is_ignored(tune_root, monkeypatch):
  path = os.path.join(
    tune_root[len("file://"):], tune.TUNED_PREFIX,
    f"{tune.device_kind()}.json",
  )
  os.makedirs(os.path.dirname(path), exist_ok=True)
  with open(path, "w") as f:
    f.write("{not json")
  tune.reset_cache()
  monkeypatch.delenv("IGNEOUS_PAGE_BATCH", raising=False)
  assert tune.tuned_config() is None
  assert tune.resolve("IGNEOUS_PAGE_BATCH") is None


def test_unresolved_tunable_falls_to_registry_default(monkeypatch):
  monkeypatch.delenv(tune.CONFIG_ENV, raising=False)
  monkeypatch.delenv("IGNEOUS_EDT_LINE_BLOCK", raising=False)
  tune.reset_cache()
  from igneous_tpu.ops.edt import _DEFAULT_LINE_BLOCK, _line_block

  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") is None
  assert _line_block() == _DEFAULT_LINE_BLOCK


# -- JAX's own persistent cache placement -----------------------------------

@pytest.mark.parametrize("where", ["env", "checkout", "installed"])
def test_place_jax_cache(monkeypatch, where):
  """A set JAX_COMPILATION_CACHE_DIR stays as JAX read it; else a checkout
  caches in <checkout>/.jax_cache and an installed package keeps JAX's
  default (no cache)."""
  import jax

  keys = ("jax_compilation_cache_dir",
          "jax_persistent_cache_min_compile_time_secs")
  before = {k: getattr(jax.config, k) for k in keys}
  monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                     raising=False)
  monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  try:
    if where == "env":
      # JAX read the variable itself at import; placement leaves it alone
      monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
      jax.config.update("jax_compilation_cache_dir", "/elsewhere/cache")
      assert cc.place_jax_cache() == "/elsewhere/cache"
    elif where == "checkout":
      assert cc.checkout_root() == repo
      assert cc.place_jax_cache() == os.path.join(repo, ".jax_cache")
    else:
      monkeypatch.setattr(cc, "checkout_root", lambda: None)
      assert cc.place_jax_cache() is None
      assert jax.config.jax_compilation_cache_dir == before[keys[0]]
    if where != "installed":
      assert jax.config.jax_compilation_cache_dir == cc.place_jax_cache()
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
  finally:
    for k, v in before.items():
      jax.config.update(k, v)


def test_place_jax_cache_before_jax_import(tmp_path):
  """Before JAX is imported placement only sets the variables JAX reads
  at import: the CLI's group callback imports no JAX for commands that
  never compile."""
  import subprocess
  import sys

  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  env = {k: v for k, v in os.environ.items()
         if not k.startswith("JAX_COMPILATION_CACHE")
         and k != "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"}
  env["PYTHONPATH"] = repo
  code = (
    "import sys\n"
    "from igneous_tpu.compile_cache import place_jax_cache\n"
    "d = place_jax_cache()\n"
    "assert 'jax' not in sys.modules\n"
    "import jax\n"
    "print(d == jax.config.jax_compilation_cache_dir,\n"
    "      jax.config.jax_persistent_cache_min_compile_time_secs)\n"
  )
  out = subprocess.run(
    [sys.executable, "-c", code], env=env, cwd=tmp_path,
    capture_output=True, text=True, timeout=120,
  )
  assert out.returncode == 0, out.stderr
  assert out.stdout.split() == ["True", "0.0"]


_ROUND_TRIP = """
import json, sys
import numpy as np
from igneous_tpu.compile_cache import place_jax_cache
place_jax_cache()
import jax
from igneous_tpu.parallel.executor import ChunkExecutor, make_mesh

hits = []
jax.monitoring.register_event_listener(
  lambda event, **kw: hits.append(event)
  if event == "/jax/compilation_cache/cache_hits" else None)

method = sys.argv[1]
planes = 2 if method == "mode" else 1
rng = np.random.default_rng(0)
data = rng.integers(0, 7, (2, 1, 4, 16, 16))
ex = ChunkExecutor(make_mesh(2), factors=((2, 2, 1), (2, 2, 1)),
                   method=method, planes=planes)
if planes == 2:
  labels = data.astype(np.uint64) * np.uint64(2**40 + 3)
  mips, _ = ex(((labels & np.uint64(0xFFFFFFFF)).astype(np.uint32),
                (labels >> np.uint64(32)).astype(np.uint32)))
  mips = [lo.astype(np.uint64) | (hi.astype(np.uint64) << np.uint64(32))
          for lo, hi in mips]
else:
  mips, _ = ex(data.astype(np.uint8))
print(json.dumps({"hits": len(hits), "mips": [m.tolist() for m in mips]}))
"""


@pytest.mark.parametrize("method", ["average", "mode"])
def test_jax_cache_round_trip(tmp_path, method):
  """A ChunkExecutor pyramid (uint8 average; uint64 mode as uint32 plane
  pairs) lands in JAX's cache under its program's module name, and a
  second process at the same signature loads it: a cache hit, no new
  entry, the same mips."""
  repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  cache = tmp_path / "jax_cache"
  env = {k: v for k, v in os.environ.items()
         if not k.startswith("JAX_COMPILATION_CACHE")
         and k != "JAX_ENABLE_COMPILATION_CACHE"}
  env.update(JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(cache),
             PYTHONPATH=repo)

  def run():
    out = subprocess.run(
      [sys.executable, "-c", _ROUND_TRIP, method], env=env, cwd=tmp_path,
      capture_output=True, text=True, timeout=300,
    )
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])

  first = run()
  entries = sorted(os.listdir(cache))
  assert any(
    e.startswith(f"jit_pooling_pyramid_{method}") for e in entries
  ), entries
  second = run()
  assert sorted(os.listdir(cache)) == entries
  assert second["mips"] == first["mips"]
  assert first["hits"] == 0 and second["hits"] >= 1
