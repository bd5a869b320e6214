"""Persistent compile cache + autotuner config resolution (ISSUE 19).

Key-hygiene contracts: version skew and topology mismatch must read as
natural misses (different digests), corruption must quarantine and fall
back to compile, concurrent writers must converge on one complete entry,
and a warm hit must tick ``device.compile_cache.hit`` — never
``device.recompiles``.
"""

import copy
import json
import os

import numpy as np
import pytest

from igneous_tpu import compile_cache as cc
from igneous_tpu import tune
from igneous_tpu.observability import device as device_mod
from igneous_tpu.parallel.executor import (
  BatchKernelExecutor, LRUCache, make_mesh,
)


@pytest.fixture
def cache_root(tmp_path, monkeypatch):
  root = f"file://{tmp_path}/cc"
  monkeypatch.setenv(cc.CACHE_ENV, root)
  cc.reset_active()
  tune.reset_cache()
  device_mod.reset()
  yield root
  cc.reset_active()
  tune.reset_cache()
  device_mod.reset()


def _meta(**overrides):
  mesh = make_mesh(2)
  meta = cc.entry_meta(
    "test.kernel", (("1x2x3", "int32"),), mesh=mesh, variant=("v", 1)
  )
  meta.update(overrides)
  return meta


def _executor(mesh):
  return BatchKernelExecutor(
    lambda x: x * 2, mesh=mesh, name="test.double",
    cache_variant=("test_double",),
  )


# -- key hygiene ------------------------------------------------------------

def test_version_skew_changes_key():
  base = _meta()
  skew = _meta(jax="999.0.0")
  assert cc.entry_key(base) != cc.entry_key(skew)
  skew_lib = _meta(jaxlib="999.0.0")
  assert cc.entry_key(base) != cc.entry_key(skew_lib)


def test_topology_mismatch_changes_key():
  base = _meta()
  assert cc.entry_key(base) != cc.entry_key(_meta(device_kind="TPU v4"))
  assert cc.entry_key(base) != cc.entry_key(_meta(device_count=8))
  assert cc.entry_key(base) != cc.entry_key(_meta(processes=4))


def test_variant_and_signature_change_key():
  base = _meta()
  assert cc.entry_key(base) != cc.entry_key(_meta(variant=repr(("v", 2))))
  assert cc.entry_key(base) != cc.entry_key(
    _meta(signature=repr((("4x4x4", "uint8"),)))
  )


def test_version_skew_reads_as_miss(cache_root):
  """An entry written under different versions lands at a different key,
  so the skewed reader simply misses — never a wrong executable."""
  cache = cc.CompileCache(cache_root)
  mesh = make_mesh(2)
  ex = _executor(mesh)
  ex(np.arange(8, dtype=np.float32).reshape(2, 4))
  assert device_mod.LEDGER.compile_cache["puts"] == 1
  skewed = cc.entry_meta(
    "test.double", next(iter(ex._cache.keys())), mesh=mesh,
    variant=ex.cache_variant,
  )
  skewed["jax"] = "999.0.0"
  assert cache.get(skewed) is None
  # and nothing was quarantined by the miss
  assert device_mod.LEDGER.compile_cache["corrupt"] == 0


# -- wire format / corruption ----------------------------------------------

def _seed_entry(cache_root):
  """Compile one real executable through the executor and return
  (cache, meta, key, entry file path on disk)."""
  cache = cc.CompileCache(cache_root)
  mesh = make_mesh(2)
  ex = _executor(mesh)
  out = ex(np.arange(8, dtype=np.float32).reshape(2, 4))
  sig = next(iter(ex._cache.keys()))
  meta = cc.entry_meta(
    "test.double", sig, mesh=mesh, variant=ex.cache_variant
  )
  key = cc.entry_key(meta)
  path = os.path.join(cache_root[len("file://"):], key)
  assert os.path.exists(path)
  return cache, meta, key, path, np.asarray(out)


def test_truncated_entry_quarantines_and_misses(cache_root):
  cache, meta, key, path, _ = _seed_entry(cache_root)
  blob = open(path, "rb").read()
  with open(path, "wb") as f:
    f.write(blob[: len(blob) // 2])
  device_mod.reset()
  assert cache.get(meta) is None
  assert device_mod.LEDGER.compile_cache["corrupt"] == 1
  # the bad entry moved aside: slot is free, evidence retained
  assert not os.path.exists(path)
  qpath = os.path.join(
    cache_root[len("file://"):],
    cc.QUARANTINE_PREFIX + key[len(cc.ENTRY_PREFIX):],
  )
  assert os.path.exists(qpath)


def test_bit_flip_quarantines_and_misses(cache_root):
  cache, meta, key, path, _ = _seed_entry(cache_root)
  blob = bytearray(open(path, "rb").read())
  blob[-1] ^= 0x40  # flip one bit in the body
  with open(path, "wb") as f:
    f.write(bytes(blob))
  device_mod.reset()
  assert cache.get(meta) is None
  assert device_mod.LEDGER.compile_cache["corrupt"] == 1
  assert not os.path.exists(path)


def test_corrupt_entry_falls_back_to_compile(cache_root):
  """The chaos scenario end-to-end: a bit-flipped entry must not poison
  the fleet — the next executor quarantines, recompiles, re-puts a good
  copy, and produces identical bytes."""
  cache, meta, key, path, ref = _seed_entry(cache_root)
  blob = bytearray(open(path, "rb").read())
  blob[len(blob) // 2] ^= 0x01
  with open(path, "wb") as f:
    f.write(bytes(blob))
  device_mod.reset()
  ex2 = _executor(make_mesh(2))
  out2 = ex2(np.arange(8, dtype=np.float32).reshape(2, 4))
  np.testing.assert_array_equal(ref, np.asarray(out2))
  stats = device_mod.LEDGER.compile_cache
  assert stats["corrupt"] == 1
  assert stats["hits"] == 0
  assert stats["puts"] == 1  # the self-heal re-put
  assert os.path.exists(path)  # good copy back in place
  device_mod.reset()
  # and it verifies, loaded onto the devices it was compiled for
  assert cache.get(meta, list(make_mesh(2).devices.flat)) is not None


def test_meta_mismatch_rejected(cache_root):
  cache, meta, key, path, _ = _seed_entry(cache_root)
  data = open(path, "rb").read()
  wrong = copy.deepcopy(meta)
  wrong["jax"] = "999.0.0"
  with pytest.raises(cc.CompileCacheError, match="meta mismatch"):
    cc.decode_entry(data, wrong)


def test_decode_rejects_bad_magic():
  with pytest.raises(cc.CompileCacheError, match="magic"):
    cc.decode_entry(b"NOTMAGIC" + b"\x00" * 16, {})
  with pytest.raises(cc.CompileCacheError, match="magic"):
    cc.decode_entry(b"IG", {})


# -- concurrency ------------------------------------------------------------

def test_concurrent_writers_converge(cache_root):
  """Write-once put: the second writer of the same key backs off; exactly
  one complete entry remains and it verifies."""
  cache, meta, key, path, _ = _seed_entry(cache_root)
  devices = list(make_mesh(2).devices.flat)
  compiled, _header = cache.get(meta, devices)
  assert cache.put(meta, compiled, 1.0) is False  # already exists
  device_mod.reset()
  assert cache.get(meta, devices) is not None
  assert device_mod.LEDGER.compile_cache["corrupt"] == 0


# -- executor integration ----------------------------------------------------

def test_second_executor_hits_without_recompile_tick(cache_root):
  mesh = make_mesh(2)
  batch = np.arange(12, dtype=np.float32).reshape(2, 6)
  out1 = _executor(mesh)(batch)
  assert device_mod.LEDGER.compile_cache["puts"] == 1
  assert device_mod.LEDGER.recompiles == 1

  device_mod.reset()
  out2 = _executor(mesh)(batch)
  stats = device_mod.LEDGER.compile_cache
  assert stats["hits"] == 1
  assert stats["misses"] == 0
  # satellite 2: the persistent hit must NOT read as a recompile
  assert device_mod.LEDGER.recompiles == 0
  assert stats["saved_s"] > 0.0
  kern = device_mod.LEDGER.kernels["test.double"]
  assert kern["cache_hits"] == 1 and kern["compiles"] == 0
  np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
  np.testing.assert_array_equal(np.asarray(out2), batch * 2)


def test_no_variant_stays_compile_only(cache_root, tmp_path):
  """A site that can't declare its closure variant must not share
  executables — load_or_compile stays on the plain compile path."""
  import jax

  mesh = make_mesh(2)
  fn = jax.jit(lambda x: x + 1)
  compiled = cc.load_or_compile(
    "test.anon", ("sig",), mesh,
    lambda: fn.lower(np.zeros(4, np.float32)).compile(),
  )
  assert compiled is not None
  root = cache_root[len("file://"):]
  assert not os.path.exists(
    os.path.join(root, cc.ENTRY_PREFIX, "test.anon")
  )
  stats = device_mod.LEDGER.compile_cache
  assert stats["puts"] == 0 and stats["misses"] == 0


def test_cache_disabled_is_noop(tmp_path, monkeypatch):
  monkeypatch.delenv(cc.CACHE_ENV, raising=False)
  cc.reset_active()
  device_mod.reset()
  mesh = make_mesh(2)
  batch = np.arange(8, dtype=np.float32).reshape(2, 4)
  out = _executor(mesh)(batch)
  np.testing.assert_array_equal(np.asarray(out), batch * 2)
  stats = device_mod.LEDGER.compile_cache
  assert all(v == 0 for v in stats.values())
  assert device_mod.LEDGER.recompiles == 1


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


def test_tuned_config_applies_and_env_wins(cache_root, monkeypatch):
  monkeypatch.delenv("IGNEOUS_EDT_LINE_BLOCK", raising=False)
  _write_tuned(cache_root, {"IGNEOUS_EDT_LINE_BLOCK": "128"})
  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") == "128"
  from igneous_tpu.ops.edt import _line_block

  assert _line_block() == 128
  # explicit env always outranks the tuned config
  monkeypatch.setenv("IGNEOUS_EDT_LINE_BLOCK", "64")
  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") == "64"
  assert _line_block() == 64


@pytest.mark.parametrize("source", ["env", "tuned"])
def test_unverified_ccl_tile_refused_on_tpu(cache_root, monkeypatch, source):
  """(8,16,128) gave wrong components on a v5e: on a TPU no tile outside
  ccl._TPU_TILES is taken, from the env or a tuned config, and the tuner
  sweeps none; the CPU keeps taking it."""
  import jax

  from igneous_tpu.ops import ccl

  monkeypatch.delenv("IGNEOUS_CCL_TILE", raising=False)
  if source == "env":
    monkeypatch.setenv("IGNEOUS_CCL_TILE", "8,16,128")
  else:
    _write_tuned(cache_root, {"IGNEOUS_CCL_TILE": "8,16,128"})
  assert ccl._tile_shape() == (8, 16, 128)
  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  with pytest.raises(ValueError, match="not verified on TPU"):
    ccl._tile_shape()
  assert tune.candidates("tpu")["IGNEOUS_CCL_TILE"] == [""]


def test_tune_config_root_precedence(cache_root, tmp_path, monkeypatch):
  """IGNEOUS_TUNE_CONFIG outranks IGNEOUS_COMPILE_CACHE as config root."""
  other = f"file://{tmp_path}/tuned_only"
  monkeypatch.setenv(tune.CONFIG_ENV, other)
  _write_tuned(cache_root, {"IGNEOUS_PAGE_BATCH": "7"})
  _write_tuned(other, {"IGNEOUS_PAGE_BATCH": "9"})
  monkeypatch.delenv("IGNEOUS_PAGE_BATCH", raising=False)
  assert tune.resolve("IGNEOUS_PAGE_BATCH") == "9"


def test_bad_tuned_config_is_ignored(cache_root, monkeypatch):
  path = os.path.join(
    cache_root[len("file://"):], tune.TUNED_PREFIX,
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
  monkeypatch.delenv(cc.CACHE_ENV, raising=False)
  monkeypatch.delenv(tune.CONFIG_ENV, raising=False)
  monkeypatch.delenv("IGNEOUS_EDT_LINE_BLOCK", raising=False)
  tune.reset_cache()
  from igneous_tpu.ops.edt import _DEFAULT_LINE_BLOCK, _line_block

  assert tune.resolve("IGNEOUS_EDT_LINE_BLOCK") is None
  assert _line_block() == _DEFAULT_LINE_BLOCK


# -- fleet rollup ------------------------------------------------------------

def test_fleet_rollup_reports_cache_stats(cache_root):
  mesh = make_mesh(2)
  batch = np.arange(8, dtype=np.float32).reshape(2, 4)
  _executor(mesh)(batch)
  device_mod.reset()
  _executor(mesh)(batch)  # warm: one hit
  snap = device_mod.LEDGER.snapshot()
  assert snap["compile_cache"]["hits"] == 1
  ledgers = {"worker": snap}
  summary = device_mod.fleet_summary(ledgers)
  assert summary["compile_cache"]["hits"] == 1
  assert summary["compile_cache"]["saved_s"] > 0.0
  lines = "\n".join(device_mod.render_devices(ledgers))
  assert "compile cache" in lines and "1 hits" in lines


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
