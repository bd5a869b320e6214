"""Fleet-wide persistent compile cache for AOT executables (ISSUE 19).

Every worker in a fleet pays the identical XLA compile tax for the same
(kernel, signature) — the ISSUE 7 recompile ledger measures exactly this
waste, and ISSUE 12's one-signature-per-kernel paging means a big
campaign compiles the *same* handful of programs once per worker. This
module serializes each AOT executable (``jax.experimental
.serialize_executable``) to any CloudFiles backend the moment worker 1
compiles it, so worker N>1 fetches instead of compiling.

Key anatomy — an entry is only valid for the exact compile context:

    kernel name + input signature (shapes/dtypes/treedef repr)
    + kernel variant (the closure config a name alone can't capture:
      pyramid factors, CCL tile/algo, EDT anisotropy/line block,
      infer model spec)
    + platform / device kind / device count / process count / mesh axes
    + jax AND jaxlib versions

All of it is digested (blake2b) into the storage key, so version skew or
a different topology is a *natural miss* — never a wrong executable.

Entry wire format::

    b"IGXC0001" | u32 header_len | header JSON | body

where the header carries the full key meta, the body's blake2b digest
and length, and the *producer's measured compile seconds* (the number a
hit credits to the fleet's compile-seconds-saved rollup), and the body
is a pickle of ``serialize_executable.serialize``'s (blob, in_tree,
out_tree) triple.

Degradation matrix — the cache can only ever fall back to compiling:

========================  =============================================
condition                 behavior
========================  =============================================
knob unset                executors compile exactly as before
entry absent              miss counter, compile, write-once put
version/topology skew     different digest → natural miss (as above)
truncated / bit-flipped   quarantined under ``quarantine/``, corrupt
entry                     counter, fallback compile re-puts a good copy
concurrent writers        write-once put (exists-check + the backend's
                          tmp+rename atomic rename) converges on one
storage backend error     error counter, fallback compile
========================  =============================================

Telemetry: ``device.compile_cache.hit|miss|put|corrupt`` counters and a
``device.compile_cache.hit`` span per fetch; a hit ticks the signature
into the ledger seen-set WITHOUT ``device.recompiles`` (warm fleets must
not trip the recompile-storm anomaly). ``igneous fleet devices`` rolls
the per-worker stats up into fleet-wide compile-seconds-saved.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import re
import time
from typing import Any, Callable, Optional, Tuple

from .analysis import knobs
from .observability import device as device_telemetry
from .observability import metrics

CACHE_ENV = "IGNEOUS_COMPILE_CACHE"
MAGIC = b"IGXC0001"
ENTRY_PREFIX = "executables/"
QUARANTINE_PREFIX = "quarantine/"
_DIGEST_SIZE = 20


class CompileCacheError(Exception):
  """An entry failed verification (magic/header/digest/meta/deserialize).
  Always recoverable: the reader quarantines and falls back to compile."""


def _sanitize(name: str) -> str:
  return re.sub(r"[^A-Za-z0-9._\[\]-]+", "_", str(name)) or "kernel"


def versions() -> Tuple[str, str]:
  import jax

  try:
    import jaxlib

    jaxlib_v = getattr(jaxlib, "__version__", "")
  except Exception:
    jaxlib_v = ""
  return str(jax.__version__), str(jaxlib_v)


def _devices(mesh) -> list:
  """The devices an executable compiled for ``mesh`` runs on: the mesh's,
  or the default device for an un-meshed program."""
  import jax

  return list(mesh.devices.flat) if mesh is not None else jax.devices()[:1]


def topology(mesh=None) -> dict:
  """Device-topology component of the cache key: an executable is only
  valid on the platform/device-kind/count (and mesh layout + process
  count) it was compiled for."""
  import jax

  try:
    devs = list(mesh.devices.flat) if mesh is not None else jax.devices()
  except Exception:
    devs = []
  try:
    procs = int(jax.process_count())
  except Exception:
    procs = 1
  topo = {
    "platform": str(devs[0].platform) if devs else "none",
    "device_kind": str(devs[0].device_kind) if devs else "none",
    "device_count": len(devs),
    "processes": procs,
  }
  if mesh is not None:
    topo["mesh_axes"] = list(mesh.axis_names)
    topo["mesh_shape"] = [int(s) for s in mesh.devices.shape]
  return topo


def entry_meta(kernel: str, signature, mesh=None, variant=None) -> dict:
  """The full cache key as a JSON-able dict (digested by entry_key)."""
  jax_v, jaxlib_v = versions()
  return {
    "kernel": str(kernel),
    "signature": repr(signature),
    "variant": repr(variant) if variant is not None else None,
    "jax": jax_v,
    "jaxlib": jaxlib_v,
    **topology(mesh),
  }


def entry_key(meta: dict) -> str:
  digest = hashlib.blake2b(
    json.dumps(meta, sort_keys=True).encode("utf8"),
    digest_size=_DIGEST_SIZE,
  ).hexdigest()
  return f"{ENTRY_PREFIX}{_sanitize(meta['kernel'])}/{digest}.bin"


def encode_entry(meta: dict, compiled, compile_s: float) -> bytes:
  """Serialize one AOT executable into the self-verifying wire format."""
  from jax.experimental import serialize_executable

  blob, in_tree, out_tree = serialize_executable.serialize(compiled)
  body = pickle.dumps(
    (blob, in_tree, out_tree), protocol=pickle.HIGHEST_PROTOCOL
  )
  header = json.dumps(
    {
      "meta": meta,
      "body_digest": hashlib.blake2b(
        body, digest_size=_DIGEST_SIZE
      ).hexdigest(),
      "body_len": len(body),
      "compile_s": round(float(compile_s), 6),
      "created": time.time(),
    },
    sort_keys=True,
  ).encode("utf8")
  return MAGIC + len(header).to_bytes(4, "big") + header + body


def decode_entry(data: bytes, meta: dict, devices=None):
  """(compiled, header) after full verification; raises CompileCacheError
  on any corruption, truncation, or key mismatch — never returns a
  partially-verified executable. ``devices``: the devices the executable
  was compiled for (default: the default device); without them JAX loads
  it onto every local device."""
  hstart = len(MAGIC) + 4
  if len(data) < hstart or data[: len(MAGIC)] != MAGIC:
    raise CompileCacheError("bad magic")
  hlen = int.from_bytes(data[len(MAGIC): hstart], "big")
  hend = hstart + hlen
  if hend > len(data):
    raise CompileCacheError("truncated header")
  try:
    header = json.loads(data[hstart:hend].decode("utf8"))
  except Exception as exc:
    raise CompileCacheError(f"unparseable header: {exc}")
  body = data[hend:]
  if len(body) != int(header.get("body_len", -1)):
    raise CompileCacheError("truncated body")
  digest = hashlib.blake2b(body, digest_size=_DIGEST_SIZE).hexdigest()
  if digest != header.get("body_digest"):
    raise CompileCacheError("body digest mismatch")
  if header.get("meta") != meta:
    # the digest key matched but the embedded meta did not: tampering or
    # a truncated-then-refilled write — never trust it
    raise CompileCacheError("key meta mismatch")
  try:
    from jax.experimental import serialize_executable

    blob, in_tree, out_tree = pickle.loads(body)
    compiled = serialize_executable.deserialize_and_load(
      blob, in_tree, out_tree, execution_devices=devices or _devices(None),
    )
  except Exception as exc:
    raise CompileCacheError(f"deserialize failed: {exc}")
  return compiled, header


class CompileCache:
  """Persistent executable store rooted at a CloudFiles path.

  Entries live under ``executables/<kernel>/<digest>.bin``; failed
  verifications are moved to ``quarantine/`` (self-healing: the next
  compile re-puts a good copy); tuned autotuner configs live alongside
  under ``tuned/`` (see :mod:`igneous_tpu.tune`)."""

  def __init__(self, cloudpath: str):
    from .storage import CloudFiles

    self.cloudpath = cloudpath
    self.cf = CloudFiles(cloudpath)

  def get(self, meta: dict, devices=None):
    """(compiled, header) on a fully-verified hit; None on miss. A
    corrupt or mismatched entry is quarantined and reads as a miss."""
    key = entry_key(meta)
    try:
      data = self.cf.get(key)
    except Exception:
      metrics.incr("device.compile_cache.error")
      return None
    if data is None:
      return None
    try:
      return decode_entry(bytes(data), meta, devices)
    except CompileCacheError:
      self.quarantine(key, bytes(data))
      return None

  def put(self, meta: dict, compiled, compile_s: float) -> bool:
    """Write-once publish. False when the entry already exists (another
    worker won the race — the backend's tmp+rename makes simultaneous
    writers converge on exactly one complete object) or this executable
    cannot be serialized on this backend."""
    key = entry_key(meta)
    try:
      if self.cf.exists(key):
        return False
      self.cf.put(key, encode_entry(meta, compiled, compile_s),
                  compress=None)
    except Exception:
      metrics.incr("device.compile_cache.error")
      return False
    device_telemetry.LEDGER.record_cache_event("puts")
    return True

  def quarantine(self, key: str, data: bytes) -> None:
    """Move a failed entry aside (keeps the evidence, unblocks the slot
    so the fallback compile's re-put lands a good copy)."""
    dest = QUARANTINE_PREFIX + (
      key[len(ENTRY_PREFIX):] if key.startswith(ENTRY_PREFIX) else key
    )
    try:
      self.cf.put(dest, data, compress=None)
      self.cf.delete(key)
    except Exception:
      metrics.incr("device.compile_cache.error")
    device_telemetry.LEDGER.record_cache_event("corrupt")


# [resolved knob value, CompileCache-or-None]: one instance per process
# per cache root; re-resolved when the knob changes (tests).
_ACTIVE: list = [None, None]


def get_active() -> Optional[CompileCache]:
  spec = knobs.get_str(CACHE_ENV)
  if not spec:
    _ACTIVE[0] = _ACTIVE[1] = None
    return None
  if _ACTIVE[0] != spec:
    try:
      cache = CompileCache(spec)
    except Exception:
      metrics.incr("device.compile_cache.error")
      cache = None
    _ACTIVE[0], _ACTIVE[1] = spec, cache
  return _ACTIVE[1]


def reset_active() -> None:
  """Testing hook: drop the process's resolved cache instance."""
  _ACTIVE[0] = _ACTIVE[1] = None


def load_or_compile(
  kernel: str,
  signature,
  mesh,
  compile_fn: Callable[[], Any],
  variant=None,
):
  """The executors' single AOT compile entry point.

  With no cache configured — or no declared ``variant`` (the closure
  config that disambiguates same-name-same-signature kernels; a site
  that can't state its variant must not share executables) — this is
  exactly the pre-cache behavior: recompile tick + ``device.compile``
  span around ``compile_fn()``.

  With a cache: a verified hit deserializes the stored executable,
  enters the signature into the ledger seen-set *without* ticking
  ``device.recompiles`` (satellite: warm fleets must not trip the
  recompile-storm anomaly), ticks ``device.compile_cache.hit``, credits
  the producer's measured compile seconds as saved, and emits a
  ``device.compile_cache.hit`` span instead of ``device.compile``. Any
  miss/corruption/skew compiles as before, then publishes write-once.
  """
  cache = get_active() if variant is not None else None
  meta = None
  if cache is not None:
    try:
      meta = entry_meta(kernel, signature, mesh=mesh, variant=variant)
      t0 = time.perf_counter()
      hit = cache.get(meta, _devices(mesh))
      if hit is not None:
        compiled, header = hit
        fetch_s = time.perf_counter() - t0
        saved_s = float(header.get("compile_s") or 0.0)
        device_telemetry.LEDGER.note_signature(
          kernel, signature, cached=True
        )
        device_telemetry.LEDGER.record_cache_event(
          "hits", kernel=kernel, saved_s=saved_s, fetch_s=fetch_s
        )
        device_telemetry.record_span(
          "device.compile_cache.hit", fetch_s, kernel=kernel,
          saved_s=saved_s,
        )
        return compiled
      device_telemetry.LEDGER.record_cache_event("misses", kernel=kernel)
    except Exception:
      metrics.incr("device.compile_cache.error")
      meta = None  # half-built key state: skip the put too
  device_telemetry.LEDGER.note_signature(kernel, signature)
  t0 = time.perf_counter()
  with device_telemetry.compile_span(
    kernel, device_telemetry._devices_of(mesh)
  ):
    compiled = compile_fn()
  if cache is not None and meta is not None:
    cache.put(meta, compiled, time.perf_counter() - t0)
  return compiled


def checkout_root() -> Optional[str]:
  """The source checkout this package runs from (``pyproject.toml``
  beside the package), or None for an installed package."""
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  return root if os.path.isfile(os.path.join(root, "pyproject.toml")) else None


def place_jax_cache() -> Optional[str]:
  """Place JAX's own persistent compilation cache and return its
  directory (None: no cache). Called by the CLI, ``chip_smoke.py`` and
  ``bench.py``.

  A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's to read and stays as JAX
  read it. Otherwise, in a checkout, the cache lives at
  ``<checkout>/.jax_cache``: a fixed path, so the next process on this
  checkout finds its entries; an installed package keeps JAX's default.
  The minimum compile time drops to zero, unless its own variable is
  set, so the pyramid, CCL, EDT and marching-cubes executables, which
  compile in about a second each, are written too.

  Imports no JAX, so a command that never compiles pays nothing: before
  JAX is imported the settings go into the variables JAX reads at
  import, after it into ``jax.config``."""
  import sys

  settings = {}
  cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR") or None
  if cache_dir is None and checkout_root() is not None:
    cache_dir = os.path.join(checkout_root(), ".jax_cache")
    settings["jax_compilation_cache_dir"] = cache_dir
  if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
    settings["jax_persistent_cache_min_compile_time_secs"] = 0.0
  jax = sys.modules.get("jax")
  for name, value in settings.items():
    if jax is not None:
      jax.config.update(name, value)
    else:
      os.environ[name.upper()] = str(value)
  return cache_dir
