"""Per-device-kind tuned kernel configs + generate-and-measure autotuner
(ISSUE 19).

Today's ``IGNEOUS_CCL_TILE`` / ``IGNEOUS_PAGE_SHAPE`` defaults are a
one-off hand sweep frozen into the knob registry. ``igneous tune``
replaces that with generate-and-measure: sweep candidate tile shapes,
EDT line-block geometry, and page shape/batch on seeded representative
workloads, assert every candidate's output is byte-identical to the
default path (these knobs are performance-only by construction — any
divergence is a kernel bug and fails the sweep), and persist the winners
as ``tuned/<device_kind>.json`` under a config root.

Knob resolution order for the tunables, everywhere they are read::

    explicit env  >  tuned/<device_kind>.json  >  registry default

so an operator override always wins, a fleet with a published tuned
config picks it up with zero env plumbing, and everyone else keeps the
registry defaults. The config root is ``IGNEOUS_TUNE_CONFIG``; unset,
there is no tuned layer.
"""

from __future__ import annotations

import json
import re
import time
from typing import Callable, Dict, List, Optional

from .analysis import knobs

CONFIG_ENV = "IGNEOUS_TUNE_CONFIG"
TUNED_PREFIX = "tuned/"

# every knob the autotuner sweeps and resolve() serves from tuned configs
TUNABLE = (
  "IGNEOUS_CCL_TILE",
  "IGNEOUS_EDT_LINE_BLOCK",
  "IGNEOUS_PAGE_SHAPE",
  "IGNEOUS_PAGE_BATCH",
)


def device_kind() -> str:
  """Filesystem-safe device kind for the tuned-config filename (e.g.
  ``cpu``, ``TPU_v4`` → ``TPU_v4``)."""
  try:
    import jax

    dev = jax.devices()[0]
    kind = dev.device_kind or dev.platform
  except Exception:
    kind = "unknown"
  return re.sub(r"[^A-Za-z0-9._-]+", "_", str(kind)).strip("_") or "unknown"


def config_root() -> Optional[str]:
  return knobs.get_str(CONFIG_ENV) or None


# [loaded?, config-or-None]: the tuned config is read at most once per
# process — knob resolution sits on hot paths (every page_shape() call)
_CONFIG: list = [False, None]


def tuned_config() -> Optional[dict]:
  """The active ``tuned/<device_kind>.json``; None when no config root
  is set, the file is absent, or it fails to parse — a bad tuned config
  must never take a worker down."""
  if _CONFIG[0]:
    return _CONFIG[1]
  cfg = None
  root = config_root()
  if root:
    try:
      from .storage import CloudFiles

      cfg = CloudFiles(root).get_json(f"{TUNED_PREFIX}{device_kind()}.json")
      if cfg is not None and not isinstance(cfg.get("knobs"), dict):
        cfg = None
    except Exception:
      cfg = None
  _CONFIG[0], _CONFIG[1] = True, cfg
  return cfg


def reset_cache() -> None:
  """Testing hook: forget the loaded tuned config."""
  _CONFIG[0], _CONFIG[1] = False, None


def resolve(name: str) -> Optional[str]:
  """Resolved string value of a tunable knob — explicit env > tuned
  config > None (the caller applies its registry default). Returns
  exactly what the env var would contain, so call sites keep their own
  strict parsing and error messages."""
  val = knobs.raw(name)
  if val:
    return val
  cfg = tuned_config()
  if cfg:
    tuned = cfg["knobs"].get(name)
    if tuned is not None:
      return str(tuned)
  return None


# ---------------------------------------------------------------------------
# generate-and-measure sweep


def candidates(backend: str) -> Dict[str, List[str]]:
  """Candidate values per tunable knob, per backend family. The empty
  string means "registry default" and is always swept first — it is the
  byte-identity reference AND the baseline the winner must beat."""
  if backend == "tpu":
    # ccl._TPU_TILES only: the identity check below runs at a size where
    # the (8,16,128) tile still agreed, so it cannot vet other tiles
    ccl = [""]
  else:
    ccl = ["", "2,4,8", "4,8,8", "4,8,16", "8,16,16"]
  return {
    "IGNEOUS_CCL_TILE": ccl,
    "IGNEOUS_EDT_LINE_BLOCK": ["", "64", "128", "512"],
    "IGNEOUS_PAGE_SHAPE": ["", "16,16,16", "64,64,64"],
    "IGNEOUS_PAGE_BATCH": ["", "16", "64"],
  }


def _workloads(size: int) -> Dict[str, Callable[[], bytes]]:
  """Seeded representative workloads, one per knob; each returns the
  output bytes (the byte-identity oracle) and exercises the knob through
  its real resolution path. Executors are constructed INSIDE the call so
  each candidate resolves the knob fresh."""
  import numpy as np

  rng = np.random.default_rng(19)
  s = max(int(size), 16)

  ccl_batch = rng.integers(0, 5, (2, s, s, s)).astype(np.int32)

  def run_ccl() -> bytes:
    from .ops import ccl

    outs = ccl.connected_components_batch(
      ccl_batch, 6, executor=ccl._batch_executor(6)
    )
    return b"".join(np.asarray(o).tobytes() for o in outs)

  edt_batch_in = rng.integers(0, 3, (2, s, s, s)).astype(np.int32)

  def run_edt() -> bytes:
    from .ops import edt

    outs = edt.edt_batch(
      edt_batch_in, (1.0, 1.0, 1.0),
      executor=edt.batch_edt_executor((1.0, 1.0, 1.0)),
    )
    return b"".join(np.asarray(o).tobytes() for o in outs)

  ragged = [
    rng.integers(0, 255, (s, s - 7, s // 2 + 1)).astype(np.uint8),
    rng.integers(0, 255, (s // 2, s // 2, s // 2)).astype(np.uint8),
    rng.integers(0, 255, (s - 5, s // 3, 9)).astype(np.uint8),
  ]

  def run_paged() -> bytes:
    from .parallel import paged

    results = paged.paged_pyramid(ragged, (2, 2, 1), num_mips=2)
    return b"".join(
      np.asarray(m).tobytes() for mips in results for m in mips
    )

  return {
    "IGNEOUS_CCL_TILE": run_ccl,
    "IGNEOUS_EDT_LINE_BLOCK": run_edt,
    "IGNEOUS_PAGE_SHAPE": run_paged,
    "IGNEOUS_PAGE_BATCH": run_paged,
  }


class _env_pin:
  """Set one knob for the duration of a candidate measurement, restoring
  the previous state (including genuinely-unset) on exit."""

  def __init__(self, name: str, value: str):
    self.name, self.value = name, value

  def __enter__(self):
    self.prev = knobs.raw(self.name)
    if self.value:
      knobs.set_env(self.name, self.value)
    else:
      knobs.del_env(self.name)

  def __exit__(self, *exc):
    if self.prev is None:
      knobs.del_env(self.name)
    else:
      knobs.set_env(self.name, self.prev)


def run(
  out: Optional[str] = None,
  budget_sec: Optional[float] = None,
  repeats: Optional[int] = None,
  size: int = 48,
  only: Optional[List[str]] = None,
  strict: bool = True,
  log: Callable[[str], None] = lambda _msg: None,
) -> dict:
  """Sweep every tunable knob's candidates on this device kind and
  persist the winners.

  Per candidate: pin the env, run the workload once to warm the compile
  caches, then time ``repeats`` runs (best-of). Output bytes must equal
  the registry-default output — ``strict=True`` (the default) raises on
  any divergence, because these knobs are performance-only contracts.
  ``budget_sec`` bounds the whole sweep: when the deadline passes,
  remaining candidates are recorded as skipped and the defaults stand.

  Returns the tuned config dict (also written to
  ``<out or config root>/tuned/<device_kind>.json`` when resolvable).
  """
  import jax

  backend = jax.default_backend()
  repeats = max(
    int(repeats if repeats is not None
        else knobs.get_int("IGNEOUS_TUNE_REPEATS")), 1
  )
  if budget_sec is None:
    budget_sec = knobs.get_float("IGNEOUS_TUNE_BUDGET_SEC")
  deadline = (
    time.monotonic() + float(budget_sec) if budget_sec else None
  )
  cand = candidates(backend)
  work = _workloads(size)
  names = [n for n in TUNABLE if not only or n in only]

  winners: Dict[str, str] = {}
  measurements: Dict[str, list] = {}
  default_total = 0.0
  best_total = 0.0
  for name in names:
    fn = work[name]
    rows = []
    ref_bytes = None
    for value in cand[name]:
      if deadline is not None and time.monotonic() > deadline \
         and value != "":
        rows.append({"value": value, "skipped": "budget exhausted"})
        continue
      try:
        with _env_pin(name, value):
          got = fn()  # warmup: compiles land here, not in the timing
          best = None
          for _ in range(repeats):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            best = dt if best is None else min(best, dt)
      except ValueError as exc:
        # an incompatible candidate (page/tile divisibility) is a skip,
        # not a failure — the geometry gates are doing their job
        rows.append({"value": value, "skipped": str(exc)})
        continue
      if value == "":
        ref_bytes = got
      identical = ref_bytes is not None and got == ref_bytes
      if not identical and strict:
        raise AssertionError(
          f"{name}={value!r} output diverged from the default path — "
          "tunables must be byte-identical; refusing to tune"
        )
      rows.append({
        "value": value, "seconds": round(best, 6), "identical": identical,
      })
      log(f"{name}={value or '<default>'}: {best:.4f}s"
          f"{'' if identical else ' (NOT byte-identical!)'}")
    measurements[name] = rows
    timed = [r for r in rows if r.get("identical")]
    default_row = next((r for r in rows if r["value"] == ""), None)
    if default_row is None or "seconds" not in default_row:
      continue
    winner = min(timed, key=lambda r: r["seconds"]) if timed \
      else default_row
    default_total += default_row["seconds"]
    best_total += min(winner["seconds"], default_row["seconds"])
    if winner["value"] and winner["seconds"] < default_row["seconds"]:
      winners[name] = winner["value"]
      log(f"{name}: tuned -> {winner['value']} "
          f"({default_row['seconds']:.4f}s -> {winner['seconds']:.4f}s)")

  config = {
    "version": 1,
    "device_kind": device_kind(),
    "backend": backend,
    "jax": str(jax.__version__),
    "created": time.time(),
    "knobs": winners,
    "measurements": measurements,
    "default_s": round(default_total, 6),
    "best_s": round(best_total, 6),
    "tune_best_vs_default_ratio": (
      round(best_total / default_total, 6) if default_total else None
    ),
  }
  root = out or config_root()
  if root:
    from .storage import CloudFiles

    CloudFiles(root).put_json(
      f"{TUNED_PREFIX}{device_kind()}.json", config
    )
    config["written_to"] = f"{root}/{TUNED_PREFIX}{device_kind()}.json"
  return config
