"""Shared chunk decode cache: compressed-domain residency for hot reads.

The e2e profile pins the codec wall: single-thread inflate at
~350-600 MB/s plus the chunk decode bounds every read path, while the same
chunks are fetched repeatedly — the pipeline prefetch pool, the lease
batcher's cutout prefetch, and overlapping task cutouts all re-download
and re-decode bytes a sibling just produced. This module keeps DECODED
chunks in one process-wide LRU so a repeated read costs a digest instead
of an inflate + codec pass (Palace, arXiv:2509.26213, makes the same
residency argument for accelerator pipelines).

Keying — correctness without coordination: entries are keyed by
``(layer path, mip, chunk bbox, digest of the STORED bytes)``. The digest
is computed over the wire bytes each time they are fetched, so a chunk
overwritten by a concurrent writer simply never matches a stale entry —
a hit is always byte-equivalent to decoding what storage currently holds.
Explicit ``invalidate(path, mip)`` (wired into Volume.upload/delete, the
pipeline runner's write joins, and the lease batcher's round fencing —
the same (path, mip) write-fencing discipline PR 3's review established)
is memory hygiene: it frees doomed entries early, it is not what keeps
reads correct.

Budget: a byte budget carved from the staged pipeline's buffer solver
(``IGNEOUS_PIPELINE_MEM_MB``-derived) so the cache and the stage buffers
are reasoned about together:

  IGNEOUS_CHUNK_CACHE      on|off|auto   master switch (auto = on)
  IGNEOUS_CHUNK_CACHE_MB   int           byte budget override
                                         (default: pipeline budget / 8)

Entries are stored read-only (``writeable=False``); consumers copy voxels
into their own cutout assembly, never mutate the cached array.
"""

from __future__ import annotations

import hashlib
import threading
from collections import OrderedDict
from typing import Iterable, Optional, Tuple

import numpy as np

from . import telemetry
from .analysis import knobs, racecheck


def enabled() -> bool:
  val = knobs.get_str("IGNEOUS_CHUNK_CACHE").strip().lower()
  if val in ("0", "off", "false", "no"):
    return False
  return True


def budget_bytes() -> int:
  mb = knobs.get_float("IGNEOUS_CHUNK_CACHE_MB")
  if mb:
    return max(int(mb * 1e6), 1)
  from .pipeline import config

  return max(config.memory_budget_bytes() // 8, 1)


def digest(data: bytes) -> bytes:
  """Digest of the STORED (wire) bytes — the part of the key that makes
  concurrent writers safe without coordination."""
  return hashlib.blake2b(data, digest_size=16).digest()


class ChunkDecodeCache:
  """Byte-budgeted LRU of decoded chunks, keyed on stored-bytes digests."""

  def __init__(self, budget: Optional[int] = None):
    self._budget = budget
    self._lock = threading.Lock()
    self._entries = racecheck.guard(  # guarded-by: self._lock
      OrderedDict(), self._lock, "ChunkDecodeCache._entries")
    self._by_layer = racecheck.guard(  # guarded-by: self._lock
      {}, self._lock, "ChunkDecodeCache._by_layer")
    self._bytes = 0  # guarded-by: self._lock

  @property
  def budget(self) -> int:
    return self._budget if self._budget is not None else budget_bytes()

  def make_key(self, path: str, mip: int, bbox_key, stored: bytes) -> tuple:
    # rstrip matches PrecomputedMetadata's cloudpath normalization, so
    # task-parameter paths and Volume-normalized paths address the same
    # entries (both key and invalidation sides use this)
    return (path.rstrip("/"), int(mip), bbox_key, digest(stored))

  def get(self, key: tuple) -> Optional[np.ndarray]:
    with self._lock:
      arr = self._entries.get(key)
      if arr is None:
        telemetry.incr("chunk_cache.misses")
        return None
      self._entries.move_to_end(key)
    telemetry.incr("chunk_cache.hits")
    telemetry.incr("chunk_cache.bytes_saved", int(arr.nbytes))
    return arr

  def put(self, key: tuple, arr: np.ndarray) -> np.ndarray:
    """Insert; returns the READ-ONLY view actually cached (callers hand
    that view out so no writable alias of a cached entry escapes)."""
    nbytes = int(arr.nbytes)
    arr = arr.view()
    arr.flags.writeable = False
    if nbytes > self.budget:
      return arr  # one oversized chunk must not wipe the working set
    with self._lock:
      old = self._entries.pop(key, None)
      if old is not None:
        self._bytes -= int(old.nbytes)
      self._entries[key] = arr
      self._by_layer.setdefault((key[0], key[1]), set()).add(key)
      self._bytes += nbytes
      while self._bytes > self.budget and self._entries:
        self._evict_oldest_locked()
      telemetry.gauge_max("chunk_cache.bytes", self._bytes)
    return arr

  def _evict_oldest_locked(self) -> None:
    old_key, old_arr = self._entries.popitem(last=False)
    self._bytes -= int(old_arr.nbytes)
    layer = self._by_layer.get((old_key[0], old_key[1]))
    if layer is not None:
      layer.discard(old_key)
      if not layer:
        self._by_layer.pop((old_key[0], old_key[1]), None)
    telemetry.incr("chunk_cache.evicted")

  def invalidate(self, path: str, mip: Optional[int] = None) -> int:
    """Drop every entry of (path, mip) — or of all mips when ``mip`` is
    None. Returns the number of entries dropped."""
    path = path.rstrip("/")
    with self._lock:
      if mip is None:
        layers = [k for k in self._by_layer if k[0] == path]
      else:
        layers = [(path, int(mip))]
      dropped = 0
      for layer in layers:
        for key in self._by_layer.pop(layer, ()):
          arr = self._entries.pop(key, None)
          if arr is not None:
            self._bytes -= int(arr.nbytes)
            dropped += 1
    if dropped:
      telemetry.incr("chunk_cache.invalidated", dropped)
    return dropped

  def clear(self) -> None:
    with self._lock:
      self._entries.clear()
      self._by_layer.clear()
      self._bytes = 0

  @property
  def nbytes(self) -> int:
    with self._lock:
      return self._bytes

  def __len__(self) -> int:
    with self._lock:
      return len(self._entries)


_SHARED: Optional[ChunkDecodeCache] = None
_SHARED_LOCK = threading.Lock()

# Invalidation fan-out (ISSUE 9 satellite): the decode cache is no longer
# the only consumer of "this (path, mip) was just rewritten" — the serve
# tier's stored-bytes tiers (RAM/SSD) key entries by layer+chunk and must
# drop them on overwrite/delete. Rather than having serve reach into
# Volume internals, `invalidate()` below is THE shared entry point:
# Volume.upload/delete, the pipeline runner's write joins, and serve's
# own write-back all call it, and every registered hook hears about it.
_INVALIDATION_HOOKS: list = []
_HOOKS_LOCK = threading.Lock()


def register_invalidation_hook(fn) -> None:
  """Register ``fn(path, mip_or_None)`` to be called on every
  ``invalidate()``/``invalidate_writes()``. Hooks must be fast and must
  not raise (failures are counted, never propagated)."""
  with _HOOKS_LOCK:
    if fn not in _INVALIDATION_HOOKS:
      _INVALIDATION_HOOKS.append(fn)


def unregister_invalidation_hook(fn) -> None:
  with _HOOKS_LOCK:
    try:
      _INVALIDATION_HOOKS.remove(fn)
    except ValueError:
      pass


def _notify_hooks(path: str, mip: Optional[int]) -> None:
  with _HOOKS_LOCK:
    hooks = list(_INVALIDATION_HOOKS)
  for fn in hooks:
    try:
      fn(path, mip)
    except Exception:
      telemetry.incr("chunk_cache.hook_failed")


def shared_cache() -> ChunkDecodeCache:
  global _SHARED
  with _SHARED_LOCK:
    if _SHARED is None:
      _SHARED = ChunkDecodeCache()
    return _SHARED


def lookup(path: str, mip: int, bbox_key, stored: bytes):
  """(key, decoded chunk or None). The key is returned either way so a
  miss can ``store`` its decode under the digest already computed."""
  cache = shared_cache()
  key = cache.make_key(path, mip, bbox_key, stored)
  return key, cache.get(key)


def store(key: tuple, arr: np.ndarray) -> np.ndarray:
  return shared_cache().put(key, arr)


def invalidate(path: str, mip: Optional[int] = None) -> int:
  # hooks fire even when the decode cache was never instantiated: a
  # serve tier may be the only cache alive in this process
  _notify_hooks(path, mip)
  if _SHARED is None:
    return 0
  return _SHARED.invalidate(path, mip)


def invalidate_writes(writes: Iterable[Tuple[str, int]]) -> None:
  """Invalidate a StagePlan-style set of (layer path, mip) writes."""
  for path, mip in writes:
    _notify_hooks(path, mip)
    if _SHARED is not None:
      _SHARED.invalidate(path, mip)


def clear() -> None:
  if _SHARED is not None:
    _SHARED.clear()
