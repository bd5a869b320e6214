"""Source layers made from a seed, cached per (configuration, size, seed).

A layer is built once into ``<cache>/<key>/layer`` and marked complete
last, so a build cut short is built again. A repeated seed in a check
finds its layer and skips generation. Only the ``keep`` layers used last
stay on disk.
"""

from __future__ import annotations

import os
import shutil

DONE = ".complete"


def cached_layer(cache_dir: str, key: str, build, keep: int):
  """(layer directory, True when it was built now). ``build(layer_dir)``
  writes the layer."""
  entry = os.path.join(cache_dir, key)
  layer = os.path.join(entry, "layer")
  done = os.path.join(entry, DONE)
  if os.path.exists(done):
    os.utime(done)
    return layer, False
  shutil.rmtree(entry, ignore_errors=True)
  os.makedirs(entry)
  build(layer)
  with open(done, "w"):
    pass
  _evict(cache_dir, keep)
  return layer, True


def _evict(cache_dir: str, keep: int) -> None:
  entries = []
  for name in os.listdir(cache_dir):
    path = os.path.join(cache_dir, name)
    done = os.path.join(path, DONE)
    mtime = os.path.getmtime(done) if os.path.exists(done) else 0.0
    entries.append((mtime, path))
  for _mtime, path in sorted(entries, reverse=True)[keep:]:
    shutil.rmtree(path, ignore_errors=True)
