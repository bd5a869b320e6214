"""Where JAX's persistent compilation cache lives.

JAX's own cache is the one persistent compile cache: every executor
compiles its programs ahead of time (``lower().compile()``), and JAX
writes each executable to the cache directory under the program's
module name (``jit_<name>-<hash>-cache``), so the next process with the
same program and signature loads it instead of compiling.

A fleet shares one cache by pointing ``JAX_COMPILATION_CACHE_DIR`` at a
shared directory; JAX opens a non-local path (``gs://...``) through
``etils[epath]``. :func:`place_jax_cache` leaves a set variable as it is.
"""

from __future__ import annotations

import os
from typing import Optional


def checkout_root() -> Optional[str]:
  """The source checkout this package runs from (``pyproject.toml``
  beside the package), or None for an installed package."""
  root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
  return root if os.path.isfile(os.path.join(root, "pyproject.toml")) else None


def place_jax_cache() -> Optional[str]:
  """Place JAX's own persistent compilation cache and return its
  directory (None: no cache). Called by the CLI, ``chip_smoke.py`` and
  ``benchmark/run.py``.

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
