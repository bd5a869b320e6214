"""Source/file discovery shared by `igneous lint` and tools/ scripts.

One walker, one noise policy: `__pycache__`, `.pyc`, VCS and cache
directories never leak into lint findings, chaos-soak byte maps, or
smoke-test digests again (ISSUE 14 satellite). tools/ scripts import
this instead of hand-rolling ``os.walk``.
"""

from __future__ import annotations

import os
from typing import Iterator, Optional, Sequence

NOISE_DIRS = frozenset({
  "__pycache__", ".git", ".pytest_cache", ".mypy_cache",
  ".ruff_cache", ".eggs", "node_modules", ".ipynb_checkpoints",
})
NOISE_SUFFIXES = (".pyc", ".pyo", ".pyd")

# lint scope: the package, repo tooling, and the root-level scripts
LINT_ROOTS = ("igneous_tpu", "tools")
LINT_ROOT_FILES = ("chip_smoke.py", "setup.py")


def walk_files(root: str,
               suffixes: Optional[Sequence[str]] = None) -> Iterator[str]:
  """Deterministic (sorted) file walk under ``root`` with the shared
  noise policy applied. ``suffixes`` optionally restricts by ending."""
  for dirpath, dirnames, filenames in os.walk(root):
    dirnames[:] = sorted(
      d for d in dirnames
      if d not in NOISE_DIRS and not d.endswith(".egg-info")
    )
    for fname in sorted(filenames):
      if fname.endswith(NOISE_SUFFIXES):
        continue
      if suffixes and not fname.endswith(tuple(suffixes)):
        continue
      yield os.path.join(dirpath, fname)


def iter_source_files(repo_root: str) -> Iterator[str]:
  """Every Python source file `igneous lint` analyzes, relative walk
  order stable across hosts. tests/ are deliberately out of scope:
  they monkeypatch env knobs and embed checker fixture snippets."""
  for sub in LINT_ROOTS:
    base = os.path.join(repo_root, sub)
    if os.path.isdir(base):
      yield from walk_files(base, suffixes=(".py",))
  for fname in LINT_ROOT_FILES:
    path = os.path.join(repo_root, fname)
    if os.path.isfile(path):
      yield path
