"""Shared source/artifact discovery for every lint that walks the repo.

Before ISSUE 18 three call sites hand-rolled their own "walk the package
source files" loop (the stage-name grep lint, the metric-name grep lint,
the committed-artifact schema lint) with three subtly different exclude
lists.  This module is the ONE iterator they all share: the analyzer,
the test shims and bench tooling see the same file set by construction.
"""

from __future__ import annotations

import glob
import os
from typing import Iterator, List, Sequence

#: directory names never descended into when walking package sources
EXCLUDE_DIRS = frozenset({
    "__pycache__", ".git", ".pytest_cache", ".ipynb_checkpoints",
})

#: top-level driver scripts that carry lintable literals (metric names,
#: config keys) but live outside the package directory
TOP_LEVEL_SCRIPTS = ("bench.py", "bench_configs.py")


def package_root() -> str:
    """Absolute path of the ``cluster_tools_tpu`` package directory."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def repo_root() -> str:
    """Absolute path of the repository checkout (the package's parent)."""
    return os.path.dirname(package_root())


def iter_source_files(root: str | None = None,
                      include_scripts: bool = True) -> Iterator[str]:
    """Yield every ``.py`` file of the package (sorted, exclude-list
    honored), then the known top-level scripts.  ``root`` overrides the
    package directory (fixture corpora in tests)."""
    base = root or package_root()
    for dirpath, dirnames, filenames in os.walk(base):
        dirnames[:] = sorted(d for d in dirnames if d not in EXCLUDE_DIRS)
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                yield os.path.join(dirpath, fn)
    if include_scripts and root is None:
        for script in TOP_LEVEL_SCRIPTS:
            path = os.path.join(repo_root(), script)
            if os.path.exists(path):
                yield path


def source_files(root: str | None = None,
                 include_scripts: bool = True) -> List[str]:
    return list(iter_source_files(root, include_scripts))


def committed_artifacts(pattern: str) -> List[str]:
    """Committed artifact files (``BENCH_*.json`` / ``TRACE_*.json`` /
    ``LINT_*.json``) matching ``pattern`` under the repo root, sorted."""
    return sorted(glob.glob(os.path.join(repo_root(), pattern)))


def relpath(path: str) -> str:
    """Repo-relative display path (what findings carry)."""
    try:
        rel = os.path.relpath(os.path.abspath(path), repo_root())
    except ValueError:          # different drive (windows) — keep absolute
        return path
    return path if rel.startswith("..") else rel
