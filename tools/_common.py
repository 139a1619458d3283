"""Shared bootstrap for the ``tools/`` scripts.

Every CLI in this directory needs the same three lines of ceremony: pin
JAX to the CPU host platform (the scripts run on login nodes and in CI),
put the repo root on ``sys.path`` (the repo is not pip-installed), and
resolve paths relative to the repo root regardless of the caller's cwd.
The four original ``lint_*`` scripts each carried their own copy of this
block; they now share this one.

Usable both as a module (``import _common`` works when the script is run
as ``python tools/<script>.py`` — the tools dir is ``sys.path[0]``) and
via ``importlib`` for callers loading scripts by path.
"""

from __future__ import annotations

import os
import sys


def repo_root() -> str:
    """Absolute path of the repository root (the parent of ``tools/``)."""
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def bootstrap(chdir: bool = False) -> str:
    """Standard script setup; returns the repo root.

    - defaults ``JAX_PLATFORMS=cpu`` (a lint/CLI process never takes a
      chip: it belongs to one process at a time),
    - prepends the repo root to ``sys.path`` so ``import kfac_tpu`` works
      without installation,
    - optionally chdirs to the root for scripts that use relative paths.
    """
    os.environ.setdefault('JAX_PLATFORMS', 'cpu')
    root = repo_root()
    if root not in sys.path:
        sys.path.insert(0, root)
    if chdir:
        os.chdir(root)
    return root
