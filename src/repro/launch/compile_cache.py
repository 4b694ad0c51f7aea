"""JAX's persistent compilation cache for the entry points.

One rule, shared by ``launch/train.py``, ``launch/serve.py`` and
``chip_smoke.py``: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
that directory itself and nothing is set here; otherwise the cache lives
at one fixed path inside the checkout, ``<repo>/.jax_cache`` (listed in
``.gitignore``).  The path is fixed, never derived from a temporary name,
a pid or the time, so a later run of the same checkout finds the entries.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Switch the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
