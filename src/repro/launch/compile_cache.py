"""Where JAX's persistent compilation cache lives for this repository.

A cold compile of a deployment-size step takes minutes, and the cache only
hits when its directory stays put.  ``configure`` follows
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads that
variable itself, so nothing else is set in code); otherwise it points the
cache at the fixed ``<repo root>/.jax_cache``, which ``.gitignore`` lists.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def configure() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
