"""Process-level JAX setup shared by the entry points and spawned children.

Compile cache.  Every entry point (``launch/train.py`` and
``launch/serve.py`` ``main``, ``chip_smoke.py``) calls
``enable_compile_cache`` before it compiles — never on import.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, that directory is the cache and
no other path is set.  Otherwise the cache sits at one fixed,
git-ignored path inside the checkout: the path is part of what the
cache is keyed on, so a directory that moved between runs would never
hit.

Child platform.  A chip belongs to one process at a time, and the
process that builds the parameter server holds it.  The worker,
replica and server processes it spawns therefore pin themselves to
``CHILD_PLATFORM`` before importing jax: a child that inherited the
parent's ``JAX_PLATFORMS`` would compete for the chip the parent holds,
and one that found no setting would compute wherever JAX guessed.
"""

from __future__ import annotations

import os
from pathlib import Path

#: The fixed in-checkout cache directory (listed in ``.gitignore``).
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

#: Where spawned children compute: the parent holds the accelerator.
CHILD_PLATFORM = "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def pin_child_platform() -> None:
    """Fix a spawned child's JAX platform.  Call before importing jax."""
    os.environ["JAX_PLATFORMS"] = CHILD_PLATFORM
