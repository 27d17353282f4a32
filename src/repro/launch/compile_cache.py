"""JAX's persistent compilation cache, kept at one fixed place.

A cold TPU process compiles every served program (each op of each
strategy, ingest, compaction).  The cache lets later processes reuse those
programs.  Its directory is part of what makes an entry hit, so it never
moves between runs: ``JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it
itself), otherwise ``<repo>/.jax_cache`` (listed in ``.gitignore``).
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import Mapping, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir(environ: Optional[Mapping[str, str]] = None) -> str:
    """The directory the cache uses: the environment's, else the fixed one."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory.  Sets no
    directory of its own when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
