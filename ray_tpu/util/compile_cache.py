"""Where JAX's persistent compilation cache lives.

One rule for every process of the program — the TPU worker, the bench
children, the probe scripts, the test session: ``JAX_COMPILATION_CACHE_DIR``
where the caller set it, else ``<checkout>/.jax_cache`` (git-ignored).
The path is part of what a cache entry is found by, so it is never a
temporary directory, a pid or a time. Nothing here imports jax at
module load: the head and CPU-only workers stay off the device runtime.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = str(Path(__file__).resolve().parents[2] / ".jax_cache")


def cache_dir() -> str:
    """The directory in effect (not created)."""
    return os.environ.get(ENV_VAR) or _DEFAULT


def enable() -> str:
    """Point this process's jax at :func:`cache_dir` (created if
    missing) and return it. Call before the first compile."""
    import jax

    path = cache_dir()
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
