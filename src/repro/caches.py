"""Where the repository keeps what it caches: inside its own checkout.

* :func:`repo_cache_path` — the autotuner's tilings and the activation
  calibration live under ``<repo>/.cache/`` (listed in ``.gitignore``).
* :func:`enable_compile_cache` — JAX's persistent compilation cache.
  Called by the entry points only (``launch/serve.py``,
  ``chip_smoke.py``, ``benchmarks/microbench.py``), never at import or
  from library code, so tests and ahead-of-time compiles stay uncached.
"""

from __future__ import annotations

import os
from pathlib import Path

REPO_ROOT = Path(__file__).resolve().parents[2]


def repo_cache_path(name: str) -> str:
    """``<repo>/.cache/<name>``."""
    return str(REPO_ROOT / ".cache" / name)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.  ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it
    is (JAX reads it itself) and nothing else is set.  Otherwise the
    cache goes to the fixed path ``<repo>/.jax_cache``: the path is part
    of what makes a later run find the entries, so it never moves."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


__all__ = ["REPO_ROOT", "enable_compile_cache", "repo_cache_path"]
