"""JAX persistent compilation cache for the repository's entry points.

Called from `main()` of `chip_smoke.py`, `examples/serve_pointcloud.py` and
`benchmarks/run.py`, never at import.  Where `JAX_COMPILATION_CACHE_DIR` is
set, JAX already reads it and nothing here overrides it.  Otherwise the
cache lives at the fixed path `<repo>/.jax_cache` (git-ignored): the path is
part of what a later run must match to hit, so it is never derived from a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
