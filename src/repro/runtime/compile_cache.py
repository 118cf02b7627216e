"""JAX's persistent compilation cache at one fixed place per checkout.

`enable()` is called once at start-up by every command-line entry point
(`chip_smoke.py`, `repro.launch.tune`, the tuning-service CLI and the
`benchmarks/` mains), never at import.  Where `JAX_COMPILATION_CACHE_DIR`
is set, JAX reads it itself and nothing here names another directory;
otherwise the cache lives at `<repo>/.jax_cache` (listed in
`.gitignore`).  The path is part of each entry's key, so it is fixed:
never temporary, per-process or per-run.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
