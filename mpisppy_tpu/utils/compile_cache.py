###############################################################################
# The one place that places JAX's persistent compilation cache.
#
# Entry points (the CLI, the server, bench.py, chip_smoke.py) call
# enable_compile_cache() before their first compile.  Where
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself and nothing is
# set in code; otherwise the cache lives at <repo>/.jax_cache (listed in
# .gitignore).  The path is fixed: it is part of the cache key, so a
# directory built from a temporary name, a pid or the time never hits.
###############################################################################
from __future__ import annotations

import os

#: the repository root: this file is <repo>/mpisppy_tpu/utils/
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
