"""Where compiled kernels persist between processes.

A cold process recompiles every `xjit` kernel; on the chip that is most of
a first query. JAX's persistent compilation cache keys entries by its
directory too, so the directory must not move: where the operator set
`JAX_COMPILATION_CACHE_DIR`, JAX already reads it and this module sets
nothing; where not, the cache lives at `<checkout>/.jax_cache`, resolved
from this package's own location.

Called from the entry points (`server/main.py:main`, `bench.py:main`),
never at import: importing the package must not configure JAX.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
