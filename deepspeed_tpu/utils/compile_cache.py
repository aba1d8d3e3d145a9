"""Where jax's persistent compilation cache lives.

Every entry script (``chip_smoke.py``, ``__graft_entry__.py``)
and the test harness call :func:`place_compile_cache` before their first
compile.  The rule is the on-chip-measurement guide's: whoever runs the
program places the cache from outside with ``JAX_COMPILATION_CACHE_DIR``,
and then the code sets nothing; otherwise it is a FIXED directory in the
checkout — the path is part of each entry's key, so a directory that moves
(a temp name, a pid, a date) never hits.
"""

from __future__ import annotations

import os
from typing import Optional

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def place_compile_cache() -> Optional[str]:
    """Point jax at ``<checkout>/.jax_cache`` unless the environment already
    placed the cache.  Returns the directory set here, or None when
    ``JAX_COMPILATION_CACHE_DIR`` decides (jax reads that itself)."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return None
    import jax

    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
