"""Where JAX's persistent compilation cache lives for this program.

One function, called by `cluster.process.main` before any role starts and by
`chip_smoke.py`. The cache directory is part of every entry's key, so it must
not move between runs: it is either what the operator set in
`JAX_COMPILATION_CACHE_DIR` (JAX reads that itself — this code then sets no
directory) or the fixed `<checkout>/.jax_cache`.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-checkout default (listed in .gitignore)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def place_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory. The
    thresholds are lowered so the small scan kernels (sub-second compiles) are
    written too."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
