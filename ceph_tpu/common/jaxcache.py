"""The persistent JAX compile cache's one home.

Called by the entry points only (chip_smoke.py, bench.py, the OSD
daemon's ``__main__``, crushtool), never at import and never by tests.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other path is set here; otherwise the cache lives at a fixed
``<checkout>/.jax_cache`` (listed in .gitignore).  The path is part of
the cache key, so it never depends on a temp dir, a pid or the time.
Either way every compile is kept: JAX's default skips programs that
compiled in under a second, and the EC plans are such programs (each
a few hundred ms), so a warm process would recompile all of them.  And
the checkout's own path is cut from source locations: a Pallas kernel
embeds its Mosaic module with them, where the key's debug-info strip
does not reach, so each checkout path would otherwise key its own
entries.
"""

from __future__ import annotations

import os
import re

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable() -> str:
    """Point JAX's persistent compile cache at its directory and keep
    every compile in it; returns the directory."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(CHECKOUT + os.sep))
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
