"""One home for JAX's persistent compilation cache.

Every tool invocation is a fresh process, and a cold process compiles
every (r, k, N) kernel shape it touches plus — for placement — a CRUSH
kernel that takes minutes.  Entry points call
:func:`enable_compile_cache` before first device use so those compiles
happen once per machine, not once per command; ``traced_jit``'s AOT
``lower().compile()`` goes through the same cache as plain ``jax.jit``.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — a FIXED path (never a temp name, pid or time):
# a cache that moves between runs never hits.  Git-ignored.
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX has already taken the
    directory from it and this sets none; otherwise the cache is
    :data:`DEFAULT_DIR`.  Either way every compilation is kept: JAX's
    default skips those under a second, which is most of the codec's
    kernels, and a served path touches dozens of them.

    A process pinned to the CPU (``jax_platforms == "cpu"``: the tests,
    the CLI examples) gets no cache and ``None``: CPU compiles are cheap
    and XLA's CPU loader logs a machine-feature warning on every hit.
    Initialises no backend — a status command must not take the chip
    from the server that holds it."""
    import jax
    if jax.config.jax_platforms == "cpu":
        return None
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def entry_count(path: str | None) -> int:
    """How many cache entries ``path`` holds (0 when there is no cache or
    it does not exist yet) — what a second warm run must leave unchanged."""
    if path is None:
        return 0
    try:
        return sum(1 for e in os.scandir(path) if e.is_file())
    except FileNotFoundError:
        return 0
