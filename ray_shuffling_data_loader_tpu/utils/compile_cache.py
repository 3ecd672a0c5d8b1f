"""Where compiled XLA programs are kept between processes.

A cold compile of the DLRM train step takes tens of seconds, and the
machine that holds the chip keeps nothing between runs except what lives
under the repository or where ``JAX_COMPILATION_CACHE_DIR`` points. Every
entry point that holds the chip calls :func:`enable_compile_cache` first
thing, before it compiles anything.
"""

from __future__ import annotations

import os

#: The one place that names JAX's options; tests read the config through
#: them.
CACHE_DIR_OPTION = "jax_compilation_cache_dir"
NAMES_IN_KEY_OPTION = "jax_compilation_cache_include_metadata_in_key"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads it itself and
    the directory is left alone. Otherwise the cache lives at
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what a cache entry is found by — a temporary or per-process name
    would never hit.
    """
    import jax
    # The program is read by scope (``jax.named_scope`` names in the
    # compiled step's text tell a device trace's operations apart), so the
    # names are part of what an executable is: without them in the key a
    # change that only adds or renames a scope would load the older
    # executable, whose text has the older names. Wherever the cache lives.
    jax.config.update(NAMES_IN_KEY_OPTION, True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update(CACHE_DIR_OPTION, path)
    # Keep every program, not only those that took a second to compile: a
    # fresh process on the sealed machine otherwise recompiles dozens of
    # small ones, and whether a borderline program is kept would depend on
    # how long its compile happened to take.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
