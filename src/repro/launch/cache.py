"""JAX's persistent compilation cache for the entry points.

The solve programs (while-loops over the whole rule sweep) take long to
compile, so every entry point keeps compiled programs on disk:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads it itself; no other
    directory is set here.
  * unset — ``<repo>/.jax_cache``, a fixed path inside the checkout (the
    path is part of the cache key, so it must not move between runs;
    listed in ``.gitignore``).

Either way the cache is keyed on the programs' op metadata too: the
programs name their layers with ``jax.named_scope`` (``mwis.*``), names
that live only in that metadata, and JAX leaves metadata out of the key by
default, so a program compiled before its scopes changed would come back
from the cache with the old names.  Source paths in the metadata are cut
to the checkout, so the same code in another directory still hits.

Call :func:`enable_compile_cache` before the first compilation.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The checkout's own cache directory (used when ENV is unset).
REPO_CACHE = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache")
)


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      r"^.*/(?=src/)")
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE)
    return REPO_CACHE
