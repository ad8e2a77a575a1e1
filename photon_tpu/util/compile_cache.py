"""The persistent XLA compile cache: one function, called at every entry
point (the three drivers, bench workers, ``chip_smoke.py``).

A cold fit compiles every sweep and score program, and nothing compiled
survives the process. The cache directory is part of an entry's key, so
it is a fixed path, never a temporary name: ``JAX_COMPILATION_CACHE_DIR``
where that is set (JAX reads it by itself), else ``.jax_cache`` at the
root of the checkout.
"""
from __future__ import annotations

import os

from photon_tpu import obs

#: ``<checkout>/.jax_cache`` (listed in .gitignore)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_persistent_cache() -> str:
    """Turn on jax's persistent compilation cache and return its
    directory. A directory that is already configured — by
    ``JAX_COMPILATION_CACHE_DIR`` or by the embedding process (the test
    harness) — is left as it is."""
    import jax

    with obs.span("compile_cache.enable", cat="setup") as sp:
        if jax.config.jax_compilation_cache_dir is None:
            jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
        sp.set(directory=jax.config.jax_compilation_cache_dir)
        return jax.config.jax_compilation_cache_dir
