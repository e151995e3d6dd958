"""Where JAX's persistent compilation cache lives.

The directory is part of nothing's key but must not move: a cache at a
temporary, per-process or dated path never hits. So it is placed from
OUTSIDE when ``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads that variable
itself — no directory is set in code), and otherwise at one fixed path in
the checkout, ``<checkout>/.jax_cache`` (gitignored).
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` — derived from the package's own location
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache")


def place_compile_cache() -> str:
    """Turn the persistent cache on for EVERY executable (the defaults
    skip sub-second compiles and small entries — exactly the small-config
    regime where fixed costs bite) and return the directory in use. Call
    before the first compile; idempotent."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax.config.jax_compilation_cache_dir
