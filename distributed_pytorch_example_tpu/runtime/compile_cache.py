"""Where the persistent XLA compile cache lives.

One rule, for every entry point (train.py, serve.py, chip_smoke.py,
__graft_entry__.py): where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and this module does nothing at all; where it
is not, the cache is ``.jax_cache/`` at the root of the checkout. The path
is part of the cache key's lookup, so it is fixed — never built from a
temp dir, a pid or the time — and nothing else in the repo sets one.
"""

from __future__ import annotations

import os

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it.

    Call before the first compile. A first GPT-2 124M train-step compile
    is tens of seconds on a v5e; a second process (a ``--resume``, the
    next bench model) then loads the executable instead of recompiling.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed  # JAX's own variable: it has already read it
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    return DEFAULT_CACHE_DIR
