"""Where JAX's persistent compilation cache lives, for the entry points.

The 16-layer serving and training programs take minutes to compile cold,
and every fresh process would pay that again.  The entry points
(``chip_smoke.py``, ``benchmark/run.py``,
``python -m paddle_tpu.serving.worker``) call :func:`enable_compile_cache`
once, before their first compile; ``import paddle_tpu`` never does — a
library import must not decide where a process writes.

The directory is part of the cache key, so it is placed from outside when
``JAX_COMPILATION_CACHE_DIR`` is set (JAX reads the variable itself; this
module then sets nothing in code) and otherwise sits at ONE fixed path
inside the checkout — never built from a temporary name, a pid or the
time, which would make every run a miss.  What a program is CALLED is part
of the key too (``enable_compile_cache``): a cache hit must not bring back
another version's operation names.
"""
from __future__ import annotations

import os

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: the fixed in-checkout location (listed in .gitignore)
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compilation cache on; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already taken the
    directory from the environment and nothing is set here."""
    import jax

    # an executable found in the cache carries the names and source lines of
    # whoever compiled it: with them left out of the key (JAX's default) a
    # profile shows a renamed or re-scoped program under its OLD op names
    # (seen on the chip, PR 26: the named scopes of observability.trace
    # missing from every operation of a cache hit).  A trace has to name
    # the code that ran, so the names are part of the key; the price is a
    # recompile when a traced file's lines move.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
