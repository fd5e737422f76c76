"""Where compiled work is kept on disk: two fixed directories inside the
checkout, both listed in ``.gitignore``.

What a run compiles must follow from the files git would commit, not from
what an earlier session left in ``$HOME``; and a second process of the same
checkout must find the first one's work again, so neither path is ever
built from ``tempfile``, a pid or the clock.
"""
from __future__ import annotations

import os

__all__ = ["CHECKOUT", "JAX_CACHE", "PROGRAM_CACHE", "arm_compile_cache"]

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# jax's persistent compilation cache (arm_compile_cache)
JAX_CACHE = os.path.join(CHECKOUT, ".jax_cache")
# AOT-serialized executables (programs.aot) when MXNET_PROGRAM_CACHE does
# not place them elsewhere
PROGRAM_CACHE = os.path.join(CHECKOUT, ".mxnet_programs")


def arm_compile_cache():
    """Give jax's persistent compilation cache a directory and return it.

    Called first by the programs that run on the chip (``chip_smoke.py``,
    ``benchmarks/*.py`` outside ``--smoke``) — never by library import, and never by the tests (tests/conftest.py says why).
    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: jax reads
    it itself, so when it is set no directory is configured here.

    Either way the cache's key takes in the HLO metadata.  By default jax
    leaves it out, and an executable read back then carries the
    ``op_name`` of whichever build wrote it: a cache shared with another
    build of this checkout (a parent commit, say) would hand
    ``obs.programs.scope_map`` that build's layer scopes, or none."""
    import jax

    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", JAX_CACHE)
    return JAX_CACHE
