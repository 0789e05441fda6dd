"""Where the persistent XLA compilation cache lives.

A warm cache takes most of the compile out of template-to-first-step on
every run after the first, but only if the directory is the same on the
next run: it is part of where JAX looks, so a path built from a temp dir,
a pid or a clock never hits.  One rule, applied by every entry point that
compiles (``maybe_init_distributed``, ``bench.py``, ``dlcfn serve``,
``chip_smoke.py``, the ``scripts/chip_*.py`` harnesses):

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module
  touches no cache option, so whoever placed the cache from outside
  (a machine image, a CI runner) is the only one deciding.
- unset: one fixed, git-ignored directory inside the checkout.

To run without a persistent cache (the test suite does, so a thousand
CPU programs never land in the tree), use JAX's own switch:
``JAX_ENABLE_COMPILATION_CACHE=false``.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache (see module docstring);
    must run before the first compilation.  Returns the directory in
    effect."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
