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

The same call starts the program's own account of what compiling costs:
JAX's monitoring events, folded into ``obs.tracing`` counters under
``compile.`` (``COMPILE_COUNTERS``).  Each has a count and a total, so
"how often was something traced, lowered, compiled, read from the cache
before the first step" is a number (``Trainer.fit`` freezes them under
``first_step.`` when the first step completes).  What is cached, and
when, is not changed by it.
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from pathlib import Path

import jax

from deeplearning_cfn_tpu.obs.tracing import counter

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

#: JAX monitoring event -> counter.  The durations are seconds as JAX
#: measures them: tracing a function to a jaxpr (outermost traces only),
#: lowering the jaxpr to an
#: MLIR module, and the backend's part, which is the compile itself or, on
#: a cache hit, reading and loading the executable (``cache_read_s`` is the
#: part of that spent in the cache).  The two plain events count hits and
#: misses of the persistent cache.
COMPILE_COUNTERS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "compile.cache_read_s",
    "/jax/compilation_cache/cache_hits": "compile.cache_hit",
    "/jax/compilation_cache/cache_misses": "compile.cache_miss",
}

_counting = False
# Per thread: (start, seconds) of the traces that ended and that no later
# one has enclosed; bounded, since every eager operation adds one.
_traces: dict[int, deque[tuple[float, float]]] = {}


def _on_event(event: str, **_kw) -> None:
    name = COMPILE_COUNTERS.get(event)
    if name is not None:
        counter(name)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    name = COMPILE_COUNTERS.get(event)
    if name is None:
        return
    if name == "compile.trace_s":
        # Tracing nests: a function's trace holds the trace of every jitted
        # function it calls (ResNet-50's init reports thousands), each
        # reported when it ends, the inner ones first.  Only the outermost
        # count, or the seconds are counted many times over: what the one
        # that just ended encloses is taken back.
        start = time.perf_counter() - seconds
        ended = _traces.setdefault(threading.get_ident(), deque(maxlen=65536))
        while ended and ended[-1][0] >= start:
            counter(name, -ended.pop()[1], count=-1)
        ended.append((start, seconds))
    counter(name, seconds)


def count_compiles() -> None:
    """Register the listeners, once per process."""
    global _counting
    if not _counting:
        _counting = True
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)


def enable_compile_cache() -> str:
    """Point JAX at the persistent compile cache (see module docstring);
    must run before the first compilation.  Returns the directory in
    effect."""
    count_compiles()
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
