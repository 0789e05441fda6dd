"""Reads the timed path's own state at its first steps, inside the one
`fit` call that the window belongs to.

`fit` offers a step-boundary seam to a checkpointer (`should_save(step)`,
`save(step, state)`).  `StateProbe` takes it: after step one it works the
first gradient's norm and seeded projection (`sketch.py`) per leaf out of
the optimizer's state, and after the last followed step the norm per leaf
of the parameters' change from the seeded weights, which it remakes from
the key.  All are a few scalars computed on the device; nothing is copied
and the state is left as it is.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from benchmarks.sketch import sketches


@dataclass
class Built:
    """What a builder hands back: the trainer with its seeded state, and
    how to read that state under the reference's leaf names."""

    trainer: Any
    state: Any
    # Key -> a new seeded TrainState (the control script reads many seeds
    # through one trainer).
    fresh_state: Callable
    # Program parameter tree (or one shaped like it) -> the reference's names.
    to_reference: Callable
    # Optimizer state after step one -> the gradient the optimizer was given.
    first_gradient: Callable
    # Key -> the seeded weights under the reference's names.
    seeded: Callable


def require_same_leaves(model_params, seeded_params) -> None:
    """The seeded weights have to fit the model's own tree, leaf for leaf."""
    theirs = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), model_params)
    ours = jax.tree_util.tree_map(lambda a: (a.shape, a.dtype), seeded_params)
    if theirs != ours:
        raise ValueError(
            f"the reference's seeded weights {ours} do not match the model's tree {theirs}"
        )


def optimizer_state(opt_state, kind: type):
    """The one optax state of `kind` inside a chain's state."""
    found = [
        s for s in jax.tree_util.tree_leaves(opt_state, is_leaf=lambda s: isinstance(s, kind))
        if isinstance(s, kind)
    ]
    if len(found) != 1:
        raise ValueError(f"expected one {kind.__name__} in the optimizer state, found {len(found)}")
    return found[0]


def _norms(tree: dict) -> dict:
    return {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32)))) for k, v in tree.items()
    }


class StateProbe:
    def __init__(self, built, key: jax.Array, steps: int, on_done: Callable[[], None] = lambda: None):
        self.steps = steps
        self._on_done = on_done
        self._key = key

        def gradient(opt_state, key):
            first = built.to_reference(built.first_gradient(opt_state))
            return _norms(first), sketches(first, key)

        self._grad = jax.jit(gradient)

        def moved(params, key):
            now, start = built.to_reference(params), built.seeded(key)
            return _norms(
                {k: now[k].astype(jnp.float32) - start[k].astype(jnp.float32) for k in now}
            )

        self._moved = jax.jit(moved)
        self._grad_norm = self._grad_sketch = self._update_norm = None

    def should_save(self, step: int) -> bool:
        return step in (1, self.steps)

    def save(self, step: int, state, **_unused) -> None:
        if step == 1:
            self._grad_norm, self._grad_sketch = self._grad(state.opt_state, self._key)
        if step == self.steps:
            self._update_norm = self._moved(state.params, self._key)
            # Both of the probe's programs have compiled by now.
            self._on_done()

    def readings(self) -> dict:
        if self._grad_norm is None or self._update_norm is None:
            raise RuntimeError(f"fit ended before step {self.steps}: nothing to compare")
        return {
            "grad_norm": {k: float(v) for k, v in self._grad_norm.items()},
            "grad_sketch": {k: [float(x) for x in v] for k, v in self._grad_sketch.items()},
            "update_norm": {k: float(v) for k, v in self._update_norm.items()},
        }
