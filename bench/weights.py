"""Random weights from ``--seed``, made on the device in one jitted call.

The benchmark makes the weights itself, in the layout the program's model
takes, so that the plain reference can make the same weights again from
the same seed and share nothing with the program.  The layout, its scales
and the tree are the family's (``shapes`` and ``nest`` of
``bench/arch/<arch>.py``); the layout is checked against the program's
own ``jax.eval_shape`` at set-up.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import arch


def root_key(seed: int):
    """A key from any non-negative seed, all 64 bits of it."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(spec_items):
    m = dict(spec_items)
    a = arch.of(m)
    table = a.shapes(m)

    @jax.jit
    def make(key):
        flat = {}
        for i, (name, (shape, dtype, std, mean)) in enumerate(
                sorted(table.items())):
            k = jax.random.fold_in(key, i)
            x = jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)
            flat[name] = x + jnp.asarray(mean, dtype) if mean else x
        return a.nest(flat)

    return make


def _spec(m: Dict):
    return tuple(sorted((k, v) for k, v in m.items()
                        if not isinstance(v, (list, dict))))


def make_params(m: Dict, seed: int, device=None):
    """The weights of seed ``seed`` on ``device`` (one jitted call)."""
    make = _maker(_spec(m))
    key = root_key(seed)
    if device is not None:
        key = jax.device_put(key, device)
    return jax.block_until_ready(make(key))


def layout(m: Dict):
    """The shapes and dtypes of ``make_params``'s tree, with nothing made."""
    return jax.eval_shape(_maker(_spec(m)), root_key(0))


def check_layout(params_shape, program_shape) -> None:
    """Raise unless our tree has the program's structure, shapes, dtypes."""
    ours = jax.tree_util.tree_structure(params_shape)
    theirs = jax.tree_util.tree_structure(program_shape)
    if ours != theirs:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"  ours   {ours}\n  theirs {theirs}")
    for a, b in zip(jax.tree.leaves(params_shape),
                    jax.tree.leaves(program_shape)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"weight leaf {a.shape} {a.dtype} differs from "
                             f"the program's {b.shape} {b.dtype}")
