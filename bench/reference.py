"""The plain reference: a float32 forward pass in straightforward jax.numpy.

It imports nothing of the program.  It reads the sizes from a configuration
file of ``bench/configs`` (its ``model`` part) and the weights that
``bench.weights`` makes from the run's seed, and computes every matmul in
float32 at ``highest`` precision, with no cache and no batching: each
sequence is one teacher-forced pass over its prompt and its served tokens.
The forward pass itself is the family's (``hidden`` and ``logits`` of
``bench/arch/<arch>.py``); this module holds the linear layer they share
and the gap functions that ``bench.check`` compares.

``precision="fp8"`` is the control: every linear layer's weights and
inputs rounded to float8 e4m3 with a scale per output channel and per
token, the step below bfloat16 that would tempt a faster program.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import arch

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the reduced axes are those the scale is shared over)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def linear(x, w, contract: int, fp8: bool):
    """x [..., in...] @ w [in..., out...] over ``contract`` leading axes of
    w, in float32 (fp8: both sides rounded first)."""
    w = w.astype(jnp.float32)
    if fp8:
        w = _fp8(w, tuple(range(contract)))
        x = _fp8(x, tuple(range(x.ndim - contract, x.ndim)))
    return jnp.tensordot(x, w, axes=contract, precision=HI)


def _served_logits(m, params, tokens, start, n_out, fp8):
    a = arch.of(m)
    h = a.hidden(m, params, tokens, fp8)
    h = jax.lax.dynamic_slice_in_dim(h, start, n_out, 0)
    return a.logits(m, params, h, fp8)


@functools.lru_cache(maxsize=None)
def _gap_fns(spec_items, n_out: int):
    m = dict(spec_items)

    @jax.jit
    def served_gaps(params, tokens, start, served):
        """How far each served token's reference logit lies below the
        reference's best, at positions start .. start + n_out - 1."""
        with jax.default_matmul_precision("highest"):
            lg = _served_logits(m, params, tokens, start, n_out, False)
        at = jnp.take_along_axis(lg, served[:, None], axis=1)[:, 0]
        return jnp.max(lg, axis=1) - at

    @jax.jit
    def control_gaps(params, tokens, start):
        """The same gap for the token that the fp8 control puts first."""
        with jax.default_matmul_precision("highest"):
            ref = _served_logits(m, params, tokens, start, n_out, False)
            ctl = _served_logits(m, params, tokens, start, n_out, True)
        pick = jnp.argmax(ctl, axis=1)
        at = jnp.take_along_axis(ref, pick[:, None], axis=1)[:, 0]
        return jnp.max(ref, axis=1) - at

    return served_gaps, control_gaps


def gap_fns(m: Dict, n_out: int):
    """(served_gaps, control_gaps), jitted for a padded length and
    ``n_out`` served positions."""
    return _gap_fns(tuple(sorted((k, v) for k, v in m.items()
                                 if not isinstance(v, (list, dict)))),
                    int(n_out))
