"""Pallas TPU kernel: Mamba-2 SSD chunked scan.

TPU-native adaptation of the SSD algorithm (arXiv:2405.21060 §6): instead of
a GPU warp-level scan, each chunk becomes dense MXU work —
  * intra-chunk: [Q, Q] decay-masked score matmul (C B^T ∘ L) @ X,
  * inter-chunk: the [P, N] state is carried in fp32 VMEM scratch across the
    chunk grid dimension (sequential 'arbitrary' axis), so the recurrence
    never leaves the core.

grid = (batch, heads, chunks); per-program blocks are one (sequence-chunk x
head) tile: x [Q, P], dt [1, Q], B/C [Q, N].  The wrapper lays the inputs out
heads-major ([B, H, S, P]) so each block is a tile of the last two dims, as
the TPU requires; A is read from SMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, init_ref,
            y_ref, final_ref, state_ref, *,
            chunk: int, nchunks: int, seq_len: int):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # [Q, P]
    dt = dt_ref[0, 0].astype(jnp.float32)            # [1, Q]
    A = a_ref[pl.program_id(1)].astype(jnp.float32)  # scalar (this head)
    Bm = b_ref[0, 0].astype(jnp.float32)             # [Q, N]
    Cm = c_ref[0, 0].astype(jnp.float32)             # [Q, N]

    # padded tail positions contribute nothing (dt = 0 -> decay 1, dBx 0)
    pos = c_idx * chunk + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    dt = jnp.where(pos < seq_len, dt, 0.0)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA = dt * A                                      # [1, Q] log-decay steps
    # inclusive prefix sum as a matmul: cum[j] = sum_{k <= j} dA[k]
    cum = jax.lax.dot_general(
        dA, jnp.where(row <= col, 1.0, 0.0), (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)          # [1, Q]
    total = jnp.sum(dA, axis=1, keepdims=True)       # [1, 1] = cum[-1]
    # row-vector -> column-vector through the diagonal (no transpose)
    diag = row == col
    cum_c = jnp.sum(jnp.where(diag, cum, 0.0), axis=1, keepdims=True)
    dt_c = jnp.sum(jnp.where(diag, dt, 0.0), axis=1, keepdims=True)
    # L[i,j] = exp(sum_{k in (j, i]} dA_k) for i >= j
    L = jnp.where(row >= col, jnp.exp(cum_c - cum), 0.0)  # [Q, Q]

    xq = x * dt_c                                    # dt folded into x
    scores = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * L      # [Q, Q]
    y = jax.lax.dot_general(scores, xq, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, P]

    # inter-chunk: contribution of the carried state
    # y_off[t, p] = exp(cum_t) * sum_n C[t, n] state[p, n]
    state = state_ref[...]                           # [P, N]
    y_off = jax.lax.dot_general(Cm, state, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y = y + y_off * jnp.exp(cum_c)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    # state update: S' = exp(cum[-1]) S + sum_t exp(cum[-1]-cum[t]) xq_t B_t^T
    xw = xq * jnp.exp(total - cum_c)                 # [Q, P]
    state_new = jnp.exp(total) * state + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # [P, N]
    state_ref[...] = state_new

    @pl.when(c_idx == nchunks - 1)
    def _final():
        final_ref[0, 0] = state_new.astype(final_ref.dtype)


def ssd_scan_kernel(x, dt, A, Bm, Cm, *, chunk: int = 256,
                    initial_state=None, interpret: bool = False):
    """x: [B, S, H, P]; dt: [B, S, H] (>=0); A: [H] (<0);
    Bm/Cm: [B, S, G, N].  Returns (y [B, S, H, P], final [B, H, P, N])."""
    B, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    chunk = min(chunk, max(S, 8))
    pad = (-S) % chunk
    # heads-major: x [B, H, S, P], dt [B, H, 1, S], B/C [B, G, S, N]
    x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0))).transpose(0, 2, 1)[:, :, None]
    Bm = jnp.pad(Bm, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    Cm = jnp.pad(Cm, ((0, 0), (0, pad), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    Sp = x.shape[2]
    nchunks = Sp // chunk
    if initial_state is None:
        initial_state = jnp.zeros((B, H, P, N), jnp.float32)

    kernel = functools.partial(_kernel, chunk=chunk, nchunks=nchunks,
                               seq_len=S)
    y, final = pl.pallas_call(
        kernel,
        grid=(B, H, nchunks),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1, 1, chunk, N),
                         lambda b, h, c, r=rep: (b, h // r, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Sp, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, dt, A, Bm, Cm, initial_state)
    return y.transpose(0, 2, 1, 3)[:, :S], final
