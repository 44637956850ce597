"""Pallas TPU kernel: prefill causal flash attention (GQA, sliding window,
logit softcap).

Blocked online-softmax with BlockSpec VMEM tiling:
  * the wrapper lays q/k/v out heads-major ([B, H, S, D]) so every block is
    a [block, D] tile of one head — the TPU tiles the last two dims;
  * grid = (batch, q_heads, q_blocks, kv_blocks), kv innermost so fp32
    accumulators live in VMEM scratch across the kv sweep;
  * block_q x block_kv tiles sized for VMEM (defaults 512x512 ~= 1.5 MB of
    fp32 intermediates at D=128) and MXU-aligned (multiples of 128);
  * causal + sliding-window block skipping via ``pl.when`` — off-diagonal
    blocks outside the (window, causal) band cost zero MXU cycles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, out_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_kv: int, nkv: int, causal: bool,
            window: int, softcap: float, scale: float, kv_len: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * block_q
    k_start = ik * block_kv
    # band check: does this (q,k) block intersect the visible region?
    needed = k_start < kv_len
    if causal:
        needed &= k_start <= q_start + block_q - 1
    if window > 0:
        needed &= k_start + block_kv - 1 > q_start - window

    @pl.when(needed)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)             # [bq, D]
        k = k_ref[0, 0].astype(jnp.float32)             # [bk, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = kpos < kv_len
        if causal:
            valid &= kpos <= qpos
        if window > 0:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                             # [bq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nkv - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = out.astype(out_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, scale: float,
                           window: int = 0, softcap: float = 0.0,
                           block_q: int = 512, block_kv: int = 512,
                           interpret: bool = False):
    """q: [B, S, H, D]; k/v: [B, T, KVH, D] -> [B, S, H, D].
    S and T are padded to block multiples; `kv_len` masks the padded tail."""
    B, S, H, D = q.shape
    T, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    kv_len = T

    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    pad_q = (-S) % block_q
    pad_kv = (-T) % block_kv
    # heads-major: [B, H, S, D]
    q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0))).transpose(0, 2, 1, 3)
    Sp, Tp = q.shape[2], k.shape[2]
    nq, nkv = Sp // block_q, Tp // block_kv

    kernel = functools.partial(
        _kernel, block_q=block_q, block_kv=block_kv, nkv=nkv, causal=causal,
        window=window, softcap=softcap, scale=scale, kv_len=kv_len)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik, g=G: (b, h // g, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D),
                         lambda b, h, iq, ik, g=G: (b, h // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out.transpose(0, 2, 1, 3)[:, :S]
