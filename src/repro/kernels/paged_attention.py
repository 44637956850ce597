"""Pallas TPU kernel: decode-phase GQA paged attention.

The decode phase — the memory-bandwidth-bound side of the paper's PD
imbalance — is dominated by streaming the KV cache.  TPU-native design:

  * grid = (batch, kv_heads, pages): one program instance per KV page;
  * the wrapper lays the page pools out heads-major ([KVH, P, ps, D]) so a
    page block is a [ps, D] tile of one KV head — the TPU tiles the last
    two dims;
  * the **page table is scalar-prefetched** (PrefetchScalarGridSpec) so the
    BlockSpec index_map can translate logical page -> physical page while the
    previous page's compute is in flight (HBM->VMEM pipelining by Mosaic);
  * GQA query-head packing: the q block is [G, D] (all query heads of one KV
    group), so every page contributes an MXU matmul [G, D] x [D, page_size]
    instead of G vector ops;
  * online softmax in fp32 VMEM scratch carried across the page grid dim.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _kernel(page_tables_ref, lengths_ref,        # scalar prefetch
            q_ref, k_ref, v_ref,                 # blocks
            out_ref,                             # output block
            m_ref, l_ref, acc_ref,               # VMEM scratch
            *, page_size: int, pages: int, scale: float, softcap: float):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lengths_ref[b]
    page_start = p * page_size

    @pl.when(page_start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)              # [G, D]
        k = k_ref[0, 0].astype(jnp.float32)              # [ps, D]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [G, ps]
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        pos = page_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)

        m_prev = m_ref[...]                              # [G, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new)                        # [G, ps]
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pexp, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [G, D]
        m_ref[...] = m_new

    @pl.when(p == pages - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        out_ref[0, 0] = out.astype(out_ref.dtype)


def paged_attention_kernel(q, k_pages, v_pages, page_tables, lengths, *,
                           scale: float, softcap: float = 0.0,
                           interpret: bool = False):
    """q: [B, H, D]; k/v_pages: [P, ps, KVH, D]; page_tables: [B, maxp];
    lengths: [B] -> out [B, H, D]."""
    B, H, D = q.shape
    _, ps, KVH, _ = k_pages.shape
    maxp = page_tables.shape[1]
    G = H // KVH
    qr = q.reshape(B, KVH, G, D)
    k_pages = k_pages.transpose(2, 0, 1, 3)              # [KVH, P, ps, D]
    v_pages = v_pages.transpose(2, 0, 1, 3)

    grid = (B, KVH, maxp)
    kernel = functools.partial(_kernel, page_size=ps, pages=maxp,
                               scale=scale, softcap=softcap)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, D), lambda b, h, p, pt, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, ps, D),
                             lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
                pl.BlockSpec((1, 1, ps, D),
                             lambda b, h, p, pt, ln: (h, pt[b, p], 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, D),
                                   lambda b, h, p, pt, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),     # m
                pltpu.VMEM((G, 1), jnp.float32),     # l
                pltpu.VMEM((G, D), jnp.float32),     # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_tables, lengths, qr, k_pages, v_pages)
    return out.reshape(B, H, D)
