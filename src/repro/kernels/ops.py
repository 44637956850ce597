"""jit'd public wrappers for the Pallas kernels.

The kernels lower through Mosaic for the TPU.  ``interpret=True`` runs the
kernel body in Python instead, which is how the CPU tests check the kernels
against the ref.py oracles; without it, a non-TPU backend refuses the call.
"""
from __future__ import annotations

import functools

import jax

from repro.kernels.flash_attention import flash_attention_kernel
from repro.kernels.paged_attention import paged_attention_kernel
from repro.kernels.ssd_scan import ssd_scan_kernel


@functools.partial(jax.jit, static_argnames=("scale", "softcap", "interpret"))
def paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                    scale: float, softcap: float = 0.0,
                    interpret: bool = False):
    return paged_attention_kernel(
        q, k_pages, v_pages, page_tables, lengths, scale=scale,
        softcap=softcap, interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "causal", "scale", "window", "softcap", "block_q", "block_kv",
    "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, scale: float,
                    window: int = 0, softcap: float = 0.0,
                    block_q: int = 512, block_kv: int = 512,
                    interpret: bool = False):
    return flash_attention_kernel(
        q, k, v, causal=causal, scale=scale, window=window, softcap=softcap,
        block_q=block_q, block_kv=block_kv, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk: int = 256, initial_state=None,
             interpret: bool = False):
    return ssd_scan_kernel(x, dt, A, Bm, Cm, chunk=chunk,
                           initial_state=initial_state, interpret=interpret)
