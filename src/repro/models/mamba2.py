"""Mamba-2 mixer: SSD (state-space duality) with chunked scan.

Follows the minimal SSD formulation of arXiv:2405.21060 §6: within a chunk
the recurrence is computed as dense (MXU-friendly) matmuls with a decay mask;
across chunks a short ``lax.scan`` carries the [H, P, N] state.  Decode is the
O(1) recurrent update.  The Pallas kernel in ``repro.kernels.ssd_scan`` is a
drop-in for the chunked path on TPU.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import Param, shard_act
from repro.models.layers import (dense_param, layer_of, ones_param,
                                 zeros_param)


def _dims(cfg: ModelConfig):
    s = cfg.ssm
    d_inner = s.expand * cfg.d_model
    nheads = d_inner // s.head_dim
    conv_dim = d_inner + 2 * s.ngroups * s.state_dim
    return d_inner, nheads, conv_dim


def mamba_params(cfg: ModelConfig, key) -> Dict:
    s = cfg.ssm
    d = cfg.d_model
    d_inner, nheads, conv_dim = _dims(cfg)
    d_in_proj = 2 * d_inner + 2 * s.ngroups * s.state_dim + nheads
    ks = jax.random.split(key, 4)
    # A init in [1, 16) (mamba2 default), dt_bias via inverse softplus of
    # dt ~ U[1e-3, 1e-1] — simplified to a constant here.
    a_init = jnp.log(jnp.linspace(1.0, 16.0, nheads, dtype=jnp.float32))
    return {
        "in_proj": dense_param(ks[0], (d, d_in_proj), ("embed", "ssm_inner")),
        "conv_w": dense_param(ks[1], (s.conv_width, conv_dim), ("conv", "ssm_inner"),
                              fan_in=s.conv_width),
        "conv_b": zeros_param((conv_dim,), ("ssm_inner",), jnp.float32),
        "A_log": Param(a_init, ("ssm_heads",)),
        "D": ones_param((nheads,), ("ssm_heads",), jnp.float32),
        "dt_bias": zeros_param((nheads,), ("ssm_heads",), jnp.float32),
        "norm_scale": ones_param((d_inner,), ("ssm_inner",), jnp.float32),
        "out_proj": dense_param(ks[3], (d_inner, d), ("ssm_inner", "embed"),
                                fan_in=d_inner),
    }


def mamba_cache_init(cfg: ModelConfig, batch: int) -> Dict:
    s = cfg.ssm
    d_inner, nheads, conv_dim = _dims(cfg)
    return {
        "conv": jnp.zeros((batch, s.conv_width - 1, conv_dim), jnp.float32),
        "ssm": jnp.zeros((batch, nheads, s.head_dim, s.state_dim), jnp.float32),
    }


def mamba_cache_axes() -> Dict[str, Tuple[Optional[str], ...]]:
    return {"conv": ("cache_batch", None, "ssm_inner"),
            "ssm": ("cache_batch", "ssm_heads", None, None)}


# ----------------------------------------------------------------- SSD core


def _segsum(x):
    """x: [..., l] -> [..., l, l]; out[i,j] = sum_{k in (j, i]} x_k, -inf above
    the diagonal."""
    n = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((n, n), bool), k=0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: [b, S, h, p] (pre-dt);  dt: [b, S, h] (post-softplus);  A: [h] (<0);
    B, C: [b, S, g, n] (broadcast over h // g heads per group).
    Returns (y [b, S, h, p], final_state [b, h, p, n]).
    """
    b, S, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = x.shape[1]
    c = Sp // chunk

    Bh = jnp.repeat(B, rep, axis=2)                          # [b, Sp, h, n]
    Ch = jnp.repeat(C, rep, axis=2)
    xq = (x * dt[..., None]).reshape(b, c, chunk, h, p)      # dt folded into x
    dA = (dt * A[None, None, :]).reshape(b, c, chunk, h)     # [b,c,l,h]
    dA = jnp.moveaxis(dA, 3, 1)                              # [b,h,c,l]
    Bc = Bh.reshape(b, c, chunk, h, n)
    Cc = Ch.reshape(b, c, chunk, h, n)

    dA_cum = jnp.cumsum(dA, axis=-1)                         # [b,h,c,l]
    L = jnp.exp(_segsum(dA))                                 # [b,h,c,l,l]

    # Intra-chunk (diagonal blocks)
    scores = jnp.einsum("bclhn,bcshn->bhcls", Cc, Bc,
                        preferred_element_type=jnp.float32)
    y_diag = jnp.einsum("bhcls,bcshp->bclhp", scores * L,
                        xq.astype(jnp.float32))

    # Per-chunk terminal states
    decay_states = jnp.exp(dA_cum[..., -1:] - dA_cum)        # [b,h,c,l]
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay_states,
                        xq.astype(jnp.float32))              # [b,c,h,p,n]

    # Inter-chunk recurrence
    chunk_decay = jnp.exp(dA_cum[..., -1])                   # [b,h,c]
    if initial_state is None:
        initial_state = jnp.zeros((b, h, p, n), jnp.float32)

    def carry_fn(state, inp):
        s_c, g_c = inp                                       # [b,h,p,n], [b,h]
        prev = state
        state = s_c + g_c[..., None, None] * state
        return state, prev

    (final_state, prevs) = jax.lax.scan(
        carry_fn, initial_state.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 2, 0)))
    prev_states = jnp.moveaxis(prevs, 0, 1)                  # [b,c,h,p,n]

    # Off-diagonal contribution: y_off[t] = C_t . (exp(dA_cum[t]) * prev_state)
    state_decay = jnp.exp(dA_cum)                            # [b,h,c,l]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, prev_states, state_decay)

    y = (y_diag + y_off).reshape(b, Sp, h, p)[:, :S]
    return y.astype(x.dtype), final_state


def ssd_decode_step(state, x_t, dt_t, A, B_t, C_t):
    """O(1) recurrent update for one token.

    state: [b, h, p, n]; x_t: [b, h, p]; dt_t: [b, h]; A: [h];
    B_t, C_t: [b, g, n].  Returns (y [b, h, p], new_state).
    """
    b, h, p, n = state.shape
    g = B_t.shape[1]
    rep = h // g
    Bh = jnp.repeat(B_t, rep, axis=1)                        # [b,h,n]
    Ch = jnp.repeat(C_t, rep, axis=1)
    dA = jnp.exp(dt_t * A[None, :])                          # [b,h]
    dBx = jnp.einsum("bh,bhn,bhp->bhpn", dt_t, Bh,
                     x_t.astype(jnp.float32))
    new_state = dA[..., None, None] * state + dBx
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x_t.dtype), new_state


# ------------------------------------------------------------- full mixer


def _causal_conv_full(xBC, w, bias):
    """Depthwise causal conv.  xBC: [B, S, C]; w: [W, C] -> [B, S, C]."""
    W = w.shape[0]
    pad = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    out = sum(pad[:, i:i + xBC.shape[1], :] * w[i][None, None, :]
              for i in range(W))
    return out + bias[None, None, :]


def mamba_forward(cfg: ModelConfig, p: Dict, x, cache: Optional[Dict] = None):
    """Full-sequence (train / prefill) mamba mixer.  x: [B, S, d].
    Returns (y, new_cache or None)."""
    s = cfg.ssm
    d_inner, nheads, conv_dim = _dims(cfg)
    B_, S, _ = x.shape

    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xBC, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)
    xBC = shard_act(xBC, "batch", None, "ssm_inner")

    conv_in = xBC.astype(jnp.float32)
    if cache is not None:
        conv_in = jnp.concatenate([cache["conv"], conv_in], axis=1)
        conv_out = _causal_conv_full(conv_in, p["conv_w"].astype(jnp.float32),
                                     p["conv_b"])[:, s.conv_width - 1:]
    else:
        conv_out = _causal_conv_full(conv_in, p["conv_w"].astype(jnp.float32),
                                     p["conv_b"])
    xBC = jax.nn.silu(conv_out).astype(x.dtype)

    xs, Bmat, Cmat = jnp.split(
        xBC, [d_inner, d_inner + s.ngroups * s.state_dim], axis=-1)
    xs = xs.reshape(B_, S, nheads, s.head_dim)
    Bmat = Bmat.reshape(B_, S, s.ngroups, s.state_dim)
    Cmat = Cmat.reshape(B_, S, s.ngroups, s.state_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, None, :])
    A = -jnp.exp(p["A_log"])

    init_state = cache["ssm"] if cache is not None else None
    y, final_state = ssd_chunked(xs, dt, A, Bmat, Cmat, s.chunk_size,
                                 initial_state=init_state)
    y = y + p["D"][None, None, :, None] * xs
    y = y.reshape(B_, S, d_inner)

    # gated RMSNorm then out-projection
    gated = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(gated.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    y = (gated.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)
         * p["norm_scale"]).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])

    new_cache = None
    if cache is not None:
        tail = conv_in[:, -(s.conv_width - 1):] if s.conv_width > 1 else \
            cache["conv"]
        new_cache = {"conv": tail, "ssm": final_state}
    return shard_act(out, "batch", None, "act_embed"), new_cache


def mamba_decode(cfg: ModelConfig, p: Dict, x, cache: Dict, layer):
    """One-token decode.  x: [B, 1, d].  ``cache`` stacks every layer's
    state on axis 0 and this layer is ``layer``: its state is read there and
    replaced whole.  Returns (y [B,1,d], cache)."""
    s = cfg.ssm
    state = layer_of(cache, layer)
    d_inner, nheads, conv_dim = _dims(cfg)
    B_ = x.shape[0]

    proj = jnp.einsum("bsd,de->bse", x, p["in_proj"])[:, 0]  # [B, e]
    z, xBC, dt = jnp.split(proj, [d_inner, d_inner + conv_dim], axis=-1)

    # conv ring update
    conv_hist = jnp.concatenate(
        [state["conv"], xBC.astype(jnp.float32)[:, None, :]], axis=1)
    w = p["conv_w"].astype(jnp.float32)                      # [W, C]
    conv_out = jnp.einsum("bwc,wc->bc", conv_hist, w) + p["conv_b"][None]
    xBC = jax.nn.silu(conv_out).astype(x.dtype)

    xs, Bmat, Cmat = jnp.split(
        xBC, [d_inner, d_inner + s.ngroups * s.state_dim], axis=-1)
    xs = xs.reshape(B_, nheads, s.head_dim)
    Bmat = Bmat.reshape(B_, s.ngroups, s.state_dim)
    Cmat = Cmat.reshape(B_, s.ngroups, s.state_dim)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"][None, :])
    A = -jnp.exp(p["A_log"])

    y, new_ssm = ssd_decode_step(state["ssm"], xs, dt, A, Bmat, Cmat)
    y = y + p["D"][None, :, None] * xs
    y = y.reshape(B_, d_inner)

    gated = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(gated.astype(jnp.float32)), axis=-1,
                   keepdims=True)
    y = (gated.astype(jnp.float32) * jax.lax.rsqrt(var + 1e-6)
         * p["norm_scale"]).astype(x.dtype)
    out = jnp.einsum("be,ed->bd", y, p["out_proj"])[:, None, :]

    new = {"conv": conv_hist[:, 1:], "ssm": new_ssm}
    return out, {n: jax.lax.dynamic_update_index_in_dim(c, new[n], layer, 0)
                 for n, c in cache.items()}
