"""Shared layers: norms, RoPE/M-RoPE, blocked attention, MLP variants.

Everything is pure-functional JAX.  Parameters are ``Param``-annotated with
logical axis names (see ``repro.distributed.sharding``).  Attention for long
sequences uses a blocked online-softmax formulation (scan over KV blocks
inside a scan over Q blocks) so peak memory stays bounded at 32k-500k context;
the Pallas kernels in ``repro.kernels`` are drop-in TPU replacements for the
same math (selected via ``attn_impl``).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import Activation, ModelConfig, Norm, PosEmb
from repro.distributed.sharding import Param, shard_act

# --------------------------------------------------------------------------
# Initializers
# --------------------------------------------------------------------------


def dense_param(key, shape, axes, dtype=jnp.bfloat16, fan_in: Optional[int] = None):
    fan = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / np.sqrt(max(fan, 1))
    value = (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)
    return Param(value, axes)


def embed_param(key, shape, axes, dtype=jnp.bfloat16):
    # std 1/sqrt(d_model): keeps tied-head logits O(1) at init
    scale = 1.0 / np.sqrt(shape[-1])
    value = (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)
    return Param(value, axes)


def zeros_param(shape, axes, dtype=jnp.bfloat16):
    return Param(jnp.zeros(shape, dtype=dtype), axes)


def ones_param(shape, axes, dtype=jnp.bfloat16):
    return Param(jnp.ones(shape, dtype=dtype), axes)


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------


def norm_init(cfg: ModelConfig, d: int) -> Dict:
    if cfg.norm == Norm.RMSNORM:
        return {"scale": ones_param((d,), ("embed",), jnp.float32)}
    if cfg.norm == Norm.LAYERNORM:
        return {"scale": ones_param((d,), ("embed",), jnp.float32),
                "bias": zeros_param((d,), ("embed",), jnp.float32)}
    return {}  # NONPARAM_LN


def apply_norm(cfg: ModelConfig, p: Dict, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if cfg.norm == Norm.RMSNORM:
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mean) * jax.lax.rsqrt(var + eps)
        if cfg.norm == Norm.LAYERNORM:
            y = y * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# RoPE / M-RoPE
# --------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def rope_cos_sin(positions, head_dim: int, theta: float):
    """positions [..., S] int32 -> cos/sin [..., S, head_dim//2] (fp32)."""
    inv = rope_freqs(head_dim, theta)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x [B, S, H, D]; cos/sin [B, S, D//2] (half-split convention)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * c - x2f * s, x2f * c + x1f * s], axis=-1).astype(x.dtype)


def mrope_sections(head_dim: int) -> Tuple[int, int, int]:
    """Qwen2-VL M-RoPE: temporal/height/width splits of the half-dim.
    Published split for head_dim=128 is [16, 24, 24]; generalized as
    (1/4, 3/8, 3/8) of half-dim."""
    half = head_dim // 2
    t = half // 4
    h = (half - t) // 2
    w = half - t - h
    return t, h, w


def mrope_cos_sin(positions_thw, head_dim: int, theta: float):
    """positions_thw [3, B, S] -> cos/sin [B, S, head_dim//2].

    Each frequency band takes its angle from the temporal / height / width
    position row according to its section.
    """
    inv = rope_freqs(head_dim, theta)                       # [half]
    t, h, w = mrope_sections(head_dim)
    section_id = jnp.concatenate([
        jnp.zeros((t,), jnp.int32), jnp.ones((h,), jnp.int32),
        jnp.full((w,), 2, jnp.int32)])                      # [half]
    pos = positions_thw.astype(jnp.float32)                 # [3, B, S]
    pos_sel = jnp.take(pos, section_id, axis=0)             # [half, B, S]
    ang = jnp.moveaxis(pos_sel, 0, -1) * inv                # [B, S, half]
    return jnp.cos(ang), jnp.sin(ang)


def positional_cos_sin(cfg: ModelConfig, positions):
    """Dispatch on cfg.pos_emb.  positions: [B,S] int32 or [3,B,S] for MROPE."""
    if cfg.pos_emb == PosEmb.MROPE:
        if positions.ndim == 2:  # text-only fallback: replicate across t/h/w
            positions = jnp.broadcast_to(positions[None], (3,) + positions.shape)
        return mrope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    if cfg.pos_emb == PosEmb.ROPE:
        return rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    return None, None


# --------------------------------------------------------------------------
# Attention core
# --------------------------------------------------------------------------


def _softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap > 0 else x


def attention_params(cfg: ModelConfig, key) -> Dict:
    d, h, kvh, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_param(ks[0], (d, h, hd), ("embed", "heads", "head_dim")),
        "wk": dense_param(ks[1], (d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wv": dense_param(ks[2], (d, kvh, hd), ("embed", "kv_heads", "head_dim")),
        "wo": dense_param(ks[3], (h, hd, d), ("heads", "head_dim", "embed"),
                          fan_in=h * hd),
    }


def _attn_scale(cfg: ModelConfig) -> float:
    if cfg.attn_scale_override:
        return cfg.attn_scale_override
    return 1.0 / np.sqrt(cfg.head_dim)


def blocked_attention(q, k, v, *, causal: bool, scale: float,
                      q_positions=None, kv_lengths=None, window: int = 0,
                      softcap: float = 0.0, block_q: int = 512,
                      block_kv: int = 1024):
    """Memory-bounded attention via online softmax over KV blocks.

    q: [B, Sq, H, D]; k, v: [B, Skv, KVH, D] with GQA (H % KVH == 0).
    q_positions: [B, Sq] absolute positions of queries (for causal masking
      against an absolutely-indexed KV buffer); defaults to arange.
    kv_lengths: [B] valid KV length per sequence (for decode over a cache).
    window: sliding-window size (0 = unlimited).
    """
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    orig_sq = Sq

    pad_q = (-Sq) % block_q
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        if q_positions is not None:
            q_positions = jnp.pad(q_positions, ((0, 0), (0, pad_q)),
                                  constant_values=0)
        Sq = q.shape[1]
    pad_kv = (-Skv) % block_kv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        Skv = k.shape[1]

    if q_positions is None:
        q_positions = jnp.broadcast_to(jnp.arange(Sq, dtype=jnp.int32)[None],
                                       (B, Sq))
    if kv_lengths is None:
        kv_lengths = jnp.full((B,), min(Skv, Skv - pad_kv), jnp.int32)

    nq, nkv = Sq // block_q, Skv // block_kv
    qb = q.reshape(B, nq, block_q, KVH, G, D)
    kb = k.reshape(B, nkv, block_kv, KVH, D)
    vb = v.reshape(B, nkv, block_kv, KVH, D)
    posb = q_positions.reshape(B, nq, block_q)

    kv_pos = jnp.arange(Skv, dtype=jnp.int32).reshape(nkv, block_kv)

    @jax.checkpoint
    def q_block(carry, inputs):
        # jax.checkpoint => backward recomputes this block's scores instead
        # of saving [nq, nkv, bq, bk] fp32 probabilities (flash-attention
        # memory behaviour for the XLA path; the Pallas kernel does the same
        # on TPU).
        del carry
        q_i, pos_i = inputs                     # [B, bq, KVH, G, D], [B, bq]

        @jax.checkpoint
        def kv_block(acc, kv_in):
            # checkpointed: scan AD then saves only the small (m, lse, o)
            # carries per kv block instead of the [bq, bkv] fp32 scores
            m, lse, o = acc
            k_j, v_j, pos_j = kv_in             # [B,bkv,KVH,D], ..., [bkv]
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q_i, k_j,
                           preferred_element_type=jnp.float32) * scale
            s = _softcap(s, softcap)
            valid = pos_j[None, None, :] < kv_lengths[:, None, None]
            if causal:
                valid &= pos_j[None, None, :] <= pos_i[:, :, None]
            if window > 0:
                valid &= pos_j[None, None, :] > pos_i[:, :, None] - window
            s = jnp.where(valid[:, None, None, :, :], s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            lse_new = lse * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, lse_new, o_new), None

        m0 = jnp.full((B, KVH, G, block_q), -1e30, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, block_q), jnp.float32)
        o0 = jnp.zeros((B, KVH, G, block_q, D), jnp.float32)
        (m, lse, o), _ = jax.lax.scan(
            kv_block, (m0, l0, o0),
            (jnp.moveaxis(kb, 1, 0), jnp.moveaxis(vb, 1, 0), kv_pos))
        out = o / jnp.maximum(lse[..., None], 1e-30)
        return None, out.astype(q.dtype)        # [B, KVH, G, bq, D]

    _, outs = jax.lax.scan(q_block, None,
                           (jnp.moveaxis(qb, 1, 0), jnp.moveaxis(posb, 1, 0)))
    # outs: [nq, B, KVH, G, bq, D] -> [B, Sq, H, D]
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 2, 3, 1, 4, 5)
    out = out.reshape(B, KVH, G, Sq, D).transpose(0, 3, 1, 2, 4)
    out = out.reshape(B, Sq, H, D)
    return out[:, :orig_sq]


def decode_attention(q, k_cache, v_cache, *, scale: float, lengths,
                     window: int = 0, softcap: float = 0.0):
    """Single-token decode attention over a dense cache.

    q: [B, 1, H, D]; caches: [B, T, KVH, D]; lengths: [B] (length INCLUDING
    the token just written).  Window masks to the last `window` positions.
    """
    B, _, H, D = q.shape
    _, T, KVH, _ = k_cache.shape
    G = H // KVH
    qr = q.reshape(B, KVH, G, D)
    s = jnp.einsum("bhgd,bthd->bhgt", qr, k_cache,
                   preferred_element_type=jnp.float32) * scale
    s = _softcap(s, softcap)
    pos = jnp.arange(T, dtype=jnp.int32)[None]             # [1, T]
    valid = pos < lengths[:, None]
    if window > 0:
        valid &= pos > (lengths[:, None] - 1 - window)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgt,bthd->bhgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(B, 1, H, D).astype(q.dtype)


# --------------------------------------------------------------------------
# KV cache (dense layout used by the lowered serve_step; the serving engine's
# paged cache lives in repro.serving.kvcache)
# --------------------------------------------------------------------------


def kv_cache_init(cfg: ModelConfig, batch: int, max_len: int,
                  window: int = 0) -> Dict:
    """One attention layer's cache.  window>0 -> ring buffer of that size."""
    T = min(max_len, window) if window > 0 else max_len
    kvh, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, T, kvh, hd), jnp.int8),
            "v": jnp.zeros((batch, T, kvh, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, T, kvh), jnp.float32),
            "v_scale": jnp.zeros((batch, T, kvh), jnp.float32),
        }
    dtype = jnp.bfloat16 if cfg.kv_cache_dtype == "bfloat16" else jnp.float32
    return {"k": jnp.zeros((batch, T, kvh, hd), dtype),
            "v": jnp.zeros((batch, T, kvh, hd), dtype)}


def kv_cache_axes(is_ring: bool = False) -> Tuple[Optional[str], ...]:
    # ring buffers (sliding window) are small; don't sequence-shard them.
    seq = None if is_ring else "cache_seq"
    return ("cache_batch", seq, "cache_kv_heads", "cache_head_dim")


def _quantize_kv(x):
    """[B, T, H, D] -> int8 values + per-(b,t,h) scales."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return q.astype(jnp.int8), scale


def _dequantize_kv(q, scale, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * scale[..., None]).astype(dtype)


def kv_read(cache: Dict, dtype=jnp.bfloat16):
    if "k_scale" in cache:
        return (_dequantize_kv(cache["k"], cache["k_scale"], dtype),
                _dequantize_kv(cache["v"], cache["v_scale"], dtype))
    return cache["k"], cache["v"]


def kv_write_prefill(cache: Dict, k, v) -> Dict:
    """Write a full prompt's K/V.  k/v: [B, S, KVH, D] (post-RoPE).
    Handles ring buffers (keeps the last T positions, ring-aligned)."""
    B, S, _, _ = k.shape
    T = cache["k"].shape[1]
    if S >= T:
        k_last, v_last = k[:, S - T:], v[:, S - T:]
        shift = S % T
        k_w = jnp.roll(k_last, shift, axis=1)
        v_w = jnp.roll(v_last, shift, axis=1)
        new = dict(cache)
        if "k_scale" in cache:
            new["k"], new["k_scale"] = _quantize_kv(k_w)
            new["v"], new["v_scale"] = _quantize_kv(v_w)
        else:
            new["k"] = k_w.astype(cache["k"].dtype)
            new["v"] = v_w.astype(cache["v"].dtype)
        return new
    new = dict(cache)
    if "k_scale" in cache:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new["k"] = jax.lax.dynamic_update_slice_in_dim(cache["k"], kq, 0, 1)
        new["v"] = jax.lax.dynamic_update_slice_in_dim(cache["v"], vq, 0, 1)
        new["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, 0, 1)
        new["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, 0, 1)
    else:
        new["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, 1)
        new["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, 1)
    return new


def layer_of(cache: Dict, layer) -> Dict:
    """Layer ``layer`` of a cache whose leaves stack every layer on axis 0."""
    return {n: jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)
            for n, c in cache.items()}


def kv_write_decode(cache: Dict, k, v, lengths, layer) -> Dict:
    """Write one token per sequence into layer ``layer`` of a stacked cache
    (leaves ``[L, B, T, ...]``), at slot ``lengths % T``: one row per
    sequence, nothing else is copied.
    k/v: [B, 1, KVH, D]; lengths: [B] (length BEFORE this token)."""
    B = k.shape[0]
    T = cache["k"].shape[2]
    slots = (lengths % T).astype(jnp.int32)
    bidx = jnp.arange(B, dtype=jnp.int32)
    rows = {"k": k[:, 0], "v": v[:, 0]}
    if "k_scale" in cache:
        rows["k"], rows["k_scale"] = _quantize_kv(rows["k"])
        rows["v"], rows["v_scale"] = _quantize_kv(rows["v"])
    return {n: cache[n].at[layer, bidx, slots].set(r.astype(cache[n].dtype))
            for n, r in rows.items()}


# --------------------------------------------------------------------------
# Full attention layer (projection + rope + cache + attention + out-proj)
# --------------------------------------------------------------------------


def attention_forward(cfg: ModelConfig, p: Dict, x, positions, *,
                      causal: bool = True, window: int = 0,
                      cache: Optional[Dict] = None,
                      cos=None, sin=None):
    """Teacher-forced / prefill attention.  x: [B, S, d_model].
    Returns (out, new_cache)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    # q may be sequence-sharded ("act_seq" -> model under the seq-parallel
    # serve layout); k/v are constrained seq-replicated HERE, outside the
    # q/kv block scans, so the gather happens once per layer, not per block.
    # (flag "kv_seq_sharded": leave k/v seq-sharded; GSPMD then gathers the
    # kv-block slices inside the scan instead — smaller, later gathers.)
    from repro.distributed.sharding import active_flag as _af
    kv_seq = "act_seq" if _af("kv_seq_sharded") else None
    q = shard_act(q, "batch", "act_seq", "act_heads", "act_head_dim")
    k = shard_act(k, "batch", kv_seq, "act_heads", "act_head_dim")
    v = shard_act(v, "batch", kv_seq, "act_heads", "act_head_dim")
    if cos is None and cfg.pos_emb in (PosEmb.ROPE, PosEmb.MROPE):
        cos, sin = positional_cos_sin(cfg, positions)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    from repro.distributed.sharding import active_flag
    # sequence-parallel layout: one q block spanning the (seq-sharded) length
    # — scanning q blocks would force a gather of the sharded scan axis
    bq = q.shape[1] if active_flag("single_q_block") else 512
    out = blocked_attention(q, k, v, causal=causal, scale=_attn_scale(cfg),
                            window=window, softcap=cfg.attn_logit_softcap,
                            block_q=bq)
    new_cache = kv_write_prefill(cache, k, v) if cache is not None else None
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return shard_act(out, "batch", None, "act_embed"), new_cache


def attention_decode(cfg: ModelConfig, p: Dict, x, lengths, *,
                     window: int = 0, cache: Dict, layer,
                     cos=None, sin=None):
    """One-token decode.  x: [B, 1, d_model]; lengths: [B] BEFORE this token.
    ``cache`` stacks every layer's K/V on axis 0 and this layer is
    ``layer``: the token's row is written there and the layer is read in
    place.  Returns (out, cache)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cos is None and cfg.pos_emb in (PosEmb.ROPE, PosEmb.MROPE):
        pos = lengths[:, None]                     # [B, 1] absolute position
        cos, sin = positional_cos_sin(cfg, pos)
    if cos is not None:
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    cache = kv_write_decode(cache, k, v, lengths, layer)
    kd, vd = kv_read(layer_of(cache, layer), x.dtype)
    T = kd.shape[1]
    is_ring = window > 0 and T <= window
    if is_ring:
        eff_len = jnp.minimum(lengths + 1, T)
        out = decode_attention(q, kd, vd, scale=_attn_scale(cfg),
                               lengths=eff_len, window=0,
                               softcap=cfg.attn_logit_softcap)
    else:
        out = decode_attention(q, kd, vd, scale=_attn_scale(cfg),
                               lengths=lengths + 1, window=window,
                               softcap=cfg.attn_logit_softcap)
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return out, cache


def cross_attention_forward(cfg: ModelConfig, p: Dict, x, enc_k, enc_v,
                            enc_lengths=None):
    """Decoder cross-attention over precomputed encoder K/V (no cache update).
    x: [B, S, d]; enc_k/enc_v: [B, T, KVH, D]."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    out = blocked_attention(q, enc_k, enc_v, causal=False,
                            scale=_attn_scale(cfg), kv_lengths=enc_lengths)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


def cross_kv(cfg: ModelConfig, p: Dict, enc_out):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"])
    return k, v


# --------------------------------------------------------------------------
# MLP variants
# --------------------------------------------------------------------------


def mlp_params(cfg: ModelConfig, key, d: Optional[int] = None,
               f: Optional[int] = None) -> Dict:
    d = d or cfg.d_model
    f = f or cfg.d_ff
    gated = cfg.activation in (Activation.SWIGLU, Activation.GEGLU)
    ks = jax.random.split(key, 3)
    p = {"wi": dense_param(ks[0], (d, f), ("embed", "mlp")),
         "wo": dense_param(ks[1], (f, d), ("mlp", "embed"), fan_in=f)}
    if gated:
        p["wg"] = dense_param(ks[2], (d, f), ("embed", "mlp"))
    return p


def apply_mlp(cfg: ModelConfig, p: Dict, x):
    h = jnp.einsum("...d,df->...f", x, p["wi"])
    if cfg.activation == Activation.SWIGLU:
        g = jnp.einsum("...d,df->...f", x, p["wg"])
        h = jax.nn.silu(g) * h
    elif cfg.activation == Activation.GEGLU:
        g = jnp.einsum("...d,df->...f", x, p["wg"])
        h = jax.nn.gelu(g, approximate=True) * h
    elif cfg.activation == Activation.SQUARED_RELU:
        h = jnp.square(jax.nn.relu(h))
    else:  # GELU
        h = jax.nn.gelu(h, approximate=True)
    if h.ndim == 3:
        h = shard_act(h, "batch", None, "mlp")
    return jnp.einsum("...f,fd->...d", h, p["wo"])
