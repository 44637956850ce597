"""Encoder-decoder backbone (SeamlessM4T text/audio).  The conformer speech
frontend is a STUB per the assignment: inputs arrive as precomputed frame
embeddings [B, S_enc, d_model]."""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import prepend_axis, shard_act
from repro.models import layers as L

# Decoder positions must cover the assigned decode_32k shape even though the
# published model caps at 4096 (DESIGN.md deviation note).
POS_TABLE_LEN = 32_768


def _enc_block_init(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 2)
    return {
        "norm_attn": L.norm_init(cfg, cfg.d_model),
        "attn": L.attention_params(cfg, ks[0]),
        "norm_ffn": L.norm_init(cfg, cfg.d_model),
        "mlp": L.mlp_params(cfg, ks[1]),
    }


def _dec_block_init(cfg: ModelConfig, key) -> Dict:
    ks = jax.random.split(key, 3)
    return {
        "norm_self": L.norm_init(cfg, cfg.d_model),
        "self_attn": L.attention_params(cfg, ks[0]),
        "norm_cross": L.norm_init(cfg, cfg.d_model),
        "cross_attn": L.attention_params(cfg, ks[1]),
        "norm_ffn": L.norm_init(cfg, cfg.d_model),
        "mlp": L.mlp_params(cfg, ks[2]),
    }


def encdec_init(cfg: ModelConfig, key) -> Dict:
    ke, kd, kp1, kp2 = jax.random.split(key, 4)
    enc_keys = jax.random.split(ke, cfg.encoder_layers)
    dec_keys = jax.random.split(kd, cfg.num_layers)
    enc = jax.vmap(lambda k: _enc_block_init(cfg, k))(enc_keys)
    dec = jax.vmap(lambda k: _dec_block_init(cfg, k))(dec_keys)
    return {
        "enc_blocks": prepend_axis("layers", enc),
        "dec_blocks": prepend_axis("layers", dec),
        "pos_enc": L.embed_param(kp1, (POS_TABLE_LEN, cfg.d_model),
                                 (None, "embed")),
        "pos_dec": L.embed_param(kp2, (POS_TABLE_LEN, cfg.d_model),
                                 (None, "embed")),
        "norm_enc_final": L.norm_init(cfg, cfg.d_model),
        "norm_dec_final": L.norm_init(cfg, cfg.d_model),
    }


def encode(cfg: ModelConfig, params: Dict, src_embeds, remat: bool = False):
    """Bidirectional encoder over frame embeddings.  [B, S, d] -> [B, S, d]."""
    B, S, _ = src_embeds.shape
    pos = jax.lax.dynamic_slice_in_dim(params["pos_enc"], 0, S, 0)
    x = src_embeds + pos[None].astype(src_embeds.dtype)

    def step(x, p):
        h = L.apply_norm(cfg, p["norm_attn"], x)
        mix, _ = L.attention_forward(cfg, p["attn"], h, None, causal=False)
        x = x + mix
        h = L.apply_norm(cfg, p["norm_ffn"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h)
        return shard_act(x, "batch", "act_seq", "act_embed"), None

    if remat:
        step = jax.checkpoint(step)
    x, _ = jax.lax.scan(step, x, params["enc_blocks"])
    return L.apply_norm(cfg, params["norm_enc_final"], x)


def build_cross_cache(cfg: ModelConfig, params: Dict, enc_out):
    """Per-decoder-layer cross-attention K/V from encoder output."""
    def one(carry, p):
        k, v = L.cross_kv(cfg, p["cross_attn"], enc_out)
        return carry, (k, v)
    _, (ks, vs) = jax.lax.scan(one, None, params["dec_blocks"])
    return {"cross_k": ks, "cross_v": vs}    # [L, B, T_enc, H, D]


def decode_forward(cfg: ModelConfig, params: Dict, x, enc_out, *,
                   positions, self_caches=None, remat: bool = False):
    """Teacher-forced decoder pass.  x: [B, S_dec, d] (already embedded +
    positioned).  Returns (x, new_self_caches)."""
    have_cache = self_caches is not None

    def step(x, xs):
        p = xs[0]
        cache = xs[1] if have_cache else None
        h = L.apply_norm(cfg, p["norm_self"], x)
        mix, nc = L.attention_forward(cfg, p["self_attn"], h, positions,
                                      causal=True, cache=cache)
        x = x + mix
        h = L.apply_norm(cfg, p["norm_cross"], x)
        x = x + L.cross_attention_forward(cfg, p["cross_attn"], h,
                                          *L.cross_kv(cfg, p["cross_attn"],
                                                      enc_out))
        h = L.apply_norm(cfg, p["norm_ffn"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h)
        x = shard_act(x, "batch", "act_seq", "act_embed")
        return x, (nc if nc is not None else {})

    if remat:
        step = jax.checkpoint(step)
    xs = (params["dec_blocks"], self_caches) if have_cache \
        else (params["dec_blocks"],)
    x, new_caches = jax.lax.scan(step, x, xs)
    x = L.apply_norm(cfg, params["norm_dec_final"], x)
    return x, (new_caches if have_cache else None)


def decode_step(cfg: ModelConfig, params: Dict, x, *, lengths,
                self_caches, cross_cache):
    """One decoder token.  x: [B, 1, d] (embedded + positioned).  The self
    caches ride the layer scan's carry and are updated in place, as in
    ``transformer.stack_decode``: callers use the returned ones."""
    def step(carry, xs):
        x, caches = carry
        layer, p, ck, cv = xs
        h = L.apply_norm(cfg, p["norm_self"], x)
        mix, caches = L.attention_decode(cfg, p["self_attn"], h, lengths,
                                         cache=caches, layer=layer)
        x = x + mix
        h = L.apply_norm(cfg, p["norm_cross"], x)
        q = jnp.einsum("bsd,dhk->bshk", h, p["cross_attn"]["wq"])
        o = L.decode_attention(
            q, ck, cv, scale=1.0 / (cfg.head_dim ** 0.5),
            lengths=jnp.full((x.shape[0],), ck.shape[1], jnp.int32))
        x = x + jnp.einsum("bshk,hkd->bsd", o, p["cross_attn"]["wo"])
        h = L.apply_norm(cfg, p["norm_ffn"], x)
        x = x + L.apply_mlp(cfg, p["mlp"], h)
        return (x, caches), None

    layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (x, new_caches), _ = jax.lax.scan(
        step, (x, self_caches),
        (layers, params["dec_blocks"], cross_cache["cross_k"],
         cross_cache["cross_v"]))
    x = L.apply_norm(cfg, params["norm_dec_final"], x)
    return x, new_caches
