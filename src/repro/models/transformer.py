"""Decoder stack for all decoder-only families (dense / MoE / SSM / hybrid).

The stack is described by a repeating **block pattern** — e.g. gemma2 is
``[local-attn+mlp, global-attn+mlp] x 13``, Jamba is ``[7 x mamba, attn] x 9``
with MoE FFNs on alternate layers — and lowered as ``lax.scan`` over pattern
repeats so the compiled HLO contains each distinct block body exactly once
(compile time stays flat at 96 layers / 340B params).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import Family, ModelConfig
from repro.distributed.sharding import prepend_axis, shard_act
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE


@dataclasses.dataclass(frozen=True)
class BlockDesc:
    mixer: str          # "attn" | "attn_local" | "mamba"
    ffn: str            # "dense" | "moe" | "none"
    window: int = 0     # sliding window for "attn_local" / SWA archs

    @property
    def is_attn(self) -> bool:
        return self.mixer.startswith("attn")


def block_pattern(cfg: ModelConfig) -> List[BlockDesc]:
    """The repeating unit of the layer stack."""
    if cfg.family == Family.SSM:
        return [BlockDesc("mamba", "none")]
    if cfg.local_global_alternating:
        # gemma2: even layers local (sliding window), odd layers global
        return [BlockDesc("attn_local", "dense", cfg.sliding_window),
                BlockDesc("attn", "dense")]
    if cfg.attn_every:  # hybrid (jamba): mamba x (k-1), attn at position k-1
        pat = []
        for j in range(cfg.attn_every):
            mixer = "attn" if j == cfg.attn_every - 1 else "mamba"
            ffn = "dense"
            if cfg.moe is not None and (j % cfg.moe.every) == cfg.moe.every - 1:
                ffn = "moe"
            pat.append(BlockDesc(mixer, ffn))
        return pat
    ffn = "moe" if cfg.moe is not None else "dense"
    window = cfg.sliding_window
    mixer = "attn_local" if window else "attn"
    return [BlockDesc(mixer, ffn, window)]


def num_repeats(cfg: ModelConfig) -> int:
    pat = block_pattern(cfg)
    assert cfg.num_layers % len(pat) == 0, (cfg.name, cfg.num_layers, len(pat))
    return cfg.num_layers // len(pat)


# ------------------------------------------------------------------- params


def _block_init(cfg: ModelConfig, desc: BlockDesc, key) -> Dict:
    ks = jax.random.split(key, 2)
    p: Dict = {"norm_mixer": L.norm_init(cfg, cfg.d_model)}
    if desc.mixer == "mamba":
        p["mamba"] = M.mamba_params(cfg, ks[0])
    else:
        p["attn"] = L.attention_params(cfg, ks[0])
    if desc.ffn != "none":
        p["norm_ffn"] = L.norm_init(cfg, cfg.d_model)
        if desc.ffn == "moe":
            p["moe"] = MOE.moe_params(cfg, ks[1])
        else:
            p["mlp"] = L.mlp_params(cfg, ks[1])
    if cfg.use_post_norm:
        p["post_norm_mixer"] = L.norm_init(cfg, cfg.d_model)
        if desc.ffn != "none":
            p["post_norm_ffn"] = L.norm_init(cfg, cfg.d_model)
    return p


def stack_init(cfg: ModelConfig, key) -> List[Dict]:
    """One stacked (leading dim = repeats) param tree per pattern position."""
    pat = block_pattern(cfg)
    R = num_repeats(cfg)
    out = []
    for j, desc in enumerate(pat):
        keys = jax.random.split(jax.random.fold_in(key, j), R)
        stacked = jax.vmap(lambda k, d=desc: _block_init(cfg, d, k))(keys)
        out.append(prepend_axis("layers", stacked))
    return out


# -------------------------------------------------------------------- cache


def stack_cache_init(cfg: ModelConfig, batch: int, max_len: int) -> List[Dict]:
    pat = block_pattern(cfg)
    R = num_repeats(cfg)
    caches = []
    for desc in pat:
        if desc.mixer == "mamba":
            one = M.mamba_cache_init(cfg, batch)
        else:
            one = L.kv_cache_init(cfg, batch, max_len, desc.window)
        stacked = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), one)
        caches.append(stacked)
    return caches


def stack_cache_axes(cfg: ModelConfig, max_len: int) -> List[Dict]:
    """Logical axes for each cache leaf (leading 'layers' dim)."""
    pat = block_pattern(cfg)
    axes = []
    for desc in pat:
        if desc.mixer == "mamba":
            a = {k: ("layers",) + v for k, v in M.mamba_cache_axes().items()}
        else:
            is_ring = desc.window > 0 and desc.window < max_len
            kv = ("layers",) + L.kv_cache_axes(is_ring)
            a = {"k": kv, "v": kv}
            if cfg.kv_cache_dtype == "int8":
                a["k_scale"] = kv[:-1]
                a["v_scale"] = kv[:-1]
        axes.append(a)
    return axes


# ------------------------------------------------------------------ forward


def _apply_block(cfg: ModelConfig, desc: BlockDesc, p: Dict, x, *,
                 mode: str, positions=None, lengths=None, cache=None,
                 layer=None, cos=None, sin=None, dropless: bool = False):
    """mode: 'full' (train/prefill) or 'decode'. Returns (x, new_cache, aux).
    In decode, ``cache`` is the whole stack of this pattern position and
    ``layer`` the repeat to update in it."""
    aux = jnp.zeros((), jnp.float32)
    h = L.apply_norm(cfg, p["norm_mixer"], x)
    if desc.mixer == "mamba":
        if mode == "decode":
            mix, new_cache = M.mamba_decode(cfg, p["mamba"], h, cache, layer)
        else:
            mix, new_cache = M.mamba_forward(cfg, p["mamba"], h, cache)
    else:
        window = desc.window
        if mode == "decode":
            mix, new_cache = L.attention_decode(
                cfg, p["attn"], h, lengths, window=window, cache=cache,
                layer=layer, cos=cos, sin=sin)
        else:
            mix, new_cache = L.attention_forward(
                cfg, p["attn"], h, positions, causal=True, window=window,
                cache=cache, cos=cos, sin=sin)
    if cfg.use_post_norm:
        mix = L.apply_norm(cfg, p["post_norm_mixer"], mix)
    x = x + mix
    if desc.ffn != "none":
        h = L.apply_norm(cfg, p["norm_ffn"], x)
        if desc.ffn == "moe":
            if mode == "decode":
                y, aux = MOE.apply_moe(cfg, p["moe"], h, chunk_size=1,
                                       dropless=True)
            else:
                y, aux = MOE.apply_moe(cfg, p["moe"], h, dropless=dropless)
        else:
            y = L.apply_mlp(cfg, p["mlp"], h)
        if cfg.use_post_norm:
            y = L.apply_norm(cfg, p["post_norm_ffn"], y)
        x = x + y
    return x, new_cache, aux


def stack_forward(cfg: ModelConfig, stacked_params: List[Dict], x, *,
                  positions, caches: Optional[List] = None,
                  remat: bool = False, dropless: bool = False):
    """Full-sequence pass.  x: [B, S, d].  Returns (x, new_caches, aux)."""
    pat = block_pattern(cfg)
    cos = sin = None
    if positions is not None and not cfg.attention_free:
        cos, sin = L.positional_cos_sin(cfg, positions)

    have_cache = caches is not None

    def step(carry, xs):
        x, aux = carry
        params_j = xs[0]
        caches_j = xs[1] if have_cache else [None] * len(pat)
        new_caches_j = []
        for desc, p, c in zip(pat, params_j, caches_j):
            x, nc, a = _apply_block(cfg, desc, p, x, mode="full",
                                    positions=positions, cache=c,
                                    cos=cos, sin=sin, dropless=dropless)
            new_caches_j.append(nc if nc is not None else {})
            aux = aux + a
        # "act_seq" engages Megatron-style sequence parallelism for the
        # saved-per-layer residual carry (rules-controlled; default off)
        x = shard_act(x, "batch", "act_seq", "act_embed")
        return (x, aux), tuple(new_caches_j)

    if remat:
        # save-nothing checkpointing: the scan carry (one residual stream per
        # layer) is the only saved activation — minimal HBM at 96L/340B
        step = jax.checkpoint(step)

    xs = (stacked_params, caches) if have_cache else (stacked_params,)
    (x, aux), new_caches = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                                        xs)
    return x, (list(new_caches) if have_cache else None), aux


def stack_decode(cfg: ModelConfig, stacked_params: List[Dict], x, *,
                 lengths, caches: List):
    """One-token decode.  x: [B, 1, d].  Returns (x, new_caches, aux).

    The caches ride the layer scan's carry and are updated in place: each
    attention layer writes one K/V row per sequence and reads its own
    layer where it lies, each state layer replaces its state.  Under a jit
    that donates them (the serving engine's ``_decode_step``), the returned
    caches take the given ones' buffers: the caches passed in are consumed,
    and callers must use the returned ones."""
    pat = block_pattern(cfg)
    cos = sin = None
    if not cfg.attention_free and cfg.pos_emb.value in ("rope", "mrope"):
        pos = lengths[:, None]
        cos, sin = L.positional_cos_sin(cfg, pos)

    def step(carry, xs):
        x, aux, caches = carry
        layer, params_j = xs
        new_caches = []
        for desc, p, c in zip(pat, params_j, caches):
            x, c, a = _apply_block(cfg, desc, p, x, mode="decode",
                                   lengths=lengths, cache=c, layer=layer,
                                   cos=cos, sin=sin)
            new_caches.append(c)
            aux = aux + a
        return (x, aux, tuple(new_caches)), None

    layers = jnp.arange(num_repeats(cfg), dtype=jnp.int32)
    (x, aux, caches), _ = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.float32), tuple(caches)),
        (layers, stacked_params))
    return x, list(caches), aux
