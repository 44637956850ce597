"""Unified model API over all assigned families.

``build_model(cfg)`` returns a ``Model`` whose methods are pure functions of
(params, batch/cache) — directly jit/pjit-able:

    init(rng)                  -> annotated param tree (Param-boxed)
    forward(params, batch)     -> (hidden [B,S,d], aux)       (teacher-forced)
    loss(params, batch)        -> (scalar, metrics)           (chunked CE)
    init_cache(B, max_len,...) -> cache pytree
    cache_axes(max_len, ...)   -> logical-axes pytree for the cache
    prefill(params, batch, cache)        -> (logits [B,V], cache, lengths)
    decode(params, tokens, cache, lengths) -> (logits [B,V], cache)
    input_specs(shape)         -> (ShapeDtypeStruct dict, logical-axes dict)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import Family, ModelConfig, ShapeConfig, ShapeKind
from repro.distributed.sharding import shard_act
from repro.models import encdec as ED
from repro.models import layers as L
from repro.models import transformer as T

CE_CHUNK = 512  # sequence-chunked cross-entropy (bounds the logits buffer)


def _softcap(x, cap: float):
    return jnp.tanh(x / cap) * cap if cap > 0 else x


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig

    # ------------------------------------------------------------- params
    def init(self, rng) -> Dict:
        cfg = self.cfg
        k_emb, k_stack, k_head, k_norm = jax.random.split(rng, 4)
        params: Dict[str, Any] = {
            "embed": L.embed_param(k_emb, (cfg.vocab_size, cfg.d_model),
                                   ("vocab", "embed")),
        }
        if cfg.is_encdec:
            params["encdec"] = ED.encdec_init(cfg, k_stack)
        else:
            params["blocks"] = T.stack_init(cfg, k_stack)
            params["norm_final"] = L.norm_init(cfg, cfg.d_model)
        if not cfg.tie_embeddings:
            params["head"] = L.dense_param(
                k_head, (cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
        return params

    # ------------------------------------------------------------- pieces
    def _embed(self, params, tokens):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.bfloat16)
        if cfg.scale_embedding:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
        return x

    def _logits(self, params, x):
        """x: [..., d] -> logits [..., V] (fp32)."""
        cfg = self.cfg
        if cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", x, params["embed"],
                                preferred_element_type=jnp.float32)
        else:
            logits = jnp.einsum("...d,dv->...v", x, params["head"],
                                preferred_element_type=jnp.float32)
        logits = _softcap(logits, cfg.final_logit_softcap)
        if logits.ndim == 3:
            logits = shard_act(logits, "batch", None, "act_vocab")
        elif logits.ndim == 2:
            logits = shard_act(logits, "batch", "act_vocab")
        return logits

    # ------------------------------------------------------------ forward
    def forward(self, params, batch, *, remat: Optional[bool] = None,
                dropless: bool = False):
        """Teacher-forced pass to final hidden states.  Returns (x, aux).
        dropless=True uses no-overflow MoE routing (inference semantics)."""
        cfg = self.cfg
        remat = cfg.remat if remat is None else remat
        if cfg.is_encdec:
            enc_out = ED.encode(cfg, params["encdec"], batch["src_embeds"],
                                remat=remat)
            tgt = batch["tgt_tokens"]
            B, S = tgt.shape
            x = self._embed(params, tgt)
            pos = jax.lax.dynamic_slice_in_dim(
                params["encdec"]["pos_dec"], 0, S, 0)
            x = x + pos[None].astype(x.dtype)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
            x, _ = ED.decode_forward(cfg, params["encdec"], x, enc_out,
                                     positions=positions, remat=remat)
            return x, jnp.zeros((), jnp.float32)

        if cfg.family == Family.VLM and "embeds" in batch:
            x = batch["embeds"].astype(jnp.bfloat16)
            B, S, _ = x.shape
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        else:
            tokens = batch["tokens"]
            B, S = tokens.shape
            x = self._embed(params, tokens)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
        x = shard_act(x, "batch", None, "act_embed")
        x, _, aux = T.stack_forward(cfg, params["blocks"], x,
                                    positions=positions, remat=remat,
                                    dropless=dropless)
        x = L.apply_norm(cfg, params["norm_final"], x)
        return x, aux

    # --------------------------------------------------------------- loss
    def _labels(self, batch) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Returns (labels [B,S], mask [B,S]) aligned with forward() output."""
        cfg = self.cfg
        if cfg.is_encdec:
            t = batch["tgt_tokens"]
        elif cfg.family == Family.VLM and "labels" in batch:
            lab = batch["labels"]
            return lab, (lab >= 0).astype(jnp.float32)
        else:
            t = batch["tokens"]
        labels = jnp.concatenate(
            [t[:, 1:], jnp.zeros_like(t[:, :1])], axis=1)
        mask = jnp.concatenate(
            [jnp.ones_like(t[:, 1:], jnp.float32),
             jnp.zeros_like(t[:, :1], jnp.float32)], axis=1)
        return labels, mask

    def loss(self, params, batch, *, remat: Optional[bool] = None):
        cfg = self.cfg
        x, aux = self.forward(params, batch, remat=remat)
        labels, mask = self._labels(batch)
        B, S, d = x.shape
        cs = min(CE_CHUNK, S)
        pad = (-S) % cs
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)))
            mask = jnp.pad(mask, ((0, 0), (0, pad)))
        nc = x.shape[1] // cs

        def ce_chunk(_, inp):
            xc, yc, mc = inp                       # [B, cs, d], [B, cs], ...
            logits = self._logits(params, xc)      # fp32 [B, cs, V]
            lse = jax.nn.logsumexp(logits, axis=-1)
            ll = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
            return None, (jnp.sum((lse - ll) * mc), jnp.sum(mc))

        ce_chunk = jax.checkpoint(ce_chunk)
        xs = (x.reshape(B, nc, cs, d).transpose(1, 0, 2, 3),
              labels.reshape(B, nc, cs).transpose(1, 0, 2),
              mask.reshape(B, nc, cs).transpose(1, 0, 2))
        _, (losses, counts) = jax.lax.scan(ce_chunk, None, xs)
        total = jnp.sum(losses) / jnp.maximum(jnp.sum(counts), 1.0)
        loss = total + 0.01 * aux
        return loss, {"ce": total, "aux": aux, "tokens": jnp.sum(counts)}

    # -------------------------------------------------------------- cache
    def init_cache(self, batch: int, max_len: int, enc_len: int = 0):
        cfg = self.cfg
        if cfg.is_encdec:
            enc_len = enc_len or max_len
            kvh, hd = cfg.num_kv_heads, cfg.head_dim
            self_one = L.kv_cache_init(cfg, batch, max_len)
            Ld = cfg.num_layers
            return {
                "self": jax.tree.map(
                    lambda x: jnp.broadcast_to(x[None], (Ld,) + x.shape),
                    self_one),
                "cross_k": jnp.zeros((Ld, batch, enc_len, kvh, hd),
                                     jnp.bfloat16),
                "cross_v": jnp.zeros((Ld, batch, enc_len, kvh, hd),
                                     jnp.bfloat16),
            }
        return T.stack_cache_init(cfg, batch, max_len)

    def cache_axes(self, max_len: int):
        cfg = self.cfg
        if cfg.is_encdec:
            kv = ("layers",) + L.kv_cache_axes(False)
            out = {"self": {"k": kv, "v": kv},
                   "cross_k": kv, "cross_v": kv}
            if cfg.kv_cache_dtype == "int8":
                out["self"]["k_scale"] = kv[:-1]
                out["self"]["v_scale"] = kv[:-1]
            return out
        return T.stack_cache_axes(cfg, max_len)

    # ------------------------------------------------------------ prefill
    def prefill(self, params, batch, cache):
        """Prompt pass.  Returns (last-token logits [B,V], cache, lengths)."""
        cfg = self.cfg
        if cfg.is_encdec:
            enc_out = ED.encode(cfg, params["encdec"], batch["src_embeds"])
            cross = ED.build_cross_cache(cfg, params["encdec"], enc_out)
            tgt = batch["tgt_tokens"]
            B, S = tgt.shape
            x = self._embed(params, tgt)
            pos = jax.lax.dynamic_slice_in_dim(
                params["encdec"]["pos_dec"], 0, S, 0)
            x = x + pos[None].astype(x.dtype)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
            x, new_self = ED.decode_forward(cfg, params["encdec"], x, enc_out,
                                            positions=positions,
                                            self_caches=cache["self"])
            lengths = jnp.full((B,), S, jnp.int32)
            logits = self._logits(params, x[:, -1])
            new_cache = {"self": new_self, "cross_k": cross["cross_k"],
                         "cross_v": cross["cross_v"]}
            return logits, new_cache, lengths

        if cfg.family == Family.VLM and "embeds" in batch:
            x = batch["embeds"].astype(jnp.bfloat16)
            B, S, _ = x.shape
            positions = batch.get("positions")
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(S, dtype=jnp.int32)[None], (B, S))
        else:
            tokens = batch["tokens"]
            B, S = tokens.shape
            x = self._embed(params, tokens)
            positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32)[None],
                                         (B, S))
        x = shard_act(x, "batch", None, "act_embed")
        x, new_cache, _ = T.stack_forward(cfg, params["blocks"], x,
                                          positions=positions, caches=cache,
                                          remat=False, dropless=True)
        x = L.apply_norm(cfg, params["norm_final"], x)
        lengths = batch.get("lengths")
        if lengths is None:
            lengths = jnp.full((B,), S, jnp.int32)
            x_last = x[:, -1]
        else:
            x_last = jnp.take_along_axis(
                x, (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        return self._logits(params, x_last), new_cache, lengths

    # ------------------------------------------------------------- decode
    def decode(self, params, tokens, cache, lengths):
        """One token per sequence.  tokens: [B] int32; lengths: [B] current
        cache length (count of tokens already in the cache).  The cache is
        updated in place (``transformer.stack_decode``): under a jit that
        donates it, use the returned cache, not the one passed in."""
        cfg = self.cfg
        x = self._embed(params, tokens[:, None])
        if cfg.is_encdec:
            pos = jnp.take(params["encdec"]["pos_dec"],
                           jnp.clip(lengths, 0, ED.POS_TABLE_LEN - 1), axis=0)
            x = x + pos[:, None].astype(x.dtype)
            x, new_self = ED.decode_step(
                cfg, params["encdec"], x, lengths=lengths,
                self_caches=cache["self"],
                cross_cache={"cross_k": cache["cross_k"],
                             "cross_v": cache["cross_v"]})
            new_cache = {"self": new_self, "cross_k": cache["cross_k"],
                         "cross_v": cache["cross_v"]}
            return self._logits(params, x[:, 0]), new_cache
        x, new_cache, _ = T.stack_decode(cfg, params["blocks"], x,
                                         lengths=lengths, caches=cache)
        x = L.apply_norm(cfg, params["norm_final"], x)
        return self._logits(params, x[:, 0]), new_cache

    # -------------------------------------------------------- input specs
    def input_specs(self, shape: ShapeConfig):
        """ShapeDtypeStruct stand-ins + logical axes for the dry-run."""
        cfg = self.cfg
        B, S = shape.global_batch, shape.seq_len
        f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
        SDS = jax.ShapeDtypeStruct
        if shape.kind == ShapeKind.DECODE:
            inputs = {"tokens": SDS((B,), i32), "lengths": SDS((B,), i32)}
            axes = {"tokens": ("batch",), "lengths": ("batch",)}
            return inputs, axes
        if cfg.is_encdec:
            tgt_len = S if shape.kind == ShapeKind.TRAIN else 1
            inputs = {"src_embeds": SDS((B, S, cfg.d_model), bf16),
                      "tgt_tokens": SDS((B, tgt_len), i32)}
            axes = {"src_embeds": ("batch", None, None),
                    "tgt_tokens": ("batch", None)}
            return inputs, axes
        if cfg.family == Family.VLM:
            inputs = {"embeds": SDS((B, S, cfg.d_model), bf16),
                      "positions": SDS((3, B, S), i32)}
            axes = {"embeds": ("batch", None, None),
                    "positions": (None, "batch", None)}
            if shape.kind == ShapeKind.TRAIN:
                inputs["labels"] = SDS((B, S), i32)
                axes["labels"] = ("batch", None)
            return inputs, axes
        inputs = {"tokens": SDS((B, S), i32)}
        axes = {"tokens": ("batch", None)}
        return inputs, axes


def build_model(cfg: ModelConfig) -> Model:
    return Model(cfg)
