"""The dense decoder block: multi-head or grouped-query attention and an
MLP, one stacked group of identical blocks (``"arch": "dense"``).

Weights.  The layout the program's model takes (``Model.init`` after
unboxing: one stacked tree per block, leading axis = layers).  Scales
follow the usual fan-in rule (std 1/sqrt(fan_in); embeddings
1/sqrt(d_model)).  LayerNorm scales and biases are drawn too (around 1
and 0), so that the comparison covers them.

Reference.  Multi-head and grouped-query attention, SwiGLU and tanh-GELU
MLPs, non-parametric and affine LayerNorm, RoPE (half-split rotation) and
causal sliding-window attention, each sequence one teacher-forced pass
with no cache.  Departures from the published models, which the program
shares and the reference therefore follows, are listed in each
configuration file under ``departures``.

Counts:

* decode step: every weight read once at the weights' dtype, each active
  sequence's live KV read at its own length (for windowed layers at most
  the window), one new KV row written per active sequence, and the fp32
  logits written; FLOPs are 2 x matmul parameters x active sequences plus
  attention over each live context;
* prefill: 2 x matmul parameters x prompt tokens, plus causal attention
  over the window, plus the last token's logits; bytes are the weights
  and the KV written.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.counts import DTYPE_BYTES, LOGIT_BYTES
from bench.reference import HI, linear

Q_BLOCK = 512

# program-side names of the configuration file's model keys
ACTIVATION = {"swiglu": "swiglu", "gelu_tanh": "gelu"}


def program_config(cfg: Dict):
    """The program's ModelConfig for a configuration file: the registry's
    entry with every size the file states put in."""
    from repro.configs import Activation, Norm, get_config
    m = cfg["model"]
    base = get_config(cfg["program"]["config"])
    return dataclasses.replace(
        base, num_layers=m["num_layers"], d_model=m["d_model"],
        num_heads=m["num_heads"], num_kv_heads=m["num_kv_heads"],
        head_dim=m["head_dim"], d_ff=m["d_ff"], vocab_size=m["vocab_size"],
        activation=Activation(ACTIVATION[m["activation"]]),
        norm=Norm(m["norm"]), rope_theta=m["rope_theta"],
        sliding_window=m.get("sliding_window") or 0,
        tie_embeddings=m["tie_embeddings"], param_dtype=m["param_dtype"],
        kv_cache_dtype=m["kv_dtype"])


# ------------------------------------------------------------------ weights
def shapes(m: Dict) -> Dict:
    """Leaf name -> (shape, dtype, std, mean) in the program's layout."""
    L, d, h, kvh, hd, f, v = (m["num_layers"], m["d_model"], m["num_heads"],
                              m["num_kv_heads"], m["head_dim"], m["d_ff"],
                              m["vocab_size"])
    bf = jnp.dtype(m["param_dtype"])
    out = {
        "embed": ((v, d), bf, 1 / np.sqrt(d), 0.0),
        "attn.wq": ((L, d, h, hd), bf, 1 / np.sqrt(d), 0.0),
        "attn.wk": ((L, d, kvh, hd), bf, 1 / np.sqrt(d), 0.0),
        "attn.wv": ((L, d, kvh, hd), bf, 1 / np.sqrt(d), 0.0),
        "attn.wo": ((L, h, hd, d), bf, 1 / np.sqrt(h * hd), 0.0),
        "mlp.wi": ((L, d, f), bf, 1 / np.sqrt(d), 0.0),
        "mlp.wo": ((L, f, d), bf, 1 / np.sqrt(f), 0.0),
    }
    if m["activation"] in ("swiglu", "geglu"):
        out["mlp.wg"] = ((L, d, f), bf, 1 / np.sqrt(d), 0.0)
    if not m["tie_embeddings"]:
        out["head"] = ((d, v), bf, 1 / np.sqrt(d), 0.0)
    if m["norm"] == "layernorm":
        for n in ("norm_mixer", "norm_ffn"):
            out[f"{n}.scale"] = ((L, d), jnp.float32, 0.1, 1.0)
            out[f"{n}.bias"] = ((L, d), jnp.float32, 0.1, 0.0)
        out["norm_final.scale"] = ((d,), jnp.float32, 0.1, 1.0)
        out["norm_final.bias"] = ((d,), jnp.float32, 0.1, 0.0)
    elif m["norm"] == "rmsnorm":
        raise ValueError("rmsnorm configurations are not laid out here yet")
    return out


def nest(flat: Dict) -> Dict:
    """Flat leaf names -> the program's tree."""
    block = {"attn": {}, "mlp": {}, "norm_mixer": {}, "norm_ffn": {}}
    tree = {"norm_final": {}}
    for name, x in flat.items():
        top, _, leaf = name.partition(".")
        if top in block:
            block[top][leaf] = x
        elif top == "norm_final":
            tree["norm_final"][leaf] = x
        else:
            tree[top] = x
    tree["blocks"] = [block]
    return tree


# ---------------------------------------------------------------- reference
def _norm(m: Dict, p: Dict, x):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + m["norm_eps"])
    if m["norm"] == "layernorm":
        y = y * p["scale"] + p["bias"]
    return y


def _rope(x, pos, theta: float):
    """x [S, H, D], half-split rotation at absolute positions ``pos``."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (np.arange(half, dtype=np.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _attention(m: Dict, q, k, v):
    """Causal (optionally sliding-window) attention, query block by query
    block so that the scores fit.  q [S, H, D]; k, v [S, KVH, D]."""
    S, H, D = q.shape
    G = H // m["num_kv_heads"]
    k = jnp.repeat(k, G, axis=1)       # head i reads kv head i // G
    v = jnp.repeat(v, G, axis=1)
    window = m.get("sliding_window") or 0
    kpos = jnp.arange(S)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(D)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

    out = jax.lax.map(block, jnp.arange(S // Q_BLOCK))
    return out.reshape(S, H, D)


def _mlp(m: Dict, p: Dict, x, fp8: bool):
    h = linear(x, p["wi"], 1, fp8)
    if m["activation"] == "swiglu":
        h = jax.nn.silu(linear(x, p["wg"], 1, fp8)) * h
    elif m["activation"] == "gelu_tanh":
        h = jax.nn.gelu(h, approximate=True)
    else:
        raise ValueError(f"activation {m['activation']!r} is not covered")
    return linear(h, p["wo"], 1, fp8)


def hidden(m: Dict, params: Dict, tokens, fp8: bool = False):
    """Final normed hidden states [S, d] of one teacher-forced sequence."""
    S = tokens.shape[0]
    if m.get("use_bias"):
        raise ValueError("linear biases are not covered by the reference")
    if S % Q_BLOCK:
        raise ValueError(f"sequence length {S} is not a multiple of "
                         f"{Q_BLOCK}")
    pos = jnp.arange(S)
    x = params["embed"][tokens].astype(jnp.float32)
    blk = params["blocks"][0]

    def layer(x, p):
        h = _norm(m, p["norm_mixer"], x)
        a = p["attn"]
        q = _rope(linear(h, a["wq"], 1, fp8), pos, m["rope_theta"])
        k = _rope(linear(h, a["wk"], 1, fp8), pos, m["rope_theta"])
        v = linear(h, a["wv"], 1, fp8)
        o = _attention(m, q, k, v)
        x = x + linear(o, a["wo"], 2, fp8)
        h = _norm(m, p["norm_ffn"], x)
        return x + _mlp(m, p["mlp"], h, fp8), None

    x, _ = jax.lax.scan(layer, x, blk)
    return _norm(m, params["norm_final"], x)


def logits(m: Dict, params: Dict, h, fp8: bool = False):
    """h [..., d] -> float32 logits [..., V]."""
    if m["tie_embeddings"]:
        return linear(h, params["embed"].T, 1, fp8)
    return linear(h, params["head"], 1, fp8)


# ------------------------------------------------------------------- counts
def _gated(m: Dict) -> bool:
    return m["activation"] in ("swiglu", "geglu")


def layer_matmul_params(m: Dict) -> int:
    """Matmul parameters of one transformer block."""
    d, h, kvh, hd, f = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    attn = d * h * hd + 2 * d * kvh * hd + h * hd * d
    mlp = d * f * (3 if _gated(m) else 2)
    return attn + mlp


def head_params(m: Dict) -> int:
    return m["d_model"] * m["vocab_size"]


def weight_bytes(m: Dict) -> int:
    """Every weight once: the blocks, the embedding table (which is also
    the output head where the embeddings are tied), an untied head, and
    the norms' parameters (fp32)."""
    wb = DTYPE_BYTES[m["param_dtype"]]
    n = m["num_layers"] * layer_matmul_params(m) + head_params(m)
    if not m["tie_embeddings"]:
        n += head_params(m)
    norms = {"layernorm": 2, "rmsnorm": 1, "nonparam_ln": 0}[m["norm"]]
    norm_bytes = norms * m["d_model"] * (2 * m["num_layers"] + 1) * 4
    return n * wb + norm_bytes


def kv_row_bytes(m: Dict) -> int:
    """K and V of one position in one layer."""
    return 2 * m["num_kv_heads"] * m["head_dim"] * DTYPE_BYTES[m["kv_dtype"]]


def _live(m: Dict, ctx: int) -> int:
    w = m.get("sliding_window") or 0
    return min(ctx, w) if w else ctx


def _attn_flops(m: Dict, positions: int) -> int:
    """QK^T and PV of one query over ``positions`` keys, all layers."""
    return 4 * m["num_heads"] * m["head_dim"] * positions * m["num_layers"]


def decode_step(m: Dict, contexts: Iterable[int]) -> Tuple[float, float]:
    """(FLOPs, bytes) of one decode step.  ``contexts`` holds each active
    sequence's length including the token this step writes."""
    ctx = [int(c) for c in contexts]
    b = len(ctx)
    per_token = 2 * (m["num_layers"] * layer_matmul_params(m)
                     + head_params(m))
    flops = per_token * b + sum(_attn_flops(m, _live(m, c)) for c in ctx)
    row = kv_row_bytes(m) * m["num_layers"]
    # the row written this step is read too: it is part of the live context
    kv_read = sum(_live(m, c) for c in ctx) * row
    byts = (weight_bytes(m) + kv_read + b * row
            + b * m["vocab_size"] * LOGIT_BYTES)
    return float(flops), float(byts)


def prefill(m: Dict, prompt_len: int) -> Tuple[float, float]:
    """(FLOPs, bytes) of one single-sequence prefill of ``prompt_len``."""
    s = int(prompt_len)
    per_token = 2 * m["num_layers"] * layer_matmul_params(m)
    w = m.get("sliding_window") or 0
    if w and s > w:
        keys = w * (w + 1) // 2 + (s - w) * w
    else:
        keys = s * (s + 1) // 2
    flops = per_token * s + _attn_flops(m, keys) + 2 * head_params(m)
    written = _live(m, s) * kv_row_bytes(m) * m["num_layers"]
    byts = weight_bytes(m) + written + m["vocab_size"] * LOGIT_BYTES
    return float(flops), float(byts)
