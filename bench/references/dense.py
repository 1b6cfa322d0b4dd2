"""The dense decoder: RMSNorm, rotary positions (rotate-half) over the
leading ``rope_fraction`` of each head, causal grouped-query attention
with optional q/k/v bias, a SwiGLU MLP, and an output head that is its
own matrix or, with ``tie_embeddings``, the input embedding.

Written from the published equations in plain ``jax.numpy``; it imports
nothing of the program. Every function takes the configuration file as
a dict; ``head_dim`` defaults to ``d_model // n_heads``.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

#: keys the reference reads that the program has no field for, each
#: with the value the program implies: it rotates whole heads
PROGRAM_IMPLIED = {"rope_fraction": 1.0}

Q_BLOCK = 1024


def _head_dim(cfg: Dict[str, Any]) -> int:
    return cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]


def param_layout(cfg: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf -> (shape, init, std): embed N(0, 0.02); norm gains ones;
    q/k/v biases zeros; every matrix N(0, 1/fan_in)."""
    d, L, V, f = cfg["d_model"], cfg["n_layers"], cfg["vocab"], cfg["d_ff"]
    hd = _head_dim(cfg)
    H, KV = cfg["n_heads"] * hd, cfg["n_kv_heads"] * hd

    def mat(rows, cols, layers=True):
        shape = (L, rows, cols) if layers else (rows, cols)
        return (shape, "normal", 1.0 / math.sqrt(rows))

    attn = {"wq": mat(d, H), "wk": mat(d, KV), "wv": mat(d, KV),
            "wo": mat(H, d)}
    if cfg.get("qkv_bias"):
        attn.update(bq=((L, H), "zeros", 0.0), bk=((L, KV), "zeros", 0.0),
                    bv=((L, KV), "zeros", 0.0))
    layout = {
        "embed": ((V, d), "normal", 0.02),
        "final_norm": ((d,), "ones", 0.0),
        "layers": {
            "attn_norm": ((L, d), "ones", 0.0),
            "attn": attn,
            "ffn_norm": ((L, d), "ones", 0.0),
            "ffn": {"wg": mat(d, f), "wu": mat(d, f), "wd": mat(f, d)},
        },
    }
    if not cfg.get("tie_embeddings"):
        layout["lm_head"] = mat(d, V, layers=False)
    return layout


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def rope(x, theta, fraction):
    """Rotary positions, rotate-half form, on the leading ``fraction`` of
    each head. x: (B, S, H, D)."""
    S, D = x.shape[1], x.shape[-1]
    rd = int(D * fraction) // 2 * 2
    inv = 1.0 / theta ** (jnp.arange(0, rd, 2, dtype=jnp.float32) / rd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv      # (S, rd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2, rest = x[..., :rd // 2], x[..., rd // 2:rd], x[..., rd:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest],
                           -1)


def causal_attention(q, k, v, mm):
    """softmax(q k^T / sqrt(D), causal) v, one block of query rows at a
    time. q: (B, S, H, D); k, v: (B, S, KV, D)."""
    B, S, H, D = q.shape
    rep = H // k.shape[2]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    qb = min(S, Q_BLOCK)
    qs = (q / math.sqrt(D)).reshape(B, S // qb, qb, H, D)

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_index_in_dim(qs, i, 1, keepdims=False)
        s = mm("bqhd,bkhd->bhqk", qi, k)
        rows = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, :] <= rows[:, None], s, -jnp.inf)
        return mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(S // qb))       # (nq, B, qb, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, D)


def loss_fn(params, tokens, targets, cfg: Dict[str, Any], mm):
    """Mean next-token cross-entropy of the configuration's decoder."""
    d, eps = cfg["d_model"], cfg["norm_eps"]
    hd = _head_dim(cfg)
    B, S = tokens.shape
    h = params["embed"].astype(jnp.float32)[tokens] * math.sqrt(d)

    def proj(x, a, w):
        y = mm("bsd,dh->bsh", x, a[w])
        if cfg.get("qkv_bias"):
            y = y + a["b" + w[1]]
        return y.reshape(B, S, -1, hd)

    def layer(h, lp):
        a, f = lp["attn"], lp["ffn"]
        x = rms_norm(h, lp["attn_norm"], eps)
        q, k, v = proj(x, a, "wq"), proj(x, a, "wk"), proj(x, a, "wv")
        q = rope(q, cfg["rope_theta"], cfg["rope_fraction"])
        k = rope(k, cfg["rope_theta"], cfg["rope_fraction"])
        o = causal_attention(q, k, v, mm).reshape(B, S, -1)
        h = h + mm("bsh,hd->bsd", o, a["wo"])
        x = rms_norm(h, lp["ffn_norm"], eps)
        g = mm("bsd,df->bsf", x, f["wg"])
        u = mm("bsd,df->bsf", x, f["wu"])
        return h + mm("bsf,fd->bsd", jax.nn.silu(g) * u, f["wd"]), None

    h, _ = jax.lax.scan(jax.checkpoint(layer), h, params["layers"])
    h = rms_norm(h, params["final_norm"], eps)
    if cfg.get("tie_embeddings"):
        logits = mm("bsd,vd->bsv", h, params["embed"])
    else:
        logits = mm("bsd,dv->bsv", h, params["lm_head"])
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Attention and MLP matrices of every layer and the output head (a
    tied head is the embedding, multiplied all the same); the input
    embedding is a lookup, and biases and norm gains add no products."""
    d, L = cfg["d_model"], cfg["n_layers"]
    hd = _head_dim(cfg)
    q = d * cfg["n_heads"] * hd
    kv = 2 * d * cfg["n_kv_heads"] * hd
    o = cfg["n_heads"] * hd * d
    mlp = 3 * d * cfg["d_ff"]          # SwiGLU: gate, up, down
    return L * (q + kv + o + mlp) + d * cfg["vocab"]


def attention_flops_per_token(cfg: Dict[str, Any]) -> float:
    """Causal QK^T and PV: 2 x 2 x heads x head_dim x (S + 1) / 2 a
    layer forward, three times that for forward plus backward."""
    forward = 2.0 * cfg["n_heads"] * _head_dim(cfg) * (cfg["seq"] + 1)
    return 3.0 * forward * cfg["n_layers"]
