"""Pure-jnp oracles for every Pallas kernel (test + CPU fallback path)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def topk_scatter_ref(vals: jax.Array, idxs: jax.Array, block: int):
    nb, k = vals.shape
    out = jnp.zeros((nb, block), vals.dtype)
    return jax.vmap(lambda o, i, v: o.at[i].add(v))(out, idxs, vals)


def pack_select_ref(xb: jax.Array, k: int):
    """Fused compress-and-pack oracle: top-k by magnitude, then int8
    quantization of the selected values against the per-row absmax."""
    mag = jnp.abs(xb.astype(jnp.float32))
    _, idx = jax.lax.top_k(mag, k)
    vals = jnp.take_along_axis(xb.astype(jnp.float32), idx, axis=1)
    scale = jnp.maximum(jnp.max(jnp.abs(vals), axis=1, keepdims=True)
                        / 127.0, 1e-12)
    q = jnp.clip(jnp.round(vals / scale), -127, 127).astype(jnp.int8)
    return q, idx.astype(jnp.int32), scale


def pack_scatter_ref(q: jax.Array, idxs: jax.Array, scale: jax.Array,
                     block: int):
    vals = q.astype(jnp.float32) * scale
    nb, k = vals.shape
    out = jnp.zeros((nb, block), jnp.float32)
    return jax.vmap(lambda o, i, v: o.at[i].add(v))(out, idxs, vals)


def quantize_ref(xb: jax.Array):
    x = xb.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True) / 127.0,
                        1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_ref(q: jax.Array, scale: jax.Array):
    return q.astype(jnp.float32) * scale


def adam_tile_update_ref(p, g, mu, nu, hyper):
    lr, b1, b2, eps, c1, c2 = (hyper[0, i] for i in range(6))
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    mu2 = b1 * mu + (1.0 - b1) * gf
    nu2 = b2 * nu + (1.0 - b2) * gf * gf
    step = lr * (mu2 / c1) / (jnp.sqrt(nu2 / c2) + eps)
    return (pf - step).astype(p.dtype), mu2, nu2


# ---------------- fused decompress-and-apply (replay path) -----------------

def adam_replay_update_ref(p, g, mu, nu, hyper):
    """Adam tail for the replay kernels: identical to
    ``adam_tile_update_ref`` except the moment complements come from
    hyper slots 6/7 (pre-rounded ``1-b1`` / ``1-b2``), matching
    ``optim.adam.adam_update`` bit for bit."""
    lr, b1, b2, eps, c1, c2, om1, om2 = (hyper[0, i] for i in range(8))
    pf, gf = p.astype(jnp.float32), g.astype(jnp.float32)
    mu2 = b1 * mu + om1 * gf
    nu2 = b2 * nu + om2 * gf * gf
    step = lr * (mu2 / c1) / (jnp.sqrt(nu2 / c2) + eps)
    return (pf - step).astype(p.dtype), mu2, nu2


def topk_apply_ref(vals, idxs, p, mu, nu, hyper, *, block: int):
    """Scatter-decode a top-k wire payload and apply one Adam step —
    oracle for ``replay.topk_apply`` (decode math == the host
    decompressors', update == ``optim.adam.adam_update``)."""
    nb, k = vals.shape
    g = jnp.zeros((nb, block), jnp.float32)
    g = jax.vmap(lambda o, i, v: o.at[i].add(v))(
        g, idxs, vals.astype(jnp.float32))
    return adam_replay_update_ref(p, g, mu, nu, hyper)


def packed_apply_ref(q, idxs, scale, p, mu, nu, hyper, *, block: int):
    """Dequant + scatter-decode a packed (int8 top-k) payload and apply
    one Adam step — oracle for ``replay.packed_apply``."""
    vals = q.astype(jnp.float32) * scale
    return topk_apply_ref(vals, idxs, p, mu, nu, hyper, block=block)


def quant_apply_ref(q, scale, p, mu, nu, hyper):
    """Dequant a quant8 payload and apply one Adam step — oracle for
    ``replay.quant_apply``. q: (nb, block) int8; scale: (nb, 1) f32."""
    g = q.astype(jnp.float32) * scale
    return adam_replay_update_ref(p, g, mu, nu, hyper)


# -------------------- quantized row-span codec -----------------------

def span_pack_ref(xb: jax.Array, bits: int):
    """Oracle for ``pack.span_pack``: per-row absmax quantize (int8 or
    nibble-packed int4). xb: (nb, cols) with cols even for int4."""
    x = xb.astype(jnp.float32)
    qmax = 127.0 if bits == 8 else 7.0
    # reciprocal-multiply, matching the numpy host codec bit for bit
    scale = jnp.maximum(
        jnp.max(jnp.abs(x), axis=1, keepdims=True)
        * jnp.float32(1.0 / qmax), 1e-12)
    qi = jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
    if bits == 8:
        return qi.astype(jnp.int8), scale
    lo = qi[:, 0::2] & 0xF
    hi = qi[:, 1::2] & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8), scale


def span_decode_ref(q: jax.Array, scale: jax.Array, bits: int):
    """Oracle for ``replay.quant_span_decode``: wire bytes -> dense f32
    rows (cols = wire_cols for int8, 2*wire_cols for int4)."""
    if bits == 8:
        return q.astype(jnp.float32) * scale
    u = q.astype(jnp.int32)
    lo = u & 0xF
    hi = (u >> 4) & 0xF
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    R, W = u.shape
    even = jax.lax.broadcasted_iota(jnp.int32, (R, 2 * W), 1) % 2 == 0
    g = jnp.where(even, jnp.repeat(lo, 2, axis=1),
                  jnp.repeat(hi, 2, axis=1)).astype(jnp.float32)
    return g * scale


def quant_span_apply_ref(q, scale, dst, start, *, bits: int):
    """Oracle for ``replay.quant_span_apply``: dequantize one row-span
    payload and write it into rows [start, start+n) of ``dst``."""
    n = q.shape[0]
    dense = span_decode_ref(q, scale, bits)
    cols = 1
    for d in dst.shape[1:]:
        cols *= int(d)
    rows = dense[:n, :cols].reshape((n,) + dst.shape[1:]).astype(dst.dtype)
    return jax.lax.dynamic_update_slice(
        dst, rows, (start,) + (0,) * (dst.ndim - 1))
