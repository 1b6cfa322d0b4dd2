"""Pallas TPU kernels: fused decompress-and-apply differential replay.

Recovery used to decode every compressed differential on host
(``maybe_decompress``) and ship the dense leaves over PCIe before the
replay scan touched them — recovery time was set by host CPU and
interconnect, not by the chain's information content. These kernels
take a differential's *wire form* — top-k (values, block-local
indices), packed (int8 q, indices, f32 scales) or quant8 (int8 blocks,
f32 scales) — resident in device memory and replay one optimizer step
in a single pass per tile: decode in registers (dequantize / scatter
into a VMEM accumulator), then the exact ``fused_adam`` moment update,
writing p'/mu'/nu' back out. No dense gradient ever exists in HBM and
the host never touches the payload bytes.

Per replayed step the HBM traffic is 3 reads + 3 writes of the model
state plus the (tiny) compressed payload read — the memory-bound
optimum for a stateful-optimizer replay, which is what lets a chain
replay approach the device memory-bandwidth roofline.

The decode math mirrors the pure-jnp decompressors bit-for-bit (f32
scatter of distinct per-block indices, ``q.astype(f32) * scale``
dequant) and the update mirrors ``optim.adam.adam_update``'s op order,
so a device-replayed chain is bit-identical to host serial replay.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topk import scatter_topk

ROWS = 8          # rows (blocks) per grid step — one f32 sublane tile
SPAN_COLS = 8192  # wire lanes per quantized row-span tile


def _adam_epilogue(hyper_ref, g, p_ref, mu_ref, nu_ref,
                   p_out, mu_out, nu_out):
    """Shared fused-Adam tail: identical op order to ``fused_adam`` /
    ``optim.adam.adam_update`` (bit-identity with host replay)."""
    h = hyper_ref[...]                                  # (1, 8) f32
    lr, b1, b2, eps, c1, c2, om1, om2 = (h[0, i] for i in range(8))
    # om1/om2 are 1-b1 / 1-b2 pre-rounded from python doubles the way
    # the eager update's scalar promotion rounds them — recomputing
    # 1.0f - b1f here lands one ulp off and breaks bit-identity.
    p = p_ref[...].astype(jnp.float32)
    mu = b1 * mu_ref[...] + om1 * g
    nu = b2 * nu_ref[...] + om2 * g * g
    step = lr * (mu / c1) / (jnp.sqrt(nu / c2) + eps)
    p_out[...] = (p - step).astype(p_ref.dtype)
    mu_out[...] = mu
    nu_out[...] = nu


def _topk_apply_kernel(hyper_ref, vals_ref, idx_ref, p_ref, mu_ref, nu_ref,
                       p_out, mu_out, nu_out, *, block: int):
    g = scatter_topk(vals_ref[...], idx_ref[...], block)
    _adam_epilogue(hyper_ref, g, p_ref, mu_ref, nu_ref,
                   p_out, mu_out, nu_out)


def _packed_apply_kernel(hyper_ref, q_ref, idx_ref, scale_ref,
                         p_ref, mu_ref, nu_ref,
                         p_out, mu_out, nu_out, *, block: int):
    vals = q_ref[...].astype(jnp.float32) * scale_ref[...]      # (R, k)
    g = scatter_topk(vals, idx_ref[...], block)
    _adam_epilogue(hyper_ref, g, p_ref, mu_ref, nu_ref,
                   p_out, mu_out, nu_out)


def _quant_apply_kernel(hyper_ref, q_ref, scale_ref,
                        p_ref, mu_ref, nu_ref,
                        p_out, mu_out, nu_out):
    g = q_ref[...].astype(jnp.float32) * scale_ref[...]         # (R, block)
    _adam_epilogue(hyper_ref, g, p_ref, mu_ref, nu_ref,
                   p_out, mu_out, nu_out)


def _call(kernel, wire_specs, wires, p, mu, nu, hyper, *, block: int,
          interpret: bool):
    nb = p.shape[0]
    rows = min(ROWS, nb)
    assert nb % rows == 0
    state = pl.BlockSpec((rows, block), lambda i: (i, 0))
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((1, 8), lambda i: (0, 0)),
                  *wire_specs, state, state, state],
        out_specs=[state, state, state],
        out_shape=[jax.ShapeDtypeStruct(p.shape, p.dtype),
                   jax.ShapeDtypeStruct(p.shape, jnp.float32),
                   jax.ShapeDtypeStruct(p.shape, jnp.float32)],
        interpret=interpret,
    )(hyper, *wires, p, mu, nu)


def topk_apply(vals, idxs, p, mu, nu, hyper, *, block: int,
               interpret: bool = False):
    """Fused scatter-decode + Adam apply of a top-k differential.
    vals/idxs: (nb, k); p/mu/nu: (nb, block); hyper: (1, 8) f32 =
    [lr, b1, b2, eps, c1, c2, 0, 0]. Returns (p', mu', nu')."""
    nb, k = vals.shape
    if k == 0:
        return _zero_apply(p, mu, nu, hyper, interpret=interpret)
    rows = min(ROWS, nb)
    wire = pl.BlockSpec((rows, k), lambda i: (i, 0))
    kernel = functools.partial(_topk_apply_kernel, block=block)
    return _call(kernel, [wire, wire], (vals, idxs), p, mu, nu, hyper,
                 block=block, interpret=interpret)


def packed_apply(q, idxs, scale, p, mu, nu, hyper, *, block: int,
                 interpret: bool = False):
    """Fused dequant + scatter-decode + Adam apply of a packed (int8
    top-k) differential. q/idxs: (nb, k); scale: (nb, 1)."""
    nb, k = q.shape
    if k == 0:
        return _zero_apply(p, mu, nu, hyper, interpret=interpret)
    rows = min(ROWS, nb)
    wire = pl.BlockSpec((rows, k), lambda i: (i, 0))
    sspec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    kernel = functools.partial(_packed_apply_kernel, block=block)
    return _call(kernel, [wire, wire, sspec], (q, idxs, scale),
                 p, mu, nu, hyper, block=block, interpret=interpret)


def _zero_apply(p, mu, nu, hyper, *, interpret: bool):
    """k == 0 wire payload (an all-zero block's top-0): pallas rejects
    zero-width block specs, so run the identical Adam epilogue through
    the quant kernel with a zero payload — g == 0 exactly, same bits as
    the oracle's empty scatter."""
    return quant_apply(jnp.zeros(p.shape, jnp.int8),
                       jnp.zeros((p.shape[0], 1), jnp.float32),
                       p, mu, nu, hyper, interpret=interpret)


def quant_apply(q, scale, p, mu, nu, hyper, *, interpret: bool = False):
    """Fused dequant + Adam apply of a quant8 differential.
    q: (nb, block) int8; scale: (nb, 1) f32."""
    nb, block = q.shape
    rows = min(ROWS, nb)
    wire = pl.BlockSpec((rows, block), lambda i: (i, 0))
    sspec = pl.BlockSpec((rows, 1), lambda i: (i, 0))
    return _call(_quant_apply_kernel, [wire, sspec], (q, scale),
                 p, mu, nu, hyper, block=block, interpret=interpret)


# -------------------- quantized row-span recovery --------------------

def _quant_span_kernel(q_ref, scale_ref, *out_refs, bits: int):
    """Dequantize a quantized row-span wire tile, scaled by the per-row
    absmax scale: int8 values -> one f32 tile; nibble-packed int4 (low
    nibble = even column, two's complement) -> the even-column and the
    odd-column f32 tiles, which the wrapper interleaves (an in-kernel
    lane interleave does not fit VMEM at real row widths)."""
    scale = scale_ref[...]
    if bits == 8:
        out_refs[0][...] = q_ref[...].astype(jnp.float32) * scale
        return
    u = q_ref[...].astype(jnp.int32)
    for out, nib in zip(out_refs, (u & 0xF, (u >> 4) & 0xF)):
        out[...] = jnp.where(nib > 7, nib - 16, nib).astype(
            jnp.float32) * scale


def _span_tiling(wc: int):
    """(padded wire columns, lane tile): one full-width tile for narrow
    rows, else SPAN_COLS-lane tiles — a stacked-layer leaf's row is a
    whole layer's matrix (millions of columns), far beyond VMEM."""
    if wc <= SPAN_COLS:
        return wc, wc
    return wc + (-wc % SPAN_COLS), SPAN_COLS


def quant_span_decode(q, scale, *, bits: int, interpret: bool = False):
    """q: (nb, wire_cols) + per-row scales -> dense f32 (nb, cols) where
    cols is wire_cols (int8) or 2*wire_cols (int4). nb % ROWS == 0."""
    assert bits in (8, 4)
    nb, wc = q.shape
    rows = min(ROWS, nb)
    assert nb % rows == 0
    wpad, tw = _span_tiling(wc)
    if wpad != wc:
        q = jnp.pad(q, ((0, 0), (0, wpad - wc)))
    tile = pl.BlockSpec((rows, tw), lambda i, j: (i, j))
    n_out = 1 if bits == 8 else 2
    kernel = functools.partial(_quant_span_kernel, bits=bits)
    outs = pl.pallas_call(
        kernel,
        grid=(nb // rows, wpad // tw),
        in_specs=[tile, pl.BlockSpec((rows, 1), lambda i, j: (i, 0))],
        out_specs=[tile] * n_out,
        out_shape=[jax.ShapeDtypeStruct((nb, wpad), jnp.float32)] * n_out,
        interpret=interpret,
    )(q, scale)
    if bits == 8:
        return outs[0][:, :wc]
    return jnp.stack(outs, axis=2).reshape(nb, 2 * wpad)[:, :2 * wc]


def quant_span_apply(q, scale, dst, start, *, bits: int,
                     interpret: bool = False):
    """Fused dequantize(+int4 unpack) of one quantized row-span payload,
    scattered straight into rows [start, start+n) of the destination
    state leaf ``dst`` (shape (N, *tail)) — the device-recovery overlay
    unit. The dequant math is bit-identical to the host codec
    (``repro.compression.quant_span``), so device overlay == host
    overlay byte for byte."""
    n = q.shape[0]
    rpad = -n % ROWS
    qp = jnp.pad(q, ((0, rpad), (0, 0)))
    sp = jnp.pad(scale, ((0, rpad), (0, 0)))
    dense = quant_span_decode(qp, sp, bits=bits, interpret=interpret)
    cols = 1
    for d in dst.shape[1:]:
        cols *= int(d)
    rows = dense[:n, :cols].reshape((n,) + dst.shape[1:]).astype(dst.dtype)
    return jax.lax.dynamic_update_slice(
        dst, rows, (start,) + (0,) * (dst.ndim - 1))
