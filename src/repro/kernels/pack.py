"""Pallas TPU kernel: fused top-k select + int8 quantize + wire pack.

The seed pipeline ran compression in two kernels (top-k select, then —
only for the quant family — int8 quantization) and left packing to the
host serializer. This kernel fuses all three for the differential fast
path: one (R, BLOCK) VMEM tile per grid step is read **once**, the k
iterative argmax passes run in registers exactly as in ``topk.py``, the
selected values are immediately quantized against a per-row absmax
scale, and the three wire buffers (q int8, block-local indices, f32
scales) come out contiguous — the frame serializer streams them to
storage byte-for-byte, so the differential leaves the device already in
its persisted format. Still a single pass over the gradient: the fusion
removes the second gradient read and the host-side re-encode, not just
kernel-launch overhead.

The max |value| of a block is by construction the first top-k pick, so
the quantization scale needs no second reduction over the tile — it
falls out of the selection loop for free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.topk import scatter_topk, select_topk

ROWS = 8          # rows (blocks) per grid step — one f32 sublane tile


def _pack_kernel(x_ref, q_ref, idx_ref, scale_ref, *, k: int, block: int):
    vals, idxs, _ = select_topk(x_ref[...].astype(jnp.float32), k)
    # the first selection is the absmax of the block, so the max over
    # the k picks is the quantization range — no extra reduction over
    # the (R, BLOCK) tile is needed
    scale = jnp.maximum(
        jnp.max(jnp.abs(vals), axis=1, keepdims=True) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(vals / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    idx_ref[...] = idxs
    scale_ref[...] = scale


def pack_select(xb: jax.Array, k: int, *, interpret: bool = False):
    """xb: (nb, block) -> (q int8 (nb,k), indices int32 (nb,k),
    scale f32 (nb,1)) — fused top-k + quantize + pack, one read of x."""
    nb, block = xb.shape
    rows = min(ROWS, nb)
    assert nb % rows == 0
    kernel = functools.partial(_pack_kernel, k=k, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                   pl.BlockSpec((rows, k), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, k), jnp.int8),
                   jax.ShapeDtypeStruct((nb, k), jnp.int32),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(xb)


def _span_pack_kernel(*refs, bits: int):
    """Row-blocked absmax quantizer for state-row spans: one scale per
    row, int8 values or two int4 nibbles per byte (two's complement,
    even/odd columns -> low/high nibble). For int4 the even and odd
    columns arrive as two inputs, split by the wrapper: Mosaic has no
    lane-strided slice."""
    *xs, q_ref, scale_ref = refs
    xs = [r[...].astype(jnp.float32) for r in xs]      # (R, C) or 2x (R, C/2)
    qmax = 127.0 if bits == 8 else 7.0
    absmax = functools.reduce(
        jnp.maximum, [jnp.max(jnp.abs(x), axis=1, keepdims=True) for x in xs])
    # reciprocal-multiply (not /qmax): matches the numpy host codec bit
    # for bit regardless of XLA's divide-by-constant rewrite
    scale = jnp.maximum(absmax * jnp.float32(1.0 / qmax), 1e-12)
    qs = [jnp.clip(jnp.round(x / scale), -qmax, qmax).astype(jnp.int32)
          for x in xs]
    if bits == 8:
        q_ref[...] = qs[0].astype(jnp.int8)
    else:
        lo, hi = (q & 0xF for q in qs)
        q_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)
    scale_ref[...] = scale


def span_pack(xb: jax.Array, *, bits: int, interpret: bool = False):
    """xb: (nb, cols) f32 rows (cols even when bits == 4) ->
    (q (nb, wire_cols), scale f32 (nb, 1)) where wire_cols is cols for
    int8 and cols // 2 for nibble-packed int4 — the fused row-span
    quantizer feeding :class:`~repro.compression.quant_span.QuantSpan`.
    One grid step holds whole rows, so a row must fit in VMEM."""
    assert bits in (8, 4)
    nb, cols = xb.shape
    assert bits == 8 or cols % 2 == 0
    rows = min(ROWS, nb)
    assert nb % rows == 0
    wire_cols = cols if bits == 8 else cols // 2
    wire_dt = jnp.int8 if bits == 8 else jnp.uint8
    xs = [xb] if bits == 8 else [xb[:, 0::2], xb[:, 1::2]]
    kernel = functools.partial(_span_pack_kernel, bits=bits)
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, x.shape[1]), lambda i: (i, 0))
                  for x in xs],
        out_specs=[pl.BlockSpec((rows, wire_cols), lambda i: (i, 0)),
                   pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, wire_cols), wire_dt),
                   jax.ShapeDtypeStruct((nb, 1), jnp.float32)],
        interpret=interpret,
    )(*xs)


def _unpack_kernel(q_ref, idx_ref, scale_ref, out_ref, *, block: int):
    vals = q_ref[...].astype(jnp.float32) * scale_ref[...]      # (R, k)
    out_ref[...] = scatter_topk(vals, idx_ref[...], block)


def pack_scatter(q: jax.Array, idxs: jax.Array, scale: jax.Array,
                 block: int, *, interpret: bool = False):
    """Inverse of pack_select: fused dequant + block-local scatter to a
    dense (nb, block) f32 tile — again a single kernel pass."""
    nb, k = q.shape
    rows = min(ROWS, nb)
    assert nb % rows == 0
    kernel = functools.partial(_unpack_kernel, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                  pl.BlockSpec((rows, k), lambda i: (i, 0)),
                  pl.BlockSpec((rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), jnp.float32),
        interpret=interpret,
    )(q, idxs, scale)
