"""Pallas TPU kernel: blockwise top-k gradient selection.

TPU adaptation of GPU top-k compression: no global sort / no scatter.
Each grid step loads an (R, BLOCK) tile into VMEM (R rows of 1024-lane
blocks — BLOCK=1024 is 8 native 128-lane vregs) and runs k iterative
argmax passes entirely in registers: max-reduce along the lanes, first-hit
index via 2D iota + select, then mask and repeat. k = ceil(rho*1024) is
tiny (10 at the paper's rho=0.01), so the loop is short and every pass is
a dense VPU op — the MXU is untouched and the kernel is purely
memory-bound (one read of the gradient), which is the roofline optimum
for a compression pass.

The (R, k) wire columns are written and read with a lane-iota select
(``kcol == i``) rather than a dynamic lane slice: Mosaic has no lowering
for ``dynamic_update_slice`` / ``dynamic_slice`` inside a kernel, and a
one-hot select over k lanes is exact (one non-zero term per row).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 8          # rows (blocks) per grid step — one f32 sublane tile


def select_topk(xf: jax.Array, k: int, block: int):
    """k iterative-argmax passes over an f32 (R, block) tile -> (values
    f32 (R, k), block-local indices int32 (R, k)). Ties go to the lowest
    index (first hit); pick i lands in lane i of the outputs."""
    R = xf.shape[0]
    mag = jnp.abs(xf)
    iota = jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)

    def body(i, carry):
        mag, vals, idxs = carry
        m = jnp.max(mag, axis=1, keepdims=True)                  # (R, 1)
        idx = jnp.min(jnp.where(mag == m, iota, block), axis=1,
                      keepdims=True)                             # (R, 1)
        sel = iota == idx
        val = jnp.sum(jnp.where(sel, xf, 0.0), axis=1, keepdims=True)
        slot = kcol == i
        vals = jnp.where(slot, val, vals)
        idxs = jnp.where(slot, idx, idxs)
        mag = jnp.where(sel, -1.0, mag)
        return mag, vals, idxs

    vals0 = jnp.zeros((R, k), jnp.float32)
    idxs0 = jnp.zeros((R, k), jnp.int32)
    _, vals, idxs = jax.lax.fori_loop(0, k, body, (mag, vals0, idxs0))
    return vals, idxs


def scatter_topk(vals: jax.Array, idxs: jax.Array, block: int):
    """(R, k) values + block-local indices -> dense (R, block) f32.
    Indices within a block are distinct by construction (iterative
    argmax / top_k), so add-scatter == write-scatter; wire column i is
    read back with a one-hot lane reduction (exact: one non-zero)."""
    R, k = vals.shape
    vals = vals.astype(jnp.float32)
    iota = jax.lax.broadcasted_iota(jnp.int32, (R, block), 1)
    kcol = jax.lax.broadcasted_iota(jnp.int32, (R, k), 1)

    def body(i, acc):
        slot = kcol == i
        idx = jnp.sum(jnp.where(slot, idxs, 0), axis=1, keepdims=True)
        v = jnp.sum(jnp.where(slot, vals, 0.0), axis=1, keepdims=True)
        return acc + jnp.where(iota == idx, v, 0.0)

    return jax.lax.fori_loop(0, k, body, jnp.zeros((R, block), jnp.float32))


def _topk_kernel(x_ref, vals_ref, idx_ref, *, k: int, block: int):
    vals, idxs = select_topk(x_ref[...].astype(jnp.float32), k, block)
    vals_ref[...] = vals.astype(vals_ref.dtype)
    idx_ref[...] = idxs


def topk_select(xb: jax.Array, k: int, *, interpret: bool = False):
    """xb: (nb, block) -> (values (nb,k), indices (nb,k) int32)."""
    nb, block = xb.shape
    rows = min(ROWS, nb)
    assert nb % rows == 0
    grid = (nb // rows,)
    kernel = functools.partial(_topk_kernel, k=k, block=block)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((rows, block), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                   pl.BlockSpec((rows, k), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nb, k), xb.dtype),
                   jax.ShapeDtypeStruct((nb, k), jnp.int32)],
        interpret=interpret,
    )(xb)


def _decompress_kernel(vals_ref, idx_ref, out_ref, *, block: int):
    acc = scatter_topk(vals_ref[...], idx_ref[...], block)
    out_ref[...] = acc.astype(out_ref.dtype)


def topk_scatter(vals: jax.Array, idxs: jax.Array, block: int, *,
                 interpret: bool = False):
    """Inverse of topk_select: block-local scatter to dense (nb, block)."""
    nb, k = vals.shape
    rows = min(ROWS, nb)
    assert nb % rows == 0
    kernel = functools.partial(_decompress_kernel, block=block)
    return pl.pallas_call(
        kernel,
        grid=(nb // rows,),
        in_specs=[pl.BlockSpec((rows, k), lambda i: (i, 0)),
                  pl.BlockSpec((rows, k), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, block), vals.dtype),
        interpret=interpret,
    )(vals, idxs)
