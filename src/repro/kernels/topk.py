"""Pallas TPU kernels: blockwise top-k gradient selection and scatter.

TPU adaptation of GPU top-k compression: no global sort / no scatter.
A leaf's flat order is cut into 1024-element blocks (8 native 128-lane
vregs a row) and each block keeps its k = ceil(rho*1024) largest
magnitudes, in ``lax.top_k``'s order, by k iterative-argmax passes in
registers (``select_topk``). The MXU is untouched. Each pass is two
dependent lane reductions and a few selects over every element, so the
kernel is bound by the reductions' latency, not by the one read of the
gradient: 64 block rows a pass and unrolled passes hide it (``SUB``,
``UNROLL_UPTO``, timed on a TPU v5e).

``ef_topk`` is the train step's compress: one pass per leaf reads the
gradient and the error-feedback residual, and writes the wire, the
dense picks for Adam and the new residual. It reads the leaf in its
own rows wherever a group of rows holds whole blocks at 128-lane
boundaries, so the layout work adapts to the leaf's shape.

The (R, k) wire columns are written and read with a lane-iota select
(``kcol == i``) rather than a dynamic lane slice: Mosaic has no lowering
for ``dynamic_update_slice`` / ``dynamic_slice`` inside a kernel, and a
one-hot select over k lanes is exact (one non-zero term per row).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8          # rows (blocks) per grid step of the scatter
SUB = 64          # block rows of one selection pass: 8 f32 sublane tiles
TILE_BLOCKS = 256  # blocks a grid step of ef_topk aims at: 1 MiB of f32 a buffer
UNROLL_UPTO = 16  # the most argmax passes unrolled in full


def select_topk(xf: jax.Array, k: int, vals=None, idxs=None, first=0):
    """k iterative-argmax passes over an f32 (R, block) tile -> (values
    f32, block-local indices int32, picked bool (R, block)).

    Pick i lands in lane ``first + i`` of ``vals`` and ``idxs`` (zeros
    (R, k) when not given); their other lanes pass through. The order
    is ``lax.top_k``'s: descending magnitude, ties to the lowest index.
    Each pass makes two lane reductions: the largest magnitude, then the
    least key among the lanes that hold it, where a lane's key is twice
    its index plus its sign bit. The key names the pick and carries its
    sign, so the signed value is the magnitude with that sign, exactly."""
    R, block = xf.shape
    if vals is None:
        vals = jnp.zeros((R, k), jnp.float32)
        idxs = jnp.zeros((R, k), jnp.int32)
    mag = jnp.abs(xf)
    sign = jax.lax.shift_right_logical(
        jax.lax.bitcast_convert_type(xf, jnp.int32), 31)
    key = 2 * jax.lax.broadcasted_iota(jnp.int32, xf.shape, 1) + sign
    kcol = jax.lax.broadcasted_iota(jnp.int32, vals.shape, 1) - first

    def body(i, carry):
        mag, vals, idxs = carry
        m = jnp.max(mag, axis=1, keepdims=True)                  # (R, 1)
        hit = jnp.min(jnp.where(mag == m, key, 2 * block), axis=1,
                      keepdims=True)                             # (R, 1)
        mag = jnp.where(key == hit, -1.0, mag)
        slot = kcol == i
        vals = jnp.where(slot, jnp.where((hit & 1) == 1, -m, m), vals)
        idxs = jnp.where(slot, hit >> 1, idxs)
        return mag, vals, idxs

    mag, vals, idxs = jax.lax.fori_loop(0, k, body, (mag, vals, idxs),
                                        unroll=k <= UNROLL_UPTO)
    return vals, idxs, mag < 0


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


def group_of(width: int, block: int):
    """(g, c): the fewest rows g of ``width`` lanes whose flat order
    holds a whole number c of blocks."""
    span = math.lcm(width, block)
    return span // width, span // block


def _pieces(width: int, block: int, g: int, c: int):
    """Each block of a g-row group as its row pieces [(row, lo, hi)],
    in flat order."""
    out = []
    for b in range(c):
        lo, hi, parts = b * block, (b + 1) * block, []
        while lo < hi:
            q, off = divmod(lo, width)
            n = min(hi - lo, width - off)
            parts.append((q, off, off + n))
            lo += n
        out.append(parts)
    return out


def _ef_topk_kernel(*refs, k: int, block: int, g: int, c: int, sub: int,
                    rows: int, tile_rows: int, with_ef: bool):
    n_in = 1 + with_ef
    ins, (vals_ref, idx_ref, *outs) = refs[:n_in], refs[n_in:2 * n_in + 2]
    R, width = ins[0].shape
    first = pl.program_id(0) * tile_rows
    pieces = _pieces(width, block, g, c)

    def valid(x, row):          # zero the rows past the leaf's end
        return jnp.where(row < rows, x, 0.0) if rows % tile_rows else x

    def iota(n):
        return jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

    if g == 1:
        # a block is a lane range of one row: read and write in place
        def load(r0, q, lo, hi):
            x = ins[0][pl.ds(r0, sub), pl.ds(lo, hi - lo)].astype(jnp.float32)
            if with_ef:
                x = x + ins[1][pl.ds(r0, sub), pl.ds(lo, hi - lo)]
            return valid(x, first + r0 + iota(sub))

        def store(r0, q, lo, hi, parts):
            for ref, x in zip(outs, parts):
                ref[pl.ds(r0, sub), pl.ds(lo, hi - lo)] = x.astype(ref.dtype)
    else:
        # a block spans g rows: stage g + e in 128-lane column slabs,
        # where one row of each of sub groups is a strided sublane read
        xs, stage = refs[-2], refs[-1]
        for j in range(width // 128):
            lanes = pl.ds(128 * j, 128)
            x = ins[0][:, lanes].astype(jnp.float32)
            if with_ef:
                x = x + ins[1][:, lanes]
            xs[j] = valid(x, first + iota(R))

        def slabs(r0, q, lo, hi):
            rws = pl.ds(r0 + q, sub, stride=g)
            return [(j, rws) for j in range(lo // 128, hi // 128)]

        def load(r0, q, lo, hi):
            return jnp.concatenate([xs[j, rws] for j, rws in
                                    slabs(r0, q, lo, hi)], 1)

        def store(r0, q, lo, hi, parts):
            for n, (j, rws) in enumerate(slabs(r0, q, lo, hi)):
                for ref, x in zip((stage, xs), parts):
                    ref[j, rws] = x[:, 128 * n:128 * (n + 1)].astype(
                        ref.dtype)

    def subtile(s, carry):
        r0 = s * g * sub
        vals = jnp.zeros((sub, c * k), jnp.float32)
        idxs = jnp.zeros((sub, c * k), jnp.int32)
        for b, parts in enumerate(pieces):
            segs = [load(r0, *p) for p in parts]
            xb = segs[0] if len(segs) == 1 else jnp.concatenate(segs, 1)
            vals, idxs, picked = select_topk(xb, k, vals, idxs, b * k)
            out = (jnp.where(picked, xb, 0.0), jnp.where(picked, 0.0, xb))
            off = 0
            for p in parts:
                n = p[2] - p[1]
                store(r0, *p, [x[:, off:off + n] for x in out])
                off += n
        vals_ref[pl.ds(s * sub, sub), :] = vals.astype(vals_ref.dtype)
        idx_ref[pl.ds(s * sub, sub), :] = idxs
        return carry

    jax.lax.fori_loop(0, tile_rows // (g * sub), subtile, 0)
    if g > 1:
        for j in range(width // 128):
            for ref, src in zip(outs, (stage, xs)):
                ref[:, pl.ds(128 * j, 128)] = src[j].astype(ref.dtype)


def ef_topk(x: jax.Array, e, k: int, *, block: int,
            interpret: bool = False):
    """Blockwise top-k of ``x + e`` (``e`` the error feedback, or None)
    over a (rows, width) view of a leaf whose flat order is cut every
    ``block`` elements, in one pass.

    Returns (values (nb', k), indices (nb', k) int32, dense (rows,
    width), residual (rows, width) or None): the wire of the first nb'
    >= ceil(rows * width / block) blocks in flat order, the picks in
    place with zeros elsewhere, and ``x + e`` with the picks zeroed.
    Blocks past the leaf's end are zero; the values are f32 with error
    feedback and ``x.dtype`` without.

    Each grid step takes whole rows. The width must be a multiple of
    ``block`` (a block is a lane range of one row) or of 128 lanes, when
    g rows hold c whole blocks (``group_of``) and each block is built
    from 128-aligned row pieces, so the leaf needs no relayout. Rows
    past the leaf's end in the last tile are masked to zero. The view
    needs at least 8 groups of g rows."""
    rows, width = x.shape
    g, c = group_of(width, block)
    sub = min(SUB, rows // g // 8 * 8)    # a small leaf takes fewer rows
    step = g * sub
    tile_rows = step * max(1, TILE_BLOCKS // (c * sub))
    tile_rows = min(tile_rows, rows // step * step)
    assert tile_rows, (rows, width)
    ntiles = pl.cdiv(rows, tile_rows)
    with_ef = e is not None
    vdt = jnp.float32 if with_ef else x.dtype
    wire = jax.ShapeDtypeStruct((ntiles * tile_rows // g, c * k), vdt)
    dense = jax.ShapeDtypeStruct((rows, width), vdt)
    tile = pl.BlockSpec((tile_rows, width), lambda i: (i, 0))
    wire_spec = pl.BlockSpec((tile_rows // g, c * k), lambda i: (i, 0))
    slab = (width // 128, tile_rows, 128)
    # double-buffered tiles in and out, the staging slabs, the wire
    tile_bytes = 4 * tile_rows * width
    vmem = (2 * (2 + 2 * with_ef) * tile_bytes + 2 * tile_bytes * (g > 1)
            + 16 * tile_rows // g * 128 * pl.cdiv(c * k, 128))
    kernel = functools.partial(
        _ef_topk_kernel, k=k, block=block, g=g, c=c, sub=sub, rows=rows,
        tile_rows=tile_rows, with_ef=with_ef)
    outs = pl.pallas_call(
        kernel,
        grid=(ntiles,),
        in_specs=[tile] * (1 + with_ef),
        out_specs=[wire_spec, wire_spec] + [tile] * (1 + with_ef),
        out_shape=[wire, wire.update(dtype=jnp.int32), dense]
        + [dense.update(dtype=jnp.float32)] * with_ef,
        scratch_shapes=[pltpu.VMEM(slab, jnp.float32),
                        pltpu.VMEM(slab, vdt)] if g > 1 else [],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem + (8 << 20)),
        interpret=interpret,
    )(*([x, e] if with_ef else [x]))
    vals, idxs = (o.reshape(-1, k) for o in outs[:2])
    return vals, idxs, outs[2], outs[3] if with_ef else None


def _decompress_kernel(vals_ref, idx_ref, out_ref, *, block: int):
    acc = scatter_topk(vals_ref[...], idx_ref[...], block)
    out_ref[...] = acc.astype(out_ref.dtype)


def topk_scatter(vals: jax.Array, idxs: jax.Array, block: int, *,
                 interpret: bool = False):
    """Inverse of the top-k selection: block-local scatter to dense
    (nb, block)."""
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
