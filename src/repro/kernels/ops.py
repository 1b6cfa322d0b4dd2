"""Jitted public wrappers around the Pallas kernels.

On the CPU backend the kernels execute in ``interpret=True`` mode — the
kernel body runs as traced jnp on the host, which validates the kernel
program. On a TPU backend the same call sites compile the Mosaic
kernels; any other backend raises. ``use_pallas=False`` routes to the
pure-jnp oracle instead (used to cross-check and as the default inside
larger jitted graphs).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.compression.packed import PackedDiff
from repro.compression.quant import QuantGrad
from repro.compression import sparse as _csp
from repro.compression.sparse import BLOCK, SparseGrad, _pad_len, k_for
from repro.kernels import fused_adam as _fa
from repro.kernels import pack as _pk
from repro.kernels import quant8 as _q8
from repro.kernels import ref as _ref
from repro.kernels import replay as _rp
from repro.kernels import topk as _tk

#: the most blocks the fused top-k builds from one group of a leaf's rows
MAX_GROUP = 8


def _interpret() -> bool:
    """Interpret the kernels on the CPU, compile them for the TPU, and
    refuse any other backend rather than quietly interpreting there."""
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on 'tpu' (compiled) or 'cpu' "
            f"(interpreted), not on {backend!r}")
    return backend == "cpu"


def _to_blocks(x: jax.Array, block: int):
    flat = x.reshape(-1)
    pad = _pad_len(flat.size, block)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    xb = flat.reshape(-1, block)
    # pallas grid wants row-count divisible by the tile height
    rpad = _pad_len(xb.shape[0], _tk.ROWS)
    if rpad:
        xb = jnp.pad(xb, ((0, rpad), (0, 0)))
    return xb, xb.shape[0] - rpad


def _fused_view(shape, block: int):
    """(rows, width, pad): the 2-D view ``topk.ef_topk`` reads a leaf
    through, adapted to the leaf's shape. The leaf's own rows when its
    last dim is a width the kernel takes (no relayout); else one
    relayout to the widest such width that divides its size. A leaf of
    one tile or less, or whose size is no multiple of 128, is cut flat
    into whole blocks (padded): its copy costs next to nothing."""
    n = math.prod(shape)

    def takes(w):
        g, c = _tk.group_of(w, block)
        aligned = w % 128 == 0 and block % 128 == 0
        return (c <= MAX_GROUP and (aligned or w == block) and n % w == 0
                and n // w >= 8 * g)

    if n > _tk.TILE_BLOCKS * block:
        for w in [shape[-1] if shape else 1] + [128 * m for m in range(
                MAX_GROUP * block // 128, 0, -1)]:
            if takes(w):
                return n // w, w, 0
    rows = -(-n // block)
    rows += -rows % 8
    return rows, block, rows * block - n


@functools.partial(jax.jit, static_argnames=("rho", "block"))
def ef_topk_compress(g: jax.Array, e, rho: float, *, block: int = BLOCK):
    """Blockwise top-k of ``g + e`` with error feedback ``e`` (or of
    ``g`` alone where ``e`` is None), in one fused pass over the leaf.

    Returns (SparseGrad, dense, residual): the wire, exactly
    ``compression.sparse.topk_compress(g + e, rho)``; the picks in
    place and zeros elsewhere, which is ``topk_decompress`` of the wire;
    and the new residual ``g + e`` with the picks zeroed, which is
    ``g + e - dense`` (None without ``e``)."""
    k = k_for(rho, block)
    shape, n = g.shape, math.prod(g.shape)
    rows, width, pad = _fused_view(shape, block)

    def view(x):
        return jnp.pad(x.reshape(-1), (0, pad)).reshape(rows, width) \
            if pad else x.reshape(rows, width)

    def unview(x):
        return x.reshape(-1)[:n].reshape(shape) if pad else x.reshape(shape)

    vals, idxs, dense, res = _tk.ef_topk(
        view(g), None if e is None else view(e), k, block=block,
        interpret=_interpret())
    nb = -(-n // block)
    return (SparseGrad(vals[:nb], idxs[:nb], shape, block), unview(dense),
            None if res is None else unview(res))


@functools.partial(jax.jit, static_argnames=("rho", "block", "use_pallas"))
def topk_compress(x: jax.Array, rho: float, *, block: int = BLOCK,
                  use_pallas: bool = True) -> SparseGrad:
    if use_pallas:
        return ef_topk_compress(x, None, rho, block=block)[0]
    return _csp.topk_compress(x, rho, block=block)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def topk_decompress(sg: SparseGrad, *, use_pallas: bool = True) -> jax.Array:
    nb = sg.values.shape[0]
    rpad = _pad_len(nb, _tk.ROWS)
    vals = jnp.pad(sg.values, ((0, rpad), (0, 0)))
    idx = jnp.pad(sg.indices, ((0, rpad), (0, 0)))
    if use_pallas:
        dense = _tk.topk_scatter(vals, idx, sg.block, interpret=_interpret())
    else:
        dense = _ref.topk_scatter_ref(vals, idx, sg.block)
    n = int(np.prod(sg.shape)) if sg.shape else 1
    return dense[:nb].reshape(-1)[:n].reshape(sg.shape)


@functools.partial(jax.jit, static_argnames=("rho", "block", "use_pallas"))
def packed_compress(x: jax.Array, rho: float, *, block: int = BLOCK,
                    use_pallas: bool = True) -> PackedDiff:
    """Fused compress-and-pack: one kernel pass emits the wire-format
    (q int8, indices, scales) buffers — the differential comes off the
    device already in the frame serializer's layout."""
    xb, nb = _to_blocks(x, block)
    k = k_for(rho, block)
    if use_pallas:
        q, idx, scale = _pk.pack_select(xb, k, interpret=_interpret())
    else:
        q, idx, scale = _ref.pack_select_ref(xb, k)
    return PackedDiff(q[:nb], idx[:nb], scale[:nb], x.shape, block)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def packed_decompress(pd: PackedDiff, *, use_pallas: bool = True
                      ) -> jax.Array:
    """Inverse of packed_compress: fused dequant + scatter to dense."""
    nb = pd.q.shape[0]
    rpad = _pad_len(nb, _pk.ROWS)
    q = jnp.pad(pd.q, ((0, rpad), (0, 0)))
    idx = jnp.pad(pd.indices, ((0, rpad), (0, 0)))
    scale = jnp.pad(pd.scale, ((0, rpad), (0, 0)))
    if use_pallas:
        dense = _pk.pack_scatter(q, idx, scale, pd.block,
                                 interpret=_interpret())
    else:
        dense = _ref.pack_scatter_ref(q, idx, scale, pd.block)
    n = int(np.prod(pd.shape)) if pd.shape else 1
    return dense[:nb].reshape(-1)[:n].reshape(pd.shape)


@functools.partial(jax.jit, static_argnames=("block", "use_pallas"))
def quant_compress(x: jax.Array, *, block: int = BLOCK,
                   use_pallas: bool = True):
    xb, nb = _to_blocks(x, block)
    if use_pallas:
        q, scale = _q8.quantize(xb, interpret=_interpret())
    else:
        q, scale = _ref.quantize_ref(xb)
    return q[:nb], scale[:nb]


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def quant_span_encode(x2d: jax.Array, *, bits: int,
                      use_pallas: bool = True):
    """Quantize a (rows, cols) f32 row block with per-row absmax scales:
    returns (q (rows, wire_cols), scale (rows, 1)). Pads rows to the
    kernel tile height and cols to even (int4) internally; the zero
    padding cannot change any row's absmax, so the wire bytes match the
    host codec exactly."""
    n, cols = x2d.shape
    cpad = (-cols) % 2 if bits == 4 else 0
    rpad = (-n) % _pk.ROWS
    xb = jnp.pad(x2d.astype(jnp.float32), ((0, rpad), (0, cpad)))
    if use_pallas:
        q, scale = _pk.span_pack(xb, bits=bits, interpret=_interpret())
    else:
        q, scale = _ref.span_pack_ref(xb, bits)
    return q[:n], scale[:n]


@functools.partial(jax.jit, static_argnames=("cols", "bits", "use_pallas"))
def quant_span_decode(q: jax.Array, scale: jax.Array, *, cols: int,
                      bits: int, use_pallas: bool = True) -> jax.Array:
    """Inverse of :func:`quant_span_encode`: wire bytes + per-row scales
    -> dense f32 (rows, cols)."""
    n = q.shape[0]
    rpad = (-n) % _rp.ROWS
    qp = jnp.pad(q, ((0, rpad), (0, 0)))
    sp = jnp.pad(scale, ((0, rpad), (0, 0)))
    if use_pallas:
        dense = _rp.quant_span_decode(qp, sp, bits=bits,
                                      interpret=_interpret())
    else:
        dense = _ref.span_decode_ref(qp, sp, bits)
    return dense[:n, :cols]


@functools.partial(jax.jit, static_argnames=("bits", "use_pallas"))
def fused_span_apply(dst: jax.Array, start, q: jax.Array,
                     scale: jax.Array, *, bits: int,
                     use_pallas: bool = True) -> jax.Array:
    """Fused dequantize + scatter of one quantized row-span payload into
    rows [start, start+n) of state leaf ``dst`` — the device-recovery
    overlay unit (``replay.quant_span_apply`` or its oracle)."""
    if use_pallas:
        return _rp.quant_span_apply(q, scale, dst, start, bits=bits,
                                    interpret=_interpret())
    return _ref.quant_span_apply_ref(q, scale, dst, start, bits=bits)


def adam_hyper(lr, b1, b2, eps, count) -> jax.Array:
    c1 = 1.0 - b1 ** count
    c2 = 1.0 - b2 ** count
    return jnp.asarray([[lr, b1, b2, eps, c1, c2, 1.0 - b1, 1.0 - b2]],
                       jnp.float32)


def adam_hyper_traced(lr, b1, b2, eps, count) -> jax.Array:
    """Traced variant of :func:`adam_hyper` for use inside jitted
    replay: the bias corrections are computed with the *same* f32 jnp
    ops as ``optim.adam.adam_update``, and the moment complements
    ``1-b1`` / ``1-b2`` are pre-rounded from python doubles exactly as
    the eager update's scalar promotion rounds them (recomputing
    ``1.0f - b1f`` on device is off by one ulp, which would break the
    device-replay == serial-replay bit-identity). ``count`` is the
    *post-increment* step count, i.e. ``state.count + 1``."""
    cf = jnp.asarray(count).astype(jnp.float32)
    c1 = 1.0 - b1 ** cf
    c2 = 1.0 - b2 ** cf
    row = jnp.stack([jnp.float32(lr), jnp.float32(b1), jnp.float32(b2),
                     jnp.float32(eps), c1.astype(jnp.float32),
                     c2.astype(jnp.float32), jnp.float32(1.0 - b1),
                     jnp.float32(1.0 - b2)])
    return row.reshape(1, 8)


def _unblock(x: jax.Array, shape, dt):
    n = int(np.prod(shape)) if shape else 1
    return x.reshape(-1)[:n].reshape(shape).astype(dt)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fused_sparse_apply(sg: SparseGrad, p: jax.Array, mu: jax.Array,
                       nu: jax.Array, hyper: jax.Array, *,
                       use_pallas: bool = True):
    """Fused decompress-and-apply for a top-k differential: scatter the
    wire (values, indices) straight into the Adam update — no dense
    gradient is ever materialized outside the kernel's accumulator."""
    shape, block = p.shape, sg.block
    pb, _ = _to_blocks(p, block)
    mub, _ = _to_blocks(mu, block)
    nub, _ = _to_blocks(nu, block)
    rpad = pb.shape[0] - sg.values.shape[0]
    vals = jnp.pad(sg.values, ((0, rpad), (0, 0)))
    idx = jnp.pad(sg.indices, ((0, rpad), (0, 0)))
    if use_pallas:
        p2, mu2, nu2 = _rp.topk_apply(vals, idx, pb, mub, nub, hyper,
                                      block=block, interpret=_interpret())
    else:
        p2, mu2, nu2 = _ref.topk_apply_ref(vals, idx, pb, mub, nub, hyper,
                                           block=block)
    return (_unblock(p2, shape, p.dtype), _unblock(mu2, shape, jnp.float32),
            _unblock(nu2, shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fused_packed_apply(pd: PackedDiff, p: jax.Array, mu: jax.Array,
                       nu: jax.Array, hyper: jax.Array, *,
                       use_pallas: bool = True):
    """Fused decompress-and-apply for a packed (int8 top-k) differential:
    dequantize + scatter + Adam in one pass over the wire buffers."""
    shape, block = p.shape, pd.block
    pb, _ = _to_blocks(p, block)
    mub, _ = _to_blocks(mu, block)
    nub, _ = _to_blocks(nu, block)
    rpad = pb.shape[0] - pd.q.shape[0]
    q = jnp.pad(pd.q, ((0, rpad), (0, 0)))
    idx = jnp.pad(pd.indices, ((0, rpad), (0, 0)))
    scale = jnp.pad(pd.scale, ((0, rpad), (0, 0)))
    if use_pallas:
        p2, mu2, nu2 = _rp.packed_apply(q, idx, scale, pb, mub, nub, hyper,
                                        block=block, interpret=_interpret())
    else:
        p2, mu2, nu2 = _ref.packed_apply_ref(q, idx, scale, pb, mub, nub,
                                             hyper, block=block)
    return (_unblock(p2, shape, p.dtype), _unblock(mu2, shape, jnp.float32),
            _unblock(nu2, shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fused_quant_apply(qg: QuantGrad, p: jax.Array, mu: jax.Array,
                      nu: jax.Array, hyper: jax.Array, *,
                      use_pallas: bool = True):
    """Fused decompress-and-apply for a quant8 differential: dequantize
    the int8 blocks against their scales inside the Adam pass."""
    shape, block = p.shape, qg.block
    pb, _ = _to_blocks(p, block)
    mub, _ = _to_blocks(mu, block)
    nub, _ = _to_blocks(nu, block)
    rpad = pb.shape[0] - qg.q.shape[0]
    q = jnp.pad(qg.q, ((0, rpad), (0, 0)))
    scale = jnp.pad(qg.scale.reshape(-1, 1), ((0, rpad), (0, 0)))
    if use_pallas:
        p2, mu2, nu2 = _rp.quant_apply(q, scale, pb, mub, nub, hyper,
                                       interpret=_interpret())
    else:
        p2, mu2, nu2 = _ref.quant_apply_ref(q, scale, pb, mub, nub, hyper)
    return (_unblock(p2, shape, p.dtype), _unblock(mu2, shape, jnp.float32),
            _unblock(nu2, shape, jnp.float32))


def fused_decode_apply(payload, p, mu, nu, hyper, *,
                       use_pallas: bool = True):
    """Apply one compressed differential to (p, mu, nu) without a host
    decompress or a dense intermediate: dispatches on the wire container
    type to the matching fused kernel; dense arrays fall back to
    :func:`fused_adam_update`."""
    if isinstance(payload, SparseGrad):
        return fused_sparse_apply(payload, p, mu, nu, hyper,
                                  use_pallas=use_pallas)
    if isinstance(payload, PackedDiff):
        return fused_packed_apply(payload, p, mu, nu, hyper,
                                  use_pallas=use_pallas)
    if isinstance(payload, QuantGrad):
        return fused_quant_apply(payload, p, mu, nu, hyper,
                                 use_pallas=use_pallas)
    return fused_adam_update(p, jnp.asarray(payload), mu, nu, hyper,
                             use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def fused_adam_update(p: jax.Array, g: jax.Array, mu: jax.Array,
                      nu: jax.Array, hyper: jax.Array, *,
                      use_pallas: bool = True):
    """Flat-tensor fused Adam. Shapes all equal; returns (p', mu', nu')."""
    shape = p.shape
    pb, nb = _to_blocks(p, _fa.COLS)
    gb, _ = _to_blocks(g, _fa.COLS)
    mub, _ = _to_blocks(mu, _fa.COLS)
    nub, _ = _to_blocks(nu, _fa.COLS)
    if use_pallas:
        p2, mu2, nu2 = _fa.adam_tile_update(pb, gb, mub, nub, hyper,
                                            interpret=_interpret())
    else:
        p2, mu2, nu2 = _ref.adam_tile_update_ref(pb, gb, mub, nub, hyper)
    n = int(np.prod(shape)) if shape else 1

    def unblock(x, dt):
        return x.reshape(-1)[:n].reshape(shape).astype(dt)

    return unblock(p2, p.dtype), unblock(mu2, jnp.float32), \
        unblock(nu2, jnp.float32)
