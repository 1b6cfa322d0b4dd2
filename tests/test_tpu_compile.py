"""Rehearsal compiles of the main-path Pallas kernels for a TPU v5e.

Each kernel is lowered with ``interpret=False`` for one chip of a
described (not attached) ``v5e:2x2`` topology, at gpt2-l's leaf shapes,
and compiled by the TPU compiler — which refuses what Mosaic cannot
lower and what overflows VMEM, none of which interpret mode sees.
Nothing runs: a pass says the chip's compiler takes the kernel, not
that it computes the right numbers (the interpret-mode parity tests and
``chip_smoke.py`` cover those).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.compression.sparse import BLOCK, k_for
from repro.kernels import fused_adam, pack, quant8, replay, topk

K = k_for(0.01)                     # 11 picks per 1024-lane block

#: gpt2-l leaves, as element counts: the 1280x5120 MLP, the 50257x1280
#: embedding and a 1280-wide norm (an odd tail: 2 blocks, padded to 8)
LEAVES = {"mlp": 1280 * 5120, "embed": 50257 * 1280, "norm": 1280}

#: row-span views (rows, cols) the overlay sees: an MLP matrix, the
#: embedding (rows padded to 8), the 1280x50257 head (cols padded even)
#: and, for decode, one stacked-layer row holding a whole MLP matrix
SPANS = [(1280, 5120), (50264, 1280), (1280, 50258)]
WIDE = (8, 1280 * 5120)

#: gpt2-l leaves as the fused top-k reads them: the stacked SwiGLU
#: matrix in its own rows, the embedding in groups of 4 rows of 1280
#: (5 blocks), the head after its one relayout, and a norm cut flat
EF_LEAVES = {"mlp": (8, 1280, 5120), "embed": (50257, 1280),
             "head": (1280, 50257), "norm": (1280,)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with JAX's persistent compile cache off: an
    entry written for a chip that is not attached cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _nb(n: int) -> int:
    """Block rows the ops wrappers hand the kernels for an n-element
    leaf: ceil(n / BLOCK), padded to the 8-row tile."""
    nb = -(-n // BLOCK)
    return nb + (-nb % 8)


def _block_cases(name, nb, S):
    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    dense, wire = S((nb, BLOCK), f32), S((nb, K), f32)
    idx, q, scale = S((nb, K), i32), S((nb, K), i8), S((nb, 1), f32)
    state, hyper = [dense] * 3, S((1, 8), f32)
    return {
        "topk_scatter": (lambda v, i: topk.topk_scatter(v, i, BLOCK),
                         [wire, idx]),
        "pack_select": (lambda x: pack.pack_select(x, K), [dense]),
        "pack_scatter": (lambda a, i, s: pack.pack_scatter(a, i, s, BLOCK),
                         [q, idx, scale]),
        "topk_apply": (lambda v, i, p, m, n, h: replay.topk_apply(
            v, i, p, m, n, h, block=BLOCK), [wire, idx, *state, hyper]),
        "packed_apply": (lambda a, i, s, p, m, n, h: replay.packed_apply(
            a, i, s, p, m, n, h, block=BLOCK),
            [q, idx, scale, *state, hyper]),
        "quant_apply": (replay.quant_apply,
                        [S((nb, BLOCK), i8), scale, *state, hyper]),
        "quantize": (quant8.quantize, [dense]),
        "dequantize": (quant8.dequantize, [S((nb, BLOCK), i8), scale]),
        "adam_tile_update": (fused_adam.adam_tile_update,
                             [dense] * 4 + [hyper]),
    }[name]


def _span_cases(name, bits, rows, cols, S):
    wc = cols if bits == 8 else cols // 2
    wdt = jnp.int8 if bits == 8 else jnp.uint8
    n = rows - 5                              # a span that is not 8-aligned
    return {
        "span_pack": (lambda x: pack.span_pack(x, bits=bits),
                      [S((rows, cols), jnp.float32)]),
        "quant_span_decode": (
            lambda q, s: replay.quant_span_decode(q, s, bits=bits),
            [S((rows, wc), wdt), S((rows, 1), jnp.float32)]),
        "quant_span_apply": (
            lambda q, s, d: replay.quant_span_apply(q, s, d, 3, bits=bits),
            [S((n, wc), wdt), S((n, 1), jnp.float32),
             S((rows, cols), jnp.float32)]),
    }[name]


def _assert_compiles(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", [
    "topk_scatter", "pack_select", "pack_scatter",
    "topk_apply", "packed_apply", "quant_apply", "quantize", "dequantize",
    "adam_tile_update"])
def test_block_kernel_compiles_for_v5e(name, one_chip):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    for n in LEAVES.values():
        _assert_compiles(*_block_cases(name, _nb(n), S))


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("name", ["span_pack", "quant_span_decode",
                                  "quant_span_apply"])
def test_span_kernel_compiles_for_v5e(name, bits, one_chip):
    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    # span_pack holds whole rows in VMEM, so the stacked-layer row is
    # only an overlay (decode/apply) shape
    shapes = SPANS if name == "span_pack" else SPANS + [WIDE]
    for rows, cols in shapes:
        _assert_compiles(*_span_cases(name, bits, rows, cols, S))


@pytest.mark.parametrize("leaf,with_ef", [
    ("mlp", True), ("embed", True), ("head", True), ("norm", True),
    ("embed", False)])
def test_ef_topk_compiles_for_v5e(leaf, with_ef, one_chip):
    from repro.kernels.ops import _fused_view
    rows, width, _ = _fused_view(EF_LEAVES[leaf], BLOCK)
    x = jax.ShapeDtypeStruct((rows, width), jnp.float32, sharding=one_chip)
    _assert_compiles(lambda g, e=None: topk.ef_topk(g, e, K, block=BLOCK),
                     [x, x] if with_ef else [x])
