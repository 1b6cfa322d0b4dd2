"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles.

Kernels execute in interpret mode on CPU — the exact TPU program body.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional property-testing dep; never hard-fail collection
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.compression import sparse as csp
from repro.kernels import ops as kops
from repro.kernels import ref as kref

SHAPES = [(1024,), (8, 1024), (33, 700), (5, 3, 257), (4096,), (1, 1)]
DTYPES = [jnp.float32, jnp.bfloat16]


def _rand(shape, dtype, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape), dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_topk_kernel_matches_ref(shape, dtype):
    x = _rand(shape, dtype)
    sg_k = kops.topk_compress(x, 0.05, use_pallas=True)
    sg_r = kops.topk_compress(x, 0.05, use_pallas=False)
    # compare decompressed tensors (index order within a block may differ)
    d_k = kops.topk_decompress(sg_k, use_pallas=True)
    d_r = kops.topk_decompress(sg_r, use_pallas=False)
    np.testing.assert_allclose(np.asarray(d_k, np.float32),
                               np.asarray(d_r, np.float32), atol=1e-6)
    # and against the compression-library reference implementation
    d_lib = csp.topk_decompress(csp.topk_compress(x, 0.05))
    np.testing.assert_allclose(np.asarray(d_k, np.float32),
                               np.asarray(d_lib, np.float32), atol=1e-6)


@pytest.mark.parametrize("rho", [0.001, 0.01, 0.1, 1.0])
def test_topk_kernel_rho_sweep(rho):
    x = _rand((16, 1024), jnp.float32, seed=3)
    d_k = kops.topk_decompress(kops.topk_compress(x, rho, use_pallas=True))
    d_r = csp.topk_decompress(csp.topk_compress(x, rho))
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), atol=1e-6)


def _ef_case(name):
    """(gradient, residual) of one leaf for the fused top-k parity test."""
    rng = np.random.default_rng(7)

    def normal(shape, scale=1.0):
        return (rng.normal(size=shape) * scale).astype(np.float32)

    def ties(shape, scale):      # few magnitudes, +x and -x alike
        return (rng.integers(-3, 4, size=shape) * scale).astype(np.float32)

    if name == "ties":
        return ties((16, 1024), 0.25), np.zeros((16, 1024), np.float32)
    if name == "zero_blocks":
        g, e = normal((16, 1024)), normal((16, 1024), 0.1)
        g[3:9], e[3:9] = 0.0, 0.0
        return g, e
    if name == "grouped_ties":   # 4 rows of 1280 hold 5 blocks; ragged tile
        return ties((261, 1280), 0.5), ties((261, 1280), 0.25)
    shape = {"norm": (1280,), "ragged": (50257, 8),
             "mlp3d": (2, 60, 5120)}[name]
    return normal(shape), normal(shape, 0.1)


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


@pytest.mark.parametrize("rho", [0.001, 0.01, 0.1])
@pytest.mark.parametrize("case", ["ties", "zero_blocks", "grouped_ties",
                                  "norm", "ragged", "mlp3d"])
def test_ef_topk_kernel_matches_oracle(case, rho):
    """The fused error-feedback top-k equals, bit for bit,
    ``ef_compress_tree`` + ``topk_decompress``: values, indices, the new
    residual and the dense picks, on every layout the kernel takes."""
    from repro.compression.error_feedback import ef_compress_tree
    g, e = (jnp.asarray(a) for a in _ef_case(case))
    sg, dense, res = kops.ef_topk_compress(g, e, rho)
    (cg,), (ef,) = ef_compress_tree([g], [e], rho)
    assert sg.shape == cg.shape and sg.block == cg.block
    for a, b in [(sg.values, cg.values), (sg.indices, cg.indices),
                 (res, ef), (dense, csp.topk_decompress(cg))]:
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(_bits(a), _bits(b))


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_quant_kernel_matches_ref(shape, dtype):
    x = _rand(shape, dtype, seed=1)
    q_k, s_k = kops.quant_compress(x, use_pallas=True)
    q_r, s_r = kops.quant_compress(x, use_pallas=False)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_pack_kernel_matches_ref(shape, dtype):
    """Fused compress-and-pack (top-k + int8 quantize + wire pack) vs
    the pure-jnp oracle, compared after decompression (index order
    within a block may differ between selection algorithms)."""
    x = _rand(shape, dtype, seed=6)
    d_k = kops.packed_decompress(kops.packed_compress(x, 0.05,
                                                      use_pallas=True),
                                 use_pallas=True)
    d_r = kops.packed_decompress(kops.packed_compress(x, 0.05,
                                                      use_pallas=False),
                                 use_pallas=False)
    np.testing.assert_allclose(np.asarray(d_k, np.float32),
                               np.asarray(d_r, np.float32), atol=1e-6)


def test_pack_kernel_quantization_matches_composition():
    """The fusion must equal the two-stage composition: top-k select
    then int8 quantization of the selected values (same scale rule)."""
    x = _rand((16, 1024), jnp.float32, seed=9)
    pd = kops.packed_compress(x, 0.01, use_pallas=True)
    sg = kops.topk_compress(x, 0.01, use_pallas=True)
    # same positions selected
    np.testing.assert_array_equal(np.sort(np.asarray(pd.indices), axis=1),
                                  np.sort(np.asarray(sg.indices), axis=1))
    # scale = absmax(selected)/127; absmax is the first top-k pick
    vals = np.asarray(sg.values, np.float32)
    expect_scale = np.maximum(np.abs(vals).max(axis=1, keepdims=True) / 127.0,
                              1e-12)
    np.testing.assert_allclose(np.asarray(pd.scale), expect_scale, rtol=1e-6)
    # dequantized values match within half a quantization step
    q = np.asarray(pd.q, np.float32) * np.asarray(pd.scale)
    np.testing.assert_allclose(np.sort(q, axis=1), np.sort(vals, axis=1),
                               atol=float(expect_scale.max()) * 0.5 + 1e-7)


def test_packed_wire_sizes():
    """PackedDiff is the wire format: int8 values + per-block scale —
    ~4x smaller than the f32 SparseGrad at the same rho."""
    x = _rand((64, 1024), jnp.float32, seed=10)
    pd = kops.packed_compress(x, 0.01)
    sg = kops.topk_compress(x, 0.01)
    assert np.asarray(pd.q).dtype == np.int8
    assert pd.nbytes < sg.nbytes


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_fused_adam_matches_ref(shape, dtype):
    p = _rand(shape, dtype, seed=2)
    g = _rand(shape, jnp.float32, seed=3)
    mu = _rand(shape, jnp.float32, seed=4) * 0.1
    nu = jnp.abs(_rand(shape, jnp.float32, seed=5)) * 0.1
    hyper = kops.adam_hyper(1e-3, 0.9, 0.999, 1e-8, 3)
    outs_k = kops.fused_adam_update(p, g, mu, nu, hyper, use_pallas=True)
    outs_r = kops.fused_adam_update(p, g, mu, nu, hyper, use_pallas=False)
    for a, b in zip(outs_k, outs_r):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-6, rtol=1e-5)


def test_fused_adam_matches_optimizer():
    """Kernel result == pytree Adam (the system's optimizer)."""
    from repro.optim.adam import AdamState, adam_init, adam_update
    p = {"w": _rand((600,), jnp.float32, seed=7)}
    g = {"w": _rand((600,), jnp.float32, seed=8)}
    st = adam_init(p)
    p2, st2 = adam_update(p, g, st, lr=1e-3)
    hyper = kops.adam_hyper(1e-3, 0.9, 0.999, 1e-8, 1)
    pk, muk, nuk = kops.fused_adam_update(p["w"], g["w"], st.mu["w"],
                                          st.nu["w"], hyper)
    np.testing.assert_allclose(np.asarray(pk), np.asarray(p2["w"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(muk), np.asarray(st2.mu["w"]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(nuk), np.asarray(st2.nu["w"]), atol=1e-6)


# ---------------------------- property tests -------------------------------

def _hyp(**kw):
    """@given-or-parametrize: hypothesis strategies when the optional
    dep is installed, a fixed case sweep otherwise. Each kwarg maps a
    parameter name to ((strategy_name, *args), fallback_values)."""
    def deco(fn):
        if HAVE_HYPOTHESIS:
            strategies = {k: getattr(st, spec[0])(*spec[1:])
                          for k, (spec, _) in kw.items()}
            return settings(max_examples=25, deadline=None)(
                given(**strategies)(fn))
        names = ",".join(kw)
        cases = list(zip(*(fb for _, fb in kw.values())))
        return pytest.mark.parametrize(names, cases)(fn)
    return deco


@_hyp(n=(("integers", 1, 5000), [1, 37, 1024, 5000]),
      rho=(("floats", 0.001, 0.5), [0.5, 0.01, 0.1, 0.001]),
      seed=(("integers", 0, 99), [0, 1, 2, 3]))
def test_topk_roundtrip_preserves_selected(n, rho, seed):
    """decompress(compress(x)) keeps selected entries exactly and zeroes
    the rest; selected magnitudes dominate unselected ones per block."""
    x = np.asarray(_rand((n,), jnp.float32, seed=seed))
    sg = csp.topk_compress(jnp.asarray(x), rho)
    d = np.asarray(csp.topk_decompress(sg))
    nz = d != 0
    np.testing.assert_allclose(d[nz], x[nz], atol=0)
    # block-level dominance
    block = sg.block
    pad = (-n) % block
    xp = np.pad(x, (0, pad)).reshape(-1, block)
    dp = np.pad(d, (0, pad)).reshape(-1, block)
    for xrow, drow in zip(xp, dp):
        kept = drow != 0
        if kept.any() and (~kept).any():
            assert np.abs(xrow[kept]).min() >= np.abs(xrow[~kept]).max() - 1e-6


@_hyp(n=(("integers", 1, 4000), [1, 65, 1023, 4000]),
      seed=(("integers", 0, 99), [0, 1, 2, 3]))
def test_quant_roundtrip_error_bound(n, seed):
    """|dequant(quant(x)) - x| <= scale/2 per block (absmax int8)."""
    x = np.asarray(_rand((n,), jnp.float32, seed=seed))
    qg = __import__("repro.compression.quant", fromlist=["quant_compress"])
    q = qg.quant_compress(jnp.asarray(x))
    d = np.asarray(qg.quant_decompress(q))
    scales = np.asarray(q.scale)
    pad = (-n) % q.block
    xp = np.pad(x, (0, pad)).reshape(-1, q.block)
    dp = np.pad(d, (0, pad)).reshape(-1, q.block)
    err = np.abs(xp - dp)
    assert (err <= scales[:, None] / 2 + 1e-7).all()
