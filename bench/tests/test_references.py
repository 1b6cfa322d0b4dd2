"""Architecture modules (``references/<name>.py``), found by the name a
configuration gives under ``reference`` (``dense`` without it), and the
program's ``ArchConfig`` built from every key of a configuration file."""
import dataclasses
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchlib import checks, flops, harness, inputs, reference as ref, spec

#: the keys the harness copied into the program's ArchConfig before the
#: architecture became a module; the files of that time must build the
#: same ArchConfig
OLD_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff", "vocab",
            "norm_eps", "rope_theta", "qkv_bias", "tie_embeddings",
            "param_dtype", "compute_dtype", "remat")


def _cfg(name):
    return json.loads((spec.BENCH / "configs" / f"{name}.json").read_text())


def _layers(L, d, f, V, std_d, std_f, std_v):
    return {
        "['embed']": ((V, d), "normal", 0.02),
        "['final_norm']": ((d,), "ones", 0.0),
        "['layers']['attn']['wk']": ((L, d, d), "normal", std_d),
        "['layers']['attn']['wo']": ((L, d, d), "normal", std_d),
        "['layers']['attn']['wq']": ((L, d, d), "normal", std_d),
        "['layers']['attn']['wv']": ((L, d, d), "normal", std_d),
        "['layers']['attn_norm']": ((L, d), "ones", 0.0),
        "['layers']['ffn']['wd']": ((L, f, d), "normal", std_f),
        "['layers']['ffn']['wg']": ((L, d, f), "normal", std_d),
        "['layers']['ffn']['wu']": ((L, d, f), "normal", std_d),
        "['layers']['ffn_norm']": ((L, d), "ones", 0.0),
        "['lm_head']": ((d, V), "normal", std_v),
    }


#: read from the harness before the dense architecture became a module
GOLDEN = {
    "gpt2-l-8L": dict(
        n_params=338_394_880, matmul_params=274_044_160,
        train_flops_per_token=1_707_240_960.0,
        layout=_layers(8, 1280, 5120, 50257, 0.02795084971874737,
                       0.013975424859373685, 0.02795084971874737)),
    "stablelm-1.6b-3L": dict(
        n_params=256_915_456, matmul_params=205_520_896,
        train_flops_per_token=1_384_157_184.0,
        layout=_layers(3, 2048, 5632, 25088, 0.022097086912079608,
                       0.013325044772225651, 0.022097086912079608)),
}


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_existing_configurations_count_as_before(name):
    cfg, want = _cfg(name), GOLDEN[name]
    assert "reference" not in cfg and spec.reference_of(cfg).__name__ == \
        "bench_reference_dense"
    arch = spec.reference_of(cfg)
    flat = jax.tree_util.tree_flatten_with_path(arch.param_layout(cfg),
                                                is_leaf=inputs._is_leaf)[0]
    assert {jax.tree_util.keystr(p): leaf for p, leaf in flat} == \
        want["layout"]
    assert inputs.n_params(cfg) == want["n_params"]
    assert arch.matmul_params(cfg) == want["matmul_params"]
    assert flops.train_flops_per_token(cfg) == want["train_flops_per_token"]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_existing_configurations_build_the_same_program(name):
    from repro.configs import get_config
    cfg = _cfg(name)
    old = get_config(cfg["program_arch"]).replace(
        **{k: cfg[k] for k in OLD_KEYS})
    new = harness.program_arch(cfg)
    assert dataclasses.asdict(new) == dataclasses.asdict(old)
    assert new == old and new.name != cfg["name"]


# ----------------------------------------------------------------------
# the dense reference, bit for bit as it was before the move
# ----------------------------------------------------------------------

#: loss (float.hex) and sha256 of each gradient leaf's bytes (first 16
#: hex digits), recorded from the reference's loss_fn before it moved to
#: references/dense.py; CPU capped at AVX (conftest)
REFERENCE_GOLDEN = {
    "short_f32": ("0x1.be7a5c0000000p+2", {
        "['embed']": "a4d11acd02fb3e3b",
        "['final_norm']": "ddbbe6b3824c36d9",
        "['layers']['attn']['wk']": "89f96c8afe03ada1",
        "['layers']['attn']['wo']": "86544930eaf5d0f4",
        "['layers']['attn']['wq']": "905d06e74badf32b",
        "['layers']['attn']['wv']": "219326f2d898f820",
        "['layers']['attn_norm']": "a2a90e02c7889910",
        "['layers']['ffn']['wd']": "97e341322f13f2d1",
        "['layers']['ffn']['wg']": "8634b8516ff3ce18",
        "['layers']['ffn']['wu']": "8ae420392214ef09",
        "['layers']['ffn_norm']": "d8e49369f7595150",
        "['lm_head']": "288afac90b96c2cd"}),
    "short_fp8": ("0x1.bef7b00000000p+2", {
        "['embed']": "65d9deb4d07ce120",
        "['final_norm']": "940cc45db3af542d",
        "['layers']['attn']['wk']": "faa36280eb1ce343",
        "['layers']['attn']['wo']": "6a189dcf14b2dced",
        "['layers']['attn']['wq']": "3036dedfb66ab122",
        "['layers']['attn']['wv']": "4e1fbadaf320b9d4",
        "['layers']['attn_norm']": "78adf8bc8be40547",
        "['layers']['ffn']['wd']": "c41b31506c60338b",
        "['layers']['ffn']['wg']": "e68d752422615932",
        "['layers']['ffn']['wu']": "49786dd121d11e1b",
        "['layers']['ffn_norm']": "08b79ba4bdf82c09",
        "['lm_head']": "bc845d02615ace1e"}),
    "blocked_f32": ("0x1.b0f9a40000000p+2", {
        "['embed']": "e84e8293166fe26c",
        "['final_norm']": "854705297c0a2b3d",
        "['layers']['attn']['wk']": "dcf58d636f3d2e9f",
        "['layers']['attn']['wo']": "3760970f56c653a0",
        "['layers']['attn']['wq']": "ba880aba273ce381",
        "['layers']['attn']['wv']": "87e9781b37491bf9",
        "['layers']['attn_norm']": "1b3a97f58cce5b00",
        "['layers']['ffn']['wd']": "92858a1747a956a3",
        "['layers']['ffn']['wg']": "49d7d844ab17bcae",
        "['layers']['ffn']['wu']": "952db26451bb55e5",
        "['layers']['ffn_norm']": "0e2963f4ead2d62c",
        "['lm_head']": "9d1e3faa0b975fb2"}),
}
_CASES = {"short_f32": ("f32", dict(batch=2, seq=64)),
          "short_fp8": ("fp8", dict(batch=2, seq=64)),
          # two blocks of 1024 query rows
          "blocked_f32": ("f32", dict(batch=1, seq=2048, n_layers=1))}
_TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
             vocab=512)


@pytest.mark.parametrize("case", sorted(_CASES))
def test_dense_reference_is_bit_for_bit_as_before(case):
    precision, over = _CASES[case]
    cfg = dict(_cfg("gpt2-l-8L"), **_TINY)
    cfg.update(over)
    seed = 2 ** 31 + 3
    loss_fn = spec.reference_of(cfg).loss_fn
    mm = ref.matmul(precision)
    params = jax.jit(lambda k: inputs.make_params(cfg, k))(
        inputs.seed_key(seed))
    b = inputs.batch(cfg, seed, 1)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p, t, y: loss_fn(p, t, y, cfg, mm)))(
        params, jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]))
    got = {jax.tree_util.keystr(p):
           hashlib.sha256(np.asarray(g).tobytes()).hexdigest()[:16]
           for p, g in jax.tree_util.tree_flatten_with_path(grads)[0]}
    want_loss, want_grads = REFERENCE_GOLDEN[case]
    assert float(loss).hex() == want_loss
    assert got == want_grads


# ----------------------------------------------------------------------
# the dense reference against the program, through the harness's path
# ----------------------------------------------------------------------

#: float32 products on both sides; the program sums attention online
#: over key blocks and the cross-entropy over sequence chunks, the
#: reference in one softmax, so the two differ by float32 rounding
#: order alone: at most 7e-8 of the loss and 1.3e-6 of a leaf's gradient
#: norm here. A wrong block reads 1.2e-3 and 1.6e-2 or more (a bias
#: left out, kv heads grouped by tiling, a tied head off by 1%, the
#: rotary base halved), so the tolerances sit 100x above the one and
#: 100x below the other.
LOSS_TOL = 1e-5
GRAD_TOL = 1e-4
VARIANTS = {"plain": {}, "qkv_bias": {"qkv_bias": True},
            "tie_embeddings": {"tie_embeddings": True},
            "gqa": {"n_kv_heads": 2},
            "head_dim": {"head_dim": 32, "n_kv_heads": 2}}


def _perturbed(params, key):
    """Seeded weights with every leaf moved by noise, so that norm gains
    are not ones and zero-initialised biases are not zeros."""
    leaves, td = jax.tree.flatten(params)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(td, [
        x + 0.1 * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


@pytest.mark.parametrize("variant", sorted(VARIANTS))
def test_dense_reference_is_the_programs_block(variant):
    cfg = dict(_cfg("gpt2-l-8L"), **_TINY, batch=2, seq=64,
               compute_dtype="float32")
    cfg.update(VARIANTS[variant])
    seed = 2 ** 31 + 11
    model = harness.program_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = jax.jit(lambda k: inputs.make_params(cfg, k))(
        inputs.seed_key(seed))
    assert jax.tree.structure(want) == jax.tree.structure(params)
    assert inputs.shapes(want) == inputs.shapes(params)
    params = _perturbed(params, jax.random.PRNGKey(seed % 2 ** 31))
    b = jax.tree.map(jnp.asarray, inputs.batch(cfg, seed, 1))
    p_loss, p_grads = jax.jit(jax.value_and_grad(
        lambda p: model.loss_fn(p, b)[0]))(params)
    loss_fn = spec.reference_of(cfg).loss_fn
    mm = ref.matmul("f32")
    r_loss, r_grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, b["tokens"], b["targets"], cfg, mm)))(params)
    assert abs(float(p_loss) - float(r_loss)) <= LOSS_TOL * abs(float(r_loss))
    for pg, rg in zip(jax.tree.leaves(p_grads), jax.tree.leaves(r_grads)):
        gap = float(jnp.linalg.norm(pg - rg))
        assert gap <= GRAD_TOL * float(jnp.linalg.norm(rg)), variant


# ----------------------------------------------------------------------
# configuration keys and module look-up
# ----------------------------------------------------------------------

@pytest.mark.parametrize("key,value", [("n_experts", 64),
                                       ("partial_rotary_factor", 0.25),
                                       ("lr", 1e-3)])
def test_unknown_key_is_refused_by_name(key, value):
    cfg = dict(_cfg("gpt2-l-8L"), **{key: value})
    with pytest.raises(spec.SpecError, match=key):
        harness.program_arch(cfg)


def test_partial_rotary_is_refused_with_dense():
    cfg = dict(_cfg("stablelm-1.6b-3L"), rope_fraction=0.25)
    with pytest.raises(spec.SpecError, match="rope_fraction"):
        harness.program_arch(cfg)


def test_program_fields_and_groups_are_passed():
    cfg = dict(_cfg("gpt2-l-8L"), head_dim=32, moe={"top_k": 2},
               global_attn_layers=[0, 3])
    arch = harness.program_arch(cfg)
    assert (arch.head_dim, arch.moe.top_k, arch.global_attn_layers) == \
        (32, 2, (0, 3))
    with pytest.raises(spec.SpecError, match="n_expertz"):
        harness.program_arch(dict(cfg, moe={"n_expertz": 4}))


TOY = '''"""A bigram model: the embedding row of a token, times one matrix."""
import math

import jax
import jax.numpy as jnp

PROGRAM_IMPLIED = {"bigram_only": True}


def param_layout(cfg):
    d, V = cfg["d_model"], cfg["vocab"]
    return {"embed": ((V, d), "normal", 0.02),
            "out": ((d, V), "normal", 1.0 / math.sqrt(d))}


def loss_fn(params, tokens, targets, cfg, mm):
    h = params["embed"].astype(jnp.float32)[tokens]
    logits = mm("bsd,dv->bsv", h, params["out"])
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, -1) - tgt)


def matmul_params(cfg):
    return cfg["d_model"] * cfg["vocab"]


def attention_flops_per_token(cfg):
    return 0.0
'''


def _root(tmp_path, config, modules):
    """A checkout holding one cell of ``config`` and ``modules``."""
    bench = tmp_path / "bench"
    for d in ("configs", "traffic", "workloads", "references"):
        (bench / d).mkdir(parents=True)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "configs": [{"name": config["name"],
                     "file": f"bench/configs/{config['name']}.json"}],
        "workloads": [{"name": "cell", "config": config["name"],
                       "traffic": "nockpt", "chips": 1}],
        "end_to_end": [], "per_layer": []}))
    (bench / "configs" / f"{config['name']}.json").write_text(
        json.dumps(config))
    (bench / "traffic" / "nockpt.json").write_text(
        (spec.BENCH / "traffic" / "nockpt.json").read_text())
    (bench / "workloads" / "cell.json").write_text('{"limits": {}}')
    for name, text in modules.items():
        (bench / "references" / f"{name}.py").write_text(text)
    return tmp_path


def test_a_new_architecture_is_a_new_file(tmp_path):
    config = {"name": "toy", "reference": "toy_bigram",
              "program_arch": "gpt2-l", "bigram_only": True, "d_model": 32,
              "vocab": 256, "batch": 2, "seq": 16,
              "param_dtype": "float32"}
    root = _root(tmp_path, config, {"toy_bigram": TOY})
    cell = spec.load_cell("cell", root=root)
    assert cell.arch.__file__ == str(root / "bench" / "references" /
                                     "toy_bigram.py")
    assert spec.reference_of(cell.config) is cell.arch
    assert inputs.n_params(cell.config) == 2 * 32 * 256
    assert flops.train_flops_per_token(cell.config) == 6 * 32 * 256
    readings = checks.reference_readings(cell.config, 2 ** 31 + 1, rho=0.0,
                                         lr=1e-3, steps=2)
    assert len(readings["grad_norms"]) == 2
    assert np.isfinite(readings["losses"]).all()
    assert (readings["change_norms"] > 0).all()
    arch = harness.program_arch(cell.config, cell.arch)
    assert (arch.d_model, arch.vocab) == (32, 256)
    with pytest.raises(spec.SpecError, match="bigram_only"):
        harness.program_arch(dict(cell.config, bigram_only=False), cell.arch)


def test_an_unknown_reference_is_refused_with_its_path(tmp_path):
    root = _root(tmp_path, {"name": "toy", "reference": "nosuch"}, {})
    with pytest.raises(spec.SpecError, match=re.escape(
            str(root / "bench" / "references" / "nosuch.py"))):
        spec.load_cell("cell", root=root)
    with pytest.raises(spec.SpecError, match="not a name"):
        spec.load_reference("../dense", root)


def test_a_module_lacking_the_api_is_refused(tmp_path):
    root = _root(tmp_path, {"name": "toy", "reference": "half"},
                 {"half": "PROGRAM_IMPLIED = {}\n"})
    with pytest.raises(spec.SpecError, match="param_layout"):
        spec.load_cell("cell", root=root)
