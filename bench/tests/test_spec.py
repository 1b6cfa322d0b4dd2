"""Every name in BENCHMARK.json finds its files, and the benchmark's
weight layout is the program's parameter tree."""
import json

import jax
import pytest

from benchlib import harness, inputs, spec

BENCH = json.loads((spec.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_cell_files_and_limits(cell):
    c = spec.load_cell(cell)
    assert c.traffic["mode"] in ("train", "resume")
    assert c.limits and all(v >= 0 for v in c.limits.values())
    reported = {m["name"] for m in c.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert c.per_layer and all(m["moves"] in reported for m in c.per_layer)


def test_every_metric_has_a_reader():
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert (spec.BENCH / "metrics" / f"{m['name']}.py").exists(), m


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_weight_layout_is_the_programs(entry):
    cfg = json.loads((spec.ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    model = harness.program_model(cfg)
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda k: inputs.make_params(cfg, k),
                         inputs.seed_key(2 ** 31 + 5))
    assert inputs.shapes(want) == inputs.shapes(got)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    assert inputs.n_params(cfg) == model.n_params()


def test_same_seed_same_inputs_large_seed():
    cfg = json.loads((spec.BENCH / "configs" / "gpt2-l-8L.json").read_text())
    seed = 2 ** 31 + 987654321
    a, b = inputs.batch(cfg, seed, 7), inputs.batch(cfg, seed, 7)
    assert (a["tokens"] == b["tokens"]).all()
    assert a["tokens"].max() < cfg["vocab"]
    assert not (inputs.batch(cfg, seed, 8)["tokens"] == a["tokens"]).all()
    k1, k2 = inputs.seed_key(seed), inputs.seed_key(seed + 2 ** 32)
    assert not (k1 == k2).all()
