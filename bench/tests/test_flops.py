"""FLOP and byte counts against counts made by hand."""
import json

from benchlib import flops, spec


def _cfg(name):
    return json.load(open(spec.BENCH / "configs" / f"{name}.json"))


def _matmul_params(cfg):
    return spec.reference_of(cfg).matmul_params(cfg)


def test_gpt2_l_8_layers_by_hand():
    # per layer: q, k, v, o 4 x 1280^2; SwiGLU 3 x 1280 x 5120
    layer = 4 * 1280 * 1280 + 3 * 1280 * 5120
    head = 1280 * 50257
    assert _matmul_params(_cfg("gpt2-l-8L")) == 8 * layer + head
    # causal attention: 6 x heads*head_dim x (S + 1) per layer and token
    attn = 6 * 1280 * 1025 * 8
    assert flops.train_flops_per_token(_cfg("gpt2-l-8L")) == \
        6 * (8 * layer + head) + attn == 1_707_240_960


def test_stablelm_3_layers_by_hand():
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    head = 2048 * 25088
    assert _matmul_params(_cfg("stablelm-1.6b-3L")) == 3 * layer + head
    attn = 6 * 2048 * 4097 * 3
    assert flops.train_flops_per_token(_cfg("stablelm-1.6b-3L")) == \
        6 * (3 * layer + head) + attn


def test_input_embedding_is_not_counted():
    cfg = _cfg("gpt2-l-8L")
    more_vocab = dict(cfg, vocab=cfg["vocab"] + 1)
    # one more vocabulary row adds one head column (d_model params), not
    # an embedding row as well
    assert _matmul_params(more_vocab) - _matmul_params(cfg) == \
        cfg["d_model"]


def test_replay_bytes_match_the_programs_roofline():
    import jax
    from repro.analysis.roofline import replay_roofline
    state, payload, n = 12 * 338_394_880, 21_800_000, 16
    want = replay_roofline(state, payload, n, jax.devices()[0])
    assert flops.replay_min_bytes(state, payload, n) == want["traffic_bytes"]
