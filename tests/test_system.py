"""End-to-end behaviour tests for the paper's system.

The detailed suites live in:
  test_arch_smoke.py  — per-architecture reduced-config smoke (fwd/train/decode)
  test_kernels.py     — Pallas kernels vs jnp oracles (+ hypothesis properties)
  test_lowdiff.py     — LowDiff/LowDiff+ end-to-end, recovery exactness
  test_simulator.py   — failure/MTBF simulator orderings
  test_roofline.py    — segment composition vs full-unroll validation

This module keeps the cross-cutting behaviours: a full train->fail->
recover->resume cycle driven through the public launcher, and the
config-optimizer end-to-end wiring.
"""
import argparse

import jax
import numpy as np
import pytest

from repro.checkpoint.store import CheckpointStore
from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.config_opt import OnlineTuner, SystemParams
from repro.core.lowdiff import LowDiff
from repro.core.steps import init_state
from repro.data.synthetic import TokenStream, make_batch
from repro.models.registry import build_model


def test_launcher_end_to_end_with_failure(tmp_path):
    """The public training driver survives an injected failure."""
    from repro.launch import train as T
    args = argparse.Namespace(
        arch="qwen2-1.5b", reduced=True, steps=12, batch=2, seq=32,
        lr=1e-3, rho=0.05, strategy="lowdiff", full_interval=5,
        batch_size=2, ckpt_dir=str(tmp_path / "ck"), clean=True,
        fail_at=8, seed=0, log_every=0)
    res = T.run(args)
    losses = res.losses
    assert len(losses) == 12
    assert np.isfinite(losses).all()


def test_training_is_deterministic_across_recovery(tmp_path):
    """Resume-from-recovery replays the same data and produces the same
    loss trajectory as an uninterrupted run (modulo the EF reset)."""
    cfg = get_config("stablelm-1.6b").reduced()
    model = build_model(cfg)

    def run(fail):
        store = CheckpointStore(str(tmp_path / f"d{fail}"))
        ld = LowDiff(model, store, rho=1.0, lr=1e-3, full_interval=4,
                     batch_size=1, error_feedback=False)
        state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
        if "ef" in state:
            del state["ef"]
        stream = TokenStream(cfg, 32, 2)
        losses = []
        for t in range(10):
            state, m = ld.train_step(state, next(stream))
            losses.append(float(m["loss"]))
            if fail and t + 1 == 6:
                ld.flush()
                state, _ = ld.recover()
                stream.step = int(state["step"])
        ld.close()
        return losses

    a = run(fail=False)
    b = run(fail=True)
    np.testing.assert_allclose(a, b, rtol=1e-4)


def test_online_tuner_adapts():
    tuner = OnlineTuner(SystemParams(M=3600, W=5e9, S=1e9, R_D=0.5))
    i0, b0 = tuner.current()
    for _ in range(8):
        tuner.observe_failure_gap(200.0)   # failures now very frequent
    i1, b1 = tuner.current()
    assert i1 <= i0                        # checkpoint more often

def test_all_archs_have_configs():
    assert len(ASSIGNED_ARCHS) == 10
    for a in ASSIGNED_ARCHS:
        cfg = get_config(a)
        assert cfg.param_count() > 0
        batch = make_batch(cfg.reduced(), 16, 1)
        assert batch["tokens"].shape == (1, 16)
