"""End-to-end tests of the LowDiff / LowDiff+ core (the paper's system)."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # optional property-testing dep; never hard-fail collection
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

from repro.checkpoint.store import CheckpointStore
from repro.configs import get_config
from repro.core import config_opt as co
from repro.core.baselines import CheckFreq, FullSync, Gemini, NaiveDC
from repro.core.lowdiff import LowDiff
from repro.core.lowdiff_plus import LowDiffPlus
from repro.core.reusing_queue import ReusingQueue
from repro.core.steps import init_state, make_train_step
from repro.data.synthetic import make_batch
from repro.models.registry import build_model

SEQ, BATCH = 32, 2


def tiny_model():
    return build_model(get_config("qwen2-1.5b").reduced())


def assert_trees_close(a, b, atol=0.0, rtol=0.0):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(la, np.float32),
                                   np.asarray(lb, np.float32),
                                   atol=atol, rtol=rtol)


# --------------------------------------------------------------------------
# configuration optimization (Eq. 8-10, Table I)
# --------------------------------------------------------------------------

def test_closed_form_matches_grid():
    p = co.SystemParams(N=8, M=1800, W=5e9, S=8.7e9, T=1e5, R_F=5, R_D=0.4)
    f_star, b_star = co.optimal_config(p)
    f_g, b_g, _ = co.grid_verify(p)
    assert abs(np.log(f_star / f_g)) < 0.05
    assert abs(np.log(b_star / b_g)) < 0.05


def _stationary_body(M, W, S, R_D):
    """(f*, b*) zeroes both partial derivatives of Eq. (8)."""
    p = co.SystemParams(M=M, W=W, S=S, R_D=R_D)
    f, b = co.optimal_config(p)
    epsf, epsb = f * 1e-4, b * 1e-4
    dfd = (co.wasted_time(f + epsf, b, p) - co.wasted_time(f - epsf, b, p))
    dbd = (co.wasted_time(f, b + epsb, p) - co.wasted_time(f, b - epsb, p))
    w0 = co.wasted_time(f, b, p)
    assert abs(dfd) / w0 < 1e-4
    assert abs(dbd) / w0 < 1e-4


if HAVE_HYPOTHESIS:
    @settings(max_examples=30, deadline=None)
    @given(M=st.floats(100, 1e5), W=st.floats(1e8, 1e11),
           S=st.floats(1e7, 1e11), R_D=st.floats(0.01, 10))
    def test_closed_form_is_stationary(M, W, S, R_D):
        _stationary_body(M, W, S, R_D)
else:
    @pytest.mark.parametrize("M,W,S,R_D", [
        (1800.0, 5e9, 8.7e9, 0.4), (3600.0, 1e10, 1.4e9, 0.3),
        (500.0, 2e8, 5e7, 2.0)])
    def test_closed_form_is_stationary(M, W, S, R_D):
        _stationary_body(M, W, S, R_D)


def test_table1_shape():
    """Wasted time is U-shaped in both FCF and BS (paper Table I)."""
    p = co.SystemParams(N=8, M=3600, W=5e9, S=1.4e9, T=1e5, R_F=4, R_D=0.3)
    f_star, b_star = co.optimal_config(p)
    fs = [f_star / 8, f_star, f_star * 8]
    ws = [co.wasted_time(f, b_star, p) for f in fs]
    assert ws[1] < ws[0] and ws[1] < ws[2]
    bs = [max(b_star / 8, 1e-3), b_star, b_star * 8]
    ws = [co.wasted_time(f_star, b, p) for b in bs]
    assert ws[1] < ws[0] and ws[1] < ws[2]


# --------------------------------------------------------------------------
# reusing queue
# --------------------------------------------------------------------------

def test_queue_fifo_order():
    q = ReusingQueue(maxsize=16)
    for i in range(10):
        q.put(i, {"g": i})
    got = [q.get()[0] for _ in range(10)]
    assert got == list(range(10))
    assert q.stats()["enqueued"] == 10


# --------------------------------------------------------------------------
# LowDiff end-to-end: train -> crash -> recover == live state
# --------------------------------------------------------------------------

@pytest.fixture()
def trained_lowdiff(tmp_path):
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / "ckpt"))
    ld = LowDiff(model, store, rho=0.05, lr=1e-3, full_interval=5,
                 batch_size=2, parallel_recovery=False)
    state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
    for t in range(12):
        batch = make_batch(model.cfg, SEQ, BATCH, step=t)
        state, metrics = ld.train_step(state, batch)
    ld.flush()
    return model, store, ld, state


def test_lowdiff_store_layout(trained_lowdiff):
    _, store, ld, _ = trained_lowdiff
    s = store.stats()
    assert s["fulls"] == 2           # steps 5, 10
    assert s["batches"] >= 5         # 12 diffs in batches of 2
    assert ld.queue.stats()["enqueued"] == 12


def test_lowdiff_recovery_exact_serial(trained_lowdiff):
    model, store, ld, live = trained_lowdiff
    rec_state, n = ld.recover()
    assert n == 2                    # full@10 + diffs 11,12
    assert int(rec_state["step"]) == 12
    # identical math; tolerances only for jit-vs-eager fusion rounding
    assert_trees_close(rec_state["params"], live["params"],
                       atol=1e-8, rtol=1e-4)
    assert_trees_close(rec_state["opt"].mu, live["opt"].mu,
                       atol=1e-8, rtol=1e-4)
    assert_trees_close(rec_state["opt"].nu, live["opt"].nu,
                       atol=1e-10, rtol=1e-4)


def test_lowdiff_recovery_parallel_matches_serial(trained_lowdiff):
    model, store, ld, live = trained_lowdiff
    ld.parallel_recovery = True
    rec_state, n = ld.recover()
    assert_trees_close(rec_state["params"], live["params"],
                       atol=1e-6, rtol=1e-5)
    assert_trees_close(rec_state["opt"].mu, live["opt"].mu,
                       atol=1e-6, rtol=1e-5)


def test_lowdiff_recover_uploads_state_explicitly(trained_lowdiff):
    """Recovery puts the full's params, moments and error feedback on
    the device itself, meters those bytes with the replay's payloads,
    and traces each upload where it happens."""
    from repro.checkpoint.io import COPY_METER
    from repro.obs.trace import TRACER
    _, store, ld, live = trained_lowdiff
    ld.replay_device = True
    full = store.load_full(store.manifest["fulls"][-1])
    nbytes = lambda t: sum(np.asarray(x).nbytes  # noqa: E731
                           for x in jax.tree.leaves(t))
    state_bytes = nbytes((full["params"], full["opt"]))
    ef_bytes = nbytes(full["ef"])
    del full
    COPY_METER.reset()
    TRACER.clear()
    TRACER.enable()
    try:
        rec_state, n = ld.recover()
    finally:
        TRACER.disable()
    spans = {e[0]: e for e in TRACER.events()}
    TRACER.clear()
    assert n == 2
    h2d_state, h2d_ef = spans["recovery.h2d_state"], spans["recovery.h2d_ef"]
    replay, staged = spans["recovery.replay"], spans["replay.h2d"]
    assert h2d_state[6] == {"bytes": state_bytes}
    assert h2d_ef[6] == {"bytes": ef_bytes}
    assert staged[7] == "recovery.replay"
    # state lands before the replay; the EF is issued behind it
    assert h2d_state[5] <= replay[4] <= replay[5] <= h2d_ef[4]
    assert COPY_METER.h2d_bytes == (state_bytes + ef_bytes
                                    + staged[6]["bytes"])
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(
        (rec_state["params"], rec_state["opt"], rec_state["ef"])))
    assert_trees_close(rec_state["params"], live["params"],
                       atol=1e-8, rtol=1e-4)
    COPY_METER.reset()


def test_lowdiff_train_step_spans(tmp_path):
    """The training thread's share of checkpointing is traced: one
    dispatch and one queue hand-off a step, a snapshot issue at each
    full save."""
    from repro.obs.trace import TRACER
    model = tiny_model()
    ld = LowDiff(model, CheckpointStore(str(tmp_path / "ckpt")), rho=0.05,
                 full_interval=3, batch_size=2)
    state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
    TRACER.clear()
    TRACER.enable()
    try:
        for t in range(4):
            state, _ = ld.train_step(
                state, make_batch(model.cfg, SEQ, BATCH, step=t))
    finally:
        TRACER.disable()
        ld.close()
    main = threading.get_ident()
    names = [e[0] for e in TRACER.events() if e[2] == main
             and e[0].startswith(("engine.", "snapshot.issue"))]
    TRACER.clear()
    assert names == ["engine.dispatch", "engine.queue_put"] * 3 + [
        "snapshot.issue", "engine.dispatch", "engine.queue_put"]


def test_lowdiff_diffs_much_smaller_than_full(trained_lowdiff):
    """Finding 2: compressed-gradient diffs << full checkpoints."""
    _, store, _, _ = trained_lowdiff
    full_bytes = store.manifest["fulls"][0]["bytes"]
    batch_bytes = np.mean([e["bytes"] for e in store.manifest["batches"]])
    per_diff = batch_bytes / 2
    assert per_diff < full_bytes / 10


# --------------------------------------------------------------------------
# LowDiff+ (non-compression mode)
# --------------------------------------------------------------------------

def test_lowdiff_plus_software_recovery(tmp_path):
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / "ckpt"))
    ldp = LowDiffPlus(model, store, lr=1e-3, persist_interval=4)
    state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff_plus")
    for t in range(9):
        state, _ = ldp.train_step(state, make_batch(model.cfg, SEQ, BATCH,
                                                    step=t))
    ldp.flush()
    rec = ldp.recover_software(state)
    # CPU replica applied the same dense gradients through the same Adam
    assert int(rec["step"]) == 9
    assert_trees_close(rec["params"], state["params"], atol=2e-6, rtol=1e-5)
    assert_trees_close(rec["opt"].mu, state["opt"].mu, atol=2e-6, rtol=1e-5)
    # hardware recovery: last persisted step (8)
    rec_h = ldp.recover_hardware(state)
    assert int(rec_h["step"]) == 8
    ldp.close()


# --------------------------------------------------------------------------
# baselines
# --------------------------------------------------------------------------

@pytest.mark.parametrize("cls,kw", [
    (FullSync, {"interval": 4}),
    (CheckFreq, {"interval": 5}),
    (Gemini, {"interval": 1, "persist_interval": 8}),
])
def test_baseline_roundtrip(tmp_path, cls, kw):
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / cls.__name__))
    strat = cls(model, store, lr=1e-3, **kw)
    state = init_state(model, jax.random.PRNGKey(0), mode="dense")
    saved_states = {}
    for t in range(8):
        state, _ = strat.train_step(state, make_batch(model.cfg, SEQ, BATCH,
                                                      step=t))
        saved_states[int(state["step"])] = jax.tree.map(np.asarray, state)
    strat.flush()
    rec, _ = strat.recover()
    step = int(rec["step"])
    assert step in saved_states
    assert_trees_close(rec["params"], saved_states[step]["params"], atol=0)
    strat.close()


def test_naive_dc_exact_when_lossless(tmp_path):
    """With rho=1.0 (no information loss) Naive DC recovery is exact."""
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / "ndc"))
    strat = NaiveDC(model, store, lr=1e-3, rho=1.0, full_interval=50)
    state = init_state(model, jax.random.PRNGKey(0), mode="dense")
    # force an initial full checkpoint to anchor the diff chain
    store.save_full(0, jax.tree.map(np.asarray, state))
    for t in range(6):
        state, _ = strat.train_step(state, make_batch(model.cfg, SEQ, BATCH,
                                                      step=t))
    strat.flush()
    rec, n = strat.recover()
    assert n == 6
    assert_trees_close(rec["params"], state["params"], atol=1e-5, rtol=1e-5)
    strat.close()


def test_lowdiff_quant8_compressor_roundtrip(tmp_path):
    """LowDiff with the int8-quantization compression family (§II-C):
    recovery still reconstructs the live state exactly (the model update
    uses the dequantized gradient, so Finding 1 remains an identity)."""
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / "q8"))
    ld = LowDiff(model, store, lr=1e-3, full_interval=4, batch_size=2,
                 compressor="quant8", parallel_recovery=False)
    state = init_state(model, jax.random.PRNGKey(0), mode="dense")
    for t in range(7):
        state, _ = ld.train_step(state, make_batch(model.cfg, SEQ, BATCH,
                                                   step=t))
    ld.flush()
    rec, n = ld.recover()
    assert n == 3   # full@4 + diffs 5,6,7
    assert_trees_close(rec["params"], state["params"], atol=1e-8, rtol=1e-4)
    assert_trees_close(rec["opt"].mu, state["opt"].mu, atol=1e-8, rtol=1e-4)
    # int8 differentials are ~4x smaller than dense f32
    diff_bytes = np.mean([e["bytes"] for e in store.manifest["batches"]]) / 2
    full_bytes = store.manifest["fulls"][0]["bytes"]
    assert diff_bytes < full_bytes / 8
    ld.close()


def test_naive_dc_lossy_storage_smaller(tmp_path):
    model = tiny_model()
    store = CheckpointStore(str(tmp_path / "ndc2"))
    strat = NaiveDC(model, store, lr=1e-3, rho=0.01, full_interval=50)
    state = init_state(model, jax.random.PRNGKey(0), mode="dense")
    store.save_full(0, jax.tree.map(np.asarray, state))
    for t in range(3):
        state, _ = strat.train_step(state, make_batch(model.cfg, SEQ, BATCH,
                                                      step=t))
    strat.flush()
    full_b = store.manifest["fulls"][0]["bytes"]
    diff_b = store.manifest["diffs"][0]["bytes"]
    assert diff_b < full_b / 5
    strat.close()


def test_topk_step_matches_oracle_composition():
    """Three LowDiff top-k steps with error feedback, whose compress is
    the fused kernel, equal bit for bit a step composed from the jnp
    oracle: grads -> ef_compress_tree -> decompress_tree -> adam_update.
    Params, both moments, the residual and the wire are compared."""
    from repro.compression.error_feedback import ef_compress_tree
    from repro.compression.sparse import decompress_tree
    from repro.core.steps import _grads
    from repro.optim.adam import adam_update
    model = tiny_model()
    rho, lr = 0.05, 1e-3
    step = make_train_step(model, mode="lowdiff", rho=rho, lr=lr,
                           compressor="topk")

    @jax.jit
    def oracle(state, batch):
        _, _, grads = _grads(model, state["params"], batch,
                             model.cfg.grad_accum)
        # the kernel reads the gradient whole; without the barrier XLA
        # may fold the residual's add into the embedding's scatter-add,
        # which rounds in another order
        grads = jax.lax.optimization_barrier(grads)
        cg, ef = ef_compress_tree(grads, state["ef"], rho)
        params, opt = adam_update(state["params"], decompress_tree(cg),
                                  state["opt"], lr=lr)
        return {"params": params, "opt": opt, "step": state["step"] + 1,
                "ef": ef}, cg

    fused = ref = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
    for t in range(3):
        batch = make_batch(model.cfg, SEQ, BATCH, step=t)
        fused, _, cg_fused = step(fused, batch)
        ref, cg_ref = oracle(ref, batch)
        assert (jax.tree.structure((fused, cg_fused))
                == jax.tree.structure((ref, cg_ref)))
        for a, b in zip(jax.tree.leaves((fused, cg_fused)),
                        jax.tree.leaves((ref, cg_ref))):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
