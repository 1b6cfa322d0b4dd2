"""Regression tests for the checkpointing-pipeline liveness fixes.

Each test pins one of the bugs that would corrupt or deadlock a long
run against a slow remote tier:
  * a poisoned persist handler makes flush() raise (bounded) instead of
    busy-waiting forever on a counter the dead consumer can't advance
  * the online tuner's re-solved (f, b) actually propagates to
    full_interval/batch_size (the paper's dynamic adaptation was dead)
  * ReusingQueue.close() never blocks on a full queue, and the shutdown
    sentinel is not counted as a dequeued differential
  * a step present both as a standalone diff blob and inside a batch
    blob replays exactly once (standalone wins) — double-applying it
    through Adam advances the moments twice and corrupts recovery
"""
import time

import jax
import numpy as np
import pytest

from repro.checkpoint.backends import LocalFSBackend
from repro.checkpoint.store import CheckpointStore
from repro.configs import get_config
from repro.core import recovery as rec
from repro.core.lowdiff import LowDiff
from repro.core.lowdiff_plus import LowDiffPlus
from repro.core.reusing_queue import CheckpointingError, ReusingQueue
from repro.core.steps import init_state
from repro.data.synthetic import make_batch
from repro.models.registry import build_model
from repro.optim.adam import AdamState

SEQ, BATCH = 32, 2


@pytest.fixture(scope="module")
def tiny_model():
    return build_model(get_config("qwen2-1.5b").reduced())


# --------------------------------------------------------------------------
# flush() liveness
# --------------------------------------------------------------------------

def test_poisoned_handler_flush_raises_not_hangs(tiny_model, tmp_path):
    """An exception in the consumer's handler used to kill the drain
    thread silently; flush() then spun forever. It must now re-raise
    the handler error, well inside the deadline."""
    store = CheckpointStore(str(tmp_path / "ck"))
    ld = LowDiff(tiny_model, store, full_interval=100, batch_size=2,
                 parallel_recovery=False)

    def poisoned(step, cg):
        raise RuntimeError("persist tier exploded")

    ld._handle = poisoned
    state = init_state(tiny_model, jax.random.PRNGKey(0), mode="lowdiff")
    state, _ = ld.train_step(state, make_batch(tiny_model.cfg, SEQ, BATCH))
    t0 = time.monotonic()
    with pytest.raises(CheckpointingError) as ei:
        ld.flush(timeout=30.0)
    assert time.monotonic() - t0 < 10.0       # raised, not deadline-waited
    assert "persist tier exploded" in str(ei.value.__cause__)
    # the consumer must NOT be silently restarted over the poisoned
    # queue: persisting later batches past the lost one would durably
    # write a chain with an undetectable hole
    with pytest.raises(CheckpointingError, match="previously failed"):
        ld.train_step(state, make_batch(tiny_model.cfg, SEQ, BATCH))
    # close() surfaces the same failure instead of pretending all is well
    with pytest.raises(CheckpointingError):
        ld.close()


def test_flush_raises_when_consumer_never_ran(tiny_model, tmp_path):
    store = CheckpointStore(str(tmp_path / "ck"))
    ld = LowDiff(tiny_model, store, full_interval=100, batch_size=2)
    ld.queue.put(1, {"g": np.zeros(4, np.float32)})   # consumer never started
    with pytest.raises(CheckpointingError, match="not running"):
        ld.flush(timeout=5.0)
    store.close()


def test_flush_deadline_bounds_wait(tiny_model, tmp_path):
    """A wedged (not dead) consumer must not stall flush forever: the
    deadline turns the hang into a TimeoutError."""
    store = CheckpointStore(str(tmp_path / "ck"))
    ld = LowDiff(tiny_model, store, full_interval=100, batch_size=2)

    def wedged(step, cg):
        time.sleep(5.0)
        ld._processed += 1

    ld._handle = wedged
    state = init_state(tiny_model, jax.random.PRNGKey(0), mode="lowdiff")
    ld.train_step(state, make_batch(tiny_model.cfg, SEQ, BATCH))
    with pytest.raises(TimeoutError):
        ld.flush(timeout=0.3)
    # let the wedged consumer finish so teardown is clean
    ld.flush(timeout=30.0)
    ld.close()


def test_flush_deadline_spares_a_slow_advancing_consumer(tiny_model,
                                                         tmp_path):
    """The deadline bounds a wait with no progress, not the whole
    drain: six items at 0.3 s each outlast a 0.5 s timeout, but each
    lands inside it."""
    store = CheckpointStore(str(tmp_path / "ck"))
    ld = LowDiff(tiny_model, store, full_interval=100, batch_size=1)

    def slow(step, cg):
        time.sleep(0.3)
        ld._processed += 1

    ld._handle = slow
    state = init_state(tiny_model, jax.random.PRNGKey(0), mode="lowdiff")
    batch = make_batch(tiny_model.cfg, SEQ, BATCH)
    for _ in range(6):
        state, _ = ld.train_step(state, batch)
    t0 = time.monotonic()
    ld.flush(timeout=0.5)
    assert ld._processed == 6
    assert time.monotonic() - t0 > 0.5
    ld.close()


def test_lowdiff_plus_poisoned_persist_flush_raises(tiny_model, tmp_path):
    store = CheckpointStore(str(tmp_path / "ckp"))
    ldp = LowDiffPlus(tiny_model, store, persist_interval=1)

    def poisoned(step, futures):
        raise OSError("replica persist failed")

    ldp._handle = poisoned
    state = init_state(tiny_model, jax.random.PRNGKey(0),
                       mode="lowdiff_plus")
    ldp.train_step(state, make_batch(tiny_model.cfg, SEQ, BATCH))
    with pytest.raises(CheckpointingError) as ei:
        ldp.flush(timeout=30.0)
    assert isinstance(ei.value.__cause__, OSError)
    with pytest.raises(CheckpointingError):
        ldp.close()


# --------------------------------------------------------------------------
# dynamic tuning
# --------------------------------------------------------------------------

def test_tuner_updates_propagate_in_auto_mode(tiny_model, tmp_path):
    """LowDiff fed the tuner merge times but never read current() back:
    (f, b) stayed at the Eq. (10) seed forever. After a batch flush the
    re-solved config must now be applied and recorded."""
    store = CheckpointStore(str(tmp_path / "tune"))
    ld = LowDiff(tiny_model, store)        # no overrides: auto (f, b)
    f0, b0 = ld.full_interval, ld.batch_size
    pay = {"g": np.zeros(16, np.float32)}
    ld._buffer = [(1, pay), (2, pay)]
    ld._flush_batch()
    # observed merge time (~ms) is far below the R_D prior (0.5 iter):
    # the EMA drops R_D, so b* shrinks and the full interval stretches
    assert (ld.full_interval, ld.batch_size) != (f0, b0)
    assert ld.full_interval > f0
    assert ld.batch_size < b0
    tuning = ld.stats()["tuning"]
    assert tuning["auto"] == {"full_interval": True, "batch_size": True}
    assert tuning["applied"] >= 1
    assert len(tuning["history"]) == 1
    assert tuning["history"][0]["applied"] is True
    # more observations keep converging, never diverge to nonsense
    for s in range(3, 9, 2):
        ld._buffer = [(s, pay), (s + 1, pay)]
        ld._flush_batch()
    assert 1 <= ld.batch_size <= b0
    assert len(ld.stats()["tuning"]["history"]) == 4
    store.close()


def test_tuner_respects_pinned_config(tiny_model, tmp_path):
    """Explicit (f, b) are pinned: the tuner records its recommendation
    but must not override the caller's choice."""
    store = CheckpointStore(str(tmp_path / "pin"))
    ld = LowDiff(tiny_model, store, full_interval=5, batch_size=2)
    pay = {"g": np.zeros(16, np.float32)}
    ld._buffer = [(1, pay), (2, pay)]
    ld._flush_batch()
    assert (ld.full_interval, ld.batch_size) == (5, 2)
    tuning = ld.stats()["tuning"]
    assert tuning["applied"] == 0
    assert len(tuning["history"]) == 1
    assert tuning["history"][0]["applied"] is False
    assert tuning["history"][0]["batch_size"] != 2   # it did re-solve
    store.close()


# --------------------------------------------------------------------------
# queue shutdown semantics
# --------------------------------------------------------------------------

def test_queue_close_nonblocking_on_full_queue():
    q = ReusingQueue(maxsize=2)
    q.put(1, "a")
    q.put(2, "b")                       # queue is now full
    t0 = time.monotonic()
    q.close()                           # used to block in _q.put()
    assert time.monotonic() - t0 < 0.5
    seen = []
    q.drain(lambda s, p: seen.append(s))
    assert seen == [1, 2]               # closed flag still drains the backlog


def test_queue_sentinel_not_counted_in_dequeued():
    q = ReusingQueue(maxsize=8)
    q.put(1, "a")
    q.put(2, "b")
    q.close()                           # room for the sentinel this time
    q.drain(lambda s, p: None)
    st = q.stats()
    assert st["enqueued"] == 2
    assert st["dequeued"] == 2          # sentinel excluded


def test_queue_drain_captures_handler_error():
    q = ReusingQueue(maxsize=8)
    q.put(1, "a")
    q.put(2, "b")

    def boom(step, payload):
        raise ValueError("bad payload")

    q.drain(boom)                       # returns instead of raising
    assert isinstance(q.error, ValueError)
    assert q.stats()["consumer_error"] is not None


# --------------------------------------------------------------------------
# diffs_after double-apply
# --------------------------------------------------------------------------

class CountingBackend(LocalFSBackend):
    def __init__(self, root):
        super().__init__(root)
        self.gets = 0

    def get(self, key):
        self.gets += 1
        return super().get(key)


def _grad(step):
    return {"w": np.full(8, 0.1 * step, np.float32)}


def test_diffs_after_dedups_standalone_and_batch(tmp_path):
    """A step present both as diff_* and inside batch_* must be returned
    once, from the standalone blob."""
    store = CheckpointStore(backend=CountingBackend(str(tmp_path / "d")))
    store.save_batch(1, 3, [_grad(1), _grad(2), _grad(3)])
    marker = {"w": np.full(8, 99.0, np.float32)}
    store.save_diff(2, marker)          # duplicate of batch step 2
    out = store.diffs_after(0)
    assert [s for s, _ in out] == [1, 2, 3]
    np.testing.assert_array_equal(dict(out)[2]["w"], marker["w"])
    store.close()


def test_diffs_after_skips_fully_covered_batch(tmp_path):
    be = CountingBackend(str(tmp_path / "c"))
    store = CheckpointStore(backend=be)
    store.save_batch(1, 2, [_grad(1), _grad(2)])
    store.save_diff(1, _grad(1))
    store.save_diff(2, _grad(2))
    be.gets = 0
    out = store.diffs_after(0)
    assert [s for s, _ in out] == [1, 2]
    assert be.gets == 2                 # the redundant batch never fetched
    store.close()


def test_contiguous_prefix_cuts_at_first_gap():
    """A mid-chain hole (a differential whose write-back never landed)
    must truncate replay, never be skipped over."""
    diffs = [(5, "a"), (6, "b"), (8, "c"), (9, "d")]   # 7 is missing
    assert rec.contiguous_prefix(4, diffs) == [(5, "a"), (6, "b")]
    assert rec.contiguous_prefix(4, []) == []
    assert rec.contiguous_prefix(6, [(8, "c")]) == []  # gap at the head
    assert rec.contiguous_prefix(4, [(6, "x"), (8, "y")],
                                 stride=2) == [(6, "x"), (8, "y")]


def test_lowdiff_recover_stops_at_writeback_hole(tmp_path, tiny_model):
    """LowDiff recovery over a manifest with a mid-chain hole recovers
    to the last consistent step instead of replaying across the gap."""
    store = CheckpointStore(str(tmp_path / "hole"))
    ld = LowDiff(tiny_model, store, rho=0.05, lr=1e-3, full_interval=4,
                 batch_size=2, parallel_recovery=False)
    state = init_state(tiny_model, jax.random.PRNGKey(0), mode="lowdiff")
    for t in range(9):
        state, _ = ld.train_step(state, make_batch(tiny_model.cfg, SEQ,
                                                   BATCH, step=t))
    ld.flush()
    # simulate the crash pattern _prune_missing cannot repair: the
    # newest full AND a mid-chain batch both lost (failed write-backs)
    for key, kind in (("full_00000008", "fulls"),
                      ("batch_00000005_00000006", "batches")):
        store.journal.append("del", kind, key=key)
        store.backend.delete(key)
    rec_state, n = ld.recover()
    # chain from full@4 is 5,6(missing),7,8,9 -> nothing replayable
    # past the hole at 5: recover lands exactly on the full@4 state
    assert n == 0
    assert int(rec_state["step"]) == 4
    ld.close()


def test_duplicate_replay_bit_identical_to_clean_chain(tmp_path):
    """Replaying a chain that contains a duplicated step must produce
    exactly the bytes of the duplicate-free chain — double-applying a
    differential through Adam advances count/moments twice."""
    params = {"w": np.linspace(-1, 1, 8).astype(np.float32)}
    opt = AdamState(mu={"w": np.zeros(8, np.float32)},
                    nu={"w": np.zeros(8, np.float32)},
                    count=np.zeros((), np.int32))

    clean = CheckpointStore(backend=LocalFSBackend(str(tmp_path / "a")))
    clean.save_batch(1, 3, [_grad(1), _grad(2), _grad(3)])
    dup = CheckpointStore(backend=LocalFSBackend(str(tmp_path / "b")))
    dup.save_batch(1, 3, [_grad(1), _grad(2), _grad(3)])
    dup.save_diff(2, _grad(2))          # the double-write

    p_clean, o_clean = rec.replay_serial(params, opt,
                                         clean.diffs_after(0), lr=1e-3)
    p_dup, o_dup = rec.replay_serial(params, opt,
                                     dup.diffs_after(0), lr=1e-3)
    np.testing.assert_array_equal(np.asarray(p_clean["w"]),
                                  np.asarray(p_dup["w"]))
    np.testing.assert_array_equal(np.asarray(o_clean.mu["w"]),
                                  np.asarray(o_dup.mu["w"]))
    np.testing.assert_array_equal(np.asarray(o_clean.nu["w"]),
                                  np.asarray(o_dup.nu["w"]))
    assert int(o_clean.count) == int(o_dup.count) == 3
    clean.close()
    dup.close()
