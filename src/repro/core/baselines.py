"""Baseline checkpointing strategies the paper compares against (§VIII-A).

All share the LowDiff strategy interface (train_step / flush / recover /
stats) so the benchmark harness can swap them:

* ``FullSync``      — "Torch.save": blocking full-state write every
                      ``interval`` iterations.
* ``CheckFreq``     — [FAST'21]: snapshot (sync D2H) + asynchronous
                      persist, pipelined; per-paper default interval 10.
* ``Gemini``        — [SOSP'23]: per-iteration snapshot into (peer) host
                      memory as the primary checkpoint, rare persistence;
                      recovery from host memory.
* ``NaiveDC``       — Check-N-Run style differential checkpointing for a
                      dense model: differential = M_{t+1} - M_t over the
                      *full* model state (3Ψ), top-k compressed each
                      iteration — i.e. DC *without* gradient reuse. This
                      carries the paper's Challenge-1 compression cost and
                      Challenge-2 transmission cost by construction.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.compression.sparse import compress_tree, decompress_tree
from repro.core.lowdiff import host_copy
from repro.core.steps import make_train_step


class _Base:
    def __init__(self, model, store: CheckpointStore, *, lr=1e-3,
                 interval: int = 1):
        self.model, self.store, self.lr = model, store, lr
        self.interval = interval
        self.step_fn = make_train_step(model, mode="dense", lr=lr)
        self._pool = ThreadPoolExecutor(max_workers=1)
        self._pending: List[Any] = []

    def flush(self):
        for f in self._pending:
            f.result()
        self._pending.clear()
        self.store.flush()

    def close(self):
        self.flush()
        self.store.close()

    def recover(self):
        entry = self.store.latest_full()
        if entry is None:
            raise FileNotFoundError("no checkpoint")
        return self.store.load_full(entry), 0

    def stats(self):
        return {"store": self.store.stats()}


class FullSync(_Base):
    name = "full_sync"

    def train_step(self, state, batch):
        state, metrics, _ = self.step_fn(state, batch)
        step = int(state["step"])
        if step % self.interval == 0:
            self.store.save_full(step, host_copy(state))   # blocking
        return state, metrics


class CheckFreq(_Base):
    name = "checkfreq"

    def __init__(self, model, store, *, lr=1e-3, interval: int = 10):
        super().__init__(model, store, lr=lr, interval=interval)

    def train_step(self, state, batch):
        state, metrics, _ = self.step_fn(state, batch)
        step = int(state["step"])
        if step % self.interval == 0:
            # snapshot() is synchronous w.r.t. the update (WAR hazard in
            # the paper's analysis); persist() is async.
            snap = host_copy(state)
            self.flush()   # CheckFreq admits at most one in-flight persist
            self._pending.append(
                self._pool.submit(self.store.save_full, step, snap))
        return state, metrics


class Gemini(_Base):
    """In-memory checkpointing to (simulated peer) host DRAM."""
    name = "gemini"

    def __init__(self, model, store, *, lr=1e-3, interval: int = 1,
                 persist_interval: int = 100):
        super().__init__(model, store, lr=lr, interval=interval)
        self.persist_interval = persist_interval
        self.memory_ckpt: Optional[Dict] = None
        self.memory_step = -1

    def train_step(self, state, batch):
        state, metrics, _ = self.step_fn(state, batch)
        step = int(state["step"])
        if step % self.interval == 0:
            self.memory_ckpt = host_copy(state)      # "peer CPU memory"
            self.memory_step = step
        if step % self.persist_interval == 0:
            self._pending.append(self._pool.submit(
                self.store.save_full, step, self.memory_ckpt))
        return state, metrics

    def recover(self):
        if self.memory_ckpt is not None:
            return self.memory_ckpt, 0
        return super().recover()


class NaiveDC(_Base):
    """Differential checkpointing without gradient reuse (Check-N-Run
    transplanted to dense models). The differential is computed and
    compressed *inside the training loop* — the compression stall the
    paper measures in Fig. 1 — then written asynchronously."""
    name = "naive_dc"

    def __init__(self, model, store, *, lr=1e-3, rho=0.01,
                 interval: int = 1, full_interval: int = 50):
        super().__init__(model, store, lr=lr, interval=interval)
        self.rho = rho
        self.full_interval = full_interval

        @jax.jit
        def diff_compress(new_state, old_state):
            delta = {
                "params": jax.tree.map(
                    lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
                    new_state["params"], old_state["params"]),
                "mu": jax.tree.map(lambda a, b: a - b, new_state["opt"].mu,
                                   old_state["opt"].mu),
                "nu": jax.tree.map(lambda a, b: a - b, new_state["opt"].nu,
                                   old_state["opt"].nu),
            }
            return compress_tree(delta, self.rho)   # compress all 3Ψ

        self._diff_compress = diff_compress

    def train_step(self, state, batch):
        old_state = state
        state, metrics, _ = self.step_fn(state, batch)
        step = int(state["step"])
        if step % self.interval == 0:
            cd = self._diff_compress(state, old_state)
            jax.block_until_ready(jax.tree.leaves(cd)[0])   # Challenge 1 stall
            payload = host_copy(cd)
            self._pending.append(
                self._pool.submit(self.store.save_diff, step, payload))
        if step % self.full_interval == 0:
            self._pending.append(self._pool.submit(
                self.store.save_full, step, host_copy(state)))
        return state, metrics

    def recover(self):
        from repro.core.recovery import load_latest_chain, \
            merge_deltas_pairwise
        state, diffs = load_latest_chain(self.store)
        if diffs:
            deltas = [decompress_tree(p) for _, p in diffs]
            merged, _ = merge_deltas_pairwise(deltas)
            state["params"] = jax.tree.map(
                lambda p, d: (p.astype(jnp.float32) + d).astype(p.dtype),
                state["params"], merged["params"])
            opt = state["opt"]
            state["opt"] = type(opt)(
                jax.tree.map(lambda a, b: a + b, opt.mu, merged["mu"]),
                jax.tree.map(lambda a, b: a + b, opt.nu, merged["nu"]),
                opt.count + len(diffs))
            state["step"] = np.asarray(diffs[-1][0], np.int32)
        return state, len(diffs)
