"""LowDiff: frequent differential checkpointing by compressed-gradient reuse.

Orchestrates the paper's architecture (Fig. 5): the jitted training step
emits the synchronized compressed gradient G̃_t; it is handed zero-copy to
the Reusing Queue; a background checkpointing thread drains the queue,
offloads to host memory (step ① of §V-B), batches b differentials
(step ②) and persists each batch in a single I/O (step ③). The model
state is checkpointed in full every `full_interval` steps,
asynchronously. (f, b) come from the Eq. (10) optimum unless overridden,
and the online tuner keeps re-solving Eq. (10) from observed merge
times after every batch write (§VII's optimal-configuration module) —
auto dimensions track the solution, pinned ones only record it.

Recovery (Algorithm 1 / §VII): load the latest full checkpoint, replay
the differential chain through Adam — serially or with the exact
log-depth parallel replay.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Dict, List, Optional

import numpy as np

from repro.checkpoint.store import CheckpointStore
from repro.core import recovery as rec
from repro.core.config_opt import OnlineTuner, SystemParams, practical_config
from repro.core.reusing_queue import (CheckpointingError, ReusingQueue,
                                      wait_drained)
from repro.core.snapshot import SnapshotArena, host_copy  # noqa: F401
from repro.core.steps import make_train_step
from repro.obs.timeline import TIMELINE
from repro.obs.trace import trace_span


def _payload_nbytes(payloads) -> int:
    """Host bytes of a batch of compressed differentials (what the
    batched write actually moves — the tuner history's bytes input)."""
    import jax
    return int(sum(getattr(leaf, "nbytes", 0) or 0
                   for p in payloads for leaf in jax.tree.leaves(p)))


class LowDiff:
    """Checkpointing strategy object. One per training job."""

    name = "lowdiff"

    def __init__(self, model, store: CheckpointStore, *, rho: float = 0.01,
                 lr: float = 1e-3, full_interval: Optional[int] = None,
                 batch_size: Optional[int] = None,
                 sys_params: Optional[SystemParams] = None,
                 batch_mode: str = "concat", queue_size: int = 4,
                 parallel_recovery: bool = True,
                 error_feedback: bool = True, compressor: str = "topk",
                 flush_timeout: float = 120.0,
                 replay_window: Optional[int] = None,
                 replay_device: bool = False,
                 snapshot_shards: int = 4):
        self.model, self.store = model, store
        self.rho, self.lr = rho, lr
        if compressor == "quant8":
            error_feedback = False
        self.batch_mode = batch_mode
        self.parallel_recovery = parallel_recovery
        #: bound on differentials per parallel-replay scan window (peak
        #: replay memory is O(window * model), not O(chain * model))
        self.replay_window = replay_window
        #: device-resident recovery: replay the chain as a jitted scan
        #: over the *compressed* payloads (fused decompress-and-apply
        #: kernels) instead of host-decoding each differential
        self.replay_device = replay_device
        #: >0: full-state snapshots issue per-shard D2H transfers that
        #: overlap the still-running step; 0: legacy whole-tree batch
        self.snapshot_shards = snapshot_shards
        self.flush_timeout = flush_timeout
        self.tuner = OnlineTuner(sys_params or SystemParams())
        fi, bs = practical_config(self.tuner.p)
        # an explicit (f, b) pins the config; None means "start at the
        # Eq. (10) optimum and let the online tuner keep re-solving it"
        self._auto_full_interval = full_interval is None
        self._auto_batch_size = batch_size is None
        self.full_interval = full_interval or fi
        self.batch_size = batch_size or bs
        self.queue = ReusingQueue(maxsize=queue_size)
        # double-buffered D2H snapshot permits: the full-state snapshot
        # overlaps the next training step; a persist tier more than two
        # snapshots behind backpressures instead of hoarding host copies
        self._arena = SnapshotArena(slots=2)
        self.step_fn = make_train_step(model, mode="lowdiff", rho=rho, lr=lr,
                                       error_feedback=error_feedback,
                                       compressor=compressor)
        self._buffer: List[Any] = []  # [(step, host payload)]
        # consumer thread appends, flush() (caller thread) swaps — the
        # buffer is a cross-thread structure and must be locked
        self._buffer_lock = threading.Lock()
        self._persist_pool = ThreadPoolExecutor(max_workers=2,
                                                thread_name_prefix="persist")
        self._pending: List[Future] = []
        self._consumer: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self._step_counter: Optional[int] = None
        self._processed = 0          # differentials fully handled
        # bounded: one entry per batch flush would leak memory over a
        # multi-million-step per-iteration-checkpointing run
        self._tuning_history: "deque[Dict[str, Any]]" = deque(maxlen=256)
        self.tuning_resolves = 0
        self.tuning_applied = 0
        self.full_saves = 0

    # ------------------------------------------------------------------
    # checkpointing process (background thread)
    # ------------------------------------------------------------------
    def _start_consumer(self):
        if self.queue.error is not None:
            # never restart over a poisoned queue: the failed batch is
            # lost, and persisting later ones would durably write a
            # chain with a hole that recovery cannot detect
            raise CheckpointingError(
                "checkpointing consumer previously failed; differentials "
                "were lost") from self.queue.error
        if self._consumer is None or not self._consumer.is_alive():
            self._stop.clear()
            self._consumer = threading.Thread(
                target=self.queue.drain, args=(self._handle, self._stop),
                daemon=True, name="lowdiff-ckpt")
            self._consumer.start()

    def _handle(self, step: int, cg):
        """Step ①: offload to CPU memory (frees the device buffer)."""
        with trace_span("ckpt.offload", "persist", step=step):
            host_cg = host_copy(cg)
        del cg
        with self._buffer_lock:
            self._buffer.append((step, host_cg))
            full = len(self._buffer) >= self.batch_size
        # Step ②/③: batch then persist in one I/O
        if full:
            self._flush_batch()
        self._processed += 1

    def _flush_batch(self):
        with self._buffer_lock:
            if not self._buffer:
                return
            buf, self._buffer = self._buffer, []
        t0 = time.perf_counter()
        with trace_span("persist.batch", "persist", n=len(buf),
                        first=buf[0][0], last=buf[-1][0]):
            self.store.save_batch(buf[0][0], buf[-1][0],
                                  [p for _, p in buf], mode=self.batch_mode)
        merge_t = (time.perf_counter() - t0) / max(len(buf), 1)
        self.tuner.observe_merge_time(merge_t)
        batch_bytes = _payload_nbytes([p for _, p in buf])
        self._apply_tuning(merge_time_s=merge_t, batch_bytes=batch_bytes)

    def _apply_tuning(self, **inputs):
        """Close the paper's §VII adaptation loop: re-solve Eq. (10)
        with the tuner's updated constants after each batch write and
        apply the new (f, b) to the dimensions the caller left on auto.
        Explicitly pinned dimensions are still recorded, so stats()
        shows what the tuner *would* choose.

        Each history entry carries the *inputs* the decision saw
        (observed stall fraction, merge time, batch bytes) so
        ``stats()["tuning"]`` is auditable — a (f, b) move can be
        traced back to the measurement that caused it. Entries ride
        the same bounded deque as before."""
        stall = TIMELINE.stall_fraction()
        self.tuner.observe_stall_fraction(stall)
        interval, b = self.tuner.current()
        applied = False
        if self._auto_full_interval and interval != self.full_interval:
            self.full_interval = interval
            applied = True
        if self._auto_batch_size and b != self.batch_size:
            self.batch_size = b
            applied = True
        if applied:
            self.tuning_applied += 1
        self.tuning_resolves += 1
        self._tuning_history.append(
            {"step": self._step_counter, "full_interval": interval,
             "batch_size": b, "applied": applied,
             "stall_fraction": round(self.tuner.stall_fraction, 6),
             **{k: (round(v, 6) if isinstance(v, float) else v)
                for k, v in inputs.items()}})

    # ------------------------------------------------------------------
    # training process hooks
    # ------------------------------------------------------------------
    def train_step(self, state, batch):
        if self._step_counter is None:
            self._step_counter = int(state["step"])   # one-time sync
        with trace_span("engine.dispatch", "engine"):
            state, metrics, cg = self.step_fn(state, batch)
        self._step_counter += 1
        step = self._step_counter   # host-side: never forces the device
        with trace_span("engine.queue_put", "engine", step=step):
            self._start_consumer()
            blocked = self.queue.put(step, cg)    # zero-copy hand-off
        TIMELINE.charge("queue_backpressure", blocked)
        if step % self.full_interval == 0:
            # async snapshot: only enqueue the D2H transfers here — the
            # wait for the bytes (and the write) happens on the persist
            # thread, overlapped with the next training step; sharded
            # mode additionally releases each shard's buffers as its
            # bytes land instead of pinning the whole model copy
            with trace_span("snapshot.issue", "snapshot", step=step):
                if self.snapshot_shards > 0:
                    pending = self._arena.snapshot_sharded_async(
                        state, shards=self.snapshot_shards)
                else:
                    pending = self._arena.snapshot_async(state)
            self._pending.append(
                self._persist_pool.submit(self._persist_full, step, pending))
            self.full_saves += 1
        return state, metrics

    def _persist_full(self, step: int, pending):
        try:
            with trace_span("persist.full", "persist", step=step):
                self.store.save_full(step, pending.result())
        finally:
            pending.release()

    def flush(self, timeout: Optional[float] = None):
        """Block until every queued differential/full write is durable
        (including the storage backend's own async tiers) and every
        pending maintenance slice has drained.

        Never hangs: a handler exception on the consumer thread is
        re-raised here as :class:`~repro.core.reusing_queue.
        CheckpointingError`, the queue wait raises once ``timeout``
        (default ``flush_timeout``) passes with no differential handled,
        and the store-level flush — including the maintenance drain —
        is bounded by ``timeout`` as well."""
        t = timeout if timeout is not None else self.flush_timeout
        t0 = time.perf_counter()
        with trace_span("ckpt.flush", "persist"):
            wait_drained(self.queue, lambda: self._processed,
                         self._consumer, t)
            self._flush_batch()
            for f in self._pending:
                f.result()
            self._pending.clear()
            self.store.flush(timeout=t)
        TIMELINE.event("flush_stall", time.perf_counter() - t0,
                       step=self._step_counter)

    def close(self):
        try:
            self.flush()
        finally:
            self._stop.set()
            self.queue.close()
            if self._consumer is not None:
                self._consumer.join(timeout=5)
            self._persist_pool.shutdown(wait=True)
            self.store.close()

    # ------------------------------------------------------------------
    # recovery process
    # ------------------------------------------------------------------
    def recover(self):
        """Returns (state, replayed_steps). Raises if no checkpoint.
        Works against any storage backend — the chain loader delegates
        shard re-assembly / tier lookup to the store's backend."""
        t_rec = time.perf_counter()
        with trace_span("recovery.load_chain", "recovery"):
            state, diffs = rec.load_latest_chain(self.store)
        # LowDiff writes one differential per iteration: cut the chain
        # at the first step gap (a write-back hole) rather than replay
        # across it into silently wrong state
        diffs = rec.contiguous_prefix(int(state["step"]), diffs)
        # the replay cannot start before params and moments have landed
        state["params"], state["opt"] = rec.upload(
            (state["params"], state["opt"]), "recovery.h2d_state", wait=True)
        with trace_span("recovery.replay", "recovery", n=len(diffs),
                        mode=("device" if self.replay_device else
                              "parallel" if self.parallel_recovery
                              else "serial")):
            if self.replay_device:
                params, opt, applied = rec.replay_device(
                    state["params"], state["opt"], diffs, lr=self.lr,
                    window=self.replay_window)
            elif self.parallel_recovery:
                params, opt, applied = rec.replay_parallel(
                    state["params"], state["opt"], diffs, lr=self.lr,
                    window=self.replay_window)
            else:
                params, opt = rec.replay_serial(state["params"],
                                                state["opt"],
                                                diffs, lr=self.lr)
                applied = len(diffs)
        state["params"], state["opt"] = params, opt
        if "ef" in state:
            # issued behind the replay just dispatched; the next step
            # is the first to read it
            state["ef"] = rec.upload(state["ef"], "recovery.h2d_ef",
                                     wait=False)
        TIMELINE.event("recovery", time.perf_counter() - t_rec,
                       step=self._step_counter)
        if applied:
            # a payload that failed to decode cut the chain early; the
            # state is consistent as of the last *applied* differential
            state["step"] = np.asarray(diffs[applied - 1][0], np.int32)
        # NOTE: the error-feedback state stored in the full checkpoint is
        # stale by `len(diffs)` steps; exact-resume tests therefore compare
        # params/opt. (The paper has the same property: EF lives only in
        # the training process.)
        return state, applied

    def stats(self) -> Dict[str, Any]:
        from repro.checkpoint.io import COPY_METER
        return {"queue": self.queue.stats(), "store": self.store.stats(),
                "snapshot_arena": self._arena.stats(),
                "copy_meter": COPY_METER.stats(),
                "replay_device": self.replay_device,
                "snapshot_shards": self.snapshot_shards,
                "full_interval": self.full_interval,
                "batch_size": self.batch_size,
                "tuning": {"auto": {"full_interval": self._auto_full_interval,
                                    "batch_size": self._auto_batch_size},
                           "applied": self.tuning_applied,
                           "resolves": self.tuning_resolves,
                           "history": list(self._tuning_history),
                           "params": dataclasses.asdict(self.tuner.p)},
                "full_saves": self.full_saves,
                "timeline": TIMELINE.stats()}
