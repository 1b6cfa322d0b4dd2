"""LowDiff+: frequent checkpointing without gradient compression (§VI).

Two mechanisms on top of LowDiff:

* **Layer-wise gradient reusing & snapshotting** (Insight 1): the dense
  gradient pytree is snapshotted leaf-by-leaf by a thread pool — the JAX
  analogue of streaming each layer's bucket as backprop produces it (on
  TPU the D2H DMAs overlap compute; on this CPU container the overlap is
  the thread pool's concurrency). Each leaf is enqueued to the reusing
  queue as soon as its copy lands.

* **CPU-resident model replica + asynchronous persistence** (Insight 2):
  the checkpointing thread maintains a numpy replica of (params, Adam
  moments) and applies the reused gradient with a numpy Adam step — an
  always-up-to-date in-memory checkpoint (Gemini-style). Persistence
  writes the *replica*, never the raw gradients, every
  ``persist_interval`` steps — full+diff fused in host memory, so storage
  traffic is one model state, not a gradient stream.

Recovery: software failures restore from the in-memory replica
(near-instant); hardware failures reload the last persisted replica.

**Incremental-merging persistence** (``persist_mode="incremental"``):
the replica tracks which leaves each Adam apply actually changed, and
every persist after the first writes a *patch blob* holding only those
dirty leaves — storage bytes and host copies per persist are
O(changed bytes), not O(model). An optional ``persist_threshold``
defers near-converged leaves (accumulated relative L∞ drift below the
threshold) so they stop being re-persisted until they move enough to
matter. The checkpoint store journals each patch against its base full
and a background fold (the maintenance service's incremental merger)
pwrites accumulated patches into the base frame in place, so recovery
stays one frame read and the chain never grows unboundedly.
"""
from __future__ import annotations

import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.patchset import RowUpdate, mask_to_intervals
from repro.checkpoint.store import CheckpointStore
from repro.compression.quant_span import (DIFF_QUANTS, QUANT_METER,
                                          QuantSpan, decode_rows,
                                          encode_rows, quant_bits)
from repro.core.reusing_queue import (CheckpointingError, ReusingQueue,
                                      wait_drained)
from repro.core.snapshot import host_copy, start_host_transfer
from repro.core.steps import make_train_step
from repro.obs.timeline import TIMELINE
from repro.obs.trace import trace_span


class _NumpyAdam:
    """Host-side Adam replica (elementwise; matches repro.optim.adam).

    With ``track_dirty`` the replica records, per leaf, whether its
    bytes diverged from the last persisted snapshot — the dirty set the
    incremental-merging persistence engine snapshots instead of the
    whole replica. A leaf whose gradient *and* both moments are all
    zero is provably unchanged by the step (the update is exactly 0)
    and is skipped without touching it; every other applied leaf is
    marked dirty and its accumulated L∞ parameter drift tracked for
    the optional ``--persist-threshold`` filter.

    ``dirty_granularity="row"`` drops the tracked unit from leaves to
    axis-0 rows: a row is provably unchanged when its gradient and both
    pre-update moment rows are all zero (its Adam update is exactly
    0.0), so a sparse step — one routed expert's rows of a big MoE
    table — dirties only those rows. Per-row drift carries the
    ``--persist-threshold`` semantics at row granularity, and adjacent
    dirty runs separated by up to ``coalesce_rows`` *clean* rows merge
    into one span before snapshot (re-writing a clean row is a
    byte-identical no-op, so bridging trades a few redundant bytes for
    far fewer spans; a dirty-but-deferred row is never bridged over).
    Scalar and single-row leaves keep leaf granularity.

    ``diff_quant`` ("int8"/"int4") additionally quantizes each persisted
    row span against per-row absmax scales
    (:class:`~repro.compression.quant_span.QuantSpan` payloads instead
    of raw :class:`RowUpdate`), holding a per-row **error-feedback
    residual** per component: the next quantization of a row encodes
    ``value + residual``, so deferred quantization error is corrected
    on the next persist instead of silently drifting. With a persist
    threshold active, a row whose residual exceeds the threshold is
    immediately re-marked dirty (at most once per quantized persist —
    a re-marked row that re-persists without a fresh gradient is not
    re-marked again, so a static row cannot ping-pong forever)."""

    GRANULARITIES = ("leaf", "row")

    def __init__(self, params, mu, nu, count, *, lr, b1=0.9, b2=0.999,
                 eps=1e-8, track_dirty: bool = False,
                 dirty_granularity: str = "leaf", coalesce_rows: int = 4,
                 diff_quant: str = "off"):
        if dirty_granularity not in self.GRANULARITIES:
            raise ValueError(f"dirty_granularity must be one of "
                             f"{self.GRANULARITIES}")
        if diff_quant not in DIFF_QUANTS:
            raise ValueError(f"diff_quant must be one of {DIFF_QUANTS}")
        self.params = {k: np.array(v, np.float32) if v.dtype != np.float32
                       else np.array(v) for k, v in params.items()}
        self.dtypes = {k: v.dtype for k, v in params.items()}
        self.mu = {k: np.array(v) for k, v in mu.items()}
        self.nu = {k: np.array(v) for k, v in nu.items()}
        self.count = int(count)
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps
        self.track_dirty = track_dirty
        self.dirty_granularity = dirty_granularity
        self.coalesce_rows = int(coalesce_rows)
        #: leaves whose replica bytes differ from the last snapshot
        self._dirty = set(self.params)
        #: accumulated L∞ parameter change since the leaf last persisted
        self._drift = {k: 0.0 for k in self.params}
        #: row-granular leaves: per-row dirty mask + drift (everything
        #: starts dirty, like the leaf-level set — nothing is persisted
        #: yet)
        self._row_dirty: Dict[str, np.ndarray] = {}
        self._row_drift: Dict[str, np.ndarray] = {}
        self.diff_quant = diff_quant
        #: per-(component, leaf) error-feedback residuals (f32, lazily
        #: allocated on a leaf's first quantized persist)
        self._row_resid: Dict[tuple, np.ndarray] = {}
        #: rows dirty *only* because quantization error re-marked them —
        #: they get one corrective persist, not an endless loop
        self._row_qpending: Dict[str, np.ndarray] = {}
        if track_dirty and dirty_granularity == "row":
            for k, v in self.params.items():
                if v.ndim >= 1 and v.shape[0] > 1:
                    self._row_dirty[k] = np.ones(v.shape[0], bool)
                    self._row_drift[k] = np.zeros(v.shape[0], np.float32)
                    if diff_quant != "off":
                        self._row_qpending[k] = np.zeros(v.shape[0], bool)
        self.skipped_applies = 0

    def _resid(self, comp: str, k: str, like: np.ndarray) -> np.ndarray:
        key = (comp, k)
        r = self._row_resid.get(key)
        if r is None:
            r = np.zeros(like.shape, np.float32)
            self._row_resid[key] = r
        return r

    @staticmethod
    def _row_any(a: np.ndarray) -> np.ndarray:
        """Per-row nonzero mask (bool, shape (rows,))."""
        return a.reshape(a.shape[0], -1).any(axis=1)

    def apply(self, grads: Dict[str, np.ndarray]):
        self.count += 1
        c1 = 1.0 - self.b1 ** self.count
        c2 = 1.0 - self.b2 ** self.count
        for k, g in grads.items():
            g = np.asarray(g, np.float32)
            mu = self.mu[k]
            nu = self.nu[k]
            if self.track_dirty and not (g.any() or mu.any() or nu.any()):
                # zero gradient onto zero moments: the update is exactly
                # zero and the moments stay zero — the leaf provably
                # does not change, so neither math nor dirty-marking runs
                self.skipped_applies += 1
                continue
            rd = self._row_dirty.get(k) if self.track_dirty else None
            if rd is not None:
                # pre-update mask: a row changes iff its gradient or a
                # pre-update moment row is nonzero (same proof as the
                # leaf-level skip, per row)
                changed = (self._row_any(g) | self._row_any(mu)
                           | self._row_any(nu))
            mu *= self.b1
            mu += (1 - self.b1) * g
            nu *= self.b2
            nu += (1 - self.b2) * g * g
            upd = self.lr * (mu / c1) / (np.sqrt(nu / c2) + self.eps)
            self.params[k] -= upd
            if self.track_dirty:
                self._dirty.add(k)
                if upd.size:
                    self._drift[k] += float(np.max(np.abs(upd)))
                if rd is not None:
                    rd |= changed
                    qp = self._row_qpending.get(k)
                    if qp is not None:
                        # a fresh gradient supersedes a pending
                        # quantization correction: the row is again
                        # eligible for an error-feedback re-mark
                        qp[changed] = False
                    if upd.size:
                        rowmax = np.abs(
                            upd.reshape(upd.shape[0], -1)).max(axis=1)
                        dr = self._row_drift[k]
                        dr[changed] += rowmax[changed].astype(np.float32)

    def state(self):
        return {"params": dict(self.params), "mu": dict(self.mu),
                "nu": dict(self.nu), "count": self.count}

    # -- persistence snapshots (caller holds the replica lock) ---------
    def snapshot_full(self):
        """Copy every leaf for a full persist; the whole replica is
        the persisted state, so all leaves become clean."""
        snap = {"params": {k: np.array(v) for k, v in self.params.items()},
                "mu": {k: np.array(v) for k, v in self.mu.items()},
                "nu": {k: np.array(v) for k, v in self.nu.items()},
                "count": np.array(self.count, np.int64)}
        if self.track_dirty:
            self._dirty.clear()
            self._drift = {k: 0.0 for k in self._drift}
            for k in self._row_dirty:
                self._row_dirty[k][:] = False
                self._row_drift[k][:] = 0.0
            # a raw full persists exact bytes: no deferred quant error
            for r in self._row_resid.values():
                r[:] = 0.0
            for qp in self._row_qpending.values():
                qp[:] = False
        return snap

    def snapshot_dirty(self, threshold: float = 0.0):
        """Copy only the dirty leaves — or, at row granularity, only
        each dirty leaf's dirty row spans as :class:`RowUpdate` values —
        plus the always-advancing Adam count, for an incremental
        persist. With ``threshold`` > 0 a dirty leaf (or row) whose
        accumulated relative L∞ drift is still below ``threshold``
        (scaled by the leaf's max |param|) is *deferred*: it stays
        dirty and its drift keeps accumulating, so near-converged state
        stops being re-persisted until it has moved enough to matter.
        Returns ``(partial state dict, deferred leaf count)`` — a
        row-granular leaf counts deferred only when *none* of its dirty
        rows passed the threshold."""
        updates = {"params": {}, "mu": {}, "nu": {},
                   "count": np.array(self.count, np.int64)}
        deferred = 0
        for k in sorted(self._dirty):
            rd = self._row_dirty.get(k)
            if rd is None:
                # leaf granularity (or a scalar / single-row leaf)
                if threshold > 0.0:
                    p = self.params[k]
                    scale = float(np.max(np.abs(p))) if p.size else 0.0
                    if self._drift[k] <= threshold * (scale + 1e-12):
                        deferred += 1
                        continue
                updates["params"][k] = np.array(self.params[k])
                updates["mu"][k] = np.array(self.mu[k])
                updates["nu"][k] = np.array(self.nu[k])
                self._dirty.discard(k)
                self._drift[k] = 0.0
                continue
            dr = self._row_drift[k]
            if threshold > 0.0:
                p = self.params[k]
                scale = float(np.max(np.abs(p))) if p.size else 0.0
                persist = rd & (dr > threshold * (scale + 1e-12))
            else:
                persist = rd.copy()
            if not persist.any():
                deferred += 1
                continue
            # bridge only across *clean* rows: a deferred dirty row's
            # replica bytes differ from its persisted bytes, so writing
            # it would defeat the deferral — a clean row re-writes to
            # identical bytes
            ivs = mask_to_intervals(persist, bridgeable=~rd,
                                    max_gap=self.coalesce_rows)
            rows = int(rd.shape[0])
            if self.diff_quant == "off":
                for comp, src in (("params", self.params),
                                  ("mu", self.mu), ("nu", self.nu)):
                    a = src[k]
                    if len(ivs) == 1 and ivs[0] == (0, rows):
                        # every row persists: plain whole-leaf update
                        # (same blob shape leaf granularity writes)
                        updates[comp][k] = np.array(a)
                    else:
                        updates[comp][k] = RowUpdate(
                            starts=np.asarray([s for s, _ in ivs],
                                              np.int64),
                            rows=[np.array(a[s:e]) for s, e in ivs],
                            shape=tuple(a.shape))
                rd[persist] = False
                dr[persist] = 0.0
            else:
                self._snapshot_quant(k, ivs, updates)
                rd[persist] = False
                # error feedback: the persisted rows now carry their
                # quantization error as drift — below any threshold it
                # just waits for the next real update to fold in, above
                # it the row is re-marked dirty for one corrective pass
                pres = self._row_resid[("params", k)]
                qerr = np.abs(pres.reshape(rows, -1)).max(axis=1) \
                    .astype(np.float32)
                dr[persist] = qerr[persist]
                if threshold > 0.0:
                    p = self.params[k]
                    scale = float(np.max(np.abs(p))) if p.size else 0.0
                    qp = self._row_qpending[k]
                    redo = (persist & (qerr > threshold * (scale + 1e-12))
                            & ~qp)
                    qp[persist] = False
                    qp[redo] = True
                    rd[redo] = True
            if rd.any():
                self._drift[k] = float(dr[rd].max())
            else:
                self._dirty.discard(k)
                self._drift[k] = 0.0
        return updates, deferred

    def _snapshot_quant(self, k: str, ivs, updates) -> None:
        """Emit one leaf's persisting intervals as
        :class:`~repro.compression.quant_span.QuantSpan` payloads,
        folding each component's error-feedback residual into the values
        being quantized and storing the fresh residual back.

        The Adam moments floor at 8 bits even under ``int4``: the
        update divides ``mu`` by ``sqrt(nu)``, so per-row quantization
        error in the moments is amplified by ``1/sqrt(nu)`` at small-
        moment elements — 4-bit moments make a resumed run take a huge
        first step and diverge, while 4-bit params + 8-bit moments
        resume within noise of raw (and still cut the patch stream
        >4x)."""
        pbits = quant_bits(self.diff_quant)
        t0 = time.perf_counter()
        bytes_in = bytes_out = 0
        starts = tuple(int(s) for s, _ in ivs)
        for comp, src in (("params", self.params), ("mu", self.mu),
                          ("nu", self.nu)):
            bits = pbits if comp == "params" else max(pbits, 8)
            a = src[k]
            res = self._resid(comp, k, a)
            qs, scales = [], []
            for s, e in ivs:
                corrected = a[s:e].astype(np.float32) + res[s:e]
                q, sc = encode_rows(corrected, bits)
                c2 = corrected.reshape(e - s, -1)
                deq = decode_rows(q, sc, c2.shape[1], bits)
                res[s:e] = (c2 - deq).reshape(corrected.shape)
                qs.append(q)
                scales.append(sc)
                bytes_in += int(a[s:e].nbytes)
            span = QuantSpan(starts=starts, qs=qs, scales=scales,
                             shape=tuple(a.shape), bits=bits,
                             dtype=np.dtype(a.dtype).name)
            bytes_out += span.nbytes
            updates[comp][k] = span
        QUANT_METER.add_encode(time.perf_counter() - t0, bytes_in,
                               bytes_out)

    def remark_dirty(self, updates) -> None:
        """Undo a snapshot's clean-marking after its persist *failed*:
        the leaves (or row spans) it carried never became durable, so
        they must ride the next persist or every later recovery
        silently restores stale values for them. Infinite drift defeats
        any threshold."""
        for k, v in updates.get("params", {}).items():
            self._dirty.add(k)
            self._drift[k] = float("inf")
            rd = self._row_dirty.get(k)
            if rd is None:
                continue
            dr = self._row_drift[k]
            if isinstance(v, (RowUpdate, QuantSpan)):
                extents = v.extents()
            else:
                extents = [(0, rd.shape[0])]
            for s, e in extents:
                rd[s:e] = True
                dr[s:e] = np.inf
                for comp in ("params", "mu", "nu"):
                    # the residual was computed against a snapshot that
                    # never landed — stale correction must not leak into
                    # the next quantization of these rows
                    res = self._row_resid.get((comp, k))
                    if res is not None:
                        res[s:e] = 0.0
                qp = self._row_qpending.get(k)
                if qp is not None:
                    qp[s:e] = False


def fold_due(since_fold: int, fold_interval: int, amplification: float,
             fold_amplification: float) -> bool:
    """Fold-trigger policy: adaptive on observed chain-read
    amplification (chain overlay bytes / base frame bytes crossing
    ``fold_amplification``), with the fixed patch count
    ``fold_interval`` as a cap. ``fold_interval == 0`` keeps its
    historical meaning — never fold — and ``fold_amplification <= 0``
    disables the adaptive trigger."""
    if not fold_interval:
        return False
    return (since_fold >= fold_interval
            or (fold_amplification > 0
                and amplification >= fold_amplification))


def _flatten(tree):
    """path-keyed flat dict of leaves."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(k): v for k, v in flat}


def _unflatten_like(tree, flat):
    leaves, treedef = jax.tree.flatten(tree)
    keys = [jax.tree_util.keystr(k)
            for k, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]
    return jax.tree.unflatten(treedef, [flat[k] for k in keys])


class LowDiffPlus:
    name = "lowdiff_plus"

    PERSIST_MODES = ("full", "incremental")

    def __init__(self, model, store: CheckpointStore, *, lr: float = 1e-3,
                 persist_interval: int = 1, snapshot_workers: int = 4,
                 queue_size: int = 8, flush_timeout: float = 120.0,
                 persist_mode: str = "full",
                 persist_threshold: float = 0.0, fold_interval: int = 16,
                 dirty_granularity: str = "leaf",
                 fold_amplification: float = 1.5,
                 diff_quant: str = "off"):
        if persist_mode not in self.PERSIST_MODES:
            raise ValueError(f"persist_mode must be one of "
                             f"{self.PERSIST_MODES}")
        if dirty_granularity not in _NumpyAdam.GRANULARITIES:
            raise ValueError(f"dirty_granularity must be one of "
                             f"{_NumpyAdam.GRANULARITIES}")
        if diff_quant not in DIFF_QUANTS:
            raise ValueError(f"diff_quant must be one of {DIFF_QUANTS}")
        if diff_quant != "off" and (persist_mode != "incremental"
                                    or dirty_granularity != "row"):
            raise ValueError(
                "--diff-quant quantizes row-span differentials: it "
                "requires --persist-mode incremental and "
                "--dirty-granularity row")
        if (persist_mode == "incremental" and store is not None
                and getattr(store.backend, "fmt", "npz") == "npz"):
            raise ValueError(
                "--persist-mode incremental patches checkpoint leaves "
                "in place, which requires the frame format; this store "
                "writes npz — use --format frame or --persist-mode full")
        self.model, self.store, self.lr = model, store, lr
        self.persist_interval = persist_interval
        self.flush_timeout = flush_timeout
        self.persist_mode = persist_mode
        self.persist_threshold = float(persist_threshold)
        #: schedule a background fold after this many patches (0 = never)
        self.fold_interval = int(fold_interval)
        self.dirty_granularity = dirty_granularity
        self.diff_quant = diff_quant
        #: adaptive fold trigger: fold when chain overlay bytes / base
        #: frame bytes crosses this (<= 0 disables; fold_interval caps)
        self.fold_amplification = float(fold_amplification)
        self.step_fn = make_train_step(model, mode="lowdiff_plus", lr=lr)
        self.queue = ReusingQueue(maxsize=queue_size)
        self._snap_pool = ThreadPoolExecutor(max_workers=snapshot_workers,
                                             thread_name_prefix="snapshot")
        self._persist_pool = ThreadPoolExecutor(max_workers=1,
                                                thread_name_prefix="persist")
        self._replica: Optional[_NumpyAdam] = None
        self._replica_lock = threading.Lock()
        self._consumer: Optional[threading.Thread] = None
        self._stop = threading.Event()
        # _handle appends on the consumer thread while flush() iterates
        # and clears on the caller thread — must be locked
        self._pending = []
        self._pending_lock = threading.Lock()
        self._processed = 0
        self.persists = 0
        self.patch_persists = 0
        self.leaves_deferred = 0
        self.adaptive_folds = 0
        # incremental-persist chain state: only ever touched on the
        # consumer / persist threads (single-threaded each, FIFO between)
        self._base_step: Optional[int] = None
        self._since_fold = 0

    # ------------------------------------------------------------------
    def attach(self, state):
        """Initialize the CPU replica from the live state (deepcopy)."""
        params = _flatten(state["params"])
        mu = _flatten(state["opt"].mu)
        nu = _flatten(state["opt"].nu)
        self._replica = _NumpyAdam(
            host_copy(params), host_copy(mu), host_copy(nu),
            int(state["opt"].count), lr=self.lr,
            track_dirty=(self.persist_mode == "incremental"),
            dirty_granularity=self.dirty_granularity,
            diff_quant=self.diff_quant)
        self._replica_step = int(state["step"])
        self._base_step = None

    def _start_consumer(self):
        if self.queue.error is not None:
            # a lost gradient means the replica is stale forever after:
            # fail fast instead of resuming the apply stream over a hole
            raise CheckpointingError(
                "checkpointing consumer previously failed; the CPU "
                "replica is missing gradients") from self.queue.error
        if self._consumer is None or not self._consumer.is_alive():
            self._stop.clear()
            self._consumer = threading.Thread(
                target=self.queue.drain, args=(self._handle, self._stop),
                daemon=True, name="lowdiffplus-ckpt")
            self._consumer.start()

    # ------------------------------------------------------------------
    def train_step(self, state, batch):
        if self._replica is None:
            self.attach(state)
            self._step_counter = int(state["step"])
        state, metrics, grads = self.step_fn(state, batch)
        self._step_counter += 1
        step = self._step_counter   # host-side: never forces the device
        self._start_consumer()
        flat = _flatten(grads)
        # layer-wise snapshot: enqueue every leaf's non-blocking D2H
        # transfer first (they all run concurrently with the next step),
        # then let the pool materialize each leaf as its bytes land
        start_host_transfer(flat)
        futures = {k: self._snap_pool.submit(np.asarray, v)
                   for k, v in flat.items()}
        blocked = self.queue.put(step, futures)
        TIMELINE.charge("queue_backpressure", blocked)
        return state, metrics

    def _handle(self, step: int, futures):
        with trace_span("ckpt.offload", "persist", step=step):
            grads = {k: f.result() for k, f in futures.items()}
        with self._replica_lock, \
                trace_span("replica.apply", "persist", step=step):
            self._replica.apply(grads)        # in-memory checkpoint update
            self._replica_step = step
        if step % self.persist_interval == 0:
            # snapshot under the lock (a concurrent recover_software
            # must never see a half-copied persist image) but submit
            # outside it — the lock is held only for the copy, and in
            # incremental mode the copy is only the *dirty* leaves, not
            # an O(model) deep copy of the whole replica
            incremental = (self.persist_mode == "incremental"
                           and self._base_step is not None)
            with self._replica_lock:
                if incremental:
                    updates, deferred = self._replica.snapshot_dirty(
                        self.persist_threshold)
                    self.leaves_deferred += deferred
                    snap = ("patch", self._base_step, updates)
                else:
                    snap = ("full", None, self._replica.snapshot_full())
            if snap[0] == "full" and self.persist_mode == "incremental":
                self._base_step = step      # later persists chain on it
            with self._pending_lock:
                self._pending.append(
                    self._persist_pool.submit(self._persist, step, snap))
        self._processed += 1

    def _persist(self, step: int, snap):
        kind, base_step, payload = snap
        with trace_span(f"persist.{kind}", "persist", step=step):
            return self._persist_impl(step, kind, base_step, payload)

    def _persist_impl(self, step: int, kind, base_step, payload):
        if kind == "full":
            self.store.save_full(
                step, payload,
                record_names=(self.persist_mode == "incremental"))
        else:
            try:
                self.store.save_patch(step, f"full_{base_step:08d}", payload)
            except BaseException:
                # the dirty bits were cleared at snapshot time; a lost
                # patch must re-dirty its leaves or no later patch ever
                # carries them again (an invisible, permanent hole)
                with self._replica_lock:
                    self._replica.remark_dirty(payload)
                raise
            self.patch_persists += 1
            self._since_fold += 1
            amp = self.store.chain_amplification()
            if fold_due(self._since_fold, self.fold_interval, amp,
                        self.fold_amplification):
                # bound the patch chain: fold it into the base frame off
                # the hot path (maintenance service when attached)
                if self._since_fold < self.fold_interval:
                    self.adaptive_folds += 1   # amplification fired first
                self._since_fold = 0
                self.store.request_fold()
        self.persists += 1

    def flush(self, timeout: Optional[float] = None):
        """Block until every enqueued gradient is applied to the replica
        and every scheduled persist (plus any pending maintenance
        slice) is durable. Never hangs: consumer failures re-raise here,
        the queue wait raises once ``timeout`` passes with no gradient
        applied, and the store's maintenance drain is bounded by
        ``timeout`` too."""
        t = timeout if timeout is not None else self.flush_timeout
        t0 = time.perf_counter()
        with trace_span("ckpt.flush", "persist"):
            wait_drained(self.queue, lambda: self._processed,
                         self._consumer, t)
            with self._pending_lock:
                pending = list(self._pending)
            for f in pending:
                f.result()              # a failure keeps the rest pending
            with self._pending_lock:
                # _handle only ever appends, so the futures just waited
                # on are exactly the list's prefix: drain it by index —
                # O(n) total — instead of the old O(n²) membership
                # re-scan
                del self._pending[:len(pending)]
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
            self._snap_pool.shutdown(wait=True)
            self._persist_pool.shutdown(wait=True)
            self.store.close()

    # ------------------------------------------------------------------
    def recover_software(self, template_state):
        """Software failure: training process dies, checkpointing process
        (and its CPU replica) survives — restore from memory."""
        t_rec = time.perf_counter()
        with self._replica_lock, \
                trace_span("recovery.software", "recovery"):
            rep = self._replica.state()
        TIMELINE.event("recovery", time.perf_counter() - t_rec,
                       step=self._step_counter)
        dtypes = {k: np.asarray(v).dtype
                  for k, v in _flatten(template_state["params"]).items()}
        params = _unflatten_like(
            template_state["params"],
            {k: np.asarray(rep["params"][k]).astype(dtypes[k])
             for k in dtypes})
        opt = template_state["opt"]
        opt = type(opt)(_unflatten_like(opt.mu, rep["mu"]),
                        _unflatten_like(opt.nu, rep["nu"]),
                        np.asarray(rep["count"], np.int32))
        return {"params": params, "opt": opt,
                "step": np.asarray(self._replica_step, np.int32)}

    def recover_hardware(self, template_state):
        """Hardware failure: reload the last persisted replica — the
        latest full overlaid with its committed patch chain when
        persisting incrementally (one frame read once the background
        fold has consolidated it)."""
        t_rec = time.perf_counter()
        try:
            with trace_span("recovery.hardware", "recovery"):
                blob, step = self.store.load_latest_state()
        except FileNotFoundError:
            raise FileNotFoundError("no persisted checkpoint")
        TIMELINE.event("recovery", time.perf_counter() - t_rec,
                       step=self._step_counter)
        dtypes = {k: np.asarray(v).dtype
                  for k, v in _flatten(template_state["params"]).items()}
        params = _unflatten_like(
            template_state["params"],
            {k: np.asarray(blob["params"][k]).astype(dtypes[k])
             for k in dtypes})
        opt = template_state["opt"]
        opt = type(opt)(_unflatten_like(opt.mu, blob["mu"]),
                        _unflatten_like(opt.nu, blob["nu"]),
                        np.asarray(blob["count"], np.int32))
        return {"params": params, "opt": opt,
                "step": np.asarray(step, np.int32)}

    def stats(self):
        return {"queue": self.queue.stats(), "store": self.store.stats(),
                "persists": self.persists,
                "persist_mode": self.persist_mode,
                "dirty_granularity": self.dirty_granularity,
                "diff_quant": self.diff_quant,
                "quant": QUANT_METER.stats(),
                "patch_persists": self.patch_persists,
                "leaves_deferred": self.leaves_deferred,
                "fold_amplification": self.fold_amplification,
                "chain_amplification": self.store.chain_amplification(),
                "max_amplification": self.store.max_amplification,
                "adaptive_folds": self.adaptive_folds,
                "apply_leaves_skipped": (self._replica.skipped_applies
                                         if self._replica is not None
                                         else 0),
                "timeline": TIMELINE.stats()}
