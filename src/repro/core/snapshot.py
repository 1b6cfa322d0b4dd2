"""Overlapped device-to-host snapshots (§V-B step ① without the stall).

The seed's ``host_copy`` walked the pytree calling ``np.asarray`` leaf
by leaf — each call blocks the caller until that leaf's D2H transfer
finishes, serializing the transfers *and* charging them to the training
loop. This module replaces it with the two-phase pattern:

1. **start** — issue ``copy_to_host_async()`` on every jax leaf. This
   only enqueues DMA descriptors; on TPU the transfers run out of a
   pinned staging area while the next training step computes.
2. **materialize** — ``np.asarray`` each leaf *later* (on the persist /
   consumer thread), which merely waits for the already-running
   transfers and hands back the landed host buffer. The D2H transfer is
   the single host-side copy of the tensor bytes; the frame serializer
   streams those same buffers to storage with no further copies.

:class:`SnapshotArena` adds double-buffering semantics on top: at most
``slots`` (default 2) snapshots may be in flight, so a slow persist
tier exerts backpressure on the training loop instead of accumulating
unbounded host copies of the model state — the JAX adaptation of a
fixed pinned-arena design (the runtime owns the actual pinned staging
memory; the arena owns the lifetime and the bound).
"""
from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from repro.checkpoint.io import COPY_METER


def _issue_d2h(leaf) -> None:
    """Enqueue one leaf's D2H transfer. Host leaves need none; a jax
    array that this process cannot address whole (a shard living on
    another host) raises — a snapshot that skipped it would persist a
    checkpoint with a hole."""
    if not isinstance(leaf, jax.Array):
        return
    if not leaf.is_fully_addressable:
        raise ValueError(
            f"cannot snapshot a {leaf.shape} array that is not fully "
            f"addressable from this process (sharding {leaf.sharding})")
    leaf.copy_to_host_async()


def start_host_transfer(tree) -> Any:
    """Phase 1: enqueue non-blocking D2H transfers for every jax leaf.
    Returns the tree unchanged (transfers run in the background)."""
    for leaf in jax.tree.leaves(tree):
        _issue_d2h(leaf)
    return tree


def materialize(tree):
    """Phase 2: wait for the transfers and return a numpy-leaf tree.
    Counts the D2H bytes as the one metered host copy."""
    out = jax.tree.map(np.asarray, tree)
    COPY_METER.add(sum(a.nbytes for a in jax.tree.leaves(out)
                       if isinstance(a, np.ndarray)))
    return out


def host_copy(tree):
    """Batched synchronous snapshot: start *all* transfers first, then
    gather — the transfers overlap each other even though the caller
    still blocks until the last one lands. Drop-in replacement for the
    seed's per-leaf ``np.asarray`` walk."""
    return materialize(start_host_transfer(tree))


class PendingSnapshot:
    """A snapshot whose D2H transfers have been issued but not awaited.

    ``result()`` (any thread) materializes the host tree — the first
    caller pays only the residual transfer wait, later callers get the
    cached tree. ``release()`` frees the arena slot and drops the
    buffer references; call it once the snapshot has been persisted.
    """

    def __init__(self, tree, arena: Optional["SnapshotArena"] = None):
        self._tree = start_host_transfer(tree)
        self._arena = arena
        self._host: Any = None
        self._done = False
        self._lock = threading.Lock()

    def result(self):
        with self._lock:
            if not self._done:
                self._host = materialize(self._tree)
                self._tree = None          # device refs no longer needed
                self._done = True
            return self._host

    def release(self) -> None:
        with self._lock:
            self._tree = None
            self._host = None
            self._done = True
        if self._arena is not None:
            self._arena._release()
            self._arena = None

    def __enter__(self):
        return self.result()

    def __exit__(self, *exc):
        self.release()


def _partition_leaves(nbytes: List[int], shards: int) -> List[List[int]]:
    """Split leaf positions into up to ``shards`` contiguous groups of
    roughly equal bytes (contiguity preserves the producer's layer
    order, so shard 0 holds the leaves the backward pass finishes
    first and its D2H can start while later layers still compute)."""
    if not nbytes:
        return []
    shards = max(1, min(int(shards), len(nbytes)))
    weights = nbytes if sum(nbytes) else [1] * len(nbytes)
    total = sum(weights)
    groups: List[List[int]] = [[]]
    acc = 0
    for i, w in enumerate(weights):
        if (groups[-1] and len(groups) < shards
                and acc >= total * len(groups) / shards):
            groups.append([])
        groups[-1].append(i)
        acc += w
    return groups


class ShardedPendingSnapshot:
    """Per-shard overlapped D2H snapshot (§V-B step ① at DMA grain).

    The tree's leaves are partitioned into contiguous byte-balanced
    shards and each shard's ``copy_to_host_async`` descriptors are
    enqueued immediately at construction — on TPU the transfers drain
    behind the still-running step (issue order matches the backward
    pass's layer order, so a shard's DMA starts as soon as its grads
    are available rather than after the whole post-step batch).

    ``result()`` (persist thread) then materializes shard by shard and
    *releases each shard's device references as soon as its bytes
    land* — the donation analogue: the runtime can reuse a shard's
    staging memory while later shards are still in flight, instead of
    the whole model's worth of buffers pinning until the last leaf.
    The residual block time per shard vs the issue-to-landed window is
    reported to :data:`COPY_METER` as the measured overlap ratio.
    """

    def __init__(self, tree, shards: int = 4,
                 arena: Optional["SnapshotArena"] = None):
        self._leaves, self._treedef = jax.tree.flatten(tree)
        sizes = [getattr(l, "nbytes", 0) or 0 for l in self._leaves]
        self._groups = _partition_leaves(sizes, shards)
        self._arena = arena
        self._host: Any = None
        self._done = False
        self._lock = threading.Lock()
        self._issued_at = time.perf_counter()
        for group in self._groups:      # chunked issue, shard order
            for i in group:
                _issue_d2h(self._leaves[i])

    @property
    def shards(self) -> int:
        return len(self._groups)

    def result(self):
        from repro.obs.trace import trace_span
        with self._lock, trace_span("snapshot.d2h", "snapshot",
                                    shards=len(self._groups)) as sp:
            if self._done:
                return self._host
            host: List[Any] = list(self._leaves)
            wait = 0.0
            nbytes = 0
            for group in self._groups:
                t0 = time.perf_counter()
                for i in group:
                    host[i] = np.asarray(self._leaves[i])
                    self._leaves[i] = None     # early release: the
                    # shard's device/staging buffers free while later
                    # shards are still transferring
                    if isinstance(host[i], np.ndarray):
                        nbytes += host[i].nbytes
                wait += time.perf_counter() - t0
            span = time.perf_counter() - self._issued_at
            COPY_METER.add(nbytes)             # the one metered host copy
            COPY_METER.add_d2h(nbytes, wait_s=wait, span_s=span)
            sp.set(bytes=nbytes, wait_ms=round(wait * 1e3, 3))
            self._host = jax.tree.unflatten(self._treedef, host)
            self._leaves = []
            self._done = True
            return self._host

    def release(self) -> None:
        with self._lock:
            self._leaves = []
            self._host = None
            self._done = True
        if self._arena is not None:
            self._arena._release()
            self._arena = None

    def __enter__(self):
        return self.result()

    def __exit__(self, *exc):
        self.release()


class SnapshotArena:
    """Double-buffered snapshot permits.

    ``snapshot_async(tree)`` issues the async transfers and returns a
    :class:`PendingSnapshot`; it blocks only when ``slots`` snapshots
    are already in flight (persist tier behind by two full states) —
    bounded memory, no unbounded queue of model copies.
    ``snapshot_sharded_async`` is the per-shard variant: same permit
    semantics, but the transfers issue and land shard by shard so the
    D2H overlaps the still-running step and buffers release early.
    """

    #: stats() keys, synced against the instrument set by
    #: tests/test_observability.py (``slots`` is config, not a metric)
    KEYS = ("snapshots", "stalls")

    def __init__(self, slots: int = 2):
        from repro.obs.metrics import InstrumentSet
        if slots < 1:
            raise ValueError("slots must be >= 1")
        self.slots = slots
        self._sem = threading.Semaphore(slots)
        self._inst = InstrumentSet("snapshot_arena")
        self._snapshots = self._inst.counter("snapshots")
        self._stalls = self._inst.counter("stalls")
        self._stall_time = self._inst.histogram("stall_time_s")

    # legacy attribute surface
    @property
    def snapshots(self) -> int:
        return int(self._snapshots.value)

    @property
    def stalls(self) -> int:
        return int(self._stalls.value)

    def _acquire(self) -> float:
        """Acquire a permit; returns the seconds the caller blocked so
        the producer can charge snapshot-stall attribution."""
        stalled = 0.0
        if not self._sem.acquire(blocking=False):
            from repro.obs.timeline import TIMELINE
            from repro.obs.trace import trace_span
            self._stalls.add(1)
            t0 = time.perf_counter()
            with trace_span("snapshot.permit_wait", "snapshot"):
                self._sem.acquire()
            stalled = time.perf_counter() - t0
            self._stall_time.observe(stalled)
            TIMELINE.charge("snapshot_stall", stalled)
        self._snapshots.add(1)
        return stalled

    def snapshot_async(self, tree) -> PendingSnapshot:
        self._acquire()
        return PendingSnapshot(tree, arena=self)

    def snapshot_sharded_async(self, tree,
                               shards: int = 4) -> ShardedPendingSnapshot:
        self._acquire()
        return ShardedPendingSnapshot(tree, shards=shards, arena=self)

    def _release(self) -> None:
        self._sem.release()

    def instruments(self):
        """The backing :class:`~repro.obs.metrics.InstrumentSet`."""
        return self._inst

    def stats(self) -> Dict[str, int]:
        return {"slots": self.slots, "snapshots": self.snapshots,
                "stalls": self.stalls}
