"""Exp. 5 (Fig. 15): recovery time — Baseline (full reload) vs Naïve DC
(serial delta merge) vs LowDiff parallel recovery vs LowDiff+(S)
in-memory restore.

Paper claims: LowDiff parallel recovery beats Baseline by 83.2% and Naïve
DC by 55.8% at FCF=10; LowDiff+(S) is 9.4-57.1x faster than Baseline.
"""
from __future__ import annotations

import time

import jax

from benchmarks.common import BATCH, SEQ, bench_model, fresh_store, row, timeit
from repro.core.lowdiff import LowDiff
from repro.core.lowdiff_plus import LowDiffPlus
from repro.core.steps import init_state
from repro.data.synthetic import make_batch


def main(out):
    model = bench_model()
    for n_diffs in (10, 30):
        store = fresh_store(f"/tmp/repro_bench/rec{n_diffs}")
        ld = LowDiff(model, store, rho=0.01, full_interval=10_000,
                     batch_size=2)
        state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
        store.save_full(0, jax.tree.map(lambda x: x, state))
        b = make_batch(model.cfg, SEQ, BATCH)
        for _ in range(n_diffs):
            state, _ = ld.train_step(state, b)
        ld.flush()

        t_base = timeit(lambda: store.load_full(store.latest_full()),
                        iters=3)
        ld.parallel_recovery = False
        t_serial = timeit(lambda: ld.recover(), iters=3)
        ld.parallel_recovery = True
        ld.recover()   # compile the scan kernel once
        t_par = timeit(lambda: ld.recover(), iters=3)
        import math
        depth = math.ceil(math.log2(n_diffs)) + 1
        out(row(f"exp5.n{n_diffs}.full_reload", t_base, "baseline io"))
        out(row(f"exp5.n{n_diffs}.serial_replay", t_serial,
                f"depth={n_diffs} merges"))
        out(row(f"exp5.n{n_diffs}.parallel_replay", t_par,
                f"depth={depth} (log n) wall={t_serial / t_par:.2f}x "
                f"on 1 core"))
        ld.close()

    # LowDiff+ software recovery (from CPU replica)
    store = fresh_store("/tmp/repro_bench/rec_plus")
    ldp = LowDiffPlus(model, store, persist_interval=1000)
    state = init_state(model, jax.random.PRNGKey(1), mode="lowdiff_plus")
    b = make_batch(model.cfg, SEQ, BATCH)
    for _ in range(5):
        state, _ = ldp.train_step(state, b)
    ldp.flush()
    t_mem = timeit(lambda: ldp.recover_software(state), iters=3)
    out(row("exp5.lowdiff_plus_mem_restore", t_mem, "in-memory"))
    ldp.close()


def _compressed_chain(params, n, rho, rng):
    """n synthetic differentials in wire form with numpy leaves — the
    same shape payloads take after a storage round-trip."""
    import numpy as np

    from repro.compression.sparse import compress_tree
    diffs = []
    for i in range(n):
        grads = jax.tree.map(
            lambda p: rng.standard_normal(p.shape).astype(np.float32), params)
        payload = jax.tree.map(np.asarray, compress_tree(grads, rho))
        diffs.append((i + 1, payload))
    return diffs


def main17(out):
    """Exp. 17: device-resident recovery fast path.

    Replay wall-clock vs chain length (16/64/256), host (dense-decode
    parallel scan) vs device (fused decompress-and-apply scan over the
    compressed wire payloads), each against the memory-bandwidth
    roofline; plus the snapshot stall with vs without overlapped
    per-shard D2H."""
    import numpy as np

    from repro.analysis.roofline import replay_roofline
    from repro.checkpoint.io import COPY_METER
    from repro.compression.sparse import tree_nbytes
    from repro.core import recovery as rec
    from repro.core.snapshot import SnapshotArena, host_copy

    model = bench_model()
    state = init_state(model, jax.random.PRNGKey(0), mode="lowdiff")
    params, opt = state["params"], state["opt"]
    rng = np.random.default_rng(0)
    rho = 0.01
    chain = _compressed_chain(params, 256, rho, rng)
    state_bytes = sum(l.nbytes for l in jax.tree.leaves(params)) + \
        sum(l.nbytes for l in jax.tree.leaves((opt.mu, opt.nu)))
    payload_bytes = tree_nbytes(chain[0][1])
    window = 32

    speedup64 = None
    for n in (16, 64, 256):
        diffs = chain[:n]

        def host():
            p, o, k = rec.replay_parallel(params, opt, diffs,
                                          window=window)
            assert k == n
            jax.block_until_ready(jax.tree.leaves(p))

        def device():
            p, o, k = rec.replay_device(params, opt, diffs, window=window)
            assert k == n
            jax.block_until_ready(jax.tree.leaves(p))

        t_host = timeit(host, warmup=1, iters=3)
        t_dev = timeit(device, warmup=1, iters=3)
        roof = replay_roofline(state_bytes, payload_bytes, n,
                               jax.devices()[0])
        if n == 64:
            speedup64 = t_host / t_dev
        out(row(f"exp17.n{n}.host_replay", t_host,
                f"dense H2D={n * state_bytes // 3} bytes"))
        out(row(f"exp17.n{n}.device_replay", t_dev,
                f"host/device={t_host / t_dev:.2f}x "
                f"roofline={roof['min_seconds'] / t_dev:.1%} "
                f"compressed H2D={n * payload_bytes} bytes"))
    out(row("exp17.speedup64", 0.0,
            f"device_vs_host_64={speedup64:.2f}x"))

    # snapshot stall: blocking whole-tree copy vs overlapped per-shard
    # DMA (training-loop-side time only; materialization is the persist
    # thread's problem)
    t_block = timeit(lambda: host_copy(state), warmup=1, iters=3)
    arena = SnapshotArena(slots=2)
    COPY_METER.reset()
    stalls = []
    for _ in range(4):
        t0 = time.perf_counter()
        ps = arena.snapshot_sharded_async(state, shards=8)
        stalls.append(time.perf_counter() - t0)
        ps.result()
        ps.release()
    t_issue = float(np.median(stalls))
    overlap = COPY_METER.d2h_overlap_ratio()
    out(row("exp17.snapshot.blocking", t_block, "whole-tree host_copy"))
    out(row("exp17.snapshot.sharded_issue", t_issue,
            f"stall_ratio={t_issue / t_block:.3f} "
            f"d2h_overlap={overlap if overlap is None else round(overlap, 3)}"))


if __name__ == "__main__":
    main(print)
    main17(print)
