"""End-to-end training driver with pluggable checkpointing strategies.

Runs a real training loop (synthetic data, native Adam) with LowDiff /
LowDiff+ / baselines attached, reports per-strategy overhead vs the
no-checkpoint bound, and supports failure injection + recovery.

All flags map through :class:`repro.core.engine.EngineConfig` (engine
knobs) and :class:`repro.checkpoint.config.StoreConfig` (the tier
stack) — ``EngineConfig.from_args`` owns the flag→config translation
in one place, and ``tests/test_flag_config_sync.py`` fails if a flag
and its config field drift apart.

``--reduced`` is the CPU cut used by the tests; on a chip the model
keeps its published widths and ``--layers N`` cuts only its depth.

Examples::

    PYTHONPATH=src python -m repro.launch.train --arch gpt2-l --layers 8 \
        --batch 4 --seq 1024 --steps 20 --strategy lowdiff
    PYTHONPATH=src python -m repro.launch.train --arch gpt2-l --reduced \
        --steps 50 --strategy lowdiff --ckpt-dir /tmp/ck
    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
        --steps 30 --strategy lowdiff_plus --fail-at 20
    PYTHONPATH=src python -m repro.launch.train --arch gpt2-l --reduced \
        --steps 40 --backend local --peers 2 --fail-at 25
"""
from __future__ import annotations

import argparse
import dataclasses
import shutil
import time
import warnings
from typing import Dict, List

import jax
import numpy as np

from repro.checkpoint import BACKENDS, FORMATS
from repro.configs import get_config
from repro.core.engine import STRATEGIES, EngineConfig, make_engine
from repro.core.steps import init_state, make_train_step
from repro.data.synthetic import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.obs.log import configure as configure_logging, get_logger
from repro.obs.metrics import REGISTRY
from repro.obs.timeline import STALL_CATEGORIES, TIMELINE
from repro.obs.trace import TRACER


def build_strategy(name: str, model, store, *, lr, rho, full_interval,
                   batch_size, compressor="topk", persist_mode="full",
                   persist_threshold=0.0, fold_interval=16,
                   replay_window=None):
    """Deprecated shim: construct a strategy from loose keywords. New
    code builds an :class:`EngineConfig` and calls ``make_engine``."""
    warnings.warn(
        "build_strategy() is deprecated; build an "
        "repro.core.engine.EngineConfig and call make_engine()",
        DeprecationWarning, stacklevel=2)
    cfg = EngineConfig(strategy=name, lr=lr, rho=rho,
                       full_interval=full_interval or 0,
                       batch_size=batch_size or 0, compressor=compressor,
                       persist_mode=persist_mode,
                       persist_threshold=persist_threshold,
                       fold_interval=fold_interval,
                       replay_window=replay_window or 0)
    return make_engine(cfg, model, store=store)


def _stall_suffix(rec) -> str:
    """Render a committed step record's stall attribution (only the
    categories that actually charged time — quiet steps stay short)."""
    parts = []
    for cat in STALL_CATEGORIES:
        if rec.get(cat, 0.0) > 0.0:
            parts.append(f"{cat}={rec[cat] * 1e3:.1f}ms")
    parts.append(f"stall%={TIMELINE.stall_fraction() * 100:.1f}")
    return " ".join(parts)


@dataclasses.dataclass
class RunResult:
    """What one training run produced."""
    losses: List[float]
    times: List[float]                 #: wall seconds per step
    #: one record per injected failure: the step it struck, the step
    #: training resumed from and, for LowDiff, the differentials replayed
    recoveries: List[Dict[str, int]]


def arch_config(args):
    """The model config the flags ask for: ``--arch``, cut to CPU size
    by ``--reduced`` and to ``--layers`` layers (every width kept)."""
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    layers = getattr(args, "layers", 0)
    if layers:
        get_logger("train").info(
            f"depth cut: n_layers {cfg.n_layers} -> {layers}")
        cfg = cfg.replace(n_layers=layers)
    return cfg


def run(args) -> RunResult:
    configure_logging(getattr(args, "log_level", "info"))
    log = get_logger("train")
    cfg = arch_config(args)
    model = build_model(cfg)
    log.info(f"arch={cfg.name} params={model.n_params() / 1e6:.1f}M "
             f"strategy={args.strategy}")
    if getattr(args, "clean", False) and args.ckpt_dir:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)
    engine_cfg = EngineConfig.from_args(args)
    TIMELINE.clear()
    if engine_cfg.trace_out:
        TRACER.enable(engine_cfg.trace_buffer)
    store = engine_cfg.build_store()
    strat = make_engine(engine_cfg, model, store=store)
    mode = ("lowdiff" if args.strategy == "lowdiff" else
            "lowdiff_plus" if args.strategy == "lowdiff_plus" else "dense")
    state = init_state(model, jax.random.PRNGKey(args.seed), mode=mode)
    plain_step = make_train_step(model, mode=mode, lr=args.lr, rho=args.rho)
    stream = TokenStream(cfg, args.seq, args.batch, seed=args.seed)

    losses, times, recoveries = [], [], []
    t_start = time.perf_counter()
    for t in range(args.steps):
        batch = next(stream)
        t0 = time.perf_counter()
        TIMELINE.begin(t + 1)
        if strat is not None:
            state, metrics = strat.train_step(state, batch)
        else:
            state, metrics, _ = plain_step(state, batch)
        jax.block_until_ready(state["params"])
        step_wall = time.perf_counter() - t0
        rec = TIMELINE.commit(t + 1, step_wall)
        times.append(step_wall)
        losses.append(float(metrics["loss"]))
        if args.log_every and (t + 1) % args.log_every == 0:
            log.info(f"step {t + 1:5d} loss={losses[-1]:.4f} "
                     f"it={np.mean(times[-args.log_every:]) * 1e3:.1f}ms "
                     + _stall_suffix(rec))
        if args.fail_at and t + 1 == args.fail_at:
            log.info(f"\n*** injected failure at step {t + 1} ***")
            assert strat is not None, "--fail-at needs a strategy"
            strat.flush()
            record = {"fail_at": t + 1}
            if args.strategy == "lowdiff_plus":
                state = strat.recover_software(state)
            else:
                state = None     # a killed process keeps no device state
                state, record["applied"] = strat.recover()
            record["step"] = int(state["step"])
            recoveries.append(record)
            log.info(f"recovered at step {record['step']}; resuming\n")
            stream.step = record["step"]

    wall = time.perf_counter() - t_start
    if strat is not None:
        strat.close()
    elif store is not None:
        store.close()
    log.info(f"\n{args.steps} steps in {wall:.1f}s "
             f"(mean iter {np.mean(times) * 1e3:.1f}ms, "
             f"p50 {np.percentile(times, 50) * 1e3:.1f}ms)")
    log.info(f"loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    if strat is not None:
        log.info(f"strategy stats: {strat.stats()}")
    if engine_cfg.trace_out:
        n = TRACER.export_chrome(engine_cfg.trace_out)
        log.info(f"wrote {n} trace events -> {engine_cfg.trace_out}")
    if engine_cfg.metrics_out:
        extras = [{"kind": "metric", **m} for m in REGISTRY.collect()]
        n = TIMELINE.write_jsonl(engine_cfg.metrics_out, extra=extras)
        log.info(f"wrote {n} records -> {engine_cfg.metrics_out}")
    return RunResult(losses, times, recoveries)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2-l")
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the published config's depth to this many "
                         "layers, keeping every width (0 = published "
                         "depth)")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--rho", type=float, default=0.01)
    ap.add_argument("--strategy", choices=STRATEGIES, default="lowdiff")
    ap.add_argument("--full-interval", type=int, default=20,
                    help="full-checkpoint interval f (0 = Eq. (10) optimum "
                         "+ online tuning)")
    ap.add_argument("--batch-size", type=int, default=2,
                    help="differential batching size b (0 = Eq. (10) "
                         "optimum + online tuning)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--backend", choices=BACKENDS, default="local",
                    help="checkpoint storage backend (local FS, CPU-memory "
                         "tier with async spill, or sharded concurrent)")
    ap.add_argument("--format", choices=FORMATS, default="frame",
                    help="checkpoint serialization: 'frame' (streamed "
                         "zero-copy, memmap reads) or 'npz' (legacy); "
                         "reads sniff, so old chains recover either way")
    ap.add_argument("--compressor", choices=("topk", "quant8", "packed"),
                    default="topk",
                    help="lowdiff gradient compression: topk sparsification, "
                         "quant8 blockwise int8, or packed (fused top-k + "
                         "int8 + wire pack in one Pallas kernel)")
    ap.add_argument("--shards", type=int, default=4,
                    help="shard count for --backend sharded")
    ap.add_argument("--memory-capacity-mb", type=float, default=None,
                    help="RAM-tier byte budget for --backend memory/remote")
    ap.add_argument("--remote-url", default=None,
                    help="object store for --backend remote: fake://bucket "
                         "(in-process) or file:///path (directory-backed); "
                         "default file://<ckpt-dir>")
    ap.add_argument("--chunk-mb", type=float, default=4.0,
                    help="remote-tier content chunk size in MiB")
    ap.add_argument("--max-retries", type=int, default=4,
                    help="bounded retries per remote chunk transfer")
    ap.add_argument("--remote-fault-rate", type=float, default=0.0,
                    help="injected transient-fault probability on fake:// "
                         "stores (exercises retry/backoff)")
    ap.add_argument("--peers", type=int, default=0,
                    help="replicate every differential to this many "
                         "failure-domain-diverse peer hosts' memory "
                         "(Checkmate-style tier above the local stack; "
                         "0 = off). Single-process runs simulate peers "
                         "in-process via the loopback transport")
    ap.add_argument("--peer-hub", default=None,
                    help="peer membership group name; hosts sharing a hub "
                         "replicate to each other (default: 'default')")
    ap.add_argument("--peer-domain", default="d0",
                    help="failure domain of this host (rack/pod); peer "
                         "selection prefers one replica per domain")
    ap.add_argument("--peer-window", type=int, default=8,
                    help="max in-flight peer replication sends before "
                         "put() backpressures")
    ap.add_argument("--peer-fault-rate", type=float, default=0.0,
                    help="injected transient-fault probability on peer "
                         "sends (exercises retry/backoff)")
    ap.add_argument("--retention", type=int, default=0,
                    help="keep this many full checkpoints + their chains "
                         "(0 = never garbage-collect)")
    ap.add_argument("--eviction", choices=("fifo", "lru"), default="fifo",
                    help="memory-tier eviction policy over size-class "
                         "buckets; lru refreshes recency on recovery reads")
    ap.add_argument("--maintenance", choices=("on", "off"), default="off",
                    help="background maintenance service: journaled "
                         "resumable GC + integrity scrub off the step "
                         "loop (off = synchronous GC fallback)")
    ap.add_argument("--persist-mode", choices=("full", "incremental"),
                    default="full",
                    help="lowdiff_plus persistence: 'full' rewrites the "
                         "whole replica every persist; 'incremental' "
                         "writes only the leaves that changed since the "
                         "last persist as a patch chain on a base full, "
                         "folded back in the background (requires "
                         "--format frame)")
    ap.add_argument("--persist-threshold", type=float, default=0.0,
                    help="incremental persist filter: defer re-persisting "
                         "a dirty leaf until its accumulated relative "
                         "L-inf change exceeds this (0 = exact: persist "
                         "every changed leaf)")
    ap.add_argument("--dirty-granularity", choices=("leaf", "row"),
                    default="leaf",
                    help="incremental persist unit: 'leaf' re-persists "
                         "whole changed arrays; 'row' tracks dirtiness "
                         "per first-axis row and patches only the "
                         "changed row ranges")
    ap.add_argument("--diff-quant", choices=("off", "int8", "int4"),
                    default="off",
                    help="quantize row-span patch payloads on the wire "
                         "(per-row-block absmax scales, error-feedback "
                         "residuals; requires --persist-mode incremental "
                         "--dirty-granularity row)")
    ap.add_argument("--fold-interval", type=int, default=16,
                    help="fold the patch chain into its base frame after "
                         "this many incremental persists (0 = never)")
    ap.add_argument("--fold-amplification", type=float, default=1.5,
                    help="also fold when chain overlay bytes divided by "
                         "base frame bytes reach this ratio (0 = "
                         "disable the adaptive trigger; --fold-interval "
                         "stays as the hard cap)")
    ap.add_argument("--merge-slice", type=int, default=64,
                    help="leaves patched per journaled fold slice "
                         "(bounded work between progress records)")
    ap.add_argument("--replay-window", type=int, default=0,
                    help="differentials per parallel-replay scan window; "
                         "bounds peak recovery memory to O(window * "
                         "model) (0 = one window)")
    ap.add_argument("--replay-device", choices=("on", "off"), default="off",
                    help="device-resident recovery: stage the compressed "
                         "payloads H2D and replay the chain as one jitted "
                         "scan through the fused decompress-and-apply "
                         "kernels (bit-identical to serial replay)")
    ap.add_argument("--snapshot-shards", type=int, default=4,
                    help="per-shard overlapped D2H snapshot transfers; "
                         "each shard's buffers release as its bytes land "
                         "(0 = legacy whole-tree batch copy)")
    ap.add_argument("--gc-slice", type=int, default=64,
                    help="keys swept per journaled GC slice (bounded "
                         "work between progress records)")
    ap.add_argument("--scrub-interval", type=float, default=0.0,
                    help="seconds between background integrity scrubs "
                         "(0 = scrub only on demand)")
    ap.add_argument("--host-id", default=None,
                    help="journal segment id for multi-controller jobs: "
                         "each host appends to its own manifest segment, "
                         "merged deterministically on read/compaction")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace_event JSON of the pipeline "
                         "spans here (load in chrome://tracing or "
                         "ui.perfetto.dev); also enables the span tracer")
    ap.add_argument("--metrics-out", default=None,
                    help="write per-step stall-attribution records and the "
                         "final metrics-registry collection as JSON Lines")
    ap.add_argument("--trace-buffer", type=int, default=65536,
                    help="span ring-buffer capacity; oldest spans drop "
                         "beyond this (the Chrome export reports drops)")
    ap.add_argument("--clean", action="store_true", default=True)
    ap.add_argument("--fail-at", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--log-level", default="info",
                    choices=("debug", "info", "warning", "error"),
                    help="driver log verbosity (default keeps the "
                         "human-readable step lines)")
    return ap


def main():
    enable_compile_cache()
    run(build_parser().parse_args())


if __name__ == "__main__":
    main()
