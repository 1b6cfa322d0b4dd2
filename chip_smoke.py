#!/usr/bin/env python3
"""Smoke run of the checkpointing engine on one TPU chip (or four).

Drives the normal entry point, ``repro.launch.train.run``, at gpt2-l's
published width (d_model 1280, 20 heads, d_ff 5120, vocab 50257) with
only the depth cut, on random weights made from a seed, and checks what
comes out. Phases, in order:

* ``lowdiff``: top-k differentials every step, a kill a few steps past
  a full checkpoint, and a resume that replays the chain on the device
  through the Pallas kernels. The replay is then repeated with the jnp
  oracle and the two are compared.
* ``packed``: the fused top-k + int8 pack kernels inside the train step.
* ``lowdiff_plus``: incremental row-granular int8 persistence with a
  kill and resume; the device overlay of the persisted chain must equal
  the host overlay.

``--four-chip`` runs only the sharded data-parallel LowDiff step on a
four-chip mesh, against the same steps of the dense step on one chip.

Run from the repository root::

    python chip_smoke.py
    python chip_smoke.py --four-chip

It exits non-zero without a TPU. Every phase prints one JSON line with
its wall time, compile time and the device's peak memory so far; the
last line of the output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

#: one model the repo supports, at its published width. Depth is cut so
#: that one 16 GB chip holds the step's input and output state (params,
#: Adam moments, error feedback: 16 bytes a parameter), its ~2 GB of
#: temporaries, and what is left of a full checkpoint whose D2H copy is
#: still in flight. 8 layers: 338 M params, 5.4 GB a copy.
ARCH = ["--arch", "gpt2-l", "--layers", "8", "--batch", "4",
        "--seq", "1024"]
SEED = 0
LR = 1e-3
CKPT = ROOT / ".smoke_ckpt"

#: kernel vs oracle replay: same per-element op sequence, so any
#: difference is a few ulps of the Adam step (far below one lr)
REPLAY_TOL = 1e-6
#: device vs host overlay of int8 spans: one f32 multiply per element,
#: so the only admissible difference is a subnormal flushed to zero
OVERLAY_TOL = float(np.finfo(np.float32).tiny)
#: four chips vs one, both computing in f32 with f32 ("highest")
#: matmuls so that only the reduction order differs: the loss agrees to
#: a relative 1e-3 and the parameter update of the run to a relative
#: 1e-2 in L2. At the published bf16 compute the two programs round at
#: different points, and Adam's normalized step turns that into an 8%
#: update distance, which says nothing about the sharding.
LOSS_RTOL = 1e-3
UPDATE_RTOL = 1e-2


class SmokeError(AssertionError):
    """A phase produced a wrong result."""


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


# --------------------------------------------------------------------------
# measurement helpers
# --------------------------------------------------------------------------

_COMPILE = {"seconds": 0.0, "compiles": 0, "cache_hits": 0}


def _on_duration(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["seconds"] += duration
        _COMPILE["compiles"] += 1


def _on_event(event, **_):
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["cache_hits"] += 1


def _peak_bytes():
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def run_phase(name, fn, *args):
    """Run one phase and print its line: wall time, time spent compiling
    (persistent-cache reads included), and each device's peak memory
    since the process started."""
    before = dict(_COMPILE)
    t0 = time.perf_counter()
    out = fn(*args)
    rec = {"phase": name, "wall_s": time.perf_counter() - t0,
           "compile_s": _COMPILE["seconds"] - before["seconds"],
           "compiles": _COMPILE["compiles"] - before["compiles"],
           "cache_hits": _COMPILE["cache_hits"] - before["cache_hits"],
           "peak_bytes_in_use": _peak_bytes(), **out}
    print(json.dumps(rec), flush=True)
    return out


def _count_kernels(lowered_text: str) -> int:
    return lowered_text.count("tpu_custom_call")


def _sds(x):
    return jax.ShapeDtypeStruct(np.shape(x), x.dtype)


def _max_abs_diff(a_tree, b_tree):
    """(max |a - b| over every leaf, all leaves bitwise equal)."""
    worst, same = 0.0, True
    for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree)):
        a, b = np.asarray(a), np.asarray(b)
        check(a.shape == b.shape and a.dtype == b.dtype,
              f"leaf mismatch {a.shape}/{a.dtype} vs {b.shape}/{b.dtype}")
        same &= np.array_equal(a.reshape(-1).view(np.uint8),
                               b.reshape(-1).view(np.uint8))
        if a.size:
            worst = max(worst, float(np.max(np.abs(
                a.astype(np.float64) - b.astype(np.float64)))))
    return worst, bool(same)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def _train_args(base, phase, extra):
    from repro.launch.train import build_parser
    root = CKPT / phase
    shutil.rmtree(root, ignore_errors=True)
    return build_parser().parse_args(
        base + ["--seed", str(SEED), "--lr", str(LR), "--log-every", "1",
                "--ckpt-dir", str(root)] + extra)


def _model(args):
    from repro.launch.train import arch_config
    from repro.models.registry import build_model
    return build_model(arch_config(args))


def _finite_losses(res):
    check(len(res.losses) > 0 and np.isfinite(res.losses).all(),
          f"non-finite loss: {res.losses}")
    return {"loss_first": res.losses[0], "loss_last": res.losses[-1]}


def _store(args):
    from repro.core.engine import EngineConfig
    return EngineConfig.from_args(args).build_store()


def _host(tree):
    from repro.core.snapshot import host_copy
    return host_copy(tree)


def phase_lowdiff(base):
    """Top-k LowDiff, per-iteration differentials, kill and resume with
    device replay; then the same chain through kernels and oracle."""
    from repro.core import recovery as rec
    from repro.launch.train import run
    full, fail = 5, 8
    args = _train_args(base, "lowdiff", [
        "--strategy", "lowdiff", "--compressor", "topk",
        "--batch-size", "1", "--full-interval", str(full),
        "--fail-at", str(fail), "--steps", str(fail + 1),
        "--replay-device", "on"])
    res = run(args)
    out = _finite_losses(res)
    (r,) = res.recoveries
    check(r["step"] == fail, f"resumed at step {r['step']}, killed at {fail}")

    store = _store(args)
    try:
        state, diffs = rec.load_latest_chain(store)
        base_step = int(state["step"])
        chain = [d for d in rec.contiguous_prefix(base_step, diffs)
                 if d[0] <= fail]
        check(base_step == full and len(chain) == fail - full,
              f"chain: full at {base_step}, {len(chain)} differentials")
        check(r["applied"] == len(chain),
              f"recovery applied {r['applied']} of {len(chain)}")

        replays = {}
        for up in (True, False):
            p, o, n = rec.replay_device(state["params"], state["opt"], chain,
                                        lr=LR, use_pallas=up)
            check(n == len(chain), f"replay applied {n} of {len(chain)}")
            replays[up] = _host((p, o.mu, o.nu))
            del p, o
        diff, bitwise = _max_abs_diff(replays[True], replays[False])
        check(diff <= REPLAY_TOL,
              f"kernel vs oracle replay differ by {diff} > {REPLAY_TOL}")

        stacked = jax.tree.map(lambda *xs: np.stack(xs),
                               *[p for _, p in chain])
        g = [jax.tree.map(_sds, c)
             for c in jax.tree.leaves(stacked, is_leaf=rec._is_compressed)]
        leaves = [[_sds(x) for x in jax.tree.leaves(t)]
                  for t in (state["params"], state["opt"].mu,
                            state["opt"].nu)]
        text = rec._device_replay.lower(
            *leaves, g, jax.ShapeDtypeStruct((), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.float32), use_pallas=True).as_text()
        kernels = _count_kernels(text)
        check(kernels > 0, "the device replay program holds no Pallas kernel")
    finally:
        store.close()
    return {**out, "full_step": base_step, "killed_at": fail,
            "resumed_at": r["step"], "applied": r["applied"],
            "chain": len(chain), "replay_max_abs_diff": diff,
            "replay_tol": REPLAY_TOL, "replay_bitwise_equal": bitwise,
            "replay_tpu_custom_calls": kernels}


def phase_packed(base):
    """A few steps with the fused top-k + int8 pack kernels in the step;
    the persisted packed differentials decode the same through the
    kernel and the oracle."""
    from repro.compression.packed import PackedDiff
    from repro.core.steps import init_state, make_train_step
    from repro.kernels import ops
    from repro.launch.train import run
    steps = 3
    args = _train_args(base, "packed", [
        "--strategy", "lowdiff", "--compressor", "packed",
        "--batch-size", "1", "--full-interval", "1000",
        "--steps", str(steps)])
    res = run(args)
    out = _finite_losses(res)

    store = _store(args)
    try:
        diffs = store.diffs_after(0)
        check([s for s, _ in diffs] == list(range(1, steps + 1)),
              f"persisted steps {[s for s, _ in diffs]}")
        pds = jax.tree.leaves(diffs[0][1],
                              is_leaf=lambda x: isinstance(x, PackedDiff))
        check(all(isinstance(x, PackedDiff) for x in pds),
              "a persisted differential is not in packed wire form")
        dec = {up: [ops.packed_decompress(jax.tree.map(jnp.asarray, pd),
                                          use_pallas=up) for pd in pds]
               for up in (True, False)}
        diff, bitwise = _max_abs_diff(dec[True], dec[False])
        check(diff <= REPLAY_TOL,
              f"packed decode kernel vs oracle differ by {diff}")
    finally:
        store.close()

    model = _model(args)
    state = jax.eval_shape(lambda: init_state(model, jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
             for k in ("tokens", "targets")}
    step = make_train_step(model, mode="lowdiff", rho=args.rho, lr=LR,
                           compressor="packed")
    kernels = _count_kernels(step.lower(state, batch).as_text())
    check(kernels > 0, "the packed train step holds no Pallas kernel")
    return {**out, "persisted": len(diffs), "decode_max_abs_diff": diff,
            "decode_bitwise_equal": bitwise,
            "step_tpu_custom_calls": kernels}


def phase_lowdiff_plus(base):
    """LowDiff+ with incremental, row-granular, int8-quantized patches,
    a kill and resume; the device overlay of the persisted chain (the
    quant_span_apply kernel) against the host overlay."""
    from repro.core import recovery as rec
    from repro.launch.train import run
    # the host replica applies Adam to the whole model in numpy, tens of
    # seconds a step at this width: keep the run to three steps
    fail, steps = 2, 3
    args = _train_args(base, "lowdiff_plus", [
        "--strategy", "lowdiff_plus", "--persist-mode", "incremental",
        "--dirty-granularity", "row", "--diff-quant", "int8",
        "--batch-size", "1", "--fail-at", str(fail),
        "--steps", str(steps)])
    res = run(args)
    out = _finite_losses(res)
    (r,) = res.recoveries
    check(r["step"] == fail, f"resumed at step {r['step']}, killed at {fail}")

    store = _store(args)
    try:
        quantized = sum("int8" in e.get("codec", ())
                        for e in store.manifest.get("patches", []))
        check(quantized > 0, "no int8 patch was persisted")
        host, hstep = store.load_latest_state()
        dev, dstep = rec.load_state_device(store)
    finally:
        store.close()
    check(hstep == dstep == steps, f"overlay steps host {hstep} dev {dstep}")
    diff, bitwise = _max_abs_diff(dev, host)
    check(diff <= OVERLAY_TOL,
          f"device vs host overlay differ by {diff} > {OVERLAY_TOL}")
    return {**out, "killed_at": fail, "resumed_at": r["step"],
            "int8_patches": quantized, "overlay_step": dstep,
            "overlay_max_abs_diff": diff, "overlay_tol": OVERLAY_TOL,
            "overlay_bitwise_equal": bitwise}


def phase_four_chip(base, steps=3):
    """Sharded data-parallel LowDiff on a (data=4, model=1) mesh: the
    train step emits shard-local top-k differentials, copied to host
    through the snapshot path; compared with the dense step on one
    chip from the same initial state and batches, both in f32."""
    from repro.configs.base import ShapeConfig
    from repro.core.steps import init_state, make_train_step
    from repro.data.synthetic import make_batch
    from repro.distributed import sharding as shd
    from repro.distributed.step_builder import make_sharded_train_step
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import arch_config, build_parser
    from repro.models.registry import build_model
    args = build_parser().parse_args(base)
    devices = jax.devices()
    check(len(devices) == 4, f"{len(devices)} devices, the mesh needs 4")
    model = build_model(arch_config(args).replace(compute_dtype="float32"))
    cfg = model.cfg
    state0 = _host(init_state(model, jax.random.PRNGKey(SEED), mode="dense"))
    batches = [jax.tree.map(np.asarray, make_batch(
        cfg, args.seq, args.batch, step=t, seed=SEED)) for t in range(steps)]

    losses, diff_bytes = {}, 0
    with jax.default_matmul_precision("highest"), \
            shd.use_mesh(make_local_mesh(4, 1)):
        jstep, abs_state, abs_batch = make_sharded_train_step(
            model, ShapeConfig("smoke", args.seq, args.batch, "train"),
            mode="lowdiff_sharded", rho=args.rho, lr=LR)
        st = jax.device_put(state0, jax.tree.map(lambda a: a.sharding,
                                                 abs_state))
        bsh = {k: v.sharding for k, v in abs_batch.items()}
        losses["4"] = []
        for b in batches:
            st, metrics, cg = jstep(st, jax.device_put(b, bsh))
            losses["4"].append(float(metrics["loss"]))
            for leaf in jax.tree.leaves(cg):
                shards = leaf.addressable_shards
                check({s.device for s in shards} == set(devices),
                      f"a differential of shape {leaf.shape} is not on "
                      f"every device")
                check(all(s.data.nbytes > 0 for s in shards),
                      "a device holds an empty differential shard")
            host = _host(cg)
            diff_bytes += sum(np.asarray(x).nbytes
                              for x in jax.tree.leaves(host))
        p4 = _host(st["params"])
        del st, cg
    check(diff_bytes > 0, "no differential bytes reached the host")

    with jax.default_matmul_precision("highest"):
        step1 = make_train_step(model, mode="dense", lr=LR)
        st = jax.device_put(state0, devices[0])
        losses["1"] = []
        for b in batches:
            st, metrics, _ = step1(st, jax.device_put(b, devices[0]))
            losses["1"].append(float(metrics["loss"]))
        p1 = _host(st["params"])
        del st

    loss_rdiff = max(abs(a - b) / abs(b)
                     for a, b in zip(losses["4"], losses["1"]))
    # how far apart the two runs' parameter updates are, relative to
    # the one-chip update
    num = den = worst = 0.0
    moved = total = 0
    for a, b, c in zip(jax.tree.leaves(p4), jax.tree.leaves(p1),
                       jax.tree.leaves(state0["params"])):
        a, b, c = (np.asarray(x, np.float64) for x in (a, b, c))
        d = np.abs(a - b)
        num += float(np.sum(d * d))
        den += float(np.sum((b - c) ** 2))
        worst = max(worst, float(d.max()))
        moved += int((d > REPLAY_TOL).sum())
        total += d.size
    update_rdiff = float(np.sqrt(num / den))
    out = {"steps": steps, "mesh": "data=4,model=1",
           "compute_dtype": "float32", "matmul_precision": "highest",
           "loss_4chip": losses["4"], "loss_1chip": losses["1"],
           "loss_max_rel_diff": loss_rdiff, "loss_rtol": LOSS_RTOL,
           "update_rel_l2_diff": update_rdiff, "update_rtol": UPDATE_RTOL,
           "param_max_abs_diff": worst, "params_differing": moved,
           "params_total": total, "differential_host_bytes": diff_bytes}
    check(np.isfinite(losses["4"]).all() and loss_rdiff <= LOSS_RTOL,
          f"losses of four chips and one disagree: {out}")
    check(update_rdiff <= UPDATE_RTOL,
          f"updates of four chips and one disagree: {out}")
    return out


# --------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded four-chip path and its "
                         "one-chip comparison")
    opts = ap.parse_args(argv)
    cache = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"device": device, "jax": jax.__version__,
                      "compile_cache": cache,
                      "bytes_limit": (dev.memory_stats() or {}).get(
                          "bytes_limit")}), flush=True)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    try:
        if opts.four_chip:
            run_phase("four_chip", phase_four_chip, ARCH)
        else:
            run_phase("lowdiff", phase_lowdiff, ARCH)
            run_phase("packed", phase_packed, ARCH)
            run_phase("lowdiff_plus", phase_lowdiff_plus, ARCH)
    finally:
        shutil.rmtree(CKPT, ignore_errors=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
