#!/usr/bin/env python3
"""Readings that set a cell's correctness limits (not run by the
benchmark's own runs).

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 [--control] \
        [--faults]

Train cells: for each seed, the program's first three steps through the
window's call and feed against the plain reference (the lower readings),
with ``--control`` the reference computed with fp8 products put in the
program's place (an upper reading), and with ``--faults`` the program
with a fault planted in its timed path: half of every batch left out of
the mean, and a differential altered where the step produces it. A step
that returns its state unchanged reads 1 on ``grad_norm_gap`` and
``param_change_gap`` by their definition and needs no run.

Resume cells: ``--control`` replays the chain read back from the store
in bfloat16, one step below the float32 state, and compares it as if it
were the recovered state; ``--faults`` makes a whole run with recovery
skipping the replay, and one with a recovered parameter altered.

Prints one JSON line per seed and reading; runs on the chip like
``bench/run.py`` and in the same checkout.
"""
import argparse
import functools
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchlib import checks, faults, harness, spec  # noqa: E402
from benchlib import reference as ref  # noqa: E402


def train_readings(cell, seed, *, control, fault_names):
    out = []
    eng = cell.traffic["engine"]
    rho = eng["rho"] if eng["strategy"] == "lowdiff" else 0.0
    refr = checks.reference_readings(cell.config, seed, rho=rho, lr=eng["lr"])
    for fault in [None] + fault_names:
        run = harness.Run(cell, seed, 0, False)
        model = harness.program_model(cell.config)
        strat = harness.build_engine(run, model)
        step = harness.Stepper(run, model, strat)
        if fault is not None:
            faults.apply(fault, step, strat)
        readings = {}
        harness._first_steps(run, model, strat, step, readings)
        if strat is not None:
            strat.close()
        nums = checks.compare_training(readings, refr)
        if "diff_identity_gap" in readings:
            nums["diff_identity_gap"] = readings["diff_identity_gap"]
        out.append({"reading": fault or "program", **nums})
    if control:
        ctl = checks.reference_readings(cell.config, seed, rho=rho,
                                        lr=eng["lr"], precision="fp8")
        out.append({"reading": "control_fp8",
                    **checks.compare_training(ctl, refr)})
    return out


def resume_control(cell, seed):
    """The chain read back, replayed in bfloat16 by the reference's
    Adam, against the state at the kill, in the recovered state's
    place."""
    lr = cell.traffic["engine"]["lr"]
    run = harness.Run(cell, seed, 0, False)
    model = harness.program_model(cell.config)
    strat = harness.build_engine(run, model)
    state, kill = harness.build_chain(run, model, strat,
                                      harness.Stepper(run, model, strat))
    m, v, _ = harness._opt_parts(state["opt"])
    kill_p = state["params"]
    store = strat.store
    full = store.manifest["fulls"][-1]
    loaded = store.load_full(full)
    diffs = [d for d in store.diffs_after(int(full["step"])) if d[0] <= kill]
    lm, lv, lc = harness._opt_parts(loaded["opt"])
    bf = lambda t: jax.tree.map(  # noqa: E731
        lambda x: jnp.asarray(x, jnp.bfloat16), t)
    p, mm, vv = bf(loaded["params"]), bf(lm), bf(lv)
    shapes = tuple(tuple(x.shape) for x in jax.tree.leaves(p))
    for i, (_, payload) in enumerate(diffs):
        pay = [(jnp.asarray(a), jnp.asarray(b))
               for a, b in harness._payload_leaves(payload)]
        p, mm, vv = _bf16_adam(p, mm, vv, jnp.int32(int(lc) + i + 1), pay,
                               lr, shapes=shapes)
    gp, _ = harness._max_gaps(p, kill_p)
    gm, sm = harness._max_gaps((mm, vv), (m, v))
    strat.close()
    return [{"reading": "control_bf16_replay",
             "recover_params_gap": float(jnp.max(gp)) / lr,
             "recover_moments_gap": float(jnp.max(gm / jnp.maximum(sm,
                                                                   1e-30))),
             "replay_ref_gap": float(jnp.max(gp)) / lr}]


@functools.partial(jax.jit, static_argnames=("shapes",),
                   donate_argnums=(0, 1, 2))
def _bf16_adam(params, m, v, t, payload, lr, *, shapes):
    """The reference's Adam step with every tensor in bfloat16 (the
    scalar bias corrections are worked out in float32, since 0.999
    rounds to 1 in bfloat16)."""
    bf = jnp.bfloat16
    tf = t.astype(jnp.float32)
    c1 = (1.0 - ref.B1 ** tf).astype(bf)
    c2 = (1.0 - ref.B2 ** tf).astype(bf)
    outs = []
    for p, mm, vv, (vals, idx), shape in zip(
            jax.tree.leaves(params), jax.tree.leaves(m), jax.tree.leaves(v),
            payload, shapes):
        g = ref.decompress(vals, idx, shape).astype(bf)
        mm = ref.B1 * mm + (1.0 - ref.B1) * g
        vv = ref.B2 * vv + (1.0 - ref.B2) * g * g
        p = p - lr * (mm / c1) / (jnp.sqrt(vv / c2) + ref.EPS)
        outs.append((p.astype(bf), mm.astype(bf), vv.astype(bf)))
    td = jax.tree.structure(params)
    return tuple(jax.tree.unflatten(td, [o[i] for o in outs])
                 for i in range(3))


def resume_faults(cell, seed):
    """A whole run (one-second window) with each resume fault planted."""
    rows = []
    for fault in faults.RESUME:
        r = harness.run_cell(cell, seed, 1.0, False, time.perf_counter(),
                             fault=fault)
        rows.append({"reading": fault, **{k: c["value"] for k, c in
                                          r["checks"].items()}})
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--faults", action="store_true")
    a = ap.parse_args()
    cell = spec.load_cell(a.workload)
    try:
        harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache()
    for seed in a.seeds:
        t0 = time.perf_counter()
        if cell.traffic["mode"] == "resume":
            rows = resume_control(cell, seed) if a.control else []
            if a.faults:
                rows += resume_faults(cell, seed)
        else:
            names = faults.train_faults(cell) if a.faults else []
            rows = train_readings(cell, seed, control=a.control,
                                  fault_names=names)
        for r in rows:
            print(json.dumps({"workload": cell.name, "seed": seed, **r,
                              "s": time.perf_counter() - t0}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
