"""One run of one cell: set-up, the measured window, the comparison
with the plain reference, and the result line.

Two kinds of traffic (the traffic file's ``mode``):

- ``train``: the window runs the body of the program's training loop
  (``launch/train.run``): next batch, the engine's ``train_step`` (or
  the bare dense step when the strategy is ``none``),
  ``block_until_ready``, ``TIMELINE.commit``. Set-up drives the same
  object through the first three steps with the same call and feed.
- ``resume``: set-up trains through one full save and ``chain``
  differentials and flushes; every resume in the window drops the device
  state, calls the engine's ``recover()`` and runs one step on the
  recovered state. That step goes through the engine's compiled step
  function but not through its queue, so the chain is the same for every
  resume.
"""
from __future__ import annotations

import dataclasses
import functools
import gc
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import checks, faults, inputs, reference as ref, spec, tracing
from benchlib.peaks import peaks

STORE_ROOT = spec.BENCH / ".store"
TRACE_ROOT = spec.BENCH / ".trace"
CACHE_DIR = spec.BENCH / ".jax_cache"
ann = jax.profiler.TraceAnnotation


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def require_chips(n: int) -> List[Any]:
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX found "
                     f"{len(devs)} {devs[0].platform!r} device(s)")
    return devs


def enable_compile_cache() -> str:
    """JAX's persistent cache, at ``$JAX_COMPILATION_CACHE_DIR`` or a
    fixed directory inside the checkout, for programs of any size."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Backend compiles and persistent-cache hits, from JAX's events."""

    def __init__(self):
        self.compiles = 0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._ev)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _ev(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


_COUNTER: Optional[CompileCounter] = None


def compile_counter() -> CompileCounter:
    global _COUNTER
    if _COUNTER is None:
        _COUNTER = CompileCounter()
    return _COUNTER


class Run:
    """What a run measured; the metric readers read it."""

    def __init__(self, cell: spec.Cell, seed: int, seconds: float,
                 trace: bool):
        self.cell, self.seed, self.seconds, self.trace = (cell, seed,
                                                          seconds, trace)
        self.cfg = cell.config
        self.arch = cell.arch             #: references/<reference>.py
        self.traffic = cell.traffic
        self.mode = cell.traffic["mode"]
        self.tokens_per_step = cell.config["batch"] * cell.config["seq"]
        self.setup_s = 0.0
        self.window_s = 0.0
        self.steps = 0                    #: train steps in the window
        self.step_walls: List[float] = []
        self.step_ids: List[int] = []
        self.full_steps: List[int] = []   #: window steps that saved a full
        self.resume_times: List[float] = []
        self.replayed: List[int] = []     #: differentials per resume
        self.counters: Dict[str, float] = {}
        self.spans: List[tuple] = []      #: program spans in the window
        self.trace_summary: Optional[Dict] = None
        self.peaks = None
        self.n_params = inputs.n_params(cell.config)
        self.payload_bytes = 0            #: one differential's payload
        self.window_compiles = 0


# ----------------------------------------------------------------------
# the program under test
# ----------------------------------------------------------------------

#: keys of a configuration file that are the benchmark's own
HARNESS_KEYS = frozenset({"name", "source", "program_arch", "reference",
                          "batch", "seq", "published", "reduced", "assumed",
                          "deployment", "departures"})


def program_arch(cfg: Dict[str, Any], arch=None):
    """The program's ``ArchConfig``: its ``program_arch`` entry with
    every key of the configuration file that is one of its fields
    replaced (a nested group, such as ``moe``, key by key). A key the
    program has no field for must be in the architecture module's
    ``PROGRAM_IMPLIED`` with the value given there."""
    from repro.configs import get_config
    arch = arch or spec.reference_of(cfg)
    base = get_config(cfg["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    implied = arch.PROGRAM_IMPLIED
    over = {}
    for k, v in cfg.items():
        if k in HARNESS_KEYS:
            continue
        if k in fields:
            cur = getattr(base, k)
            if dataclasses.is_dataclass(cur) and isinstance(v, dict):
                unknown = set(v) - {f.name for f in dataclasses.fields(cur)}
                if unknown:
                    raise spec.SpecError(f"configuration group {k!r} has "
                                         f"keys {sorted(unknown)} the "
                                         f"program does not know")
                v = dataclasses.replace(cur, **v)
            elif isinstance(cur, tuple) and isinstance(v, list):
                v = tuple(v)
            over[k] = v
        elif k not in implied:
            raise spec.SpecError(
                f"configuration key {k!r} is neither a field of the "
                f"program's ArchConfig nor in {arch.__file__}'s "
                f"PROGRAM_IMPLIED")
        elif v != implied[k]:
            raise spec.SpecError(
                f"configuration key {k!r} is {v!r}; the program has no "
                f"such field and implies {implied[k]!r}")
    return base.replace(**over)


def program_model(cfg: Dict[str, Any], arch=None):
    from repro.models.registry import build_model
    return build_model(program_arch(cfg, arch))


def build_engine(run: Run, model):
    """The engine the traffic file asks for (None: checkpointing off)."""
    from repro.checkpoint.config import StoreConfig
    from repro.core.engine import EngineConfig, make_engine
    st = run.traffic["store"]
    store = None
    if st is not None:
        root = STORE_ROOT / run.cell.name
        shutil.rmtree(root, ignore_errors=True)
        store = StoreConfig.from_legacy(
            str(root), backend=st["backend"], fmt=st["format"],
            retention_fulls=st["retention"])
    return make_engine(EngineConfig(store=store, **run.traffic["engine"]),
                       model)


def program_state(run: Run, model, start_step: int = 0):
    """Weights from the seed on the device, in one jitted call, with
    the program's optimizer and error-feedback state around them."""
    from repro.compression.error_feedback import ef_init
    from repro.optim.adam import adam_init
    cfg, eng = run.cfg, run.traffic["engine"]
    with_ef = eng["strategy"] == "lowdiff"

    def make(key):
        p = inputs.make_params(cfg, key)
        s = {"params": p, "opt": adam_init(p),
             "step": jnp.asarray(start_step, jnp.int32)}
        if with_ef:
            s["ef"] = ef_init(p)
        return s

    state = jax.jit(make)(inputs.seed_key(run.seed))
    want = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if inputs.shapes(want) != inputs.shapes(state["params"]):
        raise spec.SpecError("the benchmark's weight layout no longer "
                             "matches the program's parameters")
    return state


class Stepper:
    """The window's call and feed, used by set-up as well."""

    def __init__(self, run: Run, model, strat):
        from repro.core.steps import make_train_step
        from repro.obs.timeline import TIMELINE
        self.run, self.strat, self.timeline = run, strat, TIMELINE
        self.dense = (None if strat is not None else
                      make_train_step(model, mode="dense",
                                      lr=run.traffic["engine"]["lr"]))

    def feed(self, n: int):
        with ann("bench.batch"):
            return jax.device_put(inputs.batch(self.run.cfg, self.run.seed,
                                               n))

    def __call__(self, state, n: int):
        batch = self.feed(n)
        self.timeline.begin(n)
        t0 = time.perf_counter()
        with ann("bench.train_step"):
            if self.strat is not None:
                state, metrics = self.strat.train_step(state, batch)
            else:
                state, metrics, _ = self.dense(state, batch)
        with ann("bench.block"):
            jax.block_until_ready(state["params"])
        wall = time.perf_counter() - t0
        self.timeline.commit(n, wall)
        return state, metrics["loss"], wall


# ----------------------------------------------------------------------
# small jitted readers of the program's state
# ----------------------------------------------------------------------

@jax.jit
def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(x.astype(jnp.float32).reshape(-1))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def _max_gaps(a_tree, b_tree):
    """Per leaf: max |a - b| and max |b|."""
    gaps = [jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
            for a, b in zip(jax.tree.leaves(a_tree), jax.tree.leaves(b_tree))]
    scale = [jnp.max(jnp.abs(b.astype(jnp.float32)))
             for b in jax.tree.leaves(b_tree)]
    return jnp.stack(gaps), jnp.stack(scale)


@functools.partial(jax.jit, static_argnames=("shapes",),
                   donate_argnums=(0, 1, 2))
def _adam_apply(params, m, v, t, payload, lr, *, shapes):
    """The reference's Adam step of every leaf, with the gradient
    decompressed from a persisted payload [(values, indices)]."""
    out_p, out_m, out_v = [], [], []
    for p, mm, vv, (vals, idx), shape in zip(
            jax.tree.leaves(params), jax.tree.leaves(m), jax.tree.leaves(v),
            payload, shapes):
        g = ref.decompress(vals, idx, shape)
        a, b, c = ref.adam(p.astype(jnp.float32), g, mm, vv,
                           t.astype(jnp.float32), lr)
        out_p.append(a.astype(p.dtype))
        out_m.append(b)
        out_v.append(c)
    td = jax.tree.structure(params)
    return (jax.tree.unflatten(td, out_p), jax.tree.unflatten(td, out_m),
            jax.tree.unflatten(td, out_v))


def _payload_leaves(payload) -> List[tuple]:
    """(values, indices) per leaf of a persisted top-k differential."""
    leaves = jax.tree.leaves(payload, is_leaf=lambda x: hasattr(x, "indices"))
    return [(np.asarray(x.values), np.asarray(x.indices)) for x in leaves]


def _opt_parts(opt):
    if hasattr(opt, "mu"):
        return opt.mu, opt.nu, opt.count
    if isinstance(opt, dict):
        return opt["mu"], opt["nu"], opt["count"]
    return opt[0], opt[1], opt[2]


@functools.partial(jax.jit, static_argnames=("shapes",))
def _step_gap(params, m, v, t, payload, target, lr, *, shapes):
    """Largest |Adam(params, payload) - target| over every leaf, without
    keeping the stepped state (nothing is donated)."""
    gaps = []
    for p, mm, vv, (vals, idx), shape, q in zip(
            jax.tree.leaves(params), jax.tree.leaves(m), jax.tree.leaves(v),
            payload, shapes, jax.tree.leaves(target)):
        g = ref.decompress(vals, idx, shape)
        a, _, _ = ref.adam(p.astype(jnp.float32), g, mm, vv,
                           t.astype(jnp.float32), lr)
        gaps.append(jnp.max(jnp.abs(a.astype(p.dtype).astype(jnp.float32)
                                    - q.astype(jnp.float32))))
    return jnp.max(jnp.stack(gaps))


def step_gap(params, m, v, t: int, payload, target_params, lr: float):
    """One differential applied by the reference's Adam to (params, m,
    v) at Adam count ``t``, against ``target_params``; in learning
    rates."""
    shapes = tuple(tuple(x.shape) for x in jax.tree.leaves(params))
    pay = [(jnp.asarray(a), jnp.asarray(b))
           for a, b in _payload_leaves(payload)]
    return float(_step_gap(params, m, v, jnp.asarray(t, jnp.int32), pay,
                           target_params, lr, shapes=shapes)) / lr


def replay_gap(params, m, v, t0: int, diffs, target_params, lr: float):
    """Replay ``diffs`` [(step, payload)] through the reference's Adam
    from (params, m, v) at Adam count ``t0``, which it consumes, and
    return the largest gap to ``target_params`` in units of the
    learning rate."""
    shapes = tuple(tuple(x.shape) for x in jax.tree.leaves(params))
    p, mm, vv = params, m, v
    for i, (_, payload) in enumerate(diffs):
        pay = [(jnp.asarray(a), jnp.asarray(b))
               for a, b in _payload_leaves(payload)]
        p, mm, vv = _adam_apply(p, mm, vv, jnp.asarray(t0 + i + 1, jnp.int32),
                                pay, lr, shapes=shapes)
    gaps, _ = _max_gaps(p, target_params)
    return float(jnp.max(gaps)) / lr


# ----------------------------------------------------------------------
# train traffic
# ----------------------------------------------------------------------

def _first_steps(run: Run, model, strat, step: Stepper, readings):
    """The state from the seed, then steps 1-3 through the window's call
    and feed; the readings the comparison needs are taken on the device
    as the steps pass. No more than two states are alive at once."""
    lr = run.traffic["engine"]["lr"]
    losses = []
    state1, loss, _ = step(program_state(run, model), 1)
    losses.append(loss)
    mu1, _, _ = _opt_parts(state1["opt"])
    readings["grad_norms"] = np.asarray(_leaf_norms(mu1)) / (1 - ref.B1)
    state2, loss, _ = step(state1, 2)
    losses.append(loss)
    if strat is not None:
        strat.flush()
        (s, payload), = [d for d in strat.store.diffs_after(1) if d[0] == 2]
        run.payload_bytes = int(sum(a.nbytes + b.nbytes
                                    for a, b in _payload_leaves(payload)))
        m1, v1, c1 = _opt_parts(state1["opt"])
        readings["diff_identity_gap"] = step_gap(
            state1["params"], m1, v1, int(c1) + 1, payload,
            state2["params"], lr)
    del state1
    state3, loss, _ = step(state2, 3)
    del state2
    losses.append(loss)
    readings["losses"] = [float(x) for x in losses]
    readings["change_norms"] = np.asarray(
        checks.change_norms(run.cfg)(state3["params"],
                                     inputs.seed_key(run.seed)))
    return state3


def _train(run: Run, model, strat, t_start: float, trace_dir, fault):
    from repro.checkpoint.io import COPY_METER
    from repro.obs.trace import TRACER
    step = Stepper(run, model, strat)
    if fault:
        faults.apply(fault, step, strat)
    readings: Dict[str, Any] = {}
    state = _first_steps(run, model, strat, step, readings)
    store = strat.store if strat is not None else None
    fi = run.traffic["engine"].get("full_interval", 0)
    if strat is not None:
        strat.flush()
    base = _counters(store, COPY_METER)
    TRACER.clear()
    counter = compile_counter()
    c0 = counter.compiles
    n = 3
    run.setup_s = time.perf_counter() - t_start
    if trace_dir:
        jax.profiler.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    with ann("bench.window"):
        while time.perf_counter() - t0 < run.seconds:
            n += 1
            state, _, wall = step(state, n)
            run.step_walls.append(wall)
            run.step_ids.append(n)
            if strat is not None and n % fi == 0:
                run.full_steps.append(n)
    run.window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    run.window_compiles = counter.compiles - c0
    run.steps = len(run.step_walls)
    run.spans = [e for e in TRACER.events() if e[4] >= t0]
    peak = _peak_bytes()
    if strat is not None:
        strat.flush()
    run.counters = {k: v - base[k]
                    for k, v in _counters(store, COPY_METER).items()}
    if run.full_steps:
        full = store.manifest["fulls"][-1]
        loaded = store.load_full(full)
        t_full = int(full["step"])
        diffs = [d for d in store.diffs_after(t_full) if d[0] <= n]
        m, v, c = _opt_parts(loaded["opt"])
        put = lambda x: jax.tree.map(jnp.asarray, x)  # noqa: E731
        readings["chain_replay_gap"] = replay_gap(
            put(loaded["params"]), put(m), put(v), int(c), diffs,
            state["params"], run.traffic["engine"]["lr"])
        del loaded
    del state
    if strat is not None:
        strat.close()
    gc.collect()
    numbers = checks.train_numbers(run, readings)
    return peak, numbers


def _counters(store, meter) -> Dict[str, float]:
    from repro.obs.timeline import TIMELINE
    out = {"d2h_wait_s": meter.d2h_wait_s, "d2h_events": meter.d2h_events,
           "queue_backpressure_s": TIMELINE.totals().get(
               "queue_backpressure", 0.0)}
    if store is not None:
        out["bytes_written"] = store.bytes_written
        out["write_time_s"] = store.instruments().histogram(
            "write_time_s").sum
    return out


# ----------------------------------------------------------------------
# resume traffic
# ----------------------------------------------------------------------

def build_chain(run: Run, model, strat, step: Stepper):
    """Train through one full save and ``chain`` differentials, all
    durable. Returns (state at the kill, its step). The full's snapshot
    is let land before the next step: held while two more steps run, it
    would need a third copy of the state on the chip."""
    fi, chain = run.traffic["engine"]["full_interval"], run.traffic["chain"]
    state, _, _ = step(program_state(run, model, start_step=fi - 1), fi)
    strat.flush()
    for n in range(fi + 1, fi + chain + 1):
        state, _, _ = step(state, n)
    strat.flush()
    return state, fi + chain


def _resume(run: Run, model, strat, t_start: float, trace_dir, fault):
    from repro.obs.trace import TRACER
    lr = run.traffic["engine"]["lr"]
    step = Stepper(run, model, strat)
    state, kill = build_chain(run, model, strat, step)
    if fault:
        faults.apply(fault, step, strat)
    m, v, _ = _opt_parts(state["opt"])
    at_kill = jax.device_get({"params": state["params"], "mu": m, "nu": v})
    next_batch = step.feed(kill + 1)
    _, metrics, _ = strat.step_fn(state, next_batch)
    loss_next = float(metrics["loss"])
    del state, metrics, m, v
    gc.collect()

    def resume():
        with ann("bench.recover"):
            rec, applied = strat.recover()
        with ann("bench.first_step"):
            new, metrics, _ = strat.step_fn(rec, next_batch)
            jax.block_until_ready(new["params"])
        return rec, applied, float(metrics["loss"])

    rec, applied, _ = resume()            # warms the replay program
    del rec
    gc.collect()
    TRACER.clear()
    counter = compile_counter()
    c0 = counter.compiles
    run.setup_s = time.perf_counter() - t_start
    if trace_dir:
        jax.profiler.start_trace(str(trace_dir))
    t0 = time.perf_counter()
    with ann("bench.window"):
        while time.perf_counter() - t0 < run.seconds:
            rec = None                    # the kill: no device state left
            gc.collect()
            t1 = time.perf_counter()
            rec, applied, loss_first = resume()
            run.resume_times.append(time.perf_counter() - t1)
            run.replayed.append(int(applied))
    run.window_s = time.perf_counter() - t0
    if trace_dir:
        jax.profiler.stop_trace()
    run.window_compiles = counter.compiles - c0
    run.spans = [e for e in TRACER.events() if e[4] >= t0]
    peak = _peak_bytes()

    readings: Dict[str, Any] = {"kill": kill, "loss_next": loss_next,
                                "loss_first": loss_first,
                                "step": int(rec["step"])}
    m, v, _ = _opt_parts(rec["opt"])
    at_kill = jax.device_put(at_kill)
    gp, _ = _max_gaps(rec["params"], at_kill["params"])
    gm, sm = _max_gaps((m, v), (at_kill["mu"], at_kill["nu"]))
    readings["kill_params_gap"] = float(jnp.max(gp)) / lr
    readings["kill_moments_gap"] = float(jnp.max(gm / jnp.maximum(sm, 1e-30)))
    del at_kill
    store = strat.store
    full = store.manifest["fulls"][-1]
    loaded = store.load_full(full)
    diffs = [d for d in store.diffs_after(int(full["step"])) if d[0] <= kill]
    run.payload_bytes = int(sum(a.nbytes + b.nbytes
                                for a, b in _payload_leaves(diffs[0][1])))
    lm, lv, lc = _opt_parts(loaded["opt"])
    put = lambda x: jax.tree.map(jnp.asarray, x)  # noqa: E731
    readings["replay_ref_gap"] = replay_gap(
        put(loaded["params"]), put(lm), put(lv), int(lc), diffs,
        rec["params"], lr)
    del rec, loaded, m, v
    strat.close()
    gc.collect()
    return peak, checks.resume_numbers(run, readings)


# ----------------------------------------------------------------------

def _peak_bytes() -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def read_metrics(run: Run, metrics: List[dict]) -> Dict[str, Dict]:
    """Each metric's reader, ``metrics/<name>.py``; a reader that finds
    nothing to read returns None and the metric is left out."""
    import importlib.util
    out = {}
    for m in metrics:
        path = spec.BENCH / "metrics" / f"{m['name']}.py"
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        value = mod.read(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, fault: Optional[str] = None) -> Dict:
    """Set up, measure and check one run; returns the result object.
    ``fault`` plants one of ``faults`` in the timed path (tests only)."""
    from repro.obs.timeline import TIMELINE
    from repro.obs.trace import TRACER
    run = Run(cell, seed, seconds, trace)
    dev = jax.local_devices()[0]
    run.peaks = peaks(dev.device_kind) if dev.platform == "tpu" else None
    TIMELINE.clear()
    if trace:
        TRACER.enable()
    trace_dir = None
    if trace:
        trace_dir = TRACE_ROOT / cell.name
        shutil.rmtree(trace_dir, ignore_errors=True)
    model = program_model(run.cfg, run.arch)
    strat = build_engine(run, model)
    body = _resume if run.mode == "resume" else _train
    peak, numbers = body(run, model, strat, t_start, trace_dir, fault)
    limits = cell.limits
    missing = sorted(set(numbers) - set(limits))
    if missing:
        raise spec.SpecError(f"no limit for {missing} in "
                             f"workloads/{cell.name}.json")
    compared = {k: {"value": float(v), "limit": float(limits[k])}
                for k, v in numbers.items()}
    correct = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
                  for c in compared.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {"correct": bool(correct),
                              "attempted": (len(run.resume_times)
                                            if run.mode == "resume"
                                            else run.steps),
                              "failed": 0}
    if trace:
        run.trace_summary = tracing.reduce(tracing.load_events(
            tracing.find_xplane(str(trace_dir))))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        result["metrics"] = read_metrics(run, cell.per_layer)
    else:
        result["metrics"] = read_metrics(run, cell.end_to_end)
    result["device"] = device
    if trace:
        result["breakdown"] = {
            "device_ops": run.trace_summary["device_ops"],
            "idle_gaps": run.trace_summary["idle_gaps"]}
    walls = sorted(run.step_walls or run.resume_times or [0.0])
    result["info"] = {"setup_s": run.setup_s, "window_s": run.window_s,
                      "wall_ms_min_median_max": [
                          1e3 * walls[0], 1e3 * walls[len(walls) // 2],
                          1e3 * walls[-1]],
                      "steps": run.steps, "resumes": len(run.resume_times),
                      "full_steps": run.full_steps,
                      "window_compiles": run.window_compiles,
                      "compile_cache_hits": compile_counter().hits}
    result["checks"] = compared
    return result


def report(result: Dict) -> None:
    """Stdout: the result object as its last line. Stderr: each number
    compared beside its limit, as its last lines."""
    print(json.dumps(result), flush=True)
    for k, c in result["checks"].items():
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    a = ap.parse_args(argv)
    cell = spec.load_cell(a.workload)
    try:
        require_chips(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    enable_compile_cache()
    compile_counter()
    report(run_cell(cell, a.seed, a.seconds, bool(a.trace), t_start))
    return 0
