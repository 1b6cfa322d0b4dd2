"""Device time of the train step split by the program's named scopes.

The program runs each phase of its step under a ``jax.named_scope``
(``core/steps.py``): ``fwd_bwd``, ``compress`` (error feedback
included), ``decompress`` and ``adam``. The compiled instructions keep
the scope in their ``op_name`` metadata, but the trace reduction keeps
only each op's device seconds by op name (``op_s``). So this module
compiles the step the cell ran, maps every instruction of the compiled
module to the outermost scope in its ``op_name``, and sums ``op_s`` by
scope. Ops with no scope, and ops of other programs, go under
``other``.

It runs in traced runs only, after the window: the compile is a read
of the persistent cache that set-up filled, or a compile outside every
measured span. A program whose step has no such scope gives None.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

SCOPES = ("fwd_bwd", "compress", "decompress", "adam")
OTHER = "other"

_COMP = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*? ([a-z][\w\-]*)\((.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str:
    """The outermost of ``SCOPES`` among the ``/``-parts of an op_name
    (``jit(step)/jit(main)/compress/...`` is ``compress``)."""
    for part in op_name.split("/"):
        if part in SCOPES:
            return part
    return OTHER


def _parse(hlo_text: str):
    """{computation: [(name, op_name | None, callees, operands)]}."""
    comps: Dict[str, list] = {}
    body = None
    for line in hlo_text.splitlines():
        if body is None:
            m = _COMP.match(line)
            if m:
                body = comps.setdefault(m.group(1), [])
            continue
        if line.startswith("}"):
            body = None
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        rest = m.group(3)
        op = _OP_NAME.search(rest)
        callees = _CALLS.findall(rest)
        for group in _BRANCHES.findall(rest):
            callees += [c.strip().lstrip("%") for c in group.split(",")]
        args = rest.split(")", 1)[0]
        body.append((m.group(1), op.group(1) if op else None, callees,
                     _OPERAND.findall(args)))
    return comps


def scope_map(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> scope, for every instruction of a compiled
    module's text. An instruction's own ``op_name`` decides; one the
    compiler made without an op_name takes the scope of the
    computations it calls (a fusion's body), else of its operands (the
    phase that produced its input), else of the instruction that
    calls its computation (a loop's body)."""
    comps = _parse(hlo_text)
    instrs = {i[0]: i for c in comps.values() for i in c}
    caller = {callee: i[0] for c in comps.values() for i in c
              for callee in i[2]}
    owner = {i[0]: comp for comp, c in comps.items() for i in c}
    memo: Dict[str, str] = {}

    def of_comp(comp: str, seen) -> str:
        for i in reversed(comps.get(comp, [])):       # ROOT first
            s = of(i[0], seen)
            if s != OTHER:
                return s
        return OTHER

    def of(name: str, seen=frozenset()) -> str:
        if name in memo:
            return memo[name]
        if name in seen or name not in instrs:
            return OTHER
        seen = seen | {name}
        _, op_name, callees, operands = instrs[name]
        if op_name is not None:
            s = scope_of(op_name)
        else:
            s = next((x for x in (of_comp(c, seen) for c in callees)
                      if x != OTHER), OTHER)
            if s == OTHER:
                s = next((x for x in (of(o, seen) for o in operands)
                          if x != OTHER), OTHER)
            if s == OTHER and owner[name] in caller:
                s = of(caller[owner[name]], seen)
        memo[name] = s
        return s

    return {name: of(name) for name in instrs}


def scope_seconds(op_s: Dict[str, float],
                  names: Dict[str, str]) -> Dict[str, float]:
    """Device seconds of ``op_s`` (keyed as the trace names ops) summed
    by the scope ``names`` gives each op's short name. Ops that only
    hold others (a scan's ``while``) are left out: their bodies' ops
    are counted themselves."""
    from benchlib.tracing import CONTAINERS, short_name
    out = {s: 0.0 for s in SCOPES + (OTHER,)}
    for op, secs in op_s.items():
        name = short_name(op)
        if not name.startswith(CONTAINERS):
            out[names.get(name, OTHER)] += secs
    return out


def step_text(run) -> str:
    """The compiled text of the train step the cell's engine runs, for
    the state and batch the harness gives it."""
    import jax
    import jax.numpy as jnp

    from benchlib import harness, inputs
    from repro.core.engine import EngineConfig
    from repro.core.steps import make_train_step
    eng = EngineConfig(**run.traffic["engine"])
    model = harness.program_model(run.cfg)
    if eng.strategy == "lowdiff":
        fn = make_train_step(model, mode="lowdiff", rho=eng.rho, lr=eng.lr,
                             compressor=eng.compressor)
    else:
        fn = make_train_step(model, mode="dense", lr=eng.lr)
    state = jax.eval_shape(lambda: harness.program_state(run, model))
    batch = jax.eval_shape(lambda: jax.tree.map(
        jnp.asarray, inputs.batch(run.cfg, run.seed, 1)))
    return fn.lower(state, batch).compile().as_text()


def step_scope_seconds(run) -> Optional[Dict[str, float]]:
    """Device seconds of the traced window by scope, or None where the
    run was not traced, is not a training run, timed no device op, or
    ran a step with none of the scopes. Computed once per run, and kept
    on it for the other readers."""
    if run.mode != "train" or run.trace_summary is None \
            or not run.trace_summary["op_s"]:
        return None
    if not hasattr(run, "scope_s"):
        names = scope_map(step_text(run))
        run.scope_s = (scope_seconds(run.trace_summary["op_s"], names)
                       if any(s != OTHER for s in names.values()) else None)
    return run.scope_s


def step_ms(run, scope: str) -> Optional[float]:
    """Device milliseconds per window step in ``scope``, or None."""
    s = step_scope_seconds(run)
    return 1e3 * s[scope] / run.steps if s is not None and run.steps \
        else None
