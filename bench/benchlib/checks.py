"""The numbers that decide ``correct``, each held to its own limit in
``workloads/<cell>.json``.

Train cells (program's first three steps against the reference's):

- ``loss_rel_gap``: the largest |loss - reference loss| / reference
  loss over steps 1-3.
- ``grad_norm_gap``: for the gradient Adam received at step 1 (read
  from the program's first moment after one step), the worst leaf's
  |norm - reference norm| over the larger of that leaf's reference
  norm and the median leaf's.
- ``param_change_gap``: the same for each leaf's change after three
  steps. Leaves whose reference gradient is under a thousandth of the
  median leaf's move under Adam by rounding alone and are left out.
- ``diff_identity_gap`` (LowDiff): the differential of step 2 read back
  from the store, applied by the reference's Adam to the program's
  state after step 1, against the program's parameters after step 2;
  the largest gap in units of the learning rate.
- ``chain_replay_gap`` (full saves in the window): the newest full read
  back and every later differential, replayed by the reference's Adam,
  against the program's parameters at the end of the window.

Resume cells: the recovered parameters (in learning rates) and moments
(relative to each leaf's largest) against the state the uninterrupted
run had at the kill; the recovered step; the loss of the first step
after the resume against the uninterrupted run's next loss; and the
chain read back and replayed by the reference's Adam against the
recovered parameters.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import inputs, reference as ref

#: a leaf whose reference gradient norm is under this share of the
#: median leaf's is left out of ``param_change_gap``
ROUNDING_ONLY = 1e-3


def reference_readings(cfg: Dict[str, Any], seed: int, *, rho: float,
                       lr: float, precision: str = "f32",
                       steps: int = 3) -> Dict[str, Any]:
    """The reference's (or, at ``fp8``, the control's) losses, step-1
    gradient norms and change norms after ``steps`` steps."""
    key = inputs.seed_key(seed)
    state = jax.jit(lambda k: ref.init_state(inputs.make_params(cfg, k)))(key)
    items = ref.hashable(cfg)
    losses, grad_norms = [], None
    for n in range(1, steps + 1):
        b = inputs.batch(cfg, seed, n)
        state, loss, norms = ref.train_step(
            state, jnp.asarray(b["tokens"]), jnp.asarray(b["targets"]), lr,
            cfg_items=items, precision=precision, rho=rho)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = np.asarray(norms)
    change = np.asarray(change_norms(cfg)(state.params, key))
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change}


def change_norms(cfg):
    """Jitted (params, key) -> per-leaf norms of params minus the
    weights the seed's key makes."""
    @jax.jit
    def f(params, key):
        p0 = inputs.make_params(cfg, key)
        return jnp.stack([jnp.linalg.norm((a.astype(jnp.float32)
                                           - b.astype(jnp.float32))
                                          .reshape(-1))
                          for a, b in zip(jax.tree.leaves(params),
                                          jax.tree.leaves(p0))])
    return f


def worst_leaf_gap(prog, refr, keep=None) -> float:
    prog, refr = np.asarray(prog, np.float64), np.asarray(refr, np.float64)
    keep = np.ones(refr.shape, bool) if keep is None else keep
    med = float(np.median(refr[keep]))
    denom = np.maximum(refr, med)
    return float(np.max(np.abs(prog - refr)[keep] / denom[keep]))


def compare_training(prog: Dict[str, Any], refr: Dict[str, Any]
                     ) -> Dict[str, float]:
    lp, lr_ = np.asarray(prog["losses"]), np.asarray(refr["losses"])
    gn = np.asarray(refr["grad_norms"], np.float64)
    keep = gn >= ROUNDING_ONLY * np.median(gn)
    return {"loss_rel_gap": float(np.max(np.abs(lp - lr_) / np.abs(lr_))),
            "grad_norm_gap": worst_leaf_gap(prog["grad_norms"], gn),
            "param_change_gap": worst_leaf_gap(prog["change_norms"],
                                               refr["change_norms"], keep)}


def train_numbers(run, readings: Dict[str, Any]) -> Dict[str, float]:
    eng = run.traffic["engine"]
    rho = eng["rho"] if eng["strategy"] == "lowdiff" else 0.0
    out = compare_training(readings, reference_readings(
        run.cfg, run.seed, rho=rho, lr=eng["lr"]))
    for k in ("diff_identity_gap", "chain_replay_gap"):
        if k in readings:
            out[k] = readings[k]
    if eng["strategy"] == "lowdiff" and "diff_identity_gap" not in out:
        out["diff_identity_gap"] = float("inf")
    if run.full_steps and "chain_replay_gap" not in out:
        out["chain_replay_gap"] = float("inf")
    return out


def resume_numbers(run, readings: Dict[str, Any]) -> Dict[str, float]:
    return {"recover_params_gap": readings["kill_params_gap"],
            "recover_moments_gap": readings["kill_moments_gap"],
            "recover_step_gap": float(abs(readings["step"]
                                          - readings["kill"])),
            "resume_loss_gap": abs(readings["loss_first"]
                                   - readings["loss_next"])
            / abs(readings["loss_next"]),
            "replay_ref_gap": readings["replay_ref_gap"]}
