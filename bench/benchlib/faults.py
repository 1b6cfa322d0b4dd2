"""Faults planted in the timed path, to show that the comparison sees
them (``tests/test_faults.py`` on the CPU, ``calibrate.py --faults`` on
the chip). One chip has no exchange between chips, so that fault has
no place here.

- ``state_unchanged``: the step returns the state it was given.
- ``half_batch``: half of every batch is left out of the mean (its rows,
  or with a single row the second half of its positions).
- ``diff_altered``: the differential the step hands to the queue has one
  value altered where it is produced; the update used the true one.
- ``replay_skipped``: recovery loads the newest full and applies none of
  the differentials after it.
- ``recovered_altered``: one recovered parameter is altered.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

TRAIN = ("state_unchanged", "half_batch", "diff_altered")
RESUME = ("replay_skipped", "recovered_altered")


def train_faults(cell):
    lowdiff = cell.traffic["engine"]["strategy"] == "lowdiff"
    return [f for f in TRAIN if f != "diff_altered" or lowdiff]


def half_batch_feed(feed, cfg):
    B, S = cfg["batch"], cfg["seq"]
    mask = np.ones((B, S), np.float32)
    if B > 1:
        mask[B // 2:] = 0.0
    else:
        mask[:, S // 2:] = 0.0

    def fed(n):
        b = dict(feed(n))
        b["loss_mask"] = jax.device_put(mask)
        return b
    return fed


def _alter_first(cg):
    leaves, td = jax.tree.flatten(cg, is_leaf=lambda x: hasattr(x,
                                                                "indices"))
    x = leaves[0]
    vals = x.values.at[0, 0].set(-2.0 * x.values[0, 0] + 1e-3)
    leaves[0] = type(x)(vals, x.indices, x.shape, x.block)
    return jax.tree.unflatten(td, leaves)


def apply(name, stepper, strat):
    """Plant fault ``name`` in the stepper (train) or engine (resume)."""
    if name == "half_batch":
        stepper.feed = half_batch_feed(stepper.feed, stepper.run.cfg)
    elif name in ("state_unchanged", "diff_altered"):
        host = strat if strat is not None else stepper
        attr = "step_fn" if strat is not None else "dense"
        orig = getattr(host, attr)

        def step(state, batch):
            new, metrics, cg = orig(state, batch)
            if name == "state_unchanged":
                return state, metrics, cg
            return new, metrics, _alter_first(cg)
        setattr(host, attr, step)
    elif name == "replay_skipped":
        from repro.core import recovery as rec

        def recover():
            state, _ = rec.load_latest_chain(strat.store)
            return state, 0
        strat.recover = recover
    elif name == "recovered_altered":
        orig = strat.recover

        def recover():
            state, n = orig()
            leaves, td = jax.tree.flatten(state["params"])
            leaves[0] = jnp.asarray(leaves[0]).at[(0,) * leaves[0].ndim].add(
                0.01)
            state["params"] = jax.tree.unflatten(td, leaves)
            return state, n
        strat.recover = recover
    else:
        raise ValueError(f"unknown fault {name!r}")
