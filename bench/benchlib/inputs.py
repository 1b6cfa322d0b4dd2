"""Weights and token batches made from ``--seed``.

The same seed gives the same weights and the same batches. Weights are
made on the device in one jitted call, in the layout the configuration's
architecture module gives (the program's parameter tree, stacked
layers) and in the configuration's parameter dtype. Batches draw every
row anew from a counter-based generator keyed by (seed, step), so no two
rows of a run repeat, and ids come from the configuration's (possibly
sliced) vocabulary.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    if seed < 0:
        raise ValueError(f"seed must be non-negative, got {seed}")
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    hi = seed >> 32
    while hi:
        key = jax.random.fold_in(key, np.uint32(hi & 0xFFFFFFFF))
        hi >>= 32
    return key


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 3 and isinstance(x[1], str)


def make_params(cfg: Dict[str, Any], key) -> Dict[str, Any]:
    """Traceable: the parameter tree for ``key`` (wrap in ``jax.jit``),
    in the layout of the configuration's architecture module."""
    dtype = jnp.dtype(cfg["param_dtype"])
    leaves, treedef = jax.tree.flatten(
        spec.reference_of(cfg).param_layout(cfg), is_leaf=_is_leaf)
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, (shape, init, std) in zip(keys, leaves):
        if init == "ones":
            out.append(jnp.ones(shape, dtype))
        elif init == "zeros":
            out.append(jnp.zeros(shape, dtype))
        elif init == "normal":
            out.append((jax.random.normal(k, shape, jnp.float32) * std)
                       .astype(dtype))
        else:
            raise spec.SpecError(f"unknown init {init!r} in the layout of "
                                 f"{spec.reference_of(cfg).__file__}")
    return jax.tree.unflatten(treedef, out)


def n_params(cfg: Dict[str, Any]) -> int:
    leaves = jax.tree.leaves(spec.reference_of(cfg).param_layout(cfg),
                             is_leaf=_is_leaf)
    return int(sum(np.prod(s) for s, _, _ in leaves))


def batch(cfg: Dict[str, Any], seed: int, step: int) -> Dict[str, np.ndarray]:
    """Host batch of step ``step``: ``batch`` rows of ``seq`` tokens and
    their next-token targets."""
    rng = np.random.default_rng([seed, step])
    toks = rng.integers(0, cfg["vocab"], (cfg["batch"], cfg["seq"] + 1),
                        dtype=np.int32)
    return {"tokens": np.ascontiguousarray(toks[:, :-1]),
            "targets": np.ascontiguousarray(toks[:, 1:])}


def shapes(tree) -> Tuple:
    return tuple((tuple(x.shape), str(x.dtype)) for x in jax.tree.leaves(tree))
