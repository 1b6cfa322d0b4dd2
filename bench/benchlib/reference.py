"""The plain reference: the configuration's loss and gradients, blockwise
top-k with error feedback, and Adam, written from the published
equations in straightforward ``jax.numpy``. The loss is the
architecture module's (``references/<reference>.py``, by
``spec.reference_of``); this file holds what every architecture shares.

It imports nothing of the program and takes nothing the program made:
weights come from ``inputs.make_params`` and the seed. Matrix products
run at float32 ``HIGHEST`` precision; ``precision="fp8"`` is the control,
which rounds both operands of every product to float8 e4m3 and the
gradient flowing back into it to e5m2 (per-tensor scales), and so
computes one step below the bf16 products the configurations state.

Memory: an architecture module recomputes its layers in the backward
pass (``dense`` also computes attention in blocks of query rows), so a
step at the cells' sizes fits one chip beside nothing else. The state is
donated.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import spec

B1, B2, EPS = 0.9, 0.999, 1e-8
TOPK_BLOCK = 1024
_HI = jax.lax.Precision.HIGHEST


def _fp8(x, dtype=jnp.float8_e4m3fn):
    """Round to an fp8 format under a per-tensor scale that maps the
    largest magnitude to the format's largest value."""
    x = x.astype(jnp.float32)
    scale = float(jnp.finfo(dtype).max) / jnp.maximum(jnp.max(jnp.abs(x)),
                                                      1e-30)
    return (x * scale).astype(dtype).astype(jnp.float32) / scale


@functools.lru_cache(maxsize=None)
def _fp8_einsum(spec: str):
    """An einsum whose operands are rounded to e4m3 and whose incoming
    gradient is rounded to e5m2 in the backward pass (the usual fp8
    training recipe); products accumulate in float32."""
    def mm(a, b):
        return jnp.einsum(spec, a, b, precision=_HI)

    @jax.custom_vjp
    def f(a, b):
        return mm(_fp8(a), _fp8(b))

    def fwd(a, b):
        qa, qb = _fp8(a), _fp8(b)
        return mm(qa, qb), (qa, qb)

    def bwd(res, ct):
        _, vjp = jax.vjp(mm, *res)
        return vjp(_fp8(ct, jnp.float8_e5m2))

    f.defvjp(fwd, bwd)
    return f


def matmul(precision: str):
    if precision == "f32":
        return lambda spec, a, b: jnp.einsum(spec, a, b, precision=_HI)
    if precision == "fp8":
        return lambda spec, a, b: _fp8_einsum(spec)(a, b)
    raise ValueError(f"unknown precision {precision!r}")


def topk_keep(x, rho: float):
    """Keep the ceil(rho * 1024) entries of largest magnitude in every
    block of 1024 consecutive elements of the flattened tensor."""
    k = max(1, math.ceil(rho * TOPK_BLOCK))
    flat = x.reshape(-1)
    pad = (-flat.size) % TOPK_BLOCK
    xb = jnp.pad(flat, (0, pad)).reshape(-1, TOPK_BLOCK)
    thr = jax.lax.top_k(jnp.abs(xb), k)[0][:, -1:]
    kept = jnp.where(jnp.abs(xb) >= thr, xb, 0.0)
    return kept.reshape(-1)[:flat.size].reshape(x.shape)


def decompress(values, indices, shape):
    """Dense tensor of a blockwise top-k payload: ``values`` and
    block-local ``indices`` are (blocks, k)."""
    nb = values.shape[0]
    rows = jnp.arange(nb)[:, None]
    dense = jnp.zeros((nb, TOPK_BLOCK), jnp.float32)
    dense = dense.at[rows, indices.astype(jnp.int32)].set(
        values.astype(jnp.float32))
    n = int(np.prod(shape)) if shape else 1
    return dense.reshape(-1)[:n].reshape(shape)


def adam(p, g, m, v, t, lr):
    """One Adam step of one leaf; ``t`` is the step count after it."""
    m = B1 * m + (1.0 - B1) * g
    v = B2 * v + (1.0 - B2) * g * g
    mhat = m / (1.0 - B1 ** t)
    vhat = v / (1.0 - B2 ** t)
    return p - lr * mhat / (jnp.sqrt(vhat) + EPS), m, v


class State(NamedTuple):
    params: Any
    m: Any
    v: Any
    e: Any          #: error-feedback residual (zeros when unused)
    t: Any          #: Adam step count


def init_state(params) -> State:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return State(params, zeros, jax.tree.map(jnp.copy, zeros),
                 jax.tree.map(jnp.copy, zeros), jnp.zeros((), jnp.int32))


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision",
                                             "rho"),
                   donate_argnums=0)
def train_step(state: State, tokens, targets, lr, *, cfg_items, precision,
               rho):
    """One training step: loss and gradient, then (rho > 0) top-k of
    gradient plus residual with the residual kept, then Adam. Returns
    (state, loss, per-leaf norms of the gradient Adam received)."""
    cfg = dict(cfg_items)
    mm = matmul(precision)
    loss_fn = spec.reference_of(cfg).loss_fn
    loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens, targets,
                                              cfg, mm)
    if rho > 0:
        corrected = jax.tree.map(jnp.add, grads, state.e)
        g = jax.tree.map(lambda c: topk_keep(c, rho), corrected)
        e = jax.tree.map(jnp.subtract, corrected, g)
    else:
        g, e = grads, state.e
    t = state.t + 1
    tf = t.astype(jnp.float32)
    out = jax.tree.map(lambda p, gg, m, v: adam(p, gg, m, v, tf, lr),
                       state.params, g, state.m, state.v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,  # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    norms = [jnp.linalg.norm(x.reshape(-1)) for x in jax.tree.leaves(g)]
    return State(pick(0), pick(1), pick(2), e, t), loss, jnp.stack(norms)


def hashable(cfg: Dict[str, Any]):
    """The configuration's scalar entries, as a static jit argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, bool))))
