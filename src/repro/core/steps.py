"""Jitted training-step builders with LowDiff integrated as a first-class
feature.

Modes:
  dense         — plain Adam step (baselines; checkpoint reads the state).
  lowdiff       — paper Algorithm 1 training process: compress the
                  synchronized gradient, *update the model from the
                  decompressed compressed gradient* (that identity is what
                  makes G̃_t an exact differential checkpoint), return G̃_t
                  as an extra jit output for the Reusing Queue.
  lowdiff_plus  — §VI: no compression; the dense gradient is the extra
                  output, streamed leaf-by-leaf ("layer-wise") to the host.

Gradient accumulation (cfg.grad_accum) scans over microbatches inside the
step — the accumulated gradient is what gets compressed/checkpointed,
exactly as a DeepSpeed gradient-accumulation boundary would.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.compression.error_feedback import ef_compress_tree_with, ef_init
from repro.optim.adam import adam_init, adam_update


def init_state(model, rng, *, mode: str = "lowdiff",
               error_feedback: bool = True) -> Dict[str, Any]:
    params = model.init(rng)
    state = {"params": params, "opt": adam_init(params),
             "step": jnp.zeros((), jnp.int32)}
    if mode == "lowdiff" and error_feedback:
        state["ef"] = ef_init(params)
    return state


def _grads(model, params, batch, accum: int):
    acc_dt = jnp.dtype(model.cfg.grad_accum_dtype)
    if accum <= 1:
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def micro(i, batch):
        return jax.tree.map(
            lambda x: x.reshape((accum, -1) + x.shape[1:])[i]
            if x.ndim >= 1 else x, batch)

    def body(carry, i):
        acc, loss_acc = carry
        (loss, _), g = jax.value_and_grad(model.loss_fn, has_aux=True)(
            params, micro(i, batch))
        acc = jax.tree.map(lambda a, b: a + b.astype(acc_dt), acc, g)
        return (acc, loss_acc + loss), None

    from repro.models.ops import scan_unroll
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, acc_dt), params)
    (gsum, loss_sum), _ = jax.lax.scan(body, (zeros, jnp.float32(0)),
                                       jnp.arange(accum),
                                       unroll=scan_unroll())
    grads = jax.tree.map(lambda g: g / accum, gsum)
    loss = loss_sum / accum
    return loss, {"xent": loss, "aux": jnp.float32(0),
                  "tokens": jnp.float32(0)}, grads


def _topk_tree(grads, ef, rho: float):
    """(wire, dense picks, new residual) of every leaf: one fused pass
    of ``kernels.ops.ef_topk_compress`` per leaf. Without error feedback
    (``ef`` None) the residual tree is None."""
    from repro.kernels.ops import ef_topk_compress
    g_flat, treedef = jax.tree.flatten(grads)
    e_flat = ([None] * len(g_flat) if ef is None
              else treedef.flatten_up_to(ef))
    outs = [ef_topk_compress(g, e, rho) for g, e in zip(g_flat, e_flat)]
    cg, dense, res = ([o[i] for o in outs] for i in range(3))
    return (jax.tree.unflatten(treedef, cg), jax.tree.unflatten(treedef, dense),
            None if ef is None else jax.tree.unflatten(treedef, res))


def make_train_step(model, *, mode: str = "lowdiff", rho: float = 0.01,
                    lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                    eps: float = 1e-8, error_feedback: bool = True,
                    compressor: str = "topk", jit: bool = True):
    """``compressor``: 'topk' (sparsification, paper default), 'quant8'
    (blockwise int8 — the paper's other §II-C compression family) or
    'packed' (fused top-k + int8 quantize + wire pack — the differential
    leaves the device already in frame layout). All produce reusable
    differential checkpoints; EF applies to topk and packed.

    Each phase runs under a ``jax.named_scope`` (``fwd_bwd``,
    ``compress`` with error feedback, ``decompress``, ``adam``): the
    compiled ops carry it in their ``op_name`` metadata, so a profiler
    trace can split the step's device time by phase. The ``topk``
    compress is one fused kernel pass per leaf that also emits the
    decompressed gradient, so that step has no ``decompress`` ops."""
    cfg = model.cfg
    accum = cfg.grad_accum

    def step(state, batch):
        params = state["params"]
        with jax.named_scope("fwd_bwd"):
            loss, metrics, grads = _grads(model, params, batch, accum)
        g_upd, ef, extra = grads, None, None
        if mode == "lowdiff":
            with_ef = error_feedback and "ef" in state
            if compressor == "quant8":
                from repro.compression.quant import (quant_compress,
                                                     quant_decompress)
                with jax.named_scope("compress"):
                    cg = jax.tree.map(quant_compress, grads)
                with jax.named_scope("decompress"):
                    g_upd = jax.tree.map(
                        quant_decompress, cg,
                        is_leaf=lambda x: hasattr(x, "scale"))
            elif compressor == "packed":
                from repro.compression.packed import PackedDiff
                from repro.kernels.ops import (packed_compress,
                                               packed_decompress)
                is_pd = lambda x: isinstance(x, PackedDiff)  # noqa: E731
                with jax.named_scope("compress"):
                    if with_ef:
                        cg, ef = ef_compress_tree_with(
                            grads, state["ef"],
                            lambda g: packed_compress(g, rho),
                            packed_decompress)
                    else:
                        cg = jax.tree.map(lambda g: packed_compress(g, rho),
                                          grads)
                with jax.named_scope("decompress"):
                    g_upd = jax.tree.map(packed_decompress, cg,
                                         is_leaf=is_pd)
            else:
                with jax.named_scope("compress"):
                    cg, g_upd, ef = _topk_tree(
                        grads, state["ef"] if with_ef else None, rho)
            extra = cg
        elif mode == "lowdiff_plus":
            extra = grads
        with jax.named_scope("adam"):
            params2, opt2 = adam_update(params, g_upd, state["opt"], lr=lr,
                                        b1=b1, b2=b2, eps=eps)
        new_state = {"params": params2, "opt": opt2,
                     "step": state["step"] + 1}
        if ef is not None:
            new_state["ef"] = ef
        metrics = dict(metrics, loss=loss)
        return new_state, metrics, extra

    return jax.jit(step) if jit else step
