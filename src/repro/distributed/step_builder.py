"""Sharded (pjit) train / serve step builders for the production mesh.

Builds the in/out shardings for the full train state (params + Adam
moments + error feedback), the batch, and the decode cache from the
models' logical axes, with divisibility-safe fallback, and returns
``jax.jit``-wrapped steps ready to ``.lower()`` (dry-run) or execute.

LowDiff integration on a sharded mesh: gradients live sharded (FSDP x
TP); compression must be *shard-local* (a global reshape of a 405B
gradient would gather it). ``compress_sharded`` wraps the block top-k in
a shard_map so each device compresses — and later checkpoints — exactly
its own gradient slice. The differential checkpoint is therefore sharded
the same way as the optimizer state, and recovery is shard-local too
(an extension beyond the paper).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.compression.sparse import SparseGrad, k_for, topk_compress
from repro.core.steps import make_train_step
from repro.data.synthetic import input_specs
from repro.distributed import sharding as shd
from repro.models.param import ParamSpec, abstractify, is_spec
from repro.optim.adam import AdamState, adam_init, adam_update


# --------------------------------------------------------------------------
# sharding trees for the train state
# --------------------------------------------------------------------------

def param_shardings(model):
    abs_params = model.abstract_params()
    return shd.safe_sharding_tree(abs_params, model.logical_axes())


def state_shardings(model, *, mode: str = "lowdiff",
                    error_feedback: bool = True) -> Dict[str, Any]:
    ctx = shd.current()
    psh = param_shardings(model)
    rep = NamedSharding(ctx.mesh, P())
    out = {"params": psh,
           "opt": AdamState(mu=psh, nu=psh, count=rep),
           "step": rep}
    if mode == "lowdiff" and error_feedback:
        out["ef"] = psh
    return out


def abstract_state(model, *, mode: str = "lowdiff",
                   error_feedback: bool = True) -> Dict[str, Any]:
    sh = state_shardings(model, mode=mode, error_feedback=error_feedback)
    pdt = model.cfg.pdtype()

    def leaf(spec: ParamSpec, s, dtype=None):
        dt = jnp.dtype(spec.dtype) if spec.dtype else (dtype or pdt)
        return jax.ShapeDtypeStruct(spec.shape, dt, sharding=s)

    params = jax.tree.map(leaf, model.specs, sh["params"], is_leaf=is_spec)
    f32 = functools.partial(leaf, dtype=jnp.float32)
    mu = jax.tree.map(f32, model.specs, sh["opt"].mu, is_leaf=is_spec)
    nu = jax.tree.map(f32, model.specs, sh["opt"].nu, is_leaf=is_spec)
    out = {"params": params,
           "opt": AdamState(mu, nu, jax.ShapeDtypeStruct(
               (), jnp.int32, sharding=sh["step"])),
           "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=sh["step"])}
    if "ef" in sh:
        out["ef"] = jax.tree.map(f32, model.specs, sh["ef"], is_leaf=is_spec)
    return out


def batch_shardings(model, shape_cfg):
    ctx = shd.current()
    logical = {"tokens": ("batch", None), "targets": ("batch", None),
               "loss_mask": ("batch", None),
               "patch_embeds": ("batch", None, None),
               "src_embeds": ("batch", None, None), "pos": ()}
    specs = input_specs(model.cfg, shape_cfg)
    return {k: NamedSharding(ctx.mesh,
                             shd.safe_spec(v.shape, ctx.spec(logical[k]),
                                           ctx.mesh))
            for k, v in specs.items()}


def abstract_batch(model, shape_cfg):
    sh = batch_shardings(model, shape_cfg)
    return input_specs(model.cfg, shape_cfg, shardings=sh)


# --------------------------------------------------------------------------
# shard-local gradient compression (shard_map)
# --------------------------------------------------------------------------

def compress_sharded(grads, pspecs, mesh, rho: float):
    """Blockwise top-k on each device's *local* gradient shard."""
    leaves, treedef = jax.tree.flatten(grads)
    spec_leaves = treedef.flatten_up_to(pspecs)

    def out_spec(spec: P) -> P:
        used = []
        for ax in spec:
            if ax is None:
                continue
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                used.append(a)
        first = tuple(used) if used else None
        return (P(first, None), P(first, None))

    outs = []
    for g, spec in zip(leaves, spec_leaves):
        sp = spec.spec if isinstance(spec, NamedSharding) else spec

        def local(x):
            sg = topk_compress(x, rho)
            return sg.values, sg.indices

        fn = jax.shard_map(local, mesh=mesh, in_specs=(sp,),
                           out_specs=out_spec(sp), check_vma=False)
        vals, idx = fn(g)
        # NOTE: block order follows the shard layout (each device's local
        # flatten); the differential checkpoint is saved and replayed
        # per-shard with the same sharding, so order is consistent.
        outs.append(SparseGrad(vals, idx, g.shape))
    return jax.tree.unflatten(treedef, outs)


# --------------------------------------------------------------------------
# step builders
# --------------------------------------------------------------------------

def effective_accum(cfg_accum: int, global_batch: int, dp: int) -> int:
    """Largest accum <= cfg_accum such that the microbatch still spans
    the data-parallel shards evenly."""
    limit = max(1, global_batch // dp)
    a = min(cfg_accum, limit)
    while a > 1 and (global_batch % a or (global_batch // a) % dp):
        a -= 1
    return max(a, 1)


def make_sharded_train_step(model, shape_cfg, *, mode: str = "dense",
                            rho: float = 0.01, lr: float = 1e-3,
                            error_feedback: bool = False,
                            donate: bool = True):
    """Returns (jitted_step, abstract_state, abstract_batch)."""
    ctx = shd.current()
    mesh = ctx.mesh
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.devices.shape[mesh.axis_names.index(a)]
    accum = effective_accum(model.cfg.grad_accum, shape_cfg.global_batch, dp)
    from repro.models.registry import build_model
    model = build_model(model.cfg.replace(grad_accum=accum))

    st_sh = state_shardings(model, mode=mode, error_feedback=error_feedback)

    if mode == "lowdiff_sharded":
        # paper-faithful step with the differential-checkpoint output: the
        # dense step emits the synchronized gradient; compression happens
        # shard-locally so no gather of a sharded gradient ever occurs.
        inner = make_train_step(model, mode="lowdiff_plus", rho=rho, lr=lr,
                                jit=False)
        pspecs = jax.tree.map(lambda s: s.spec, st_sh["params"])

        def step(state, batch):
            new_state, metrics, grads = inner(state, batch)
            cg = compress_sharded(grads, pspecs, mesh, rho)
            return new_state, metrics, cg
    else:
        step = make_train_step(model, mode=mode, rho=rho, lr=lr,
                               error_feedback=error_feedback, jit=False)

    jstep = jax.jit(
        step,
        in_shardings=(st_sh, batch_shardings(model, shape_cfg)),
        out_shardings=(st_sh, None, None),
        donate_argnums=(0,) if donate else (),
    )
    return jstep, abstract_state(model, mode=mode,
                                 error_feedback=error_feedback), \
        abstract_batch(model, shape_cfg)


def make_sharded_prefill_step(model, shape_cfg):
    """Full-sequence forward to final-position logits (inference prefill)."""
    psh = param_shardings(model)
    abs_params = jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        model.abstract_params(), psh)
    jstep = jax.jit(model.logits_fn,
                    in_shardings=(psh, batch_shardings(model, shape_cfg)),
                    out_shardings=None)
    return jstep, abs_params, abstract_batch(model, shape_cfg)


def make_sharded_serve_step(model, shape_cfg, *, donate: bool = True):
    """Single-token decode step with sharded KV cache."""
    ctx = shd.current()
    seq_len = shape_cfg.seq_len
    B = shape_cfg.global_batch

    psh = param_shardings(model)
    abs_params = jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        model.abstract_params(), psh)
    cache_abs = model.init_cache(B, seq_len, abstract=True)
    cache_sh = shd.safe_sharding_tree(cache_abs, model.cache_logical())
    cache_abs = jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        cache_abs, cache_sh)
    bsh = batch_shardings(model, shape_cfg)
    babs = abstract_batch(model, shape_cfg)

    def step(params, cache, batch):
        return model.decode_step(params, cache, batch, seq_len)

    jstep = jax.jit(step,
                    in_shardings=(psh, cache_sh, bsh),
                    out_shardings=(None, cache_sh),
                    donate_argnums=(1,) if donate else ())
    return jstep, abs_params, cache_abs, babs
