"""Capacity-based top-k Mixture-of-Experts layer (expert-parallel).

Dispatch is the cumsum/position-in-expert formulation (Switch/T5X style),
realized with gather/scatter instead of the (tokens, experts, capacity)
one-hot einsum — the one-hot dispatch tensor is infeasible at the assigned
scales (1M tokens x 128 experts x 80k capacity). Experts are sharded over
the ``model`` mesh axis ('experts' logical axis); GSPMD turns the
scatter/gather into the expert-parallel all-to-all pattern.

DeepSeekMoE-style shared experts are dense SwiGLU paths added on top.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import shard
from repro.models.param import ParamSpec


def moe_specs(cfg: ArchConfig, layers: int) -> dict:
    d = cfg.d_model
    m = cfg.moe
    L = (layers,)
    specs = {
        "router": ParamSpec(L + (d, m.n_experts), ("layers", "fsdp", "experts")),
        "we_g": ParamSpec(L + (m.n_experts, d, m.expert_ff),
                          ("layers", "experts", "fsdp", "expert_mlp")),
        "we_u": ParamSpec(L + (m.n_experts, d, m.expert_ff),
                          ("layers", "experts", "fsdp", "expert_mlp")),
        "we_d": ParamSpec(L + (m.n_experts, m.expert_ff, d),
                          ("layers", "experts", "expert_mlp", "fsdp")),
    }
    if m.n_shared:
        f = m.expert_ff * m.n_shared
        specs["ws_g"] = ParamSpec(L + (d, f), ("layers", "fsdp", "mlp"))
        specs["ws_u"] = ParamSpec(L + (d, f), ("layers", "fsdp", "mlp"))
        specs["ws_d"] = ParamSpec(L + (f, d), ("layers", "mlp", "fsdp"))
    return specs


def capacity(T: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(math.ceil(T * m.top_k * m.capacity_factor / m.n_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


def moe_apply(p, x, cfg: ArchConfig):
    """x: (B, S, d) -> (out, aux_loss).

    With an active mesh whose 'model' axis divides the expert count, this
    dispatches to the shard_map expert-parallel path (each model rank owns
    E/ep experts and processes its data shard's assignments locally — no
    token all-to-all; outputs combine with one psum_scatter). Without a
    mesh (CPU tests) it runs the GSPMD/dense-dispatch reference path.
    """
    from repro.distributed import sharding as shd
    ctx = shd.current()
    T = x.shape[0] * x.shape[1]
    if ctx is not None and "model" in ctx.mesh.axis_names:
        ep = ctx.mesh.devices.shape[ctx.mesh.axis_names.index("model")]
        # EP pays one expert-weight gather per rank per layer; only worth
        # it when there is real token work (training/prefill). Decode
        # (a handful of tokens) keeps weights sharded and moves tokens.
        if ep > 1 and cfg.moe.n_experts % ep == 0 \
                and T >= 16 * cfg.moe.n_experts:
            return _moe_apply_ep(p, x, cfg, ctx, ep)
    return _moe_apply_dense(p, x, cfg)


def _moe_apply_dense(p, x, cfg: ArchConfig):
    """Reference dispatch (single device / arbitrary sharding)."""
    B, S, d = x.shape
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    T = B * S
    C = capacity(T, cfg)
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf, p["router"].astype(x.dtype))
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)   # (T,E)
    gate, eid = jax.lax.top_k(probs, K)                            # (T,K)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # Load-balance auxiliary loss (Switch): E * mean_e(frac_e * prob_e)
    oh = jax.nn.one_hot(eid, E, dtype=jnp.float32)                 # (T,K,E)
    frac = oh.sum(axis=(0, 1)) / (T * K)
    aux = E * jnp.sum(frac * probs.mean(axis=0))

    # position-in-expert via cumsum over flattened (T*K) assignments
    oh_flat = oh.reshape(T * K, E)
    pos = jnp.cumsum(oh_flat, axis=0) - oh_flat                    # (T*K,E)
    pos_in_e = jnp.einsum("ae,ae->a", pos, oh_flat).astype(jnp.int32)
    eid_flat = eid.reshape(T * K)
    valid = pos_in_e < C
    dest = jnp.where(valid, eid_flat * C + pos_in_e, E * C)        # drop slot

    # scatter per k-slot (K small) to avoid materializing (T*K, d)
    dest_k = dest.reshape(T, K)
    buf = jnp.zeros((E * C + 1, d), x.dtype)
    for kk in range(K):
        buf = buf.at[dest_k[:, kk]].add(xf)
    xe = buf[: E * C].reshape(E, C, d)
    xe = shard(xe, "experts", None, None)

    g = jnp.einsum("ecd,edf->ecf", xe, p["we_g"].astype(x.dtype))
    u = jnp.einsum("ecd,edf->ecf", xe, p["we_u"].astype(x.dtype))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = shard(h, "experts", None, "expert_mlp")
    ye = jnp.einsum("ecf,efd->ecd", h, p["we_d"].astype(x.dtype))
    ye = shard(ye, "experts", None, None)

    y_flat = jnp.concatenate([ye.reshape(E * C, d),
                              jnp.zeros((1, d), x.dtype)], axis=0)
    valid_k = valid.reshape(T, K)
    y = jnp.zeros((T, d), x.dtype)
    for kk in range(K):
        w = (gate[:, kk] * valid_k[:, kk]).astype(x.dtype)[:, None]
        y = y + y_flat[dest_k[:, kk]] * w

    if m.n_shared:
        gs = jnp.einsum("td,df->tf", xf, p["ws_g"].astype(x.dtype))
        us = jnp.einsum("td,df->tf", xf, p["ws_u"].astype(x.dtype))
        hs = jax.nn.silu(gs.astype(jnp.float32)).astype(x.dtype) * us
        y = y + jnp.einsum("tf,fd->td", hs, p["ws_d"].astype(x.dtype))

    return y.reshape(B, S, d), aux


# --------------------------------------------------------------------------
# shard_map expert-parallel path
# --------------------------------------------------------------------------

def _shared_expert(p, xf, dtype):
    gs = jnp.einsum("td,df->tf", xf, p["ws_g"].astype(dtype))
    us = jnp.einsum("td,df->tf", xf, p["ws_u"].astype(dtype))
    hs = jax.nn.silu(gs.astype(jnp.float32)).astype(dtype) * us
    return jnp.einsum("tf,fd->td", hs, p["ws_d"].astype(dtype))


def _moe_apply_ep(p, x, cfg: ArchConfig, ctx, ep: int):
    """Expert-parallel MoE: expert group e on model-rank e; each rank
    processes its own data shard's assignments to its group (the tokens
    are already resident — no all-to-all); partial outputs combine with a
    single psum(_scatter) over 'model'.

    Capacity is per-(data shard) — the t5x/Switch 'group' capacity
    semantics; with one shard it equals the dense path exactly."""
    from jax.sharding import PartitionSpec as P

    mesh = ctx.mesh
    m = cfg.moe
    E, K = m.n_experts, m.top_k
    E_loc = E // ep
    B, S, d = x.shape

    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    dp = 1
    for a in dp_axes:
        dp *= sizes[a]
    b_ax = dp_axes if (dp_axes and B % dp == 0) else None
    B_loc = B // dp if b_ax else B
    seq_shard = S % ep == 0 and S > 1
    s_ax = "model" if seq_shard else None

    T_loc = B_loc * S                       # tokens per data shard
    C = capacity(T_loc, cfg)                # per-shard capacity

    x_spec = P(b_ax, s_ax, None)
    w_spec = P("model", None, None)         # expert weights by group
    r_spec = P(None, None)                  # router replicated (tiny)

    def local(xl, router, wg, wu, wd):
        if seq_shard:
            xl = jax.lax.all_gather(xl, "model", axis=1, tiled=True)
        Bl, Sl, _ = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, d)
        logits = jnp.einsum("td,de->te", xf, router.astype(xl.dtype))
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gate, eid = jax.lax.top_k(probs, K)
        gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

        oh = jax.nn.one_hot(eid, E, dtype=jnp.float32)
        frac = oh.sum(axis=(0, 1)) / (T * K)
        aux = E * jnp.sum(frac * probs.mean(axis=0))

        base = jax.lax.axis_index("model") * E_loc
        rel = eid - base                                  # (T,K)
        mine = (rel >= 0) & (rel < E_loc)
        # position among assignments to my group (others masked out)
        oh_loc = jnp.where(mine[..., None],
                           jax.nn.one_hot(rel, E_loc, dtype=jnp.float32),
                           0.0).reshape(T * K, E_loc)
        pos = jnp.cumsum(oh_loc, axis=0) - oh_loc
        pos_in_e = jnp.einsum("ae,ae->a", pos, oh_loc).astype(jnp.int32)
        valid = mine.reshape(T * K) & (pos_in_e < C)
        dest = jnp.where(valid,
                         jnp.clip(rel.reshape(T * K), 0, E_loc - 1) * C
                         + pos_in_e, E_loc * C)
        dest_k = dest.reshape(T, K)

        buf = jnp.zeros((E_loc * C + 1, d), xl.dtype)
        for kk in range(K):
            buf = buf.at[dest_k[:, kk]].add(xf)
        xe = buf[: E_loc * C].reshape(E_loc, C, d)

        g = jnp.einsum("ecd,edf->ecf", xe, wg.astype(xl.dtype))
        u = jnp.einsum("ecd,edf->ecf", xe, wu.astype(xl.dtype))
        hmid = jax.nn.silu(g.astype(jnp.float32)).astype(xl.dtype) * u
        ye = jnp.einsum("ecf,efd->ecd", hmid, wd.astype(xl.dtype))

        y_flat = jnp.concatenate([ye.reshape(E_loc * C, d),
                                  jnp.zeros((1, d), xl.dtype)], axis=0)
        valid_k = valid.reshape(T, K)
        y = jnp.zeros((T, d), xl.dtype)
        for kk in range(K):
            w = (gate[:, kk] * valid_k[:, kk]).astype(xl.dtype)[:, None]
            y = y + y_flat[dest_k[:, kk]] * w
        y = y.reshape(Bl, Sl, d)
        if seq_shard:
            y = jax.lax.psum_scatter(y, "model", scatter_dimension=1,
                                     tiled=True)
        else:
            y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, dp_axes) if dp_axes else aux
        return y, aux

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False)
    y, aux = fn(x, p["router"], p["we_g"], p["we_u"], p["we_d"])
    if m.n_shared:
        xf = x.reshape(B * S, d)
        y = y + _shared_expert(p, xf, x.dtype).reshape(B, S, d)
    return y, aux
