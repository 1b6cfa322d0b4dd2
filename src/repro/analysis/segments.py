"""Segment-composed cost accounting for the roofline analysis.

XLA's ``cost_analysis`` counts a while-loop (scan) body exactly once, so
a full train step with layers/microbatches scanned massively undercounts
FLOPs. The composer therefore lowers each *segment* of the step
separately — one layer fwd+bwd, the embed/loss head, the optimizer, the
compression pass — with the production shardings and all inner scans
unrolled, then multiplies per-segment costs by their static trip counts:

    total = Σ_seg count(seg) × cost(lower(seg))

Validated against a fully-unrolled small-arch lowering in
tests/test_roofline.py.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo_stats import collective_bytes
from repro.configs.base import ArchConfig, ShapeConfig
from repro.distributed import sharding as shd
from repro.distributed.step_builder import (batch_shardings, compress_sharded,
                                            effective_accum, param_shardings)
from repro.models import encdec, lm, ops
from repro.models.param import ParamSpec, is_spec
from repro.optim.adam import AdamState, adam_init, adam_update


@dataclasses.dataclass
class Segment:
    name: str
    count: int                  # static trip count in the real step
    fn: Callable                # positional fn to jit+lower
    args: tuple                 # ShapeDtypeStructs (sharded)


def _sds(shape, dtype, logical):
    ctx = shd.current()
    spec = shd.safe_spec(shape, ctx.spec(logical), ctx.mesh)
    from jax.sharding import NamedSharding
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(ctx.mesh, spec))


def _layer_params_abs(layer_specs_tree, pdtype):
    """Single-layer abstract params: strip the leading 'layers' dim."""
    ctx = shd.current()
    from jax.sharding import NamedSharding

    def one(s: ParamSpec):
        shape, logical = s.shape[1:], s.logical[1:]
        dt = jnp.dtype(s.dtype) if s.dtype else pdtype
        spec = shd.safe_spec(shape, ctx.spec(logical), ctx.mesh)
        return jax.ShapeDtypeStruct(shape, dt,
                                    sharding=NamedSharding(ctx.mesh, spec))

    return jax.tree.map(one, layer_specs_tree, is_leaf=is_spec)


def _grad_of(block_fn):
    """fwd+bwd of a rematerialized block, as in the real scan body."""
    blk = jax.checkpoint(block_fn)

    def f(lp, h, *rest):
        def loss(lp, h):
            out = blk(lp, h, *rest)
            out0 = out[0] if isinstance(out, tuple) else out
            extra = (out[1].astype(jnp.float32)
                     if isinstance(out, tuple) and out[1] is not None
                     and getattr(out[1], "ndim", 1) == 0 else 0.0)
            return jnp.sum(out0.astype(jnp.float32)) * 1e-6 + extra
        return jax.value_and_grad(loss, argnums=(0, 1))(lp, h)

    return f


# --------------------------------------------------------------------------
# per-shape segment builders
# --------------------------------------------------------------------------

def train_segments(model, shape: ShapeConfig) -> List[Segment]:
    cfg: ArchConfig = model.cfg
    ctx = shd.current()
    dp = 1
    for a in ("pod", "data"):
        if a in ctx.mesh.axis_names:
            dp *= ctx.mesh.devices.shape[ctx.mesh.axis_names.index(a)]
    accum = effective_accum(cfg.grad_accum, shape.global_batch, dp)
    Bm = shape.global_batch // accum
    S = shape.seq_len
    cdt = cfg.cdtype()
    h = _sds((Bm, S, cfg.d_model), cdt, ("batch", "residual_seq", None))
    positions = jnp.arange(S)
    segs: List[Segment] = []

    if cfg.arch_type == "audio":
        Ss = encdec.src_len(cfg, S)
        he = _sds((Bm, Ss, cfg.d_model), cdt, ("batch", "residual_seq", None))
        mem = _sds((Bm, Ss, cfg.d_model), cdt, ("batch", None, None))
        enc_lp = _layer_params_abs(model.specs["enc_layers"], cfg.pdtype())
        dec_lp = _layer_params_abs(model.specs["dec_layers"], cfg.pdtype())
        pe = jnp.arange(Ss)
        segs.append(Segment(
            "enc_layer", cfg.n_encoder_layers * accum,
            _grad_of(lambda lp, x: encdec.enc_block(lp, x, cfg, pe)),
            (enc_lp, he)))
        segs.append(Segment(
            "dec_layer", cfg.n_layers * accum,
            _grad_of(lambda lp, x, m: encdec.dec_block(lp, x, m, cfg,
                                                       positions)),
            (dec_lp, h, mem)))
    elif cfg.arch_type == "ssm":
        pair_lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())

        def pair(lp, x):
            from repro.models import xlstm as _x
            x = _x.mlstm_apply(lp["mlstm"], x, cfg)
            return _x.slstm_apply(lp["slstm"], x, cfg)

        # xLSTM block cost is linear in S (fixed mLSTM chunk width, one
        # sLSTM step per token): lower at S'=256 with the sequential scan
        # unrolled and scale the count by S/S'.
        Sp = min(S, 256)
        hp = _sds((Bm, Sp, cfg.d_model), cdt,
                  ("batch", "residual_seq", None))
        segs.append(Segment("xlstm_pair",
                            (cfg.n_layers // 2) * accum * (S // Sp),
                            _grad_of(pair), (pair_lp, hp)))
    else:
        lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())
        wins = lm.layer_windows(cfg)
        uniq, counts = np.unique(wins, return_counts=True)
        for w, c in zip(uniq.tolist(), counts.tolist()):
            segs.append(Segment(
                f"layer_w{w}", int(c) * accum,
                _grad_of(lambda lpp, x, _w=w: lm._std_block(
                    lpp, x, cfg, positions, _w)),
                (lp, h)))

    # embed (gather fwd + scatter-add bwd)
    V = cfg.vocab
    emb = _sds((V, cfg.d_model), cfg.pdtype(), ("vocab", "embed"))
    toks = _sds((Bm, S), jnp.int32, ("batch", None))

    def embed_seg(emb, toks):
        def loss(emb):
            return jnp.sum(emb.astype(cdt)[toks].astype(jnp.float32)) * 1e-6
        return jax.value_and_grad(loss)(emb)

    segs.append(Segment("embed", accum, embed_seg, (emb, toks)))

    # loss head: single-chunk xent fwd+bwd (S folded into one chunk)
    wlm = _sds((cfg.d_model, V), cfg.pdtype(), ("embed", "vocab"))
    tgt = _sds((Bm, S), jnp.int32, ("batch", None))

    def head_seg(h, wlm, tgt):
        def loss(h, wlm):
            tot, cnt = ops.chunked_softmax_xent(h, wlm, tgt,
                                                chunk=cfg.loss_chunk)
            return tot / jnp.maximum(cnt, 1.0)
        return jax.value_and_grad(loss, argnums=(0, 1))(h, wlm)

    segs.append(Segment("loss_head", accum, head_seg, (h, wlm, tgt)))

    # optimizer (full tree, once per step)
    psh = param_shardings(model)
    abs_p = jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, sds.dtype, sharding=s),
        model.abstract_params(), psh)
    abs_g = jax.tree.map(
        lambda sds, s: jax.ShapeDtypeStruct(sds.shape, jnp.float32,
                                            sharding=s),
        model.abstract_params(), psh)
    abs_opt = AdamState(abs_g, jax.tree.map(lambda x: x, abs_g),
                        jax.ShapeDtypeStruct((), jnp.int32))

    def opt_seg(p, g, o):
        return adam_update(p, g, o, lr=1e-3)

    segs.append(Segment("optimizer", 1, opt_seg, (abs_p, abs_g, abs_opt)))

    # LowDiff shard-local compression (once per step)
    pspecs = jax.tree.map(lambda s: s.spec, psh)
    mesh = ctx.mesh

    def comp_seg(g):
        return compress_sharded(g, pspecs, mesh, 0.01)

    segs.append(Segment("compress", 1, comp_seg, (abs_g,)))
    return segs


def prefill_segments(model, shape: ShapeConfig) -> List[Segment]:
    cfg: ArchConfig = model.cfg
    B, S = shape.global_batch, shape.seq_len
    cdt = cfg.cdtype()
    h = _sds((B, S, cfg.d_model), cdt, ("batch", "residual_seq", None))
    positions = jnp.arange(S)
    segs: List[Segment] = []
    if cfg.arch_type == "audio":
        Ss = encdec.src_len(cfg, S)
        he = _sds((B, Ss, cfg.d_model), cdt, ("batch", "residual_seq", None))
        mem = _sds((B, Ss, cfg.d_model), cdt, ("batch", None, None))
        enc_lp = _layer_params_abs(model.specs["enc_layers"], cfg.pdtype())
        dec_lp = _layer_params_abs(model.specs["dec_layers"], cfg.pdtype())
        pe = jnp.arange(Ss)
        segs.append(Segment("enc_layer", cfg.n_encoder_layers,
                            lambda lp, x: encdec.enc_block(lp, x, cfg, pe),
                            (enc_lp, he)))
        segs.append(Segment("dec_layer", cfg.n_layers,
                            lambda lp, x, m: encdec.dec_block(
                                lp, x, m, cfg, positions),
                            (dec_lp, h, mem)))
    elif cfg.arch_type == "ssm":
        pair_lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())

        def pair(lp, x):
            from repro.models import xlstm as _x
            x = _x.mlstm_apply(lp["mlstm"], x, cfg)
            return _x.slstm_apply(lp["slstm"], x, cfg)

        Sp = min(S, 256)
        hp = _sds((B, Sp, cfg.d_model), cdt, ("batch", "residual_seq", None))
        segs.append(Segment("xlstm_pair", (cfg.n_layers // 2) * (S // Sp),
                            pair, (pair_lp, hp)))
    else:
        lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())
        wins = lm.layer_windows(cfg)
        uniq, counts = np.unique(wins, return_counts=True)
        for w, c in zip(uniq.tolist(), counts.tolist()):
            segs.append(Segment(
                f"layer_w{w}", int(c),
                lambda lpp, x, _w=w: lm._std_block(lpp, x, cfg,
                                                   positions, _w)[0],
                (lp, h)))
    # final-position lm head
    V = cfg.vocab
    wlm = _sds((cfg.d_model, V), cfg.pdtype(), ("embed", "vocab"))
    hl = _sds((B, cfg.d_model), cdt, ("batch", None))
    segs.append(Segment(
        "lm_head", 1,
        lambda x, w: jnp.einsum("bd,dv->bv", x, w.astype(x.dtype),
                                preferred_element_type=jnp.float32),
        (hl, wlm)))
    return segs


def decode_segments(model, shape: ShapeConfig) -> List[Segment]:
    cfg: ArchConfig = model.cfg
    B = shape.global_batch
    seq_len = shape.seq_len
    cdt = cfg.cdtype()
    h = _sds((B, 1, cfg.d_model), cdt, ("batch", None, None))
    pos = jnp.asarray(seq_len - 1, jnp.int32)
    segs: List[Segment] = []
    cache_abs = model.init_cache(B, seq_len, abstract=True)
    cache_sh = shd.safe_sharding_tree(cache_abs, model.cache_logical())

    def strip(t_abs, t_sh):
        # single-layer slice of a stacked (L, ...) cache leaf
        return jax.tree.map(
            lambda sds, s: jax.ShapeDtypeStruct(
                sds.shape[1:], sds.dtype,
                sharding=type(s)(s.mesh,
                                 type(s.spec)(*tuple(s.spec)[1:]))),
            t_abs, t_sh)

    Lc = lm.cache_len(cfg, seq_len)
    ring = Lc < seq_len
    if cfg.arch_type == "ssm":
        pair_lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())
        mc = strip(cache_abs.mlstm, cache_sh.mlstm)
        sc = strip(cache_abs.slstm, cache_sh.slstm)
        segs.append(Segment(
            "xlstm_pair_decode", cfg.n_layers // 2,
            lambda lp, x, m, s: lm.ssm_decode_block(lp, x, cfg, m, s),
            (pair_lp, h, mc, sc)))
    elif cfg.arch_type == "audio":
        dec_lp = _layer_params_abs(model.specs["dec_layers"], cfg.pdtype())
        ck = strip(cache_abs.k, cache_sh.k)
        cv = strip(cache_abs.v, cache_sh.v)
        xk = strip(cache_abs.cross_k, cache_sh.cross_k)
        xv = strip(cache_abs.cross_v, cache_sh.cross_v)
        segs.append(Segment(
            "dec_layer_decode", cfg.n_layers,
            lambda lp, x, a, b, c, d: encdec.dec_decode_block(
                lp, x, cfg, a, b, c, d, pos, ring),
            (dec_lp, h, ck, cv, xk, xv)))
    else:
        lp = _layer_params_abs(model.specs["layers"], cfg.pdtype())
        ck = strip(cache_abs.k, cache_sh.k)
        cv = strip(cache_abs.v, cache_sh.v)
        wins = lm.layer_windows(cfg)
        uniq, counts = np.unique(wins, return_counts=True)
        if cfg.arch_type == "hybrid":
            mam = strip(cache_abs.mamba, cache_sh.mamba)
            for w, c in zip(uniq.tolist(), counts.tolist()):
                segs.append(Segment(
                    f"layer_decode_w{w}", int(c),
                    lambda lpp, x, a, b, m, _w=w: lm.decode_block(
                        lpp, x, cfg, a, b, pos, window=_w, ring=ring, mam=m),
                    (lp, h, ck, cv, mam)))
        else:
            for w, c in zip(uniq.tolist(), counts.tolist()):
                segs.append(Segment(
                    f"layer_decode_w{w}", int(c),
                    lambda lpp, x, a, b, _w=w: lm.decode_block(
                        lpp, x, cfg, a, b, pos, window=_w, ring=ring)[:3],
                    (lp, h, ck, cv)))
    V = cfg.vocab
    wlm = _sds((cfg.d_model, V), cfg.pdtype(), ("embed", "vocab"))
    hl = _sds((B, cfg.d_model), cdt, ("batch", None))
    segs.append(Segment(
        "lm_head", 1,
        lambda x, w: jnp.einsum("bd,dv->bv", x, w.astype(x.dtype),
                                preferred_element_type=jnp.float32),
        (hl, wlm)))
    return segs


def segments_for(model, shape: ShapeConfig) -> List[Segment]:
    if shape.kind == "train":
        return train_segments(model, shape)
    if shape.kind == "prefill":
        return prefill_segments(model, shape)
    return decode_segments(model, shape)


# --------------------------------------------------------------------------
# lowering + accounting
# --------------------------------------------------------------------------

def normalize_cost_analysis(ca) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` as a dict ({} when unavailable)."""
    return ca or {}


def measure_segment(seg: Segment) -> Dict[str, float]:
    ops.set_analysis_unroll(True)
    try:
        compiled = jax.jit(seg.fn).lower(*seg.args).compile()
    finally:
        ops.set_analysis_unroll(False)
    ca = normalize_cost_analysis(compiled.cost_analysis())
    coll = collective_bytes(compiled.as_text())
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll_bytes": float(coll.get("total", 0)),
            "coll_count": int(coll.get("count", 0))}


def compose(model, shape: ShapeConfig) -> Dict:
    """Per-device composed cost over all segments."""
    segs = segments_for(model, shape)
    total = {"flops": 0.0, "bytes": 0.0, "coll_bytes": 0.0}
    detail = []
    for seg in segs:
        m = measure_segment(seg)
        for k in total:
            total[k] += m[k] * seg.count
        detail.append({"segment": seg.name, "count": seg.count, **m})
    return {"total": total, "segments": detail}
