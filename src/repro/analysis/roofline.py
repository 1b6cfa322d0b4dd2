"""Roofline analysis: compute / memory / collective terms per
(architecture x input shape) on the production mesh.

    compute_term    = FLOPs_per_chip / 197e12        [s]
    memory_term     = HBM_bytes_per_chip / 819e9     [s]
    collective_term = collective_bytes_per_chip / 50e9 [s]

FLOPs/bytes come from segment-composed ``cost_analysis`` of the compiled
dry-run pieces (scan trip counts folded in — see segments.py); collective
bytes from the partitioned HLO text. MODEL_FLOPS is the analytic
6·N_active·T (train) / 2·N_active·T (inference) divided across chips —
its ratio to compiled FLOPs exposes remat/masking/dispatch waste.
"""
from __future__ import annotations

import os

if __name__ == "__main__":  # standalone: fake the 512 hosts before jax init
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=512")

import json
from typing import Dict, Optional

from repro.analysis.segments import compose
from repro.configs import INPUT_SHAPES, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_production_mesh, peaks
from repro.models.registry import build_model


def model_flops(cfg, shape) -> float:
    """Analytic useful FLOPs per step (global, all chips)."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: one token


#: the chip the production-mesh analysis describes
TARGET_KIND = "TPU v5 lite"


def roofline(arch: str, shape_id: str, *, multi_pod: bool = False,
             rules: Optional[dict] = None) -> Dict:
    cfg = get_config(arch)
    peak = peaks(TARGET_KIND)
    model = build_model(cfg)
    shape = INPUT_SHAPES[shape_id]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    if rules is None:
        rules = cfg.rules(shape.kind)
    with shd.use_mesh(mesh, rules):
        comp = compose(model, shape)
    t = comp["total"]
    terms = {
        "compute_s": t["flops"] / peak.flops,
        "memory_s": t["bytes"] / peak.hbm_bw,
        "collective_s": t["coll_bytes"] / peak.ici_bw,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape) / chips
    rec = {
        "arch": arch, "shape": shape_id,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips,
        "flops_per_chip": t["flops"],
        "bytes_per_chip": t["bytes"],
        "coll_bytes_per_chip": t["coll_bytes"],
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops_per_chip": mf,
        "useful_flops_ratio": round(mf / t["flops"], 4) if t["flops"] else 0,
        "segments": comp["segments"],
    }
    return rec


def measured_copy_bandwidth(nbytes: int = 1 << 26, iters: int = 5) -> float:
    """Measured memory-copy bandwidth of this host in bytes/s (2x the
    copied size: one read + one write stream). The replay roofline's
    denominator on the CPU backend, where the training state lives in
    host RAM."""
    import time as _time

    import numpy as np
    src = np.ones(nbytes, np.uint8)
    dst = np.empty_like(src)
    ts = []
    for _ in range(iters):
        t0 = _time.perf_counter()
        np.copyto(dst, src)
        ts.append(_time.perf_counter() - t0)
    return 2.0 * nbytes / float(np.median(ts))


def replay_roofline(state_bytes: int, payload_bytes: int, n_diffs: int,
                    device) -> Dict:
    """Memory-bandwidth lower bound for replaying ``n_diffs``
    differentials through a stateful optimizer: each step must read and
    write the full optimizer state (params + both f32 moments) once and
    read its compressed payload — nothing less recovers Adam exactly.
    ``payload_bytes`` is per differential. ``device`` is the
    ``jax.Device`` holding the state: the bound uses the host's
    measured copy rate on the CPU backend and the chip's published HBM
    rate otherwise (an unknown chip raises)."""
    bw = (measured_copy_bandwidth() if device.platform == "cpu"
          else peaks(device.device_kind).hbm_bw)
    traffic = n_diffs * (2 * state_bytes + payload_bytes)
    return {"traffic_bytes": int(traffic), "bandwidth": float(bw),
            "min_seconds": traffic / bw}


def main():
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--out", default="results/roofline.json")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    from repro.configs import ASSIGNED_ARCHS
    archs = [args.arch] if args.arch else list(ASSIGNED_ARCHS)
    shapes = [args.shape] if args.shape else list(INPUT_SHAPES)

    results = []
    if args.out and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results}
    mesh_name = "2x16x16" if args.multi_pod else "16x16"
    for arch in archs:
        for shape_id in shapes:
            if (arch, shape_id, mesh_name) in done:
                continue
            try:
                rec = roofline(arch, shape_id, multi_pod=args.multi_pod)
            except Exception as e:  # noqa: BLE001
                import traceback
                rec = {"arch": arch, "shape": shape_id, "mesh": mesh_name,
                       "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc(limit=4)}
            if "error" in rec:
                print(f"[FAIL] {arch:24s} {shape_id:12s} {rec['error']}",
                      flush=True)
            else:
                print(f"[OK ] {arch:24s} {shape_id:12s} "
                      f"comp={rec['compute_s'] * 1e3:8.2f}ms "
                      f"mem={rec['memory_s'] * 1e3:8.2f}ms "
                      f"coll={rec['collective_s'] * 1e3:8.2f}ms "
                      f"dom={rec['dominant']:10s} "
                      f"useful={rec['useful_flops_ratio']:.2f}", flush=True)
            results.append(rec)
            if args.out:
                os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
                json.dump(results, open(args.out, "w"), indent=1)


if __name__ == "__main__":
    main()
