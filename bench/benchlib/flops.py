"""Operations and bytes the algorithms need, from the shapes alone.

``train_flops_per_token`` is the model FLOP count of one training token:
6 x the parameters that multiply activations (attention and MLP matrices
of every layer, and the output head; the input embedding is a lookup and
does not count) plus the attention products. Attention is causal: a
query at position i attends to i + 1 keys, so QK^T and PV together cost
2 x 2 x (heads x head_dim) x (S + 1) / 2 FLOPs per token and layer
forward, and three times that for forward plus backward. Recomputation
(activation remat) is not counted.

``replay_min_bytes`` is the memory traffic that replaying differentials
through Adam cannot avoid: each differential reads and writes the whole
optimizer state (params and both f32 moments) once and reads its own
compressed payload. It is the arithmetic of the program's
``analysis.roofline.replay_roofline``, kept here so that no program
change can move it.
"""
from __future__ import annotations

from typing import Any, Dict


def matmul_params(cfg: Dict[str, Any]) -> int:
    d, L = cfg["d_model"], cfg["n_layers"]
    hd = cfg.get("head_dim") or d // cfg["n_heads"]
    q = d * cfg["n_heads"] * hd
    kv = 2 * d * cfg["n_kv_heads"] * hd
    o = cfg["n_heads"] * hd * d
    mlp = 3 * d * cfg["d_ff"]          # SwiGLU: gate, up, down
    return L * (q + kv + o + mlp) + d * cfg["vocab"]


def attention_flops_per_token(cfg: Dict[str, Any]) -> float:
    hd = cfg.get("head_dim") or cfg["d_model"] // cfg["n_heads"]
    forward = 2.0 * cfg["n_heads"] * hd * (cfg["seq"] + 1)
    return 3.0 * forward * cfg["n_layers"]


def train_flops_per_token(cfg: Dict[str, Any]) -> float:
    return 6.0 * matmul_params(cfg) + attention_flops_per_token(cfg)


def replay_min_bytes(state_bytes: int, payload_bytes: int,
                     n_diffs: int) -> int:
    """``state_bytes``: params + mu + nu; ``payload_bytes``: one
    differential's compressed payload."""
    return int(n_diffs * (2 * state_bytes + payload_bytes))
