"""Operations and bytes the algorithms need, from the shapes alone.

``train_flops_per_token`` is the model FLOP count of one training token:
6 x the parameters that multiply each token's activations (the active
ones of a sparse layer; the input embedding is a lookup and does not
count) plus the attention products, both as the configuration's
architecture module (``references/<reference>.py``) counts them.
Recomputation (activation remat) is not counted.

``replay_min_bytes`` is the memory traffic that replaying differentials
through Adam cannot avoid: each differential reads and writes the whole
optimizer state (params and both f32 moments) once and reads its own
compressed payload. It is the arithmetic of the program's
``analysis.roofline.replay_roofline``, kept here so that no program
change can move it.
"""
from __future__ import annotations

from typing import Any, Dict

from benchlib import spec


def train_flops_per_token(cfg: Dict[str, Any]) -> float:
    arch = spec.reference_of(cfg)
    return 6.0 * arch.matmul_params(cfg) + arch.attention_flops_per_token(cfg)


def replay_min_bytes(state_bytes: int, payload_bytes: int,
                     n_diffs: int) -> int:
    """``state_bytes``: params + mu + nu; ``payload_bytes``: one
    differential's compressed payload."""
    return int(n_diffs * (2 * state_bytes + payload_bytes))
