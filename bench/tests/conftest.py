"""Tests of the benchmark itself: ``python -m pytest bench/tests`` from
the checkout root. They run on the CPU at tiny sizes.

The CPU code generator is capped at AVX (no fused multiply-add), as for
the repo's own tests, so that a value recorded as a literal is met bit
for bit on any x86 host. It must be set before JAX starts its CPU
backend."""
import copy
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
_AVX = "--xla_cpu_max_isa=AVX"
if _AVX not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + _AVX).strip()
BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import pytest  # noqa: E402

#: every width cut, for the CPU; the cells' own files keep theirs
TINY = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
            vocab=512, batch=2, seq=64)


@pytest.fixture
def tiny_cell():
    from benchlib import spec

    def make(name):
        cell = spec.load_cell(name)
        cell.config = dict(cell.config, **TINY)
        cell.traffic = copy.deepcopy(cell.traffic)
        eng = cell.traffic["engine"]
        if cell.traffic["mode"] == "train" and \
                0 < eng.get("full_interval", 0) < 100:
            eng["full_interval"] = min(eng["full_interval"], 6)
        return cell
    return make
