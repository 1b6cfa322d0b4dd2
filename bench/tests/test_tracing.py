"""The trace reduction on small traces whose answers are known."""
import json
from pathlib import Path

import numpy as np
import pytest

from benchlib import tracing

DEV, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = tracing.OPS_LINE, tracing.MODULES_LINE


def _ev(plane, line, name, start, dur):
    return (plane, line, name, float(start), float(dur))


def test_busy_idle_and_gaps_of_a_hand_made_trace():
    evs = [
        _ev(HOST, "python", "bench.window", 100, 1000),
        _ev(HOST, "python", "bench.train_step", 100, 300),
        _ev(HOST, "python", "bench.block", 400, 600),
        _ev(DEV, MODS, "jit_step(1)", 150, 500),
        _ev(DEV, OPS, "fusion.1", 150, 200),
        _ev(DEV, OPS, "fusion.2", 300, 100),      # overlaps fusion.1
        _ev(DEV, OPS, "fusion.1", 800, 100),
        _ev(DEV, OPS, "copy.3", 50, 100),         # half before the window
        _ev(DEV, OPS, "fusion.9", 1200, 50),      # after the window
    ]
    r = tracing.reduce(evs)
    assert r["window_s"] == pytest.approx(1000e-9)
    # [100,400) (copy.3 clipped, fusion.1, fusion.2) and [800,900)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["op_s"]["fusion.1"] == pytest.approx(300e-9)
    assert r["module_s"] == {"jit_step(1)": [pytest.approx(500e-9)]}
    # gaps [400,800) under bench.block and [900,1100) under bench.block
    assert r["idle_gaps"] == [["bench.block", pytest.approx(400e-9)],
                              ["bench.block", pytest.approx(200e-9)]]
    assert r["device_ops"][0][0] == "fusion.1"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tracing.reduce([_ev(DEV, OPS, "fusion.1", 0, 10)])


def _raster_busy(evs, lo, hi):
    grid = np.zeros(int(hi - lo), bool)
    for p, l, n, s, d in evs:
        if p.startswith("/device:") and l == OPS:
            a, b = max(int(s), int(lo)), min(int(s + d), int(hi))
            if b > a:
                grid[a - int(lo):b - int(lo)] = True
    return grid.sum()


RECORDED = Path(__file__).parent / "data" / "recorded_trace.json"


@pytest.mark.skipif(not RECORDED.exists(), reason="no recorded trace")
def test_recorded_chip_trace_against_a_raster():
    """A slice of a trace recorded on a TPU v5e (gpt2l8-lowdiff-diffs,
    --trace 1): the interval union agrees with a nanosecond raster."""
    evs = [tuple(e) for e in json.loads(RECORDED.read_text())]
    r = tracing.reduce(evs)
    lo, hi = tracing.window_of(evs)
    assert r["busy_s"] == pytest.approx(_raster_busy(evs, lo, hi) * 1e-9,
                                        rel=1e-6, abs=2e-9)
    assert 0 < r["busy_s"] <= r["window_s"]
