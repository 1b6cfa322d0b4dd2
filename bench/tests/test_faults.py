"""A run with its timed path broken underneath comes out not correct.

Each test drives the rest of a run (set-up, window, the comparison with
the plain reference, the result line) on the CPU at a tiny size, past
the harness's look for a chip, with one fault of ``benchlib.faults``
planted, and holds it to the cell's committed limits. The limits were
set at the cells' own sizes on the chip, where a sound run reads less
than at this size; so each test also checks that the number the fault
fails reads at least three times what the sound run reads here. One
chip exchanges nothing between chips, so that fault has no test here.
"""
import time

import pytest

from benchlib import faults, harness

SEED = 2 ** 31 + 17
_SOUND = {}


def _run(cell, fault=None):
    return harness.run_cell(cell, SEED, 0.5, False, time.perf_counter(),
                            fault=fault)


def _sound(tiny_cell, name):
    if name not in _SOUND:
        _SOUND[name] = _run(tiny_cell(name))
    return _SOUND[name]


@pytest.mark.parametrize("name", ["gpt2l8-lowdiff-diffs", "gpt2l8-nockpt",
                                  "gpt2l8-lowdiff-resume"])
def test_sound_run_result_line(tiny_cell, name):
    r = _sound(tiny_cell, name)
    assert r["attempted"] > 0 and r["failed"] == 0
    assert set(r["checks"]) == set(tiny_cell(name).limits)
    assert list(r)[-1] == "checks"
    assert r["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name,fault", [
    ("gpt2l8-lowdiff-diffs", f) for f in faults.TRAIN] + [
    ("gpt2l8-nockpt", f) for f in faults.TRAIN if f != "diff_altered"] + [
    ("gpt2l8-lowdiff-resume", f) for f in faults.RESUME])
def test_fault_is_not_correct(tiny_cell, name, fault):
    sound = _sound(tiny_cell, name)["checks"]
    r = _run(tiny_cell(name), fault)
    assert not r["correct"], r["checks"]
    failed = [k for k, c in r["checks"].items()
              if not c["value"] <= c["limit"]]
    assert any(r["checks"][k]["value"] >= 3 * sound[k]["value"]
               for k in failed), (r["checks"], sound)
