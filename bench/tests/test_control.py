"""The control, the reference computed one precision step below what the
configuration states and put in the program's place, fails the cell's
limits and reads at least three times what the program reads (on the
chip it is read at the cell's own size by ``calibrate.py --control``;
this is the same comparison at a size a test run can hold)."""
import pytest

import calibrate
from benchlib import checks, harness


def _fails(numbers, limits, program=None):
    """Some number fails its limit, and (given the program's readings at
    this size) reads at least three times the program's."""
    return any(not v <= limits[k] and (program is None or v >= 3 * program[k])
               for k, v in numbers.items() if k in limits)


@pytest.mark.parametrize("name", ["gpt2l8-lowdiff-diffs", "gpt2l8-nockpt"])
def test_fp8_control_fails_a_training_limit(tiny_cell, name):
    cell = tiny_cell(name)
    eng = cell.traffic["engine"]
    rho = eng["rho"] if eng["strategy"] == "lowdiff" else 0.0
    refr = checks.reference_readings(cell.config, 5, rho=rho, lr=eng["lr"])
    ctl = checks.reference_readings(cell.config, 5, rho=rho, lr=eng["lr"],
                                    precision="fp8")
    run = harness.Run(cell, 5, 0, False)
    model = harness.program_model(cell.config)
    strat = harness.build_engine(run, model)
    readings = {}
    harness._first_steps(run, model, strat, harness.Stepper(run, model, strat),
                         readings)
    if strat is not None:
        strat.close()
    program = checks.compare_training(readings, refr)
    assert _fails(checks.compare_training(ctl, refr), cell.limits, program)


def test_bf16_replay_control_fails_the_resume_limits(tiny_cell):
    cell = tiny_cell("gpt2l8-lowdiff-resume")
    (row,) = calibrate.resume_control(cell, 5)
    assert _fails({k: v for k, v in row.items() if k != "reading"},
                  cell.limits)
