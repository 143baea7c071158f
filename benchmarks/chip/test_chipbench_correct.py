"""``correct`` at a size a CPU test run holds, on the sim backend: a
sound run passes, and the control and each fault the sim cell can have
fail. The harness runs as on the chip, past its look for a chip."""
import os
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))

from chipbench import check, faults, harness, tiny  # noqa: E402

WORKLOAD = "qwen3-0.6b.sim1.seq1024x1"


def _run(seed, mutate=None):
    cell = tiny.cell(WORKLOAD)
    return harness.run_cell(cell, seed, 0.2, False, t0=time.perf_counter(),
                            require_chip=False, mutate=mutate)


def test_sound_run_is_correct():
    run = _run(31)
    assert run.result["correct"], run.report
    assert run.result["window_compiles"] == 0
    assert list(run.result)[-1] == "checks"
    assert set(run.result["checks"]) == set(check.NUMBERS)


@pytest.mark.parametrize("fault", [faults.state_unchanged, faults.half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_is_not_correct(fault):
    run = _run(3000000019, mutate=fault)
    assert not run.result["correct"], run.report


@pytest.mark.parametrize("seed", [31, 32])
def test_control_is_not_correct(seed):
    """The reference one precision down (fp8 under bf16 weights) in the
    program's place."""
    cell = tiny.cell(WORKLOAD)
    s = harness.set_up(cell, seed, require_chip=False)
    arrivals = list(s.rec.arrivals)
    s.tr = s.rec = None
    ref = harness.reference_readings(cell, s, seed, arrivals)
    ctl = harness.reference_readings(cell, s, seed, arrivals, precision="low")
    checks = check.judge(check.gaps(ctl, ref), cell.limits["limits"])
    assert not check.passed(checks), checks
    assert check.passed(check.judge(check.gaps(s.prog, ref),
                                    cell.limits["limits"]))
