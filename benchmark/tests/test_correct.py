"""`correct` must come out false when the timed path is broken underneath.

Each case skips the harness's look for a chip (`rehearse`: the cell's tiny
stand-in, on the CPU) and drives the rest of a run through `run.run_cell`,
with the cell's own limits.  The faults at the cells' own sizes were read on
the chip (PERF.md section 2); here they run at a size a test can hold.
"""
import json

import pytest

import run as bench_run
from harness import check

CELLS = ["gpt3-125m.train.seq1024", "gpt3-125m.train.seq2048"]


def _drive(cell, seed, plant=None):
    line = json.loads(bench_run.run_cell(cell, seed, 1.0, 0, rehearse=True,
                                         plant=plant))
    return line["correct"], line["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_train_sound_run_is_correct(cell):
    ok, checks = _drive(cell, 11)
    assert ok, checks
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap", "sane"} == set(checks)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(cell, fault):
    ok, checks = _drive(cell, 12, plant=fault)
    assert not ok, checks


def test_a_number_without_a_limit_is_not_correct():
    checks = check.with_limits({"loss_gap": 0.0, "grad_norm_gap": 0.0},
                               {"loss_gap": 1e-3}, True)
    assert check.verdict(checks) == (False, ["grad_norm_gap"])
    assert check.verdict({"sane": (1.0, 0.0)}) == (False, ["sane"])


def test_the_measured_command_has_no_knobs(capsys):
    with pytest.raises(SystemExit):
        bench_run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                        "--trace", "0", "--fault", "half_batch"])
    capsys.readouterr()
