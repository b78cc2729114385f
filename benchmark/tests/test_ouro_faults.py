"""The Ouro cell's planted faults and its control, at the rehearsal size:
`correct` must come out false when a pass is left out, when the exit
distribution is detached, when the sandwich norms after each sub-layer are
dropped and when half of the batch is left out, and true for a sound run.
(A file of its own: a PR that is no `benchmark` PR adds files here and
edits none.)  What the faults and controls read at the cell's own size on
the chip is in PERF.md section 2."""
import functools
import json

import pytest

import run as bench_run
from harness import check, common, traffic

CELL = "ouro-2.6b-pp8.train.seq4096"


def _drive(seed, plant=None):
    line = json.loads(bench_run.run_cell(CELL, seed, 1.0, 0, rehearse=True,
                                         plant=plant))
    return line["correct"], line["checks"]


def test_sound_run_is_correct():
    ok, checks = _drive(11)
    assert ok, checks
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap", "sane"} == set(checks)


@pytest.mark.parametrize("fault", ["loop_short", "exit_detached",
                                   "sandwich_dropped", "half_batch",
                                   "state_unchanged"])
def test_planted_fault_is_not_correct(fault):
    ok, checks = _drive(12, plant=fault)
    assert not ok, checks
    if fault == "loop_short":       # the loop's counters see it too
        assert checks["sane"]["value"] == 1.0


def test_a_detached_exit_is_undone_after_its_run():
    from paddle_tpu.models import ouro

    before = ouro.exit_distribution
    _drive(13, plant="exit_detached")
    assert ouro.exit_distribution is before


def _stand_in():
    cell = common.load_json("workloads", CELL + ".json")
    config = common.load_json("configs", cell["config"] + ".json")
    for dotted, value in cell["rehearse"].items():
        bench_run._set({"cell": cell, "config": config}, dotted, value)
    return cell, config


@pytest.mark.parametrize("fault", ["loop_short", "exit_detached",
                                   "sandwich_dropped"])
def test_reference_with_the_fault_fails_the_cells_limits(fault):
    """The fault's mathematics in the reference itself, held to the
    rehearsal's limits against the sound reference."""
    cell, cfg = _stand_in()
    ref = common.load_module("reference", "ouro")
    drv = common.load_module("drivers", "train_ouro")
    assert set(drv.FAULTS) == set(ref.FAULTS) == {
        "loop_short", "exit_detached", "sandwich_dropped"}
    batches = [traffic.train_batch(cell["job"], cfg["vocab_size"], 5, i)
               for i in range(3)]
    follow = functools.partial(ref.train_readings, cfg,
                               cfg["training"]["optimizer"],
                               lambda: drv.make(cfg, 5), batches, 1)
    sound = follow()
    numbers, _ = check.train_numbers(follow(fault=fault), sound)
    ok, failing = check.verdict(check.with_limits(numbers, cell["limits"], True))
    assert not ok and failing, numbers
    same, _ = check.train_numbers(sound, sound)
    assert check.verdict(check.with_limits(same, cell["limits"], True)) == (True, [])
