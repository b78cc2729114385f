"""The new cell's planted faults and its control, at the rehearsal size:
`correct` must come out false when the selection is ignored, when the
indexer's loss is dropped and when half of the batch is left out, and true
for a sound run.  (A file of its own: a PR that is no `benchmark` PR adds
files here and edits none — test_correct.py and test_control.py hold the GPT
cells' cases.)  What the faults and controls read at the cell's own size on
the chip is in PERF.md section 2."""
import functools
import json

import pytest

import run as bench_run
from harness import check, common, traffic

CELL = "keye-vl2-ep8.train.seq8192"


def _drive(seed, plant=None):
    line = json.loads(bench_run.run_cell(CELL, seed, 1.0, 0, rehearse=True,
                                         plant=plant))
    return line["correct"], line["checks"]


def test_sound_run_is_correct():
    ok, checks = _drive(11)
    assert ok, checks
    assert {"loss_gap", "grad_norm_gap", "change_norm_gap", "sane"} == set(checks)


@pytest.mark.parametrize("fault", ["selection_ignored", "indexer_loss_dropped",
                                   "half_batch", "state_unchanged"])
def test_planted_fault_is_not_correct(fault):
    ok, checks = _drive(12, plant=fault)
    assert not ok, checks
    if fault == "selection_ignored":    # the count of selected pairs sees it too
        assert checks["sane"]["value"] == 1.0


def _stand_in():
    cell = common.load_json("workloads", CELL + ".json")
    config = common.load_json("configs", cell["config"] + ".json")
    for dotted, value in cell["rehearse"].items():
        bench_run._set({"cell": cell, "config": config}, dotted, value)
    return cell, config


@pytest.mark.parametrize("fault", ["selection_ignored", "indexer_loss_dropped"])
def test_reference_with_the_fault_fails_the_cells_limits(fault):
    """The fault's mathematics in the reference itself, held to the
    rehearsal's limits against the sound reference."""
    cell, cfg = _stand_in()
    ref = common.load_module("reference", "keye")
    drv = common.load_module("drivers", "train_keye")
    assert set(drv.FAULTS) == set(ref.FAULTS) == {"selection_ignored",
                                                  "indexer_loss_dropped"}
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


class _Device:
    def __init__(self, platform):
        self.platform = platform


@pytest.mark.parametrize("missing,ok", [
    (None, True), ("sparse_index.dispatch{kernel=%s,op=select}", False),
    ("sparse_index.dispatch{kernel=%s,op=loss}", False),
    ("sparse_attn.dispatch{kernel=%s}", False)])
def test_a_fallback_on_the_tpu_fails_sane(missing, ok):
    """On a TPU each of the four entries took its kernels once a layer and
    its jax.numpy form never; off the TPU the term holds nothing."""
    drv = common.load_module("drivers", "train_keye")
    ctx = {"devices": [_Device("tpu")], "config": {"num_hidden_layers": 3}}
    dispatch = {e % "pallas": 3 for e in drv.KERNEL_ENTRIES}
    if missing:
        del dispatch[missing % "pallas"]
        dispatch[missing % "reference"] = 3
    assert drv._on_kernels(ctx, dispatch) is ok
    assert drv._on_kernels(dict(ctx, devices=[_Device("cpu")]), {}) is True
