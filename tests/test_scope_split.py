"""`tools/scope_split.py`: the split of a scope metric's device time by
named scope, phase and primitive, on hand-made joined rows."""
import importlib.util
import os

import pytest

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "tools", "scope_split.py")
_spec = importlib.util.spec_from_file_location("scope_split", _PATH)
scope_split = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(scope_split)

FWD = "jit(step)/train_step.loss/jvp(M)/layers.1/moe/moe.route/reduce_sum"
BWD = ("jit(step)/train_step.loss/transpose(jvp(M))/layers.1/checkpoint/moe/"
       "moe.combine/scatter-add")
REPLAY = ("jit(step)/train_step.loss/transpose(jvp(M))/layers.1/checkpoint/"
          "rematted_computation/moe/moe.route/reduce_sum")


@pytest.mark.parametrize("op,want", [(FWD, "fwd"), (BWD, "bwd"),
                                     (REPLAY, "replay")])
def test_phase_is_read_from_the_op_name(op, want):
    assert scope_split.phase_of(op) == want


def test_split_sums_by_scope_phase_and_primitive():
    rows = [(FWD, "fusion.1", 2_000_000), (FWD, "fusion.2", 1_000_000),
            (REPLAY, "fusion.3", 4_000_000), (BWD, "fusion.4", 8_000_000),
            (None, "copy.1", 5_000_000),                  # joined no op_name
            ("jit(step)/train_step.loss/jvp(M)/attn/dot_general", "f", 9e6),
            (FWD.replace("reduce_sum", "mul"), "fusion.5", 40_000)]
    got = scope_split.split(rows, r"/moe\.(route|sort|combine)(/|$)", steps=2)
    assert got["sum"] == 7.52
    assert got["by_scope_phase"] == {"combine.bwd": 4.0, "route.fwd": 1.52,
                                     "route.replay": 2.0}
    # largest first; the 0.02 ms row is under the floor
    assert got["rows"] == [["combine", "bwd", "scatter-add", 4.0],
                           ["route", "replay", "reduce_sum", 2.0],
                           ["route", "fwd", "reduce_sum", 1.5]]
    # a regex without a group names the rows by its match
    assert scope_split.split(rows, r"/attn", 1)["by_scope_phase"] == {
        "/attn.fwd": 9.0}
