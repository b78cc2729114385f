"""The yardstick's arithmetic, checked on hand-made cases."""
import json
import os

import pytest

from harness import common, tracing, traffic

MS = 1_000_000


def test_union_and_idle_gaps_by_hand():
    ev = [("a", 0, 10 * MS), ("b", 5 * MS, 10 * MS), ("c", 30 * MS, 5 * MS)]
    assert tracing.union_seconds(ev) == pytest.approx(0.020)
    spans = [("host.x", 14 * MS, 31 * MS), ("host.inner", 16 * MS, 20 * MS)]
    gaps = tracing.idle_gaps(ev, (0, 50 * MS), spans)
    # gap 15..30 ms: its midpoint 22.5 lies in host.x only; 35..50 in none
    assert gaps == {"host.x": pytest.approx(0.015),
                    "unattributed": pytest.approx(0.015)}


def test_container_ops_are_not_leaves():
    w = "%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %t), body=%b"
    c = "%jvp__.1 = (bf16[8], f32[8]) custom-call(bf16[8] %q)"
    assert tracing.is_container(w) and not tracing.is_container(c)
    assert tracing.short_name(w) == "while.3"


def test_flash_cost_by_hand():
    cost = common.load_module("readers", "cost_flash")
    flops, bytes_ = cost.per_pass(32, 1024, 12, 64)
    # one full matmul is 2*b*H*s*s*d = 51,539,607,552; causal halves it;
    # forward 2 + backward 5 matmuls
    assert flops == 7 * 51_539_607_552 / 2 == 180_388_626_432
    tensor = 32 * 1024 * 12 * 64 * 2          # 50,331,648 bytes in bf16
    lse = 32 * 12 * 1024 * 4
    assert bytes_ == 12 * tensor + 2 * lse == 607_125_504


def test_gpt_flops_by_hand():
    cost = common.load_module("readers", "cost_gpt")
    cfg = {"hidden_size": 768, "intermediate_size": 3072,
           "num_hidden_layers": 12, "vocab_size": 50304}
    block = 2 * 768 * 2304 + 2 * 768 * 768 + 4 * 768 * 3072     # 14,155,776
    attn = 2 * 2 * 768 * 1025 / 2
    fwd = 12 * (block + attn) + 2 * 768 * 50304
    assert cost.train_flops_per_token(cfg, 1024) == pytest.approx(3 * fwd)
    assert cost.train_flops_per_token(cfg, 1024) == pytest.approx(798_087_168)


def test_traffic_same_seed_same_batches_other_seed_others():
    job = {"global_batch": 4, "sequence_length": 8}
    x1, y1 = traffic.train_batch(job, 100, 12345678901, 0)
    again, _ = traffic.train_batch(job, 100, 12345678901, 0)
    x2, _ = traffic.train_batch(job, 100, 12345678901, 1)
    other, _ = traffic.train_batch(job, 100, 7, 0)
    assert (x1 == again).all() and (x1 != x2).any() and (x1 != other).any()
    assert x1.shape == other.shape == (4, 8)         # the seed never changes the work
    assert (x1[:, 1:] == y1[:, :-1]).all()           # labels are the next token
    assert len({tuple(r) for r in x1.tolist()}) == 4  # rows all differ


def test_unknown_device_kind_is_refused():
    with pytest.raises(SystemExit) as e:
        common.peaks_for("TPU v9 imaginary")
    assert "not in benchmark/peaks.json" in str(e.value)
    assert common.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_benchmark_json_points_at_files_that_exist():
    with open(os.path.join(common.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
    for w in bench["workloads"]:
        cell = common.load_json("workloads", w["name"] + ".json")
        assert cell["config"] == w["config"] and cell["chips"] == w["chips"]
        assert cell["traffic_name"] == w["traffic"]
        assert cell["why"] == w["why"]
    for m in bench["per_layer"]:
        spec = common.load_json("metrics", m["name"] + ".json")
        assert os.path.exists(os.path.join(
            common.BENCH_DIR, "readers", spec["reader"] + ".py"))
        assert {k: spec[k] for k in m} == m
