"""The trace reduction against a small recorded trace: two steps of the
train driver's tiny stand-in (2 layers, flash kernels dispatched) on one
TPU v5e, recorded by `run.py --rehearse --trace 1 --keep-trace` in PR 25."""
import gzip
import os
import statistics

import pytest

from harness import common, tracing

TRACE = os.path.join(common.BENCH_DIR, "testdata", "tiny_train.xplane.pb.gz")


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "tiny.xplane.pb"
    with gzip.open(TRACE) as f:
        path.write_bytes(f.read())
    return tracing.reduce_trace(str(path))


def test_planes_lines_and_anchor(red):
    assert sorted(red["devices"]) == [0]
    dev = red["devices"][0]
    assert len(dev["ops"]) == 1666 and len(dev["modules"]) == 4
    assert red["anchor_ns"] == 40490399       # the bench.anchor annotation


def test_busy_idle_union(red):
    ops = red["devices"][0]["ops"]
    lo = min(s for _, s, _ in ops)
    hi = max(s + d for _, s, d in ops)
    assert (lo, hi) == (44837133, 48405796)
    busy = tracing.union_seconds(ops)
    assert busy == pytest.approx(0.00042561, rel=1e-6)
    # a tiny model leaves the chip idle between its two steps
    gaps = tracing.idle_gaps(ops, (lo, hi), [("bench.dispatch", lo, hi)])
    assert gaps["bench.dispatch"] == pytest.approx((hi - lo) / 1e9 - busy, rel=1e-6)
    clipped = tracing.clip(ops, (lo, (lo + hi) // 2))
    assert 0 < tracing.union_seconds(clipped) < busy


def test_flash_family_and_step_gap(red):
    fam = common.load_json("kernels", "flash_train.json")
    ops = red["devices"][0]["ops"]
    events = tracing.family_events(ops, fam["events"])
    assert len(events) == 12                  # 2 steps x 2 layers x (fwd, dq, dkv)
    assert len(tracing.family_events(events, fam["passes"])) == 4
    assert sum(d for _, _, d in events) == 54120
    assert {tracing.family_name(n) for n, _, _ in events} == {"jvp__", "transpose_jvp___"}
    steps = sorted(s for n, s, _ in red["devices"][0]["modules"] if n.startswith("jit_step"))
    assert statistics.median(b - a for a, b in zip(steps, steps[1:])) == 3353311
