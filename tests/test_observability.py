"""Observability subsystem tests (ISSUE 1): metrics registry, flight
recorder, step-stats stream, profiler scheduler edge cases, and the
flash dispatch-tier / gate-reject / autotune telemetry wiring —
asserting end-to-end that the snapshot schema bench.py --telemetry
embeds carries the dispatch-tier counts, autotune hit/miss, retrace
count, and per-step wall stats the acceptance criteria name.
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.observability import flight, metrics, step_stats


@pytest.fixture(autouse=True)
def _clean_registry():
    """Each test starts from a disabled, empty registry and an empty
    flight ring (the default registry is process-global)."""
    metrics.reset()
    flight.clear()
    metrics.disable()
    yield
    metrics.reset()
    flight.clear()
    metrics.disable()


def _rand(shape, dtype=jnp.float32):
    return jnp.asarray(np.random.RandomState(0).randn(*shape), dtype)


# ============================ metrics ============================

def test_counter_labels_and_snapshot():
    metrics.enable()
    metrics.inc("flash.dispatch", tier="flat")
    metrics.inc("flash.dispatch", tier="flat")
    metrics.inc("flash.dispatch", tier="transpose")
    metrics.inc("plain")
    metrics.set_gauge("mem.peak_bytes_in_use", 123)
    metrics.observe("step.wall_ms", 2.0)
    metrics.observe("step.wall_ms", 4.0)
    snap = metrics.snapshot()
    assert snap["counters"]["flash.dispatch{tier=flat}"] == 2
    assert snap["counters"]["flash.dispatch{tier=transpose}"] == 1
    assert snap["counters"]["plain"] == 1
    assert snap["gauges"]["mem.peak_bytes_in_use"] == 123
    h = snap["histograms"]["step.wall_ms"]
    assert h["count"] == 2 and h["mean"] == 3.0
    assert h["min"] == 2.0 and h["max"] == 4.0


def test_declare_pre_registers_zero():
    # declare works even while disabled — schema, not a hot path
    metrics.declare("autotune.hit")
    metrics.declare("flash.dispatch", tier="biased")
    snap = metrics.snapshot()
    assert snap["counters"]["autotune.hit"] == 0
    assert snap["counters"]["flash.dispatch{tier=biased}"] == 0


def test_disabled_path_is_noop_and_cheap():
    assert not metrics.enabled()
    t0 = time.perf_counter()
    for _ in range(20000):
        metrics.inc("hot.path", tier="x")
        metrics.observe("hot.hist", 1.0)
    dt = time.perf_counter() - t0
    snap = metrics.snapshot()
    assert snap["counters"] == {} and snap["histograms"] == {}
    # generous bound: 40k disabled calls in well under a second
    assert dt < 1.0, f"disabled-path overhead too high: {dt:.3f}s"


def test_thread_safety():
    metrics.enable()
    n_threads, n_inc = 8, 2000

    def worker():
        for _ in range(n_inc):
            metrics.inc("concurrent.counter")
            metrics.observe("concurrent.hist", 1.0)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = metrics.snapshot()
    assert snap["counters"]["concurrent.counter"] == n_threads * n_inc
    assert snap["histograms"]["concurrent.hist"]["count"] == \
        n_threads * n_inc


def test_prometheus_export():
    metrics.enable()
    metrics.inc("flash.dispatch", tier="flat")
    metrics.set_gauge("mem.peak_bytes_in_use", 7)
    metrics.observe("step.wall_ms", 3.5)
    text = metrics.to_prometheus()
    assert '# TYPE paddle_tpu_flash_dispatch counter' in text
    assert 'paddle_tpu_flash_dispatch{tier="flat"} 1' in text
    assert 'paddle_tpu_mem_peak_bytes_in_use 7' in text
    assert 'paddle_tpu_step_wall_ms_count 1' in text


def test_jsonl_dump(tmp_path):
    metrics.enable()
    metrics.inc("a.b", kind="x")
    path = str(tmp_path / "metrics.jsonl")
    metrics.dump_jsonl(path, extra={"run": "t"})
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["phase"] == "metrics_snapshot"
    assert lines[0]["counters"]["a.b{kind=x}"] == 1
    assert lines[0]["run"] == "t"


def test_record_event_scope_tags_metrics():
    """profiler.RecordEvent spans tag HISTOGRAMS and flight events with
    the active scope (the RecordEvent <-> telemetry integration);
    counters are never auto-tagged so their keys stay schema-stable."""
    import paddle_tpu.profiler as profiler

    metrics.enable()
    with profiler.RecordEvent("train_step"):
        metrics.observe("inside.hist", 1.0)
        metrics.inc("inside.counter")
        metrics.inc("explicit.counter", scope="train_step")
        flight.record("inside.event")
        assert metrics.current_scope() == "train_step"
    assert metrics.current_scope() is None
    snap = metrics.snapshot()
    assert snap["histograms"]["inside.hist{scope=train_step}"][
        "count"] == 1
    # counters keep their exact label set (schema stability)
    assert snap["counters"]["inside.counter"] == 1
    assert snap["counters"]["explicit.counter{scope=train_step}"] == 1
    evts = [e for e in flight.events() if e["kind"] == "inside.event"]
    assert evts and evts[0]["scope"] == "train_step"


# ========================= flight recorder =========================

def test_flight_ring_bounded_and_dump(tmp_path):
    rec = flight.FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("test.event", i=i)
    evts = rec.events()
    assert len(evts) == 8
    assert [e["i"] for e in evts] == list(range(12, 20))  # newest kept
    path = str(tmp_path / "flight.jsonl")
    rec.dump(path, reason="unit")
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["kind"] == "flight.dump"
    assert lines[0]["reason"] == "unit" and lines[0]["n_events"] == 8
    assert [l["i"] for l in lines[1:]] == list(range(12, 20))


def test_flight_disabled_records_nothing():
    rec = flight.FlightRecorder()
    rec.enabled = False
    rec.record("x")
    assert rec.events() == []


# ====================== profiler make_scheduler ======================

def test_make_scheduler_repeat_expiry():
    import paddle_tpu.profiler as profiler

    sched = profiler.make_scheduler(closed=1, ready=1, record=2, repeat=2)
    S = profiler.ProfilerState
    period = 4
    # two full periods follow the closed/ready/record pattern
    for base in (0, period):
        assert sched(base + 0) == S.CLOSED
        assert sched(base + 1) == S.READY
        assert sched(base + 2) == S.RECORD
        assert sched(base + 3) == S.RECORD_AND_RETURN
    # after `repeat` periods the scheduler stays CLOSED forever
    for step in range(2 * period, 2 * period + 8):
        assert sched(step) == S.CLOSED


def test_make_scheduler_zero_period():
    """record=0 with nothing else => never anything to record: CLOSED,
    not a perpetual RECORD (and no ZeroDivisionError)."""
    import paddle_tpu.profiler as profiler

    sched = profiler.make_scheduler(record=0)
    S = profiler.ProfilerState
    for step in range(5):
        assert sched(step) == S.CLOSED


def test_make_scheduler_skip_first():
    import paddle_tpu.profiler as profiler

    sched = profiler.make_scheduler(record=1, skip_first=3)
    S = profiler.ProfilerState
    assert [sched(i) for i in range(3)] == [S.CLOSED] * 3
    assert sched(3) == S.RECORD_AND_RETURN


# ===================== flash dispatch telemetry =====================

def _flash_fa():
    from paddle_tpu.ops.pallas import flash_attention as fa

    return fa


def test_flash_dispatch_tier_counters(monkeypatch):
    """End-to-end dispatch-tier counters for representative shapes: the
    shape routes to flat or transpose (interpret-mode kernels on CPU)
    and each dispatch increments its tier counter; the CPU fallback
    increments tier=fallback."""
    fa = _flash_fa()
    metrics.enable()
    q = _rand((1, 128, 2, 64))

    # fallback: flash unavailable on CPU
    fa.flash_attention_fwd(q, q, q, is_causal=True)
    snap = metrics.snapshot()
    assert snap["counters"]["flash.dispatch{tier=fallback}"] == 1
    assert snap["counters"][
        "flash.fallback_reason{reason=unavailable}"] == 1

    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    # H*D = 128 at head size 64 takes flat; a length off 8 is padded
    # and takes transpose
    for x, tier in ((q, "flat"), (_rand((1, 60, 2, 64)), "transpose")):
        fa.flash_attention_fwd(x, x, x, is_causal=True)
        snap = metrics.snapshot()
        assert snap["counters"].get(
            "flash.dispatch{tier=%s}" % tier, 0) == 1, (tier, snap)


def test_flash_gate_reject_metric_and_flight(monkeypatch):
    """Satellite: gate rejects increment flash.gate_reject with the
    reason and leave shape evidence in the flight recorder."""
    fa = _flash_fa()
    metrics.enable()
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    # d=32: lane-aligned (4*32=128) but head width not compile-proven
    q = _rand((1, 128, 4, 32))
    fa.flash_attention_fwd(q, q, q, is_causal=True)
    snap = metrics.snapshot()
    assert snap["counters"][
        "flash.gate_reject{gate=flat,reason=head_width}"] == 1
    # the reject fell back to the transpose core
    assert snap["counters"]["flash.dispatch{tier=transpose}"] == 1
    evts = [e for e in flight.events() if e["kind"] == "flash.gate_reject"]
    assert evts and evts[-1]["reason"] == "head_width"
    assert evts[-1]["q_shape"] == [1, 128, 4, 32]

    # vmem reject at tuned-size blocks (gate-only: no kernel runs)
    class _Mid:
        shape = (1, 1024, 12, 64)
        dtype = jnp.dtype(jnp.bfloat16)

    assert not fa._flat_native_ok(_Mid(), _Mid(), 1024, 1024)
    snap = metrics.snapshot()
    assert snap["counters"]["flash.gate_reject{gate=flat,reason=vmem}"] == 1


def test_autotune_cross_layout_reject(monkeypatch):
    """Satellite: a transpose-tuned cache entry is NOT silently reused
    by the flat core — the refusal counts
    autotune.cross_layout_reject."""
    fa = _flash_fa()
    from paddle_tpu.ops.pallas import autotune

    metrics.enable()
    b, sq, sk, h, d = 2, 1024, 1024, 4, 64
    base_sig = f"{b}x{sq}x{sk}x{h}x{d}|bfloat16|c1"
    devkind = jax.devices()[0].platform  # "cpu" in tests
    monkeypatch.setattr(autotune, "_cache", {
        f"{devkind}|{fa._AUTOTUNE_OP}|{base_sig}": {"config": [512, 1024]}})
    monkeypatch.setattr(autotune, "_devkind", lambda: devkind)
    assert autotune.cached_config(fa._AUTOTUNE_OP, base_sig) == (512, 1024)
    fa._tuned_blocks(b, sq, sk, h, d, jnp.bfloat16, True, layout="flat")
    snap = metrics.snapshot()
    assert snap["counters"][
        "autotune.cross_layout_reject{layout=flat}"] == 1
    # transpose signature itself does NOT count a refusal
    fa._tuned_blocks(b, sq, sk, h, d, jnp.bfloat16, True)
    snap = metrics.snapshot()
    assert snap["counters"][
        "autotune.cross_layout_reject{layout=flat}"] == 1


def test_autotune_hit_miss_counters(monkeypatch):
    from paddle_tpu.ops.pallas import autotune

    metrics.enable()
    monkeypatch.setattr(autotune, "_enabled", lambda: True)
    monkeypatch.setattr(autotune, "_devkind", lambda: "testdev")
    monkeypatch.setattr(autotune, "_cache",
                        {"testdev|op1|s1": {"config": [1, 2]}})
    monkeypatch.setattr(autotune, "_save", lambda: None)
    assert autotune.pick("op1", "s1", [(1, 2), (3, 4)], None, (3, 4)) \
        == (1, 2)
    snap = metrics.snapshot()
    assert snap["counters"]["autotune.hit"] == 1

    def run(cfg):
        return (lambda y: y + 1.0), jnp.zeros((8, 8), jnp.float32)

    monkeypatch.setattr(autotune, "_slope_time", lambda f, x: 1.0)
    autotune.pick("op1", "s2", [(1, 2), (3, 4)], run, (3, 4))
    snap = metrics.snapshot()
    assert snap["counters"]["autotune.miss"] == 1


# ===================== jit trace-cache telemetry =====================

def test_jit_retrace_counter():
    import paddle_tpu as P

    metrics.enable()

    @P.jit.to_static
    def f(x):
        return x * 2.0

    a = P.to_tensor(np.ones((4,), np.float32))
    f(a)  # first build: miss, but NOT a retrace
    f(a)  # hit
    snap = metrics.snapshot()
    assert snap["counters"]["jit.trace_cache.miss"] == 1
    assert snap["counters"]["jit.trace_cache.hit"] == 1
    assert "jit.retrace" not in snap["counters"]
    b = P.to_tensor(np.ones((8,), np.float32))
    f(b)  # new signature: miss AND retrace
    snap = metrics.snapshot()
    assert snap["counters"]["jit.trace_cache.miss"] == 2
    assert snap["counters"]["jit.retrace"] == 1
    evts = [e for e in flight.events() if e["kind"] == "jit.retrace"]
    assert evts and evts[-1]["fn"] == "f"


# ======================= collective telemetry =======================

def test_collective_call_counter():
    import paddle_tpu as P
    from paddle_tpu.distributed import collective, fleet, topology

    topology.reset_topology()
    fleet.init(is_collective=True)
    metrics.enable()
    t = P.to_tensor(np.ones((4,), np.float32))
    collective.all_reduce(t)
    snap = metrics.snapshot()
    key = [k for k in snap["counters"]
           if k.startswith("collective.calls") and "all_reduce" in k]
    assert key and snap["counters"][key[0]] == 1


# ========================== step stats ==========================

def test_step_timer_records_and_summary(tmp_path):
    metrics.enable()
    sink = str(tmp_path / "steps.jsonl")
    timer = step_stats.StepTimer(
        run_id="t1", tokens_per_step=1000, flops_per_step=1e9,
        peak_flops=1e12, sink=sink, read_device_memory=False)
    timer.record(2.0, compile_step=True, transfer_bytes=64)
    for _ in range(4):
        timer.record(0.01)
    s = timer.summary()
    assert s["schema"] == step_stats.SCHEMA_VERSION
    assert s["run_id"] == "t1"
    assert s["steps"] == 5 and s["records"] == 5
    assert s["compile_ms"]["count"] == 1
    assert s["compile_ms"]["total"] == pytest.approx(2000.0)
    assert s["wall_ms"]["count"] == 4
    assert s["wall_ms"]["mean"] == pytest.approx(10.0, rel=1e-3)
    assert s["tokens_per_s"] == pytest.approx(1000 / 0.01, rel=1e-3)
    assert s["mfu"] == pytest.approx(1e9 / 0.01 / 1e12, rel=1e-3)
    assert s["transfer_bytes"] == 64
    # metrics side-channel: wall histogram observed
    snap = metrics.snapshot()
    assert snap["histograms"]["step.wall_ms{run_id=t1}"]["count"] == 4
    assert snap["histograms"]["step.compile_ms{run_id=t1}"]["count"] == 1


def test_step_stats_jsonl_roundtrip(tmp_path):
    """Round-trip: StepTimer sink -> chip-log loader -> schema validate
    -> summarize (the analyze_chip_log consumption path)."""
    sink = str(tmp_path / "steps.jsonl")
    timer = step_stats.StepTimer(run_id="rt", tokens_per_step=512,
                                 sink=sink, read_device_memory=False)
    timer.record(1.5, compile_step=True)
    timer.record(0.25, n_steps=5)
    entries = [json.loads(l) for l in open(sink)]
    assert len(entries) == 2
    assert step_stats.validate_stream(entries) == []
    summ = step_stats.summarize_stream(entries)
    assert summ["rt"]["records"] == 2 and summ["rt"]["steps"] == 6
    assert summ["rt"]["compile_ms_total"] == pytest.approx(1500.0)
    assert summ["rt"]["steady_wall_ms"]["mean"] == pytest.approx(50.0)
    # the stream is chip-session-log compatible: every line has phase+t
    assert all(e["phase"] == "step_stats" and "t" in e for e in entries)


def test_step_stats_validation_catches_bad_entries():
    good = {"phase": "step_stats", "t": "2026-08-04T00:00:00",
            "run_id": "x", "step": 0, "n_steps": 1, "wall_ms": 1.0,
            "compile": False}
    assert step_stats.validate_stream([good]) == []
    bad_missing = {k: v for k, v in good.items() if k != "wall_ms"}
    bad_type = dict(good, wall_ms="fast")
    bad_neg = dict(good, wall_ms=-1.0)
    other_phase = {"phase": "bench", "whatever": 1}  # ignored
    errs = step_stats.validate_stream(
        [good, bad_missing, bad_type, bad_neg, other_phase])
    assert len(errs) == 3
    assert any("missing required key 'wall_ms'" in e for e in errs)
    assert any("has type str" in e for e in errs)
    assert any("negative wall_ms" in e for e in errs)


def test_analyze_chip_log_digests_step_stats(tmp_path):
    """tools/analyze_chip_log.py consumes interleaved chip-session +
    step-stats streams uniformly (the satellite CI/tooling item)."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "_acl", os.path.join(os.path.dirname(__file__), os.pardir,
                             "tools", "analyze_chip_log.py"))
    acl = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(acl)
    log = tmp_path / "log.jsonl"
    rows = [
        {"phase": "bench", "t": "t0", "metric": "m", "value": 1.0},
        {"phase": "step_stats", "t": "t1", "run_id": "r1", "step": 0,
         "n_steps": 1, "wall_ms": 100.0, "compile": True},
        {"phase": "step_stats", "t": "t2", "run_id": "r1", "step": 1,
         "n_steps": 4, "wall_ms": 10.0, "compile": False,
         "tokens_per_s": 200.0},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    entries = acl.load(str(log))
    text = acl.digest(entries)
    assert "## step_stats" in text
    assert "r1" in text and "compile_ms_total" in text
    assert "schema errors" not in text
    # a corrupt stream is called out
    rows.append({"phase": "step_stats", "t": "t3"})
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    text = acl.digest(acl.load(str(log)))
    assert "schema errors" in text


# ==================== attach() snapshot schema ====================

def test_attach_snapshot_schema_end_to_end(monkeypatch):
    """The acceptance-criteria schema: after attach(), a run that
    dispatches flash attention and feeds a StepTimer yields a snapshot
    containing (at least) flash dispatch-tier counts, autotune hit/miss,
    retrace count, and per-step wall-time stats — the exact keys
    bench.py --telemetry embeds in the bench JSON."""
    fa = _flash_fa()
    reg = obs.attach(crash_hook=False)
    assert metrics.enabled()
    # drive a dispatch (CPU fallback tier) and a couple of steps
    q = _rand((1, 64, 2, 32))
    fa.flash_attention_fwd(q, q, q, is_causal=True)
    timer = obs.StepTimer(run_id="e2e", tokens_per_step=128,
                          read_device_memory=False)
    timer.record(0.5, compile_step=True)
    timer.record(0.02, n_steps=2)
    snap = reg.snapshot()
    c = snap["counters"]
    # dispatch tiers all present (pre-declared), fallback actually fired
    # ON the declared key — declared schema keys carry exactly the label
    # sets the live increments use
    for tier in ("transpose", "flat", "fallback", "biased"):
        assert "flash.dispatch{tier=%s}" % tier in c
    assert c["flash.dispatch{tier=fallback}"] >= 1
    assert c["flash.fallback_reason{reason=unavailable}"] >= 1
    # autotune + retrace + collective schema present even when cold
    for key in ("autotune.hit", "autotune.miss",
                "autotune.cross_layout_reject{layout=flat}",
                "jit.retrace", "jit.trace_cache.hit",
                "jit.trace_cache.miss",
                "collective.calls{kind=all_reduce}",
                "collective.calls{kind=barrier}"):
        assert key in c, key
    # per-step wall stats
    assert snap["histograms"]["step.wall_ms{run_id=e2e}"]["count"] == 1
    summ = timer.summary()
    assert summ["wall_ms"]["mean"] == pytest.approx(10.0, rel=1e-3)
    assert summ["compile_ms"]["count"] == 1


@pytest.mark.parametrize("section,keys", [
    ("counters", [
        "engine.steps{kind=decode}", "engine.decode_slots", "engine.decode_live_tokens",
        "engine.prefill_tokens{cache=hit}",
        "engine.prefill_tokens{cache=partial}",
        "engine.prefill_tokens{cache=miss}"]),
    ("histograms", [
        "engine.submit_wait_ms", "engine.admit_wait_ms",
        "engine.lock_wait_ms{who=loop}"]),
])
def test_attach_declares_engine_accounting_schema(section, keys):
    """ISSUE 33: a fresh process shows the engine's step accounting and
    its wait histograms at zero — a reader takes deltas and never has
    to ask whether the key exists."""
    snap = obs.attach(crash_hook=False).snapshot()[section]
    try:
        for key in keys:
            assert key in snap, key
            got = snap[key]
            assert (got["count"] if section == "histograms" else got) == 0
    finally:
        obs.detach()


def test_bench_telemetry_stack_importable():
    """Satellite CI gate: the bench entrypoint and the whole telemetry
    stack import under JAX_PLATFORMS=cpu (conftest pins cpu), and the
    bench knows its --telemetry flag."""
    import bench

    assert bench._TELEMETRY_FLAG == "--telemetry"
    assert callable(bench._attach_telemetry)
    import paddle_tpu.observability  # noqa: F401
    import paddle_tpu.observability.flight  # noqa: F401
    import paddle_tpu.observability.metrics  # noqa: F401
    import paddle_tpu.observability.step_stats  # noqa: F401
    from paddle_tpu.ops import pallas  # noqa: F401  # dispatch wiring


@pytest.mark.slow
def test_bench_telemetry_subprocess(tmp_path):
    """Full acceptance run: `python bench.py --force-cpu --telemetry`
    emits a headline JSON line with the metrics snapshot embedded."""
    import subprocess
    import sys as _sys

    root = os.path.join(os.path.dirname(__file__), os.pardir)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [_sys.executable, os.path.join(root, "bench.py"), "--force-cpu",
         "--telemetry"],
        capture_output=True, text=True, timeout=1200, env=env, cwd=root)
    lines = [l for l in r.stdout.splitlines() if l.strip().startswith("{")]
    assert lines, r.stderr[-2000:]
    head = json.loads(lines[-1])
    tele = head.get("telemetry")
    assert tele, head
    c = tele["metrics"]["counters"]
    assert any(k.startswith("flash.dispatch") for k in c)
    assert "autotune.hit" in c and "autotune.miss" in c
    assert "jit.retrace" in c
    assert tele["step_stats"]["wall_ms"]["count"] >= 1
