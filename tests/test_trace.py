"""Unified trace timeline tests (ISSUE 2): span tracer nesting/threads,
Perfetto round-trip, signal correlation (RecordEvent scopes, flight
instants, StepTimer frames), xla_cost capture on a jitted fn, the
profiler chrome-export pid/tid fix, and the perf_gate
pass/regress/update/check-only/merge paths.
"""
from __future__ import annotations

import importlib.util
import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import observability as obs
from paddle_tpu.observability import flight, metrics, step_stats, trace, \
    xla_cost

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)


def _reset_telemetry():
    trace.clear()
    trace.disable()
    metrics.reset()
    metrics.disable()
    flight.clear()


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts from a disabled, empty tracer/registry/ring (the
    defaults are process-global)."""
    _reset_telemetry()
    yield
    _reset_telemetry()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        "_" + name, os.path.join(ROOT, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ============================ span tracer ============================

def test_span_nesting_and_args():
    trace.enable()
    with trace.span("outer", kind="a"):
        assert trace.current_span() == "outer"
        with trace.span("inner") as sp:
            sp.args["extra"] = 42
    evts = [e for e in trace.events() if e["ph"] == "X"]
    assert [e["name"] for e in evts] == ["inner", "outer"]  # close order
    inner, outer = evts
    assert inner["args"]["parent"] == "outer"
    assert inner["args"]["extra"] == 42
    assert outer["args"]["kind"] == "a"
    # child strictly inside parent on the timeline
    assert inner["ts"] >= outer["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-6
    assert inner["tid"] == outer["tid"]


def test_span_disabled_is_noop():
    assert not trace.enabled()
    with trace.span("nope"):
        pass
    assert trace.begin("x") is None
    trace.end(None)
    trace.instant("nope")
    trace.frame("nope", 10.0)
    assert trace.events() == []


def test_disable_mid_span_pops_stack():
    """end() after a mid-span disable must still pop the thread-local
    stack: a leaked entry would mislabel every later span's parent and
    grow the stack on each toggle."""
    trace.enable()
    sp = trace.begin("outer")
    trace.disable()
    trace.end(sp)
    assert trace.current_span() is None
    trace.enable()
    with trace.span("later"):
        pass
    later = [e for e in trace.events() if e["name"] == "later"][0]
    assert "parent" not in later["args"]


def test_traced_decorator():
    trace.enable()

    @trace.traced("my_fn", cat="op")
    def f(x):
        return x + 1

    assert f(1) == 2
    evts = trace.events()
    assert evts and evts[0]["name"] == "my_fn" and evts[0]["cat"] == "op"


def test_span_nesting_under_threads():
    """Each thread gets its own small stable tid and its own nesting
    stack; spans from different threads never share a stack."""
    trace.enable()
    barrier = threading.Barrier(2)

    def worker(tag):
        barrier.wait()
        with trace.span(f"{tag}.outer"):
            with trace.span(f"{tag}.inner"):
                pass

    threads = [threading.Thread(target=worker, args=(f"t{i}",))
               for i in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evts = {e["name"]: e for e in trace.events()}
    assert len(evts) == 4
    assert evts["t0.inner"]["tid"] == evts["t0.outer"]["tid"]
    assert evts["t1.inner"]["tid"] == evts["t1.outer"]["tid"]
    assert evts["t0.outer"]["tid"] != evts["t1.outer"]["tid"]
    assert evts["t0.inner"]["args"]["parent"] == "t0.outer"
    assert evts["t1.inner"]["args"]["parent"] == "t1.outer"
    # tids are small and stable, not raw thread idents (~1e14): the
    # GLOBAL tracer numbers every span-emitting thread the test
    # session ever had, so the bound is the design constraint — real
    # threads must sort BELOW the synthetic-track base — not an
    # arbitrary small count that suite growth can tip over
    assert all(e["tid"] < trace._VIRTUAL_SORT_BASE
               for e in evts.values())


def test_bounded_buffer_reports_drops():
    tr = trace.SpanTracer(capacity=8, enabled=True)
    for i in range(20):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 8
    assert tr.dropped() == 12
    assert [e["name"] for e in tr.events()] == [f"e{i}" for i in range(12, 20)]
    assert tr.to_chrome()["otherData"]["dropped_events"] == 12


def test_perfetto_roundtrip(tmp_path):
    """export -> json.load -> schema check (the acceptance-criteria
    'json.loads cleanly' property plus the metadata Perfetto needs)."""
    trace.enable()
    with trace.span("work", step=1):
        trace.instant("decision", tier="flat")
    trace.frame("step 0", 5000.0, track="steps:run1", step=0)
    trace.counter("mem", track="mem:run1", bytes=123)
    path = str(tmp_path / "trace.json")
    assert trace.export(path) == path
    with open(path) as f:
        doc = json.load(f)
    evts = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    assert doc["otherData"]["schema"] == trace.SCHEMA_VERSION
    by_ph = {}
    for e in evts:
        by_ph.setdefault(e["ph"], []).append(e)
    # metadata: process_name + thread_name for the real thread AND the
    # synthetic tracks
    meta_names = {(e["name"], e["args"].get("name")) for e in by_ph["M"]}
    assert ("process_name", "paddle_tpu") in meta_names
    assert any(n == "thread_name" and v == "steps:run1"
               for n, v in meta_names)
    assert any(n == "thread_name" and v == "mem:run1"
               for n, v in meta_names)
    # every non-meta event carries pid/tid/ts
    for e in evts:
        if e["ph"] == "M":
            continue
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert e["ts"] >= 0
    assert by_ph["X"] and by_ph["i"] and by_ph["C"]
    # frames/counters sit on synthetic tracks distinct from the thread
    assert by_ph["C"][0]["tid"] != by_ph["i"][0]["tid"]


def test_trace_jsonl_stream_validates(tmp_path):
    trace.enable()
    with trace.span("s"):
        trace.instant("i")
    path = str(tmp_path / "trace.jsonl")
    trace.dump_jsonl(path)
    entries = [json.loads(l) for l in open(path)]
    assert all(e["phase"] == trace.TRACE_PHASE and "t" in e
               for e in entries)
    assert trace.validate_trace_stream(entries) == []
    s = trace.summarize_trace_stream(entries)
    assert s["events"] == 2 and s["by_ph"]["X"] == 1
    # corrupt entries are called out
    bad = [{"phase": trace.TRACE_PHASE, "ph": "X", "name": "x",
            "ts": -1.0, "pid": 1, "tid": 1, "dur": "slow"},
           {"phase": trace.TRACE_PHASE, "ph": "Z", "name": "y"}]
    errs = trace.validate_trace_stream(entries + bad)
    assert len(errs) >= 3


# ========================= signal correlation =========================

def test_record_event_emits_span():
    import paddle_tpu.profiler as profiler

    trace.enable()
    with profiler.RecordEvent("train_step"):
        with profiler.RecordEvent("fwd"):
            pass
    evts = {e["name"]: e for e in trace.events() if e["ph"] == "X"}
    assert set(evts) == {"train_step", "fwd"}
    assert evts["fwd"]["cat"] == "user_scope"
    assert evts["fwd"]["args"]["parent"] == "train_step"


def test_flight_events_become_instants():
    trace.enable()
    flight.get_recorder().enabled = True
    flight.record("flash.gate_reject", gate="flat", reason="vmem")
    evts = [e for e in trace.events() if e["ph"] == "i"]
    assert evts and evts[0]["name"] == "flash.gate_reject"
    assert evts[0]["args"]["reason"] == "vmem"
    # ring still recorded normally
    assert any(e["kind"] == "flash.gate_reject" for e in flight.events())


def test_step_timer_emits_frames():
    trace.enable()
    timer = step_stats.StepTimer(run_id="fr", read_device_memory=False)
    timer.record(0.05, compile_step=True)
    timer.record(0.01, n_steps=4)
    frames = [e for e in trace.events() if e["cat"] == "step"]
    assert len(frames) == 2
    assert frames[0]["name"] == "compile+step"
    assert frames[1]["name"] == "steps 1..4"
    assert frames[1]["args"]["n_steps"] == 4
    assert frames[1]["dur"] == pytest.approx(0.01 * 1e6, rel=1e-2)
    # both frames on the same per-run synthetic track
    assert frames[0]["tid"] == frames[1]["tid"] >= 1000


def test_collective_span_on_timeline():
    import paddle_tpu as P
    from paddle_tpu.distributed import collective, fleet, topology

    topology.reset_topology()
    fleet.init(is_collective=True)
    trace.enable()
    t = P.to_tensor(np.ones((4,), np.float32))
    collective.all_reduce(t)
    spans = [e for e in trace.events() if e["ph"] == "X"]
    assert any(e["name"] == "all_reduce" and e["cat"] == "collective"
               for e in spans)


# ============================ xla_cost ============================

def test_xla_cost_capture_on_jitted_fn():
    """instrument(): first call per signature compiles inside an
    xla.compile span carrying cost_analysis flops/bytes, gauges land on
    the registry, and replays don't recompile."""
    trace.enable()
    metrics.enable()
    inst = xla_cost.instrument(jax.jit(lambda x: x @ x), label="mm")
    x = jnp.ones((32, 32), jnp.float32)
    np.testing.assert_allclose(np.asarray(inst(x)),
                               np.asarray(x @ x), rtol=1e-6)
    inst(x)  # replay: no second compile span
    spans = [e for e in trace.events()
             if e["ph"] == "X" and e["name"] == "xla.compile:mm"]
    assert len(spans) == 1
    assert spans[0]["cat"] == "compile"
    assert spans[0]["args"]["flops"] > 0
    assert "bytes_accessed" in spans[0]["args"]
    snap = metrics.snapshot()
    assert snap["gauges"]["xla.cost.flops{label=mm}"] > 0
    assert xla_cost.last_costs("mm")["flops"] == spans[0]["args"]["flops"]
    # flight carries the compile event too (crash-dump evidence)
    assert any(e["kind"] == "xla.compile" for e in flight.events())
    # a new signature is a new compile span
    inst(jnp.ones((16, 16), jnp.float32))
    spans = [e for e in trace.events()
             if e["ph"] == "X" and e["name"] == "xla.compile:mm"]
    assert len(spans) == 2


def test_xla_cost_tracer_guard_and_disabled_passthrough():
    inst = xla_cost.instrument(jax.jit(lambda x: (x * x).sum()), "sq")
    x = jnp.ones((8,), jnp.float32)
    # telemetry off: plain jit passthrough, nothing captured
    assert float(inst(x)) == 8.0
    assert xla_cost.last_costs("sq") is None
    # ... and the program ledger stays empty: the wrapper forwarded
    # straight to the jitted callable, it compiled nothing itself
    assert xla_cost.program_ledger("sq") is None
    assert inst._compiled == {}
    # telemetry on under an outer trace: Compiled refuses tracers, the
    # guard must route through the composable jit path
    trace.enable()
    g = jax.grad(lambda x: inst(x))(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.ones((8,)), rtol=1e-6)
    assert xla_cost.program_ledger("sq") is None   # the tracer guard too
    assert float(inst(x)) == 8.0  # concrete call still captures
    assert xla_cost.last_costs("sq")["flops"] >= 0
    assert xla_cost.program_ledger("sq")["n_compiles"] == 1


def test_jit_to_static_compile_span():
    """The StaticFunction build path carries the instrument: telemetry-on
    first call produces an annotated compile span."""
    import paddle_tpu as P

    trace.enable()
    metrics.enable()

    @P.jit.to_static
    def f(x):
        return x * 2.0

    a = P.to_tensor(np.ones((4,), np.float32))
    out = f(a)
    np.testing.assert_allclose(out.numpy(), 2 * np.ones((4,)), rtol=1e-6)
    spans = [e for e in trace.events()
             if e["ph"] == "X" and e["name"].startswith("xla.compile:jit::")]
    assert spans and "flops" in spans[0]["args"]


# ====================== profiler chrome export ======================

def test_profiler_chrome_export_pid_tid_metadata(tmp_path):
    """Satellite: exported host traces carry process_name/thread_name
    metadata and small stable per-thread tids so nested scopes render
    in Perfetto instead of collapsing onto one row."""
    import paddle_tpu.profiler as profiler

    prof = profiler.Profiler(timer_only=True)
    prof.start()

    def worker():
        with profiler.RecordEvent("w.outer"):
            with profiler.RecordEvent("w.inner"):
                pass

    with profiler.RecordEvent("main.scope"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    path = str(tmp_path / "host.trace.json")
    prof._export_chrome(path)
    prof.stop()
    with open(path) as f:
        doc = json.load(f)
    evts = doc["traceEvents"]
    meta = [e for e in evts if e["ph"] == "M"]
    xs = [e for e in evts if e["ph"] == "X"]
    assert any(m["name"] == "process_name" for m in meta)
    tids = {e["tid"] for e in xs}
    assert len(tids) == 2  # main thread + worker
    assert all(isinstance(t, int) and 0 < t < 100 for t in tids)
    named = {m["tid"] for m in meta if m["name"] == "thread_name"}
    assert tids <= named
    pid = os.getpid()
    assert all(e["pid"] == pid for e in xs)
    by_name = {e["name"]: e for e in xs}
    assert by_name["w.inner"]["tid"] == by_name["w.outer"]["tid"]
    assert by_name["w.outer"]["tid"] != by_name["main.scope"]["tid"]


# ============================ perf gate ============================

def _write_jsonl(path, rows):
    with open(path, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")


def test_perf_gate_pass_regress_update(tmp_path):
    pg = _load_tool("perf_gate")
    baseline = str(tmp_path / "base.jsonl")
    _write_jsonl(baseline, [
        {"metric": "m.tokens", "value": 100.0, "unit": "tok/s",
         "captured_at": 100.0},
        {"metric": "m.tokens", "value": 90.0, "unit": "tok/s",
         "captured_at": 50.0},  # stale row must not win
        {"metric": "m.lat_ms", "value": 10.0, "lower_better": True,
         "captured_at": 100.0},
        {"metric": "m.degraded", "value": 5.0, "degraded": True,
         "captured_at": 100.0},  # degraded baseline rows are ignored
    ])
    results = str(tmp_path / "res.json")

    # within tolerance (higher-better -5% at 10%): pass
    _write_jsonl(results, [{"metric": "m.tokens", "value": 95.0}])
    assert pg.main([results, "--baseline", baseline]) == 0

    # beyond tolerance: regression exit code
    _write_jsonl(results, [{"metric": "m.tokens", "value": 80.0}])
    assert pg.main([results, "--baseline", baseline]) == 2

    # per-metric tolerance override rescues it
    assert pg.main([results, "--baseline", baseline,
                    "--metric-tolerance", "m.tokens=0.25"]) == 0

    # lower-better: value above floor fails
    _write_jsonl(results, [{"metric": "m.lat_ms", "value": 12.0}])
    assert pg.main([results, "--baseline", baseline]) == 2
    _write_jsonl(results, [{"metric": "m.lat_ms", "value": 10.5}])
    assert pg.main([results, "--baseline", baseline]) == 0

    # degraded current rows are skipped, new metrics pass
    _write_jsonl(results, [
        {"metric": "m.tokens", "value": 1.0, "degraded": True},
        {"metric": "m.new", "value": 7.0}])
    assert pg.main([results, "--baseline", baseline]) == 0

    # --update rolls the baseline: the new floor now gates
    _write_jsonl(results, [{"metric": "m.tokens", "value": 200.0}])
    assert pg.main([results, "--baseline", baseline, "--update"]) == 0
    _write_jsonl(results, [{"metric": "m.tokens", "value": 150.0}])
    assert pg.main([results, "--baseline", baseline]) == 2


def test_perf_gate_telemetry_derived_metrics(tmp_path):
    """A headline row with an embedded telemetry block gates the derived
    mfu (higher-better) and steady-wall (lower-better) series."""
    pg = _load_tool("perf_gate")
    head = {"metric": "m", "value": 100.0,
            "telemetry": {"metrics": {}, "step_stats": {
                "mfu": 0.40, "wall_ms": {"mean": 210.0, "count": 5}}}}
    results = str(tmp_path / "res.json")
    _write_jsonl(results, [head])
    rows = pg.load_results(results)
    by_m = {r["metric"]: r for r in rows}
    assert by_m["m.mfu"]["value"] == pytest.approx(0.40)
    assert by_m["m.steady_wall_ms"]["lower_better"] is True
    baseline = str(tmp_path / "base.jsonl")
    _write_jsonl(baseline, [{"metric": "m", "value": 100.0}])
    assert pg.main([results, "--baseline", baseline, "--update"]) == 0
    # mfu collapse now fails the gate even with the headline flat
    head2 = {"metric": "m", "value": 100.0,
             "telemetry": {"metrics": {}, "step_stats": {
                 "mfu": 0.20, "wall_ms": {"mean": 210.0, "count": 5}}}}
    _write_jsonl(results, [head2])
    assert pg.main([results, "--baseline", baseline]) == 2


def test_perf_gate_check_only_smoke():
    """Satellite CI hook: the repo's own baseline validates (fast,
    non-slow — this is the smoke the suite always runs)."""
    pg = _load_tool("perf_gate")
    assert pg.main(["--check-only"]) == 0


def test_perf_gate_check_only_catches_corruption(tmp_path):
    pg = _load_tool("perf_gate")
    bad = str(tmp_path / "bad.jsonl")
    with open(bad, "w") as f:
        f.write('{"metric": "ok", "value": 1.0}\nnot json\n'
                '{"metric": "noval"}\n')
    assert pg.main(["--check-only", "--baseline", bad]) == 1
    missing = str(tmp_path / "missing.jsonl")
    assert pg.main(["--check-only", "--baseline", missing]) == 1


def test_perf_gate_merge_trace(tmp_path):
    """Merge mode folds tracer export + step_stats JSONL + flight dump
    into one Perfetto file that json.loads cleanly."""
    pg = _load_tool("perf_gate")
    # span file from a real tracer
    trace.enable()
    with trace.span("compile", flops=123.0):
        pass
    span_file = trace.export(str(tmp_path / "spans.json"))
    # step stats stream
    steps = str(tmp_path / "steps.jsonl")
    timer = step_stats.StepTimer(run_id="r1", sink=steps,
                                 read_device_memory=False)
    timer.record(0.2, compile_step=True)
    timer.record(0.01, n_steps=3)
    # flight dump
    flight.get_recorder().enabled = True
    flight.record("jit.retrace", fn="f")
    fdump = flight.dump(str(tmp_path / "flight.jsonl"))
    out = str(tmp_path / "merged.json")
    rc = pg.main(["--merge-trace", out, "--spans", span_file,
                  "--step-stats", steps, "--flight", fdump])
    assert rc == 0
    with open(out) as f:
        doc = json.load(f)
    evts = doc["traceEvents"]
    names = [e["name"] for e in evts]
    assert "compile" in names            # span survived
    assert "compile+step" in names       # step frame reconstructed
    assert "jit.retrace" in names        # flight instant folded
    # the three families live on distinct processes
    pids = {e["pid"] for e in evts if e["ph"] != "M"}
    assert len(pids) >= 3
    # step frames accumulate: steady frame starts after the compile wall
    step_evts = [e for e in evts if e.get("cat") == "step"]
    assert step_evts[1]["ts"] == pytest.approx(
        step_evts[0]["ts"] + step_evts[0]["dur"], rel=1e-6)


# ======================= analyze_chip_log hook =======================

def test_analyze_chip_log_validates_trace_stream(tmp_path):
    """Satellite: the chip-log analyzer digests and validates trace
    JSONL streams interleaved with step_stats."""
    acl = _load_tool("analyze_chip_log")
    log = tmp_path / "log.jsonl"
    rows = [
        {"phase": "step_stats", "t": "t1", "run_id": "r1", "step": 0,
         "n_steps": 1, "wall_ms": 100.0, "compile": True},
        {"phase": "trace_event", "t": "t2", "name": "fwd", "ph": "X",
         "ts": 0.0, "dur": 5.0, "pid": 1, "tid": 1},
        {"phase": "trace_event", "t": "t3", "name": "gate", "ph": "i",
         "ts": 2.0, "pid": 1, "tid": 1},
    ]
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    text = acl.digest(acl.load(str(log)))
    assert "## trace_events" in text and "## step_stats" in text
    assert "schema errors" not in text
    # a corrupt trace entry fails the digest AND the CLI exit code
    rows.append({"phase": "trace_event", "t": "t4", "name": "bad",
                 "ph": "X", "ts": 1.0, "pid": 1, "tid": 1, "dur": -3.0})
    log.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    text = acl.digest(acl.load(str(log)))
    assert "schema errors" in text
    assert acl.main(["analyze_chip_log.py", str(log)]) == 1


# ========================== attach wiring ==========================

def test_attach_enables_tracer_detach_disables():
    assert not trace.enabled()
    obs.attach(crash_hook=False)
    assert trace.enabled() and metrics.enabled()
    with trace.span("alive"):
        pass
    assert any(e["name"] == "alive" for e in trace.events())
    obs.detach()
    assert not trace.enabled() and not metrics.enabled()


def test_export_compat_resolves_jax_export():
    """Export consumers resolve jax.export at call time through
    core.export_compat; the installed jax always has it."""
    from paddle_tpu.core import export_compat as ec

    assert ec.jax_export_available()
    assert hasattr(ec.get_jax_export(), "export")
    assert issubclass(ec.ExportUnavailableError, ImportError)
