"""The readers that join the device trace to the program's own ledger
(PR 26): `scope_ms`, `unscoped_share`, `program_ledger_s` on hand-made runs,
and one traced rehearsal of a cell on the CPU stand-in that has to end with
every per-layer metric that can exist there."""
import json
import time

import pytest

from harness import common

scope_ms = common.load_module("readers", "scope_ms")
unscoped_share = common.load_module("readers", "unscoped_share")
program_ledger_s = common.load_module("readers", "program_ledger_s")

LOSS = "jit(step)/train_step.loss/"
OPS = {
    "fusion.1": LOSS + "jvp(GPTForCausalLM)/gpt/h.0/attn/dot_general",
    "fusion.2": LOSS + "transpose(jvp(GPTForCausalLM))/gpt/h.0/mlp/dot_general",
    "jvp_flash_flat_fwd_.3": LOSS + "jvp(GPTForCausalLM)/gpt/h.1/attn/"
                             "jvp(flash_flat_fwd)/pallas_call",
    "copy.4": LOSS + "transpose(train_step.loss)/jvp(GPTForCausalLM)/gpt/h.1/"
              "attn/flash.layout/transpose",
    "fusion.5": LOSS + "transpose(train_step.loss)/jvp(GPTPretrainingCriterion)"
                "/head_ce/while/body/dot_general",
    "divide_subtract_fusion": "jit(step)/train_step.update/sub",
    "copy.6": "",                      # the compiler's own: no metadata
    "while.7": LOSS + "jvp(GPTPretrainingCriterion)/head_ce/while",
}


def _hlo(name, op="fusion"):
    return f"%{name} = f32[8]{{0}} {op}(%p), kind=kLoop"


def _run(ops=OPS, steps=2):
    ms = 1_000_000
    events = [(_hlo("fusion.1"), 0, 4 * ms),
              (_hlo("fusion.2"), 4 * ms, 6 * ms),
              (_hlo("jvp_flash_flat_fwd_.3", "custom-call"), 10 * ms, 2 * ms),
              (_hlo("copy.4", "copy"), 12 * ms, 1 * ms),
              (_hlo("while.7", "while"), 13 * ms, 9 * ms),     # a container
              (_hlo("fusion.5"), 13 * ms, 8 * ms),             # ... its body
              (_hlo("divide_subtract_fusion"), 22 * ms, 3 * ms),
              (_hlo("copy.6", "copy"), 25 * ms, 1 * ms),       # no op_name
              (_hlo("fusion.99"), 26 * ms, 1 * ms)]            # not in table
    return {"trace": {"events": {0: events}, "fullest": 0},
            "cell": {"trace": {"steps": steps}},
            "reduced": {"window": (0, 30 * ms), "perf_offset_ns": 0},
            "ledger": {"train_step": {"ops": ops, "module": "jit_step"}}}


@pytest.fixture
def ledger_of_the_run(monkeypatch):
    """The readers ask the program for its ledger; here the run carries it."""
    current = {}

    def install(run):
        current["run"] = run
        return run

    monkeypatch.setattr(scope_ms, "program_ledger",
                        lambda program: current["run"]["ledger"].get(program))
    return install


def _spec(metric):
    return common.load_json("metrics", metric + ".json")


def test_scope_ms_sums_matched_events_per_step(ledger_of_the_run, capsys):
    run = ledger_of_the_run(_run())
    read = lambda m: scope_ms.read(run, _spec(m))
    assert read("fwd_ms.train") == pytest.approx((4 + 2) / 2)
    assert read("bwd_ms.train") == pytest.approx((6 + 1 + 8) / 2)
    assert read("update_ms.train") == pytest.approx(3 / 2)
    assert read("attn_ms.train") == pytest.approx((4 + 2 + 1) / 2)
    assert read("mlp_ms.train") == pytest.approx(6 / 2)
    assert read("head_ce_ms.train") == pytest.approx(8 / 2)  # not the `while`
    assert read("attn_layout_ms.train") == pytest.approx(1 / 2)
    # one `scopes` line for the run, however many metrics read it
    lines = [l for l in capsys.readouterr().out.splitlines()
             if l.startswith("scopes ")]
    assert len(lines) == 1
    assert "'train_step.update:': 1.5" in lines[0]
    assert "'train_step.loss.bwd:GPTForCausalLM/gpt/h.N/mlp': 3.0" in lines[0]
    assert "'fusion': 0.5" in lines[0] and "'copy': 0.5" in lines[0]
    # XLA's families with the scopes they sit under
    assert "'divide_subtract_fusion': {'train_step.update:': 1.5}" in lines[0]
    assert "'copy': {'train_step.loss.bwd:GPTForCausalLM/gpt/h.N/attn': 0.5}" \
        in lines[0]


def test_a_scope_with_no_device_time_reads_zero_not_none(ledger_of_the_run):
    ops = {k: v for k, v in OPS.items() if "flash.layout" not in v}
    run = ledger_of_the_run(_run(ops))
    assert scope_ms.read(run, _spec("attn_layout_ms.train")) == 0.0


def test_unscoped_share_counts_unmatched_and_unnamed(ledger_of_the_run):
    run = ledger_of_the_run(_run())
    # leaf time 26 ms: copy.6 (no op_name) 1 + fusion.99 (no row) 1
    assert unscoped_share.read(run, _spec("unscoped_share.train")) == \
        pytest.approx(100 * 2 / 26)


def test_no_op_table_is_loud_no_ledger_is_silent(ledger_of_the_run):
    run = ledger_of_the_run(_run(ops=None))
    assert unscoped_share.read(run, _spec("unscoped_share.train")) == 100.0
    assert scope_ms.read(run, _spec("fwd_ms.train")) is None
    run = ledger_of_the_run(_run())
    run["ledger"] = {}                        # a program that keeps none
    assert unscoped_share.read(run, _spec("unscoped_share.train")) is None
    assert scope_ms.read(run, _spec("update_ms.train")) is None


def test_readers_find_nothing_on_a_program_without_the_ledger(monkeypatch):
    """The parent of PR 26: `xla_cost` has no `program_ledger`."""
    from paddle_tpu.observability import xla_cost

    monkeypatch.delattr(xla_cost, "program_ledger")
    run = _run()
    assert scope_ms.read(run, _spec("fwd_ms.train")) is None
    assert unscoped_share.read(run, _spec("unscoped_share.train")) is None
    for m in ("setup_trace_lower_s.train", "setup_compile_s.train",
              "setup_other_programs_s.train"):
        assert program_ledger_s.read(run, _spec(m)) is None


def test_program_ledger_s_reads_stages_as_of_the_window(monkeypatch):
    from paddle_tpu.observability import xla_cost

    now = time.perf_counter()
    rec = lambda at, t, l, c: {"trace_ms": t, "lower_ms": l, "compile_ms": c,
                               "ledger_ms": 1.0, "n_ops": 3, "at": at}
    ledger = {"train_step": {"compiles": [rec(now - 50, 20000.0, 13000.0, 9000.0),
                                          rec(now + 50, 1.0, 1.0, 1.0)],
                             "ops": {}, "n_compiles": 2},
              "jit::f": {"compiles": [rec(now - 40, 100.0, 200.0, 300.0)],
                         "ops": {}, "n_compiles": 1}}
    monkeypatch.setattr(xla_cost, "program_ledger",
                        lambda label=None: ledger if label is None
                        else ledger.get(label))
    asked = []

    def totals(until=None):
        asked.append(until)
        return {"trace_ms": 25100.0, "trace_n": 9, "lower_ms": 15200.0,
                "lower_n": 7, "compile_ms": 17300.0, "compile_n": 7}

    monkeypatch.setattr(xla_cost, "process_compile_totals", totals)
    run = _run()
    run["reduced"] = {"window": (int(now * 1e9) + 7, 0), "perf_offset_ns": 7}
    read = lambda m: program_ledger_s.read(run, _spec(m))
    assert read("setup_trace_lower_s.train") == pytest.approx(33.0)
    assert read("setup_compile_s.train") == pytest.approx(9.0)
    # process totals minus BOTH labelled programs, stage by stage
    assert read("setup_other_programs_s.train") == pytest.approx(
        (25100 - 20100 + 15200 - 13200 + 17300 - 9300) / 1e3)
    assert asked == [pytest.approx(now)]


def test_traced_rehearsal_reports_every_per_layer_metric(capsys):
    import run as bench_run

    with open(common.ROOT + "/BENCHMARK.json") as f:
        bench = json.load(f)
    cell = "gpt3-125m.train.seq1024"
    line = json.loads(bench_run.run_cell(cell, 3000000007, 1.0, 1,
                                         rehearse=True))
    _, per_layer = bench_run.cell_metrics(bench, cell)
    assert len(per_layer) == 16
    # the CPU stand-in has no step program line, no Mosaic calls and no
    # device memory counters: those three cannot exist there
    cannot = {"step_ms_p50.train", "flash_roofline.train",
              "peak_hbm_share.train"}
    assert {m["name"] for m in per_layer} - set(line["metrics"]) == cannot
    new = [m["name"] for m in per_layer[5:]]
    assert len(new) == 11 and all(line["metrics"][m]["value"] is not None
                                  for m in new)
    assert line["correct"] is True
    out = capsys.readouterr().out
    assert "\nscopes {" in out and "\nledger {" in out
