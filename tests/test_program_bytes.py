"""The program ledger accounts for the step's BYTES (ISSUE 37): a liveness
sweep of the scheduled program (`observability.xla_cost.buffer_sweep`) on
hand-written HLO texts (on compiled programs: tests/test_chip_compile.py),
the phase and scope a buffer is named by, the ledger entry's `memory` / `bytes`,
and the benchmark's readers over them.
"""
from __future__ import annotations

import os

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observability import flight, metrics, trace, xla_cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MB = 1 << 20


@pytest.fixture(autouse=True)
def _clean():
    def reset():
        trace.clear()
        trace.disable()
        metrics.reset()
        metrics.disable()
        flight.clear()

    reset()
    yield
    reset()


def _sweep(entry_body, others=""):
    """The sweep of a module whose ENTRY holds `entry_body`; op_names from
    the text's own metadata."""
    text = ("HloModule jit_step, is_scheduled=true\n\n" + others +
            "ENTRY %main.1 (a: f32[262144]) -> f32[262144] {\n"
            "  %a = f32[262144]{0} parameter(0)\n" + entry_body + "}\n")
    ops, _ = xla_cost.op_table(text)
    return xla_cost.buffer_sweep(text, ops)


def _meta(op_name):
    return f', metadata={{op_name="{op_name}"}}'


FWD = "jit(step)/train_step.loss/jvp(M)/layers.1/attn/dot_general"
BWD = "jit(step)/train_step.loss/transpose(jvp(M))/layers.1/attn/dot_general"
REPLAY = ("jit(step)/train_step.loss/transpose(jvp(M))/layers.1/train_step."
          "loss/jvp(M)/layers.1/checkpoint/rematted_computation/attn/mul")
UPDATE = "jit(step)/train_step.update/sub"


# ============================ the sweep's rules ============================

def test_chain_peaks_where_two_neighbours_are_live():
    got = _sweep(
        "  %b = f32[262144]{0} negate(%a)\n"            # 1 MB each
        "  %c = f32[524288]{0} concatenate(%b, %b)\n"   # 2 MB, reads b
        "  %d = f32[262144]{0} slice(%c)\n"             # c dies here
        "  ROOT %e = f32[262144]{0} negate(%d)\n")
    assert got["n_buffers"] == 4 and got["n_containers"] == 0
    # b + c live at c; then c + d; the ROOT's result is an output, the
    # parameter an argument: neither is a temporary
    assert got["peak_bytes"] == 3 * MB
    assert got["peak_at"]["instruction"] in ("c", "d")
    assert [row[:2] for row in got["live_at_peak"]][0] == [2 * MB, "c"]


def test_life_runs_through_bitcast_and_get_tuple_element():
    got = _sweep(
        "  %b = f32[262144]{0} negate(%a)\n"
        "  %v = f32[512,512]{1,0} bitcast(%b)\n"
        "  %t = (f32[512,512]{1,0}, f32[262144]{0}) tuple(%v, %a)\n"
        "  %g = f32[512,512]{1,0} get-tuple-element(%t), index=0\n"
        "  %big = f32[1048576]{0} broadcast(%a)\n"      # 4 MB, b still live
        "  %sum = f32[262144]{0} add(%g, %a)\n"         # the last reader of b
        "  ROOT %e = f32[262144]{0} add(%sum, %big)\n")
    # the views made no buffer; b lived from its line to `sum` through them
    assert got["n_buffers"] == 4          # b, big, sum, e
    assert got["peak_bytes"] == 6 * MB    # b + big + sum at `sum`
    assert got["peak_at"]["instruction"] == "sum"


def test_copy_start_done_pair_counts_its_destination_once():
    got = _sweep(
        "  %b = f32[262144]{0} negate(%a)\n"
        "  %cs = (f32[262144]{0}, f32[262144]{0}, u32[]{:S(2)}) copy-start(%b)\n"
        "  %cd = f32[262144]{0} copy-done(%cs)\n"
        "  %big = f32[524288]{0} broadcast(%a)\n"
        "  ROOT %e = f32[524288]{0} add(%cd, %big)\n")
    assert got["n_buffers"] == 4          # b, the copy's destination, big, e
    # at `big`: the destination (kept alive through copy-done) + big; b died
    # at copy-start, and the tuple's second element is b itself
    assert got["peak_bytes"] == 3 * MB


def test_result_outside_hbm_is_not_counted():
    got = _sweep(
        "  %v = f32[262144]{0:T(1024)S(1)} negate(%a)\n"     # VMEM
        "  %t = (f32[262144]{0:T(1024)S(1)}, f32[262144]{0:T(1024)}) "
        "fusion(%v), kind=kLoop, calls=%f\n"
        "  %g = f32[262144]{0:T(1024)} get-tuple-element(%t), index=1\n"
        "  ROOT %e = f32[524288]{0} broadcast(%g)\n")
    assert got["n_buffers"] == 2          # the tuple's HBM element, e
    assert got["peak_bytes"] == 1 * MB


def test_tuple_shaped_fusion_is_one_buffer_an_element():
    got = _sweep(
        "  %t = (f32[262144]{0}, f32[524288]{0}) fusion(%a), kind=kLoop, "
        "calls=%f\n"
        "  %small = f32[262144]{0} get-tuple-element(%t), index=0\n"
        "  %big = f32[524288]{0} get-tuple-element(%t), index=1\n"
        "  %x = f32[524288]{0} negate(%big)\n"          # big dies here
        "  %y = f32[1048576]{0} broadcast(%x)\n"        # 4 MB
        "  ROOT %e = f32[1048576]{0} add(%y, %small)\n")
    # reading one element keeps the other no longer: at `y` the live set is
    # small (1) + x (2) + y (4), not the tuple's 2 MB element too
    assert got["n_buffers"] == 5
    assert got["peak_bytes"] == 7 * MB
    assert got["peak_at"]["instruction"] == "y"


def test_while_lives_in_its_operands_buffers_and_adds_its_bodys_peak():
    body = (
        "%body.1 (p: (s32[], f32[262144])) -> (s32[], f32[262144]) {\n"
        "  %p = (s32[], f32[262144]{0}) parameter(0)\n"
        "  %i = s32[] get-tuple-element(%p), index=0\n"
        "  %x = f32[262144]{0} get-tuple-element(%p), index=1\n"
        "  %tmp = f32[786432]{0} broadcast(%x)\n"       # 3 MB inside a turn
        "  %y = f32[262144]{0} slice(%tmp)\n"
        "  ROOT %r = (s32[], f32[262144]{0}) tuple(%i, %y)\n"
        "}\n\n"
        "%cond.1 (p: (s32[], f32[262144])) -> pred[] {\n"
        "  %p = (s32[], f32[262144]{0}) parameter(0)\n"
        "  ROOT %lt = pred[] constant(true)\n"
        "}\n\n")
    got = _sweep(
        "  %zero = s32[] constant(0)\n"
        "  %b = f32[262144]{0} negate(%a)\n"
        "  %init = (s32[], f32[262144]{0}) tuple(%zero, %b)\n"
        "  %w = (s32[], f32[262144]{0}) while(%init), condition=%cond.1, "
        "body=%body.1\n"
        "  %out = f32[262144]{0} get-tuple-element(%w), index=1\n"
        "  %big = f32[524288]{0} broadcast(%out)\n"
        "  ROOT %e = f32[524288]{0} negate(%big)\n", others=body)
    assert got["n_containers"] == 1
    # the loop's state is b (1 MB), counted once for init, while and out;
    # one turn of the body holds tmp (3 MB) beside it: 4 MB at the while
    assert got["peak_bytes"] == 4 * MB
    assert got["peak_at"]["instruction"] == "w"
    rows = {row[1]: row[0] for row in got["live_at_peak"]}
    assert rows == {"w": 3 * MB, "b": 1 * MB}


def test_output_aliased_to_an_operand_is_that_operand():
    got = _sweep(
        "  %b = f32[262144]{0} negate(%a)\n"
        "  %u = f32[262144]{0} custom-call(%b, %a), custom_call_target=\"k\", "
        "output_to_operand_aliasing={{}: (0, {})}\n"    # written into b
        "  %big = f32[524288]{0} broadcast(%a)\n"
        "  ROOT %e = f32[524288]{0} add(%big, %u)\n")
    assert got["n_buffers"] == 3          # b (which u is), big, e
    assert got["peak_bytes"] == 3 * MB    # b lives on as u, beside big


@pytest.mark.parametrize("type_text,want", [
    ("f32[8,128]{1,0}", (4096, 0)),
    ("bf16[16,768,2048]{2,1,0:T(8,128)(2,1)S(1)}", (16 * 768 * 2048 * 2, 1)),
    # a [B, T, 1] under T(8,128) takes 128 lanes a row
    ("f32[2,8192,1]{2,1,0:T(8,128)}", (2 * 8192 * 128 * 4, 0)),
    # minor_to_major {0,1}: the 3 is the minor dimension, padded to 128
    ("f32[3,16]{0,1:T(8,128)}", (16 * 128 * 4, 0)),
    ("pred[20480]{0:T(1024)(128)(4,1)}", (20480, 0)),
    ("s32[]{:T(128)}", (512, 0)),
    ("u32[]{:S(2)}", (4, 2)),
    ("token[]", (0, 0)),
])
def test_a_results_bytes_are_its_layouts(type_text, want):
    assert xla_cost._array_bytes(type_text) == want


# ======================== phase, scope, residuals ========================

@pytest.mark.parametrize("op_name,want", [
    (FWD, "fwd"), (BWD, "bwd"), (REPLAY, "replay"), (UPDATE, "update"),
    ("ragged-dot-none", "other"), ("", "other"),
])
def test_phase_of_reads_the_op_name(op_name, want):
    assert xla_cost.phase_of(op_name) == want


@pytest.mark.parametrize("op_name", [
    FWD, BWD, REPLAY, UPDATE, "ragged-dot-none", "params['w']",
    "jit(step)/train_step.loss/jvp(GPT)/gpt/h.11/attn/flash.layout/transpose",
])
def test_scope_of_is_the_key_the_benchmarks_scopes_line_prints(
        op_name, monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    from harness import common

    reader = common.load_module("readers", "scope_ms")
    assert xla_cost.scope_of(op_name) == reader._scope_of(op_name)


def test_residuals_are_what_the_forward_holds_when_the_backward_begins():
    got = _sweep(
        "  %w = f32[262144]{0} transpose(%a)" + _meta(BWD) + "\n"   # early
        "  %kept = f32[262144]{0} negate(%a)" + _meta(FWD) + "\n"
        "  %gone = f32[524288]{0} broadcast(%a)" + _meta(FWD) + "\n"
        "  %loss = f32[262144]{0} slice(%gone)" + _meta(FWD) + "\n"
        "  %again = f32[262144]{0} negate(%kept)" + _meta(REPLAY) + "\n"
        "  %dx = f32[262144]{0} multiply(%again, %w)" + _meta(BWD) + "\n"
        "  ROOT %new = f32[262144]{0} subtract(%a, %dx)" + _meta(UPDATE)
        + "\n")
    # the backward begins at its first reader of a forward buffer (`again`),
    # not at the weight's transpose the scheduler ran early; `gone` is dead
    # by then, `loss` was never read again, `w` was born in phase bwd
    assert got["backward_at"]["instruction"] == "again"
    assert got["residual_bytes"] == 1 * MB
    assert got["residual_by_scope"] == {"train_step.loss.fwd:M/layers.N/attn":
                                        1 * MB}
    assert got["peak_at"]["phase"] == "fwd"


def _two_blocks(kept):
    """Two recomputed blocks, hand-scheduled: each makes h (1 MB) and g
    (2 MB); the backward of block 2, then of block 1, reads the block's
    input, and of h and g what `kept` names from the forward: the rest it
    makes again."""
    fwd = ("jit(step)/train_step.loss/jvp(M)/layers.%d/mlp/dot_general")
    again = ("jit(step)/train_step.loss/transpose(jvp(M))/layers.%d/"
             "checkpoint/rematted_computation/mlp/dot_general")
    back = ("jit(step)/train_step.loss/transpose(jvp(M))/layers.%d/"
            "checkpoint/mlp/dot_general")
    lines, x = [], "a"
    for i in (1, 2):
        lines += [f"  %h{i} = f32[262144]{{0}} negate(%{x})" + _meta(fwd % i),
                  f"  %g{i} = f32[524288]{{0}} broadcast(%h{i})"
                  + _meta(fwd % i),
                  f"  %x{i} = f32[262144]{{0}} slice(%g{i})" + _meta(fwd % i)]
        x = f"x{i}"
    d = "x2"
    for i, inp in ((2, "x1"), (1, "a")):
        h, g = f"h{i}", f"g{i}"
        if "h" not in kept:
            h = f"h{i}r"
            lines.append(f"  %{h} = f32[262144]{{0}} negate(%{inp})"
                         + _meta(again % i))
        if "g" not in kept:
            g = f"g{i}r"
            lines.append(f"  %{g} = f32[524288]{{0}} broadcast(%{h})"
                         + _meta(again % i))
        lines.append(f"  %d{i} = f32[262144]{{0}} fusion(%{d}, %{inp}, %{h}, %{g}), "
                     "kind=kLoop, calls=%f" + _meta(back % i))
        d = f"d{i}"
    lines.append(f"  ROOT %new = f32[262144]{{0}} subtract(%a, %{d})"
                 + _meta(UPDATE))
    return _sweep("\n".join(lines) + "\n")


def test_a_kept_value_costs_exactly_its_bytes_a_block():
    """What a block keeps across its replay shows in `residual_bytes`: one
    more kept value, one more array a block.  (On a compiled two-block
    `recompute()` model: tests/test_chip_compile.py.)"""
    none, h, both = _two_blocks(""), _two_blocks("h"), _two_blocks("hg")
    # with nothing kept the forward holds block 2's input and its output
    assert none["residual_bytes"] == 2 * MB
    assert h["residual_bytes"] - none["residual_bytes"] == 2 * (1 * MB)
    assert both["residual_bytes"] - h["residual_bytes"] == 2 * (2 * MB)
    assert both["residual_by_scope"] == {
        "train_step.loss.fwd:M/layers.N/mlp": 8 * MB}
    for got, first in ((none, "replay"), (h, "replay"), (both, "bwd")):
        assert got["backward_at"]["phase"] == first
        assert got["residual_bytes"] <= got["peak_bytes"]
    # the keys, and nothing kept per buffer beyond the bounded fields
    assert set(both) == {
        "peak_bytes", "peak_at", "live_at_peak", "by_scope_at_peak",
        "residual_bytes", "residual_by_scope", "backward_at", "n_buffers",
        "n_containers", "sweep_ms"}
    many = _sweep("".join(f"  %b{i} = f32[1024]{{0}} negate(%a)\n"
                          for i in range(100))
                  + "  ROOT %e = f32[1024]{0} concatenate("
                  + ", ".join(f"%b{i}" for i in range(100)) + ")\n")
    assert many["n_buffers"] == 101 and len(many["live_at_peak"]) == 32


def _scoped_step():
    def step(w, x):
        with jax.named_scope("train_step.loss"):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean(jnp.square(jnp.tanh(x @ w) @ w.T)))(w)
        with jax.named_scope("train_step.update"):
            return loss, w - 0.1 * g

    return jax.jit(step)


# ========================= the ledger's new fields =========================

def test_ledger_entry_keeps_the_compilers_memory_beside_the_sweep():
    metrics.enable()
    inst = xla_cost.instrument(_scoped_step(), "bytes_memory")
    inst(jnp.ones((128, 192)), jnp.ones((64, 128)))
    entry = xla_cost.program_ledger("bytes_memory")
    memory, swept = entry["memory"], entry["bytes"]
    assert {"argument_bytes", "output_bytes", "temp_bytes"} <= set(memory)
    assert memory == {k: v for k, v in
                      xla_cost.last_costs("bytes_memory").items()
                      if k in memory}
    # (held to the compiler's total where the compiler is the chip's:
    # tests/test_chip_compile.py; XLA's CPU backend lets an elementwise
    # result take its operand's buffer, which no text shows)
    assert 0 < swept["peak_bytes"] and swept["peak_at"]["op_name"]
    # the sweep's wall is inside the ledger's
    assert 0 < swept["sweep_ms"] <= entry["compiles"][0]["ledger_ms"]


def test_a_text_the_sweep_cannot_read_costs_no_compile(monkeypatch):
    metrics.enable()

    def boom(text, ops):
        raise ValueError("unbalanced HLO text")

    monkeypatch.setattr(xla_cost, "buffer_sweep", boom)
    inst = xla_cost.instrument(jax.jit(lambda x: x * 3.0), "bytes_unread")
    assert float(inst(jnp.float32(2.0))) == 6.0
    entry = xla_cost.program_ledger("bytes_unread")
    assert entry["bytes"] is None and entry["ops"] and entry["memory"]
    assert any(ev["kind"] == "xla.buffer_sweep_failed"
               for ev in flight.events())


def test_telemetry_off_takes_the_plain_jit_path(monkeypatch):
    before = xla_cost.program_ledger()
    calls = []
    jitted = jax.jit(lambda x: x + 1.0)

    class Spy:
        lower = jitted.lower
        trace = jitted.trace

        def __call__(self, *a, **kw):
            calls.append(a)
            return jitted(*a, **kw)

    inst = xla_cost.instrument(Spy(), "bytes_off")
    monkeypatch.setattr(inst, "aot_compile", lambda *a, **kw: 1 / 0)
    assert float(inst(jnp.float32(1.0))) == 2.0
    assert len(calls) == 1                        # the jitted callable ran
    assert xla_cost.program_ledger() == before    # and the ledger saw nothing
    assert xla_cost.program_ledger("bytes_off") is None


def test_aot_compile_records_without_running():
    """`tools/step_bytes.py`'s way in: shapes, not arrays."""
    metrics.enable()
    inst = xla_cost.instrument(_scoped_step(), "bytes_aot")
    compiled = inst.aot_compile(
        jax.ShapeDtypeStruct((128, 192), jnp.float32),
        jax.ShapeDtypeStruct((64, 128), jnp.float32))
    assert hasattr(compiled, "memory_analysis")
    entry = xla_cost.program_ledger("bytes_aot")
    assert entry["n_compiles"] == 1 and entry["bytes"]["peak_bytes"] > 0


# ============================ the readers ============================

@pytest.fixture
def readers(monkeypatch):
    monkeypatch.syspath_prepend(os.path.join(REPO, "benchmark"))
    from harness import common

    return lambda name: common.load_module("readers", name)


def _run(**over):
    return {"cell": {"chips": 1}, "peaks": {"hbm_bytes": 16e9},
            "state": {"memory_peak_bytes": 14e9}, **over}


def test_program_bytes_reader_reads_three_fields_and_logs_once(
        readers, monkeypatch, capsys):
    reader = readers("program_bytes")
    swept = {"peak_bytes": int(7.2e9), "residual_bytes": int(4e9),
             "peak_at": {"instruction": "x", "phase": "replay"},
             "backward_at": None, "by_scope_at_peak": {"a": int(5e9)},
             "residual_by_scope": {"a": int(4e9)},
             "live_at_peak": [[int(5e9), "x", "op", "fwd"]],
             "n_buffers": 3, "n_containers": 0, "sweep_ms": 1.0}
    entry = {"memory": {"temp_bytes": int(8e9), "argument_bytes": int(6e9)},
             "bytes": swept, "ledger_ms": 321.0}
    monkeypatch.setattr(reader.scope_ms, "program_ledger", lambda p: entry)
    run = _run()
    assert reader.read(run, {"field": "temp_bytes"}) == pytest.approx(50.0)
    assert reader.read(run, {"field": "residual_bytes"}) == pytest.approx(25.0)
    assert reader.read(run, {"field": "sweep_gap"}) == pytest.approx(10.0)
    out = capsys.readouterr()
    assert (out.out + out.err).count("bytes {") == 1
    with pytest.raises(ValueError):
        reader.read(run, {"field": "no_such"})
    # no sweep: the compiler's total still reads, the sweep's fields do not
    entry["bytes"] = None
    assert reader.read(_run(), {"field": "temp_bytes"}) == pytest.approx(50.0)
    assert reader.read(_run(), {"field": "sweep_gap"}) is None


@pytest.mark.parametrize("entry", [None, {"ops": {}}, {"memory": None}],
                         ids=["no_ledger", "parents_entry", "no_analysis"])
def test_program_bytes_reader_returns_none_where_nothing_is_kept(
        readers, monkeypatch, entry):
    reader = readers("program_bytes")
    monkeypatch.setattr(reader.scope_ms, "program_ledger", lambda p: entry)
    for field in ("temp_bytes", "residual_bytes", "sweep_gap"):
        assert reader.read(_run(), {"field": field}) is None


def test_compile_cache_reader_reads_the_share_before_the_window(
        readers, monkeypatch):
    reader = readers("compile_cache")
    run = _run(reduced={"window": (5_000_000_000, 0), "perf_offset_ns": 0},
               e2e={"setup_s": (42.0, "s")})
    asked = []

    def totals(until=None):
        asked.append(until)
        return {"cache_requests": 8, "cache_hits": 6, "cache_writes": 1,
                "cache_retrieval_ms": 10.0, "cache_saved_ms": 99.0,
                "compile_n": 8, "compile_ms": 12.0}

    monkeypatch.setattr(xla_cost, "process_compile_totals", totals)
    assert reader.read(run, {}) == pytest.approx(75.0)
    assert asked == [5.0]
    # nothing requested (no cache directory), or a program that counts no
    # such thing: no reading, and nothing raises
    monkeypatch.setattr(xla_cost, "process_compile_totals",
                        lambda until=None: {**totals(), "cache_requests": 0})
    assert reader.read(run, {}) is None
    monkeypatch.setattr(xla_cost, "process_compile_totals",
                        lambda until=None: {"compile_n": 3, "compile_ms": 1.0})
    assert reader.read(run, {}) is None
