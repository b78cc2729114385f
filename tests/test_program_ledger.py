"""The train step names its own device time (ISSUE 26): scopes in the
program (`train_step.*`, module paths from `Layer.__call__`, `head_ce`,
`flash.layout`, a `name=` on every pallas_call), the compile-time program
ledger of `observability.xla_cost` that keeps `{HLO instruction: op_name}`
per labelled program, process-wide compile totals, and host spans on the
profiler's clock.
"""
from __future__ import annotations

import ast
import gc
import os
import re
import time
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu import nn
from paddle_tpu.core import flags
from paddle_tpu.distributed import fleet, topology
from paddle_tpu.observability import flight, lifecycle, metrics, trace, \
    xla_cost

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_METRICS = os.path.join(REPO, "benchmark", "metrics")


def _reset_telemetry():
    trace.clear()
    trace.disable()
    metrics.reset()
    metrics.disable()
    flight.clear()


@pytest.fixture(autouse=True)
def _clean():
    _reset_telemetry()
    topology.reset_topology()
    yield
    _reset_telemetry()
    topology.reset_topology()


def _metric_regex(name):
    """The regex the benchmark's metric file matches op_names with."""
    import json

    with open(os.path.join(BENCH_METRICS, name + ".json")) as f:
        spec = json.load(f)
    return re.compile(spec.get("scope") or spec["scoped"])


# ============================ the ledger ============================

def _scoped_step():
    """A small step with the scopes the real one has: a differentiated
    loss over a module scope and `head_ce`, then an update."""
    def loss_of(w, x):
        with jax.named_scope("Net"):
            with jax.named_scope("h.0"):
                with jax.named_scope("mlp"):
                    h = jnp.tanh(x @ w)
        with jax.named_scope("head_ce"):
            return jnp.mean(jnp.square(h @ w.T))

    def step(w, x):
        with jax.named_scope("train_step.loss"):
            loss, g = jax.value_and_grad(loss_of)(w, x)
        with jax.named_scope("train_step.update"):
            w = w - 0.1 * g
        return loss, w

    return jax.jit(step)


def test_ledger_op_table_classifies_forward_backward_update():
    metrics.enable()
    inst = xla_cost.instrument(_scoped_step(), "ledger_fbu")
    w, x = jnp.ones((16, 16)), jnp.ones((4, 16))
    inst(w, x)
    entry = xla_cost.program_ledger("ledger_fbu")
    ops = entry["ops"]
    assert ops and entry["module"] == "jit_step"
    fwd, bwd, upd = (_metric_regex(m) for m in
                     ("fwd_ms.train", "bwd_ms.train", "update_ms.train"))
    classes = {"fwd": [o for o in ops.values() if fwd.search(o)],
               "bwd": [o for o in ops.values() if bwd.search(o)],
               "upd": [o for o in ops.values() if upd.search(o)]}
    assert all(classes.values()), classes
    # the three classes are disjoint and every scoped op is in one
    scoped = _metric_regex("unscoped_share.train")
    for o in ops.values():
        n = sum(bool(rx.search(o)) for rx in (fwd, bwd, upd))
        assert n == (1 if scoped.search(o) else 0), o
    assert any("transpose(" in o for o in classes["bwd"])
    assert not any("transpose(" in o for o in classes["fwd"])
    # module and head_ce scopes reach the table (XLA may fuse a small op
    # into a neighbour and keep one of the two names)
    under_loss = classes["fwd"] + classes["bwd"]
    mlp, head = _metric_regex("mlp_ms.train"), _metric_regex("head_ce_ms.train")
    assert any(mlp.search(o) or head.search(o) for o in under_loss)
    # instruction names are the device trace's: unique, no leading %
    assert not any(n.startswith("%") for n in ops)


def test_ledger_stage_times_and_recompile_count():
    metrics.enable()
    inst = xla_cost.instrument(_scoped_step(), "ledger_stages")
    inst(jnp.ones((16, 16)), jnp.ones((4, 16)))
    e1 = xla_cost.program_ledger("ledger_stages")
    assert e1["n_compiles"] == 1
    for k in ("trace_ms", "lower_ms", "compile_ms", "ledger_ms"):
        assert e1[k] > 0, k
    assert e1["ledger_ms"] < 1000
    inst(jnp.ones((16, 16)), jnp.ones((4, 16)))       # a replay
    assert xla_cost.program_ledger("ledger_stages")["n_compiles"] == 1
    inst(jnp.ones((16, 16)), jnp.ones((8, 16)))       # a second signature
    e2 = xla_cost.program_ledger("ledger_stages")
    assert e2["n_compiles"] == 2 and len(e2["compiles"]) == 2
    assert e2["compile_ms"] == pytest.approx(
        sum(c["compile_ms"] for c in e2["compiles"]))
    assert e2["compiles"][0]["at"] <= e2["compiles"][1]["at"] \
        <= time.perf_counter()
    # last_costs stays as it was, with the finer split beside it
    costs = xla_cost.last_costs("ledger_stages")
    assert {"trace_ms", "lower_ms", "compile_ms", "flops"} <= set(costs)
    # the lifecycle ledger takes the three stages; compile_ms{program}
    # keeps meaning the whole wall
    rec = lifecycle.get_ledger().record()["compiles"]["ledger_stages"]
    assert rec["count"] >= 2 and rec["trace_ms"] > 0
    whole = rec["trace_ms"] + rec["lower_ms"] + rec["compile_ms"]
    assert metrics.snapshot()["gauges"][
        "lifecycle.compile_ms{program=ledger_stages}"] == pytest.approx(whole)
    assert xla_cost.program_ledger("never_compiled") is None
    assert "ledger_stages" in xla_cost.program_ledger()


def test_ledger_without_hlo_text_says_none_and_does_not_raise(monkeypatch):
    metrics.enable()
    real = jax.stages.Compiled.as_text

    def boom(self, *a, **kw):
        raise RuntimeError("no HLO from this backend")

    monkeypatch.setattr(jax.stages.Compiled, "as_text", boom)
    inst = xla_cost.instrument(jax.jit(lambda x: x * 3.0), "ledger_notext")
    assert float(inst(jnp.float32(2.0))) == 6.0
    e = xla_cost.program_ledger("ledger_notext")
    assert e["ops"] is None and e["module"] is None and e["n_compiles"] == 1
    assert e["compiles"][0]["n_ops"] is None
    assert any(ev["kind"] == "xla.op_table_failed" for ev in flight.events())
    monkeypatch.setattr(jax.stages.Compiled, "as_text", real)


def test_ledger_keeps_no_compiled_alive(monkeypatch):
    metrics.enable()
    seen = []
    real = xla_cost.capture

    def spy(compiled, label="jit"):
        seen.append(weakref.ref(compiled))
        return real(compiled, label)

    monkeypatch.setattr(xla_cost, "capture", spy)
    inst = xla_cost.instrument(jax.jit(lambda x: jnp.sin(x) + 1.0),
                               "ledger_weak")
    inst(jnp.ones((8,)))
    assert len(seen) == 1 and seen[0]() is not None   # the wrapper holds it
    del inst
    gc.collect()
    assert seen[0]() is None                           # and nothing else did
    assert xla_cost.program_ledger("ledger_weak")["ops"]   # the table stays


def test_op_table_parses_fusions_while_bodies_and_joined_names():
    text = '''HloModule jit_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %inner.1 = f32[8]{0} add(%p, %p), metadata={op_name="jit(step)/inner"}
}

%body.2 (c: (s32[], f32[8])) -> (s32[], f32[8]) {
  %c = (s32[], f32[8]{0}) parameter(0)
  %dot.7 = f32[8]{0} dot(%c, %c), metadata={op_name="jit(step)/train_step.loss/jvp(M)/head_ce/while/body/dot_general" source_file="a.py"}
  ROOT %tuple.3 = (s32[], f32[8]{0}) tuple(%c, %dot.7)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0), metadata={op_name="a"}
  %fusion.12 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/train_step.update/sub;jit(step)/train_step.update/mul"}
  %while.4 = (s32[], f32[8]{0}) while(%fusion.12), condition=%cond.1, body=%body.2
  %gte.6 = f32[8]{0:T(1024)S(1)} get-tuple-element(%while.4), index=1
  %bitcast.8 = f32[2,4]{1,0:T(8,128)(2,1)} bitcast(%gte.6)
  %copy.5 = (f32[2,4]{0,1}, u32[]{:S(2)}) copy-start(%bitcast.8)
  %constant.1 = s32[] constant(0)
  ROOT %copy.9 = s32[] copy(%constant.1)
}
'''
    ops, module = xla_cost.op_table(text)
    assert module == "jit_step"
    update = "jit(step)/train_step.update/sub"
    assert ops["fusion.12"] == update                    # first of `;`
    assert ops["dot.7"].endswith("head_ce/while/body/dot_general")
    # no metadata: the compiler's own instruction takes the op_name of the
    # value it moves, through the chain copy <- bitcast <- gte <- while <- fusion
    assert ops["while.4"] == ops["gte.6"] == ops["bitcast.8"] == \
        ops["copy.5"] == update
    assert ops["copy.9"] == "" == ops["constant.1"]      # the chain ends at none
    assert ops["tuple.3"] == ""
    assert "inner.1" not in ops and "p" not in ops       # a fusion's inside
    assert ops["a"] == "a"


def test_process_compile_totals_count_every_program_once():
    metrics.enable()                       # registers the listener
    def f(x):
        for _ in range(20):                # nested jit traces (jnp.where)
            x = jnp.where(x > 0, jnp.sin(x), x)
        return x

    x = jnp.ones((5,))
    time.sleep(0.15)                       # `until` answers to within 0.1 s
    before = xla_cost.process_compile_totals()
    t0 = time.perf_counter()
    jax.jit(f).lower(x).compile()
    wall_ms = (time.perf_counter() - t0) * 1e3
    after = xla_cost.process_compile_totals()
    d = {k: after[k] - before[k] for k in after}
    assert d["compile_n"] >= 1 and d["lower_n"] >= 1 and d["trace_n"] >= 20
    # nested traces are counted once: the stages fit inside the wall
    assert 0 < d["trace_ms"] + d["lower_ms"] + d["compile_ms"] <= wall_ms * 1.05
    # the totals as they stood before this program
    then = xla_cost.process_compile_totals(until=t0)
    assert then["compile_n"] <= before["compile_n"]
    assert xla_cost.process_compile_totals(until=0.0)["compile_n"] == 0


@pytest.fixture
def cache_dir(tmp_path):
    """JAX's persistent compilation cache at a directory of this test's
    own, every compile worth storing; the session's settings back after."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (str(tmp_path), True, 0.0, -1)):
        jax.config.update(k, v)
    cc.reset_cache()
    yield tmp_path
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_ledger_says_whether_a_compile_was_a_compile(cache_dir):
    """The same program twice against an empty cache directory: compiled
    and stored, then loaded — on the compile's own record and in the
    process totals, on the timeline `until=` reads."""
    metrics.enable()
    args = (jnp.ones((24, 24)), jnp.ones((6, 24)))    # their own programs
    time.sleep(0.15)                       # `until` answers to within 0.1 s
    before = xla_cost.process_compile_totals()
    t0 = time.perf_counter()
    for _ in range(2):                     # a new wrapper: nothing in memory
        xla_cost.instrument(_scoped_step(), "ledger_cache")(*args)
    entry = xla_cost.program_ledger("ledger_cache")
    assert [c["cache"] for c in entry["compiles"]] == ["miss", "hit"]
    assert os.listdir(cache_dir)           # the miss was written
    after = xla_cost.process_compile_totals()
    d = {k: after[k] - before[k] for k in after}
    assert d["cache_requests"] == 2 and d["cache_hits"] == 1
    assert d["cache_writes"] == 1
    assert d["cache_retrieval_ms"] > 0 and "cache_saved_ms" in d
    then = xla_cost.process_compile_totals(until=t0)
    assert then["cache_requests"] <= before["cache_requests"]
    assert then["cache_hits"] <= before["cache_hits"]


def test_ledger_cache_is_none_where_no_cache_was_asked():
    from jax.experimental.compilation_cache import compilation_cache as cc

    metrics.enable()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        xla_cost.instrument(_scoped_step(), "ledger_nocache")(
            jnp.ones((16, 16)), jnp.ones((4, 16)))
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()
    entry = xla_cost.program_ledger("ledger_nocache")
    assert entry["compiles"][0]["cache"] is None


def test_ledger_memory_is_the_latest_compiles():
    metrics.enable()
    inst = xla_cost.instrument(_scoped_step(), "ledger_memory")
    inst(jnp.ones((16, 16)), jnp.ones((4, 16)))
    small = xla_cost.program_ledger("ledger_memory")["memory"]
    assert set(small) <= {"argument_bytes", "output_bytes", "alias_bytes",
                          "temp_bytes", "code_bytes"}
    assert small["argument_bytes"] == (16 * 16 + 4 * 16) * 4
    inst(jnp.ones((16, 16)), jnp.ones((64, 16)))      # a second signature
    entry = xla_cost.program_ledger("ledger_memory")
    assert entry["memory"]["argument_bytes"] == (16 * 16 + 64 * 16) * 4
    assert entry["bytes"]["peak_at"]["instruction"] in entry["ops"]
    # the gauges `capture()` always set say the same
    assert metrics.snapshot()["gauges"][
        "xla.cost.temp_bytes{label=ledger_memory}"] == \
        entry["memory"]["temp_bytes"]


# ====================== scopes in the program ======================

class _Block(nn.Layer):
    def __init__(self):
        super().__init__()
        self.attn = nn.Linear(8, 8)
        self.mlp = nn.Linear(8, 8)

    def forward(self, x):
        return self.mlp(self.attn(x))


class _TwoLayer(nn.Layer):
    def __init__(self):
        super().__init__()
        self.h = nn.LayerList([_Block(), _Block()])
        self.ln_f = nn.LayerNorm(8)

    def forward(self, x):
        for block in self.h:
            x = block(x)
        return self.ln_f(x)


def test_layer_call_enters_module_scopes_only_in_trace():
    model = _TwoLayer()
    params, buffers = model.functional_state()

    def run(params, x):
        with flags.trace_guard(), model.bind_state(params, buffers):
            return model(P.Tensor(x))._value

    text = jax.jit(run).lower(params, jnp.ones((2, 8))).as_text(
        debug_info=True)
    for path in ("_TwoLayer/h.0/attn", "_TwoLayer/h.0/mlp",
                 "_TwoLayer/h.1/attn", "_TwoLayer/h.1/mlp",
                 "_TwoLayer/ln_f"):
        assert path in text, path
    # a LayerList is iterated, never called: no component of its own
    assert "_TwoLayer/h/" not in text

    # eager mode: the same call enters no scope
    def run_eager(params, x):
        with model.bind_state(params, buffers):
            return model(P.Tensor(x))._value

    model.eval()
    from jax._src import source_info_util

    seen = []
    orig = _Block.forward

    def spy(self, x):
        seen.append(str(source_info_util.current_name_stack()))
        return orig(self, x)

    _Block.forward = spy
    try:
        run_eager(params, jnp.ones((2, 8)))
    finally:
        _Block.forward = orig
    assert seen and all("h." not in s for s in seen), seen


def _gpt_step(dp, **kw):
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    s = fleet.DistributedStrategy()
    s.hybrid_configs = {"dp_degree": dp, "mp_degree": 1, "pp_degree": 1,
                        "sep_degree": 1, "sharding_degree": dp}
    fleet.init(is_collective=True, strategy=s)
    P.seed(0)
    inner = GPTForCausalLM(GPTConfig(vocab_size=256, hidden_size=32,
                                     num_layers=2, num_heads=4,
                                     max_seq_len=32, fused_head_ce=True))
    m = fleet.distributed_model(inner)
    o = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=m.parameters(), learning_rate=1e-3))
    return m.build_train_step(o, GPTPretrainingCriterion(model=inner), **kw)


def _ids(batch=8):
    rs = np.random.RandomState(0)
    return P.to_tensor(rs.randint(0, 256, (batch, 32)), "int32")


def test_train_step_lowered_text_carries_every_stage_scope():
    """All six `train_step.*` stages, on the virtual CPU mesh: dp=2 ZeRO-1
    (gather, grad_sync), clipping, the guard."""
    step = _gpt_step(dp=2, grad_clip_norm=1.0, guard=True)
    text = step.lower(_ids(), _ids()).as_text(debug_info=True)
    for stage in ("gather", "loss", "clip", "grad_sync", "update", "guard"):
        assert f"train_step.{stage}" in text, stage
    # the model's module paths and the fused head + CE scope sit under loss
    assert re.search(r"train_step\.loss/[^\"]*gpt/h\.1/attn", text)
    assert re.search(r"train_step\.loss/[^\"]*gpt/h\.0/mlp", text)
    assert re.search(r"train_step\.loss/[^\"]*head_ce", text)


def test_train_step_one_device_scopes_and_ledger_join():
    """One device: only the stages that apply; the ledger of the step that
    RAN classifies its instructions with the benchmark's regexes."""
    metrics.enable()
    step = _gpt_step(dp=1)
    text = step.lower(_ids(), _ids()).as_text(debug_info=True)
    assert "train_step.loss" in text and "train_step.update" in text
    for stage in ("gather", "clip", "grad_sync", "guard"):
        assert f"train_step.{stage}" not in text, stage
    assert np.isfinite(float(step(_ids(), _ids())))
    ops = xla_cost.program_ledger("train_step")["ops"]
    named = [o for o in ops.values() if o]
    scoped = _metric_regex("unscoped_share.train")
    assert sum(bool(scoped.search(o)) for o in named) > 0.5 * len(named)
    for metric in ("fwd_ms.train", "bwd_ms.train", "update_ms.train",
                   "attn_ms.train", "mlp_ms.train", "head_ce_ms.train"):
        rx = _metric_regex(metric)
        assert any(rx.search(o) for o in named), metric


def test_unfused_head_and_ce_scopes():
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    P.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=16,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=16))
    crit = GPTPretrainingCriterion()
    params, buffers = model.functional_state()

    def run(params, ids):
        with flags.trace_guard(), model.bind_state(params, buffers):
            return crit(model(P.Tensor(ids)), P.Tensor(ids))._value

    text = jax.jit(run).lower(
        params, jnp.zeros((2, 16), jnp.int32)).as_text(debug_info=True)
    assert "GPTForCausalLM/head/" in text
    assert "GPTPretrainingCriterion/ce/" in text


# ====================== a name on every kernel ======================

PALLAS_DIR = os.path.join(REPO, "paddle_tpu", "ops", "pallas")
PALLAS_FILES = ["flash_attention.py", "paged_attention.py",
                "decode_attention.py", "varlen_attention.py",
                "window_attention.py", "fused_norm.py", "rope.py",
                "conv_norm.py"]


@pytest.mark.parametrize("fname", PALLAS_FILES)
def test_every_pallas_call_is_named_and_names_are_distinct(fname):
    with open(os.path.join(PALLAS_DIR, fname)) as f:
        tree = ast.parse(f.read())
    sites, literals = 0, []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                isinstance(node.func, ast.Attribute) and \
                node.func.attr == "pallas_call":
            sites += 1
            kw = [k for k in node.keywords if k.arg == "name"]
            assert kw, f"{fname}:{node.lineno}: pallas_call( without name="
            literals += [(c.value, node.lineno) for c in ast.walk(kw[0].value)
                         if isinstance(c, ast.Constant)
                         and isinstance(c.value, str)]
    assert sites, fname
    names = [n for n, _ in literals]
    assert len(names) == sites and len(set(names)) == sites, literals
    stem = fname[:-3]
    prefix = "flash_" if stem == "flash_attention" else stem + "_"
    assert all(n.startswith(prefix) for n in names), names


def test_flash_kernel_names_mark_the_differentiated_calls():
    """XLA names a Mosaic custom call after the innermost scope of its
    op_name, which pallas_call makes the kernel's name: the forward under
    differentiation and the backward kernels write JAX's transform marks
    there, so a device trace's event says which direction it is."""
    from paddle_tpu.ops.pallas import flash_attention as fa

    assert fa._fwd_name("flash_flat_fwd", diff=False) == "flash_flat_fwd"
    assert fa._fwd_name("flash_flat_fwd", diff=True) == "jvp(flash_flat_fwd)"
    assert fa._bwd_name("flash_flat_bwd") == "transpose(jvp(flash_flat_bwd))"

    def loss(q, k, v):
        with jax.named_scope("attn"):
            return fa._flash_core(q, k, v, True, 8, 8).sum()

    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, q, q).as_text(debug_info=True)
    for name in ("jvp(flash_transpose_fwd)",
                 "transpose(jvp(flash_transpose_bwd))", "flash.layout"):
        assert name in text, name
    assert "flash_transpose_dq" not in text   # one backward kernel, not two
    fwd = jax.jit(lambda q: fa._flash_core(q, q, q, True, 8, 8)).lower(
        q).as_text(debug_info=True)
    assert "flash_transpose_fwd" in fwd and "jvp(flash" not in fwd


# ================= host spans on the profiler's clock =================

def test_spans_mirror_into_profiler_annotations(monkeypatch):
    opened, closed = [], []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            opened.append(self.name)

        def __exit__(self, *exc):
            closed.append(self.name)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    with trace.span("off"):                      # tracer off: no mirror
        pass
    assert opened == []
    trace.enable()
    with trace.span("outer"):
        sp = trace.begin("inner")
        trace.end(sp)
    assert opened == ["outer", "inner"] and closed == ["inner", "outer"]
    # an unbalanced exit closes what was left open inside, innermost first
    a = trace.begin("a")
    trace.begin("b")
    trace.end(a)
    assert closed[-2:] == ["b", "a"]
    # the tracer publishes its perf_counter_ns origin
    tr = trace.get_tracer()
    t = time.perf_counter_ns()
    trace.instant("now")
    ts_us = trace.events()[-1]["ts"]
    assert abs((t - tr.epoch_perf_ns) / 1e3 - ts_us) < 5e3
    assert trace.to_chrome()["otherData"]["epoch_perf_ns"] == tr.epoch_perf_ns


def test_train_step_call_emits_host_spans():
    step = _gpt_step(dp=1, guard=True)
    step(_ids(), _ids())                         # tracer off: no event
    assert not [e for e in trace.events()
                if e["name"].startswith("train_step.")]
    trace.enable()
    step(_ids(), _ids())
    names = [e["name"] for e in trace.events() if e["ph"] == "X"]
    for n in ("train_step.place_batch", "train_step.dispatch",
              "train_step.guard_sync"):
        assert names.count(n) == 1, (n, names)


def test_engine_loop_waiting_for_a_request_is_one_span():
    """The engine's loop, blocked on an empty batch, lies in ONE
    `engine.wait_request` span per idle stretch, closed before the step
    that serves the request (ToyEngine has no loop of its own: this drives
    the real engine's, with no request and so no compile)."""
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    model = GPTForCausalLM(GPTConfig(vocab_size=64, hidden_size=16,
                                     num_layers=1, num_heads=2,
                                     max_seq_len=32))
    model.eval()
    trace.enable()
    eng = InferenceEngine(model, EngineConfig(max_slots=2, max_seq_len=32,
                                              page_size=8, num_pages=16))
    eng.start()
    time.sleep(0.25)                             # several 50 ms waits
    eng.stop()
    waits = [e for e in trace.events()
             if e["ph"] == "X" and e["name"] == "engine.wait_request"]
    assert len(waits) == 1, waits
    assert waits[0]["cat"] == "engine" and waits[0]["dur"] >= 0.15e6


def test_telemetry_off_is_the_plain_jit_path():
    """With telemetry off `InstrumentedJit.__call__` forwards and the
    ledger learns nothing (the other half is test_trace.py's)."""
    inst = xla_cost.instrument(jax.jit(lambda x: x + 1), "ledger_off")
    assert float(inst(jnp.float32(1.0))) == 2.0
    assert xla_cost.program_ledger("ledger_off") is None
    assert inst._compiled == {}
