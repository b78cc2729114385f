"""XLA compile-time cost/memory annotation for the trace timeline.

Every jit compile the framework performs can carry the compiler's OWN
accounting — `Compiled.cost_analysis()` (FLOPs, bytes accessed,
transcendentals) and `Compiled.memory_analysis()` (argument/output/temp
buffer bytes, generated code size) — instead of only the host-side wall
the compile ledger records.  Three consumers per capture:

  * the trace timeline: the `xla.compile:<label>` span that wrapped the
    compile gets the cost dict attached as span args, so clicking a
    compile slice in Perfetto shows what the compiler thought it built;
  * the metrics registry: `xla.cost.*{label=...}` gauges (latest compile
    per label wins — the steady-state executable);
  * the flight recorder: an `xla.compile` event, so a crash dump shows
    the last programs built before the incident;
  * the lifecycle ledger: compile WALL time in three stages (trace,
    lower, compile), recorded per program label for replica cold-start
    attribution (`lifecycle.compile_ms{program}`) plus an
    `xla.cost.compile_ms{label}` gauge;
  * the PROGRAM LEDGER (`program_ledger`): per label the three stage
    walls, the compile count, and the op table `{HLO instruction name ->
    op_name}` of the optimized program — the one place where a device
    trace's event names (XLA's: `fusion.12`) can be joined to the scopes
    the program put around its work (`train_step.update`, `h.3/attn`).
    The `Compiled` itself is never retained.  `process_compile_totals`
    sums JAX's own trace/lower/compile durations over EVERY program of
    the process (a `jax.monitoring` listener, registered when telemetry
    is first seen on), so totals minus the labelled programs is what
    the unlabelled small programs cost.

`instrument(jitted, label)` wraps a `jax.jit` callable with capture-on-
first-call-per-signature semantics.  When the telemetry stack is off
(neither metrics nor trace enabled) — or when the call is happening
under an outer jax trace (autograd through the dispatch gate hands the
wrapped program Tracers) — the wrapper forwards straight to the jitted
callable: byte-identical behavior to an uninstrumented jit.  When on,
the first call for a new aval signature lowers + AOT-compiles (the same
work `jitted(...)` would do on that call), captures the analysis, and
replays the compiled executable on subsequent calls; any failure in the
AOT path falls back to the plain jitted call.

jax is imported lazily: this module loads during
``paddle_tpu.observability`` import, which must stay stdlib-cheap.
"""
from __future__ import annotations

import collections
import re
import threading
import time

from . import flight as _flight
from . import metrics as _metrics
from . import trace as _trace

__all__ = ["analyze_compiled", "capture", "instrument", "last_costs",
           "program_ledger", "process_compile_totals", "op_table",
           "watch_process_compiles", "InstrumentedJit"]

# cost_analysis keys -> snapshot keys (values are floats)
_COST_KEYS = (("flops", "flops"),
              ("bytes accessed", "bytes_accessed"),
              ("transcendentals", "transcendentals"))
# memory_analysis attrs -> snapshot keys (values are ints)
_MEM_KEYS = (("argument_size_in_bytes", "argument_bytes"),
             ("output_size_in_bytes", "output_bytes"),
             ("temp_size_in_bytes", "temp_bytes"),
             ("alias_size_in_bytes", "alias_bytes"),
             ("generated_code_size_in_bytes", "code_bytes"))
# the subset worth a registry gauge per label
_GAUGE_KEYS = ("flops", "bytes_accessed", "temp_bytes", "argument_bytes",
               "output_bytes")

_last: dict = {}
_last_lock = threading.Lock()


def analyze_compiled(compiled, label: str = "jit") -> dict:
    """Cost/memory dict from a `jax.stages.Compiled` (best-effort: every
    backend/version quirk degrades to fewer keys, never an exception)."""
    out = {"label": str(label)}
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # one entry per device program
            ca = ca[0] if ca else {}
        if ca:
            for src, dst in _COST_KEYS:
                if src in ca:
                    out[dst] = float(ca[src])
    except Exception as e:
        # degrade to fewer keys, but visibly: a backend whose
        # cost_analysis() suddenly stops answering is a signal (it was
        # the whole r5 MFU-forensics channel), not routine
        _flight.record("xla.cost_analysis_failed", label=str(label),
                       error=type(e).__name__)
    try:
        ma = compiled.memory_analysis()
        for attr, dst in _MEM_KEYS:
            v = getattr(ma, attr, None)
            if v is not None:
                out[dst] = int(v)
    except Exception as e:
        _flight.record("xla.memory_analysis_failed", label=str(label),
                       error=type(e).__name__)
    return out


def capture(compiled, label: str = "jit") -> dict:
    """Analyze `compiled` and fan the result out to gauges + flight (and
    remember it per label for `last_costs`).  Returns the cost dict so
    the caller can also attach it to the surrounding compile span."""
    costs = analyze_compiled(compiled, label)
    for k in _GAUGE_KEYS:
        if k in costs:
            _metrics.set_gauge(f"xla.cost.{k}", costs[k], label=label)
    _flight.record("xla.compile", **costs)
    with _last_lock:
        _last[str(label)] = dict(costs)
    return costs


def last_costs(label=None):
    """Most recent capture for `label`, or the whole {label: costs} map."""
    with _last_lock:
        if label is not None:
            return _last.get(str(label))
        return dict(_last)


# ----------------------------- program ledger -----------------------------

# per label: the stage walls summed over its compiles, each compile's own
# record (a second signature under one label is a recompile), and the op
# table + HLO module name of the LATEST compile (the steady-state
# executable, as `last_costs`)
_ledger: dict = {}
_LEDGER_COMPILES_KEPT = 64
_STAGES = ("trace_ms", "lower_ms", "compile_ms", "ledger_ms")

# `  ROOT %fusion.12 = f32[8]{0} fusion(...), ..., metadata={op_name="a/b" ...}`
_HLO_INSTR = re.compile(r"^\s+(?:ROOT )?%?([\w.\-]+) = (.*)$")
_HLO_OP_NAME = re.compile(r'op_name="([^"]*)"')
# `copy(%bitcast.37), ...` after the result type: the first operand
_HLO_FIRST_OPERAND = re.compile(r"(?:^|[ )])[a-z][\w\-]*\(%?([\w.\-]+)")
_HLO_FUSED = re.compile(r" fusion\(.*calls=%?([\w.\-]+)")
# `%fused_computation.3 (p: f32[8]) -> f32[8] {` / `ENTRY %main.5 (...) -> ... {`
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$")
_HLO_MODULE = re.compile(r"^HloModule ([\w.\-]+)")


def op_table(hlo_text: str):
    """(`{instruction name: op_name}`, HLO module name) of an optimized
    HLO text: every instruction of every NON-fused computation (entry,
    `while` bodies, called computations); a fusion is one instruction and
    carries its root's op_name.  Where XLA joined several op_names with
    `;` the first is kept.  An instruction without metadata — the
    compiler's own copy, bitcast or async pair — takes the op_name of the
    value it moves: its first operand's, followed to the first instruction
    that has one, and "" where that chain ends at none.  Instruction names
    are the names a device trace's events carry."""
    lines = hlo_text.splitlines()
    fused = set()
    for line in lines:
        if " fusion(" in line:
            m = _HLO_FUSED.search(line)
            if m:
                fused.add(m.group(1))
    module = _HLO_MODULE.match(lines[0]) if lines else None
    ops, moved_from, skip = {}, {}, False
    for line in lines:
        if not line.startswith(" "):
            m = _HLO_COMPUTATION.match(line)
            if m:
                skip = m.group(1) in fused
            continue
        if skip:
            continue
        m = _HLO_INSTR.match(line)
        if m:
            op = _HLO_OP_NAME.search(m.group(2))
            ops[m.group(1)] = op.group(1).split(";", 1)[0] if op else ""
            if not op:
                src = _HLO_FIRST_OPERAND.search(m.group(2))
                if src:
                    moved_from[m.group(1)] = src.group(1)
    for name in moved_from:
        src = name
        for _ in range(16):            # copy <- bitcast <- get-tuple-element ...
            src = moved_from.get(src)
            if src is None or ops.get(src):
                break
        ops[name] = ops.get(src, "") if src else ""
    return ops, (module.group(1) if module else None)


def _record_program(label, compiled, trace_ms, lower_ms, compile_ms) -> dict:
    """One compile into the ledger.  Reads `compiled.as_text()` once and
    lets it go: neither the text nor the `Compiled` is kept."""
    t0 = time.perf_counter()
    ops = module = None
    try:
        ops, module = op_table(compiled.as_text())
    except Exception as e:
        # a backend or a cache load that gives no HLO: the ledger says
        # so (`ops: None`), loudly downstream, and nothing raises
        _flight.record("xla.op_table_failed", label=str(label),
                       error=type(e).__name__)
    rec = {"trace_ms": trace_ms, "lower_ms": lower_ms,
           "compile_ms": compile_ms,
           "ledger_ms": (time.perf_counter() - t0) * 1e3,
           "n_ops": len(ops) if ops is not None else None,
           "at": time.perf_counter()}
    with _last_lock:
        e = _ledger.setdefault(str(label), dict.fromkeys(_STAGES, 0.0) | {
            "n_compiles": 0, "compiles": []})
        for k in _STAGES:
            e[k] += rec[k]
        e["n_compiles"] += 1
        if len(e["compiles"]) < _LEDGER_COMPILES_KEPT:
            e["compiles"].append(rec)
        e["ops"], e["module"] = ops, module
    return rec


def program_ledger(label=None):
    """`{label: {"trace_ms", "lower_ms", "compile_ms", "ledger_ms" (each
    summed over the label's compiles), "n_compiles", "compiles" (one
    record per compile, with `at`, the `time.perf_counter()` at its
    end), "ops": {instruction name: op_name} | None,
    "module": HLO module name | None}}`, or one label's entry (None when
    it never compiled under telemetry).  Process-wide; outlives the
    wrapper and its executables.  The op table is shared, not copied:
    read it, do not write it."""
    with _last_lock:
        if label is not None:
            e = _ledger.get(str(label))
            return dict(e) if e is not None else None
        return {k: dict(v) for k, v in _ledger.items()}


# JAX's own stage durations, for EVERY program of the process
_JAX_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_totals = {f"{s}_{k}": 0 for s in _JAX_STAGE_EVENTS.values()
           for k in ("ms", "n")}
# (perf_counter of a 0.1 s bucket's first event, totals at the bucket's
# last): what `process_compile_totals(until=...)` answers from
_totals_timeline: collections.deque = collections.deque(maxlen=8192)
_TIMELINE_BUCKET_S = 0.1
_watching = False
_stage_events = threading.local()   # .stack: (start_s, duration_s), newest last


def _on_jax_duration(event, duration, **_kw):
    stage = _JAX_STAGE_EVENTS.get(event)
    if stage is None:
        return
    # stages nest — every jnp function is a jit whose trace reports before
    # the outer trace that covers it, a lowering rule traces, an autotune
    # search compiles inside the step's trace — and an inner event reports
    # first: take what this one covers back out, so a second counts once
    stack = _stage_events.__dict__.setdefault(
        "stack", collections.deque(maxlen=256))
    now = time.perf_counter()
    start, own = now - duration, duration
    while stack and stack[-1][0] >= start - 20e-6:
        own -= stack.pop()[1]
    stack.append((start, duration))
    with _last_lock:
        _totals[stage + "_ms"] += max(own, 0.0) * 1e3
        _totals[stage + "_n"] += 1
        if _totals_timeline and \
                now - _totals_timeline[-1][0] < _TIMELINE_BUCKET_S:
            _totals_timeline[-1] = (_totals_timeline[-1][0], dict(_totals))
        else:
            _totals_timeline.append((now, dict(_totals)))


def watch_process_compiles() -> None:
    """Register the `jax.monitoring` listener behind
    `process_compile_totals` (once; `metrics.enable()`/`trace.enable()`
    call this, so the totals start no later than telemetry does)."""
    global _watching
    with _last_lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)


def process_compile_totals(until=None) -> dict:
    """`{"trace_ms", "trace_n", "lower_ms", "lower_n", "compile_ms",
    "compile_n"}`: JAX's own durations and counts of jaxpr tracing,
    lowering to MLIR and backend compilation (a persistent-cache load
    counts as the latter; a stage nested in another counts once, under
    its own name), summed over every program since
    `watch_process_compiles`.  Minus the labelled programs of
    `program_ledger` it is what the unlabelled small programs cost.  `until` (a `time.perf_counter()` value) asks
    for the totals as they stood then, to within 0.1 s — a benchmark's
    set-up, not the reference it compiles afterwards."""
    with _last_lock:
        if until is None:
            return dict(_totals)
        then = dict.fromkeys(_totals, 0)
        for t, totals in _totals_timeline:
            if t > until:
                break
            then = totals
        return dict(then)


def _telemetry_on() -> bool:
    return _metrics.enabled() or _trace.enabled()


def _feed_lifecycle(label, trace_ms, lower_ms, compile_ms) -> None:
    """Attribute a compile to the process lifecycle ledger (replica
    cold-start accounting).  Best-effort: the ledger is observability
    of observability — it must never fail a compile."""
    try:
        from . import lifecycle

        lifecycle.get_ledger().record_compile(label, lower_ms, compile_ms,
                                              trace_ms=trace_ms)
    except Exception:  # pt-lint: ok[PT005]
        pass           # (the compile_ms span args above already carry
        # the measurement; a ledger failure must never sink a compile)


# sentinel marking a signature whose compile is in flight on another
# thread (callers fall back to the jitted path until it resolves)
_PENDING = object()


class InstrumentedJit:
    """Wraps a jax.jit callable; first call per aval signature compiles
    AOT inside an `xla.compile:<label>` span and captures cost_analysis.
    Exposes `.lower()` (delegated) so callers that lower-for-analysis
    (DistributedTrainStep.lower) keep working."""

    def __init__(self, jitted, label: str):
        self._jitted = jitted
        self.label = str(label)
        self._compiled: dict = {}
        self._lock = threading.Lock()
        try:
            self.__name__ = getattr(jitted, "__name__", self.label)
        except (AttributeError, TypeError):
            pass  # some wrappers refuse __name__; the label suffices

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def _sig(self, leaves):
        """Hashable aval signature, or None when any leaf isn't a plain
        array (then capture is skipped — a repr-based key could differ
        every call and turn the AOT cache into a compile-per-call).

        Shardings are deliberately NOT in the key: jit outputs fed back
        as inputs (train-step state) carry GSPMDSharding objects that
        hash differently from the NamedSharding the first call was
        placed with even when semantically equal, which would recompile
        the steady-state executable every step.  A genuinely different
        sharding is still safe — the Compiled call rejects it before
        executing and __call__ falls back to the plain jit path."""
        sig = []
        for l in leaves:
            shape = getattr(l, "shape", None)
            dtype = getattr(l, "dtype", None)
            if shape is None or dtype is None:
                return None
            sig.append((tuple(shape), str(dtype),
                        bool(getattr(l, "weak_type", False))))
        return tuple(sig)

    def __call__(self, *args, **kwargs):
        if not _telemetry_on():
            return self._jitted(*args, **kwargs)
        import jax

        leaves = jax.tree_util.tree_leaves((args, kwargs))
        if any(isinstance(l, jax.core.Tracer) for l in leaves):
            # under an outer trace (autograd through the dispatch gate):
            # Compiled objects refuse tracers; jit composes fine
            return self._jitted(*args, **kwargs)
        key = self._sig(leaves)
        if key is None:
            return self._jitted(*args, **kwargs)
        # deliberate lock-free fast path: dict membership is GIL-atomic
        # and a stale miss only costs re-entering the claim protocol
        if key not in self._compiled:  # pt-lint: ok[PT102]
            # claim the signature under the lock so concurrent first
            # calls never run the multi-second lower+compile twice;
            # losers (and callers racing the winner) take the plain
            # jitted path, whose own cache dedupes the compile
            with self._lock:
                claimed = key not in self._compiled
                if claimed:
                    self._compiled[key] = _PENDING
            if claimed:
                with _trace.span(f"xla.compile:{self.label}",
                                 cat="compile") as sp:
                    try:
                        # three stage walls: jaxpr trace, lowering to
                        # MLIR, backend compile (or a cache load)
                        t0 = time.perf_counter()
                        traced = self._jitted.trace(*args, **kwargs)
                        t1 = time.perf_counter()
                        lowered = traced.lower()
                        t2 = time.perf_counter()
                        compiled = lowered.compile()
                        t3 = time.perf_counter()
                        del traced, lowered
                        costs = capture(compiled, self.label)
                        costs["trace_ms"] = (t1 - t0) * 1e3
                        costs["lower_ms"] = (t2 - t1) * 1e3
                        costs["compile_ms"] = (t3 - t2) * 1e3
                        rec = _record_program(
                            self.label, compiled, costs["trace_ms"],
                            costs["lower_ms"], costs["compile_ms"])
                        costs["ledger_ms"] = rec["ledger_ms"]
                        _metrics.set_gauge("xla.cost.compile_ms",
                                           costs["compile_ms"],
                                           label=self.label)
                        _feed_lifecycle(self.label, costs["trace_ms"],
                                        costs["lower_ms"],
                                        costs["compile_ms"])
                        with _last_lock:
                            _last[self.label] = dict(costs)
                        if sp is not None:
                            sp.args.update(costs)
                    except Exception:
                        compiled = None  # permanent fallback for this sig
                # single-writer by the claim protocol above (only the
                # thread that claimed `key` ever stores to it), and a
                # one-slot dict store is GIL-atomic
                self._compiled[key] = compiled  # pt-lint: ok[PT101,PT102]
        entry = self._compiled[key]  # pt-lint: ok[PT102] (GIL-atomic read)
        if entry is None or entry is _PENDING:
            return self._jitted(*args, **kwargs)
        try:
            return entry(*args, **kwargs)
        except (TypeError, ValueError):
            # aval/sharding drift the signature key didn't see: the
            # Compiled rejects the call before executing, so the plain
            # jitted path (which re-specializes) is still safe to run
            return self._jitted(*args, **kwargs)


def instrument(jitted, label: str = "jit"):
    """Wrap a jax.jit callable for compile-cost capture; returns the
    input unchanged when it has no `.lower` (not an AOT-capable stage)."""
    if not hasattr(jitted, "lower"):
        return jitted
    return InstrumentedJit(jitted, label)
