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
    From the same single read of the program's text the ledger also
    accounts for the step's BYTES: `memory` (the compiler's own totals)
    and `bytes`, a liveness sweep of the scheduled program
    (`buffer_sweep`): the peak of the temporaries in HBM and the
    instruction it lies at, what is live there by scope, and what the
    forward holds for the backward.  The `Compiled` itself is never
    retained, and no per-buffer table outlives the compile.
    `process_compile_totals` sums JAX's own trace/lower/compile
    durations over EVERY program of the process (`jax.monitoring`
    listeners, registered when telemetry is first seen on), so totals
    minus the labelled programs is what the unlabelled small programs
    cost; beside them what the persistent compilation cache did
    (requests, hits, writes), and each labelled compile's record says
    whether it was a compile or a load.

`instrument(jitted, label)` wraps a `jax.jit` callable with capture-on-
first-call-per-signature semantics.  When the telemetry stack is off
(neither metrics nor trace enabled) — or when the call is happening
under an outer jax trace (autograd through the dispatch gate hands the
wrapped program Tracers) — the wrapper forwards straight to the jitted
callable: byte-identical behavior to an uninstrumented jit.  When on,
the first call for a new aval signature lowers + AOT-compiles (the same
work `jitted(...)` would do on that call; `InstrumentedJit.aot_compile`,
which also takes shapes where nothing is to run), captures the analysis,
and replays the compiled executable on subsequent calls; any failure in
the AOT path falls back to the plain jitted call.

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
           "buffer_sweep", "phase_of", "scope_of",
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


def _record_program(label, compiled, trace_ms, lower_ms, compile_ms,
                    costs=None, cache=None) -> dict:
    """One compile into the ledger.  Reads `compiled.as_text()` once and
    lets it go: neither the text nor the `Compiled` is kept.  `costs` is
    what `capture()` read of this compile (its `memory_analysis()` part is
    kept as the entry's `memory`), `cache` what the persistent compilation
    cache did for it (`"hit"`, `"miss"`, None: not asked)."""
    t0 = time.perf_counter()
    ops = module = swept = None
    try:
        text = compiled.as_text()
        ops, module = op_table(text)
    except Exception as e:
        # a backend or a cache load that gives no HLO: the ledger says
        # so (`ops: None`), loudly downstream, and nothing raises
        text = None
        _flight.record("xla.op_table_failed", label=str(label),
                       error=type(e).__name__)
    if text is not None:
        try:
            swept = buffer_sweep(text, ops)
        except Exception as e:
            # a text the sweep cannot read (`bytes: None`) costs no compile
            _flight.record("xla.buffer_sweep_failed", label=str(label),
                           error=type(e).__name__)
        del text
    memory = {k: costs[k] for _, k in _MEM_KEYS if k in (costs or ())}
    rec = {"trace_ms": trace_ms, "lower_ms": lower_ms,
           "compile_ms": compile_ms,
           "ledger_ms": (time.perf_counter() - t0) * 1e3,
           "n_ops": len(ops) if ops is not None else None,
           "cache": cache,
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
        e["memory"], e["bytes"] = memory or None, swept
    return rec


def program_ledger(label=None):
    """`{label: {"trace_ms", "lower_ms", "compile_ms", "ledger_ms" (each
    summed over the label's compiles), "n_compiles", "compiles" (one
    record per compile, with `at`, the `time.perf_counter()` at its
    end, and `cache`: `"hit"` where the persistent compilation cache
    answered it, `"miss"` where it was asked and did not, None where no
    cache was asked), "ops": {instruction name: op_name} | None,
    "module": HLO module name | None, "memory": the compiler's own totals
    of the latest compile (`argument_bytes`, `output_bytes`, `alias_bytes`,
    `temp_bytes`, `code_bytes`) | None, "bytes": `buffer_sweep` of the
    latest compile | None}}`, or one label's entry (None when it never
    compiled under telemetry).  Process-wide; outlives the
    wrapper and its executables.  The op table is shared, not copied:
    read it, do not write it."""
    with _last_lock:
        if label is not None:
            e = _ledger.get(str(label))
            return dict(e) if e is not None else None
        return {k: dict(v) for k, v in _ledger.items()}


# ------------------------------ the step's bytes ------------------------------

def phase_of(op_name: str) -> str:
    """Which pass of the step an op_name belongs to: `replay` (what a
    recomputed block runs again: JAX writes `rematted_computation` into
    it), `bwd` (the true backward: `transpose(` without that mark), `fwd`
    (anything else under `train_step.loss`), `update` (`train_step.update`),
    `other`."""
    if "rematted_computation" in op_name:
        return "replay"
    if "transpose(" in op_name:
        return "bwd"
    if "train_step.loss" in op_name:
        return "fwd"
    return "update" if "train_step.update" in op_name else "other"


def scope_of(op_name: str) -> str:
    """`jit(step)/train_step.loss/transpose(jvp(GPT))/gpt/h.3/attn/dot_general`
    -> `train_step.loss.bwd:GPT/gpt/h.N/attn`: the stage, the direction
    where the stage is differentiated, then up to four components of the
    module path with the layer indices folded and the primitive left off —
    the key the benchmark's `scopes {...}` line sums device time by
    (`benchmark/readers/scope_ms.py::_scope_of`; a test holds the two
    equal), so five layers' kept values read as one row."""
    parts = [p for p in op_name.split("/") if not p.startswith("jit(")]
    stage = parts[0] if parts and parts[0].startswith("train_step.") else "-"
    if "transpose(" in op_name:
        stage += ".bwd"
    elif stage == "train_step.loss":
        stage += ".fwd"
    path = [re.sub(r"\d+", "N", re.sub(r"\w+\(|\)", "", p))
            for p in parts[1 if stage != "-" else 0:-1]]
    path = [p for p in path if p and not p.startswith("train_step.")]
    return stage + ":" + "/".join(path[:4])


_BITS = {"pred": 8, "token": 0, "opaque": 0, "c64": 64, "c128": 128}
_HLO_ARRAY = re.compile(r"^(\w+)\[([^\]]*)\](?:\{([^}]*)\})?$")
_HLO_TILE = re.compile(r"T\(([\d,]+)\)((?:\([\d,]+\))*)")
_HLO_SPACE = re.compile(r"S\((\d+)\)")
_HLO_ELEMENT_BITS = re.compile(r"E\((\d+)\)")
_HLO_OPCODE = re.compile(r"^ ?([\w\-]+)\(")
_HLO_NAME = re.compile(r"%([\w.\-]+)")
_HLO_COMMENT = re.compile(r"/\*.*?\*/")
# `output_to_operand_aliasing={{1}: (0, {}), {}: (2, {0})}`
_HLO_ALIASING = re.compile(r"output_to_operand_aliasing=\{(.*?\)) ?\}")
_HLO_ALIAS_PAIR = re.compile(r"\{([\d, ]*)\}: \((\d+), \{([\d, ]*)\}\)")
_HLO_TUPLE_INDEX = re.compile(r"\bindex=(\d+)")
# instructions that move no bytes: their result IS what they read (as are,
# element by element, `tuple` and `get-tuple-element`)
_HLO_VIEWS = {"bitcast", "add-dependency", "optimization-barrier",
              "opt-barrier"}
_HLO_CONTAINERS = {"while", "call", "conditional"}
# the computations such an instruction runs
_HLO_CALLED = re.compile(
    r"(?:body|to_apply|calls|true_computation|false_computation)"
    r"=%?([\w.\-]+)")
_HLO_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_LIVE_AT_PEAK_KEPT = 32


def _closing(text, start=0):
    """Index of the bracket that closes the one at `text[start]`."""
    depth = 0
    for i in range(start, len(text)):
        c = text[i]
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
            if depth == 0:
                return i
    raise ValueError("unbalanced HLO text")


def _split_top(text):
    """The comma-separated parts of `text` outside any bracket."""
    parts, depth, start = [], 0, 0
    for i, c in enumerate(text):
        if c in "([{":
            depth += 1
        elif c in ")]}":
            depth -= 1
        elif c == "," and depth == 0:
            parts.append(text[start:i].strip())
            start = i + 1
    last = text[start:].strip()
    return parts + [last] if last else parts


def _array_bytes(type_text):
    """(bytes in memory, memory space) of one array type with its layout,
    `bf16[16,768,2048]{2,1,0:T(8,128)(2,1)S(1)}`: the dimensions padded up
    to the layout's tiles (a `[B, T, 1]` under `T(8,128)` takes 128 lanes a
    row), space 0 (HBM) where the layout names none."""
    m = _HLO_ARRAY.match(type_text)
    if m is None:
        return 0, 0
    dtype, dims, layout = m.group(1), m.group(2), m.group(3) or ""
    bits = _BITS.get(dtype)
    if bits is None:
        digits = re.search(r"(\d+)", dtype)      # f32, bf16, s8, f8e4m3fn
        bits = int(digits.group(1)) if digits else 32
    dims = [int(d.lstrip("<=")) for d in dims.split(",") if d.strip()]
    order, _, attrs = layout.partition(":")
    space = _HLO_SPACE.search(attrs)
    packed = _HLO_ELEMENT_BITS.search(attrs)
    if packed:
        bits = int(packed.group(1))
    tile = _HLO_TILE.search(attrs)
    if tile:
        dims = dims or [1]           # a tiled scalar takes one tile
        minor_to_major = [int(d) for d in order.split(",") if d.strip()]
        if len(minor_to_major) == len(dims):     # major -> minor, as stored
            dims = [dims[d] for d in reversed(minor_to_major)]
        tiles = [tile.group(1)] + re.findall(r"\(([\d,]+)\)", tile.group(2))
        for t in tiles:
            t = [int(x) for x in t.split(",")]
            k = len(t)
            if k > len(dims):
                dims = [1] * (k - len(dims)) + dims
            outer = [-(-d // x) for d, x in zip(dims[-k:], t)]
            dims = dims[:-k] + outer + t
    n = 1
    for d in dims:
        n *= d
    return (n * bits + 7) // 8, int(space.group(1)) if space else 0


def _result_elements(type_text):
    """[(bytes, space)] an element of the result: one for an array, one a
    top-level element for a tuple (a nested tuple's parts summed)."""
    type_text = _HLO_COMMENT.sub("", type_text).strip()
    if not type_text.startswith("("):
        return [_array_bytes(type_text)]
    out = []
    for part in _split_top(type_text[1:-1]):
        inner = _result_elements(part)
        hbm = sum(b for b, space in inner if space == 0)
        out.append((hbm, 0) if hbm or not inner else (0, inner[0][1]))
    return out


def _computations(hlo_text):
    """({computation name: its instruction lines}, the ENTRY's name)."""
    out, entry, lines = {}, None, None
    for line in hlo_text.splitlines():
        if line.startswith(" "):
            if lines is not None:
                lines.append(line)
            continue
        m = _HLO_COMPUTATION.match(line)
        lines = out.setdefault(m.group(1), []) if m else None
        if m and line.startswith("ENTRY "):
            entry = m.group(1)
    return out, entry


def _instructions(lines):
    """[(name, is_root, result type, opcode, operand names, attributes)] of
    one computation, in the order of the text — the schedule, where the
    module's header says `is_scheduled=true`."""
    out = []
    for line in lines:
        m = _HLO_INSTR.match(line)
        if m is None:
            continue
        rest = m.group(2)
        end = _closing(rest) + 1 if rest.startswith("(") else rest.index(" ")
        op = _HLO_OPCODE.match(rest[end:])
        if op is None:
            continue
        args_at = end + op.end() - 1
        args_end = _closing(rest, args_at)
        out.append((m.group(1), line.lstrip().startswith("ROOT "),
                    rest[:end], op.group(1),
                    _HLO_NAME.findall(rest[args_at:args_end]),
                    rest[args_end + 1:args_end + 400]))
    return out


def _union(elements):
    return set().union(*elements) if elements else set()


class _Sweep:
    """One computation's buffers in schedule order: a row a buffer in
    `size` / `born` / `last` / `producer` / `born_phase`, the phase of each
    instruction, where the live set peaks, and where the backward begins."""

    def __init__(self, instructions, ops, body_peak):
        self.instructions = instructions
        self.size, self.born, self.last = [], [], []
        self.producer, self.born_phase = [], []
        self.phases, self.n_containers, self.backward = [], 0, None
        values = {}           # instruction -> [set of buffers] an element
        destination = {}      # `*-start` -> the element its `*-done` yields
        outputs = set()
        for i, (name, is_root, type_text, opcode, operands, attrs) in \
                enumerate(instructions):
            phase = phase_of(ops.get(name, ""))
            self.phases.append(phase)
            read = [values.get(o, []) for o in operands]
            if opcode == "get-tuple-element" and read:
                # reads one element: the tuple's others are not kept by it
                k = _HLO_TUPLE_INDEX.search(attrs)
                k = int(k.group(1)) if k else -1
                read = [read[0][k:k + 1] if 0 <= k < len(read[0])
                        else read[0]]
            for elements in read:
                for b in _union(elements):
                    self.last[b] = i
                    # the backward begins where it first reads what the
                    # forward made (a weight's transpose scheduled early
                    # reads an argument, and is no such reader)
                    if self.backward is None and \
                            phase in ("replay", "bwd") and \
                            self.born_phase[b] == "fwd":
                        self.backward = i
            first = read[0] if read else [set()]

            def fresh(elements):
                return [self._fresh(nbytes, space, i, name, phase)
                        for nbytes, space in elements]

            if opcode in ("parameter", "constant"):
                value = [set() for _ in _result_elements(type_text)]
            elif opcode == "tuple":
                value = [_union(elements) for elements in read]
            elif opcode == "get-tuple-element":
                value = [_union(first)]
            elif opcode in _HLO_VIEWS or opcode == "while":
                # a loop's state lives in its operand's buffers; what one
                # turn of its body holds beside them is added below
                value = list(first)
            elif opcode.endswith("-done"):
                k = destination.get(operands[0] if operands else None)
                value = [first[k]] if k is not None and k < len(first) \
                    else [_union(first)]
            elif opcode.endswith("-start"):
                elements = _result_elements(type_text)
                k = 0 if opcode == "copy-start" or len(elements) < 2 else 1
                destination[name] = k
                value = [set() for _ in elements]
                value[k] = fresh([elements[k]])[0]
            else:
                elements = _result_elements(type_text)
                aliased = _aliased_outputs(attrs, read)
                value = fresh([(0, 0) if k in aliased else e
                               for k, e in enumerate(elements)])
                for k, buffers in aliased.items():
                    if k < len(value):
                        value[k] = buffers
            if opcode in _HLO_CONTAINERS:
                self.n_containers += 1
                called = _HLO_CALLED.findall(attrs)
                for branches in _HLO_BRANCHES.findall(attrs):
                    called += _HLO_NAME.findall(branches)
                fresh([(max(map(body_peak, called), default=0), 0)])
            values[name] = value
            if is_root:
                outputs = _union(value)
        n = len(instructions)
        delta = [0] * (n + 1)
        for b in outputs:             # an output is no temporary
            self.size[b] = 0
        for b, nbytes in enumerate(self.size):
            delta[self.born[b]] += nbytes
            delta[self.last[b] + 1] -= nbytes
        self.peak, self.peak_i, live = 0, 0, 0
        for i in range(n):
            live += delta[i]
            if live > self.peak:
                self.peak, self.peak_i = live, i

    def _fresh(self, nbytes, space, i, name, phase):
        """The buffers (one or none) of a result element made at `i`."""
        if space or not nbytes:       # outside HBM, or a token
            return set()
        self.size.append(nbytes)
        self.born.append(i)
        self.last.append(i)
        self.producer.append(name)
        self.born_phase.append(phase)
        return {len(self.size) - 1}

    def live_at(self, at):
        """[(bytes, producer, born phase)] live at instruction `at`,
        largest first."""
        rows = [(self.size[b], self.producer[b], self.born_phase[b])
                for b in range(len(self.size))
                if self.size[b] and self.born[b] <= at <= self.last[b]]
        rows.sort(key=lambda r: -r[0])
        return rows


def _aliased_outputs(attrs, read):
    """{output element: the operand's buffers it is written into} from an
    instruction's `output_to_operand_aliasing`."""
    m = _HLO_ALIASING.search(attrs)
    aliased = {}
    for out_index, operand, operand_index in \
            _HLO_ALIAS_PAIR.findall(m.group(1)) if m else ():
        src = read[int(operand)] if int(operand) < len(read) else []
        out_k = int(out_index.split(",")[0]) if out_index.strip() else 0
        k = int(operand_index.split(",")[0]) if operand_index.strip() \
            else None
        aliased[out_k] = src[k] if k is not None and k < len(src) \
            else _union(src)
    return aliased


def buffer_sweep(hlo_text: str, ops) -> dict:
    """A liveness sweep of the scheduled ENTRY computation of an optimized
    HLO text: which temporaries are in HBM at which instruction.

    One buffer per instruction result (one a top-level element of a tuple
    result), born at its line and dead after its last reader, with these
    rules: `parameter` and `constant` are arguments, whatever reaches ROOT
    is an output (donated state aliases its argument) — neither is a
    temporary; `bitcast`, `get-tuple-element`, `tuple`, `add-dependency`
    and `*-done` move no bytes and lengthen the life of what they read (a
    `get-tuple-element` only of its element); of a `*-start` tuple only the
    destination counts; an output that the instruction says aliases an
    operand (`output_to_operand_aliasing`) IS that operand; a result whose
    layout names a memory space other than HBM (`S(n)`, n >= 1) is not
    counted; a `while` lives in its operand's buffers and a `call` /
    `conditional` counts as its result, each with ONE more buffer live at
    that instruction alone: the peak of the same sweep over its body
    (`n_containers` says how many such instructions the ENTRY has).  Sizes
    are the layout's: dimensions padded to its tiles.  Each buffer takes
    the op_name `ops` gives its producer, and from it a phase (`phase_of`)
    and a scope (`scope_of`).

    Returns the bounded account (no per-buffer table is kept):
    `peak_bytes` and `peak_at` (`{"instruction", "index", "of", "op_name",
    "phase"}`: where the live set is largest); `live_at_peak` (the 32
    largest buffers there as `[bytes, instruction, op_name, born_phase]`)
    and `by_scope_at_peak` (`{scope: bytes}`, all of them, largest first);
    `residual_bytes`, `residual_by_scope` and `backward_at`: buffers born
    in phase `fwd` (or `other`: the TPU compiler renames a grouped product
    `ragged-dot-none`, which no scope reaches; row `-:`) and still live at
    the first instruction of phase `replay` or `bwd` that reads a forward
    buffer — what the forward holds for the backward; `n_buffers`,
    `n_containers`, `sweep_ms`.  What it cannot
    see: which buffers the compiler lets share memory and what it loses
    between them (its own total, `memory_analysis().temp_size_in_bytes`, is
    the check), and in which turn of a loop a body's buffer is live."""
    t0 = time.perf_counter()
    ops = ops or {}
    computations, entry = _computations(hlo_text)
    peaks = {}

    def body_peak(name):
        if name not in peaks:
            peaks[name] = 0                      # a cycle ends here
            peaks[name] = _Sweep(_instructions(computations.get(name, ())),
                                 ops, body_peak).peak
        return peaks[name]

    sweep = _Sweep(_instructions(computations.get(entry, ())), ops, body_peak)
    n = len(sweep.instructions)

    def account(rows):
        named = [[nbytes, name, ops.get(name, ""), phase]
                 for nbytes, name, phase in rows]
        by_scope = {}
        for nbytes, _, op_name, _ in named:
            key = scope_of(op_name) if op_name else "-:"
            by_scope[key] = by_scope.get(key, 0) + nbytes
        return named, dict(sorted(by_scope.items(), key=lambda kv: -kv[1]))

    at_peak, by_scope_at_peak = account(sweep.live_at(sweep.peak_i)) \
        if n else ([], {})
    residual, residual_by_scope = account(
        [r for r in sweep.live_at(sweep.backward)
         if r[2] in ("fwd", "other")]) \
        if sweep.backward is not None else ([], {})

    def where(i):
        if i is None or not n:
            return None
        name = sweep.instructions[i][0]
        return {"instruction": name, "index": i, "of": n,
                "op_name": ops.get(name, ""), "phase": sweep.phases[i]}

    return {
        "peak_bytes": sweep.peak, "peak_at": where(sweep.peak_i),
        "live_at_peak": at_peak[:_LIVE_AT_PEAK_KEPT],
        "by_scope_at_peak": by_scope_at_peak,
        "residual_bytes": sum(r[0] for r in residual),
        "residual_by_scope": residual_by_scope,
        "backward_at": where(sweep.backward),
        "n_buffers": len(sweep.size), "n_containers": sweep.n_containers,
        "sweep_ms": (time.perf_counter() - t0) * 1e3}


# JAX's own stage durations, for EVERY program of the process
_JAX_STAGE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
# ... and what JAX says of its persistent compilation cache: a request is
# a compile that asked the cache, a hit one that was LOADED from it (both
# durations fire on a hit only), a write a compile that was stored (JAX
# names that event `cache_misses`; a miss too quick or too small to be
# worth storing fires nothing)
_JAX_CACHE_DURATIONS = {
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_retrieval_ms",
    "/jax/compilation_cache/compile_time_saved_sec": "cache_saved_ms",
}
_JAX_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "cache_requests",
    "/jax/compilation_cache/cache_misses": "cache_writes",
}
_totals = {f"{s}_{k}": 0 for s in _JAX_STAGE_EVENTS.values()
           for k in ("ms", "n")} | dict.fromkeys(
    ("cache_requests", "cache_hits", "cache_writes",
     "cache_retrieval_ms", "cache_saved_ms"), 0)
# (perf_counter of a 0.1 s bucket's first event, totals at the bucket's
# last): what `process_compile_totals(until=...)` answers from
_totals_timeline: collections.deque = collections.deque(maxlen=8192)
_TIMELINE_BUCKET_S = 0.1
_watching = False
# .stack: (start_s, duration_s), newest last; .cache: [requests, hits] of
# this thread, which is how a labelled compile learns what ITS
# `lowered.compile()` was
_stage_events = threading.local()


def _add_to_totals(now, **adds):
    with _last_lock:
        for k, v in adds.items():
            _totals[k] += v
        if _totals_timeline and \
                now - _totals_timeline[-1][0] < _TIMELINE_BUCKET_S:
            _totals_timeline[-1] = (_totals_timeline[-1][0], dict(_totals))
        else:
            _totals_timeline.append((now, dict(_totals)))


def _cache_counts_of_thread():
    return _stage_events.__dict__.setdefault("cache", [0, 0])


def _on_jax_duration(event, duration, **_kw):
    cache_key = _JAX_CACHE_DURATIONS.get(event)
    if cache_key is not None:
        adds = {cache_key: duration * 1e3}
        if cache_key == "cache_retrieval_ms":      # one a hit
            adds["cache_hits"] = 1
            _cache_counts_of_thread()[1] += 1
        _add_to_totals(time.perf_counter(), **adds)
        return
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
    _add_to_totals(now, **{stage + "_ms": max(own, 0.0) * 1e3,
                           stage + "_n": 1})


def _on_jax_event(event, **_kw):
    key = _JAX_CACHE_EVENTS.get(event)
    if key is None:
        return
    if key == "cache_requests":
        _cache_counts_of_thread()[0] += 1
    _add_to_totals(time.perf_counter(), **{key: 1})


def watch_process_compiles() -> None:
    """Register the `jax.monitoring` listeners behind
    `process_compile_totals` (once; `metrics.enable()`/`trace.enable()`
    call this, so the totals start no later than telemetry does)."""
    global _watching
    with _last_lock:
        if _watching:
            return
        _watching = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_jax_duration)
    jax.monitoring.register_event_listener(_on_jax_event)


def process_compile_totals(until=None) -> dict:
    """`{"trace_ms", "trace_n", "lower_ms", "lower_n", "compile_ms",
    "compile_n"}`: JAX's own durations and counts of jaxpr tracing,
    lowering to MLIR and backend compilation (a persistent-cache load
    counts as the latter; a stage nested in another counts once, under
    its own name), summed over every program since
    `watch_process_compiles`.  Minus the labelled programs of
    `program_ledger` it is what the unlabelled small programs cost.
    Beside them what the persistent compilation cache did: `cache_requests`
    (compiles that asked it), `cache_hits` (loaded from it, in
    `cache_retrieval_ms`, sparing `cache_saved_ms` of compilation by the
    entry's own record) and `cache_writes` (compiled and stored; a request
    that is neither was compiled and judged too quick to keep).  `until`
    (a `time.perf_counter()` value) asks for the totals as they stood then,
    to within 0.1 s — a benchmark's set-up, not the reference it compiles
    afterwards."""
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

    def aot_compile(self, *args, **kwargs):
        """Trace, lower and compile for these arguments (arrays, or
        `jax.ShapeDtypeStruct`s where nothing is to run) inside an
        `xla.compile:<label>` span, record the compile in the program
        ledger and hand back the `Compiled`.  What the first call of a
        signature does under telemetry; also the way to read a program's
        ledger entry without a device to run it on
        (`tools/step_bytes.py`)."""
        with _trace.span(f"xla.compile:{self.label}", cat="compile") as sp:
            # three stage walls: jaxpr trace, lowering to MLIR, backend
            # compile (or a cache load)
            t0 = time.perf_counter()
            traced = self._jitted.trace(*args, **kwargs)
            t1 = time.perf_counter()
            lowered = traced.lower()
            t2 = time.perf_counter()
            asked, hit = _cache_counts_of_thread()
            compiled = lowered.compile()
            t3 = time.perf_counter()
            asked_now, hit_now = _cache_counts_of_thread()
            # what THIS compile was: loaded from the persistent cache,
            # compiled, or (None) compiled with no cache asked
            cache = None if asked_now == asked else \
                "hit" if hit_now > hit else "miss"
            del traced, lowered
            costs = capture(compiled, self.label)
            costs["trace_ms"] = (t1 - t0) * 1e3
            costs["lower_ms"] = (t2 - t1) * 1e3
            costs["compile_ms"] = (t3 - t2) * 1e3
            rec = _record_program(
                self.label, compiled, costs["trace_ms"],
                costs["lower_ms"], costs["compile_ms"], costs, cache)
            costs["ledger_ms"] = rec["ledger_ms"]
            _metrics.set_gauge("xla.cost.compile_ms", costs["compile_ms"],
                               label=self.label)
            _feed_lifecycle(self.label, costs["trace_ms"],
                            costs["lower_ms"], costs["compile_ms"])
            with _last_lock:
                _last[self.label] = dict(costs)
            if sp is not None:
                sp.args.update(costs)
        return compiled

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
                try:
                    compiled = self.aot_compile(*args, **kwargs)
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
