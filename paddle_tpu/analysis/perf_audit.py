"""Layer 4: static performance auditor (rules PT400–PT405).

Layers 1–3 catch *correctness* bug classes; this layer catches the
*cost* classes PERF.md's xprof forensics measured on hardware — and
holds them to committed per-model budgets so they cannot regress
silently on a CPU-only CI box:

  PT400  audit failure      a representative program failed to build/
                            trace/lower — the auditor is blind there;
                            surfaced, never swallowed
  PT401  layout tax         explicit transpose/copy/bitcast-convert ops
                            and the bytes they move per step — the
                            static twin of the measured 66 ms/step (20%)
                            transpose burn (PERF.md "Where the remaining
                            MFU lives")
  PT402  recompile hazard   weak-typed scalar inputs to a traced
                            program (a Python float and a jnp.float32
                            compile twice), and call sites feeding a
                            jitted function host scalars / unhashable
                            literals — PT004 generalized from signatures
                            to call sites
  PT403  replicated state   big (≥ threshold) program arguments the
                            sharding spec leaves replicated — params or
                            optimizer state that a ZeRO-1/weight-update
                            sharding pass should shard (ROADMAP item 3)
  PT404  collective shape   all-gather whose result is immediately
                            reduced (a reduce-scatter + smaller gather
                            does the same work moving 1/N the bytes),
                            and chained collectives with no compute
                            between them (nothing to overlap with)
  PT405  hot-loop host sync device round-trips (callbacks/infeed)
                            *inside a compiled loop body* — PT201 with
                            loop context: once per step is bad, once per
                            scan iteration caps decode throughput
  PT406  dequant placement  int8→float dequantize ops traced OUTSIDE the
                            decode scan body (weight-only tier, ISSUE
                            12): a dequant hoisted out of the loop
                            materializes a full-precision weight copy
                            and the per-step HBM stream is no longer
                            int8 — the measured 1.33×/1.91× win
                            evaporates.  Audited at the JAXPR level
                            (the view WE control): the XLA:CPU proxy's
                            LICM hoists loop-invariant dequant fusions
                            regardless (observed, documented in
                            PERF.md), while the TPU pipeline does not
                            hoist size-inflating ops — so the
                            source-placement pin is the honest gate.

Representative programs (all built under ``JAX_PLATFORMS=cpu``):
  * ``train_step``  — the hybrid GPT train step at a small proxy shape
                      (same structure/dtypes as the bench shape)
  * ``sharded_train_step`` — the SAME GPT proxy under the default
                      multi-chip configuration (dp=8 over the audit
                      env's virtual devices → auto ZeRO-1, ISSUE 11):
                      its committed budget pins the sharded weight
                      update — ``pt403_replicated_*`` ≈ 0 (params AND
                      optimizer state live dp-sharded) and the
                      ``pt404_opt_*`` collective counts hold the wire
                      shape, so a reintroduced replicated update fails
                      CI before a TPU ever runs
  * ``swin_train_step`` — the Swin train step at a tiny proxy shape
                      (pins the windowed-attention layout tax: roll /
                      window-partition transposes, rel-pos-bias
                      plumbing — ISSUE 10)
  * ``decode_step`` — the scanned KV-cache decode program
                      (``GenerationMixin._decode_chunk_program``)
  * ``call_sites``  — AST scan of the repo for PT402 call-site hazards
                      (stdlib-only: no jax import)
  * ``op_table``    — the OPS_MANIFEST unary/binary conformance surface
                      (tracing only; slow tier)

Each program yields a metrics dict (``pt401_transpose_mbytes`` …)
aggregated into ``tools/perf_budget.json`` — the perf analog of
``tools/lint_baseline.json``.  ``tools/pt_lint.py --perf --check``
exits 2 when any metric exceeds its committed budget;
``--update-budget`` ratchets the file after a verified win.
``tools/perf_gate.py`` merges the same budgets next to its measured
bench metrics (rows named ``static.<program>.<metric>``) so a PR that
adds transposes fails CI before a TPU ever runs.

jax imports are function-local: importing this module is stdlib-cheap,
so the ``call_sites`` program (and the CLI fast path) never pays for
the model stack.
"""
from __future__ import annotations

import ast
import os
import re
import sys

from .report import Violation
from .trace_safety import _dotted, _is_jit_callee, _jit_decorator

__all__ = [
    "RULE_IDS", "DEFAULT_PROGRAMS", "FULL_PROGRAMS",
    "layout_tax", "weak_input_count", "replicated_args",
    "replicated_arg_details", "collective_hlo_counts",
    "collective_patterns", "host_sync_counts", "dequant_placement",
    "call_site_hazards",
    "audit_program_texts", "audit_perf", "metrics_to_static_rows",
    "audit_hlo", "train_step_hlo",
]

RULE_IDS = ("PT400", "PT401", "PT402", "PT403", "PT404", "PT405",
            "PT406")

# program names: the fast subset runs in the tier-1 smoke; FULL adds the
# op-table sweep (slow tier — imports + traces the whole exported surface)
DEFAULT_PROGRAMS = ("train_step", "sharded_train_step", "swin_train_step",
                    "decode_step", "paged_decode_step",
                    "quantized_decode_step", "cached_prefill_step",
                    "call_sites")
FULL_PROGRAMS = ("train_step", "sharded_train_step", "swin_train_step",
                 "decode_step", "paged_decode_step",
                 "quantized_decode_step", "cached_prefill_step",
                 "call_sites", "op_table")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "i64": 8,
             "i32": 4, "ui32": 4, "i16": 2, "i8": 1, "ui8": 1, "i1": 1}

# collective primitives as they appear in jaxprs (psum_scatter is jax's
# reduce-scatter; ppermute shows up in ring schedules)
_COLLECTIVE_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "ppermute",
    "psum_scatter", "reduce_scatter",
}
_REDUCE_PRIMS = {"reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "reduce_and", "reduce_or", "argmax", "argmin"}
_HOST_SYNC_PRIMS = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed",
}
_LOOP_PRIMS = {"scan", "while", "fori_loop", "cumred_loop"}


def _numel(dims: str) -> int:
    n = 1
    for d in dims.split("x"):
        if d.strip():
            n *= int(d)
    return n


def _r2(x: float) -> float:
    """Budget values are rounded once, here — the determinism contract
    (byte-identical budget JSON across runs) depends on every float
    passing through exactly one rounding."""
    return round(float(x), 2)


# ------------------------- PT401: layout tax -------------------------

_SHLO_TRANSPOSE = re.compile(
    r"stablehlo\.transpose[^\n]*?->\s*tensor<([0-9x]+)x(\w+)>")
# optimized HLO: `%name = f32[4,8]{1,0} transpose(...)` — the op name
# sits between the shape/layout annotation and the open paren
_OPT_OP = re.compile(
    r"=\s*[a-z0-9]+\[[0-9,]*\][^ ]*\s+(transpose|copy|bitcast-convert)\(")


def layout_tax(stablehlo_text: str, opt_hlo_text: str = "") -> dict:
    """PT401 metrics for one program.

    StableHLO transposes are the backend-independent (deterministic)
    budget basis; the optimized-HLO counts record what the compiled
    executable actually schedules (fusion elides some, layout
    assignment adds copies) — both are budgeted so a regression in
    either view trips the gate."""
    count, mbytes = 0, 0.0
    for m in _SHLO_TRANSPOSE.finditer(stablehlo_text):
        dims, dt = m.groups()
        count += 1
        mbytes += _numel(dims) * _ITEMSIZE.get(dt, 4) / 2**20
    opt = {"transpose": 0, "copy": 0, "bitcast-convert": 0}
    for m in _OPT_OP.finditer(opt_hlo_text):
        opt[m.group(1)] += 1
    return {
        "pt401_transpose_count": count,
        "pt401_transpose_mbytes": _r2(mbytes),
        "pt401_opt_transpose_count": opt["transpose"],
        "pt401_opt_copy_count": opt["copy"],
        "pt401_opt_bitcast_convert_count": opt["bitcast-convert"],
    }


# --------------------- PT402: recompile hazards ---------------------


def weak_input_count(closed_jaxpr) -> int:
    """Weak-typed input avals: each is a cache-key split (`f(x, 0.1)`
    and `f(x, jnp.float32(0.1))` compile two programs) and a promotion
    trap (weak f32 scalar * bf16 array stays bf16, but a strong one
    promotes)."""
    return sum(1 for a in getattr(closed_jaxpr, "in_avals", ())
               if getattr(a, "weak_type", False))


_HOST_SCALAR_CALLS = {"int", "float", "bool", "len"}


def _jitted_wrapper_names(tree: ast.Module) -> set:
    """Names bound to a jit-wrapped callable in this module:
    ``g = jax.jit(f, ...)`` assignments plus ``@jax.jit``-decorated
    defs (any dotted jit/pjit/to_static spelling)."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign) and \
                isinstance(node.value, ast.Call) and \
                _is_jit_callee(node.value.func):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    names.add(t.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_jit_decorator(d) for d in node.decorator_list):
                names.add(node.name)
    return names


def call_site_hazards(source: str, path: str,
                      tree: ast.Module | None = None) -> list:
    """PT402 at call sites: arguments to a known-jitted callable that
    force recompiles or cache-key churn —

      * ``g(x, int(n))`` / ``float(...)`` / ``len(...)`` / ``.item()``:
        a host Python scalar rebuilt per call; as a static arg it
        retraces per distinct value, as a traced arg it is a weak-type
        cache split (and the ``.item()`` is a device sync besides)
      * ``g(x, [1, 2])`` / ``{...}``: a fresh mutable literal per call —
        unhashable if static (TypeError at call time), retrace-bait if
        its contents ever vary

    Constant-folded literals (plain numbers/strings) are fine and not
    flagged."""
    if tree is None:
        tree = ast.parse(source)
    jitted = _jitted_wrapper_names(tree)
    out = []
    if not jitted:
        return out

    def hazard_of(arg) -> str:
        if isinstance(arg, (ast.List, ast.Dict, ast.Set)):
            return "a mutable literal (unhashable as a static arg, " \
                   "retrace-bait as a traced one)"
        if isinstance(arg, ast.Call):
            callee = _dotted(arg.func)
            if callee in _HOST_SCALAR_CALLS:
                return (f"`{callee}(...)` — a host Python scalar per "
                        f"call (weak-type cache split / retrace per "
                        f"value)")
            if isinstance(arg.func, ast.Attribute) and \
                    arg.func.attr == "item":
                return "`.item()` — a device sync feeding a fresh " \
                       "Python scalar per call"
        return ""

    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Name)
                and node.func.id in jitted):
            continue
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            why = hazard_of(arg)
            if why:
                out.append(Violation(
                    path, node.lineno, "PT402",
                    f"jitted `{node.func.id}` called with {why}"))
    return out


# ------------------- PT403: replicated big buffers -------------------

_ARG_TENSOR = re.compile(r"tensor<(?:(\d+(?:x\d+)*)x)?([a-z]\w*)>")
_SHARDED_ATTR = re.compile(r'mhlo\.sharding\s*=\s*"\{devices=')
_DONATED = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")


def _iter_replicated_args(stablehlo_text: str, min_mbytes: float):
    """Yield ``(arg_index, mbytes)`` for every ``@main`` argument at
    least ``min_mbytes`` big whose sharding attr is absent or
    ``{replicated}``."""
    main = stablehlo_text.split("func.func public @main", 1)
    if len(main) < 2:
        return
    header = main[1].split("->", 1)[0]
    parts = re.split(r"%arg(\d+):", header)[1:]
    for i in range(0, len(parts) - 1, 2):
        idx, chunk = int(parts[i]), parts[i + 1]
        m = _ARG_TENSOR.search(chunk)
        if m is None:
            continue
        dims, dt = m.groups()
        mb = _numel(dims or "") * _ITEMSIZE.get(dt, 4) / 2**20
        if mb < min_mbytes:
            continue
        if not _SHARDED_ATTR.search(chunk):
            yield idx, mb


def replicated_args(stablehlo_text: str, min_mbytes: float = 0.05) -> dict:
    """PT403: ``@main`` arguments at least ``min_mbytes`` big whose
    sharding attr is absent or ``{replicated}`` — the state a
    cross-replica weight-update sharding pass (ZeRO-1) should shard.
    Donated-but-replicated still counts: donation halves peak memory,
    sharding divides it by the replica count."""
    count, mbytes = 0, 0.0
    for _idx, mb in _iter_replicated_args(stablehlo_text, min_mbytes):
        count += 1
        mbytes += mb
    return {"pt403_replicated_count": count,
            "pt403_replicated_mbytes": _r2(mbytes)}


def replicated_arg_details(stablehlo_text: str, min_mbytes: float = 0.05,
                           arg_names=None) -> list:
    """PT403 offenders as ``[(owner, mbytes)]``, biggest first.  With
    ``arg_names`` (flattened jit-argument names, index-aligned with the
    ``@main`` args) the owner is the PARAMETER the replicated buffer
    belongs to — budget regressions become actionable from the lint
    output alone (ISSUE 11 satellite)."""
    out = []
    for idx, mb in _iter_replicated_args(stablehlo_text, min_mbytes):
        name = None
        if arg_names is not None and 0 <= idx < len(arg_names):
            name = arg_names[idx]
        out.append((name or f"arg{idx}", _r2(mb)))
    out.sort(key=lambda t: (-t[1], t[0]))
    return out


# ---------------- PT404: compiled collective shape ----------------

# optimized-HLO collective ops (async forms count once via `-start`;
# `-done` is the same op completing).  The result-type run between `=`
# and the op name must admit parentheses: async collectives carry TUPLE
# result types (`= (f32[64]{0}, f32[64]{0}) all-reduce-start(`).  `%`
# stays excluded so operand references to collective-named values
# (`fusion(f32[] %all-reduce.3)`) never count.
_OPT_COLLECTIVE = re.compile(
    r"=\s*[a-z0-9_\[\](),{}:\s]*?\b"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute)"
    r"(?:-start)?\(")


def collective_hlo_counts(opt_hlo_text: str) -> dict:
    """PT404 metrics from the COMPILED (partitioned) program: how many
    of each collective the executable actually schedules.  For the
    sharded train step these pin the ZeRO-1 wire shape from both
    directions: the committed count ceilings catch growth-class
    regressions (per-layer param gathers), and the derived
    ``pt404_grad_sync_deficit`` (params minus scheduled additive
    collectives, budget 0 — computed in ``audit_perf``) catches the
    opposite one, grad syncs fused into an end-of-backward barrier,
    which LOWERS the raw counts and would otherwise read as an
    "improvement".  Note the CPU
    partitioner realizes reduce-scatter as all-reduce+dynamic-slice
    (the fused op is the TPU pipeline's rewrite — the *Automatic
    Cross-Replica Sharding* pass), so ``reduce_scatter`` may read 0 on
    the CPU-audited view while the same program scatters on TPU."""
    counts = {"all-reduce": 0, "all-gather": 0, "reduce-scatter": 0,
              "collective-permute": 0}
    for m in _OPT_COLLECTIVE.finditer(opt_hlo_text):
        counts[m.group(1)] += 1
    return {
        "pt404_opt_all_reduce_count": counts["all-reduce"],
        "pt404_opt_all_gather_count": counts["all-gather"],
        "pt404_opt_reduce_scatter_count": counts["reduce-scatter"],
        "pt404_opt_collective_permute_count": counts["collective-permute"],
    }


# -------------------- PT404 / PT405: jaxpr walks --------------------


def _iter_subjaxprs(param):
    import jax.core as jcore

    closed = getattr(jcore, "ClosedJaxpr", ())
    raw = getattr(jcore, "Jaxpr", ())
    if isinstance(param, (closed, raw)):
        yield param
    elif isinstance(param, (list, tuple)):
        for p in param:
            yield from _iter_subjaxprs(p)


def _walk_eqns_ctx(jaxpr, in_loop=False):
    """Yield ``(eqn, in_loop)`` for every eqn, recursing into sub-jaxprs
    and marking everything under a scan/while body as loop context."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn, in_loop
        child_loop = in_loop or eqn.primitive.name in _LOOP_PRIMS
        for param in eqn.params.values():
            for sub in _iter_subjaxprs(param):
                yield from _walk_eqns_ctx(sub, child_loop)


def collective_patterns(closed_jaxpr) -> dict:
    """PT404 metrics: all-gather feeding a reduction, and collectives
    chained output-to-input (back-to-back on the wire — nothing between
    them for the scheduler to overlap)."""
    producer = {}  # id(var) -> primitive name
    allgather_reduce = 0
    chained = 0
    for eqn, _ in _walk_eqns_ctx(closed_jaxpr):
        name = eqn.primitive.name
        in_prims = {producer.get(id(v)) for v in eqn.invars}
        if name in _REDUCE_PRIMS and "all_gather" in in_prims:
            allgather_reduce += 1
        if name in _COLLECTIVE_PRIMS and in_prims & _COLLECTIVE_PRIMS:
            chained += 1
        for v in eqn.outvars:
            producer[id(v)] = name
    return {"pt404_allgather_reduce": allgather_reduce,
            "pt404_chained_collectives": chained}


def host_sync_counts(closed_jaxpr) -> dict:
    """PT405 metrics: host round-trips total and inside loop bodies."""
    total, in_loop = 0, 0
    for eqn, loop in _walk_eqns_ctx(closed_jaxpr):
        if eqn.primitive.name in _HOST_SYNC_PRIMS:
            total += 1
            if loop:
                in_loop += 1
    return {"pt405_host_syncs": total, "pt405_loop_host_syncs": in_loop}


def dequant_placement(closed_jaxpr) -> dict:
    """PT406 metrics: int8→float ``convert_element_type`` eqns inside
    vs outside compiled loop bodies.  In the quantized decode program
    every dequant (weights AND KV pages) must be traced INSIDE the scan
    body — a count appearing outside means someone moved
    `_dequant_params` (or the page dequant) out of the loop, and the
    weights would stream full-precision per step on every backend."""
    import jax.numpy as jnp

    in_loop, hoisted = 0, 0
    for eqn, loop in _walk_eqns_ctx(closed_jaxpr):
        if eqn.primitive.name != "convert_element_type":
            continue
        src = getattr(getattr(eqn.invars[0], "aval", None), "dtype",
                      None)
        dst = getattr(getattr(eqn.outvars[0], "aval", None), "dtype",
                      None)
        if src is None or dst is None:
            continue
        if src == jnp.int8 and jnp.issubdtype(dst, jnp.floating):
            if loop:
                in_loop += 1
            else:
                hoisted += 1
    return {"pt406_dequant_in_loop_count": in_loop,
            "pt406_dequant_hoisted_count": hoisted}


# ---------------------- per-program aggregation ----------------------


def audit_program_texts(where: str, closed_jaxpr=None,
                        stablehlo_text: str = "",
                        opt_hlo_text: str = "",
                        min_replicated_mbytes: float = 0.05,
                        arg_names=None):
    """(violations, metrics) for one program given whichever of its
    three views (jaxpr / StableHLO / optimized HLO) the caller has.
    Pure aggregation — no jax imports, so text fixtures test it
    directly.  ``arg_names`` (flattened jit-argument names) lets the
    PT403 finding name the owning parameters."""
    metrics = {}
    metrics.update(layout_tax(stablehlo_text, opt_hlo_text))
    metrics.update(replicated_args(stablehlo_text,
                                   min_replicated_mbytes))
    metrics.update(collective_hlo_counts(opt_hlo_text))
    if closed_jaxpr is not None:
        metrics["pt402_weak_inputs"] = weak_input_count(closed_jaxpr)
        metrics.update(collective_patterns(closed_jaxpr))
        metrics.update(host_sync_counts(closed_jaxpr))
    out = []
    w = f"perf:{where}"
    if metrics.get("pt401_transpose_count"):
        out.append(Violation(
            w, 0, "PT401",
            f"layout tax: {metrics['pt401_transpose_count']} explicit "
            f"transpose(s) moving {metrics['pt401_transpose_mbytes']} "
            f"MiB per step (compiled: "
            f"{metrics['pt401_opt_transpose_count']} transpose / "
            f"{metrics['pt401_opt_copy_count']} copy / "
            f"{metrics['pt401_opt_bitcast_convert_count']} "
            f"bitcast-convert)"))
    if metrics.get("pt402_weak_inputs"):
        out.append(Violation(
            w, 0, "PT402",
            f"{metrics['pt402_weak_inputs']} weak-typed scalar "
            f"input(s) — each is a jit cache-key split (Python scalar "
            f"vs array argument compile twice)"))
    if metrics.get("pt403_replicated_count"):
        owners = replicated_arg_details(
            stablehlo_text, min_replicated_mbytes, arg_names)
        top = ", ".join(f"{n} {mb} MiB" for n, mb in owners[:4])
        if len(owners) > 4:
            top += f", +{len(owners) - 4} more"
        out.append(Violation(
            w, 0, "PT403",
            f"{metrics['pt403_replicated_count']} argument(s) "
            f"≥{min_replicated_mbytes} MiB left replicated "
            f"({metrics['pt403_replicated_mbytes']} MiB — ZeRO-1 "
            f"weight-update sharding opportunity; top: {top})"))
    if metrics.get("pt404_allgather_reduce"):
        out.append(Violation(
            w, 0, "PT404",
            f"{metrics['pt404_allgather_reduce']} all-gather(s) feeding "
            f"a reduction — reduce-scatter moves 1/N the bytes"))
    if metrics.get("pt404_chained_collectives"):
        out.append(Violation(
            w, 0, "PT404",
            f"{metrics['pt404_chained_collectives']} collective(s) "
            f"chained back-to-back — nothing between them to overlap"))
    if metrics.get("pt405_loop_host_syncs"):
        out.append(Violation(
            w, 0, "PT405",
            f"{metrics['pt405_loop_host_syncs']} host round-trip(s) "
            f"inside a compiled loop body — one device sync per "
            f"iteration"))
    elif metrics.get("pt405_host_syncs"):
        out.append(Violation(
            w, 0, "PT405",
            f"{metrics['pt405_host_syncs']} host round-trip(s) in the "
            f"step program — a device sync per call"))
    return out, metrics


# ---------------------- representative programs ----------------------


def _flat_arg_names(step, placed):
    """Flattened jit-argument names for a ``DistributedTrainStep``'s
    compiled step, index-aligned with the lowered ``@main`` arguments
    (jit flattens positional args in order; dict leaves flatten in
    sorted-key order).  Lets PT403 findings name the owning parameter
    instead of a bare arg index."""
    import jax

    def walk(label, tree):
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        out = []
        for path, _leaf in flat:
            suffix = ""
            for k in path:
                part = getattr(k, "key", None)
                if part is None:
                    part = getattr(k, "idx", None)
                if part is None:
                    part = getattr(k, "name", k)
                suffix += f".{part}"
            out.append(label + suffix)
        return out

    s = step._state
    names = walk("param", s["params"]) + walk("opt", s["opt"]) + \
        walk("buffer", s["buffers"]) + ["key", "lr"]
    names += [f"batch.{i}" for i in range(len(placed))]
    return names


def _train_step_program(batch=2, seq=128, layers=1):
    """The hybrid GPT train step at the proxy shape the Layer-3 audit
    uses (same structure/dtypes as the bench shape, small enough that
    CPU lowering is seconds). Returns ``(lowered, closed_jaxpr)`` — the
    jaxpr is retraced from the step's own ``_step_fn`` with the exact
    placed arguments the executed program sees."""
    import jax
    import jax.numpy as jnp

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from memory_report import _build_lowered
    finally:
        sys.path.pop(0)
    import paddle_tpu as P

    rs_cfg = dict(vocab_size=1024, hidden_size=64, num_layers=layers,
                  num_heads=4, max_seq_len=seq, fused_head_ce=True,
                  dropout=0.0)
    lowered, model = _build_lowered(rs_cfg, batch, seq)
    step = model._train_step
    jaxpr = names = None
    if step is not None and getattr(step, "_step_fn", None) is not None:
        import numpy as np

        rs = np.random.RandomState(0)
        ids = P.to_tensor(
            rs.randint(0, rs_cfg["vocab_size"], (batch, seq)), "int32")
        labels = P.to_tensor(
            rs.randint(0, rs_cfg["vocab_size"], (batch, seq)), "int32")
        placed, _ = step._place_batch((ids, labels), batch_axis=0)
        s = step._state
        lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
        jaxpr = jax.make_jaxpr(step._step_fn)(
            s["params"], s["opt"], s["buffers"], s["key"], lr, *placed)
        names = _flat_arg_names(step, placed)
    return lowered, jaxpr, names


def build_default_multichip_step(model_cfg=None, dp=8, seq=128, layers=1):
    """ONE definition of "the default multi-chip training
    configuration" (docs/SHARDING.md): dp=``dp`` with
    ``sharding_degree=dp`` and NO explicit stage, so the fleet wiring
    must auto-resolve ZeRO-1.  Shared by the static audit below and
    bench.py's ``--multichip-sharded-probe`` — the CI gate and the
    bench placement proof audit the SAME configuration by
    construction.  Returns ``(step, cfg)``; raises if the wiring does
    not resolve ZeRO-1."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": dp, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": dp}
    fleet.init(is_collective=True, strategy=strategy)
    P.seed(0)
    cfg = model_cfg or GPTConfig(
        vocab_size=1024, hidden_size=64, num_layers=layers,
        num_heads=4, max_seq_len=seq, fused_head_ce=True, dropout=0.0)
    inner = GPTForCausalLM(cfg)
    model = fleet.distributed_model(inner)
    opt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-4))
    step = model.build_train_step(
        opt, GPTPretrainingCriterion(model=inner),
        amp_dtype="bfloat16")
    if step.sharding_stage != 1:
        raise RuntimeError(
            f"expected auto ZeRO-1 under sharding_degree={dp}, got "
            f"stage {step.sharding_stage} — fleet sharding_degree "
            f"wiring broken")
    return step, cfg


def _sharded_train_step_program(batch=8, seq=128, layers=1):
    """The SAME GPT proxy as ``train_step``, built under the default
    multi-chip configuration (``build_default_multichip_step``) — this
    program audits the path users actually get, not a hand-assembled
    one.  The global fleet/topology state it installs is RESTORED
    afterwards: audit results must not depend on program order (the
    later programs re-audit under their own configs, and in-process
    callers like pytest keep their fleet).  Returns
    ``(lowered, closed_jaxpr, arg_names)``."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology

    prev_topo = topology._topology
    prev_strategy = fleet._state.strategy
    prev_fleet_topo = fleet._state.topo
    prev_init = fleet._state.initialized
    try:
        step, cfg = build_default_multichip_step(
            dp=8, seq=seq, layers=layers)
        rs = np.random.RandomState(0)
        ids = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                          "int32")
        labels = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                             "int32")
        lowered = step.lower(ids, labels)
        placed, _ = step._place_batch((ids, labels), batch_axis=0)
        s = step._state
        lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
        jaxpr = jax.make_jaxpr(step._step_fn)(
            s["params"], s["opt"], s["buffers"], s["key"], lr, *placed)
        return lowered, jaxpr, _flat_arg_names(step, placed)
    finally:
        topology.set_topology(prev_topo)
        fleet._state.strategy = prev_strategy
        fleet._state.topo = prev_fleet_topo
        fleet._state.initialized = prev_init


def _decode_step_program(batch=2, prompt=8, new_tokens=8):
    """The scanned KV-cache decode program — the exact jit object
    ``generate()`` dispatches per chunk (``_decode_chunk_program``),
    lowered at a tiny proxy shape. Returns ``(lowered, closed_jaxpr)``."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=4, max_seq_len=prompt + new_tokens)
    model = GPTForCausalLM(cfg)
    model.eval()
    params, buffers = model.functional_state()
    caches = model.init_kv_caches(batch, prompt + new_tokens)
    cap = caches[0][0].shape[2]
    decode_n = model._decode_chunk_program(
        new_tokens, batch, cap, False, 1.0, 0, False, None)
    args = (params, buffers, jnp.zeros((batch,), jnp.int32), caches,
            jnp.asarray(prompt, jnp.int32), jax.random.PRNGKey(0),
            None, jnp.zeros((batch,), bool))
    lowered = decode_n.lower(*args)
    jaxpr = jax.make_jaxpr(decode_n)(*args)
    return lowered, jaxpr


def _swin_train_step_program(batch=2, img=32):
    """The Swin train step at a tiny proxy shape (one shifted block in
    stage 1, bf16 AMP, Momentum) — the vision twin of ``train_step``.
    Its PT401 numbers pin the windowed-attention layout tax (roll /
    window-partition 6-D transposes, rel-pos-bias plumbing) statically,
    the same way the GPT step's budget pins the flash layout tax
    (ISSUE 10; PERF.md Swin ablation: that machinery alone costs ~43%
    of achievable step rate on-chip).  Returns ``(lowered, jaxpr)``."""
    import numpy as np

    import jax

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.vision.models import SwinTransformer

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    P.seed(0)
    inner = SwinTransformer(img_size=img, patch_size=4, embed_dim=32,
                            depths=(2, 2), num_heads=(2, 4),
                            window_size=4, num_classes=8)
    model = fleet.distributed_model(inner)
    opt = fleet.distributed_optimizer(P.optimizer.Momentum(
        parameters=model.parameters(), learning_rate=1e-3, momentum=0.9))
    step = model.build_train_step(opt, P.nn.CrossEntropyLoss(),
                                  amp_dtype="bfloat16")
    rs = np.random.RandomState(0)
    imgs = P.to_tensor(rs.rand(batch, 3, img, img).astype(np.float32))
    labels = P.to_tensor(rs.randint(0, 8, (batch,)), "int32")
    lowered = step.lower(imgs, labels)
    jaxpr = names = None
    if getattr(step, "_step_fn", None) is not None:
        import jax.numpy as jnp

        placed, _ = step._place_batch((imgs, labels), batch_axis=0)
        s = step._state
        lr = jnp.asarray(step.optimizer.get_lr(), jnp.float32)
        jaxpr = jax.make_jaxpr(step._step_fn)(
            s["params"], s["opt"], s["buffers"], s["key"], lr, *placed)
        names = _flat_arg_names(step, placed)
    return lowered, jaxpr, names


def _paged_decode_step_program(slots=2, pages_per_seq=4, page_size=8,
                               chunk=4):
    """The continuous-batching engine's ragged paged decode program
    (``InferenceEngine._decode_program``) at a tiny proxy shape — the
    serving hot step (ISSUE 8).  Budgeting its layout/transpose counts
    means a relayout regression in the paged-attention path fails CI
    before any hardware run.  Returns ``(lowered, closed_jaxpr)``."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    max_len = page_size * pages_per_seq
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=4, max_seq_len=max_len)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = InferenceEngine(model, EngineConfig(
        page_size=page_size, max_slots=slots, decode_chunk=chunk,
        max_seq_len=max_len))
    decode = eng._decode_program(chunk)
    args = (eng._params, eng._buffers, eng._k_pools, eng._v_pools,
            [], [],
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, eng.max_pages_per_seq), jnp.int32),
            jnp.zeros((slots,), jnp.int32))
    lowered = decode.lower(*args)
    jaxpr = jax.make_jaxpr(decode)(*args)
    return lowered, jaxpr


def _quantized_decode_step_program(slots=2, pages_per_seq=4, page_size=8,
                                   chunk=4):
    """The SAME paged decode proxy under BOTH quantized tiers
    (``weight_precision='int8'`` + ``kv_precision='int8'`` — ISSUE 12):
    its budget pins the quantized hot step's layout counts AND the
    PT406 dequant placement (every int8→float dequant traced inside the
    scan body, none hoisted).  Returns
    ``(lowered, closed_jaxpr, None, meta)`` where meta carries the
    expected dequant count (quantized weights + K/V page dequants per
    layer) for the derived ``pt406_dequant_deficit``."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    max_len = page_size * pages_per_seq
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=4, max_seq_len=max_len)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = InferenceEngine(model, EngineConfig(
        page_size=page_size, max_slots=slots, decode_chunk=chunk,
        max_seq_len=max_len, weight_precision="int8",
        kv_precision="int8"))
    decode = eng._decode_program(chunk)
    args = (eng._params, eng._buffers, eng._k_pools, eng._v_pools,
            eng._k_scales, eng._v_scales,
            jnp.zeros((slots,), jnp.int32),
            jnp.zeros((slots, eng.max_pages_per_seq), jnp.int32),
            jnp.zeros((slots,), jnp.int32))
    lowered = decode.lower(*args)
    jaxpr = jax.make_jaxpr(decode)(*args)
    # per scan step: one dequant per quantized weight + one per K and V
    # page gather per layer
    meta = {"expected_s8_dequants": len(eng._wq_meta) + 2 * eng._layers}
    return lowered, jaxpr, None, meta


def _cached_prefill_step_program(slots=2, pages_per_seq=8, page_size=8,
                                 tail_bucket=8, prefix_pages=2):
    """The prefix cache's WARM tail-prefill program
    (``InferenceEngine._cached_prefill_program``, ISSUE 13) at a tiny
    proxy shape: prefix capacity bucketed to `prefix_pages` (power of
    two), tail bucketed to `tail_bucket`.  Budgeting it pins the warm
    path's layout counts AND its PT402 surface — a per-cached-length
    recompile hazard (shapes leaking the actual shared length instead
    of the bucket) is exactly the regression this program exists to
    catch.  Returns ``(lowered, closed_jaxpr)``."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(0)
    max_len = page_size * pages_per_seq
    cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=1,
                    num_heads=4, max_seq_len=max_len)
    model = GPTForCausalLM(cfg)
    model.eval()
    eng = InferenceEngine(model, EngineConfig(
        page_size=page_size, max_slots=slots,
        prefill_bucket=tail_bucket, max_seq_len=max_len))
    cpre = eng._cached_prefill_program(tail_bucket, prefix_pages)
    args = (eng._params, eng._buffers,
            jnp.zeros((1, tail_bucket), jnp.int32),
            jnp.zeros((1,), jnp.int32),
            jnp.zeros((prefix_pages,), jnp.int32),
            jnp.asarray(page_size * prefix_pages, jnp.int32),
            eng._k_pools, eng._v_pools)
    lowered = cpre.lower(*args)
    jaxpr = jax.make_jaxpr(cpre)(*args)
    return lowered, jaxpr


def _audit_lowered(name: str, lowered, jaxpr=None, arg_names=None):
    """All three views of one lowered program -> (violations, metrics).
    A missing view is a PT400 — an absent metric is invisible to the
    budget diff (only present metrics are judged), so partial blindness
    must fail the gate loudly, not pass quietly."""
    text = lowered.as_text()
    opt = ""
    pre = []
    if jaxpr is None:
        pre.append(Violation(f"perf:{name}", 0, "PT400",
                             "jaxpr view unavailable — PT402/PT404/"
                             "PT405 metrics not audited for this "
                             "program"))
    try:
        opt = lowered.compile().as_text()
    except Exception as e:
        # compiled view is additive evidence — keep the text/jaxpr audit
        # alive on backends that refuse to compile the proxy shape, but
        # surface the blind spot
        pre.append(Violation(f"perf:{name}", 0, "PT400",
                             f"compile failed ({type(e).__name__}) — "
                             f"optimized-HLO view unavailable"))
    v, m = audit_program_texts(name, closed_jaxpr=jaxpr,
                               stablehlo_text=text, opt_hlo_text=opt,
                               arg_names=arg_names)
    return pre + v, m


def _audit_op_table(limit=None):
    """PT4xx sweep over the manifest's unary/binary conformance surface
    (tracing only — the jaxpr carries everything these rules need for
    elementwise ops)."""
    import jax

    from .hlo_audit import iter_op_callables

    violations, totals = [], {
        "pt401_transpose_count": 0, "pt402_weak_inputs": 0,
        "pt404_allgather_reduce": 0, "pt404_chained_collectives": 0,
        "pt405_host_syncs": 0, "pt405_loop_host_syncs": 0,
    }
    for name, fn, args in iter_op_callables(limit=limit):
        if fn is None:
            violations.append(Violation(
                f"perf:op:{name}", 0, "PT400",
                "op does not resolve — cannot audit"))
            continue
        try:
            jaxpr = jax.make_jaxpr(fn)(*args)
        except Exception as e:
            jaxpr = None
            if len(args) == 2:
                # ternary-shaped "binary" ops (lerp): scalar third
                # operand, mirroring the Layer-3 sweep's retry
                from .hlo_audit import _resolve_op

                import paddle_tpu as P
                from paddle_tpu.core.tensor import Tensor

                op = _resolve_op(name)

                def traced3(a, b, _op=op):
                    r = _op(P.to_tensor(a), P.to_tensor(b), 0.5)
                    return r._value if isinstance(r, Tensor) else r
                try:
                    jaxpr = jax.make_jaxpr(traced3)(*args)
                except Exception:
                    jaxpr = None
            if jaxpr is None:
                violations.append(Violation(
                    f"perf:op:{name}", 0, "PT400",
                    f"trace failed ({type(e).__name__})"))
                continue
        totals["pt402_weak_inputs"] += weak_input_count(jaxpr)
        for k, v in collective_patterns(jaxpr).items():
            totals[k] += v
        for k, v in host_sync_counts(jaxpr).items():
            totals[k] += v
        n_t = sum(1 for eqn, _ in _walk_eqns_ctx(jaxpr)
                  if eqn.primitive.name == "transpose")
        totals["pt401_transpose_count"] += n_t
        if n_t:
            violations.append(Violation(
                f"perf:op:{name}", 0, "PT401",
                f"{n_t} transpose(s) in an elementwise op's trace"))
    return violations, totals


def _audit_call_sites(repo_root=None, roots=None):
    """The stdlib-only program: PT402 call-site hazards across the
    tree."""
    from .runner import DEFAULT_ROOTS, iter_python_files

    repo_root = repo_root or _REPO
    violations = []
    for rel in iter_python_files(repo_root, roots or DEFAULT_ROOTS):
        with open(os.path.join(repo_root, rel), encoding="utf-8") as f:
            source = f.read()
        try:
            tree = ast.parse(source)
        except SyntaxError:
            continue  # the ast layer owns PT000 for unparsable files
        violations.extend(call_site_hazards(source, rel, tree=tree))
    return violations, {"pt402_call_site_hazards": len(violations)}


def _ensure_cpu_env():
    """Pin the audit environment to CPU + 8 virtual devices — the same
    mesh the test conftest forces. The optimized-HLO metrics are only
    byte-stable within one backend config, so the CLI and the pytest
    gate must compile under the same one or the committed budget cannot
    satisfy both.

    Something may have imported jax already, so env vars alone do not
    stick: when the config already points at a non-CPU platform, route
    through ``backend_guard.force_cpu_mesh`` (overrides the captured
    config, clears stale backends). A jax already on CPU
    (the pytest path — conftest set the 8-device mesh) is left alone:
    force-clearing live backends mid-suite would invalidate arrays."""
    if "jax" not in sys.modules:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8"
            ).strip()
        # fall through: the config may still have been pinned
    import jax

    platforms = getattr(jax.config, "jax_platforms", None) or \
        os.environ.get("JAX_PLATFORMS", "")
    if platforms and not str(platforms).startswith("cpu"):
        try:
            from ..backend_guard import force_cpu_mesh
        except ImportError:
            # standalone package load (pt_lint's jax-free fast path
            # loads analysis/ as top-level `pt_analysis`)
            from paddle_tpu.backend_guard import force_cpu_mesh

        force_cpu_mesh(8)


def audit_perf(programs=DEFAULT_PROGRAMS, repo_root=None):
    """Run the perf audit over the named representative programs.

    Returns ``(violations, metrics)`` where metrics is
    ``{program_name: {metric: number}}`` — the budget unit. Program
    build failures surface as PT400 findings with an empty metrics
    entry (a blind audit must fail the gate loudly, not pass quietly)."""
    if set(programs) - {"call_sites"}:
        _ensure_cpu_env()
    violations, metrics = [], {}
    for prog in programs:
        if prog == "call_sites":
            v, m = _audit_call_sites(repo_root)
        elif prog in ("train_step", "sharded_train_step",
                      "swin_train_step", "decode_step",
                      "paged_decode_step", "quantized_decode_step",
                      "cached_prefill_step"):
            full = {"train_step": "gpt125m_train_step",
                    "sharded_train_step": "gpt_sharded_train_step",
                    "swin_train_step": "swin_train_step",
                    "decode_step": "gpt_decode_step",
                    "paged_decode_step": "gpt_paged_decode_step",
                    "quantized_decode_step":
                        "gpt_quantized_decode_step",
                    "cached_prefill_step":
                        "gpt_cached_prefill_step"}[prog]
            build = {"train_step": _train_step_program,
                     "sharded_train_step": _sharded_train_step_program,
                     "swin_train_step": _swin_train_step_program,
                     "decode_step": _decode_step_program,
                     "paged_decode_step": _paged_decode_step_program,
                     "quantized_decode_step":
                         _quantized_decode_step_program,
                     "cached_prefill_step":
                         _cached_prefill_step_program}[prog]
            try:
                out = build()
            except Exception as e:
                v, m = [Violation(f"perf:{full}", 0, "PT400",
                                  f"{prog} failed to build/lower "
                                  f"({type(e).__name__}: "
                                  f"{str(e)[:80]})")], {}
            else:
                lowered, jaxpr = out[0], out[1]
                names = out[2] if len(out) > 2 else None
                prog_meta = out[3] if len(out) > 3 else {}
                v, m = _audit_lowered(full, lowered, jaxpr,
                                      arg_names=names)
                if prog == "quantized_decode_step" and m \
                        and jaxpr is not None:
                    # PT406: every int8 dequant must be TRACED inside
                    # the scan body — hoisted > 0 means the weights
                    # stream full-precision per step; the deficit
                    # (expected minus in-loop, floored at 0) catches
                    # the opposite failure, the tier silently not
                    # quantizing at all (fewer dequants would read as
                    # an "improvement" under a plain ceiling)
                    m.update(dequant_placement(jaxpr))
                    expected = prog_meta.get("expected_s8_dequants", 0)
                    m["pt406_dequant_deficit"] = max(
                        0, expected - m["pt406_dequant_in_loop_count"])
                    if m["pt406_dequant_hoisted_count"]:
                        v.append(Violation(
                            f"perf:{full}", 0, "PT406",
                            f"{m['pt406_dequant_hoisted_count']} "
                            f"int8 dequant(s) traced OUTSIDE the "
                            f"decode scan body — the weight stream "
                            f"is full-precision per step"))
                    if m["pt406_dequant_deficit"]:
                        v.append(Violation(
                            f"perf:{full}", 0, "PT406",
                            f"only "
                            f"{m['pt406_dequant_in_loop_count']} of "
                            f"{expected} expected int8 dequants in "
                            f"the scan body — a quantized tier is "
                            f"silently inactive"))
                if prog == "sharded_train_step" and m and names:
                    # per-parameter grad sync or bust: the raw counts
                    # only gate INCREASES (budget = ceiling), but the
                    # fused-barrier regression LOWERS them — this
                    # derived deficit (params minus scheduled additive
                    # collectives, floored at 0) rises instead, and its
                    # committed budget of 0 makes `--perf --check` fail
                    n_params = sum(1 for x in names
                                   if x.startswith("param."))
                    sync = m.get("pt404_opt_all_reduce_count", 0) + \
                        m.get("pt404_opt_reduce_scatter_count", 0)
                    m["pt404_grad_sync_deficit"] = max(
                        0, n_params - sync)
                    if m["pt404_grad_sync_deficit"]:
                        v.append(Violation(
                            f"perf:{full}", 0, "PT404",
                            f"only {sync} additive collective(s) for "
                            f"{n_params} parameters — grad sync has "
                            f"been fused toward a barrier (overlap "
                            f"lost)"))
            metrics[full] = m
            violations.extend(v)
            continue
        elif prog == "op_table":
            v, m = _audit_op_table()
        else:
            raise ValueError(f"unknown perf program {prog!r}; expected "
                             f"one of {FULL_PROGRAMS}")
        metrics[prog] = m
        violations.extend(v)
    violations.sort(key=Violation.sort_key)
    return violations, metrics


def metrics_to_static_rows(metrics: dict) -> list:
    """Budget metrics -> perf_gate-compatible metric rows
    (``static.<program>.<metric>``, all lower-better: every PT4xx
    number is a cost)."""
    rows = []
    for prog in sorted(metrics):
        for name in sorted(metrics[prog]):
            rows.append({"metric": f"static.{prog}.{name}",
                         "value": metrics[prog][name],
                         "unit": "mbytes" if name.endswith("_mbytes")
                         else "count",
                         "lower_better": True})
    return rows


# ----------------- MFU forensics (tools/hlo_audit shim) -----------------

_DOT = re.compile(
    r"stablehlo\.dot_general[^\n]*:\s*\(tensor<[0-9x]+x(\w+)>,\s*"
    r"tensor<[0-9x]+x(\w+)>\)\s*-> tensor<([0-9x]+)x(\w+)>")
_TRANSPOSE_FULL = re.compile(
    r"stablehlo\.transpose[^\n]*?dims = \[([\d, ]+)\][^\n]*"
    r"-> tensor<([0-9x]+)x(\w+)>")


def audit_hlo(hlo_text: str, min_numel: int = 1 << 14):
    """Bucket dots by OPERAND dtype and big transposes by moved bytes —
    the chip-free MFU forensics previously in ``tools/hlo_audit.py``
    (that file is now a thin shim over this function, so the tool and
    the analysis package cannot drift).

    bf16 operands with f32 accumulation (``preferred_element_type``) is
    the full-rate MXU mode — a dot is only a quarter-rate problem when
    an OPERAND is f32."""
    dots = {"bf16_operands": 0, "f32_operands": 0, "mixed": 0, "other": 0}
    f32_dot_shapes = []
    for m in _DOT.finditer(hlo_text):
        lhs, rhs, dims, _ = m.groups()
        if lhs == rhs == "bf16":
            key = "bf16_operands"
        elif lhs == rhs == "f32":
            key = "f32_operands"
        elif {lhs, rhs} <= {"bf16", "f32"}:
            key = "mixed"
        else:
            key = "other"
        dots[key] += 1
        if key != "bf16_operands" and _numel(dims) >= min_numel:
            f32_dot_shapes.append(f"{lhs}x{rhs}->[{dims}]")
    transposes = []
    for m in _TRANSPOSE_FULL.finditer(hlo_text):
        perm, dims, dt = m.groups()
        n = _numel(dims)
        if n >= min_numel:
            transposes.append(
                {"dtype": dt, "shape": dims,
                 "perm": perm.replace(" ", ""),
                 "mbytes": round(n * _ITEMSIZE.get(dt, 4) / 2**20, 2)})
    transposes.sort(key=lambda t: -t["mbytes"])
    return {"dot_counts": dots,
            "big_non_bf16_dots": f32_dot_shapes[:20],
            "big_transposes": transposes[:20],
            "transpose_mbytes_total": round(
                sum(t["mbytes"] for t in transposes), 1)}


def train_step_hlo(batch=4, seq=1024, layers=2):
    """Lower the GPT train step at bench dtypes (reduced batch/depth)
    and return its PRE-OPTIMIZATION StableHLO text. Pre-optimization is
    the honest view for dtypes: XLA:CPU's optimized HLO legalizes every
    bf16 dot to f32 (no bf16 units on CPU), which says nothing about
    the TPU program."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from memory_report import _build_lowered
    finally:
        sys.path.pop(0)
    lowered, _ = _build_lowered(
        dict(vocab_size=50304, hidden_size=768, num_layers=layers,
             num_heads=12, max_seq_len=seq, fused_head_ce=True,
             dropout=0.0),
        batch, seq)
    return lowered.as_text()
