"""Layer 3: jaxpr/HLO audit (rules PT201/PT202/PT203).

Where Layers 1–2 read source, this layer reads the *program*: trace a
callable to its jaxpr (or lower it to StableHLO) and flag the three
compiled-program sins that silently cap a TPU step:

  PT201  host transfer      a callback/infeed/outfeed primitive inside
                            a traced function — every call is a device
                            round-trip hidden in what looks like one
                            fused XLA program
  PT202  f64 promotion      an op whose inputs are ≤f32 but whose
                            output is f64 — doubles bytes moved and
                            falls off the MXU entirely
  PT203  un-donated buffer  a train-step argument big enough to matter
                            (params/opt state) lowered without
                            ``tf.aliasing_output``/buffer donation —
                            doubles peak memory for the step

Entry points:
  * ``audit_jaxpr(closed_jaxpr, where)``      — walk eqns recursively
  * ``audit_callable(fn, *args, where=...)``  — make_jaxpr + audit
  * ``audit_lowered_donation(text, where)``   — PT203 on StableHLO text
  * ``audit_op_table(...)``                   — trace the exported op
    surface from OPS_MANIFEST.json conformance kinds (unary/binary)
  * ``audit_train_step(...)``                 — the hybrid GPT train
    step via tools/memory_report (slow: builds + lowers a real model)

jax imports are function-local: importing this module costs nothing, so
`tools/pt_lint.py` can expose the layer behind a flag without paying a
jax import for the AST-only fast path.
"""
from __future__ import annotations

import os
import re
import sys

from .report import Violation

__all__ = [
    "audit_jaxpr", "audit_callable", "audit_lowered_donation",
    "audit_op_table", "audit_train_step", "RULE_IDS",
    "HOST_TRANSFER_PRIMITIVES",
]

RULE_IDS = ("PT200", "PT201", "PT202", "PT203")

HOST_TRANSFER_PRIMITIVES = {
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_local_array_to_global",
}

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _walk_eqns(jaxpr):
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs
    (cond/scan/while/pjit bodies)."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in inner.eqns:
        yield eqn
        for param in eqn.params.values():
            for sub in _iter_subjaxprs(param):
                yield from _walk_eqns(sub)


def _iter_subjaxprs(param):
    import jax.core as jcore

    closed = getattr(jcore, "ClosedJaxpr", ())
    raw = getattr(jcore, "Jaxpr", ())
    if isinstance(param, (closed, raw)):
        yield param
    elif isinstance(param, (list, tuple)):
        for p in param:
            yield from _iter_subjaxprs(p)


def _dtype_of(var):
    aval = getattr(var, "aval", None)
    return getattr(aval, "dtype", None)


def audit_jaxpr(closed_jaxpr, where: str) -> list:
    """PT201 + PT202 over one traced program."""
    out = []
    for eqn in _walk_eqns(closed_jaxpr):
        name = eqn.primitive.name
        if name in HOST_TRANSFER_PRIMITIVES:
            out.append(Violation(
                where, 0, "PT201",
                f"host-transfer primitive `{name}` inside traced "
                f"program — device round-trip per call"))
        in_dtypes = {str(d) for d in map(_dtype_of, eqn.invars)
                     if d is not None}
        if any("float64" in str(_dtype_of(v)) for v in eqn.outvars
               if _dtype_of(v) is not None) and \
                "float64" not in in_dtypes:
            out.append(Violation(
                where, 0, "PT202",
                f"primitive `{name}` promotes ≤f32 inputs to a "
                f"float64 output — silent f64 promotion"))
    return out


def audit_callable(fn, *args, where: str, enable_x64: bool = True,
                   **kwargs) -> list:
    """Trace `fn(*args)` and audit the jaxpr. x64 is enabled during the
    trace by default: without it jax silently *downcasts* f64, so the
    promotion this rule exists to catch is unobservable."""
    import jax

    try:
        if enable_x64:
            with jax.enable_x64(True):
                jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
        else:
            jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    except Exception as e:  # tracing failed — report, don't crash the lint
        return [Violation(
            where, 0, "PT200",
            f"trace failed ({type(e).__name__}) — program could not "
            f"be audited")]
    return audit_jaxpr(jaxpr, where)


# --------------------------- PT203: donation ---------------------------

_ALIAS_RE = re.compile(r"tf\.aliasing_output|jax\.buffer_donor")
#     tensor<512x512xf32> / tensor<f32> — dims are digit groups, the
# dtype starts with a letter (`\w+` alone would eat "512x512xf32":
# `x` is a word character)
_TENSOR_RE = re.compile(r"tensor<(?:(\d+(?:x\d+)*)x)?([a-z]\w*)>")


def audit_lowered_donation(stablehlo_text: str, where: str,
                           min_mbytes: float = 1.0) -> list:
    """PT203: big @main arguments with no aliasing/donation marker.

    Only arguments at least `min_mbytes` matter — activations/ids ride
    through undonated by design; params and optimizer state must not.

    Parsing splits the @main signature on `%argN:` tokens rather than
    regexing one attr dict: sharding attrs contain *nested braces
    inside quoted strings* (``mhlo.sharding = "{replicated}"``), which
    a naive ``\\{[^}]*\\}`` silently truncates — exactly the kind of
    wrong-tool parse that once reported 0 donated args on a fully
    donated step."""
    out = []
    main = stablehlo_text.split("func.func public @main", 1)
    if len(main) < 2:
        return out
    header = main[1].split("->", 1)[0]
    itemsize = {"bf16": 2, "f16": 2, "f32": 4, "f64": 8, "i32": 4,
                "ui32": 4, "i64": 8, "i8": 1, "i1": 1}
    undonated_mb = 0.0
    n_undonated = 0
    chunks = re.split(r"%arg\d+:", header)[1:]
    for chunk in chunks:
        m = _TENSOR_RE.search(chunk)
        if m is None:
            continue
        dims, dt = m.groups()
        numel = 1
        for d in (dims or "").split("x"):
            if d.strip():
                numel *= int(d)
        mb = numel * itemsize.get(dt, 4) / 2**20
        if mb < min_mbytes:
            continue
        if not _ALIAS_RE.search(chunk):
            n_undonated += 1
            undonated_mb += mb
    if n_undonated:
        out.append(Violation(
            where, 0, "PT203",
            f"{n_undonated} train-step argument(s) ≥{min_mbytes} MiB "
            f"lowered without buffer donation "
            f"({undonated_mb:.1f} MiB un-donated — doubles peak "
            f"memory)"))
    return out


# --------------------------- op-table audit ---------------------------


def _manifest_conformance_ops(manifest_path=None):
    """(name, kind) for every manifest op with a unary/binary
    conformance sweep entry — the machine-true 'exported op table'."""
    import json

    path = manifest_path or os.path.join(_REPO, "OPS_MANIFEST.json")
    with open(path) as f:
        manifest = json.load(f)
    out = []
    for entry in manifest.get("ops", []):
        conf = entry.get("conformance") or {}
        if entry.get("present") and conf.get("kind") in ("unary",
                                                         "binary"):
            out.append((entry["name"], conf["kind"]))
    return sorted(out)


def _resolve_op(name):
    import paddle_tpu as P

    for mod in (P, P.nn.functional, P.linalg, P.fft, P.signal, P.sparse,
                P.geometric, P.incubate.nn.functional, P.vision.ops):
        obj = getattr(mod, name, None)
        if callable(obj):
            return obj
    return None


def iter_op_callables(limit: int | None = None, manifest_path=None):
    """Yield ``(name, traced_fn_or_None, args)`` for every manifest op
    with a unary/binary conformance sweep — the shared program source
    for this layer's correctness audit and the perf layer's op-table
    sweep (one place decides what 'the exported op surface' means).

    ``traced_fn`` is a plain jax-traceable callable using the sweep's
    own domain-correct input factories; ``None`` when the op does not
    resolve."""
    import jax.numpy as jnp

    import paddle_tpu as P
    from paddle_tpu.core.tensor import Tensor

    sys.path.insert(0, os.path.join(_REPO, "tests"))
    try:
        import conformance_tables
    finally:
        sys.path.pop(0)

    ops = _manifest_conformance_ops(manifest_path)
    if limit is not None:
        ops = ops[:limit]

    def unwrap(r):
        if isinstance(r, (tuple, list)):
            return [unwrap(x) for x in r]
        return r._value if isinstance(r, Tensor) else r

    for name, kind in ops:
        fn = _resolve_op(name)
        table = conformance_tables.UNARY_OPS if kind == "unary" \
            else conformance_tables.BINARY_OPS
        spec = table.get(name)
        if fn is None or spec is None:
            yield name, None, ()
            continue
        shape = (3, 4)
        if kind == "unary":
            # UNARY_OPS rows carry the sweep's own domain-correct input
            # factory — e.g. acosh needs inputs > 1
            try:
                x = jnp.asarray(spec[0](shape))
            except Exception:
                x = jnp.ones(shape, jnp.float32)

            def traced(a, _fn=fn):
                return unwrap(_fn(P.to_tensor(a)))
            args = (x,)
        else:
            x = jnp.asarray(
                conformance_tables._pos(shape))  # positive: safe for
            # divide/pow/log-family binary domains

            def traced(a, b, _fn=fn):
                return unwrap(_fn(P.to_tensor(a), P.to_tensor(b)))
            args = (x, x + 0.5)
        yield name, traced, args


def audit_op_table(limit: int | None = None, manifest_path=None) -> list:
    """Trace every conformance-swept unary/binary op from the manifest
    with the sweep's own input factories and audit each jaxpr.

    Tracing only — no compilation, no execution — so the full ~200-op
    sweep is seconds, not minutes; still gated behind the slow tier /
    ``--jaxpr`` because it imports jax + paddle_tpu + the model stack."""
    import paddle_tpu as P
    from paddle_tpu.core.tensor import Tensor

    def unwrap(r):
        if isinstance(r, (tuple, list)):
            return [unwrap(x) for x in r]
        return r._value if isinstance(r, Tensor) else r

    out = []
    for name, traced, args in iter_op_callables(limit, manifest_path):
        if traced is None:
            out.append(Violation(
                "OPS_MANIFEST.json", 0, "PT200",
                f"op `{name}` claims a conformance sweep but does not "
                f"resolve — cannot audit"))
            continue
        found = audit_callable(traced, *args, where=f"op:{name}")
        if found and found[0].rule == "PT200" and len(args) == 2:
            # ternary-shaped "binary" ops (lerp: x, y, weight): retry
            # with a scalar third operand before reporting un-auditable
            fn = _resolve_op(name)

            def traced3(a, b, _fn=fn):
                return unwrap(_fn(P.to_tensor(a), P.to_tensor(b), 0.5))
            found = audit_callable(traced3, *args, where=f"op:{name}")
        out.extend(found)
    return out


def audit_train_step(batch: int = 2, seq: int = 128, layers: int = 1) -> list:
    """Lower the hybrid GPT train step (small proxy shape) and audit
    donation + host transfers + promotions. Heavy (model build + CPU
    lowering): slow tier / ``--jaxpr`` only."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    try:
        from memory_report import _build_lowered
    finally:
        sys.path.pop(0)

    lowered, _model = _build_lowered(
        dict(vocab_size=1024, hidden_size=64, num_layers=layers,
             num_heads=4, max_seq_len=seq, fused_head_ce=True,
             dropout=0.0),
        batch, seq)
    text = lowered.as_text()
    where = "train_step"
    out = audit_lowered_donation(text, where, min_mbytes=0.05)
    # host transfers / f64 in the lowered program: textual scan of the
    # StableHLO (the jaxpr is gone by this point; custom_call with a
    # callback target or any f64 tensor type is the same evidence)
    if re.search(r"stablehlo\.custom_call[^\n]*callback", text):
        out.append(Violation(
            where, 0, "PT201",
            "callback custom_call inside the lowered train step — "
            "host round-trip per step"))
    for m in re.finditer(r"tensor<[0-9x]*x?f64>", text):
        out.append(Violation(
            where, 0, "PT202",
            "f64 tensor inside the lowered train step — silent "
            "promotion"))
        break  # one finding per program is enough signal
    return out
