"""Device time per step under one of the program's scopes.

The program puts `jax.named_scope`s around its work (`train_step.update`,
module paths such as `.../h.3/attn`, `head_ce`, `flash.layout`) and keeps, at
compile time, `{HLO instruction name: op_name}` for each labelled program
(`paddle_tpu.observability.xla_cost.program_ledger`).  A device event of the
trace carries the instruction's name and nothing else, so this reader joins
the two: events of the fullest device inside the traced window, containers
left out, each looked up by `tracing.short_name`; the device time of those
whose op_name matches the metric's `"scope"` regex, over the cell's traced
steps, in ms.  One earlier line, `scopes {...}`, gives ms per step for every
scope (top 20), for the ten instruction families with most time the scopes
they sit under, and the instructions with most time that joined to none.

Returns None where there is nothing to read FROM (a program without the
ledger, as the parent of PR 26; an op table the backend did not give); 0.0
where the table is there and no device time lies under the scope.
"""
import re

from harness import common, tracing

SCOPED = r"(^|/)train_step\."    # an op_name with one of the step's scopes in it


def program_ledger(program):
    """The running program's ledger entry for `program`, or None where the
    program keeps none."""
    try:
        from paddle_tpu.observability import xla_cost
    except ImportError:
        return None
    fn = getattr(xla_cost, "program_ledger", None)
    return fn(program) if fn is not None else None


def joined(run, program):
    """[(op_name or None, instruction name, duration_ns)] of the leaf device
    events of the traced window (None: the event joined no row of the op
    table), or None without a ledger entry.  Kept on `run`: eight metrics
    read it."""
    key = "joined:" + program
    if key not in run:
        entry = program_ledger(program)
        rows = None
        if entry is not None:
            # a trace's event names are cut to 80 characters (short_name)
            ops = {k[:80]: v for k, v in (entry.get("ops") or {}).items()}
            t = run["trace"]
            names = [(tracing.short_name(n), d)
                     for n, _, d in t["events"][t["fullest"]]
                     if not tracing.is_container(n)]
            rows = [(ops.get(name), name, d) for name, d in names]
            _log_scopes(run, entry, rows)
        run[key] = rows
    return run[key]


def _scope_of(op_name):
    """`jit(step)/train_step.loss/transpose(jvp(GPT))/gpt/h.3/attn/dot_general`
    -> `train_step.loss.bwd:GPT/gpt/h.N/attn`: the stage, the direction where
    the stage is differentiated, then up to four components of the module
    path with the layer indices folded and the primitive left off."""
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


def _log_scopes(run, entry, rows):
    steps = run["cell"]["trace"]["steps"]
    by_scope, unmatched, unnamed, by_family = {}, {}, {}, {}
    for op, name, d in rows:
        family = tracing.family_name(name)
        if op is None:
            unmatched[family] = unmatched.get(family, 0) + d
        elif not op:
            unnamed[family] = unnamed.get(family, 0) + d
        else:
            scope = _scope_of(op)
            by_scope[scope] = by_scope.get(scope, 0) + d
            under = by_family.setdefault(family, {})
            under[scope] = under.get(scope, 0) + d

    def top(acc, n):
        return {k: round(v / steps / 1e6, 3) for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]}

    # XLA's own names for the device-op list, each with the scopes it sits
    # under: a fusion carries ONE op_name, that of the op XLA built it around
    families = sorted(by_family, key=lambda f: -sum(by_family[f].values()))[:10]
    common.log("scopes", {"ms_per_step": top(by_scope, 20),
                          "families": {f: top(by_family[f], 4) for f in families},
                          "no_op_name": top(unnamed, 8),
                          "not_in_op_table": top(unmatched, 8),
                          "op_table_rows": len(entry["ops"])
                          if entry.get("ops") is not None else None})


def read(run, spec):
    rows = joined(run, spec.get("program", "train_step"))
    if not rows or all(op is None for op, _, _ in rows):
        return None
    rx = re.compile(spec["scope"])
    under = sum(d for op, _, d in rows if op and rx.search(op))
    return under / run["cell"]["trace"]["steps"] / 1e6
