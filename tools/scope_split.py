"""scope_split — one traced benchmark run, its device time split below a
metric's scope.

A per-layer metric such as `moe_dispatch_ms.train` is ONE number for a
regex over the program's scopes.  This runs the benchmark's own traced
command and splits what `benchmark/readers/scope_ms.joined` joined — by
the named scopes the regex captures, by phase (forward; `replay`: what a
recomputed block runs again, `rematted_computation` in the op_name;
`bwd`: the true backward, `transpose(` without it) and by the primitive
at the op_name's end (a TPU fusion carries ONE op_name, and every
gather or scatter is a `fusion` to the trace, so the instruction family
says nothing there).  Outside the benchmark: it judges nothing, it prints.

    python tools/scope_split.py '/moe\\.(route|sort|combine)(/|$)' \\
        --workload trinity-mini-ep8.train.seq8192 --seed 3400000011 --seconds 40
    python tools/scope_split.py --root <another checkout> ...   # its parent
    JAX_PLATFORMS=cpu python tools/scope_split.py '/attn' --rehearse --workload ...

Earlier lines are the benchmark's; then `split {...}` (ms a step: `sum`,
`by_scope_phase`, `rows` = [scope, phase, primitive, ms] over 0.05 ms) and
the benchmark's result line last.  Exits as the benchmark does (1 without
a TPU unless `--rehearse`).
"""
from __future__ import annotations

import argparse
import os
import re
import sys


def phase_of(op_name):
    """`observability.xla_cost.phase_of` of the checkout that is run (one
    from PR 37 on): the program ledger names a buffer's phase with it."""
    from paddle_tpu.observability.xla_cost import phase_of as of

    return of(op_name)


def split(rows, scope, steps, floor_ms=0.05):
    """rows: [(op_name or None, instruction, ns)] as `scope_ms.joined`
    gives them.  {"sum", "by_scope_phase", "rows"} in ms a step for the
    op_names `scope` matches; a row's scope is the regex's first group
    (the whole match where it has none)."""
    rx = re.compile(scope)
    cells = {}
    for op, _, ns in rows:
        m = rx.search(op) if op else None
        if m:
            key = (m.group(1) if rx.groups else m.group(0), phase_of(op),
                   op.rsplit("/", 1)[-1])
            cells[key] = cells.get(key, 0.0) + ns / steps / 1e6
    by = {}
    for (name, phase, _), ms in cells.items():
        by[f"{name}.{phase}"] = by.get(f"{name}.{phase}", 0.0) + ms
    return {"sum": round(sum(cells.values()), 3),
            "by_scope_phase": {k: round(v, 3) for k, v in sorted(by.items())},
            "rows": [[*k, round(ms, 3)] for k, ms in
                     sorted(cells.items(), key=lambda kv: -kv[1])
                     if ms >= floor_ms]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("scope", help="regex over op_names, as a metric file's")
    ap.add_argument("--root", default=os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), help="the checkout to run")
    a, rest = ap.parse_known_args(argv)
    root = os.path.abspath(a.root)
    sys.path[:0] = [os.path.join(root, "benchmark"), root]
    os.chdir(root)
    import run as bench_run
    from harness import common

    reader = common.load_module("readers", "scope_ms")
    logged = reader._log_scopes

    def log_and_split(run, entry, rows):
        logged(run, entry, rows)
        common.log("split", split(rows, a.scope, run["cell"]["trace"]["steps"]))

    reader._log_scopes = log_and_split
    return bench_run.main(rest + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
