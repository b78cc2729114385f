#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process that holds the cell's chips.  It finds the cell, its
configuration, its driver, its per-layer metrics' readers and its kernel
families BY NAME in files of their own (see README.md), warms up exactly the
cell's shapes, measures for `--seconds`, checks what the timed path produced
against the plain reference, and prints as its last line the result.

`--rehearse` runs a tiny stand-in of the cell on whatever backend there is
and ends with {"rehearsed": true}, never a result.  The measured command has
no other switch: readings for limits, planted faults and kept traces are
`tools/calibrate.py`'s, which drives `run_cell` below.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

from harness import check, common  # noqa: E402
from harness.common import log  # noqa: E402


def _set(tree, dotted, value):
    keys = dotted.split(".")
    for k in keys[:-1]:
        tree = tree[k]
    tree[keys[-1]] = value


def cell_metrics(bench, cell_name):
    """(end-to-end names, per-layer entries) that this cell reports."""
    def has(entry):
        return "workloads" not in entry or cell_name in entry["workloads"]

    e2e = [m["name"] for m in bench["end_to_end"] if has(m)]
    per_layer = [m for m in bench["per_layer"]
                 if (cell_name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e)]
    return e2e, per_layer


def run_cell(workload, seed, seconds, trace, rehearse=False, plant=None,
             readings=False, keep_trace=None):
    """Drive one run and return its result line (a string), or with
    `rehearse` the line it would have printed.  `plant` (a fault broken into
    the timed path), `readings` (the control's and the faults' numbers on
    earlier lines) and `keep_trace` are for the tests and tools/calibrate.py."""
    cell, config, bench = common.load_cell(workload)
    if rehearse:            # the cell's own tiny stand-in, from its file
        for dotted, value in cell.get("rehearse", {}).items():
            _set({"cell": cell, "config": config}, dotted, value)

    import jax

    devices, peaks = common.require_chips(cell["chips"], rehearse)
    cache_dir = common.enable_compile_cache()
    log("run", {"jax": jax.__version__, "workload": workload, "seed": seed,
                "seconds": seconds, "trace": trace,
                "device_kind": devices[0].device_kind, "chips": len(devices),
                "compile_cache": cache_dir, "plant": plant})
    ctx = {"cell": cell, "config": config, "seed": seed, "seconds": seconds,
           "trace": bool(trace), "devices": devices, "peaks": peaks,
           "rehearse": rehearse, "readings": readings, "plant": plant}
    out = common.load_module("drivers", cell["driver"]).run(ctx)

    e2e_names, per_layer = cell_metrics(bench, workload)
    summary = None
    if trace:
        from harness import tracing

        red = out["tracer"].reduce(out["state"].get("program_spans", ()),
                                   keep=keep_trace, cpu_stand_in=rehearse)
        summary = tracing.summarise(red)
        calls = {}
        for n, _, d in summary["events"][summary["fullest"]]:
            if " custom-call(" in n:
                c = calls.setdefault(tracing.family_name(n), [0, 0.0])
                c[0] += 1
                c[1] += d / 1e9
        log("custom_calls", calls)     # Pallas kernels as the trace names them
        run = {"state": out["state"], "e2e": out["e2e"], "trace": summary,
               "reduced": red, "cell": cell, "config": config, "peaks": peaks}
        metrics = {}
        for m in per_layer:
            spec = common.load_json("metrics", m["name"] + ".json")
            value = common.load_module("readers", spec["reader"]).read(run, spec)
            if value is not None:       # nothing to read: leave the metric out
                metrics[m["name"]] = (float(value), m["unit"])
    else:
        metrics = {k: v for k, v in out["e2e"].items() if k in e2e_names}
    correct, _ = check.verdict(out["checks"])
    common.print_checks(out["checks"])
    return common.result_line(correct, out["attempted"], out["failed"], metrics,
                              devices, out["memory_peak_bytes"], out["checks"],
                              summary)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    line = run_cell(a.workload, a.seed, a.seconds, a.trace, a.rehearse)
    if a.rehearse:
        log("would_print", line)
        line = json.dumps({"rehearsed": True,
                           "correct": json.loads(line)["correct"]})
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
