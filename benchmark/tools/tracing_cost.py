#!/usr/bin/env python3
"""What the program's own tracing costs when it is on: the cell's window with
the span tracer enabled (`observability.trace.enable()`: every span of the
train step's host path is buffered and mirrored into a
`jax.profiler.TraceAnnotation`), to set beside a plain run of the same cell
and seed.  Run by hand on the chip, never by the driver:

    python3 benchmark/tools/tracing_cost.py --workload <cell> --seed <n> [--seconds 40]

The last line is the cell's result line (`train_tokens_per_s` with tracing
on); the line before it counts the spans the window produced.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    from paddle_tpu.observability import trace

    trace.enable()
    line = run.run_cell(a.workload, a.seed, a.seconds, 0, a.rehearse)
    tracer = trace.get_tracer()
    names = {}
    for e in trace.events():
        names[e["name"]] = names.get(e["name"], 0) + 1
    print("tracing_on", {"events": tracer.added(), "dropped": tracer.dropped(),
                         "by_name": dict(sorted(names.items(),
                                                key=lambda kv: -kv[1])[:8])},
          flush=True)
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
