#!/usr/bin/env python3
"""Is a train cell's `memory_peak_bytes` the true peak?  Not the measured
command; run once on the chip per train cell, the result is in PERF.md.

The train driver reports the backend's `peak_bytes_in_use` plus its
`peak_bytes_reserved`, because on this TPU runtime the first counter leaves a
running program's temporaries out and the second is what the runtime reserves
for them.  This probe shows it on the chip: it builds the cell's step as the
driver does, runs it, prints the device's whole `memory_stats()` and the
compiler's `temp_size_in_bytes` of the step program, then holds a ballast array
of growing size beside the step and runs the step again.  If the sum is the
true peak, the step runs while ballast <= bytes_limit - live arrays -
temporaries and is refused (RESOURCE_EXHAUSTED) just past it; if the
temporaries needed no memory of their own, it would run until ballast reached
bytes_limit - live arrays.

    python3 benchmark/tools/memory_probe.py --workload <train cell> --seed <n>
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import run  # noqa: E402,F401  (puts benchmark/ and the repo on sys.path)
from harness import common, traffic  # noqa: E402
from harness.common import log  # noqa: E402

GB = 1e9
OFFSETS_GB = (-1.0, -0.5, -0.25, 0.25, 0.5, 1.0)   # around the predicted edge


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    a = ap.parse_args(argv)
    cell, config, _ = common.load_cell(a.workload)

    import jax.numpy as jnp

    devices, peaks = common.require_chips(cell["chips"], False)
    common.enable_compile_cache()
    train = common.load_module("drivers", cell["driver"])
    ctx = {"cell": cell, "config": config, "seed": a.seed, "devices": devices}
    step, P = train.build(ctx)

    def feed(i):
        ids, labels = traffic.train_batch(cell["job"], config["vocab_size"],
                                          a.seed, i)
        return P.to_tensor(ids, "int32"), P.to_tensor(labels, "int32")

    float(step(*feed(0)))
    float(step(*feed(1)))
    stats = dict(devices[0].memory_stats())
    log("memory_stats after two steps", stats)
    # the compiler's count for the same program (step.lower is public and the
    # same lowering as the call's, so the compile is a cache hit)
    temp = int(step.lower(*feed(0)).compile().memory_analysis().temp_size_in_bytes)
    live, limit = stats["bytes_in_use"], stats["bytes_limit"]
    edge = limit - live - temp
    log("probe", {"bytes_limit": limit, "live_bytes_in_use": live,
                  "peak_bytes_in_use": stats["peak_bytes_in_use"],
                  "step_program_temp_bytes": temp,
                  "predicted_largest_ballast": edge,
                  "largest_ballast_if_temporaries_were_free": limit - live})
    largest_ok = first_refused = None
    for k, off in enumerate(OFFSETS_GB):
        size = int(edge + off * GB)
        ballast = jnp.zeros((size,), jnp.uint8)
        ballast.block_until_ready()
        try:
            loss = float(step(*feed(2 + k)))
            largest_ok = size
            log("probe ran", {"ballast": size, "off_predicted_edge_GB": off,
                              "loss": loss, "peak_bytes_in_use": devices[0]
                              .memory_stats()["peak_bytes_in_use"]})
        except Exception as e:          # the runtime's refusal is the reading
            first_refused = size
            log("probe refused", {"ballast": size, "off_predicted_edge_GB": off,
                                  "error": str(e)[:600]})
            break
        finally:
            ballast.delete()
    # the step needs more than limit - first_refused and at most limit - largest_ok
    log("probe result", {
        "reported_peak_bytes": stats["peak_bytes_in_use"]
        + stats["peak_bytes_reserved"],
        "held_plus_compiler_temp_bytes": stats["peak_bytes_in_use"] + temp,
        "step_needs_at_most": None if largest_ok is None else limit - largest_ok,
        "step_needs_more_than": None if first_refused is None
        else limit - first_refused,
        "backend_counter_alone": stats["peak_bytes_in_use"]})
    return 0


if __name__ == "__main__":
    sys.exit(main())
