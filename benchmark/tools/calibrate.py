#!/usr/bin/env python3
"""Readings from which the limits of `correct` are set, and faults planted
under the timed path.  Not the measured command: it drives `run.run_cell`,
so every reading goes through the same comparison and the same limits.

    python3 benchmark/tools/calibrate.py --workload <cell> --seed <n> [--seconds 2]
        sound program; earlier lines `readings ...` give, beside the program's
        numbers, the reference put in the program's place one precision below
        the stated one (control_fp8, control_int8) and with half of the batch
        left out (fault_half_batch), each with the verdict under the cell's limits
    ... --fault half_batch | state_unchanged
        the fault planted in the program itself; the last line's `correct`
        has to read false
    ... --trace 1 --keep-trace <file>      keep the .xplane.pb (tests/testdata)
    ... --rehearse                         tiny stand-in on the CPU
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
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args(argv)
    print(run.run_cell(a.workload, a.seed, a.seconds, a.trace, a.rehearse,
                       plant=a.fault, readings=a.fault is None,
                       keep_trace=a.keep_trace), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
