"""Median gap between consecutive starts of the step program on the fullest
device, from the trace's `XLA Modules` line."""
import statistics

from harness import tracing


def read(run, spec):
    t, red = run["trace"], run["reduced"]
    mods = tracing.clip(red["devices"][t["fullest"]]["modules"], red["window"])
    total = tracing.sum_by_name(mods)
    if not total:
        return None
    step = max(total, key=total.get)            # the program that ran longest
    starts = sorted(s for n, s, _ in mods if n == step)
    if len(starts) < 2:
        return None
    return statistics.median(b - a for a, b in zip(starts, starts[1:])) / 1e6
