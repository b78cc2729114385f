"""The comparison that decides `correct`, and the faults its tests plant.

Each number compared has a limit of its own in the cell's file (`limits`),
set from readings that PERF.md lists.  The reference (benchmark/reference/)
runs only after the window has closed, the peak has been read and the
program's state is freed.
"""
from __future__ import annotations

import statistics

from harness import common, traffic, weights

FIRST_STEPS = 3


def worst_leaf_gap(prog, ref, counted=None):
    """Gap between the program's norm and the reference's, by the worst
    leaf, against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some gradients are all but zero)."""
    med = statistics.median(ref.values())
    names = list(ref) if counted is None else counted
    worst, at = 0.0, None
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], med, 1e-30)
        if gap > worst:
            worst, at = gap, n
    return worst, at


def counted_leaves(ref_grad_norms):
    """Leaves whose reference gradient is nought to rounding (under a
    thousandth of the median leaf's) move under Adam by round-off alone and
    are left out of the change."""
    med = statistics.median(ref_grad_norms.values())
    return [n for n, g in ref_grad_norms.items() if g >= 1e-3 * med]


def train_numbers(got, ref):
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    grad_gap, grad_at = worst_leaf_gap(got["grad_norms"], ref["grad_norms"])
    counted = counted_leaves(ref["grad_norms"])
    change_gap, change_at = worst_leaf_gap(got["change_norms"],
                                           ref["change_norms"], counted)
    return ({"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
             "change_norm_gap": change_gap},
            {"grad_at": grad_at, "change_at": change_at,
             "leaves_counted": len(counted), "leaves": len(ref["grad_norms"])})


def verdict(checks):
    """`correct`, and the numbers that fail it.  A number whose limit was
    never set (null in the cell's file) fails: an unproven cell cannot pass."""
    failing = [k for k, (v, lim) in checks.items()
               if lim is None or not v <= lim]
    return not failing, failing


def with_limits(numbers, limits, sane):
    checks = {k: (float(v), limits.get(k)) for k, v in numbers.items()}
    checks["sane"] = (0.0 if sane else 1.0, 0.0)
    return checks


def train_checks(ctx, got, sane):
    """Reference over the first three steps, then each number beside its
    limit.  `ctx["readings"]` (tools/calibrate.py) adds the control and the
    faults, put in the program's place and held to the same limits."""
    import functools

    ref_mod = common.load_module("reference", ctx["config"]["reference"])
    cfg, cell = ctx["config"], ctx["cell"]
    rows = cell["reference"]["rows_per_block"]
    p0 = weights.make(cfg, ctx["seed"], "float32")
    batches = [traffic.train_batch(cell["job"], cfg["vocab_size"], ctx["seed"], i)
               for i in range(FIRST_STEPS)]
    follow = functools.partial(
        ref_mod.train_readings, cfg, cfg["training"]["optimizer"], p0,
        leaves=weights.logical_leaves)
    ref = follow(batches, rows)
    numbers, where = train_numbers(got, ref)
    detail = [f"reference {{'losses': {ref['losses']}, 'program_losses': "
              f"{got['losses']}, 'numbers': {numbers}, 'where': {where}}}"]
    checks = with_limits(numbers, cell["limits"], sane)
    if ctx.get("readings"):
        half = [(i[: i.shape[0] // 2], l[: l.shape[0] // 2]) for i, l in batches]
        runs = {"control_fp8": follow(batches, rows, quant=ref_mod.fp8_fake_quant),
                "control_int8": follow(batches, rows, quant=ref_mod.int8_fake_quant),
                "fault_half_batch": follow(half, rows)}
        detail.append(f"readings program {numbers} correct={verdict(checks)[0]}")
        for name, r in runs.items():
            n = train_numbers(r, ref)[0]
            ok, failing = verdict(with_limits(n, cell["limits"], True))
            detail.append(f"readings {name} {n} correct={ok} failing={failing}")
    return checks, detail


def plant_train_fault(step, fault):
    """For the tests: break the timed path underneath the driver."""
    import jax
    import jax.numpy as jnp

    orig = step.__class__.__call__

    class Broken(step.__class__):
        def __call__(self, ids, labels):
            if fault == "half_batch":      # the mean taken over the rest
                n = ids.shape[0] // 2
                return orig(self, ids[:n], labels[:n])
            if fault == "state_unchanged":  # the step returns its state as it was
                kept = jax.tree_util.tree_map(jnp.copy, self._state)
                loss = orig(self, ids, labels)
                self._state = kept
                return loss
            raise ValueError(fault)

    step.__class__ = Broken
    return step
