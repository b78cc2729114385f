"""Activation recomputation (parity:
`python/paddle/distributed/fleet/recompute/recompute.py:108,404`).

TPU-first: under tracing this is `jax.checkpoint` (XLA rematerialization) —
the compiler replays the segment in backward instead of saving activations;
the reference's RNG-state tracker for deterministic dropout replay is
unnecessary because the PRNG key threading makes dropout functional.
Eagerly it's a pass-through (tape autograd already frees per-op residuals
after backward).

What a recomputed segment keeps.  With no policy named (`recompute=True`
in the models, `fleet.utils.recompute(f, x)`) the backward replays the
segment EXCEPT the values the code inside it marked with `keep()`: results
that are dear to replay and cheap to hold.  Marked today (`KEPT`): the flash
cores' output and log-sum-exp — their VJP's residuals, so the backward
does not run the flash forward kernel a second time — and the routed
expert layer's result, which spares the replay the whole grouped forward
(its VJP's residuals are its inputs), with the router's choice and the
sort's small integer products (no `top_k`, no argsort in the replay),
learned sparse attention's selection (the [B, T, T] int8 mask: the replay
runs no search over the index scores), and the gradient of the indexer's
loss by the index scores (`indexer_grad`, [B, T, T] float32, formed in the
loss's forward rule as its only residual: the replay makes neither the index
scores nor the attention's head-mean probabilities nor the loss again).
Projections, norms, rotary, layout swaps and the router's scores are
replayed.  `policy="full"` replays everything
(the last bytes: nothing but the segment's input is held); `"dots"` and
`"dots_no_batch"` are JAX's matmul-output policies and keep no mark.

A new kernel marks its residuals INSIDE its `custom_vjp` forward rule, on
the values the rule returns as residuals and before anything else reads
them: `out, lse = keep(out, "flash_out"), keep(lse, "flash_lse")`.  The
kernel call then has no reader left in the replay and is dropped from it.
Outside a recomputed segment a mark lowers to nothing.
"""
from __future__ import annotations

import jax
from jax.ad_checkpoint import checkpoint_name

from ..core import flags
from ..core.tensor import Tensor

__all__ = ["recompute", "recompute_sequential", "keep", "keeping", "KEPT"]

# the names `keep()` takes: what the default policy holds across a replay
KEPT = ("flash_out", "flash_lse", "moe_out", "moe_sort", "sparse_select",
        "indexer_grad")

# Named rematerialization policies (the TPU memory/FLOPs dial — SURVEY §7
# hard part (c)). None keeps the marked values (above) and replays the
# rest; "full" replays everything in backward (max memory savings); "dots"
# saves every matmul output (min recompute FLOPs); "dots_no_batch" saves
# matmul outputs except batched dots — the standard transformer sweet
# spot: the attention/mlp GEMMs whose recompute costs real MXU time are
# saved, cheap elementwise replays.
_POLICIES = {
    None: jax.checkpoint_policies.save_only_these_names(*KEPT),
    "full": None,
    "dots": jax.checkpoint_policies.checkpoint_dots,
    "dots_no_batch":
        jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
}

# one entry a segment being traced: does its policy hold the marks?
_segments = []


def keep(value, name):
    """Mark `value` (an array, inside traced code) as kept across
    recomputation under the name `name`, one of `KEPT`."""
    if name not in KEPT:
        raise ValueError(f"recompute.keep: {name!r} is not one of {KEPT}")
    return checkpoint_name(value, name)


def keeping() -> bool:
    """True while the body of a `recompute()` that holds the marks is being
    traced: what the trace-time `*.recompute_kept` counters ask."""
    return bool(_segments) and _segments[-1]


def _resolve_policy(name):
    if name not in _POLICIES:
        raise ValueError(
            f"recompute policy must be one of {sorted(k for k in _POLICIES if k)}"
            f" or None, got {name!r}")
    return _POLICIES[name]


def recompute(function, *args, use_reentrant=True, preserve_rng_state=True,
              policy=None, **kwargs):
    # validate uniformly: a typo'd policy must fail in eager debugging
    # too, not only once the job reaches a traced run
    pol = _resolve_policy(policy)
    if not flags.in_trace():
        return function(*args, **kwargs)

    leaves, treedef = jax.tree_util.tree_flatten(
        (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
    tensor_idx = [i for i, l in enumerate(leaves) if isinstance(l, Tensor)]
    vals = [leaves[i]._value for i in tensor_idx]

    def pure(*tvals):
        cur = list(leaves)
        for i, v in zip(tensor_idx, tvals):
            cur[i] = Tensor(v, stop_gradient=False)
        a, kw = jax.tree_util.tree_unflatten(treedef, cur)
        _segments.append(policy is None)
        try:
            out = function(*a, **kw)
        finally:
            _segments.pop()
        return jax.tree_util.tree_map(
            lambda o: o._value if isinstance(o, Tensor) else o, out,
            is_leaf=lambda x: isinstance(x, Tensor))

    out_vals = jax.checkpoint(pure, policy=pol)(*vals)
    return jax.tree_util.tree_map(lambda v: Tensor(v), out_vals)


def recompute_sequential(ctx, functions, *args, **kwargs):
    """Recompute over a Sequential in `segments` chunks (parity:
    recompute_sequential)."""
    segments = ctx.get("segments", 1) if isinstance(ctx, dict) else 1
    sublayers = list(functions) if isinstance(functions, (list, tuple)) else \
        list(functions.children())
    n = len(sublayers)
    seg = max(1, n // max(1, segments))
    out = args[0] if len(args) == 1 else args

    def run_segment(layers):
        def f(x):
            for l in layers:
                x = l(x)
            return x

        return f

    i = 0
    while i < n:
        chunk = sublayers[i:i + seg]
        out = recompute(run_segment(chunk), out)
        i += seg
    return out
