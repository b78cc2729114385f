"""SPMD collective pipeline parallelism: ONE jit program over the global
mesh, stage shifts via `lax.ppermute` — multi-host-ready by construction.

Role parity: the reference's cross-rank pipeline runtime — the send/recv
tier (`fleet/meta_parallel/pp_utils/p2p_communication.py`) plus the
schedule loops (`fleet/meta_parallel/pipeline_parallel.py:440`) — rebuilt
the TPU-native way: every stage's parameters live stacked along a `pp`
mesh axis, all devices run the SAME compiled program, and the boundary
activation shifts one stage per tick through `ppermute` (XLA
collective-permute, riding ICI/DCN like any other collective). The
single-controller tier (`pipeline.py`: per-stage jit programs + async
device_put boundaries, dispatch-order 1F1B) cannot cross process
boundaries — a process cannot jit onto devices it does not own. This tier
can: under multi-process JAX every process executes the same program and
XLA moves the boundary activations between hosts.

Autodiff reverses the schedule for free: the transpose of a forward
ppermute(i -> i+1) is ppermute(i+1 -> i), so `jax.grad` of the scanned
forward IS the backward pipeline — no hand-written reverse schedule, no
SendRecvMeta handshakes.

Memory model: GPipe-style — boundary activations for all `m` microbatches
persist until backward (the classic collective-pipeline trade, cf. GSPMD
pipelining). `remat_stage=True` wraps the stage in `jax.checkpoint`, so
per microbatch ONLY the boundary activation is saved and stage internals
recompute in backward: per-device residual footprint O(m * |act|). The
dispatch-order 1F1B tier in `pipeline.py` keeps the lower-memory schedule
for single-process meshes; this module is the one-program tier that
scales past one process.

Bubble fraction is the GPipe (pp-1)/(m+pp-1); the schedule runs
m + pp - 1 ticks and every device computes every tick (devices outside
their active window compute on zeros — in SPMD the bubble is wasted FLOPs,
not idleness, which is exactly how GSPMD-pipelined TPU programs behave).

No interleaved (VPP) variant here, by design: VPP's bubble win comes from
interleaving FORWARD and BACKWARD micro-steps, and in this tier the
backward order belongs to autodiff (that is the point — the reverse
schedule is derived, not hand-written). Interleaved 1F1B lives in the
per-stage tier (`pipeline.py`), which owns its backward explicitly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

__all__ = ["stack_stages", "spmd_pipeline", "spmd_pipeline_reference"]


def stack_stages(per_stage_params):
    """[pytree] * pp (identical treedefs, identical leaf shapes) ->
    one pytree whose every leaf gains a leading [pp] dim. The inverse of
    what each device sees inside `spmd_pipeline` (its own stage's slice).
    """
    if len(per_stage_params) == 0:
        raise ValueError("stack_stages: need at least one stage")
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *per_stage_params)


def spmd_pipeline_reference(stage_fn, per_stage_params, x_mb):
    """Sequential semantics `spmd_pipeline` must reproduce: every
    microbatch through every stage in order (the parity oracle for
    tests; also the pp=1 execution path)."""
    def one(xb):
        for p in per_stage_params:
            xb = stage_fn(p, xb)
        return xb

    return jax.lax.map(one, x_mb)


def spmd_pipeline(stage_fn, stage_params, x_mb, mesh=None, axis="pp",
                  remat_stage=False):
    """Run `x_mb` microbatches through a `pp`-stage pipeline as one SPMD
    program.

    stage_fn(params_i, act) -> act        (shape- and dtype-preserving)
    stage_params: pytree with a leading [pp] dim on every leaf
                  (`stack_stages`), sharded/shardable over `axis`
    x_mb: [m, ...] microbatches entering stage 0 (replicated over `axis`;
          other mesh axes stay with the compiler — `shard_map` runs in
          partial-manual mode over `axis` alone, so dp/mp/sep sharding
          inside the stage is still GSPMD's job)
    Returns [m, ...] outputs of the LAST stage, replicated over `axis`.
    """
    if mesh is None:
        from . import topology as topo_mod

        mesh = topo_mod.current_spmd_mesh()
    if axis not in mesh.shape:
        raise ValueError(f"mesh has no '{axis}' axis: {mesh.shape}")
    pp = mesh.shape[axis]
    lead = {l.shape[0] for l in jax.tree_util.tree_leaves(stage_params)}
    if lead != {pp}:
        raise ValueError(
            f"stage_params leaves must carry a leading [pp={pp}] dim "
            f"(stack_stages); got leading dims {sorted(lead)}")
    if pp == 1:
        fn = jax.checkpoint(stage_fn) if remat_stage else stage_fn
        p0 = jax.tree_util.tree_map(lambda l: l[0], stage_params)
        return spmd_pipeline_reference(fn, [p0], x_mb)
    treedef = jax.tree_util.tree_structure(stage_params)
    compiled = _compiled_pipeline(stage_fn, mesh, axis, pp, remat_stage,
                                  treedef)
    return compiled(stage_params, x_mb)


@functools.lru_cache(maxsize=64)
def _compiled_pipeline(stage_fn, mesh, axis, pp, remat_stage, treedef):
    """One jitted pipeline program per (stage_fn, mesh, axis, pp, remat,
    param treedef): an eager caller in a loop hits jit's compile cache
    instead of rebuilding (and retracing) a fresh closure per call. The
    jit is also load-bearing for eager use at all — shard_map cannot
    eagerly evaluate closed_call bodies (a lax.scan inside stage_fn)."""
    fn = jax.checkpoint(stage_fn) if remat_stage else stage_fn

    def body(params_local, xloc):
        # shard_map hands each device its [1, ...] stage slice
        params_i = jax.tree_util.tree_map(lambda l: l[0], params_local)
        m = xloc.shape[0]
        sid = jax.lax.axis_index(axis)
        perm = [(i, i + 1) for i in range(pp - 1)]
        # carries must enter the scan already marked varying-over-pp:
        # the tick output is (per-device activations differ), and scan
        # requires carry-in/out types — including the vma component —
        # to match
        if hasattr(jax.lax, "pcast"):
            act0 = jax.lax.pcast(jnp.zeros_like(xloc[0]), axis,
                                 to="varying")
            ys0 = jax.lax.pcast(jnp.zeros_like(xloc), axis, to="varying")
        else:
            # jax 0.4.x has no varying-manual-axes tracking (check_rep
            # era): the carries need no vma marking there
            act0 = jnp.zeros_like(xloc[0])
            ys0 = jnp.zeros_like(xloc)

        def tick(carry, t):
            act, ys = carry
            # previous tick's outputs move one stage down the ring;
            # stage 0 instead ingests the next microbatch (a clamped
            # index past m re-feeds the last one — those ticks' results
            # never reach the collection window)
            shifted = jax.lax.ppermute(act, axis, perm)
            inj = jax.lax.dynamic_index_in_dim(
                xloc, jnp.minimum(t, m - 1), 0, keepdims=False)
            act_in = jnp.where(sid == 0, inj, shifted)
            act_out = fn(params_i, act_in)
            # the last stage emits microbatch t-(pp-1) at tick t
            idx = jnp.clip(t - (pp - 1), 0, m - 1)
            cur = jax.lax.dynamic_index_in_dim(ys, idx, 0, keepdims=False)
            keep = jnp.where(t >= pp - 1, act_out, cur)
            ys = jax.lax.dynamic_update_index_in_dim(ys, keep, idx, 0)
            return (act_out, ys), None

        (_, ys), _ = jax.lax.scan(tick, (act0, ys0),
                                  jnp.arange(m + pp - 1))
        # only the last stage holds real outputs; the masked psum makes
        # them global (its transpose routes the cotangent straight back
        # to the last stage — the backward pipeline's entry point)
        ys = jax.lax.psum(
            jnp.where(sid == pp - 1, ys, jnp.zeros_like(ys)), axis)
        return ys

    pspecs = jax.tree_util.tree_unflatten(
        treedef, [P(axis)] * treedef.num_leaves)
    try:
        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(pspecs, P()),
            out_specs=P(),
            axis_names=frozenset({axis}),
        )
    except TypeError:
        # jax 0.4.x: no axis_names — the manual-axes set is expressed as
        # its complement via `auto` (axes left to the compiler), and its
        # replication checker predates vma marking (mis-flags the
        # pipeline's ppermute carries), so it is disabled
        mapped = shard_map(
            body,
            mesh=mesh,
            in_specs=(pspecs, P()),
            out_specs=P(),
            auto=frozenset(mesh.axis_names) - {axis},
            check_rep=False,
        )
    return jax.jit(mapped)
