"""Collective communication API.

Role parity: `paddle.distributed.{all_reduce,all_gather,...}`
(`python/paddle/distributed/communication/`) over ProcessGroup
(`paddle/fluid/distributed/collective/process_group.h:47`).

TPU-first semantics (SURVEY §5 backend note): there is one backend — XLA
collectives over ICI/DCN. A "group" is a mesh axis. Two operating modes:

* **SPMD (inside jit/shard_map)** — the functions lower to `lax.psum` /
  `all_gather` / `ppermute` / `all_to_all` on the named axis: this is the
  performance path, the analog of collective ops compiled into the program.
* **Eager (single-controller)** — the input Tensor holds a global jax.Array
  (possibly sharded over the group axis); the collective is executed as a
  tiny shard_map program over the topology mesh. This gives ProcessGroup-
  style imperative collectives without NCCL ring management; `Task.wait`
  becomes jax's async dispatch (returned arrays are futures already).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import flags
from ..core.dispatch import apply
from ..core.tensor import Tensor
from ..observability import metrics as _metrics
from ..observability import trace as _trace
from ..resilience import faults as _faults
from . import topology as topo_mod

__all__ = [
    "ReduceOp", "Group", "new_group", "get_group", "all_reduce", "all_gather",
    "all_gather_object", "reduce", "reduce_scatter", "alltoall",
    "alltoall_single", "broadcast", "scatter", "send", "recv", "isend",
    "irecv", "barrier", "stream",
]


class ReduceOp:
    SUM = "sum"
    MAX = "max"
    MIN = "min"
    PROD = "prod"
    AVG = "avg"


class Group:
    """A communication group = a mesh axis of the hybrid topology."""

    def __init__(self, axis="dp", topo=None, name=None):
        self.axis = axis
        self._topo = topo
        self.name = name or f"group_{axis}"

    @property
    def topo(self):
        return self._topo or topo_mod.get_topology()

    @property
    def mesh(self):
        return self.topo.spmd_mesh

    def get_world_size(self):
        return int(self.mesh.shape[self.axis])

    @property
    def nranks(self):
        return self.get_world_size()

    def get_rank(self):
        # single-controller: the calling process sees all shards; axis index
        # is only meaningful inside shard_map (lax.axis_index)
        return 0

    @property
    def rank(self):
        return self.get_rank()

    def get_group_rank(self, rank):
        return rank

    def __repr__(self):
        return f"<Group axis={self.axis} size={self.get_world_size()}>"


_groups = {}


def new_group(ranks=None, backend=None, timeout=None, axis="dp"):
    g = Group(axis=axis)
    _groups[g.name] = g
    return g


def get_group(gid=None):
    return Group("dp")


def _default_group(group):
    return group if group is not None else Group("dp")


def _in_spmd():
    """True when called inside shard_map/jit tracing with named axes bound."""
    try:
        import jax.core as jcore

        frame = jcore.get_axis_env() if hasattr(jcore, "get_axis_env") else None
    except Exception:
        frame = None
    try:
        # jax>=0.4: axis names visible via jax.interpreters context
        from jax._src.core import trace_ctx

        return bool(getattr(trace_ctx, "axis_env", None) and
                    trace_ctx.axis_env.axis_sizes)
    except Exception:
        return False


def _axis_bound(axis):
    try:
        jax.lax.axis_index(axis)  # cheap probe: raises if not bound
        return True
    except Exception:
        return False


def _collective_retry():
    """Retry policy for eager collectives: a host-dispatched collective
    that dies on a transient fault (link drop, preempted slice,
    injected collective.call) is re-issued with backoff before the
    error surfaces — "retry then raise" (EQuARX-class collective
    faults, ISSUE 3).  PADDLE_TPU_COLLECTIVE_RETRIES tunes attempts."""
    from ..resilience.retry import env_policy

    return env_policy(
        "collective", "PADDLE_TPU_COLLECTIVE_RETRIES", 3,
        base_delay=0.02, max_delay=0.5,
        # shape/dtype/spec mistakes are deterministic — only
        # runtime-class failures (infra, injected) are transient
        give_up_on=(TypeError, ValueError, KeyError, AttributeError,
                    IndexError))


def _eager_collective(name, x, group, per_shard_fn, out_sharding_spec=None):
    """Run `per_shard_fn` under shard_map over the group axis."""
    g = _default_group(group)
    mesh = g.mesh
    axis = g.axis
    val = x._value if isinstance(x, Tensor) else jnp.asarray(x)
    from jax import shard_map

    in_spec = _infer_spec(val, mesh, axis)
    out_spec = out_sharding_spec if out_sharding_spec is not None else in_spec

    try:
        fn = shard_map(per_shard_fn, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_vma=False)
    except TypeError:  # jax 0.4.x spells the replication check check_rep
        fn = shard_map(per_shard_fn, mesh=mesh, in_specs=(in_spec,),
                       out_specs=out_spec, check_rep=False)
    # span wrapper (timeline correlation): the eager collective is a
    # host-dispatched program, so its wall is a real slice on the trace;
    # the SPMD path compiles into the surrounding program and is covered
    # by that program's compile span instead
    with _trace.span(name, cat="collective", axis=axis,
                     shape=list(getattr(val, "shape", ()))):
        def _dispatch():
            # fault point INSIDE the retried callable: an armed
            # collective.call rule with times=N fails the first N
            # dispatches, then the retry succeeds — exactly the
            # transient-fault shape the policy exists for
            _faults.fire("collective.call", op=name, axis=axis)
            return apply(name, fn,
                         x if isinstance(x, Tensor) else Tensor(val))

        return _collective_retry().call(_dispatch)


def _infer_spec(val, mesh, axis):
    """Sharding spec of val w.r.t. mesh: preserve existing sharding if the
    array is placed on this mesh, else treat as replicated."""
    sh = getattr(val, "sharding", None)
    if isinstance(sh, NamedSharding) and sh.mesh.shape == mesh.shape:
        return sh.spec
    return P()


def _resolve_precision(op, precision):
    """The EQuARX tier applies to additive reductions only (sum/avg —
    the gradient-sync ops); max/min/prod stay exact.  Resolution
    happens per call so the env knob can flip between eager steps.
    Validation runs for EVERY op — a typo'd tier on a max/min sync must
    fail loudly, not silently run exact."""
    from . import quantized as _quantized

    prec = _quantized.collective_precision(precision)
    if op not in (ReduceOp.SUM, ReduceOp.AVG):
        return None
    return prec


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True,
               precision=None):
    """All-reduce over the group axis.  ``precision`` (or the
    ``PADDLE_TPU_COLLECTIVE_PRECISION`` env knob) selects the quantized
    wire tier for sum/avg: per-chunk-scaled int8 (int32-accumulated) or
    bf16 payloads — docs/SHARDING.md "Precision knob"."""
    _metrics.inc("collective.calls", kind="all_reduce")
    g = _default_group(group)
    axis = g.axis
    prec = _resolve_precision(op, precision)
    if prec is not None:
        from . import quantized as _quantized

        val = tensor._value if isinstance(tensor, Tensor) else tensor
        if _quantized._quantizable(val):
            # count only payloads that actually ride the lossy codec —
            # integer syncs reduce exactly (quantized._quantizable)
            _metrics.inc("collective.quantized", kind="all_reduce",
                         precision=prec)

        def red_q(v, a):
            out = _quantized.psum(v, a, prec)
            if op == ReduceOp.AVG:
                out = out / g.get_world_size()
            return out

    if flags.in_trace():
        # SPMD path: lower directly to the named-axis collective
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
               "avg": lambda v, a: jax.lax.pmean(v, a)}[op]
        if prec is not None:
            red = red_q
        out = apply("all_reduce", lambda v: red(v, axis), tensor)
        tensor._rebind(out) if isinstance(tensor, Tensor) else None
        return tensor

    def body(v):
        red = {"sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
               "avg": lambda t, a: jax.lax.pmean(t, a),
               "prod": lambda t, a: jnp.exp(jax.lax.psum(jnp.log(t), a))}[op]
        if prec is not None:
            red = red_q
        return red(v, axis)

    out = _eager_collective("all_reduce", tensor, g, body)
    if isinstance(tensor, Tensor):
        tensor._rebind(out)
        return tensor
    return out


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    _metrics.inc("collective.calls", kind="all_gather")
    g = _default_group(group)
    ax = g.axis

    def body(v):
        return jax.lax.all_gather(v, ax)

    if flags.in_trace():
        out = apply("all_gather", body, tensor)
    else:
        out = _eager_collective("all_gather", tensor, g, body,
                                out_sharding_spec=P())
    if tensor_list is not None:
        n = g.get_world_size()
        for i in range(n):
            tensor_list.append(out[i])
        return tensor_list
    return out


def all_gather_object(object_list, obj, group=None):
    # single-controller: every "rank" sees the same object
    g = _default_group(group)
    object_list.extend([obj] * g.get_world_size())
    return object_list


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    # on ICI a reduce is an all_reduce whose non-root results are ignored
    return all_reduce(tensor, op, group, sync_op)


def reduce_scatter(tensor, tensor_or_tensor_list, op=ReduceOp.SUM, group=None,
                   sync_op=True, precision=None):
    """Reduce-scatter over the group axis (the ZeRO-1 grad-sync shape:
    every replica receives its 1/N summed slice, moving 1/N the bytes an
    all-reduce would).  ``precision`` / the env knob select the
    quantized wire tier — chunks are laid out per destination slice so
    each replica dequantizes its slice with pmax-shared scales."""
    _metrics.inc("collective.calls", kind="reduce_scatter")
    g = _default_group(group)
    ax = g.axis
    # the quantized tier applies to SUM only here: this function's
    # non-sum ops have always reduced as SUM (pre-existing psum_scatter
    # semantics), and the knob must never make AVG/MAX behave
    # differently from the exact path
    prec = _resolve_precision(op, precision)
    if op != ReduceOp.SUM:
        prec = None
    if prec is not None:
        from . import quantized as _quantized

        src0 = tensor_or_tensor_list
        if isinstance(src0, (list, tuple)):
            src0 = src0[0]
        if _quantized._quantizable(
                src0._value if isinstance(src0, Tensor) else src0):
            _metrics.inc("collective.quantized", kind="reduce_scatter",
                         precision=prec)
    src = tensor_or_tensor_list
    if isinstance(src, (list, tuple)):
        from .. import ops

        src = ops.concat(list(src), axis=0)

    def body(v):
        if prec is not None:
            return _quantized.psum_scatter(v, ax, g.get_world_size(), prec)
        return jax.lax.psum_scatter(v, ax, scatter_dimension=0, tiled=True)

    if flags.in_trace():
        out = apply("reduce_scatter", body, src)
    else:
        out = _eager_collective("reduce_scatter", src, g, body,
                                out_sharding_spec=P(ax))
    if isinstance(tensor, Tensor):
        tensor._rebind(out)
        return tensor
    return out


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    _metrics.inc("collective.calls", kind="alltoall")
    g = _default_group(group)
    ax = g.axis
    from .. import ops

    stacked = ops.stack(list(in_tensor_list), axis=0)

    def body(v):
        # v: [world, ...local] per shard -> exchange leading dim
        return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                  tiled=False)

    if flags.in_trace():
        out = apply("alltoall", body, stacked)
    else:
        out = _eager_collective("alltoall", stacked, g, body)
    n = g.get_world_size()
    if out_tensor_list is not None:
        for i in range(n):
            out_tensor_list.append(out[i])
        return out_tensor_list
    return out


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    _metrics.inc("collective.calls", kind="alltoall_single")
    g = _default_group(group)
    ax = g.axis

    def body(v):
        return jax.lax.all_to_all(v, ax, split_axis=0, concat_axis=0,
                                  tiled=True)

    if flags.in_trace():
        out = apply("alltoall_single", body, in_tensor)
    else:
        out = _eager_collective("alltoall_single", in_tensor, g, body)
    if isinstance(out_tensor, Tensor):
        out_tensor._rebind(out)
        return out_tensor
    return out


def broadcast(tensor, src=0, group=None, sync_op=True):
    # single-controller: values are already consistent; inside shard_map the
    # source shard's value is selected
    _metrics.inc("collective.calls", kind="broadcast")
    g = _default_group(group)
    ax = g.axis
    if flags.in_trace() or _axis_bound(ax):
        def body(v):
            return jax.lax.all_gather(v, ax)[src]

        out = apply("broadcast", body, tensor)
        if isinstance(tensor, Tensor):
            tensor._rebind(out)
            return tensor
        return out
    return tensor


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    g = _default_group(group)
    if tensor_list is not None:
        # single-controller eager: take the src rank's piece for this process
        tensor._rebind(tensor_list[src] if isinstance(tensor, Tensor)
                       else tensor)
    return tensor


def send(tensor, dst=0, group=None, sync_op=True):
    """Point-to-point on TPU = ppermute along the pp/mesh axis; outside SPMD
    tracing this is the pipeline runner's device_put (see parallel/pipeline)."""
    _metrics.inc("collective.calls", kind="send")
    g = _default_group(group)
    if flags.in_trace():
        ax = g.axis
        n = g.get_world_size()
        perm = [(i, (i + 1) % n) for i in range(n)]
        return apply("send", lambda v: jax.lax.ppermute(v, ax, perm), tensor)
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    return tensor


isend = send
irecv = recv


def barrier(group=None):
    _metrics.inc("collective.calls", kind="barrier")
    for d in jax.local_devices():
        try:
            jax.device_put(0, d).block_until_ready()
        except Exception:
            _metrics.inc("collective.barrier_sync_errors")


class stream:
    """paddle.distributed.stream.* parity: on TPU the compiler owns streams,
    so these are the same collectives (kept for API compatibility)."""

    all_reduce = staticmethod(all_reduce)
    all_gather = staticmethod(all_gather)
    reduce_scatter = staticmethod(reduce_scatter)
    alltoall = staticmethod(alltoall)
    broadcast = staticmethod(broadcast)
    send = staticmethod(send)
    recv = staticmethod(recv)
