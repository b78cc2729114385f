"""The SPMD train-step builder: one compiled program for the whole hybrid
(dp × mp × sep [+ ZeRO]) training step.

Role parity (SURVEY §2.5, §3.3): this is where the reference's imperative
machinery — `fleet.distributed_model` wrappers, `EagerReducer` bucketed
allreduce, `DygraphShardingOptimizer`/GroupSharded stage 1-3,
`HybridParallelOptimizer` grad clip across axes — collapses into sharding
annotations on ONE jit'd function:

* DP grad sync          → XLA auto-inserts the grad all-reduce because params
                          are replicated over dp while the batch is sharded
                          (no bucketing logic: the compiler fuses collectives)
* TP / SP               → param + activation shardings from mpu layers
* ZeRO-1/2 (stage 1/2)  → params AND optimizer slots live dp-sharded between
                          steps (weight-update sharding, ISSUE 11): the step
                          opens with one all-gather restoring full params for
                          the forward, each parameter's gradient carries its
                          own sharding constraint at the point the backward
                          produces it (per-layer reduce-scatters the
                          scheduler can overlap with remaining backward
                          compute — no end-of-backward barrier), and the
                          optimizer update runs on 1/dp of every parameter
                          (*Automatic Cross-Replica Sharding of Weight
                          Update in Data-Parallel Training*, PAPERS.md).
                          Bit-identical to the replicated update (pinned by
                          tests/test_sharding_zero.py on the 8-device mesh).
* ZeRO-3 (stage 3)      → params themselves dp-sharded; forward all-gathers
                          per-layer on demand (compiler-scheduled)
* grad clip             → global norm computed inside the same program, so
                          the cross-axis reductions ride ICI with everything
                          else
* collective precision  → PADDLE_TPU_COLLECTIVE_PRECISION=bf16|int8 runs the
                          gradient sync payload through the EQuARX-style
                          chunked codec (distributed/quantized.py); off by
                          default — the default step is exact (docs/
                          SHARDING.md "Precision knob")

``sharding_stage=None`` (the default) resolves to ZeRO-1 whenever the mesh
has a real dp axis and stage 0 on a single chip — sharded weight update IS
the default multi-chip training configuration (ROADMAP item 1).

Buffers (batch-norm stats) and the PRNG key are threaded through as carried
state, donated each step.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core import flags, rng
from ..core.tensor import Tensor
from ..observability import trace as _trace
from ..observability import xla_cost as _xla_cost
from . import topology as topo_mod

__all__ = ["DistributedTrainStep", "param_placements",
           "save_train_checkpoint", "load_train_checkpoint"]

_LR_SIDECAR = "lr_scheduler.json"


def save_train_checkpoint(tensors, path, lr_sched=None):
    """Shared writer for both training tiers (hybrid step + pipeline):
    distributed checkpoint of the flat leaf dict, plus a host-side LR
    scheduler sidecar JSON when one is attached."""
    import json as _json
    import os as _os

    from ..optimizer.lr import LRScheduler
    from .checkpoint import save_state_dict

    save_state_dict(tensors, path)
    if isinstance(lr_sched, LRScheduler):
        with open(_os.path.join(path, _LR_SIDECAR), "w") as f:
            _json.dump(lr_sched.state_dict(), f)


def load_train_checkpoint(tensors, path, lr_sched=None):
    """Shared strict loader: every leaf in `tensors` must exist in the
    checkpoint (a partial match would silently mix loaded and fresh
    state), and when the caller trains under an LRScheduler its sidecar
    must be present too (restoring the step counter but restarting the
    warmup/decay schedule is the same silent divergence). Loads in place
    (leaves reshard onto each target tensor's placement)."""
    import json as _json
    import os as _os

    from ..optimizer.lr import LRScheduler
    from .checkpoint import load_state_dict
    from .checkpoint.api import _load_metadata

    meta = _load_metadata(path)
    if meta is None:
        raise ValueError(f"no checkpoint metadata found under {path!r}")
    missing = sorted(set(tensors) - set(meta.state_dict_metadata))
    if missing:
        raise ValueError(
            f"checkpoint at {path!r} is missing {len(missing)} of "
            f"{len(tensors)} training-state leaves (first: "
            f"{missing[:5]}) — refusing a partial resume (wrong model "
            "config or corrupt checkpoint?)")
    sched_file = _os.path.join(path, _LR_SIDECAR)
    if isinstance(lr_sched, LRScheduler):
        if not _os.path.exists(sched_file):
            raise ValueError(
                f"checkpoint at {path!r} has no {_LR_SIDECAR} but this "
                "run trains under an LRScheduler — resuming would "
                "restart the schedule at step 0 (was the checkpoint "
                "saved with a float learning rate?)")
        with open(sched_file) as f:
            state = _json.load(f)
    load_state_dict(tensors, path)
    if isinstance(lr_sched, LRScheduler):
        lr_sched.set_state_dict(state)


def param_placements(param, ndim=None):
    """Per-dim axis names from a parameter's dist_attr annotation."""
    ndim = ndim if ndim is not None else param.ndim
    da = getattr(param, "dist_attr", None)
    if isinstance(da, tuple) and (not da or not hasattr(da[0], "jax_mesh")):
        spec = list(da) + [None] * (ndim - len(da))
        return tuple(spec[:ndim])
    return (None,) * ndim


def _zero_shard_spec(spec, shape, dp_size, used_axes):
    """Add 'dp' to the first free, divisible dim (ZeRO weight partitioning)."""
    spec = list(spec)
    for d, s in enumerate(shape):
        if spec[d] is None and dp_size > 0 and s % dp_size == 0 and s >= dp_size:
            spec[d] = "dp"
            return tuple(spec)
    return tuple(spec)


class DistributedTrainStep:
    def __init__(self, model, optimizer, loss_fn=None, topo=None,
                 sharding_stage=None, recompute=False, amp_dtype=None,
                 grad_clip_norm=None, loss_has_aux=False, guard=None,
                 checkpoint_manager=None, preemption_guard=None,
                 collective_precision=None):
        self.model = model
        self.optimizer = optimizer
        self.loss_fn = loss_fn
        self.topo = topo or topo_mod.get_topology()
        if sharding_stage is None:
            # ZeRO-1 is the default multi-chip configuration: a real dp
            # axis means the replicated weight update is pure waste
            # (PT403's finding); a single chip has nothing to shard over.
            dp = self.topo.spmd_mesh.shape.get("dp", 1)
            sharding_stage = 1 if dp > 1 else 0
        self.sharding_stage = int(sharding_stage)
        # resolve the EQuARX tier once, at build time: an invalid knob
        # must fail construction, not step N of a training run
        from . import quantized as _quantized

        self.collective_precision = _quantized.collective_precision(
            collective_precision)
        self.amp_dtype = amp_dtype
        self.grad_clip_norm = grad_clip_norm
        self._compiled = None
        self._state = None
        self._param_names = [n for n, _ in model.named_parameters()]
        # --- resilience (docs/RESILIENCE.md) ---
        # guard=True/StepGuard: a finiteness reduction over loss+grads is
        # fused into the compiled step and bad steps keep the previous
        # state ON DEVICE (jnp.where select — with ok the selected leaves
        # are the new values bit-for-bit, so a fault-free guarded run
        # matches the unguarded trajectory exactly); the host sees one
        # ok scalar per dispatch and escalates warn→skip→rollback.
        if guard is True:
            from ..resilience.guards import StepGuard

            guard = StepGuard(name="train_step")
        self.guard = guard or None
        self._ckpt_mgr = checkpoint_manager
        if self.guard is not None and self._ckpt_mgr is not None \
                and self.guard.on_rollback is None:
            self.guard.set_rollback(self.rollback)
        # preemption_guard: a resilience.preemption.PreemptionGuard this
        # step consults at its safe points (between dispatches) — a
        # SIGTERM/maintenance event checkpoints through the attached
        # manager and raises TrainingPreempted instead of vanishing
        # mid-collective with unsaved state.
        self._preemption_guard = preemption_guard
        self._preemption_handled = None  # TrainingPreempted once raised

    # --- sharding planning ---------------------------------------------------
    def _plan(self, params, slots):
        """Storage shardings for params and optimizer slots.

        Returns ``(p_spec, s_spec)`` — the specs the state LIVES under
        between steps (and the compiled step's output pins):

          stage 0   params/slots follow the mpu placements (replicated
                    over dp)
          stage 1/2 ZeRO weight-update sharding: params AND slots carry
                    a dp shard on their first free divisible dim; the
                    step all-gathers full params for the forward
                    (``_p_full_spec`` keeps the forward-view spec)
          stage 3   same sharded storage, but no up-front gather — the
                    compiler all-gathers per use site on demand
        """
        mesh = self.topo.spmd_mesh
        dp = mesh.shape.get("dp", 1)
        named = dict(self.model.named_parameters())
        p_spec, p_full = {}, {}
        for n, v in params.items():
            spec = param_placements(named[n], np.ndim(v))
            p_full[n] = spec
            if self.sharding_stage >= 1:
                spec = _zero_shard_spec(spec, np.shape(v), dp, None)
            p_spec[n] = spec
        s_spec = {}
        for n, slotdict in slots.items():
            # slots inherit the param's storage spec: under ZeRO it is
            # already dp-sharded, so re-running _zero_shard_spec here
            # would pick a SECOND dim for same-shaped slots (the bug the
            # old dead `base = ... if ... else ...` branch masked)
            base = p_spec[n]
            out = {}
            for k, v in slotdict.items():
                if np.shape(v) == np.shape(params[n]):
                    out[k] = base
                else:
                    spec = param_placements(named[n], np.ndim(v))
                    if self.sharding_stage >= 1:
                        spec = _zero_shard_spec(spec, np.shape(v), dp,
                                                None)
                    out[k] = spec
            s_spec[n] = out
        self._p_full_spec = p_full
        return p_spec, s_spec

    def _sharding(self, spec):
        return NamedSharding(self.topo.spmd_mesh, P(*spec))

    # --- state ---------------------------------------------------------------
    def _put_state(self, v, sharding):
        """Place a host value (held in FULL on every process) with
        `sharding`. Single-process: plain device_put. Multi-process
        (multi-host training over the jax coordination service): the
        sharding spans non-addressable devices, which device_put rejects
        — build the global array from per-device slices of the full
        value instead (each process materializes only its addressable
        shards)."""
        if jax.process_count() == 1:
            return jax.device_put(v, sharding)
        v = jnp.asarray(v)
        return jax.make_array_from_callback(v.shape, sharding,
                                            lambda idx: v[idx])

    def init_state(self):
        params, buffers = self.model.functional_state()
        opt_state = self.optimizer.init_state(params)
        p_spec, s_spec = self._plan(params, opt_state["slots"])
        mesh = self.topo.spmd_mesh

        params = {n: self._put_state(v, self._sharding(p_spec[n]))
                  for n, v in params.items()}
        slots = {n: {k: self._put_state(v, self._sharding(s_spec[n][k]))
                     for k, v in sd.items()}
                 for n, sd in opt_state["slots"].items()}
        buffers = {n: self._put_state(v, NamedSharding(mesh, P()))
                   for n, v in buffers.items()}
        self._p_spec, self._s_spec = p_spec, s_spec
        # every leaf — including the scalar step counter and the PRNG key —
        # must carry the mesh sharding the compiled step emits, or the
        # second call's input avals differ from the first's and jit
        # retraces+recompiles the whole program (a full second XLA compile)
        rep = NamedSharding(mesh, P())
        self._state = {
            "params": params,
            "opt": {"slots": slots,
                    "step": self._put_state(
                        jnp.asarray(opt_state["step"]), rep)},
            "buffers": buffers,
            # fresh buffer: the step donates its state, so it must NOT alias
            # the global generator's key array
            "key": self._put_state(
                jax.random.fold_in(rng.default_generator.get_state(), 7),
                rep),
        }
        return self._state

    # --- compiled step -------------------------------------------------------
    def _build(self, batch_treedef, batch_specs):
        model = self.model
        optimizer = self.optimizer
        loss_fn = self.loss_fn
        amp_dtype = self.amp_dtype
        clip_norm = self.grad_clip_norm
        mesh = self.topo.spmd_mesh

        def loss_of(params, buffers, key, batch_leaves):
            old = rng.default_generator.get_state()
            rng.default_generator.set_state(key)
            try:
                run_params = params
                if amp_dtype is not None:
                    run_params = {
                        n: (v.astype(amp_dtype)
                            if jnp.issubdtype(v.dtype, jnp.floating) else v)
                        for n, v in params.items()}
                def _amp_in(b):
                    # O2 semantics: floating model inputs enter in the
                    # compute dtype (conv/matmul operands must agree)
                    if amp_dtype is not None and \
                            jnp.issubdtype(b.dtype, jnp.floating):
                        return b.astype(amp_dtype)
                    return b

                # use_spmd_mesh: kernel dispatch learns the mesh this
                # program is traced over (Pallas kernels run per shard)
                with flags.trace_guard(), topo_mod.use_spmd_mesh(mesh):
                    with model.bind_state(run_params, buffers) as (np_, nb_):
                        args = jax.tree_util.tree_unflatten(
                            batch_treedef,
                            [Tensor(_amp_in(b)) for b in batch_leaves])
                        if loss_fn is not None:
                            inputs, labels = args
                            out = model(inputs)
                            loss = loss_fn(out, labels)
                        else:
                            loss = model(*args)
                        new_buffers = {n: nb_[n]._value for n in nb_}
                new_key = rng.default_generator.get_state()
            finally:
                rng.default_generator.set_state(old)
            lv = loss._value if isinstance(loss, Tensor) else loss
            if lv.ndim > 0:
                lv = jnp.mean(lv)
            return lv.astype(jnp.float32), (new_buffers, new_key)

        guarded = self.guard is not None
        dp = mesh.shape.get("dp", 1)
        # ZeRO weight-update sharding is live when state storage carries a
        # dp shard: stage 1/2 materialize full params up front (ONE
        # gather the scheduler can prefetch); stage 3 leaves gathering to
        # the compiler per use site.
        zero_sharded = self.sharding_stage >= 1 and dp > 1
        gather_full = zero_sharded and self.sharding_stage < 3
        precision = self.collective_precision if zero_sharded else None
        if precision is not None:
            from . import quantized as _quantized
            from ..observability import metrics as _metrics

            # counted only when the tier is actually traced into the
            # step — on a single chip (or stage 0) the knob is inert and
            # every collective stays exact, so telemetry must not claim
            # a lossy codec ran
            _metrics.inc("collective.quantized_tier", precision=precision)

        def step(params, opt_state, buffers, key, lr, *batch_leaves):
            # each stage under a `train_step.*` scope: the names reach the
            # optimized program's op_name metadata, where
            # `xla_cost.program_ledger` joins a device trace to them
            # (trace time only; nothing runs per step)
            if gather_full:
                # all-gather: full params for the next forward (ZeRO-1's
                # per-step gather — the bits equal the sharded storage's)
                with jax.named_scope("train_step.gather"):
                    run_params = {
                        n: jax.lax.with_sharding_constraint(
                            v, self._sharding(self._p_full_spec[n]))
                        for n, v in params.items()}
            else:
                run_params = params
            # JAX marks the ops inside with jvp(...) / transpose(...):
            # that is the forward/backward split
            with jax.named_scope("train_step.loss"):
                (loss, (new_buffers, new_key)), grads = jax.value_and_grad(
                    loss_of, has_aux=True)(run_params, buffers, key,
                                           list(batch_leaves))
            if clip_norm is not None:
                with jax.named_scope("train_step.clip"):
                    gsq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                              for g in jax.tree_util.tree_leaves(grads))
                    scale = jnp.minimum(
                        1.0, clip_norm / jnp.maximum(jnp.sqrt(gsq), 1e-6))
                    grads = jax.tree_util.tree_map(
                        lambda g: (g.astype(jnp.float32)
                                   * scale).astype(g.dtype),
                        grads)
            if zero_sharded:
                # per-parameter sharding constraint at the point the
                # backward produces each grad: the partitioner reduces
                # straight into 1/dp shards (reduce-scatter on TPU; the
                # CPU partitioner realizes it as all-reduce+slice, same
                # math) — one collective per layer, overlappable with
                # the remaining backward, not one end-of-backward
                # barrier.  The quantized tier codecs the payload first.
                def _sync(n, g):
                    if precision is not None:
                        g = _quantized.qdq(g, precision)
                    return jax.lax.with_sharding_constraint(
                        g, self._sharding(self._p_spec[n]))

                with jax.named_scope("train_step.grad_sync"):
                    grads = {n: _sync(n, g) for n, g in grads.items()}
            with jax.named_scope("train_step.update"):
                # the update consumes the SHARDED params/grads/slots:
                # every optimizer is elementwise over same-shaped leaves,
                # so the whole weight update runs on 1/dp of each
                # parameter
                new_params, new_opt = optimizer.apply_gradients(
                    params, grads, opt_state, lr)
                # pin result shardings so the update stays
                # ZeRO-partitioned
                new_params = {
                    n: jax.lax.with_sharding_constraint(
                        v, self._sharding(self._p_spec[n]))
                    for n, v in new_params.items()}
                new_opt_slots = {
                    n: {k: jax.lax.with_sharding_constraint(
                        v, self._sharding(self._s_spec[n][k]))
                        for k, v in sd.items()}
                    for n, sd in new_opt["slots"].items()}
                new_opt = {"slots": new_opt_slots, "step": new_opt["step"]}
            if guarded:
                # in-step NaN/Inf guard: one fused finiteness reduction
                # over loss + grads; a bad step keeps params/opt/buffers
                # (incl. the opt step counter) on device — no host
                # round-trip, no torn half-applied update.  The PRNG key
                # still advances: a skipped step must not replay the
                # same dropout mask into the retry.
                from ..resilience import guards as _guards

                with jax.named_scope("train_step.guard"):
                    ok = _guards.tree_finite(loss, grads)
                    new_params = _guards.tree_select(ok, new_params, params)
                    new_opt = _guards.tree_select(ok, new_opt, opt_state)
                    new_buffers = _guards.tree_select(ok, new_buffers,
                                                      buffers)
            else:
                ok = jnp.bool_(True)
            return loss, ok, new_params, new_opt, new_buffers, new_key

        self._step_fn = step
        # with telemetry on, the compile happens inside an
        # `xla.compile:train_step` span annotated with cost_analysis
        # FLOPs/bytes (plain jit call otherwise)
        return _xla_cost.instrument(
            jax.jit(step, donate_argnums=(0, 1, 2, 3),
                    compiler_options=flags.jit_compiler_options()),
            label="train_step")

    def _build_multi(self, batch_treedef, is_repeat):
        """N steps in ONE compiled program: lax.scan over the leading batch
        axis (or `repeat` times over one batch). Host dispatches once per
        N steps — the per-dispatch host gap otherwise shows up as device
        IDLE between steps. XLA keeps state resident across scan
        iterations, so this is also the idiomatic TPU shape for a training
        loop (host loop minimization)."""
        self._build(batch_treedef, None)  # ensure _step_fn exists
        step = self._step_fn

        def multi(params, opt_state, buffers, key, lrs, *batch_leaves):
            def body(carry, sl):
                params, opt_state, buffers, key = carry
                lr_i = sl[0]
                batch_sl = batch_leaves if is_repeat else sl[1:]
                loss, ok, p2, o2, b2, k2 = step(params, opt_state, buffers,
                                                key, lr_i, *batch_sl)
                return (p2, o2, b2, k2), (loss, ok)

            # scan length comes from lrs' leading dim: one jit WRAPPER
            # serves every step count in this mode (a new N still
            # retraces inside it, since lrs' shape changes — but the
            # previous N's executable stays cached alongside)
            xs = (lrs,) if is_repeat else (lrs,) + tuple(batch_leaves)
            (p, o, b, k), (losses, oks) = jax.lax.scan(
                body, (params, opt_state, buffers, key), xs)
            return losses, oks, p, o, b, k

        return _xla_cost.instrument(
            jax.jit(multi, donate_argnums=(0, 1, 2, 3),
                    compiler_options=flags.jit_compiler_options()),
            label="train_step_multi")

    def run_steps(self, *batch, lrs=None, repeat=None):
        """Run one optimizer step per leading-axis slice of `batch` (every
        leaf shaped [n_steps, ...]) inside a single compiled program;
        returns the per-step losses as one [n_steps] Tensor.

        repeat: alternatively, pass ONE batch (no leading step axis) and
        scan it `repeat` times — same dispatch amortization without
        materializing n_steps copies of the data (benchmarks, gradient
        sanity loops).

        lrs: optional per-step learning rates, shape [n_steps]. With an
        LRScheduler-driven optimizer and lrs=None, the schedule's next
        n_steps values are read (and the scheduler advanced n_steps) here
        — matching the sequential `__call__`+`scheduler.step()` loop. An
        explicit lrs leaves the scheduler untouched: the caller owns the
        schedule position in that mode."""
        from ..optimizer.lr import LRScheduler

        self._check_preemption()  # don't start a scan we can't keep
        if repeat is not None:
            repeat = int(repeat)
            if repeat < 1:
                raise ValueError(f"repeat must be >= 1, got {repeat}")
        placed, treedef = self._place_batch(
            batch, batch_axis=0 if repeat else 1)
        if repeat:
            n_steps = repeat
        else:
            n_steps = int(placed[0].shape[0]) if placed else 0
        if lrs is None:
            sched = self.optimizer._learning_rate
            if isinstance(sched, LRScheduler):
                # consume the next n_steps of the schedule host-side (the
                # scan cannot step the scheduler), leaving it positioned
                # exactly as n_steps sequential __call__+step()s would
                vals = []
                for _ in range(n_steps):
                    vals.append(float(self.optimizer.get_lr()))
                    sched.step()
                lrs = jnp.asarray(vals, jnp.float32)
            else:
                lrs = jnp.full((n_steps,), self.optimizer.get_lr(),
                               jnp.float32)
        else:
            lrs = jnp.asarray(
                lrs._value if isinstance(lrs, Tensor) else lrs,
                jnp.float32)
            if lrs.shape != (n_steps,):
                raise ValueError(
                    f"lrs must have shape ({n_steps},), got {lrs.shape}")
        multi_sig = (treedef, repeat is not None)
        if getattr(self, "_compiled_multi", None) is None or \
                getattr(self, "_multi_sig", None) != multi_sig:
            self._multi_sig = multi_sig
            self._compiled_multi = self._build_multi(
                treedef, repeat is not None)
        placed = self._maybe_poison(placed, n_steps=n_steps)
        s = self._state
        losses, oks, params, opt, buffers, key = self._compiled_multi(
            s["params"], s["opt"], s["buffers"], s["key"], lrs, *placed)
        self._swap_state(params, opt, buffers, key)
        if self.guard is not None:
            for ok in np.asarray(oks):
                self.guard.observe(bool(ok))
        self._check_preemption()  # signal landed mid-scan: state is
        return Tensor(losses)     # post-scan consistent → save now

    def _place_batch(self, batch, batch_axis):
        """Unwrap/flatten a batch and device_put each leaf with the dp
        axis on `batch_axis` (0 for single steps, 1 under a leading step
        axis). Returns (placed_leaves, treedef)."""
        if self._state is None:
            self.init_state()
        vals = jax.tree_util.tree_map(
            lambda b: b._value if isinstance(b, Tensor) else jnp.asarray(b),
            batch, is_leaf=lambda x: isinstance(x, Tensor))
        leaves, treedef = jax.tree_util.tree_flatten(vals)
        mesh = self.topo.spmd_mesh
        dp = mesh.shape.get("dp", 1)
        placed = []
        multiproc = jax.process_count() > 1
        # multi-host: each process holds its LOCAL shard, which must be
        # divisible by the dp devices *this process* contributes — not
        # by the global degree
        dp_div = max(dp // jax.process_count(), 1) if multiproc \
            else max(dp, 1)
        for b in leaves:
            batched = np.ndim(b) > batch_axis
            if batched and b.shape[batch_axis] % dp_div == 0:
                spec = [None] * batch_axis + ["dp"] + \
                    [None] * (np.ndim(b) - batch_axis - 1)
            elif batched and multiproc and dp > 1:
                # replicating per-rank-DIFFERENT data as a "replicated"
                # global array would silently diverge the ranks — refuse
                raise ValueError(
                    f"multi-process batch leaf with local batch "
                    f"{b.shape[batch_axis]} not divisible by the "
                    f"process-local dp share ({dp_div}); pad or resize "
                    f"the per-rank batch")
            else:
                spec = [None] * np.ndim(b)
            if multiproc:
                # assemble the global array across processes (global
                # batch = sum of local batches along the dp axis)
                from jax.experimental import multihost_utils

                placed.append(
                    multihost_utils.host_local_array_to_global_array(
                        np.asarray(b), mesh, P(*spec)))
            else:
                placed.append(
                    jax.device_put(b, NamedSharding(mesh, P(*spec))))
        return placed, treedef

    def _swap_state(self, params, opt, buffers, key):
        self._state = {"params": params, "opt": opt, "buffers": buffers,
                       "key": key}

    def _ensure_compiled(self, treedef):
        """One compile-cache keying for __call__ and lower(): a drift
        between the lowered-for-analysis and executed programs would
        defeat the analyzer's purpose."""
        if self._compiled is None or \
                getattr(self, "_batch_treedef", None) != treedef:
            self._batch_treedef = treedef
            self._compiled = self._build(treedef, None)
        return self._compiled

    def lower(self, *batch):
        """Lower the compiled step for `batch` without executing it
        (state does NOT advance). Feeds the completion/reshard analyzers
        (`distributed.completion.analyze`): `.as_text()` carries the
        GSPMD sharding annotations, `.compile().as_text()` the inserted
        collectives."""
        placed, treedef = self._place_batch(batch, batch_axis=0)
        compiled = self._ensure_compiled(treedef)
        s = self._state
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        return compiled.lower(
            s["params"], s["opt"], s["buffers"], s["key"], lr, *placed)

    def _maybe_poison(self, placed, n_steps=1):
        """`train.step` fault point: kind="error" raises at dispatch;
        kind="nan" poisons the first floating batch leaf so a NaN flows
        through the REAL compiled program (loss and grads go non-finite
        the way a genuinely bad batch/overflow makes them — the guard is
        exercised end-to-end, not mocked)."""
        from ..resilience import faults as _faults

        action = _faults.fire("train.step", n_steps=n_steps)
        if action is not None and action.kind == "nan":
            for i, b in enumerate(placed):
                if jnp.issubdtype(b.dtype, jnp.floating):
                    placed = list(placed)
                    # 0*nan propagates NaN elementwise, sharding intact
                    placed[i] = b + jnp.asarray(
                        float("nan"), b.dtype) * jnp.zeros_like(b)
                    break
        return placed

    def __call__(self, *batch):
        """batch: (inputs, labels) Tensors (loss_fn mode) or raw model args.
        Returns the loss as a Tensor; model/optimizer state advances."""
        self._check_preemption()  # safe point: pre-dispatch
        # host spans (one branch each while the tracer is off): the
        # device idles under one of these when the host is in its way
        sp = _trace.begin("train_step.place_batch")
        placed, treedef = self._place_batch(batch, batch_axis=0)
        _trace.end(sp)
        compiled = self._ensure_compiled(treedef)
        placed = self._maybe_poison(placed)
        s = self._state
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        sp = _trace.begin("train_step.dispatch")
        loss, ok, params, opt, buffers, key = compiled(
            s["params"], s["opt"], s["buffers"], s["key"], lr, *placed)
        _trace.end(sp)
        self._swap_state(params, opt, buffers, key)
        if self.guard is not None:
            # ONE host-visible scalar per dispatch (the guarded mode's
            # only extra transfer) drives the warn→skip→rollback ladder
            sp = _trace.begin("train_step.guard_sync")
            self.guard.observe(bool(ok))
            _trace.end(sp)
        self._check_preemption()  # safe point: post-step, state swapped
        return Tensor(loss)

    # --- state sync back to the eager model ---------------------------------
    def sync_to_model(self):
        """Write compiled-state params/buffers back into the eager Layer
        (for checkpointing / eval in eager mode)."""
        if self._state is None:
            return
        named_p = dict(self.model.named_parameters())
        for n, v in self._state["params"].items():
            if n in named_p:
                named_p[n]._value = v
        named_b = dict(self.model.named_buffers())
        for n, v in self._state["buffers"].items():
            if n in named_b:
                named_b[n]._value = v

    def state_dict(self):
        self.sync_to_model()
        return self.model.state_dict()

    # --- exact training resume (params + slots + step), reshard-aware -------
    def train_state_dict(self):
        """The COMPLETE resumable training state as a flat dict of
        Tensors wrapping the live (sharded) arrays: parameters, every
        optimizer slot, the step counter, and buffers. Keys are stable
        across topologies (`param.<name>` / `slot.<slot>.<name>` /
        `opt.step` / `buffer.<name>`), so a checkpoint saved under one
        mesh loads into a step built under another — the distributed
        checkpoint reshards leaf-by-leaf (reference role:
        fleet checkpointing of params + DygraphShardingOptimizer slots).
        The PRNG key is deliberately excluded: dropout streams are not
        resumable across topology changes (keys fold per-device)."""
        if self._state is None:
            self.init_state()
        s = self._state
        out = {}
        for n, v in s["params"].items():
            out[f"param.{n}"] = Tensor(v)
        for n, sd in s["opt"]["slots"].items():
            for k, v in sd.items():
                out[f"slot.{k}.{n}"] = Tensor(v)
        out["opt.step"] = Tensor(s["opt"]["step"])
        for n, v in s["buffers"].items():
            out[f"buffer.{n}"] = Tensor(v)
        return out

    def save_train_state(self, path):
        """Write the full training state with the distributed checkpoint
        writer (per-shard files, reshard-on-load). A host-side LR
        scheduler's position (warmup/decay progress) rides alongside as
        JSON — the device step counter alone would resume Adam bias
        correction correctly but silently restart the LR schedule."""
        save_train_checkpoint(self.train_state_dict(), path,
                              self.optimizer._learning_rate)

    def load_train_state(self, path):
        """Resume exactly: load a `save_train_state` checkpoint into
        THIS step's shardings (any source topology — the checkpoint
        loader reshards), then swap the loaded leaves into the live
        state. Strict: every leaf of this step's state must exist in the
        checkpoint — a partial match would silently mix loaded and
        freshly-initialized state (wrong model/config checkpoints fail
        loudly instead). The optimizer's step counter AND any host-side
        LR scheduler position resume mid-schedule."""
        if self._state is None:
            self.init_state()
        tgt = self.train_state_dict()
        load_train_checkpoint(tgt, path, self.optimizer._learning_rate)
        self._adopt(tgt)

    def _adopt(self, tgt):
        """Swap loaded train_state_dict leaves into the live state."""
        s = self._state
        s["params"] = {n: tgt[f"param.{n}"]._value for n in s["params"]}
        s["opt"]["slots"] = {
            n: {k: tgt[f"slot.{k}.{n}"]._value for k in sd}
            for n, sd in s["opt"]["slots"].items()}
        s["opt"]["step"] = tgt["opt.step"]._value
        s["buffers"] = {n: tgt[f"buffer.{n}"]._value
                        for n in s["buffers"]}

    # --- resilience: preemption safe points ----------------------------------
    def attach_preemption_guard(self, guard):
        """Consult `guard` (resilience.preemption.PreemptionGuard) at
        this step's safe points: a trip checkpoints through the attached
        manager and raises TrainingPreempted with the resumable path."""
        self._preemption_guard = guard
        return self

    def _check_preemption(self):
        """Safe-point probe, called between dispatches (never inside
        one): the live state is a complete, consistent post-step
        snapshot here, so the emergency checkpoint it writes is exactly
        what `load_train_state`/`rollback` resumes bit-for-bit."""
        g = self._preemption_guard
        if g is None or not g.check():
            return
        if self._preemption_handled is not None:
            # already checkpointed for this trip: a caller ignoring the
            # first TrainingPreempted must not silently keep training —
            # re-raise the same resumable exception, without re-saving
            raise self._preemption_handled
        from ..resilience.preemption import TrainingPreempted

        ckpt_dir = step_no = None
        if self._ckpt_mgr is not None and self._state is not None:
            try:
                step_no = int(np.asarray(self._state["opt"]["step"]))
            except (TypeError, ValueError):
                step_no = None  # manager picks newest+1
            ckpt_dir = self.save_checkpoint(step=step_no)
            try:
                from ..observability import flight as _flight
                from ..observability import metrics as _metrics

                _metrics.inc("preemption.checkpoints")
                _flight.record("preemption.checkpoint_saved",
                               path=ckpt_dir, step=step_no,
                               reason=g.reason)
            except Exception:  # pt-lint: ok[PT005]
                pass           # (observability fan-out guard: the
                # checkpoint landed — telemetry must not turn a clean
                # preemption exit into a crash)
        exit_code = getattr(g, "exit_code", 0)
        self._preemption_handled = TrainingPreempted(
            g.reason, checkpoint_dir=ckpt_dir, step=step_no,
            exit_code=exit_code)
        raise self._preemption_handled

    # --- resilience: rotation checkpointing + guard rollback -----------------
    def attach_checkpoint_manager(self, manager):
        """Use a `distributed.checkpoint.CheckpointManager` as this
        step's save target and (when a guard is active with no explicit
        rollback) the guard's rollback source."""
        self._ckpt_mgr = manager
        if self.guard is not None and self.guard.on_rollback is None:
            self.guard.set_rollback(self.rollback)
        return self

    def save_checkpoint(self, step=None, async_save=False):
        """Checkpoint the full training state through the attached
        manager (atomic, CRC'd, rotated); returns the checkpoint dir."""
        if self._ckpt_mgr is None:
            raise ValueError("no CheckpointManager attached "
                             "(attach_checkpoint_manager first)")
        return self._ckpt_mgr.save(self.train_state_dict(), step=step,
                                   async_save=async_save)

    def rollback(self):
        """Restore the newest VERIFIED checkpoint from the attached
        manager into the live state (corrupt ones are quarantined and
        skipped) — the guard escalation lands here after K consecutive
        non-finite steps.  Returns the checkpoint step restored."""
        if self._ckpt_mgr is None:
            raise ValueError("no CheckpointManager attached "
                             "(attach_checkpoint_manager first)")
        if self._state is None:
            self.init_state()
        tgt = self.train_state_dict()
        step = self._ckpt_mgr.restore(tgt)
        self._adopt(tgt)
        return step
