"""jit.to_static / save / load: graph capture to XLA.

Role parity: `paddle.jit.to_static` (python/paddle/jit/ — SOT bytecode capture
+ AST fallback + PirInterpreter execution) and `jit.save/load`.

TPU-first collapse (SURVEY §3.5 note): capture-by-tracing into one XLA
program replaces all three reference IRs. A decorated function/Layer traces
once per input signature; the compiled executable replays with zero Python
op dispatch. Autograd integration: in eager mode the whole compiled program
re-enters the op-dispatch gate as ONE op, so `loss.backward()` runs the
compiled VJP — the "same code runs eager and compiled" capability.

RNG under capture: the global generator key is threaded as an implicit
input/output of the traced program, so dropout stays correct and advances
state across replays (the reference needs its RNG-state tracker for this;
here it falls out of functional PRNG).
"""
from __future__ import annotations

import functools
import os
import pickle

import jax
import jax.numpy as jnp

from ..core import flags, rng
from ..core.dispatch import apply
from ..core.export_compat import get_jax_export
from ..core.tensor import Tensor
from ..nn.layer_base import Layer
from ..observability import flight as _flight
from ..observability import metrics as _metrics
from ..observability import xla_cost as _xla_cost


def _compile_retry():
    """Retry policy for trace/compile builds: transient compile-path
    faults (transient faults, injected jit.compile) retry with
    backoff before surfacing.  PADDLE_TPU_COMPILE_RETRIES tunes it."""
    from ..resilience.retry import env_policy

    return env_policy(
        "jit.compile", "PADDLE_TPU_COMPILE_RETRIES", 2,
        base_delay=0.05, max_delay=1.0,
        # deterministic user bugs (shape/type errors in the traced
        # fn) must not pay a second multi-second trace+compile
        give_up_on=(TypeError, ValueError, KeyError, AttributeError,
                    IndexError))


def _sig_of(x):
    if isinstance(x, Tensor):
        return ("T", tuple(x._value.shape), str(x._value.dtype))
    if isinstance(x, jax.Array):
        return ("A", tuple(x.shape), str(x.dtype))
    if isinstance(x, (list, tuple)):
        return tuple(_sig_of(v) for v in x)
    if isinstance(x, dict):
        return tuple(sorted((k, _sig_of(v)) for k, v in x.items()))
    return ("S", repr(x))


class StaticFunction:
    def __init__(self, function, input_spec=None, build_strategy=None,
                 backend=None, **kwargs):
        self._fn = function
        self._layer = None
        if isinstance(function, Layer):
            self._layer = function
            self._fn = function.forward
        elif hasattr(function, "__self__") and isinstance(
                function.__self__, Layer):
            self._layer = function.__self__
        self._cache = {}
        self._input_spec = input_spec
        functools.update_wrapper(self, self._fn)
        self._last_concrete = None

    @property
    def layer(self):
        return self._layer

    def _collect_state(self):
        if self._layer is None:
            return {}, {}
        return self._layer.functional_state()

    def _build(self, treedef, static_leaves, n_dyn, training):
        from ..resilience import faults as _faults

        # `jit.compile` fault point: a transient fault mid-compile —
        # the caller retries the build via
        # the jit.compile retry policy before raising
        _faults.fire("jit.compile",
                     fn=getattr(self._fn, "__name__", "fn"))
        from . import dy2static

        # AST tier: rewrite tensor-dependent if/while to lax.cond/while_loop
        # before tracing (reference dy2static transformers role); functions
        # without retrievable source trace as-is
        fn = dy2static.convert(self._fn)
        layer = self._layer

        def pure(params, buffers, key, *dyn_vals):
            leaves = list(static_leaves)
            it = iter(dyn_vals)
            leaves = [next(it) if l is _DYN else l for l in leaves]
            args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
            old_key = rng.default_generator.get_state()
            rng.default_generator.set_state(key)
            def wrap_leaf(v):
                return Tensor(v) if isinstance(v, jax.Array) else v

            # wrap dynamic leaves in BOTH args and kwargs (kwarg tensors must
            # reach the user function as Tensors too)
            w_args, w_kwargs = jax.tree_util.tree_map(wrap_leaf, (args, kwargs))
            try:
                with flags.trace_guard():
                    if layer is not None:
                        with layer.bind_state(params, buffers) as (np_, nb_):
                            out = fn(*w_args, **w_kwargs)
                            new_buffers = {n: nb_[n]._value for n in nb_}
                    else:
                        out = fn(*w_args, **w_kwargs)
                        new_buffers = {}
                new_key = rng.default_generator.get_state()
            finally:
                rng.default_generator.set_state(old_key)

            out_vals = jax.tree_util.tree_map(
                lambda o: o._value if isinstance(o, Tensor) else o, out,
                is_leaf=lambda x: isinstance(x, Tensor))
            return out_vals, new_buffers, new_key

        # compile-cost capture: with telemetry on, the first call per
        # signature AOT-compiles inside an `xla.compile:jit::<fn>` span
        # carrying cost_analysis FLOPs/bytes; with telemetry off (or
        # under an outer trace) this is a plain jit call
        return _xla_cost.instrument(
            jax.jit(pure),
            label=f"jit::{getattr(self._fn, '__name__', 'fn')}")

    def __call__(self, *args, **kwargs):
        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        dyn_idx = [i for i, l in enumerate(leaves)
                   if isinstance(l, (Tensor, jax.Array))]
        static_leaves = [
            _DYN if i in dyn_idx else l for i, l in enumerate(leaves)]
        training = self._layer.training if self._layer is not None else True
        key = (tuple(_sig_of(leaves[i]) for i in dyn_idx),
               tuple((i, _sig_of(l)) for i, l in enumerate(static_leaves)
                     if l is not _DYN), training)
        compiled = self._cache.get(key)
        if compiled is None:
            # trace-cache telemetry: a miss past the first build is a
            # RETRACE — the silent recompile class the round-5 "44 ms
            # IDLE per step" hunt chased by hand.  Counted, and the
            # triggering signature lands in the flight recorder.
            _metrics.inc("jit.trace_cache.miss")
            if self._cache:
                _metrics.inc("jit.retrace")
                _flight.record(
                    "jit.retrace",
                    fn=getattr(self._fn, "__name__", "fn"),
                    n_cached=len(self._cache),
                    dyn_sig=repr(key[0])[:200])
            compiled = _compile_retry().call(
                self._build, treedef, static_leaves, len(dyn_idx),
                training)
            self._cache[key] = compiled
        else:
            _metrics.inc("jit.trace_cache.hit")
        self._last_concrete = (compiled, treedef, static_leaves, dyn_idx)

        params, buffers = self._collect_state()
        gen_key = rng.default_generator.get_state()

        param_tensors = dict(self._layer.named_parameters()) \
            if self._layer is not None else {}
        dyn_args = [leaves[i] for i in dyn_idx]

        def mega(params_t, buffers_v, key_v, *dyn):
            vals = [d for d in dyn]
            return compiled(params_t, buffers_v, key_v, *vals)

        # Route through the dispatch gate: one op covering the whole program,
        # so eager backward() differentiates through the compiled executable.
        out_vals, new_buffers, new_key = apply(
            f"jit::{getattr(self._fn, '__name__', 'fn')}",
            mega, param_tensors, buffers, gen_key, *dyn_args)

        new_key_val = new_key._value if isinstance(new_key, Tensor) \
            else new_key
        # under an outer trace (e.g. jit.save exporting a Layer whose
        # forward is already a StaticFunction) the threaded key is a
        # tracer — writing it into the global generator would leak it
        if not isinstance(new_key_val, jax.core.Tracer):
            rng.default_generator.set_state(new_key_val)
        if self._layer is not None and new_buffers:
            named_b = dict(self._layer.named_buffers())
            items = new_buffers.items() if isinstance(new_buffers, dict) else []
            for n, v in items:
                if n in named_b:
                    named_b[n]._value = v._value if isinstance(v, Tensor) else v
        return out_vals

    def concrete_program(self):
        return self._last_concrete


class _Dyn:
    __slots__ = ()

    def __repr__(self):
        return "<dyn>"


_DYN = _Dyn()


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, **kwargs):
    """backend=None (default): trace + AST dy2static into one XLA program.
    backend='sot': the SOT-role eager-capture tier (jit/sot/) — arbitrary
    Python incl. source-less functions, graph breaks at value forces,
    guarded branch cache (reference's default `to_static` tier)."""
    def decorate(fn):
        if backend in ("sot", "SOT"):
            from .sot import symbolic_translate

            if isinstance(fn, Layer):
                fn.forward = symbolic_translate(fn.forward)
                return fn
            return symbolic_translate(fn)
        if isinstance(fn, Layer):
            fn.forward = StaticFunction(fn.forward, input_spec)
            return fn
        return StaticFunction(fn, input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def save(layer, path, input_spec=None, **configs):
    """Serialize a Layer (or StaticFunction) for deployment: params +
    jax.export'd StableHLO program when an input_spec is given.

    Parity: `paddle.jit.save` (program + persistables); the exported artifact
    is the AOT analog of the saved ProgramDesc.
    """
    if input_spec is not None:
        get_jax_export()  # fail before writing partial artifacts
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    from ..framework.io_utils import save as fsave

    target = layer
    if isinstance(layer, StaticFunction):
        target = layer.layer
    state = target.state_dict() if isinstance(target, Layer) else {}
    fsave(state, path + ".pdparams")

    exported_path = None
    if input_spec is not None and isinstance(target, Layer):
        params, buffers = target.functional_state()
        key = rng.default_generator.get_state()

        # if the Layer's forward was to_static-wrapped, export the original
        # forward — re-entering StaticFunction during export tracing would
        # thread the traced RNG key through the global generator
        fwd = target.forward
        call = fwd._fn if isinstance(fwd, StaticFunction) else target

        def pure(params, buffers, key, *dyn):
            with flags.trace_guard():
                with target.bind_state(params, buffers):
                    wrapped = [Tensor(v) for v in dyn]
                    out = call(*wrapped)
            return jax.tree_util.tree_map(
                lambda o: o._value if isinstance(o, Tensor) else o, out,
                is_leaf=lambda x: isinstance(x, Tensor))

        specs = [
            jax.ShapeDtypeStruct(tuple(s.shape), s.dtype)
            if hasattr(s, "shape") else s for s in input_spec
        ]
        was_training = target.training
        target.eval()
        try:
            exp = get_jax_export().export(jax.jit(pure))(
                jax.tree_util.tree_map(
                    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), params),
                jax.tree_util.tree_map(
                    lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), buffers),
                jax.ShapeDtypeStruct(key.shape, key.dtype), *specs)
            with open(path + ".pdmodel", "wb") as f:
                f.write(exp.serialize())
            exported_path = path + ".pdmodel"
        finally:
            if was_training:
                target.train()
    meta = {"exported": exported_path is not None,
            "class": type(target).__name__}
    if isinstance(target, Layer):
        meta["param_names"] = [n for n, _ in target.named_parameters()]
        meta["buffer_names"] = [n for n, _ in target.named_buffers()]
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer(Layer):
    """Deployment-side loaded model (parity: paddle.jit.TranslatedLayer /
    C++ jit::Layer)."""

    def __init__(self, exported, state, key, param_names=(), buffer_names=()):
        super().__init__()
        self._exported = exported
        self._state = state
        self._key = key
        self._param_names = list(param_names)
        self._buffer_names = list(buffer_names)

    def forward(self, *inputs):
        vals_of = {k: (v._value if isinstance(v, Tensor) else v)
                   for k, v in self._state.items()}
        p = {k: vals_of[k] for k in self._param_names if k in vals_of}
        b = {k: vals_of[k] for k in self._buffer_names if k in vals_of}
        vals = [x._value if isinstance(x, Tensor) else jnp.asarray(x)
                for x in inputs]
        out = self._exported.call(p, b, self._key, *vals)
        return jax.tree_util.tree_map(Tensor, out)


def load(path, **configs):
    from ..framework.io_utils import load as fload

    state = fload(path + ".pdparams") if os.path.exists(path + ".pdparams") \
        else {}
    meta_path = path + ".pdmeta"
    meta = {}
    if os.path.exists(meta_path):
        with open(meta_path, "rb") as f:
            meta = pickle.load(f)
    if meta.get("exported") and os.path.exists(path + ".pdmodel"):
        with open(path + ".pdmodel", "rb") as f:
            exp = get_jax_export().deserialize(bytearray(f.read()))
        return TranslatedLayer(exp, state, rng.default_generator.get_state(),
                               meta.get("param_names", ()),
                               meta.get("buffer_names", ()))
    raise FileNotFoundError(
        f"no exported program at {path}.pdmodel; load params with "
        f"paddle_tpu.load({path!r} + '.pdparams') instead")
