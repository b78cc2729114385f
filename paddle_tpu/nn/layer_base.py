"""nn.Layer: module base class.

Role parity: `paddle.nn.Layer` (python/paddle/nn/layer/layers.py:334) —
parameter/buffer/sublayer registries, hooks, state_dict, train/eval, to().

TPU-first addition: `functional_state` / `functional_call` — the bridge that
lets the same Layer run eagerly (params as mutable Tensors) or inside a
traced/jitted/sharded program (params as a pytree of jax arrays), which is
what jit.to_static and every parallelism recipe build on.
"""
from __future__ import annotations

import contextlib
import threading
from collections import OrderedDict

import jax
import numpy as np

from ..core import dtypes as _dtypes
from ..core import flags
from ..core.tensor import Parameter, Tensor

# the layers being called on this thread, innermost last (trace time only)
_scope_stack = threading.local()


class HookRemoveHelper:
    def __init__(self, hooks, hook_id):
        self._hooks = hooks
        self._id = hook_id

    def remove(self):
        self._hooks.pop(self._id, None)


class Layer:
    def __init__(self, name_scope=None, dtype="float32"):
        object.__setattr__(self, "_parameters", OrderedDict())
        object.__setattr__(self, "_buffers", OrderedDict())
        object.__setattr__(self, "_sub_layers", OrderedDict())
        self._non_persistable_buffer_names = set()
        self.training = True
        self._dtype = _dtypes.convert_dtype(dtype)
        self._name_scope = name_scope or self.__class__.__name__.lower()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._hook_id = 0

    # --- attribute magic -----------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call Layer.__init__ before assigning params")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call Layer.__init__ before assigning layers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
        else:
            if params is not None and name in params:
                if value is None:
                    params[name] = None
                    return
                raise TypeError(
                    f"cannot assign non-Parameter to parameter slot {name!r}")
            if buffers is not None and name in buffers:
                buffers[name] = value if (
                    value is None or isinstance(value, Tensor)
                ) else Tensor(value)
                return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + \
            list(self._buffers) + list(self._sub_layers)

    # --- registration --------------------------------------------------------
    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            raise TypeError("add_parameter expects a Parameter")
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        if not isinstance(sublayer, Layer):
            raise TypeError("add_sublayer expects a Layer")
        self._sub_layers[str(name)] = sublayer
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        if tensor is not None and not isinstance(tensor, Tensor):
            tensor = Tensor(tensor)
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        elif name in self._non_persistable_buffer_names:
            self._non_persistable_buffer_names.discard(name)
        return tensor

    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None):
        from .initializer import Constant, _resolve_initializer

        dtype = _dtypes.convert_dtype(dtype) or self._dtype
        init = None
        name = None
        learning_rate = 1.0
        if attr is not None and attr is not False:
            init = getattr(attr, "initializer", None)
            name = getattr(attr, "name", None)
            learning_rate = getattr(attr, "learning_rate", 1.0)
        if init is None:
            init = default_initializer
        if init is None:
            init = Constant(0.0) if is_bias else None
        init = _resolve_initializer(init, shape, dtype, is_bias)
        data = init(shape, dtype)
        p = Parameter(data, name=name)
        p.optimize_attr["learning_rate"] = learning_rate
        return p

    # --- iteration -----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (prefix + name if not prefix else f"{prefix}.{name}"), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix):
                    if id(p) not in seen:
                        seen.add(id(p))
                        yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                yield from layer.named_buffers(sub_prefix)

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def named_children(self):
        for name, layer in self._sub_layers.items():
            if layer is not None:
                yield name, layer

    def children(self):
        for _, l in self.named_children():
            yield l

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, layer in self._sub_layers.items():
            if layer is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, layer
            yield from layer.named_sublayers(sub_prefix)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # --- modes ---------------------------------------------------------------
    def train(self):
        self.training = True
        for l in self.sublayers():
            l.training = True
        return self

    def eval(self):
        self.training = False
        for l in self.sublayers():
            l.training = False
        return self

    # --- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        out = OrderedDict() if destination is None else destination
        for n, p in self.named_parameters(prefix=structured_name_prefix.rstrip(".")):
            out[n] = p
        # identity-based filter: each layer owns its non-persistable set
        skip_ids = set()
        for _, layer in self.named_sublayers(include_self=True):
            for name in layer._non_persistable_buffer_names:
                b = layer._buffers.get(name)
                if b is not None:
                    skip_ids.add(id(b))
        for n, b in self.named_buffers(prefix=structured_name_prefix.rstrip(".")):
            if id(b) not in skip_ids:
                out[n] = b
        return out

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                tgt = own[k]
                val = v._value if isinstance(v, Tensor) else v
                val = np.asarray(val) if not hasattr(val, "dtype") else val
                if tuple(tgt._value.shape) != tuple(val.shape):
                    raise ValueError(
                        f"shape mismatch for {k}: {tgt.shape} vs {list(val.shape)}")
                tgt.set_value(val)
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict

    # --- dtype / device ------------------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            dtype = _dtypes.convert_dtype(dtype)
            for p in self.parameters():
                if jax.numpy.issubdtype(p._value.dtype, np.floating):
                    p._value = p._value.astype(dtype)
            for b in self.buffers():
                if jax.numpy.issubdtype(b._value.dtype, np.floating):
                    b._value = b._value.astype(dtype)
            self._dtype = dtype
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    # --- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook):
        self._hook_id += 1
        self._forward_pre_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_pre_hooks, self._hook_id)

    def register_forward_post_hook(self, hook):
        self._hook_id += 1
        self._forward_post_hooks[self._hook_id] = hook
        return HookRemoveHelper(self._forward_post_hooks, self._hook_id)

    # --- call ----------------------------------------------------------------
    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        if flags.in_trace():
            out = self._forward_in_scope(inputs, kwargs)
        else:
            out = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def _forward_in_scope(self, inputs, kwargs):
        """Inside a traced program, `forward` runs under a
        `jax.named_scope` of this layer's module path component, so every
        op it issues carries `.../gpt/h.3/attn/...` in its `op_name`
        (what `observability.xla_cost.program_ledger` joins a device
        trace to).  The component is the name under which the calling
        layer holds this one, as `named_sublayers` spells it — a layer
        held in a `LayerList`, which is iterated and never called, takes
        `<list>.<index>` — and the class name for a layer called from
        outside any layer.  Trace time only: eager calls never get here.
        """
        stack = _scope_stack.__dict__.setdefault("layers", [])
        name = None
        if stack:
            name = next((n for n, l in stack[-1].named_sublayers()
                         if l is self), None)
        stack.append(self)
        try:
            with jax.named_scope(name or type(self).__name__):
                return self.forward(*inputs, **kwargs)
        finally:
            stack.pop()

    # --- functional bridge (TPU-native jit/shard path) -----------------------
    def functional_state(self):
        """Return (params, buffers) as flat name->jax.Array dicts."""
        params = {n: p._value for n, p in self.named_parameters()}
        buffers = {n: b._value for n, b in self.named_buffers()}
        return params, buffers

    @contextlib.contextmanager
    def bind_state(self, params=None, buffers=None):
        """Temporarily swap parameter/buffer payloads (e.g. with tracers),
        restoring (and surfacing buffer mutations) on exit."""
        named_p = dict(self.named_parameters())
        named_b = dict(self.named_buffers())
        saved_p = {n: t._value for n, t in named_p.items()}
        saved_b = {n: t._value for n, t in named_b.items()}
        try:
            if params:
                for n, v in params.items():
                    if n in named_p:
                        named_p[n]._value = v
            if buffers:
                for n, v in buffers.items():
                    if n in named_b:
                        named_b[n]._value = v
            yield named_p, named_b
        finally:
            for n, t in named_p.items():
                t._value = saved_p[n]
            for n, t in named_b.items():
                t._value = saved_b[n]

    def functional_call(self, params, buffers, *inputs, **kwargs):
        """Pure apply: run forward with the given arrays; returns
        (outputs, new_buffers). Safe to call under jax transforms."""
        from ..core import flags

        with self.bind_state(params, buffers) as (named_p, named_b):
            with flags.trace_guard():
                wrapped = [Tensor(x, stop_gradient=True)
                           if not isinstance(x, Tensor) and hasattr(x, "shape")
                           else x for x in inputs]
                # params need stop_gradient=False so downstream logic branches
                # identically to eager
                out = self(*wrapped, **kwargs)
            new_buffers = {n: named_b[n]._value for n in named_b}

        def unwrap(o):
            return o._value if isinstance(o, Tensor) else o

        return jax.tree_util.tree_map(
            unwrap, out,
            is_leaf=lambda x: isinstance(x, Tensor)), new_buffers

    def full_name(self):
        return self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = [f"{self.__class__.__name__}({extra}"]
        for name, layer in self._sub_layers.items():
            rep = repr(layer).split("\n")
            body = "\n  ".join(rep)
            lines.append(f"  ({name}): {body}")
        return "\n".join(lines) + ")"
