"""Global runtime flags and modes.

Role parity: `paddle/phi/core/flags.cc` (FLAGS_*) + dygraph/static mode
switches (`python/paddle/base/framework.py` in_dynamic_or_pir_mode). Here the
two modes are: eager (op-by-op with tape autograd) and trace (inside a
`jax.jit`/`jax.grad` transform, where autograd and fusion belong to XLA).
"""
from __future__ import annotations

import contextlib
import os
import threading


class _State(threading.local):
    def __init__(self):
        self.grad_enabled = True
        self.tracing = 0  # nesting depth of functional tracing
        self.static_mode = False  # paddle.enable_static() graph-build mode

_state = _State()


def is_grad_enabled() -> bool:
    return _state.grad_enabled and not _state.tracing


def set_grad_enabled(mode: bool):
    _state.grad_enabled = bool(mode)


@contextlib.contextmanager
def no_grad_guard():
    old = _state.grad_enabled
    _state.grad_enabled = False
    try:
        yield
    finally:
        _state.grad_enabled = old


@contextlib.contextmanager
def enable_grad_guard():
    old = _state.grad_enabled
    _state.grad_enabled = True
    try:
        yield
    finally:
        _state.grad_enabled = old


def in_trace() -> bool:
    return _state.tracing > 0


def in_static_mode() -> bool:
    return _state.static_mode and not _state.tracing


def set_static_mode(on: bool):
    _state.static_mode = bool(on)


@contextlib.contextmanager
def trace_guard():
    """Inside: ops run raw on jax values; no tape nodes are created."""
    _state.tracing += 1
    try:
        yield
    finally:
        _state.tracing -= 1


# --- FLAGS_* style runtime flags (paddle.set_flags parity) -------------------
def _env_bool(name, default="0"):
    return os.environ.get(name, default) in ("1", "true", "True")


_flags = {
    "FLAGS_check_nan_inf": _env_bool("FLAGS_check_nan_inf"),
    "FLAGS_eager_jit_ops": _env_bool("FLAGS_eager_jit_ops"),
    # kernel-granular degradation (VERDICT r2 task 3): a broken Pallas
    # kernel must cost speed, not the whole datapoint. The master flag
    # disables the entire tier; per-kernel flags disable one dispatch site.
    "FLAGS_disable_pallas": _env_bool("FLAGS_disable_pallas"),
    "FLAGS_disable_pallas_flash": _env_bool("FLAGS_disable_pallas_flash"),
    "FLAGS_disable_pallas_fused_norm": _env_bool("FLAGS_disable_pallas_fused_norm"),
    # (ring attention is jnp/lax collectives, not pallas_call — no flag)
    "FLAGS_disable_pallas_rope": _env_bool("FLAGS_disable_pallas_rope"),
    "FLAGS_disable_pallas_decode": _env_bool("FLAGS_disable_pallas_decode"),
    # fused vision kernels (ISSUE 10): Swin window attention and the
    # conv+norm+act inference fusion
    "FLAGS_disable_pallas_window_attn": _env_bool(
        "FLAGS_disable_pallas_window_attn"),
    "FLAGS_disable_pallas_conv_norm": _env_bool(
        "FLAGS_disable_pallas_conv_norm"),
    "FLAGS_use_autotune": _env_bool("FLAGS_use_autotune", "1"),
    # Extra scoped-VMEM budget for Pallas kernels (KiB, 0 = compiler
    # default of 16 MiB). Kernels that walk heads in a static loop keep
    # all heads' intermediates on the Mosaic stack and need ~32-64 MiB at
    # training block sizes; v5e has 128 MiB VMEM, so raising the limit
    # is real headroom, not overcommit. Applied via jit compiler_options
    # at the train-step jit sites (the local XLA_FLAGS parser rejects
    # TPU-only flags on a CPU-built jaxlib, so env XLA_FLAGS cannot
    # carry it).
    "FLAGS_scoped_vmem_limit_kib": int(
        os.environ.get("FLAGS_scoped_vmem_limit_kib", "0")),
}


def jit_compiler_options():
    """Per-jit XLA compiler options implied by flags (None when empty):
    pass as jax.jit(..., compiler_options=...) at hot jit sites."""
    lim = _flags.get("FLAGS_scoped_vmem_limit_kib") or 0
    if lim:
        return {"xla_tpu_scoped_vmem_limit_kib": int(lim)}
    return None


def pallas_enabled(kernel: str) -> bool:
    """Dispatch-site gate for one Pallas kernel ('flash', 'fused_norm',
    'rope', 'ring', 'decode', 'window_attn', 'conv_norm')."""
    return not (_flags.get("FLAGS_disable_pallas")
                or _flags.get(f"FLAGS_disable_pallas_{kernel}"))


def set_flags(d: dict):
    _flags.update(d)


def get_flags(keys=None):
    if keys is None:
        return dict(_flags)
    if isinstance(keys, str):
        return {keys: _flags.get(keys)}
    return {k: _flags.get(k) for k in keys}
