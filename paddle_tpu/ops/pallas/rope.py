"""Fused rotary position embedding — Pallas TPU kernel.

Role parity: `paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu`
(exposed as `incubate.nn.functional.fused_rotary_position_embedding`).

Design (TPU-first):
  * Elementwise rotate in one VMEM pass: out = x·cos + rotate_half(x)·sin
    (neox layout — the half-split rotation keeps lane access contiguous;
    the interleaved layout would stride lanes and falls back to jnp).
  * q/k/v share the same (cos, sin) phases, so one kernel instance per
    tensor; the grid walks (B·S) row-blocks with heads×dim resident.
  * Backward is the same kernel with the adjoint rotation
    (rotate_half^T(u) = concat(u2, −u1)) — a Pallas kernel both ways.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _pick_block


def rope_available(x) -> bool:
    from ...core import flags

    if not flags.pallas_enabled("rope"):
        return False
    if x.ndim != 4:
        return False
    d = x.shape[-1]
    h = x.shape[-2]
    if d % 2 != 0 or (h * d) % 128 != 0:
        return False
    return not _interpret()


def _rope_kernel(x_ref, cos_ref, sin_ref, o_ref, *, adjoint):
    x = x_ref[:].astype(jnp.float32)       # [br, H, D]
    cos = cos_ref[:].astype(jnp.float32)   # [br, D]
    sin = sin_ref[:].astype(jnp.float32)
    d = x.shape[-1]
    half = d // 2
    x1 = x[..., :half]
    x2 = x[..., half:]
    if not adjoint:
        rot = jnp.concatenate([-x2, x1], axis=-1)
    else:
        rot = jnp.concatenate([x2, -x1], axis=-1)
    out = x * cos[:, None, :] + rot * sin[:, None, :]
    o_ref[:] = out.astype(o_ref.dtype)


def _rope_call(x, cos, sin, adjoint, interpret=None):
    b, s, h, d = x.shape
    rows = b * s
    x2 = x.reshape(rows, h, d)
    # phases broadcast to [rows, d] (cos/sin come in as [B|1, S, 1, D])
    cos2 = jnp.broadcast_to(cos.reshape(cos.shape[0], s, d),
                            (b, s, d)).reshape(rows, d)
    sin2 = jnp.broadcast_to(sin.reshape(sin.shape[0], s, d),
                            (b, s, d)).reshape(rows, d)
    br = _pick_block(rows, max(8, min(512, (1 << 20) // (4 * h * d))))
    grid = (pl.cdiv(rows, br),)
    out = pl.pallas_call(
        functools.partial(_rope_kernel, adjoint=adjoint),
        grid=grid,
        in_specs=[pl.BlockSpec((br, h, d), lambda r: (r, 0, 0)),
                  pl.BlockSpec((br, d), lambda r: (r, 0)),
                  pl.BlockSpec((br, d), lambda r: (r, 0))],
        out_specs=pl.BlockSpec((br, h, d), lambda r: (r, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, h, d), x.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="rope_rotate",
    )(x2, cos2, sin2)
    return out.reshape(b, s, h, d)


@jax.custom_vjp
def rope_pallas(x, cos, sin):
    """x: [B,S,H,D]; cos/sin: [B|1, S, 1, D] neox-layout phases."""
    return _rope_call(x, cos, sin, adjoint=False)


def _rope_fwd(x, cos, sin):
    return _rope_call(x, cos, sin, adjoint=False), (cos, sin)


def _rope_bwd(saved, g):
    cos, sin = saved
    return _rope_call(g, cos, sin, adjoint=True), None, None


rope_pallas.defvjp(_rope_fwd, _rope_bwd)
