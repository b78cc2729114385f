"""Fused (residual+bias+)RMS/LayerNorm — Pallas TPU kernels.

Role parity: `paddle/phi/kernels/fusion/gpu/fused_layernorm_kernel.cu` and
`fused_rms_norm` (exposed as `incubate.nn.functional.fused_rms_norm` /
`fused_layer_norm` in the reference).

Design (TPU-first):
  * One VMEM pass per row-block: optional bias-add + residual-add, the
    norm statistics in f32, scale(+shift) — the pre-norm sum `z` is the
    second output (the transformer residual stream), so HBM sees exactly
    one read of (x, residual) and one write of (y, z).
  * Rows = all leading dims flattened; the feature axis stays whole in
    lanes (d multiple of 128 for the Pallas path; anything else falls
    back to the jnp body, which XLA fuses well for small d anyway).
  * Backward is recompute-style jnp (bandwidth-bound elementwise +
    row reductions that XLA emits as a single fused pass — see PERF.md
    for what has and hasn't been measured on hardware). The Pallas win
    is the forward, which sits on the decode / inference hot path and
    inside every transformer layer.
  * Non-TPU backends run the same kernel through the Pallas interpreter
    in tests (tests/test_pallas.py) to validate kernel code on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .flash_attention import _interpret, _pick_block


def fused_norm_available(x, weight, bias) -> bool:
    from ...core import flags

    if not flags.pallas_enabled("fused_norm"):
        return False
    d = x.shape[-1]
    if d % 128 != 0 or d > 16384:
        return False
    if weight is not None and weight.shape != (d,):
        return False
    if bias is not None and bias.shape != (d,):
        return False
    return not _interpret()


def _row_block(rows, d):
    """Row-block size: big enough to amortize, small enough for VMEM
    (~2MB f32 working set), and dividing rows (full-array refs)."""
    pref = max(8, min(256, (2 << 20) // (4 * d)))
    return _pick_block(rows, pref)


# ============================ kernels ============================

def _norm_kernel(*refs, eps, kind, has_w, has_b, has_bias, has_res,
                 want_z):
    # refs order: x, [w], [b], [bias], [res], out, [z_out]
    i = 0
    x_ref = refs[i]; i += 1
    w_ref = refs[i] if has_w else None; i += has_w
    b_ref = refs[i] if has_b else None; i += has_b
    bias_ref = refs[i] if has_bias else None; i += has_bias
    res_ref = refs[i] if has_res else None; i += has_res
    o_ref = refs[i]; i += 1
    z_ref = refs[i] if want_z else None

    z = x_ref[:]
    if has_bias:
        z = z + bias_ref[:]
    if has_res:
        z = z + res_ref[:]
    if want_z:
        z_ref[:] = z.astype(z_ref.dtype)
    x32 = z.astype(jnp.float32)
    if kind == "rms":
        ms = jnp.mean(x32 * x32, axis=1, keepdims=True)
        y = x32 * jax.lax.rsqrt(ms + eps)
    else:
        mu = jnp.mean(x32, axis=1, keepdims=True)
        xc = x32 - mu
        var = jnp.mean(xc * xc, axis=1, keepdims=True)
        y = xc * jax.lax.rsqrt(var + eps)
    if has_w:
        y = y * w_ref[:].astype(jnp.float32)
    if has_b:
        y = y + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _pallas_norm_fwd(x, w, b, bias, res, eps, kind, want_z,
                     interpret=None):
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    br = _row_block(rows, d)
    grid = (pl.cdiv(rows, br),)

    row_spec = pl.BlockSpec((br, d), lambda r: (r, 0))
    vec_spec = pl.BlockSpec((1, d), lambda r: (0, 0))

    operands, in_specs = [x2], [row_spec]
    if w is not None:
        operands.append(w.reshape(1, d)); in_specs.append(vec_spec)
    if b is not None:
        operands.append(b.reshape(1, d)); in_specs.append(vec_spec)
    if bias is not None:
        operands.append(bias.reshape(1, d)); in_specs.append(vec_spec)
    if res is not None:
        operands.append(res.reshape(rows, d)); in_specs.append(row_spec)

    out_specs = [row_spec]
    out_shape = [jax.ShapeDtypeStruct((rows, d), x.dtype)]
    if want_z:
        out_specs.append(row_spec)
        out_shape.append(jax.ShapeDtypeStruct((rows, d), x.dtype))

    outs = pl.pallas_call(
        functools.partial(
            _norm_kernel, eps=eps, kind=kind, has_w=w is not None,
            has_b=b is not None, has_bias=bias is not None,
            has_res=res is not None, want_z=want_z),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        interpret=_interpret() if interpret is None else interpret,
        name="fused_norm_fwd",
    )(*operands)
    if want_z:
        return outs[0].reshape(shape), outs[1].reshape(shape)
    return outs[0].reshape(shape), None


# ============================ vjp (jnp recompute) ============================

def _norm_bwd_math(z, w, gy, eps, kind):
    """dz, dw, db from upstream gy at pre-norm activation z."""
    z32 = z.astype(jnp.float32)
    g32 = gy.astype(jnp.float32)
    if kind == "rms":
        ms = jnp.mean(z32 * z32, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(ms + eps)
        xhat = z32 * inv
    else:
        mu = jnp.mean(z32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(z32 - mu), axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + eps)
        xhat = (z32 - mu) * inv
    gw = g32 * w.astype(jnp.float32) if w is not None else g32
    if kind == "rms":
        dz = inv * (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    else:
        dz = inv * (gw - jnp.mean(gw, axis=-1, keepdims=True)
                    - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True))
    rdims = tuple(range(z.ndim - 1))
    dw = jnp.sum(g32 * xhat, axis=rdims) if w is not None else None
    db = jnp.sum(g32, axis=rdims)
    return dz.astype(z.dtype), dw, db


_SPECIALIZATIONS = {}


def _build(kind, has_w, has_b, has_bias, has_res, eps):
    """Specialized custom-vjp fused-norm fn for one operand combination
    (custom_vjp needs a fixed positional signature — None args don't mix)."""
    key = (kind, has_w, has_b, has_bias, has_res, float(eps))
    fn = _SPECIALIZATIONS.get(key)
    if fn is not None:
        return fn
    want_z = has_bias or has_res

    def _unpack(args):
        it = iter(args)
        x = next(it)
        w = next(it) if has_w else None
        b = next(it) if has_b else None
        bias = next(it) if has_bias else None
        res = next(it) if has_res else None
        return x, w, b, bias, res

    @jax.custom_vjp
    def core(*args):
        x, w, b, bias, res = _unpack(args)
        y, z = _pallas_norm_fwd(x, w, b, bias, res, eps, kind, want_z)
        return (y, z) if want_z else y

    def core_fwd(*args):
        x, w, b, bias, res = _unpack(args)
        y, z = _pallas_norm_fwd(x, w, b, bias, res, eps, kind, want_z)
        # save the pre-norm activation (z when the op computes it, else x
        # itself) — backward recomputes the stats from it
        out = (y, z) if want_z else y
        return out, (z if want_z else x, w)

    def core_bwd(saved, g):
        z, w = saved
        gy = g[0] if want_z else g
        dz, dw, db = _norm_bwd_math(z, w, gy, eps, kind)
        if want_z:  # z is an output too: its cotangent adds straight in
            dz = dz + g[1].astype(dz.dtype)
        rdims = tuple(range(z.ndim - 1))
        grads = [dz]
        if has_w:
            grads.append(dw.astype(w.dtype))
        if has_b:
            grads.append(db.astype(z.dtype))
        if has_bias:
            grads.append(jnp.sum(dz.astype(jnp.float32),
                                 axis=rdims).astype(z.dtype))
        if has_res:
            grads.append(dz)
        return tuple(grads)

    core.defvjp(core_fwd, core_bwd)
    _SPECIALIZATIONS[key] = core
    return core


def fused_norm_pallas(x, w=None, b=None, bias=None, res=None,
                      eps=1e-6, kind="rms"):
    """Public fused-norm entry (jax arrays in/out).

    Returns `out` — or `(out, z)` with the pre-norm residual stream when
    `bias`/`res` participate (matching the reference fused op contract).
    """
    fn = _build(kind, w is not None, b is not None, bias is not None,
                res is not None, eps)
    args = [a for a in (x, w, b, bias, res) if a is not None]
    return fn(*args)
