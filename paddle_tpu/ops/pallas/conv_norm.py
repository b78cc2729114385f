"""Fused conv → norm scale/shift → activation — Pallas TPU kernel.

Role parity: the conv+BN+act fusions under
`paddle/phi/kernels/fusion/gpu/` (conv_bn fuse pass); here it is the
ISSUE-10 vision companion to the fused Swin window-attention kernel —
the ResNet/MobileNet stem+block pattern `relu(bn(conv(x)))` runs as ONE
kernel: the conv accumulates in f32, the folded batch-norm scale/shift
and the activation apply in VMEM, and the pre-activation conv output
never materializes in HBM.

Design (TPU-first):
  * The conv is expressed as kh*kw shifted MXU matmuls: for each kernel
    tap (dy, dx), a [C_out, C_in] weight slice contracts against the
    strided input window flattened to [C_in, rows*W_out]. No im2col
    buffer, no layout change — operands stay NCHW ([C, H, W] per batch,
    W in lanes), the layout the model tensors already carry.
  * Depthwise convs (groups == C_in == C_out, the MobileNet block) take
    a VPU elementwise path over the same shifted windows: the weight
    tap is [C, 1] and broadcasts down the flattened pixels.
  * Norm folding happens at the call site (`scale = gamma/sqrt(var+eps)`,
    `shift = beta - mean*scale + conv_bias*scale`): the kernel sees one
    affine — so the tier requires FROZEN norm stats (training-mode batch
    norm needs live batch stats; the dispatch gate routes it to the
    composed ops). AD still works: a custom VJP runs the fused kernel
    forward and differentiates the reference composed ops backward
    (frozen-BN fine-tuning, input-gradient probes).
  * Spatial padding is applied by the caller (`jnp.pad`, a cheap fused
    memset+copy) so every kernel tap is a static in-bounds slice.
  * The output-row band per grid cell is the autotuned parameter under
    the existing cache.
  * Non-TPU backends run the same kernel through the Pallas interpreter
    in tests; the eager CPU fallback is the jnp reference
    (`conv_bn_act_ref`, lax.conv + affine + act).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import flight as _flight
from ...observability import metrics as _metrics
from .flash_attention import _interpret

__all__ = ["fused_conv_bn_act", "conv_bn_act_ref",
           "conv_bn_act_available"]

_VMEM_BOUND = 10 * 1024 * 1024

_ACTS = ("relu", "relu6", None)


def _apply_act(y, act):
    if act == "relu":
        return jnp.maximum(y, 0.0)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    return y


def conv_bn_act_ref(x, w, scale, shift, *, stride, padding, act,
                    depthwise=False):
    """jnp reference (the CPU dispatch fallback): lax.conv NCHW + folded
    affine + activation. x: [B, Cin, H, W]; w: [Cout, Cin/groups, kh, kw];
    scale/shift: [Cout]."""
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    p = (padding, padding) if isinstance(padding, int) else tuple(padding)
    out = jax.lax.conv_general_dilated(
        x.astype(jnp.float32), w.astype(jnp.float32), s,
        [(p[0], p[0]), (p[1], p[1])],
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=x.shape[1] if depthwise else 1)
    out = out * scale.astype(jnp.float32).reshape(1, -1, 1, 1) + \
        shift.astype(jnp.float32).reshape(1, -1, 1, 1)
    return _apply_act(out, act).astype(x.dtype)


# ========================= Pallas kernel =========================

def _conv_kernel(x_ref, w_ref, sc_ref, sh_ref, o_ref, *, kh, kw, sh_, sw_,
                 rows, w_out, act, depthwise):
    """x_ref: [Cin, rows_in, W_pad] (the full padded image — the row
    band selects its window with a provably-aligned dynamic offset);
    w_ref: [Cout, Cin_g, kh, kw]; sc/sh: [Cout, 1]; o_ref:
    [Cout, rows, W_out]."""
    cin = x_ref.shape[0]
    cout = o_ref.shape[0]
    r0 = pl.program_id(1) * (rows * sh_)    # static multiple per band
    acc = jnp.zeros((cout, rows * w_out), jnp.float32)
    for dy in range(kh):
        # rows dy, dy+s, ..., dy+(rows-1)*s of the padded input
        band = x_ref[:, pl.ds(r0 + dy, (rows - 1) * sh_ + 1), :]
        band = band[:, ::sh_, :]                    # [Cin, rows, W_pad]
        for dx in range(kw):
            win = band[:, :, dx:dx + (w_out - 1) * sw_ + 1:sw_]
            win = win.reshape(cin, rows * w_out).astype(jnp.float32)
            if depthwise:
                tap = w_ref[:, :, dy, dx].astype(jnp.float32)  # [C, 1]
                acc = acc + tap * win
            else:
                tap = w_ref[:, :, dy, dx].astype(jnp.float32)
                acc = acc + jax.lax.dot_general(
                    tap, win, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
    y = acc * sc_ref[:].astype(jnp.float32) + sh_ref[:].astype(
        jnp.float32)
    y = _apply_act(y, act)
    o_ref[:] = y.reshape(cout, rows, w_out).astype(o_ref.dtype)


def _out_dim(n, k, s, p):
    return (n + 2 * p - k) // s + 1


def _pick_rows(h_out, h_pad, cin, cin_g, cout, w_pad, w_out, kh, kw,
               itemsize):
    """Candidate output-row bands that divide H_out and fit the VMEM
    bound. The FULL padded image (cin*h_pad*w_pad) is resident in every
    cell regardless of band (the BlockSpec in `_conv_pallas` maps the
    whole image); the band only sizes the accumulator — for stride > 1
    sizing the input as the covered output rows would undercount by up
    to the stride factor and admit bands whose real cell exceeds the
    bound."""
    cands = []
    for r in (h_out, 56, 28, 16, 14, 8, 7, 4, 2, 1):
        if r <= h_out and h_out % r == 0 and r not in cands:
            # weight term uses cin_g ([C,1,kh,kw] for depthwise — a
            # cin-factor overestimate here rejected every band on the
            # exact MobileNet layers the VPU path targets)
            est = (cin * h_pad * w_pad * itemsize
                   + cout * cin_g * kh * kw * itemsize
                   + 2 * cout * r * w_out * 4)
            if est <= _VMEM_BOUND:
                cands.append(r)
    return cands


def conv_bn_act_available(x_shape, w_shape, stride, dilation, groups,
                          dtype_itemsize=4, training=False) -> bool:
    """Dispatch gate: TPU backend, pallas tier enabled, inference only
    (the scale/shift folding needs frozen norm stats), dense or
    depthwise conv, dilation 1, and a VMEM-feasible shape."""
    from ...core import flags

    if not flags.pallas_enabled("conv_norm"):
        return False
    if training:
        return False
    if len(x_shape) != 4 or len(w_shape) != 4:
        return False
    b, cin, h, w = x_shape
    cout, cin_g, kh, kw = w_shape
    d = (dilation, dilation) if isinstance(dilation, int) else dilation
    if tuple(d) != (1, 1):
        return False
    depthwise = groups == cin and cout == cin and cin_g == 1
    if groups != 1 and not depthwise:
        return False
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    if s[0] < 1 or s[1] < 1:
        return False
    # one full-image cell must fit even at the smallest band
    est = (cin * (h + kh) * (w + kw) * dtype_itemsize
           + cout * cin_g * kh * kw * dtype_itemsize
           + 2 * cout * w * 4)
    if est > _VMEM_BOUND:
        _metrics.inc("conv_norm.gate_reject", reason="vmem")
        _flight.record("conv_norm.gate_reject", reason="vmem",
                       x_shape=list(x_shape), w_shape=list(w_shape),
                       est_bytes=est)
        return False
    return not _interpret()


def _tuned_rows(x, w, stride, padding, act, depthwise, h_out, w_out,
                w_pad, cands):
    from . import autotune

    if len(cands) <= 1:
        return cands[0] if cands else h_out

    def run(rows):
        import numpy as np

        rs = np.random.RandomState(0)
        xv = jnp.asarray(rs.randn(*x.shape), x.dtype)
        wv = jnp.asarray(rs.randn(*w.shape), w.dtype)
        sc = jnp.ones((w.shape[0],), jnp.float32)
        sf = jnp.zeros((w.shape[0],), jnp.float32)

        def f(xv):
            # inference kernel: forward only; output is reshaped back to
            # the input's spatial shape only when shapes match (stride 1,
            # same padding) — otherwise chain via a resize-free trick:
            # time the kernel on a same-shaped dummy reduction feed
            y = fused_conv_bn_act(xv, wv, sc, sf, stride=stride,
                                  padding=padding, act=act,
                                  _rows_override=rows)
            # shape-preserving chain: fold the output back onto x's shape
            return jnp.broadcast_to(
                y.astype(xv.dtype).mean(), xv.shape) + xv * 0.5

        return f, xv

    sig = (f"{'x'.join(map(str, x.shape))}|{'x'.join(map(str, w.shape))}"
           f"|s{stride}|p{padding}|{'dw' if depthwise else 'g1'}"
           f"|{jnp.dtype(x.dtype).name}")
    return autotune.pick("conv_bn_act", sig, cands, run, cands[0])


def fused_conv_bn_act(x, w, scale, shift, *, stride=1, padding=0,
                      act="relu", _rows_override=None):
    """Public fused conv+norm+act entry (jax arrays in/out, NCHW).

    x: [B, Cin, H, W]; w: [Cout, Cin/groups, kh, kw] (groups inferred:
    dense when Cin_g == Cin, depthwise when Cin_g == 1 and Cout == Cin);
    scale/shift: [Cout] folded norm affine (conv bias pre-folded into
    shift by the caller). act: 'relu' | 'relu6' | None.

    Dispatch: Pallas on TPU when the gate admits the shape
    (`conv_norm.dispatch{tier=pallas}`), the lax.conv reference
    elsewhere (`tier=fallback`). Requires frozen norm stats (the affine
    is folded); differentiable — the custom VJP replays the reference
    composed ops backward."""
    assert act in _ACTS, act
    b, cin, h, w_in = x.shape
    cout, cin_g, kh, kw = w.shape
    depthwise = cin_g == 1 and cout == cin
    s = (stride, stride) if isinstance(stride, int) else tuple(stride)
    p = (padding, padding) if isinstance(padding, int) else tuple(padding)
    groups = cin if depthwise else (cin // cin_g if cin_g else 1)
    if not conv_bn_act_available(x.shape, w.shape, s, 1, groups,
                                 jnp.dtype(x.dtype).itemsize):
        _metrics.inc("conv_norm.dispatch", tier="fallback")
        return conv_bn_act_ref(x, w, scale, shift, stride=s, padding=p,
                               act=act, depthwise=depthwise)
    _metrics.inc("conv_norm.dispatch", tier="pallas")
    h_out = _out_dim(h, kh, s[0], p[0])
    w_out = _out_dim(w_in, kw, s[1], p[1])
    h_pad = h + 2 * p[0]
    w_pad = w_in + 2 * p[1]
    cands = _pick_rows(h_out, h_pad, cin, cin_g, cout, w_pad, w_out,
                       kh, kw, jnp.dtype(x.dtype).itemsize)
    if _rows_override is not None:
        rows = _rows_override
    else:
        rows = _tuned_rows(x, w, s, p, act, depthwise, h_out, w_out,
                           w_pad, cands)
    return _conv_pallas_vjp((s, p, act, depthwise, rows),
                            x, w, scale, shift)


def _conv_pallas(x, w, scale, shift, s, p, act, depthwise, rows):
    """The Pallas invocation itself (tests call this directly — the
    interpreter runs the exact kernel code on CPU)."""
    b, cin, h, w_in = x.shape
    cout, cin_g, kh, kw = w.shape
    h_out = _out_dim(h, kh, s[0], p[0])
    w_out = _out_dim(w_in, kw, s[1], p[1])
    xp = jnp.pad(x, ((0, 0), (0, 0), (p[0], p[0]), (p[1], p[1])))
    h_pad, w_pad = xp.shape[2], xp.shape[3]
    grid = (b, h_out // rows)
    return pl.pallas_call(
        functools.partial(_conv_kernel, kh=kh, kw=kw, sh_=s[0], sw_=s[1],
                          rows=rows, w_out=w_out, act=act,
                          depthwise=depthwise),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, cin, h_pad, w_pad),
                         lambda bi, ri: (bi, 0, 0, 0)),
            pl.BlockSpec((cout, cin_g, kh, kw),
                         lambda bi, ri: (0, 0, 0, 0)),
            pl.BlockSpec((cout, 1), lambda bi, ri: (0, 0)),
            pl.BlockSpec((cout, 1), lambda bi, ri: (0, 0)),
        ],
        out_specs=pl.BlockSpec((None, cout, rows, w_out),
                               lambda bi, ri: (bi, 0, ri, 0)),
        out_shape=jax.ShapeDtypeStruct((b, cout, h_out, w_out), x.dtype),
        interpret=_interpret(),
        name="conv_norm_fwd",
    )(xp, w, scale.reshape(cout, 1), shift.reshape(cout, 1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _conv_pallas_vjp(cfg, x, w, scale, shift):
    """Differentiable wrapper: fused Pallas forward, reference-composed
    backward. Without this, any AD through a fused-routed call (frozen-BN
    fine-tuning under jit, input-gradient probes) dies at trace time with
    'differentiation rule for pallas_call not implemented' — the eager
    grad gate in `vision/models/_fused.py` cannot see trace-mode AD.
    The backward replays `conv_bn_act_ref` (lax.conv + affine + act —
    the math the kernel matches exactly) and differentiates that, so
    gradients are the reference path's regardless of dispatch tier.
    cfg = (stride, padding, act, depthwise, rows), all static."""
    s, p, act, depthwise, rows = cfg
    return _conv_pallas(x, w, scale, shift, s, p, act, depthwise, rows)


def _conv_pallas_vjp_fwd(cfg, x, w, scale, shift):
    return _conv_pallas_vjp(cfg, x, w, scale, shift), (x, w, scale, shift)


def _conv_pallas_vjp_bwd(cfg, res, g):
    s, p, act, depthwise, _rows = cfg
    x, w, scale, shift = res
    _, vjp = jax.vjp(
        lambda xv, wv, sc, sh: conv_bn_act_ref(
            xv, wv, sc, sh, stride=s, padding=p, act=act,
            depthwise=depthwise),
        x, w, scale, shift)
    return vjp(g)


_conv_pallas_vjp.defvjp(_conv_pallas_vjp_fwd, _conv_pallas_vjp_bwd)
