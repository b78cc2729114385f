"""Fused Swin window attention — Pallas TPU kernel.

Role parity: the window-attention fusion the reference ecosystem gets
from its fused attention stack; here it is the ISSUE-10 answer to the
PERF.md round-5 Swin ablation, which put the windowed-attention
machinery (cyclic roll + 6-D window-partition transposes + rel-pos-bias
gather + reverse) at ~43% of achievable Swin-T step rate.

Design (TPU-first):
  * ONE kernel owns the whole windowed-attention block: cyclic shift
    (static-rotate concat of two slices — the shift is a Python int),
    window partition (static slices of the image block — the 6-D
    partition/reverse transposes never exist in the XLA program),
    per-head attention over [ws², hd] tiles with the dense precomputed
    rel-pos bias and the shift mask added to the f32 logits, softmax,
    and window reverse — the output block is assembled and stored in
    image layout.
  * Input is the POST-projection qkv image [B, H, W, 3C]: the qkv
    Linear is a per-token matmul, so projecting before partition is
    exactly equivalent to the reference order and lets the kernel read
    q/k/v as static lane slices of one block (the flat-layout idiom of
    flash_attention.py's [B,S,H*D] tier).
  * Windows are tiny (ws² = 49 tokens for Swin), so nothing streams:
    each grid cell holds a band of window rows in VMEM and walks its
    windows/heads in a static Python loop. The band height is the
    autotuned parameter (full image required when shift > 0 — the row
    roll crosses bands).
  * Backward is a second Pallas kernel over the full image: it replays
    the forward logits per window and emits dqkv in image layout plus a
    per-batch dbias partial ([B, heads, ws², ws²], summed outside — the
    rel-pos bias is trainable). The shift mask is stop-gradient by
    contract (zero cotangent).
  * Non-TPU backends run the same kernels through the Pallas
    interpreter in tests; the eager CPU fallback is the jnp reference
    below (`window_attention_ref`), which mirrors the kernel math
    op-for-op.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...observability import flight as _flight
from ...observability import metrics as _metrics
from .flash_attention import _interpret

__all__ = ["swin_window_attention", "window_attention_ref",
           "window_attention_available", "window_partition",
           "window_reverse"]

# VMEM feasibility bound for one grid cell (qkv band + out band + bias +
# mask + per-window f32 intermediates), conservative against the
# ~16 MiB/core default budget
_VMEM_BOUND = 8 * 1024 * 1024


# ========================= jnp reference =========================

def window_partition(x, ws):
    """[B, H, W, C] -> [B*nW, ws*ws, C] (row-major window order)."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // ws, ws, W // ws, ws, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(-1, ws * ws, C)


def window_reverse(windows, ws, H, W):
    """[B*nW, ws*ws, C] -> [B, H, W, C] — exact inverse of
    window_partition."""
    C = windows.shape[-1]
    B = windows.shape[0] // ((H // ws) * (W // ws))
    x = windows.reshape(B, H // ws, W // ws, ws, ws, C)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(B, H, W, C)


def _heads_attention(qkv_win, bias, mask_w, num_heads):
    """Shared per-window attention math on [N, P, 3C] window tokens —
    the single source of the numerics for the reference AND (via the
    same op order on 2-D tiles) the kernels. f32 logits/softmax,
    output in f32."""
    n, p, c3 = qkv_win.shape
    c = c3 // 3
    hd = c // num_heads
    scale = hd ** -0.5
    qkv_h = qkv_win.reshape(n, p, 3, num_heads, hd).astype(jnp.float32)
    q = qkv_h[:, :, 0].transpose(0, 2, 1, 3)        # [N, h, P, hd]
    k = qkv_h[:, :, 1].transpose(0, 2, 1, 3)
    v = qkv_h[:, :, 2].transpose(0, 2, 1, 3)
    s = jnp.einsum("nhpd,nhqd->nhpq", q * scale, k) + bias[None]
    if mask_w is not None:
        nw = mask_w.shape[0]
        s = s.reshape(n // nw, nw, num_heads, p, p) + \
            mask_w[None, :, None].astype(jnp.float32)
        s = s.reshape(n, num_heads, p, p)
    pmax = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - pmax)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("nhpq,nhqd->nphd", probs, v)    # [N, P, h, hd]
    return out.reshape(n, p, c)


def window_attention_ref(qkv, bias, mask, *, window_size, shift,
                         num_heads):
    """jnp reference (the CPU dispatch fallback): identical semantics to
    the fused kernel — roll + partition + biased/masked attention +
    reverse + unroll. qkv: [B, H, W, 3C]; bias: [heads, ws², ws²] f32;
    mask: [nW, ws², ws²] additive or None. Returns [B, H, W, C]."""
    B, H, W, c3 = qkv.shape
    ws = window_size
    x = qkv
    if shift:
        x = jnp.roll(x, (-shift, -shift), axis=(1, 2))
    wins = window_partition(x, ws)                   # [B*nW, P, 3C]
    out = _heads_attention(wins, bias.astype(jnp.float32),
                           mask, num_heads)
    out = window_reverse(out.astype(qkv.dtype), ws, H, W)
    if shift:
        out = jnp.roll(out, (shift, shift), axis=(1, 2))
    return out


# ========================= Pallas kernels =========================

def _roll2(x, sh, sw):
    """Static cyclic rotate of the two leading (row, col) axes by python
    ints — two slice+concat pairs, no gather, no transpose."""
    if sh:
        sh = sh % x.shape[0]
        x = jnp.concatenate([x[sh:], x[:sh]], axis=0)
    if sw:
        sw = sw % x.shape[1]
        x = jnp.concatenate([x[:, sw:], x[:, :sw]], axis=1)
    return x


def _window_qkv_math(win, bias_ref, mask_ref, w_idx, num_heads):
    """One window's attention on a [P, 3C] tile, walking heads with
    static lane slices (the compile-proven flat idiom). Returns
    (out [P, C] f32, probs_per_head, q/k/v per head) — the extras feed
    the backward kernel's replay."""
    p, c3 = win.shape
    c = c3 // 3
    hd = c // num_heads
    scale = hd ** -0.5
    outs, probs, qs, ks, vs = [], [], [], [], []
    for h in range(num_heads):
        q = win[:, h * hd:(h + 1) * hd].astype(jnp.float32)
        k = win[:, c + h * hd:c + (h + 1) * hd].astype(jnp.float32)
        v = win[:, 2 * c + h * hd:2 * c + (h + 1) * hd].astype(
            jnp.float32)
        s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s + bias_ref[h].astype(jnp.float32)
        if mask_ref is not None:
            s = s + mask_ref[w_idx].astype(jnp.float32)
        m = jnp.max(s, axis=-1, keepdims=True)
        e = jnp.exp(s - m)
        pr = e / jnp.sum(e, axis=-1, keepdims=True)
        o = jax.lax.dot_general(pr, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        outs.append(o)
        probs.append(pr)
        qs.append(q)
        ks.append(k)
        vs.append(v)
    return jnp.concatenate(outs, axis=-1), probs, qs, ks, vs


def _fwd_kernel(*refs, ws, shift, num_heads, n_wrows, has_mask):
    if has_mask:
        qkv_ref, bias_ref, mask_ref, o_ref = refs
    else:
        qkv_ref, bias_ref, o_ref = refs
        mask_ref = None
    x = qkv_ref[:]                                   # [rows, W, 3C]
    if shift:
        x = _roll2(x, shift, shift)
    W = x.shape[1]
    n_wcols = W // ws
    p = ws * ws
    row_bands = []
    for wi in range(n_wrows):
        row_out = []
        for wj in range(n_wcols):
            win = x[wi * ws:(wi + 1) * ws,
                    wj * ws:(wj + 1) * ws, :].reshape(p, -1)
            out, _, _, _, _ = _window_qkv_math(
                win, bias_ref, mask_ref, wi * n_wcols + wj, num_heads)
            row_out.append(out.reshape(ws, ws, -1))
        row_bands.append(jnp.concatenate(row_out, axis=1))
    img = jnp.concatenate(row_bands, axis=0)         # [rows, W, C]
    if shift:
        img = _roll2(img, -shift, -shift)
    o_ref[:] = img.astype(o_ref.dtype)


def _bwd_kernel(*refs, ws, shift, num_heads, n_wrows, has_mask):
    if has_mask:
        qkv_ref, bias_ref, mask_ref, g_ref, dqkv_ref, dbias_ref = refs
    else:
        qkv_ref, bias_ref, g_ref, dqkv_ref, dbias_ref = refs
        mask_ref = None
    x = qkv_ref[:]
    g = g_ref[:].astype(jnp.float32)
    if shift:
        x = _roll2(x, shift, shift)
        g = _roll2(g, shift, shift)
    W = x.shape[1]
    n_wcols = W // ws
    p = ws * ws
    c = x.shape[-1] // 3
    hd = c // num_heads
    scale = hd ** -0.5
    dbias = [jnp.zeros((p, p), jnp.float32) for _ in range(num_heads)]
    row_bands = []
    for wi in range(n_wrows):
        row_out = []
        for wj in range(n_wcols):
            win = x[wi * ws:(wi + 1) * ws,
                    wj * ws:(wj + 1) * ws, :].reshape(p, -1)
            gw = g[wi * ws:(wi + 1) * ws,
                   wj * ws:(wj + 1) * ws, :].reshape(p, c)
            _, probs, qs, ks, vs = _window_qkv_math(
                win, bias_ref, mask_ref, wi * n_wcols + wj, num_heads)
            parts = []
            for h in range(num_heads):
                gh = gw[:, h * hd:(h + 1) * hd]
                pr, q, k, v = probs[h], qs[h], ks[h], vs[h]
                dv = jax.lax.dot_general(
                    pr, gh, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dp = jax.lax.dot_general(
                    gh, v, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                ds = pr * (dp - jnp.sum(dp * pr, axis=-1,
                                        keepdims=True))
                dq = jax.lax.dot_general(
                    ds, k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                dk = jax.lax.dot_general(
                    ds, q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                dbias[h] = dbias[h] + ds
                parts.append((dq, dk, dv))
            dwin = jnp.concatenate(
                [t[i] for i in range(3) for t in parts], axis=-1)
            row_out.append(dwin.reshape(ws, ws, 3 * c))
        row_bands.append(jnp.concatenate(row_out, axis=1))
    dimg = jnp.concatenate(row_bands, axis=0)
    if shift:
        dimg = _roll2(dimg, -shift, -shift)
    dqkv_ref[:] = dimg.astype(dqkv_ref.dtype)
    dbias_ref[:] = jnp.stack(dbias)


def _fwd_pallas(qkv, bias, mask, ws, shift, num_heads, band):
    """band = window rows per grid cell (== nWh for shifted blocks)."""
    B, H, W, c3 = qkv.shape
    c = c3 // 3
    n_wrows = H // ws
    has_mask = mask is not None
    rows = band * ws
    grid = (B, n_wrows // band)
    in_specs = [
        pl.BlockSpec((None, rows, W, c3), lambda bi, ri: (bi, ri, 0, 0)),
        pl.BlockSpec(bias.shape, lambda bi, ri: (0, 0, 0)),
    ]
    operands = [qkv, bias]
    if has_mask:
        in_specs.append(pl.BlockSpec(mask.shape,
                                     lambda bi, ri: (0, 0, 0)))
        operands.append(mask)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, ws=ws, shift=shift,
                          num_heads=num_heads, n_wrows=band,
                          has_mask=has_mask),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, rows, W, c),
                               lambda bi, ri: (bi, ri, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, c), qkv.dtype),
        interpret=_interpret(),
        name="window_attention_fwd",
    )(*operands)


def _bwd_pallas(qkv, bias, mask, g, ws, shift, num_heads):
    """Full-image grid (B,): dbias partials are per-batch outputs summed
    by the caller — no cross-grid accumulation to serialize."""
    B, H, W, c3 = qkv.shape
    c = c3 // 3
    p = ws * ws
    n_wrows = H // ws
    has_mask = mask is not None
    in_specs = [
        pl.BlockSpec((None, H, W, c3), lambda bi: (bi, 0, 0, 0)),
        pl.BlockSpec(bias.shape, lambda bi: (0, 0, 0)),
    ]
    operands = [qkv, bias]
    if has_mask:
        in_specs.append(pl.BlockSpec(mask.shape, lambda bi: (0, 0, 0)))
        operands.append(mask)
    in_specs.append(pl.BlockSpec((None, H, W, c),
                                 lambda bi: (bi, 0, 0, 0)))
    operands.append(g)
    dqkv, dbias = pl.pallas_call(
        functools.partial(_bwd_kernel, ws=ws, shift=shift,
                          num_heads=num_heads, n_wrows=n_wrows,
                          has_mask=has_mask),
        grid=(B,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, H, W, c3), lambda bi: (bi, 0, 0, 0)),
            pl.BlockSpec((None, num_heads, p, p),
                         lambda bi: (bi, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, W, c3), qkv.dtype),
            jax.ShapeDtypeStruct((B, num_heads, p, p), jnp.float32),
        ],
        interpret=_interpret(),
        name="window_attention_bwd",
    )(*operands)
    return dqkv, dbias.sum(axis=0)


# ===================== custom-vjp cores =====================
#
# custom_vjp needs a fixed positional signature, and the mask is
# optional — two specialized cores (with/without mask) keep None out of
# the differentiable arguments. The mask core gives the mask a zero
# cotangent by contract (swin shift masks are stop-gradient constants).

@functools.lru_cache(maxsize=None)
def _build_core(ws, shift, num_heads, band, has_mask):
    if has_mask:
        @jax.custom_vjp
        def core(qkv, bias, mask):
            return _fwd_pallas(qkv, bias, mask, ws, shift, num_heads,
                               band)

        def core_fwd(qkv, bias, mask):
            return core(qkv, bias, mask), (qkv, bias, mask)

        def core_bwd(res, g):
            qkv, bias, mask = res
            dqkv, dbias = _bwd_pallas(qkv, bias, mask, g, ws, shift,
                                      num_heads)
            return dqkv, dbias.astype(bias.dtype), jnp.zeros_like(mask)
    else:
        @jax.custom_vjp
        def core(qkv, bias):
            return _fwd_pallas(qkv, bias, None, ws, shift, num_heads,
                               band)

        def core_fwd(qkv, bias):
            return core(qkv, bias), (qkv, bias)

        def core_bwd(res, g):
            qkv, bias = res
            dqkv, dbias = _bwd_pallas(qkv, bias, None, g, ws, shift,
                                      num_heads)
            return dqkv, dbias.astype(bias.dtype)

    core.defvjp(core_fwd, core_bwd)
    return core


# ===================== dispatch =====================

def window_attention_available(qkv_shape, window_size, num_heads,
                               dtype_itemsize=4) -> bool:
    """Dispatch gate for the fused kernel: TPU backend, pallas tier
    enabled, window-tileable dims, and one full-image cell within the
    VMEM bound. Rejects surface through the flight recorder (the
    silent-fallback class of failure, ADVICE r5)."""
    from ...core import flags

    if not flags.pallas_enabled("window_attn"):
        return False
    if len(qkv_shape) != 4:
        return False
    B, H, W, c3 = qkv_shape
    ws = window_size
    if c3 % 3 or H % ws or W % ws:
        return False
    c = c3 // 3
    if c % num_heads:
        return False
    p = ws * ws
    # size for the WORST cell — the BACKWARD kernel's full-image cell,
    # which holds qkv + the cotangent + dqkv together (7c vs the
    # forward's 4c) plus bias, dbias partial, and the f32 per-window
    # logit/probs replays; a forward-only estimate admits shapes whose
    # training backward then fails the VMEM check at compile time
    est = (H * W * (2 * c3 + c) * dtype_itemsize
           + num_heads * p * p * 4 * 3 + 16 * p * p * 4)
    if est > _VMEM_BOUND:
        _metrics.inc("swin_attn.gate_reject", reason="vmem")
        _flight.record("swin_attn.gate_reject", reason="vmem",
                       qkv_shape=list(qkv_shape), est_bytes=est)
        return False
    return not _interpret()


def _tuned_band(qkv, ws, shift, num_heads, has_mask):
    """Autotuned window-row band per grid cell (existing autotune cache,
    `swin_window_attn` op). Shifted blocks need the full image (the row
    roll crosses bands), so only the shift-free case searches."""
    B, H, W, c3 = qkv.shape
    n_wrows = H // ws
    if shift or has_mask:
        return n_wrows
    cands = [b for b in (1, 2, 4, 8, n_wrows)
             if b <= n_wrows and n_wrows % b == 0]
    cands = sorted(set(cands))
    if len(cands) <= 1:
        return n_wrows
    from . import autotune

    def run(band):
        import numpy as np

        rs = np.random.RandomState(0)
        qv = jnp.asarray(rs.randn(*qkv.shape), qkv.dtype)
        bias = jnp.zeros((num_heads, ws * ws, ws * ws), jnp.float32)
        core = _build_core(ws, 0, num_heads, band, False)

        def loss(qv):
            return core(qv, bias).astype(jnp.float32).sum()

        # fwd+bwd chained (training is the Swin bench workload); grad
        # is qkv-shaped so the timing loop composes
        return jax.grad(loss), qv

    sig = (f"{B}x{H}x{W}x{c3}|ws{ws}|h{num_heads}"
           f"|{jnp.dtype(qkv.dtype).name}")
    return autotune.pick("swin_window_attn", sig, cands, run, n_wrows)


def swin_window_attention(qkv, bias, mask, *, window_size, shift,
                          num_heads):
    """Public fused window-attention entry (jax arrays in/out).

    qkv: [B, H, W, 3C] post-projection image; bias: dense
    [num_heads, ws², ws²] rel-pos bias (f32, trainable — receives a real
    gradient); mask: [nW, ws², ws²] additive shift mask or None
    (stop-gradient by contract). Returns [B, H, W, C].

    Dispatch: the Pallas kernel on TPU when the gate admits the shape
    (`swin_attn.dispatch{tier=pallas}`), the jnp reference elsewhere
    (`tier=fallback`) — the reference is the same math, so tests hold
    them together."""
    bias = bias.astype(jnp.float32)
    if window_attention_available(qkv.shape, window_size, num_heads,
                                  jnp.dtype(qkv.dtype).itemsize):
        band = _tuned_band(qkv, window_size, shift, num_heads,
                           mask is not None)
        core = _build_core(window_size, int(shift), num_heads, band,
                           mask is not None)
        _metrics.inc("swin_attn.dispatch", tier="pallas")
        if mask is not None:
            return core(qkv, bias, mask)
        return core(qkv, bias)
    _metrics.inc("swin_attn.dispatch", tier="fallback")
    return window_attention_ref(qkv, bias, mask, window_size=window_size,
                                shift=shift, num_heads=num_heads)
