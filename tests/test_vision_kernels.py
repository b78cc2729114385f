"""Fused vision kernels (ISSUE 10): Swin window attention and the
conv+norm+act fusion vs their jnp references, through the Pallas
interpreter on CPU (fake-backend strategy — the exact kernel code runs,
minus Mosaic lowering, which tests/test_tpu_lowering.py-style gates
cover on the real toolchain)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.ops.pallas import conv_norm as CN
from paddle_tpu.ops.pallas import window_attention as WA


def _swin_mask(H, W, ws, shift):
    """The swin shifted-window additive mask ([nW, ws², ws²])."""
    img = np.zeros((1, H, W, 1))
    sl = (slice(0, -ws), slice(-ws, -shift), slice(-shift, None))
    cnt = 0
    for hs in sl:
        for wsl in sl:
            img[:, hs, wsl, :] = cnt
            cnt += 1
    m = img.reshape(1, H // ws, ws, W // ws, ws, 1)
    m = m.transpose(0, 1, 3, 2, 4, 5).reshape(-1, ws * ws)
    diff = m[:, None, :] - m[:, :, None]
    return jnp.asarray(np.where(diff != 0, -100.0, 0.0)
                       .astype(np.float32))


# ===================== window attention =====================


def _assert_forward_matches(out, ref, msg=""):
    """The kernel's float32 forward against the jnp reference: the same
    operations in the same order, but XLA:CPU contracts and vectorises
    the two programs as it likes, so they agree to the last few ulp and
    not to the bit (bit-equal under one jaxlib, off by up to 2 ulp of
    the output's largest value under another).  Held to 8 ulp of that
    value; a wrong window, shift, bias or mask is off by O(1)."""
    ref = np.asarray(ref)
    np.testing.assert_allclose(
        np.asarray(out), ref, rtol=0, err_msg=msg,
        atol=8 * np.finfo(np.float32).eps * np.abs(ref).max())


def test_window_attention_kernel_matches_ref_unshifted():
    """Unshifted windows, every band size: the kernel's forward matches
    the jnp reference to float32 rounding (_assert_forward_matches)."""
    rs = np.random.RandomState(0)
    B, H, W, C, heads, ws = 2, 8, 8, 12, 3, 4
    P_ = ws * ws
    qkv = jnp.asarray(rs.randn(B, H, W, 3 * C), jnp.float32)
    bias = jnp.asarray(rs.randn(heads, P_, P_), jnp.float32)
    ref = WA.window_attention_ref(qkv, bias, None, window_size=ws,
                                  shift=0, num_heads=heads)
    for band in (1, 2):
        out = WA._fwd_pallas(qkv, bias, None, ws, 0, heads, band)
        _assert_forward_matches(
            out, ref, f"band={band} forward differs from the reference")


def test_window_attention_kernel_matches_ref_shifted_masked():
    """Shifted windows WITH the swin attention mask: forward to float32
    rounding, gradients (dqkv from the analytic backward kernel, dbias summed
    over batch/windows) match jax-AD of the reference."""
    rs = np.random.RandomState(1)
    B, H, W, C, heads, ws, shift = 2, 8, 8, 8, 2, 4, 2
    P_ = ws * ws
    qkv = jnp.asarray(rs.randn(B, H, W, 3 * C), jnp.float32)
    bias = jnp.asarray(rs.randn(heads, P_, P_), jnp.float32)
    mask = _swin_mask(H, W, ws, shift)
    ref = WA.window_attention_ref(qkv, bias, mask, window_size=ws,
                                  shift=shift, num_heads=heads)
    out = WA._fwd_pallas(qkv, bias, mask, ws, shift, heads, H // ws)
    _assert_forward_matches(out, ref)

    core = WA._build_core(ws, shift, heads, H // ws, True)
    gk = jax.grad(lambda q, b: core(q, b, mask).sum(),
                  argnums=(0, 1))(qkv, bias)
    gr = jax.grad(
        lambda q, b: WA.window_attention_ref(
            q, b, mask, window_size=ws, shift=shift,
            num_heads=heads).sum(),
        argnums=(0, 1))(qkv, bias)
    for name, a, b in zip(("dqkv", "dbias"), gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"{name} mismatch")
    # the mask is stop-gradient by contract: zero cotangent
    dmask = jax.grad(lambda m: core(qkv, bias, m).sum())(mask)
    assert float(jnp.abs(dmask).max()) == 0.0


def test_window_attention_single_window_edge():
    """Edge tiling: a window covering the whole (odd-count) feature map
    — one window, no shift (the swin small-resolution stage shape)."""
    rs = np.random.RandomState(2)
    B, H, W, C, heads, ws = 1, 4, 4, 8, 2, 4
    qkv = jnp.asarray(rs.randn(B, H, W, 3 * C), jnp.float32)
    bias = jnp.asarray(rs.randn(heads, ws * ws, ws * ws), jnp.float32)
    ref = WA.window_attention_ref(qkv, bias, None, window_size=ws,
                                  shift=0, num_heads=heads)
    out = WA._fwd_pallas(qkv, bias, None, ws, 0, heads, 1)
    _assert_forward_matches(out, ref)


def test_window_attention_dispatch_counters(monkeypatch):
    """The public entry is gated: CPU routes to the reference with a
    `swin_attn.dispatch{tier=fallback}` counter (the silent-fallback
    failure class becomes a metric)."""
    from paddle_tpu import observability as obs

    obs.attach()
    try:
        before = obs.metrics.snapshot().get("counters", {})
        n0 = sum(v for k, v in before.items()
                 if "swin_attn.dispatch" in k and "fallback" in k)
        rs = np.random.RandomState(3)
        qkv = jnp.asarray(rs.randn(1, 4, 4, 12), jnp.float32)
        bias = jnp.zeros((2, 16, 16), jnp.float32)
        WA.swin_window_attention(qkv, bias, None, window_size=4,
                                 shift=0, num_heads=2)
        after = obs.metrics.snapshot().get("counters", {})
        n1 = sum(v for k, v in after.items()
                 if "swin_attn.dispatch" in k and "fallback" in k)
        assert n1 == n0 + 1, (before, after)
    finally:
        obs.detach()


def test_window_attention_band_autotuned(monkeypatch):
    """The band size goes through the existing autotune cache
    (`autotune.pick` with the swin_window_attn op); shifted blocks pin
    the full image (the row roll crosses bands)."""
    from paddle_tpu.ops.pallas import autotune

    seen = {}

    def fake_pick(op, sig, cands, run, default):
        seen["op"] = op
        seen["cands"] = list(cands)
        return default

    monkeypatch.setattr(autotune, "pick", fake_pick)
    rs = np.random.RandomState(4)
    qkv = jnp.asarray(rs.randn(1, 16, 16, 12), jnp.float32)
    band = WA._tuned_band(qkv, 4, 0, 2, False)
    assert seen["op"] == "swin_window_attn"
    assert seen["cands"] == [1, 2, 4]
    assert band == 4  # default = full image
    # shifted: no search, full image forced
    seen.clear()
    assert WA._tuned_band(qkv, 4, 2, 2, True) == 4
    assert "op" not in seen


# ===================== swin model integration =====================


def test_swin_dense_bias_matches_gather():
    """WindowAttention.dense_bias (one-hot matmul, no per-forward
    gather) equals the reference gather/reshape/transpose chain."""
    from paddle_tpu.vision.models.swin import WindowAttention

    P.seed(0)
    wa = WindowAttention(dim=12, window_size=4, num_heads=3)
    dense = wa.dense_bias().numpy()
    tab = wa.rel_bias.numpy()
    n = 16
    ref = tab[wa._rel_index.reshape(-1)].reshape(n, n, 3)
    ref = ref.transpose(2, 0, 1)
    np.testing.assert_allclose(dense, ref, atol=1e-6, rtol=1e-6)


def test_swin_block_shifted_matches_manual_reference():
    """A shifted SwinBlock through the fused entry equals the manual
    roll/partition/attention/reverse composition it replaced."""
    from paddle_tpu.vision.models.swin import SwinBlock

    P.seed(1)
    blk = SwinBlock(dim=8, input_resolution=(8, 8), num_heads=2,
                    window_size=4, shift_size=2)
    assert blk.shift == 2 and blk._attn_mask is not None
    x = P.to_tensor(np.random.RandomState(7)
                    .randn(2, 64, 8).astype(np.float32))
    out = blk(x).numpy()

    # manual reference: same modules, composed by hand
    import jax.numpy as jnp_

    xs = blk.norm1(x).numpy().reshape(2, 8, 8, 8)
    qkv = np.asarray(
        blk.attn.qkv(P.to_tensor(xs.reshape(2, 64, 8)))._value
    ).reshape(2, 8, 8, 24)
    bias = blk.attn.dense_bias().numpy()
    ref_attn = WA.window_attention_ref(
        jnp_.asarray(qkv), jnp_.asarray(bias),
        jnp_.asarray(blk._attn_mask.numpy()), window_size=4, shift=2,
        num_heads=2)
    proj = blk.attn.proj(P.to_tensor(
        np.asarray(ref_attn).reshape(2, 64, 8)))
    mid = x.numpy() + proj.numpy()
    ref = mid + blk.mlp(blk.norm2(P.to_tensor(mid))).numpy()
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_swin_rel_bias_still_trains():
    """Gradient flows to the tied rel-pos table through the dense
    one-hot matmul (the satellite must not silently freeze it)."""
    from paddle_tpu.vision.models.swin import SwinBlock

    P.seed(2)
    blk = SwinBlock(dim=8, input_resolution=(8, 8), num_heads=2,
                    window_size=4, shift_size=0)
    x = P.to_tensor(np.random.RandomState(8)
                    .randn(1, 64, 8).astype(np.float32))
    P.mean(P.square(blk(x))).backward()
    g = blk.attn.rel_bias.grad
    assert g is not None
    assert float(np.abs(g.numpy()).max()) > 0.0


# ===================== conv+norm+act =====================


@pytest.mark.parametrize(
    "shape,stride,pad,dw,act",
    [((2, 3, 16, 16, 8, 7), 2, 3, False, "relu"),    # 7x7/2 stem
     ((2, 8, 14, 14, 16, 3), 1, 1, False, "relu"),   # 3x3 block
     ((2, 8, 14, 14, 16, 1), 1, 0, False, None),     # 1x1 projection
     ((1, 6, 7, 7, 6, 3), 2, 1, True, "relu6"),      # depthwise, odd HW
     ((1, 4, 9, 11, 7, 3), 2, 1, False, "relu")])    # odd H/W edge tiles
def test_conv_bn_act_kernel_matches_ref(shape, stride, pad, dw, act):
    B, Ci, H, W, Co, k = shape
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(B, Ci, H, W), jnp.float32)
    w = jnp.asarray(rs.randn(Co, 1 if dw else Ci, k, k),
                    jnp.float32) * 0.2
    sc = jnp.asarray(rs.rand(Co) + 0.5, jnp.float32)
    sh = jnp.asarray(rs.randn(Co), jnp.float32)
    ref = CN.conv_bn_act_ref(x, w, sc, sh, stride=stride, padding=pad,
                             act=act, depthwise=dw)
    h_out = (H + 2 * pad - k) // stride + 1
    for rows in sorted({1, h_out}):
        if h_out % rows:
            continue
        out = CN._conv_pallas(x, w, sc, sh, (stride, stride),
                              (pad, pad), act, dw, rows)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-4, rtol=1e-5,
            err_msg=f"rows={rows}")


def test_conv_bn_act_helper_folding_matches_composed():
    """`_fused.conv_bn_act` in eval+no_grad (the fused-eligible route,
    folded scale/shift) equals the composed bn(conv(x))+relu ops."""
    from paddle_tpu import nn
    from paddle_tpu.vision.models._fused import conv_bn_act

    P.seed(3)
    conv = nn.Conv2D(4, 6, 3, stride=2, padding=1)
    bn = nn.BatchNorm2D(6)
    # non-trivial running stats + affine
    bn._mean.set_value(np.random.RandomState(1)
                       .randn(6).astype(np.float32))
    bn._variance.set_value((np.random.RandomState(2).rand(6) + 0.5)
                           .astype(np.float32))
    bn.weight.set_value((np.random.RandomState(3).rand(6) + 0.5)
                        .astype(np.float32))
    bn.bias.set_value(np.random.RandomState(4)
                      .randn(6).astype(np.float32))
    conv.eval()
    bn.eval()
    x = P.to_tensor(np.random.RandomState(5)
                    .rand(2, 4, 9, 9).astype(np.float32))
    with P.no_grad():
        fused = conv_bn_act(x, conv, bn, "relu").numpy()
    composed = nn.functional.relu(bn(conv(x))).numpy()
    np.testing.assert_allclose(fused, composed, atol=1e-5, rtol=1e-5)


def test_conv_bn_act_training_stays_composed():
    """Training mode must NOT fold (batch norm needs live batch stats):
    the helper routes to the composed ops and running stats update."""
    from paddle_tpu import nn
    from paddle_tpu.vision.models._fused import conv_bn_act

    P.seed(4)
    conv = nn.Conv2D(3, 4, 3, padding=1)
    bn = nn.BatchNorm2D(4)
    conv.train()
    bn.train()
    before = bn._mean.numpy().copy()
    x = P.to_tensor(np.random.RandomState(6)
                    .rand(2, 3, 8, 8).astype(np.float32) + 1.0)
    out = conv_bn_act(x, conv, bn, "relu")
    assert out.shape == [2, 4, 8, 8]
    assert not np.array_equal(before, bn._mean.numpy()), \
        "training batch-norm stats did not update — fused path leaked " \
        "into training"


def test_conv_bn_act_dispatch_counter():
    """The public fused entry counts its tier (fallback on CPU)."""
    from paddle_tpu import observability as obs

    obs.attach()
    try:
        rs = np.random.RandomState(9)
        x = jnp.asarray(rs.randn(1, 3, 8, 8), jnp.float32)
        w = jnp.asarray(rs.randn(4, 3, 3, 3), jnp.float32)
        CN.fused_conv_bn_act(x, w, jnp.ones((4,)), jnp.zeros((4,)),
                             stride=1, padding=1, act="relu")
        counters = obs.metrics.snapshot().get("counters", {})
        assert any("conv_norm.dispatch" in k and "fallback" in k
                   for k in counters), counters
    finally:
        obs.detach()


def test_resnet_eval_fused_route_matches_disabled():
    """ResNet18 eval forward is identical with the fused tier enabled
    vs FLAGS_disable_pallas_conv_norm (on CPU both run reference math —
    the equality proves the folding + routing, not the kernel)."""
    from paddle_tpu.core import flags
    from paddle_tpu.vision import models as V

    P.seed(5)
    m = V.resnet18(num_classes=4)
    m.eval()
    x = P.to_tensor(np.random.RandomState(10)
                    .rand(1, 3, 32, 32).astype(np.float32))
    with P.no_grad():
        a = m(x).numpy()
    flags.set_flags({"FLAGS_disable_pallas_conv_norm": True})
    try:
        with P.no_grad():
            b = m(x).numpy()
    finally:
        flags.set_flags({"FLAGS_disable_pallas_conv_norm": False})
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_fused_conv_vjp_matches_ref_grads():
    """jax.grad THROUGH the fused tier (`_conv_pallas_vjp`, the path
    `fused_conv_bn_act` dispatches on TPU) is bit-identical to the
    reference grads: the custom VJP runs the Pallas forward and replays
    the composed-ops backward, so frozen-BN fine-tuning / input-gradient
    probes under jit neither crash on a missing pallas AD rule nor drift
    from the composed path's gradients."""
    rs = np.random.RandomState(3)
    x = jnp.asarray(rs.randn(2, 3, 8, 8), jnp.float32)
    w = jnp.asarray(rs.randn(4, 3, 3, 3), jnp.float32) * 0.2
    sc = jnp.asarray(rs.rand(4) + 0.5, jnp.float32)
    sh = jnp.asarray(rs.randn(4), jnp.float32)
    cfg = ((1, 1), (1, 1), "relu", False, 8)

    def loss_fused(*a):
        return CN._conv_pallas_vjp(cfg, *a).astype(jnp.float32).sum()

    def loss_ref(*a):
        return CN.conv_bn_act_ref(*a, stride=(1, 1), padding=(1, 1),
                                  act="relu").astype(jnp.float32).sum()

    g_fused = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(x, w, sc, sh)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, sc, sh)
    for name, a, b in zip(("dx", "dw", "dscale", "dshift"),
                          g_fused, g_ref):
        assert np.array_equal(np.asarray(a), np.asarray(b)), name
    # and under jit (the frozen-BN fine-tune shape of the failure)
    g_jit = jax.jit(jax.grad(loss_fused))(x, w, sc, sh)
    assert np.array_equal(np.asarray(g_jit), np.asarray(g_fused[0]))
