"""Pallas kernel tests — run through the interpreter on CPU so the exact
kernel code is validated without hardware (SURVEY §4.5 fake-backend
strategy)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas import flash_attention as fa

rs = np.random.RandomState(0)


def _rand(shape):
    return jnp.asarray(rs.randn(*shape), jnp.float32)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    B, S, H, D = 2, 256, 2, 64
    q, k, v = _rand((B, S, H, D)), _rand((B, S, H, D)), _rand((B, S, H, D))
    out = fa._flash_core(q, k, v, causal, 128, 128)
    ref = fa._ref_attention(q, k, v, None, causal)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    B, S, H, D = 1, 128, 2, 64
    q, k, v = _rand((B, S, H, D)), _rand((B, S, H, D)), _rand((B, S, H, D))

    def loss_flash(q, k, v):
        return jnp.sum(fa._flash_core(q, k, v, causal, 64, 64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(fa._ref_attention(q, k, v, None, causal) ** 2)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=5e-4)


def test_flash_uneven_blocks():
    # seq not a multiple of the block: pallas pads the trailing block
    B, S, H, D = 1, 192, 2, 64
    q, k, v = _rand((B, S, H, D)), _rand((B, S, H, D)), _rand((B, S, H, D))
    out = fa._flash_core(q, k, v, True, 128, 128)
    ref = fa._ref_attention(q, k, v, None, True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_bf16_io():
    B, S, H, D = 1, 128, 2, 64
    q = _rand((B, S, H, D)).astype(jnp.bfloat16)
    k = _rand((B, S, H, D)).astype(jnp.bfloat16)
    v = _rand((B, S, H, D)).astype(jnp.bfloat16)
    out = fa._flash_core(q, k, v, True, 64, 64)
    assert out.dtype == jnp.bfloat16
    ref = fa._ref_attention(q, k, v, None, True)
    np.testing.assert_allclose(out.astype(jnp.float32),
                               ref.astype(jnp.float32), atol=3e-2, rtol=3e-2)


# ===================== fused norm (rms / layernorm) =====================

from paddle_tpu.ops.pallas import fused_norm as fn_mod


def _rms_ref(z, w, b, eps):
    z32 = z.astype(jnp.float32)
    ms = jnp.mean(z32 * z32, axis=-1, keepdims=True)
    y = z32 * jax.lax.rsqrt(ms + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(z.dtype)


def _ln_ref(z, w, b, eps):
    z32 = z.astype(jnp.float32)
    mu = jnp.mean(z32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(z32 - mu), axis=-1, keepdims=True)
    y = (z32 - mu) * jax.lax.rsqrt(var + eps)
    if w is not None:
        y = y * w.astype(jnp.float32)
    if b is not None:
        y = y + b.astype(jnp.float32)
    return y.astype(z.dtype)


@pytest.mark.parametrize("kind", ["rms", "ln"])
def test_fused_norm_forward_matches_reference(kind):
    R, D = 24, 256
    x = _rand((R, D))
    w = _rand((D,))
    b = _rand((D,))
    out = fn_mod.fused_norm_pallas(x, w, b, eps=1e-6, kind=kind)
    ref = (_rms_ref if kind == "rms" else _ln_ref)(x, w, b, 1e-6)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind", ["rms", "ln"])
def test_fused_norm_residual_bias_forward(kind):
    B, S, D = 2, 8, 128
    x = _rand((B, S, D))
    w = _rand((D,))
    bias = _rand((D,))
    res = _rand((B, S, D))
    out, z = fn_mod.fused_norm_pallas(x, w, None, bias, res,
                                      eps=1e-6, kind=kind)
    z_ref = x + bias + res
    ref = (_rms_ref if kind == "rms" else _ln_ref)(z_ref, w, None, 1e-6)
    np.testing.assert_allclose(z, z_ref, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kind", ["rms", "ln"])
def test_fused_norm_grads_match_reference(kind):
    R, D = 16, 128
    x = _rand((R, D))
    w = _rand((D,))
    b = _rand((D,))

    def loss_pallas(x, w, b):
        return jnp.sum(fn_mod.fused_norm_pallas(x, w, b, eps=1e-6,
                                                kind=kind) ** 2)

    def loss_ref(x, w, b):
        return jnp.sum(
            (_rms_ref if kind == "rms" else _ln_ref)(x, w, b, 1e-6) ** 2)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2))(x, w, b)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(a, c, atol=5e-4, rtol=5e-4)


def test_fused_norm_residual_grads():
    R, D = 16, 128
    x = _rand((R, D))
    w = _rand((D,))
    bias = _rand((D,))
    res = _rand((R, D))

    def loss_pallas(x, w, bias, res):
        y, z = fn_mod.fused_norm_pallas(x, w, None, bias, res, eps=1e-6,
                                        kind="rms")
        return jnp.sum(y ** 2) + jnp.sum(z ** 3)

    def loss_ref(x, w, bias, res):
        z = x + bias + res
        y = _rms_ref(z, w, None, 1e-6)
        return jnp.sum(y ** 2) + jnp.sum(z ** 3)

    g1 = jax.grad(loss_pallas, argnums=(0, 1, 2, 3))(x, w, bias, res)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2, 3))(x, w, bias, res)
    for a, c in zip(g1, g2):
        np.testing.assert_allclose(a, c, atol=5e-4, rtol=5e-4)


# ============================== fused rope ==============================

from paddle_tpu.ops.pallas import rope as rope_mod


def _rope_phases(s, d, base=10000.0):
    inv = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    t = jnp.arange(s, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return (jnp.cos(emb)[None, :, None, :], jnp.sin(emb)[None, :, None, :])


def _rope_ref(x, cos, sin):
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rot * sin


def test_rope_forward_matches_reference():
    B, S, H, D = 2, 16, 4, 64
    x = _rand((B, S, H, D))
    cos, sin = _rope_phases(S, D)
    out = rope_mod.rope_pallas(x, cos, sin)
    ref = _rope_ref(x, cos, sin)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_rope_grad_matches_reference():
    B, S, H, D = 1, 8, 2, 64
    x = _rand((B, S, H, D))
    cos, sin = _rope_phases(S, D)
    g1 = jax.grad(lambda x: jnp.sum(rope_mod.rope_pallas(x, cos, sin) ** 2))(x)
    g2 = jax.grad(lambda x: jnp.sum(_rope_ref(x, cos, sin) ** 2))(x)
    np.testing.assert_allclose(g1, g2, atol=5e-5, rtol=5e-5)


# ====================== blocked KV-cache decode ======================

# the package re-exports the function under the module's name — import the
# function straight from the submodule via sys.modules
import importlib
da_mod = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")


def test_decode_attention_matches_full_softmax():
    B, H, S, D = 2, 4, 64, 64
    q = _rand((B, H, D))
    kc = _rand((B, H, S, D))
    vc = _rand((B, H, S, D))
    pos = jnp.asarray([5, 33], jnp.int32)
    out = da_mod.decode_attention(q, kc, vc, pos, block_k=16)
    # reference: full-cache softmax with position mask
    scale = 1.0 / np.sqrt(D)
    scores = jnp.einsum("bhd,bhsd->bhs", q, kc) * scale
    valid = jnp.arange(S)[None, None, :] <= pos[:, None, None]
    scores = jnp.where(valid, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    ref = jnp.einsum("bhs,bhsd->bhd", p, vc)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_decode_attention_pos_zero_and_full():
    B, H, S, D = 1, 2, 32, 64
    q = _rand((B, H, D))
    kc = _rand((B, H, S, D))
    vc = _rand((B, H, S, D))
    for p0 in (0, S - 1):
        pos = jnp.asarray([p0], jnp.int32)
        out = da_mod.decode_attention(q, kc, vc, pos, block_k=8)
        scale = 1.0 / np.sqrt(D)
        scores = jnp.einsum("bhd,bhsd->bhs", q, kc) * scale
        valid = jnp.arange(S)[None, None, :] <= pos[:, None, None]
        scores = jnp.where(valid, scores, -1e30)
        pr = jax.nn.softmax(scores, axis=-1)
        ref = jnp.einsum("bhs,bhsd->bhd", pr, vc)
        np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_causal_cross_length_matches_reference():
    """Bottom-right-aligned causal (reference tril k=sk-sq) when
    seq_q != seq_k — decode/chunked-prefill shape (r3 review finding)."""
    B, H, D = 2, 2, 32
    for sq, sk in [(16, 64), (64, 16), (24, 40)]:
        q = _rand((B, sq, H, D))
        k = _rand((B, sk, H, D))
        v = _rand((B, sk, H, D))
        ref = fa._ref_attention(q, k, v, None, True)
        out = fa._flash_core(q, k, v, True, 8, 8)
        if sq > sk:
            # rows with an empty attention window are degenerate
            # (reference softmaxes all -inf to uniform; kernel emits 0) —
            # compare only rows that attend to at least one key
            valid_rows = slice(sq - sk, None)
            np.testing.assert_allclose(
                np.asarray(out)[:, valid_rows], np.asarray(ref)[:, valid_rows],
                atol=2e-5, rtol=2e-5)
        else:
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       atol=2e-5, rtol=2e-5)


def test_flash_causal_cross_length_grads():
    B, H, D, sq, sk = 1, 2, 16, 16, 48
    q = _rand((B, sq, H, D))
    k = _rand((B, sk, H, D))
    v = _rand((B, sk, H, D))
    g_ref = jax.grad(lambda q, k, v: fa._ref_attention(
        q, k, v, None, True).sum(), argnums=(0, 1, 2))(q, k, v)
    g_pal = jax.grad(lambda q, k, v: fa._flash_core(
        q, k, v, True, 8, 8).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_pal):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=3e-5, rtol=3e-5)


def test_flash_indivisible_seq_raises_loud():
    """seq % 8 != 0 must be a loud error when the kernel is invoked
    DIRECTLY without padding. The public entry handles odd lengths by
    zero-padding + real-length masking on TPU (see
    test_flash_padded_odd_lengths_match_reference); on CPU (interpret
    mode gated off) it uses the reference path — correct either way."""
    q = _rand((1, 20, 2, 16))
    with pytest.raises(ValueError, match="seq % 8"):
        fa._flash_core(q, q, q, True, 8, 8)
    # public entry: correct on every backend (reference path here;
    # padded kernel on TPU)
    out = fa.flash_attention_fwd(q, q, q, is_causal=True)
    ref = fa._ref_attention(q, q, q, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_padded_odd_lengths_match_reference(causal):
    """Odd (ViT-style) sequence lengths: zero-pad to a multiple of 8,
    mask on the REAL lengths inside the kernels, slice the output.
    Values and grads must match the unpadded reference exactly — padded
    keys contribute nothing, padded query rows carry no gradient."""
    B, SQ, SK, H, D = 2, 52, 52, 2, 16
    q, k, v = _rand((B, SQ, H, D)), _rand((B, SK, H, D)), _rand((B, SK, H, D))
    pad = (-SQ) % 8
    w = ((0, 0), (0, pad), (0, 0), (0, 0))
    qp, kp, vp = jnp.pad(q, w), jnp.pad(k, w), jnp.pad(v, w)
    out = fa._flash_core(qp, kp, vp, causal, 8, 8, SQ, SK)[:, :SQ]
    ref = fa._ref_attention(q, k, v, None, causal)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def loss_flash(q_, k_, v_):
        qq, kk, vv = jnp.pad(q_, w), jnp.pad(k_, w), jnp.pad(v_, w)
        o = fa._flash_core(qq, kk, vv, causal, 8, 8, SQ, SK)[:, :SQ]
        return (o.astype(jnp.float32) * 0.01).sum()

    def loss_ref(q_, k_, v_):
        o = fa._ref_attention(q_, k_, v_, None, causal)
        return (o.astype(jnp.float32) * 0.01).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_flat_native_matches_transpose_path(causal):
    """Flat-native core (all operands ride unpadded [B,S,H*D] views,
    per-head 64-lane slices): forward and all three gradients must be
    numerically identical to the transpose core, MHA and GQA."""
    B, S, H, D = 2, 128, 3, 32
    q, k, v = _rand((B, S, H, D)), _rand((B, S, H, D)), _rand((B, S, H, D))

    def loss(core, q_, k_, v_):
        return (core(q_, k_, v_, causal, 64, 64)
                .astype(jnp.float32) * 0.01).sum()

    out_f = fa._flash_core_flat(q, k, v, causal, 64, 64)
    out_t = fa._flash_core(q, k, v, causal, 64, 64)
    np.testing.assert_allclose(out_f, out_t, atol=1e-6, rtol=1e-6)
    g_t = jax.grad(lambda *a: loss(fa._flash_core, *a),
                   argnums=(0, 1, 2))(q, k, v)
    g_f = jax.grad(lambda *a: loss(fa._flash_core_flat, *a),
                   argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_t, g_f):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)

    # GQA: grouped KV lane reads + group-summed dk/dv
    HQ, HKV = 4, 2
    q2 = _rand((B, S, HQ, D))
    k2 = _rand((B, S, HKV, D))
    v2 = _rand((B, S, HKV, D))
    out_f = fa._flash_core_flat(q2, k2, v2, causal, 64, 64)
    out_t = fa._flash_core(q2, k2, v2, causal, 64, 64)
    np.testing.assert_allclose(out_f, out_t, atol=1e-6, rtol=1e-6)
    g_t = jax.grad(lambda *a: loss(fa._flash_core, *a),
                   argnums=(0, 1, 2))(q2, k2, v2)
    g_f = jax.grad(lambda *a: loss(fa._flash_core_flat, *a),
                   argnums=(0, 1, 2))(q2, k2, v2)
    for a, b in zip(g_t, g_f):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)


def test_flash_flat_gate_and_default_dispatch(monkeypatch):
    """The flat tier's gate (_flat_native_ok: lane alignment, head
    width, then VMEM at the blocks that will REALLY run), and the
    dispatch behind it: an eligible unpadded shape reaches the flat core
    with no flag set and matches the reference."""
    B, S, H, D = 2, 128, 2, 64
    q = _rand((B, S, H, D))
    assert fa._flat_native_ok(q, q)  # H*D = 128: lane-aligned, D%64==0

    class _Fake:  # VMEM-infeasible: all heads' sequence-long operands
        shape = (1, 8192, 32, 128)
        dtype = jnp.dtype(jnp.bfloat16)

    assert not fa._flat_native_ok(_Fake(), _Fake())

    class _OffTile:  # H*D = 64 — below the 128-lane tile
        shape = (2, 128, 4, 16)
        dtype = jnp.dtype(jnp.bfloat16)

    assert not fa._flat_native_ok(_OffTile(), _OffTile())

    class _OffHead:  # H*D = 128 lane-aligned but D=32: not compile-proven
        shape = (2, 128, 4, 32)
        dtype = jnp.dtype(jnp.bfloat16)

    assert not fa._flat_native_ok(_OffHead(), _OffHead())

    class _Mid:  # VMEM-borderline: feasible at 512 blocks, not at 1024
        shape = (1, 1024, 12, 64)
        dtype = jnp.dtype(jnp.bfloat16)

    # the gate estimates with the blocks that will REALLY run — tuned
    # 1024-blocks must be gated as 1024, not as a hardcoded 512 estimate
    assert fa._flat_native_ok(_Mid(), _Mid(), 512, 512)
    assert not fa._flat_native_ok(_Mid(), _Mid(), 1024, 1024)

    # on CPU the public entry routes to the reference path
    # (flash_attention_available gates on TPU); force the interpreter
    # kernels so the dispatch decision itself is what's under test
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    called = {}
    orig_flat = fa._flash_core_flat

    def spy_flat(*a, **kw):
        called["flat"] = True
        return orig_flat(*a, **kw)

    monkeypatch.setattr(fa, "_flash_core_flat", spy_flat)
    out = fa.flash_attention_fwd(q, q, q, is_causal=True)
    assert called.get("flat"), "an eligible shape did not reach the flat core"
    ref = fa._ref_attention(q, q, q, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


def test_flash_gqa_expand_routes_by_group_bytes(monkeypatch):
    """A KV head's resident query group past _GQA_GROUP_BYTES_MAX takes
    the expanded-KV path: the kernels then see Hkv == Hq (and the result
    still matches the reference); under the bound KV stays shrunk."""
    B, S, HQ, HKV, D = 2, 128, 4, 2, 32
    q = _rand((B, S, HQ, D))
    k = _rand((B, S, HKV, D))
    v = _rand((B, S, HKV, D))
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    seen = {}
    orig = fa._flash_core  # d = 32: the flat gate refuses, transpose runs

    def spy(q_, k_, v_, *a, **kw):
        seen["h_kv"] = k_.shape[2]
        return orig(q_, k_, v_, *a, **kw)

    monkeypatch.setattr(fa, "_flash_core", spy)
    # the group here: 3 * 2 * 128 * 32 * 4 bytes
    monkeypatch.setattr(fa, "_GQA_GROUP_BYTES_MAX", 3 * 2 * S * D * 4 - 1)
    out = fa.flash_attention_fwd(q, k, v, is_causal=True)
    assert seen.get("h_kv") == HQ, "a group past the bound was not expanded"
    ref = fa._ref_attention(q, k, v, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # at the bound: grouped (KV stays shrunk)
    monkeypatch.setattr(fa, "_GQA_GROUP_BYTES_MAX", 3 * 2 * S * D * 4)
    seen.clear()
    out = fa.flash_attention_fwd(q, k, v, is_causal=True)
    assert seen.get("h_kv") == HKV
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_expanded_reference(causal):
    """GQA-native kernels (Hkv < Hq, grouped via index maps — KV never
    expands in memory): values and grads must equal running the expanded
    MHA reference; dk/dv come back at the KV head count, equal to the
    group-summed expanded grads."""
    B, S, HQ, HKV, D = 2, 128, 4, 2, 32
    rep = HQ // HKV
    q = _rand((B, S, HQ, D))
    k = _rand((B, S, HKV, D))
    v = _rand((B, S, HKV, D))
    out = fa._flash_core(q, k, v, causal, 64, 64)
    ref = fa._ref_attention(q, k, v, None, causal)  # expands internally
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)

    def loss_flash(q_, k_, v_):
        o = fa._flash_core(q_, k_, v_, causal, 64, 64)
        return (o.astype(jnp.float32) * 0.01).sum()

    def loss_ref(q_, k_, v_):
        ke = jnp.repeat(k_, rep, axis=2)
        ve = jnp.repeat(v_, rep, axis=2)
        o = fa._ref_attention(q_, ke, ve, None, causal)
        return (o.astype(jnp.float32) * 0.01).sum()

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    assert gf[1].shape == (B, S, HKV, D)  # grads at KV head count
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


# ====================== varlen (packed) attention ======================

from paddle_tpu.ops.pallas import varlen_attention as vla


@pytest.mark.parametrize("causal", [False, True])
def test_varlen_attention_matches_per_sequence_dense(causal):
    """Packed ragged batch through the segment-masked kernels must equal
    running each sequence separately through dense attention — values
    and grads; segments must not leak into each other."""
    lens = [13, 37, 6]
    H, D = 2, 32
    T = sum(lens)
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    q = _rand((T, H, D))
    k = _rand((T, H, D))
    v = _rand((T, H, D))
    scale = 0.17  # non-default: the explicit-scale plumbing must matter

    def ref(q_, k_, v_):
        outs = []
        for i in range(len(lens)):
            s, e = int(cu[i]), int(cu[i + 1])
            qs = q_[None, s:e]  # [1, L, H, D]
            logits = jnp.einsum("bqhd,bkhd->bhqk", qs.astype(jnp.float32),
                                k_[None, s:e].astype(jnp.float32)) * scale
            if causal:
                L = e - s
                m = jnp.tril(jnp.ones((L, L), bool))
                logits = jnp.where(m, logits, -1e30)
            p = jax.nn.softmax(logits, axis=-1)
            outs.append(jnp.einsum(
                "bhqk,bkhd->bqhd", p,
                v_[None, s:e].astype(jnp.float32))[0])
        return jnp.concatenate(outs, axis=0).astype(q_.dtype)

    out = vla.varlen_attention(q, k, v, cu, cu, scale=scale,
                               causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(out, ref(q, k, v), atol=3e-5, rtol=3e-5)

    def loss_vl(q_, k_, v_):
        o = vla.varlen_attention(q_, k_, v_, cu, cu, scale=scale,
                                 causal=causal, block_q=16, block_k=16)
        return (o.astype(jnp.float32) * 0.01).sum()

    def loss_ref(q_, k_, v_):
        return (ref(q_, k_, v_).astype(jnp.float32) * 0.01).sum()

    gf = jax.grad(loss_vl, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)


def test_flash_attn_unpadded_api():
    """nn.functional surface (reference flash_attention.py:302):
    Tensor in/out, (out, None) tuple, scale honored."""
    import paddle_tpu as P
    import paddle_tpu.nn.functional as F

    lens = [5, 11]
    T, H, D = sum(lens), 2, 16
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    rs_ = np.random.RandomState(3)
    q = P.to_tensor(rs_.randn(T, H, D).astype(np.float32))
    out, sm = F.flash_attn_unpadded(
        q, q, q, P.to_tensor(cu), P.to_tensor(cu),
        max_seqlen_q=max(lens), max_seqlen_k=max(lens),
        scale=1.0 / np.sqrt(D), causal=True)
    assert sm is None
    assert list(out.shape) == [T, H, D]
    assert np.isfinite(out.numpy()).all()


def test_flash_attn_unpadded_rejects_unsupported():
    """Loud errors for semantics the fused path cannot honor: prob
    dropout, mismatched causal packings, return_softmax."""
    import paddle_tpu as P
    import paddle_tpu.nn.functional as F

    T, H, D = 16, 2, 16
    q = P.to_tensor(np.random.RandomState(0).randn(T, H, D)
                    .astype(np.float32))
    cu_a = P.to_tensor(np.array([0, 8, 16], np.int32))
    cu_b = P.to_tensor(np.array([0, 4, 16], np.int32))
    kw = dict(max_seqlen_q=8, max_seqlen_k=8, scale=0.25)
    with pytest.raises(NotImplementedError, match="softmax"):
        F.flash_attn_unpadded(q, q, q, cu_a, cu_a, return_softmax=True,
                              **kw)
    with pytest.raises(NotImplementedError, match="dropout"):
        F.flash_attn_unpadded(q, q, q, cu_a, cu_a, dropout=0.1, **kw)
    with pytest.raises(NotImplementedError, match="identical"):
        F.flash_attn_unpadded(q, q, q, cu_a, cu_b, causal=True, **kw)
    # dropout accepted outside training (inference parity)
    out, _ = F.flash_attn_unpadded(q, q, q, cu_a, cu_a, dropout=0.1,
                                   training=False, **kw)
    assert np.isfinite(out.numpy()).all()


def test_sdp_kernel_policy_context():
    """sdp_kernel() (reference flash_attention.py:27): constrains which
    backend scaled_dot_product_attention picks; restores on exit; all
    backends disabled is a loud error."""
    import paddle_tpu as P
    import paddle_tpu.nn.functional as F
    from paddle_tpu.nn.functional import attention as attn_mod

    x = P.to_tensor(np.random.RandomState(0)
                    .randn(1, 16, 2, 16).astype(np.float32))
    with F.sdp_kernel(enable_math=True, enable_flash=False,
                      enable_mem_efficient=False):
        assert attn_mod._sdp_policy == {"math": True, "flash": False}
        out = F.scaled_dot_product_attention(x, x, x, is_causal=True)
        assert np.isfinite(out.numpy()).all()
    assert attn_mod._sdp_policy == {"math": True, "flash": True}
    with pytest.raises(RuntimeError, match="backend"):
        with F.sdp_kernel(enable_math=False, enable_flash=False,
                          enable_mem_efficient=False):
            F.scaled_dot_product_attention(x, x, x, is_causal=True)
    # math disabled + flash enabled-but-unavailable (CPU eager has no
    # Mosaic kernel): silently falling through to the disabled math path
    # would violate the policy — must raise instead (ADVICE r4)
    with pytest.raises(RuntimeError, match="unavailable"):
        with F.sdp_kernel(enable_math=False, enable_flash=True,
                          enable_mem_efficient=False):
            F.scaled_dot_product_attention(x, x, x, is_causal=True)


# ===================== biased (additive-mask) flash =====================


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("bshape", [(2, 2, 64, 128), (1, 1, 64, 128),
                                    (2, 1, 64, 128)])
def test_flash_biased_matches_reference(causal, bshape):
    """Additive bias streamed blockwise must equal the reference's
    full-logits bias add — values and q/k/v grads (bias gets zero grad
    by contract; the entry gates on stop_gradient)."""
    B, SQ, SK, H, D = 2, 64, 128, 2, 16
    q, k, v = _rand((B, SQ, H, D)), _rand((B, SK, H, D)), _rand((B, SK, H, D))
    bias = _rand(bshape) * 0.3
    out = fa._flash_core_b(q, k, v, bias, causal, 32, 128)
    ref = fa._ref_attention(q, k, v, jnp.broadcast_to(
        bias, (B, H, SQ, SK)), causal)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)

    def loss_b(q_, k_, v_):
        o = fa._flash_core_b(q_, k_, v_, bias, causal, 32, 128)
        return (o.astype(jnp.float32) * 0.01).sum()

    def loss_ref(q_, k_, v_):
        o = fa._ref_attention(q_, k_, v_, jnp.broadcast_to(
            bias, (B, H, SQ, SK)), causal)
        return (o.astype(jnp.float32) * 0.01).sum()

    gf = jax.grad(loss_b, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(a, b, atol=3e-5, rtol=3e-5)
    # bias cotangent is zero by contract
    gb = jax.grad(lambda b_: (fa._flash_core_b(
        q, k, v, b_, causal, 32, 128).astype(jnp.float32) * 0.01).sum())(
        bias)
    np.testing.assert_allclose(gb, np.zeros_like(bias))


def test_flash_biased_bool_mask_and_gate():
    """Boolean masks convert to additive -inf on the biased core
    (exercised DIRECTLY — the entry falls back on CPU, so the gate logic
    is tested as a unit)."""
    B, S, H, D = 1, 128, 2, 16
    q = _rand((B, S, H, D))
    keep = jnp.asarray(
        np.random.RandomState(0).rand(1, 1, S, S) > 0.3)
    bias = jnp.where(keep, 0.0, fa.NEG_INF).astype(jnp.float32)
    out = fa._flash_core_b(q, q, q, bias, False, 64, 128)
    ref = fa._ref_attention(q, q, q, keep, False)
    np.testing.assert_allclose(out, ref, atol=3e-5, rtol=3e-5)
    # gate unit tests: accepts the canonical shape, rejects GQA, odd
    # lengths, and non-broadcastable masks
    kgqa = jnp.zeros((B, S, 1, D))
    assert fa._biased_flash_ok(q, q, jnp.zeros((1, 1, S, S)))
    assert fa._biased_flash_ok(q, q, jnp.zeros((B, H, S, S)))
    assert not fa._biased_flash_ok(q, kgqa, jnp.zeros((1, 1, S, S)))
    assert not fa._biased_flash_ok(q, q, jnp.zeros((1, 1, S, S - 8)))
    assert not fa._biased_flash_ok(q, q, jnp.zeros((3, 1, S, S)))
    q_odd = _rand((B, 200, H, D))
    assert not fa._biased_flash_ok(q_odd, q_odd,
                                   jnp.zeros((1, 1, 200, 200)))


def test_tuned_blocks_untuned_default(monkeypatch):
    """Autotune-cold default = the hardware sweep winner that FITS the
    shape under the tightened 8 MB bound (PERF.md r5: (512,1024) wins
    fwd+bwd at the bench and LLaMA shapes), never an oversized pair."""
    from paddle_tpu.ops.pallas import autotune

    monkeypatch.setattr(autotune, "_enabled", lambda: False)
    # bench shape B32 H12 S1024 D64: winner fits well under 8 MB
    assert fa._tuned_blocks(32, 1024, 1024, 12, 64, jnp.bfloat16,
                            True) == (512, 1024)
    # LLaMA-class shape: same winner at D=128
    assert fa._tuned_blocks(8, 2048, 2048, 16, 128, jnp.bfloat16,
                            True) == (512, 1024)
    # biased at S=2048 the (512,1024) bias band alone is 8 MB — the
    # default must shrink rather than return an unvalidated near-limit
    # pair (vmem_est omits backward-only accumulators)
    bq, bk = fa._tuned_blocks(8, 2048, 2048, 16, 128, jnp.bfloat16,
                              True, biased=True)
    assert (bq, bk) != (512, 1024) and bq <= 512
    # short sequences: blocks clamp to the sequence
    bq, bk = fa._tuned_blocks(8, 128, 128, 4, 64, jnp.bfloat16, True)
    assert bq <= 128 and bk <= 128


def test_autotune_pick_contract(monkeypatch, tmp_path):
    """autotune.pick's (f, x) chainable-runner contract (round-5 timing
    methodology v2): candidates are timed inside one compiled loop, the
    winner is disk-cached, and cache hits skip the search. The TPU gate
    is bypassed so the search path runs on CPU."""
    from paddle_tpu.ops.pallas import autotune

    monkeypatch.setattr(autotune, "_CACHE_PATH",
                        str(tmp_path / "autotune.json"))
    # monkeypatch restores _cache to None at teardown — without this the
    # fake test keys would stay in the module-global cache and a later
    # in-process search would _save() them into the user's real cache
    monkeypatch.setattr(autotune, "_cache", None)

    class _Dev:
        platform = "tpu"
        device_kind = "test-kind"

    monkeypatch.setattr(autotune.jax, "devices", lambda: [_Dev()])
    calls = []

    def run(cfg):
        calls.append(cfg)
        # millisecond-scale per iteration: with a microsecond toy body the
        # n2-vs-n1 slope is pure scheduler noise under a loaded CPU and
        # every candidate can "fail" its timing (observed flake: no cache
        # write -> the re-search assertion below trips)
        w = jnp.eye(256, dtype=jnp.float32) * (
            1.0 if cfg == "small" else 1.0001)

        def f(y):
            return y @ w

        return f, jnp.ones((256, 256), jnp.float32)

    got = autotune.pick("testop", "sig1", ["small", "big"], run, "small")
    assert got in ("small", "big")
    assert set(calls) == {"small", "big"}
    # disk-cached: a fresh in-process cache still skips the search
    calls.clear()
    autotune._cache = None
    again = autotune.pick("testop", "sig1", ["small", "big"], run, "small")
    assert again == got
    assert calls == []
    # a failing candidate just loses; the survivor wins
    def run2(cfg):
        if cfg == "bad":
            raise RuntimeError("no compile")
        w2 = jnp.eye(128, dtype=jnp.float32)
        return (lambda y: y @ w2 + 1.0), jnp.zeros((128, 128), jnp.float32)

    assert autotune.pick("testop", "sig2", ["bad", "ok"], run2,
                         "bad") == "ok"


def test_autotune_pick_searches_inside_a_trace(monkeypatch, tmp_path):
    """Dispatch sites call pick() while the train step is being traced
    (jit of value_and_grad).  The candidates must still run eagerly: on
    the chip every candidate once failed with a tracer-conversion error
    and the search silently fell back to the default (PR 23)."""
    from paddle_tpu.ops.pallas import autotune

    monkeypatch.setattr(autotune, "_CACHE_PATH",
                        str(tmp_path / "autotune.json"))
    monkeypatch.setattr(autotune, "_cache", None)
    monkeypatch.setattr(autotune, "_devkind", lambda: "test-kind")
    timed = []

    q = jax.ShapeDtypeStruct((2, 256, 2, 64), jnp.bfloat16)

    def flash_loss(y):
        return fa._flash_core_flat(y, y, y, True, 128, 128).astype(
            jnp.float32).sum()

    def run(cfg):
        # a candidate's Pallas kernel must TRACE out here too (the chip
        # run's second failure: 'program_id' evaluated eagerly)
        with fa.force_tpu_lowering():
            text = jax.jit(jax.grad(flash_loss)).trace(q).lower(
                lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text
        w = jnp.eye(256, dtype=jnp.float32) * cfg
        return (lambda y: y @ w), jnp.ones((256, 256), jnp.float32)

    def loss(z):
        timed.append(autotune.pick("testop", "sig", [1.0, 1.0001], run,
                                   None))
        return (z * 2).sum()

    jax.jit(jax.value_and_grad(loss))(jnp.ones(3))
    assert timed and timed[0] in (1.0, 1.0001)   # a winner, not the default
    assert autotune.cached_config("testop", "sig") == timed[0]


@pytest.mark.parametrize("layout", ["transpose", "flat"])
def test_train_step_layout_parity(monkeypatch, layout):
    """FULL GPT train step against the reference attention: on the
    interpreter both cores run the same shared recurrences, so three
    steps of training must produce the reference path's losses whether
    the dispatch reaches the transpose or the flat core. Guards the
    cores at the train-step level (not just the kernel level)."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    import paddle_tpu.ops.pallas as _pl

    # hidden 128 / 2 heads -> head_dim 64, H*D = 128: satisfies both the
    # lane-alignment gate AND the d%64 head-width gate (_flat_static_ok),
    # so the shape takes flat by itself; the transpose case refuses it
    kw = dict(vocab_size=211, hidden_size=128, num_layers=2, num_heads=2,
              max_seq_len=32, dropout=0.0, attn_dropout=0.0)
    core = {"transpose": "_flash_core", "flat": "_flash_core_flat"}[layout]
    routed = []

    def three_steps():
        topology.reset_topology()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
            "sep_degree": 1, "sharding_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        P.seed(11)
        model = GPTForCausalLM(GPTConfig(**kw))
        crit = GPTPretrainingCriterion()
        dm = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            P.optimizer.SGD(parameters=model.parameters(),
                            learning_rate=0.1))
        step = dm.build_train_step(opt, crit)
        rs = np.random.RandomState(3)
        ids = P.to_tensor(rs.randint(0, 211, (2, 32)), "int32")
        lab = P.to_tensor(rs.randint(0, 211, (2, 32)), "int32")
        return [float(step(ids, lab)) for _ in range(3)]

    reference = three_steps()   # flash unavailable on CPU: _ref_attention
    # BOTH bindings: fa.flash_attention_fwd consults the module global,
    # but nn.functional.attention gates on the package re-export — the
    # unpatched one silently routes everything to the reference path
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    monkeypatch.setattr(_pl, "flash_attention_available",
                        lambda q_: True)
    if layout == "transpose":
        monkeypatch.setattr(fa, "_flat_static_ok", lambda q_, k_: False)
    orig_core = getattr(fa, core)

    def spy(*a, **kw2):
        routed.append(layout)
        return orig_core(*a, **kw2)

    monkeypatch.setattr(fa, core, spy)
    losses = three_steps()
    assert routed, (
        f"{layout!r} never reached its flash core — dispatch fell "
        "back, the parity comparison would be vacuous")
    np.testing.assert_allclose(losses, reference, rtol=1e-5, atol=1e-5)
