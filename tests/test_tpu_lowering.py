"""TPU-lowering CI gate for the Pallas kernel tier (VERDICT r2 task 2).

Every Pallas kernel is lowered FOR THE TPU PLATFORM on the CPU host via
`jax.export(..., platforms=['tpu'])`. Mosaic runs its BlockSpec/layout
checks at lowering time, so the exact class of failure that crashed the
round-2 bench on hardware (rank-1 LSE block) is caught here without a chip.
Interpreter mode is disabled through `force_tpu_lowering()`; each test
asserts the lowered module really contains the Mosaic custom call so a
silent interpreter fallback can't make the gate vacuous.

Reference parity: kernels are compiled and run on-device in CI
(test/cpp/phi/, SURVEY §4) — this is the no-hardware TPU equivalent.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.core.export_compat import (
    get_jax_export, jax_export_available,
)
from paddle_tpu.ops.pallas import flash_attention as fa

# collection-safe on builds lacking jax.export: the whole gate skips
# with a reason instead of dying at import
pytestmark = pytest.mark.skipif(
    not jax_export_available(),
    reason="jax.export unavailable in this jax build "
           "(core.export_compat.ExportUnavailableError)")
from paddle_tpu.ops.pallas.decode_attention import decode_attention as da_fn
from paddle_tpu.ops.pallas import fused_norm as fn
from paddle_tpu.ops.pallas import rope as rp


def _lower_for_tpu(f, *args):
    """Export f for TPU from the CPU host; return StableHLO text."""
    specs = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args]
    with fa.force_tpu_lowering():
        exported = get_jax_export().export(
            jax.jit(f), platforms=["tpu"])(*specs)
    return exported.mlir_module()


def _assert_mosaic(mlir: str):
    # a silently-interpreted kernel would produce no custom call at all
    assert "tpu_custom_call" in mlir or "mosaic" in mlir.lower(), (
        "Pallas kernel did not lower through Mosaic — interpreter fallback?")


# bench shapes (B, H=12, S=1024, D=64) + model-zoo shapes:
# GPT-125M (12h, 64d), GPT-1.3B proxy (32h, 64d), LLaMA-ish (32h, 128d)
FLASH_SHAPES = [
    (8, 1024, 12, 64),
    (16, 1024, 12, 64),
    (32, 1024, 12, 64),
    (4, 2048, 32, 64),
    (2, 2048, 32, 128),
]


@pytest.mark.parametrize("shape", FLASH_SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_fwd_lowers(shape, causal):
    b, s, h, d = shape
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    f = lambda q, k, v: fa._flash_core(q, k, v, causal, 128, 128)
    mlir = _lower_for_tpu(f, q, q, q)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("shape", [(8, 1024, 12, 64), (2, 2048, 32, 128)])
def test_flash_attention_bwd_lowers(shape):
    b, s, h, d = shape
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            fa._flash_core(q, k, v, True, 128, 128).astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    _assert_mosaic(mlir)


def test_flash_attention_default_blocks_lower():
    """The untuned default pair is whatever the hardware sweep last won
    ((512,1024) since r5) and runs UNVALIDATED when autotune is off — so
    the gate must prove it lowers, fwd and bwd, at the bench shape."""
    b, s, h, d = 32, 1024, 12, 64
    bq, bk = fa._tuned_blocks(b, s, s, h, d, jnp.bfloat16, True)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            fa._flash_core(q, k, v, True, bq, bk).astype(jnp.float32))

    _assert_mosaic(_lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)),
                                  q, q, q))


@pytest.mark.parametrize("kind", ["ln", "rms"])
@pytest.mark.parametrize("rows,d", [(32 * 1024, 768), (4096, 1024)])
def test_fused_norm_lowers(kind, rows, d):
    x = jnp.zeros((rows, d), jnp.bfloat16)
    w = jnp.ones((d,), jnp.bfloat16)
    b = jnp.zeros((d,), jnp.bfloat16)

    def f(x, w, b):
        return fn.fused_norm_pallas(x, w, b, None, None, eps=1e-5, kind=kind)

    mlir = _lower_for_tpu(f, x, w, b)
    _assert_mosaic(mlir)


def test_fused_norm_bwd_lowers():
    x = jnp.zeros((8192, 768), jnp.bfloat16)
    w = jnp.ones((768,), jnp.bfloat16)

    def loss(x, w):
        out = fn.fused_norm_pallas(x, w, None, None, None,
                                   eps=1e-5, kind="rms")
        return jnp.sum(out.astype(jnp.float32))

    # value_and_grad: with grad alone XLA DCEs the pallas forward (the
    # saved residuals are (x, w), not y) and the gate would test nothing
    mlir = _lower_for_tpu(jax.value_and_grad(loss, argnums=(0, 1)), x, w)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("b,s,h,d", [(8, 1024, 12, 64), (2, 2048, 32, 128)])
def test_rope_lowers(b, s, h, d):
    x = jnp.zeros((b, s, h, d), jnp.bfloat16)
    cos = jnp.zeros((1, s, 1, d), jnp.float32)  # rope phase layout
    sin = jnp.zeros((1, s, 1, d), jnp.float32)
    mlir = _lower_for_tpu(rp.rope_pallas, x, cos, sin)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("b,h,s,d", [(8, 12, 1024, 64), (4, 32, 2048, 128)])
def test_decode_attention_lowers(b, h, s, d):
    q = jnp.zeros((b, h, d), jnp.bfloat16)
    cache = jnp.zeros((b, h, s, d), jnp.bfloat16)
    pos = jnp.zeros((b,), jnp.int32)
    f = functools.partial(da_fn, block_k=256)
    mlir = _lower_for_tpu(f, q, cache, cache, pos)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("hq,hkv", [(32, 8), (12, 12), (16, 2)])
def test_decode_attention_gqa_lowers(hq, hkv):
    """Grouped-query decode: q block [G, D] per KV head + [2,B] scalar
    prefetch (pos+start) must lower through Mosaic."""
    b, s, d = 4, 1024, 64
    q = jnp.zeros((b, hq, d), jnp.bfloat16)
    cache = jnp.zeros((b, hkv, s, d), jnp.bfloat16)
    pos = jnp.zeros((b,), jnp.int32)
    start = jnp.zeros((b,), jnp.int32)
    f = functools.partial(da_fn, block_k=256)
    mlir = _lower_for_tpu(lambda q, kc, vc, p, st: f(q, kc, vc, p, start=st),
                          q, cache, cache, pos, start)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("sq,sk", [(128, 1024), (1024, 128)])
def test_flash_cross_length_causal_lowers(sq, sk):
    """Bottom-right-aligned causal with seq_q != seq_k (decode/chunked
    shapes): traced offset loop bounds must lower."""
    q = jnp.zeros((2, sq, 8, 64), jnp.bfloat16)
    k = jnp.zeros((2, sk, 8, 64), jnp.bfloat16)
    mlir = _lower_for_tpu(
        lambda q, k, v: fa._flash_core(q, k, v, True, 128, 128), q, k, k)
    _assert_mosaic(mlir)


def test_gate_catches_bad_blockspec():
    """Meta-test: the gate actually fails on a Mosaic-illegal kernel (the
    round-2 bug shape — rank-1 stats output block)."""
    from jax.experimental import pallas as pl

    def bad_kernel(x_ref, o_ref):
        o_ref[:] = jnp.sum(x_ref[:], axis=1)

    def bad(x):
        return pl.pallas_call(
            bad_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((None, 128, 128), lambda i: (i, 0, 0))],
            out_specs=pl.BlockSpec((None, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
        )(x)

    x = jnp.zeros((4, 128, 128), jnp.float32)
    with pytest.raises(Exception):
        _lower_for_tpu(bad, x)


def test_flash_padded_vit_length_lowers():
    """The padded odd-length path (flash_attention_fwd at ViT's S=197)
    must lower: pad -> kernel with real-length masking -> slice."""
    b, s, h, d = 2, 197, 12, 64
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)

    def f(q, k, v):
        return fa.flash_attention_fwd(q, k, v, is_causal=False,
                                      block_q=128, block_k=128)

    mlir = _lower_for_tpu(f, q, q, q)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("shape", [(8, 1024, 12, 64), (2, 1024, 12, 64)])
def test_flash_flat_fwd_bwd_lowers(shape):
    """The flat-native core (unpadded [B,S,H*D] views, per-head 64-lane
    slices — round-5 kernels) must lower for both directions. NOTE: the
    local gate is necessary but not sufficient for this tier — the
    deployed server Mosaic has stricter rules, see docs/ATTENTION.md
    'The layout story'."""
    b, s, h, d = shape
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    f = lambda q, k, v: fa._flash_core_flat(q, k, v, True, 128, 128)
    mlir = _lower_for_tpu(f, q, q, q)
    _assert_mosaic(mlir)

    def loss(q, k, v):
        return jnp.sum(
            fa._flash_core_flat(q, k, v, True, 128, 128)
            .astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    _assert_mosaic(mlir)


@pytest.mark.parametrize("tier,shape", [
    ("flat", (32, 1024, 12, 64)),        # gpt3-125m.train.seq1024
    ("transpose", (16, 2048, 12, 64)),   # gpt3-125m.train.seq2048
])
def test_flash_fused_backward_lowers(tier, shape):
    """The fused backward (dQ, dK, dV from one kernel: sequence-long dQ
    resident over the KV axis, lane-dense lse rows, VMEM scratch) at the
    two benchmark cells' shapes and blocks: forward + ONE backward
    call."""
    core = {"flat": fa._flash_core_flat, "transpose": fa._flash_core}[tier]
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(core(q, k, v, True, 512, 512).astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    _assert_mosaic(mlir)
    assert mlir.count("stablehlo.custom_call @tpu_custom_call") == 2, (
        "expected the forward and one fused backward kernel")
    assert f"flash_{tier}_bwd" in mlir


@pytest.mark.parametrize("shape", [(4, 2048, 32, 8, 128)])
def test_flash_gqa_lowers(shape):
    """LLaMA-2/3-class GQA (32 query / 8 KV heads): grouped index maps
    must lower for both directions."""
    b, s, hq, hkv, d = shape
    q = jax.ShapeDtypeStruct((b, s, hq, d), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((b, s, hkv, d), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(
            fa._flash_core(q, k, v, True, 128, 128).astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, kv, kv)
    _assert_mosaic(mlir)


def test_varlen_attention_lowers():
    """Segment-masked packed attention (flash_attn_unpadded role) must
    lower for both directions at a real packed size."""
    from paddle_tpu.ops.pallas import varlen_attention as vla

    T, H, D = 4096, 12, 64
    cu = jnp.asarray([0, 1024, 2560, 4096], jnp.int32)
    q = jax.ShapeDtypeStruct((T, H, D), jnp.bfloat16)

    def loss(q, k, v):
        o = vla.varlen_attention(q, k, v, cu, cu, causal=True)
        return jnp.sum(o.astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
    _assert_mosaic(mlir)


def test_flash_biased_lowers():
    """Biased kernels (additive mask on the fused tier) must lower for
    both directions at the bench shape with a broadcast [1,H,S,S] bias."""
    b, s, h, d = 8, 1024, 12, 64
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16)
    bias = jax.ShapeDtypeStruct((1, h, s, s), jnp.float32)

    def loss(q, k, v, bias):
        o = fa._flash_core_b(q, k, v, bias, False, 256, 512)
        return jnp.sum(o.astype(jnp.float32))

    mlir = _lower_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q, bias)
    _assert_mosaic(mlir)
