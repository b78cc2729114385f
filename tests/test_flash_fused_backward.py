"""The fused flash backward (one kernel: dQ, dK and dV from a single
replay of the logits) against the split dq + dkdv pair as oracle.

Both run through the Pallas interpreter on the CPU.  The fused kernel
does the split pair's dots on the same operands and sums in the same
order, but on the TRANSPOSED tile (sT = k·qT), so the only thing that
may differ is how the backend orders the additions INSIDE a dot of the
other orientation.  XLA's CPU dot is orientation-invariant for float32
at square tiles up to 128 (checked when these tests were written: it is
not at 16 x 32, at 200 x 200 or in bfloat16), so the cases use float32
and square blocks, and equality is exact: array_equal, not allclose.

The split oracle is reached the way a shape too large for the fused
kernel reaches it: the core's VMEM limit, set to 0 by the test."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import flash_attention as fa

CORES = {"transpose": (fa._flash_core, "_T_VMEM_LIMIT"),
         "flat": (fa._flash_core_flat, "_FLAT_VMEM_LIMIT")}


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


@pytest.fixture
def backward_kinds():
    """Deltas of `flash.backward{kind,tier}` since the test began."""
    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])

    def delta():
        now = metrics.snapshot()["counters"]
        return {k: v - before.get(k, 0) for k, v in now.items()
                if k.startswith("flash.backward") and v - before.get(k, 0)}

    yield delta
    if not was:
        metrics.disable()


def _grads(f, *qkv):
    return jax.grad(lambda *a: f(*a).astype(jnp.float32).sum(),
                    argnums=(0, 1, 2))(*qkv)


def _assert_same(fused, split, what):
    for name, a, b in zip("qkv", fused, split):
        assert np.array_equal(np.asarray(a), np.asarray(b)), \
            f"d{name} of the fused backward differs from the split pair's " \
            f"({what})"


@pytest.mark.parametrize("tier", ["flat", "transpose"])
@pytest.mark.parametrize("sq,sk", [(96, 96), (64, 128)])
@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_equals_split_pair(monkeypatch, backward_kinds,
                                          causal, hq, hkv, sq, sk, tier):
    """dq, dk, dv bit for bit: causal and full, MHA and GQA (rep 2),
    sq = sk and sq < sk (bottom-right aligned), three q and KV blocks so
    the masked, the mask-free and the skipped tiles all occur."""
    core, limit = CORES[tier]
    q = _rand((2, sq, hq, 64), 0)
    k = _rand((2, sk, hkv, 64), 1)
    v = _rand((2, sk, hkv, 64), 2)

    def f(q, k, v):
        return core(q, k, v, causal, 32, 32)

    fused = _grads(f, q, k, v)
    assert backward_kinds() == {
        f"flash.backward{{kind=fused,tier={tier}}}": 1}
    monkeypatch.setattr(fa, limit, 0)
    split = _grads(f, q, k, v)
    assert backward_kinds() == {
        f"flash.backward{{kind=fused,tier={tier}}}": 1,
        f"flash.backward{{kind=split,tier={tier}}}": 1}
    _assert_same(fused, split, f"{tier} causal={causal} {hq}/{hkv} heads "
                               f"{sq}x{sk}")


@pytest.mark.parametrize("causal", [True, False])
def test_fused_backward_padded_real_length(monkeypatch, backward_kinds,
                                           causal):
    """ViT's 197 through the public entry: padded to 200, five blocks of
    40, real-length masks on both sides; the padded key rows reach
    neither dK/dV (sliced off) nor dQ (masked in the fused tile)."""
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    q, k, v = (_rand((2, 197, 2, 64), s) for s in range(3))

    def f(q, k, v):
        return fa.flash_attention_fwd(q, k, v, is_causal=causal,
                                      block_q=40, block_k=40)

    fused = _grads(f, q, k, v)
    assert backward_kinds() == {
        "flash.backward{kind=fused,tier=transpose}": 1}
    monkeypatch.setattr(fa, "_T_VMEM_LIMIT", 0)
    split = _grads(f, q, k, v)
    assert fused[0].shape == (2, 197, 2, 64)
    _assert_same(fused, split, f"197 padded, causal={causal}")


def test_estimate_forces_the_split_pair(backward_kinds):
    """Where the KV head's group of sequence-long q/o/do/dq does not fit
    the kernel's VMEM by the core's own estimate, the core runs the
    split pair: four float32 query heads of size 256 over 512 positions
    need 18.9 MB of the 16 MiB; one such head fits."""
    assert fa._t_vmem_bytes(512, 512, 4, 256, 4, 128, 128,
                            fused=True) > fa._T_VMEM_LIMIT
    assert fa._t_vmem_bytes(512, 512, 1, 256, 4, 128, 128,
                            fused=True) <= fa._T_VMEM_LIMIT
    q = _rand((1, 512, 4, 256), 0)
    k = _rand((1, 512, 1, 256), 1)
    v = _rand((1, 512, 1, 256), 2)
    got = _grads(lambda *a: fa._flash_core(*a, True, 128, 128), q, k, v)
    assert backward_kinds() == {
        "flash.backward{kind=split,tier=transpose}": 1}
    want = _grads(lambda *a: fa._ref_attention(*a, None, True), q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("tier", ["flat", "transpose"])
def test_fused_backward_bfloat16_matches_reference(backward_kinds, tier):
    """The dtype the chip trains in: bfloat16 operands, float32
    accumulation, against the float32 reference."""
    core, _ = CORES[tier]
    q, k, v = (_rand((2, 128, 2, 64), s).astype(jnp.bfloat16)
               for s in range(3))
    got = _grads(lambda *a: core(*a, True, 64, 64), q, k, v)
    assert backward_kinds() == {
        f"flash.backward{{kind=fused,tier={tier}}}": 1}
    want = _grads(lambda *a: fa._ref_attention(*a, None, True),
                  *(x.astype(jnp.float32) for x in (q, k, v)))
    for a, b in zip(got, want):
        assert a.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b), atol=0.1, rtol=0.05)


def test_col_to_row_is_exact():
    """The in-kernel column -> row relayout of delta moves values, and
    does no arithmetic on them (any length, 128 lanes at a time)."""
    for n in (8, 40, 128, 200, 512):
        col = _rand((n, 1), n)
        row = fa._col_to_row(col)
        assert row.shape == (1, n)
        assert np.array_equal(np.asarray(row)[0], np.asarray(col)[:, 0])
