"""A window in the flash cores (`window=`): forward and backward against
`_ref_attention` with a band mask, over the backward tiers a windowed call
reaches (the fused kernel and the split dq + dkdv pair), and the
un-windowed call untouched."""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.pallas import flash_attention as fa


def _qkv(rs, b, s, h, h_kv, d, dtype=jnp.float32):
    return (jnp.asarray(rs.randn(b, s, h, d), dtype),
            jnp.asarray(rs.randn(b, s, h_kv, d), dtype),
            jnp.asarray(rs.randn(b, s, h_kv, d), dtype))


def _check(q, k, v, window, blocks):
    def out(f):
        return lambda q, k, v: (f(q, k, v) ** 2).sum()

    core = lambda q, k, v: fa._flash_core(q, k, v, True, *blocks, None, None,
                                          window)
    ref = lambda q, k, v: fa._ref_attention(q, k, v, None, True, window)
    # float32 operands, interpret mode: only the order of summation
    # differs (online softmax in blocks against one softmax a row)
    np.testing.assert_allclose(core(q, k, v), ref(q, k, v), atol=2e-5)
    got = jax.grad(out(core), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(out(ref), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=2e-5 * float(jnp.abs(w).max()))


@pytest.mark.parametrize("tier", ["fused", "split"])
@pytest.mark.parametrize("window,blocks", [
    (8, (32, 32)),      # narrower than a block: both edges in one tile
    (40, (32, 32)),     # the band's edges off the blocks' edges
    (40, (32, 64)),
    (40, (64, 32)),
    (64, (32, 32)),     # a whole number of blocks
    (128, (32, 32)),    # the sequence itself: the whole causal half
    (200, (32, 32)),    # larger than the sequence
])
def test_windowed_core_matches_band_mask(rng, monkeypatch, tier, window,
                                         blocks):
    if tier == "split":     # nothing fits: _bwd_t takes the split pair
        monkeypatch.setattr(fa, "_T_VMEM_LIMIT", 0)
    _check(*_qkv(rng, 1, 128, 2, 2, 32), window, blocks)


@pytest.mark.parametrize("tier", ["fused", "split"])
def test_windowed_core_grouped_eight_to_one(rng, monkeypatch, tier):
    """GQA 8:1 through the grouped index maps, and after the dispatch's
    eightfold expansion (what the benchmark's cell runs)."""
    if tier == "split":
        monkeypatch.setattr(fa, "_T_VMEM_LIMIT", 0)
    q, k, v = _qkv(rng, 1, 128, 8, 1, 32)
    _check(q, k, v, 40, (32, 64))
    _check(*fa._expand_gqa_kv(q, k, v), 40, (32, 64))


def test_band_block_bounds_by_hand():
    """q block 3 of 32 rows (rows 96..127), window 40, KV blocks of 32:
    the lowest key any row sees is 96 - 39 = 57 (block 1); block 2 (keys
    64..95) is inside every row's band only from key 127 - 39 = 88 on, so
    no block is whole: blocks 1 and 2 take the left edge, block 3 the
    diagonal."""
    lo, full_lo, full_hi = fa._band_k_blocks(3, 32, 32, 0, 40, 3, 4)
    assert (int(lo), int(full_lo), int(full_hi)) == (1, 3, 3)
    # window 100: rows 96..127 see keys >= -3..28; block 1 (32..63) whole
    lo, full_lo, full_hi = fa._band_k_blocks(3, 32, 32, 0, 100, 3, 4)
    assert (int(lo), int(full_lo), int(full_hi)) == (0, 1, 3)
    # KV block 1 (keys 32..63), window 40, q blocks of 32: seen by rows
    # 32..102: blocks 1 (diagonal), 2 (rows 64..95: key 32 is out of row
    # 72's band) and 3 (rows 96..102): none whole
    start, first_full = fa._causal_q_blocks(1, 32, 32, 0, 4)
    first_full, full_hi, end = fa._band_q_blocks(1, 32, 32, 0, 40, start,
                                                 first_full, 4)
    assert (int(start), int(first_full), int(full_hi), int(end)) == (1, 2, 2, 4)


def _counters(metrics, before):
    now = metrics.snapshot()["counters"]
    return {k: v - before.get(k, 0) for k, v in now.items()
            if k.startswith("flash.") and v - before.get(k, 0)}


def test_dispatch_windowed_call_names_counts_and_falls_back(rng, monkeypatch):
    """`flash_attention_fwd(window=)`: the transpose core under a name of
    its own, `flash.dispatch{tier,window}`; a window that covers the
    sequence is the un-windowed call; a mask, no causality or a length
    that needs padding is refused; `flash.gqa_expand{reason}` says why K
    and V grew."""
    from paddle_tpu.observability import metrics

    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    was = metrics.enabled()
    metrics.enable()
    try:
        q, k, v = _qkv(rng, 1, 64, 4, 2, 32)
        before = dict(metrics.snapshot()["counters"])
        got = fa.flash_attention_fwd(q, k, v, is_causal=True, window=24)
        np.testing.assert_allclose(
            got, fa._ref_attention(q, k, v, None, True, 24), atol=2e-5)
        c = _counters(metrics, before)
        assert c["flash.dispatch{tier=transpose,window=24}"] == 1
        assert not any("gqa_expand" in n for n in c)    # grouped: it fits
        text = str(jax.make_jaxpr(lambda *a: fa.flash_attention_fwd(
            *a, is_causal=True, window=24))(q, k, v))
        assert "flash_transpose_window_fwd" in text

        before = dict(metrics.snapshot()["counters"])
        fa.flash_attention_fwd(q, k, v, is_causal=True, window=64)
        c = _counters(metrics, before)
        assert c["flash.dispatch{tier=transpose}"] == 1  # no window left
        assert not any("window" in n for n in c)

        # the group past the bound (here 3 * 2 * 64 * 32 * 4 bytes)
        monkeypatch.setattr(fa, "_GQA_GROUP_BYTES_MAX",
                            3 * 2 * 64 * 32 * 4 - 1)
        before = dict(metrics.snapshot()["counters"])
        fa.flash_attention_fwd(q, k, v, is_causal=True, window=24)
        assert _counters(metrics, before)[
            "flash.gqa_expand{reason=group_bytes}"] == 1
    finally:
        if not was:
            metrics.disable()
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_fwd(q, k, v, is_causal=False, window=24)
    with pytest.raises(ValueError, match="window"):
        fa.flash_attention_fwd(q, k, v, mask=jnp.zeros((1, 1, 64, 64)),
                               is_causal=True, window=24)
    # a padded length has no windowed kernel, and no reference stands in
    with pytest.raises(ValueError, match="multiples of 8"):
        fa.flash_attention_fwd(q[:, :60], k[:, :60], v[:, :60],
                               is_causal=True, window=24)


# sha256 of str(jaxpr) of the un-windowed transpose core's gradient (jax
# 0.9.0): `window=` costs the un-windowed path nothing, not one equation.
# Taken first on the commit BEFORE `window=` existed (a2c5d75:
# 68783f827b2c8f57, 3ad82295dbebd273) and again in PR 29, whose text differs
# from PR 28's in two equations and nothing else — `name[name=flash_out]` on
# out_t and `name[name=flash_lse]` on lse in `_flash_core_fwd` (640 -> 642
# and 1220 -> 1222 lines; every other line is the parent's with the later
# variables' names moved on by two).  A PR that changes these kernels on
# purpose records the hashes anew (the loop below prints them in its
# failure).
_UNWINDOWED = {
    ((2, 256, 4, 64), 4, (128, 128)): "b18fe6c7d9876274",
    ((1, 256, 8, 32), 2, (64, 128)): "78092cea31cd7807",
}


@pytest.mark.parametrize("shape,h_kv,blocks", list(_UNWINDOWED))
def test_unwindowed_jaxpr_is_the_parents(shape, h_kv, blocks):
    b, s, _, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16)

    def loss(q, k, v):
        return fa._flash_core(q, k, v, True, *blocks).astype(
            jnp.float32).sum()

    text = str(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k))
    assert "window" not in text
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == \
        _UNWINDOWED[(shape, h_kv, blocks)]
