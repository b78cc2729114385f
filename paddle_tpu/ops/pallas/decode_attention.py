"""Blocked KV-cache decode attention — Pallas TPU kernel.

Role parity: `paddle/phi/kernels/fusion/gpu/
masked_multihead_attention_kernel.cu` and
`block_multi_head_attention_kernel.cu` (exposed as
`incubate.nn.functional.masked_multihead_attention`).

Design (TPU-first):
  * One query token per (batch, head) grid cell attends over its KV cache
    with an online-softmax fori_loop over KV blocks — the loop bound is
    `ceil((pos+1)/block_k)` from a scalar-prefetched position vector, so
    a decode step costs O(tokens-in-cache), not O(cache-capacity). The
    jnp fallback attends the full fixed-size cache every step; this is
    the algorithmic win (plus: logits never hit HBM).
  * Shapes are static (cache capacity S), so the decode loop compiles
    once; only the scalar positions change step to step.
  * Inference-only (no VJP) — decode never backprops.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _pick_block, NEG_INF


def decode_attention_available(cache_shape) -> bool:
    from ...core import flags

    if not flags.pallas_enabled("decode"):
        return False
    _, b, h, s, d = cache_shape
    if d % 8 != 0 or d > 256 or s % 8 != 0:
        return False
    return not _interpret()


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_k, seq,
                   scale):
    bi = pl.program_id(0)
    pos = pos_ref[0, bi]                    # tokens start..pos are valid
    start = pos_ref[1, bi]                  # left-padded rows: start > 0
    q = q_ref[:].astype(jnp.float32) * scale        # [G, D]

    g = q.shape[0]                          # grouped queries per KV head
    d = q.shape[-1]
    # stats kept rank-2 (G, 1): rank-1 loop state does not lower through
    # Mosaic (same failure class as the round-2 flash LSE BlockSpec)
    m0 = jnp.full((g, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((g, 1), jnp.float32)
    acc0 = jnp.zeros((g, d), jnp.float32)

    first = start // block_k                # skip fully-padded blocks
    num_iters = (pos + block_k) // block_k  # == cdiv(pos+1, block_k)

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [G,bk]
        k_ids = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (g, block_k), 1)
        s = jnp.where((k_ids >= start) & (k_ids <= pos), s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m, l, acc = jax.lax.fori_loop(first, num_iters, body, (m0, l0, acc0))
    o_ref[:] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def decode_attention(q, kcache, vcache, pos, block_k=256, interpret=None,
                     start=None):
    """q: [B, Hq, D] current-token queries; kcache/vcache: [B, Hkv, S, D]
    (already containing the current token at index pos[b]); pos: [B] int32.
    start: optional [B] int32 — first valid cache index per row (> 0 for
    left-padded prompts; padding slots never contribute). Hq may be a
    multiple of Hkv (GQA): each KV head serves the Hq/Hkv-query group in
    one grid cell, so the cache is read ONCE per KV head — the bandwidth
    shape GQA exists for. Returns [B, Hq, D]."""
    b, hq, d = q.shape
    hkv = kcache.shape[1]
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of KV heads "
                         f"{hkv}")
    g = hq // hkv
    s = kcache.shape[2]
    scale = 1.0 / (d ** 0.5)
    block_k = _pick_block(s, block_k)
    q4 = q.reshape(b, hkv, g, d)
    if start is None:
        start = jnp.zeros((b,), jnp.int32)
    pos2 = jnp.stack([pos.astype(jnp.int32),
                      start.astype(jnp.int32)])      # [2, B] scalar prefetch
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv),
        in_specs=[
            pl.BlockSpec((None, None, g, d), lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s, d), lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, s, d), lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, g, d),
                               lambda bi, hi, pos_ref: (bi, hi, 0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_decode_kernel, block_k=block_k, seq=s,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), q.dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="decode_attention_fwd",
    )(pos2, q4, kcache, vcache)
    return out.reshape(b, hq, d)
