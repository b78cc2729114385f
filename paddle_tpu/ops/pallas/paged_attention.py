"""Ragged paged-attention decode — Pallas TPU kernel + jnp reference.

Role parity: `block_multi_head_attention_kernel.cu`'s block-table decode
path (the reference's paged KV cache), in the style of *Ragged Paged
Attention* (PAPERS.md): each in-flight sequence keeps its KV state in
fixed-size pages drawn from a shared pool, addressed through a
per-sequence page table, with a per-sequence length — so one compiled
decode step serves a heterogeneous (ragged) batch without head-of-line
blocking on the longest request.

Design (TPU-first):
  * Grid ``(batch, kv_heads, pages)`` with the page table and positions
    scalar-prefetched: the KV BlockSpec index map reads
    ``page_table[b, p]`` to DMA each sequence's p-th page straight from
    the pool — the gather *is* the address computation, no materialized
    per-sequence contiguous cache ever exists.
  * Online softmax accumulates across the page grid axis in VMEM
    scratch (the flash pattern); pages entirely past a sequence's
    length are skipped with ``pl.when`` (compute cost is
    O(tokens-in-cache) per sequence, not O(pool capacity)).
  * Sequences shorter than the batch's longest simply run fewer page
    steps — raggedness costs masking, not padding to max length.
  * One query token per sequence slot; GQA groups ride the KV-head grid
    cell (the pool stores KV heads, read once per group).
  * Inference-only (no VJP) — decode never backprops.

Free slots in the engine's fixed batch point their page-table row at
page 0 (a reserved scratch page) with position 0: they compute one
masked page of garbage that the host discards — the compiled shape
never changes as sequences come and go.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import NEG_INF, _interpret

__all__ = [
    "paged_attention", "paged_attention_reference",
    "paged_attention_available", "paged_attention_dispatch",
]


def paged_attention_available(pool_shape, pool_dtype=None) -> bool:
    """Can the Pallas kernel serve this pool shape on this backend?
    pool_shape: [num_pages, kv_heads, page_size, head_dim].  An int8
    pool (the quantized KV tier) additionally needs page_size to cover
    the int8 sublane tile (32) — smaller pages fall back to the jnp
    reference rather than fight the Mosaic layout."""
    from ...core import flags

    if not flags.pallas_enabled("paged"):
        return False
    _, _, ps, d = pool_shape
    if d % 8 != 0 or d > 256 or ps % 8 != 0:
        return False
    if pool_dtype is not None and jnp.dtype(pool_dtype) == jnp.int8 \
            and ps % 32 != 0:
        return False
    return not _interpret()


def _paged_kernel(sp_ref, q_ref, k_ref, v_ref, *refs, page_size,
                  block_k, scale, quantized):
    # quantized pools carry two extra inputs: the per-token-per-head
    # scale rows of this page (ks_ref/vs_ref, [page_size] each) —
    # dequantization happens HERE, on the VMEM-resident block, inside
    # the online-softmax accumulation (the pool stays int8 in HBM)
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = refs
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    p = pl.program_id(2)
    npages = pl.num_programs(2)
    pos = sp_ref[bi, 0]                     # current token's index
    q = q_ref[:].astype(jnp.float32) * scale        # [G, D]
    g = q.shape[0]

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    base = p * page_size

    @pl.when(base <= pos)                   # page holds >= 1 valid key
    def _compute():
        # valid keys within this page: indices [base, min(pos, base+ps-1)]
        valid = jnp.minimum(pos - base + 1, page_size)
        nblk = (valid + block_k - 1) // block_k

        def body(j, carry):
            m, l, acc = carry
            k = k_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
            v = v_ref[pl.ds(j * block_k, block_k), :].astype(jnp.float32)
            if quantized:
                k = k * ks_ref[pl.ds(j * block_k, block_k)][:, None]
                v = v * vs_ref[pl.ds(j * block_k, block_k)][:, None]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)     # [G, bk]
            k_ids = base + j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (g, block_k), 1)
            s = jnp.where(k_ids <= pos, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            pexp = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(pexp, axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                pexp, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        m, l, acc = jax.lax.fori_loop(
            0, nblk, body, (m_ref[:], l_ref[:], acc_ref[:]))
        m_ref[:] = m
        l_ref[:] = l
        acc_ref[:] = acc

    @pl.when(p == npages - 1)
    def _finish():
        o_ref[:] = (acc_ref[:]
                    / jnp.maximum(l_ref[:], 1e-30)).astype(o_ref.dtype)


def paged_attention(q, k_pages, v_pages, page_table, pos, block_k=None,
                    interpret=None, k_scales=None, v_scales=None):
    """q: [B, Hq, D] current-token queries; k_pages/v_pages:
    [num_pages, Hkv, page_size, D] shared page pools (already containing
    each sequence's current token); page_table: [B, P] int32 page ids
    (unused tail entries must point at a reserved scratch page, e.g. 0);
    pos: [B] int32 — index of the current token per sequence (valid
    keys are exactly 0..pos[b]).  Hq may be a multiple of Hkv (GQA).

    Quantized KV tier (ISSUE 12): int8 pools with
    ``k_scales``/``v_scales`` [num_pages, Hkv, page_size] f32 — one
    scale per token vector per head, carried alongside the page table.
    The kernel interface is otherwise UNCHANGED (the Ragged Paged
    Attention design point): the same grid/BlockSpec gather also DMAs
    each page's scale row, and dequantization happens in VMEM inside
    the online-softmax accumulation, so page HBM traffic stays int8.
    Returns [B, Hq, D]."""
    b, hq, d = q.shape
    npool, hkv, ps, _ = k_pages.shape
    if hq % hkv != 0:
        raise ValueError(f"query heads {hq} not a multiple of KV heads "
                         f"{hkv}")
    quantized = k_scales is not None
    if quantized != (v_scales is not None):
        raise ValueError("k_scales and v_scales must be given together")
    g = hq // hkv
    p = page_table.shape[1]
    scale = 1.0 / (d ** 0.5)
    if block_k is None:
        block_k = ps
    block_k = min(int(block_k), ps)
    if ps % block_k != 0:
        raise ValueError(f"block_k {block_k} must divide page_size {ps}")
    q4 = q.reshape(b, hkv, g, d)
    sp = jnp.concatenate(
        [pos.astype(jnp.int32)[:, None],
         page_table.astype(jnp.int32)], axis=1)         # [B, 1+P]

    def page_spec(bs3=None):
        # the ragged gather: this sequence's pi-th page, straight
        # from the pool (scratch page 0 for unused tail entries)
        if bs3 is None:
            return pl.BlockSpec((None, None, ps),
                                lambda bi, hi, pi, sp_ref:
                                (sp_ref[bi, pi + 1], hi, 0))
        return pl.BlockSpec((None, None, ps, bs3),
                            lambda bi, hi, pi, sp_ref:
                            (sp_ref[bi, pi + 1], hi, 0, 0))

    in_specs = [
        pl.BlockSpec((None, None, g, d),
                     lambda bi, hi, pi, sp_ref: (bi, hi, 0, 0)),
        page_spec(d),
        page_spec(d),
    ]
    inputs = [sp, q4, k_pages, v_pages]
    if quantized:
        in_specs += [page_spec(), page_spec()]
        inputs += [k_scales.astype(jnp.float32),
                   v_scales.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hkv, p),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, g, d),
                               lambda bi, hi, pi, sp_ref: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, d), jnp.float32),
        ],
    )
    out_dtype = q.dtype
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page_size=ps, block_k=block_k,
                          scale=scale, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), out_dtype),
        interpret=_interpret() if interpret is None else interpret,
        name="paged_attention_fwd",
    )(*inputs)
    return out.reshape(b, hq, d)


def paged_attention_reference(q, k_pages, v_pages, page_table, pos,
                              k_scales=None, v_scales=None):
    """Dense jnp reference (and the CPU execution path): gather each
    sequence's pages into a contiguous view and attend with a masked
    softmax.  Numerically the plain-softmax twin of the kernel's online
    accumulation.  With scale tables (quantized int8 pools) each token
    vector dequantizes with its own per-head scale before the gather
    view — the same f32 multiply the kernel applies in VMEM."""
    from ..quant import dequantize_vectors

    b, hq, d = q.shape
    _, hkv, ps, _ = k_pages.shape
    p = page_table.shape[1]
    g = hq // hkv
    scale = 1.0 / (d ** 0.5)
    # gather FIRST, dequantize the gathered [B, P, ...] view: expanding
    # the whole pool to f32 before the gather would materialize 4x the
    # int8 pool bytes per decode step for pages nobody reads (same
    # values either way — dequant is an elementwise multiply)
    kg, vg = k_pages[page_table], v_pages[page_table]
    if k_scales is not None:
        kg = dequantize_vectors(kg, k_scales[page_table])
        vg = dequantize_vectors(vg, v_scales[page_table])
    # [B, P, Hkv, PS, D] -> [B, Hkv, P*PS, D]
    k = jnp.moveaxis(kg, 2, 1).reshape(b, hkv, p * ps, d)
    v = jnp.moveaxis(vg, 2, 1).reshape(b, hkv, p * ps, d)
    q4 = q.reshape(b, hkv, g, d).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bhsd->bhgs", q4, k.astype(jnp.float32))
    ids = jnp.arange(p * ps, dtype=jnp.int32)
    mask = ids[None, :] <= pos.astype(jnp.int32)[:, None]   # [B, S]
    s = jnp.where(mask[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bhsd->bhgd", w, v.astype(jnp.float32))
    return out.reshape(b, hq, d).astype(q.dtype)


def _tuned_block_k(b, hq, d, dtype, pool_shape, n_tables,
                   pool_dtype="float32"):
    """Autotuned intra-page block_k for this paged-decode signature
    (cached per device kind on disk, like the flash/decode tiers).
    Candidates are page_size divisors ≥ 128 lanes-worth of rows — a
    sub-page block only helps when pages are large enough that the
    full-page score block pressures VMEM."""
    from . import autotune

    npool, hkv, ps, _ = pool_shape
    quantized = jnp.dtype(pool_dtype) == jnp.int8
    cands = []
    for c in (ps, 256, 128):
        c = min(c, ps)
        if ps % c == 0 and c % 8 == 0 and c not in cands:
            cands.append(c)
    if len(cands) <= 1:
        return ps
    sig = (f"b{b}h{hq}d{d}{dtype}|pool{npool}x{hkv}x{ps}"
           f"{pool_dtype}|pt{n_tables}")

    def run(cfg):
        kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(kq, (b, hq, d), jnp.dtype(dtype))
        ks = vs = None
        if quantized:
            kp = jax.random.randint(kk, pool_shape, -127, 128,
                                    jnp.int8)
            vp = jax.random.randint(kv, pool_shape, -127, 128,
                                    jnp.int8)
            ks = jnp.ones(pool_shape[:3], jnp.float32)
            vs = jnp.ones(pool_shape[:3], jnp.float32)
        else:
            kp = jax.random.normal(kk, pool_shape, jnp.dtype(dtype))
            vp = jax.random.normal(kv, pool_shape, jnp.dtype(dtype))
        pt = jnp.tile(jnp.arange(n_tables, dtype=jnp.int32)[None, :],
                      (b, 1))
        pos = jnp.full((b,), n_tables * ps - 1, jnp.int32)

        def f(qq):
            return paged_attention(qq, kp, vp, pt, pos, block_k=cfg,
                                   k_scales=ks, v_scales=vs)

        return f, q

    return autotune.pick("paged_attention", sig, cands, run, default=ps)


def paged_attention_dispatch(q, k_pages, v_pages, page_table, pos,
                             k_scales=None, v_scales=None):
    """Dispatch-tier entry (the one the engine's decode program calls):
    the Pallas kernel when available (block_k autotuned per signature),
    the jnp reference otherwise.  Counts `paged.dispatch{tier=...}`.
    Scale tables route the quantized int8-pool tier through the SAME
    kernel (dequant in VMEM) or the same reference."""
    from ...observability import metrics as _metrics

    if paged_attention_available(k_pages.shape, k_pages.dtype):
        _metrics.inc("paged.dispatch", tier="pallas")
        block_k = _tuned_block_k(
            q.shape[0], q.shape[1], q.shape[2], str(q.dtype),
            tuple(k_pages.shape), page_table.shape[1],
            pool_dtype=str(k_pages.dtype))
        return paged_attention(q, k_pages, v_pages, page_table, pos,
                               block_k=block_k, k_scales=k_scales,
                               v_scales=v_scales)
    _metrics.inc("paged.dispatch", tier="fallback")
    return paged_attention_reference(q, k_pages, v_pages, page_table,
                                     pos, k_scales=k_scales,
                                     v_scales=v_scales)
