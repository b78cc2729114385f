"""Varlen (packed / unpadded) flash attention — segment-masked kernels.

Role parity: `nn.functional.flash_attn_unpadded`
(python/paddle/nn/functional/flash_attention.py:302, backed by
third_party/flashattn's varlen CUDA kernels with cu_seqlens indexing).

TPU-first design: instead of the CUDA kernels' ragged cu_seqlens
indexing (data-dependent control flow XLA can't tile), the packed
[total, H, D] tensors run through the SAME blocked online-softmax /
backward loops as dense flash (`flash_attention._online_softmax`,
`_dq_loop`, `_dkv_loop`) with per-position SEGMENT IDS threaded into the
block masks: positions attend only within their segment, so the ragged
batch runs block-diagonal with static shapes and the T x T mask never
materializes. Segment ids ride as f32 [T, 1] columns (exact integer
equality far beyond any real batch size; f32 keeps the custom-VJP
cotangent plumbing trivial).

Layout: kernels consume head-major [H, T, D] (one transpose of the
packed tensors, same layout cost as the dense path); padded tail
positions (T padded to a multiple of 8) carry sentinel segment ids
(-1 on q, -2 on k) so they match nothing.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (
    _dkv_loop, _dq_loop, _interpret, _online_softmax, _pick_block,
)

__all__ = ["varlen_attention", "segment_ids_from_cu_seqlens"]


def segment_ids_from_cu_seqlens(cu_seqlens, total):
    """cu_seqlens [n+1] int (cu[0]=0, cu[n]=total) -> [total] segment
    ids (position t in [cu[i], cu[i+1]) gets id i)."""
    cu = jnp.asarray(cu_seqlens)
    t = jnp.arange(total, dtype=cu.dtype)
    return (jnp.searchsorted(cu, t, side="right") - 1).astype(jnp.int32)


def _dimsem():
    if _interpret():
        return None
    from .flash_attention import _ARB, _PLL, _TPUCompilerParams

    return _TPUCompilerParams(dimension_semantics=(_PLL, _ARB))


def _vl_fwd_kernel(q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, *,
                   scale, block_k, causal, seq_q, seq_k):
    block_q = q_ref.shape[0]
    out, lse = _online_softmax(
        q_ref[:],
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(1), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        seg_q=sq_ref[:],
        load_seg_k=lambda j: sk_ref[pl.ds(j * block_k, block_k), :])
    o_ref[:] = out.astype(o_ref.dtype)
    lse_ref[:] = lse.astype(jnp.float32)


def _vl_dq_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, sq_ref,
                  sk_ref, dq_ref, *, scale, block_k, causal, seq_q, seq_k):
    block_q = q_ref.shape[0]
    delta = jnp.sum(do_ref[:].astype(jnp.float32) *
                    o_ref[:].astype(jnp.float32), axis=1, keepdims=True)
    dq = _dq_loop(
        q_ref[:], do_ref[:], lse_ref[:], delta,
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(1), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        seg_q=sq_ref[:],
        load_seg_k=lambda j: sk_ref[pl.ds(j * block_k, block_k), :])
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _vl_dkv_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, sq_ref,
                   sk_ref, dk_ref, dv_ref, *, scale, block_q, causal,
                   seq_q, seq_k):
    block_k = k_ref.shape[0]
    dk, dv = _dkv_loop(
        k_ref[:], v_ref[:],
        lambda i: (q_ref[pl.ds(i * block_q, block_q), :],
                   do_ref[pl.ds(i * block_q, block_q), :],
                   o_ref[pl.ds(i * block_q, block_q), :],
                   lse_ref[pl.ds(i * block_q, block_q), :]),
        jk=pl.program_id(1), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        seg_k=sk_ref[:],
        load_seg_q=lambda i: sq_ref[pl.ds(i * block_q, block_q), :])
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _vl_fwd(qh, kh, vh, seg_q, seg_k, causal, block_q, block_k,
            seq_q_real, seq_k_real):
    """qh/kh/vh: [H, Tq|Tk, D] (padded); seg_*: [T*, 1] f32."""
    h, tq, d = qh.shape
    tk = kh.shape[1]
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(tk, block_k)
    out, lse = pl.pallas_call(
        functools.partial(_vl_fwd_kernel, scale=scale, block_k=block_k,
                          causal=causal, seq_q=seq_q_real,
                          seq_k=seq_k_real),
        grid=(h, pl.cdiv(tq, block_q)),
        in_specs=[
            pl.BlockSpec((None, block_q, d), lambda hi, qi: (hi, qi, 0)),
            pl.BlockSpec((None, tk, d), lambda hi, qi: (hi, 0, 0)),
            pl.BlockSpec((None, tk, d), lambda hi, qi: (hi, 0, 0)),
            pl.BlockSpec((block_q, 1), lambda hi, qi: (qi, 0)),
            pl.BlockSpec((tk, 1), lambda hi, qi: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, d), lambda hi, qi: (hi, qi, 0)),
            pl.BlockSpec((None, block_q, 1), lambda hi, qi: (hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((h, tq, d), qh.dtype),
            jax.ShapeDtypeStruct((h, tq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_dimsem(),
        name="varlen_attention_fwd",
    )(qh, kh, vh, seg_q, seg_k)
    return out, lse


def _vl_bwd(qh, kh, vh, ot, lse, dot, seg_q, seg_k, causal, block_q,
            block_k, seq_q_real, seq_k_real):
    h, tq, d = qh.shape
    tk = kh.shape[1]
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(tq, block_q)
    block_k = _pick_block(tk, block_k)

    q_spec = pl.BlockSpec((None, block_q, d), lambda hi, i: (hi, i, 0))
    full_q = pl.BlockSpec((None, tq, d), lambda hi, i: (hi, 0, 0))
    full_k = pl.BlockSpec((None, tk, d), lambda hi, i: (hi, 0, 0))
    lse_spec = pl.BlockSpec((None, block_q, 1), lambda hi, i: (hi, i, 0))
    full_lse = pl.BlockSpec((None, tq, 1), lambda hi, i: (hi, 0, 0))
    segq_blk = pl.BlockSpec((block_q, 1), lambda hi, i: (i, 0))
    segq_full = pl.BlockSpec((tq, 1), lambda hi, i: (0, 0))
    segk_full = pl.BlockSpec((tk, 1), lambda hi, i: (0, 0))
    segk_blk = pl.BlockSpec((block_k, 1), lambda hi, j: (j, 0))
    kv_spec = pl.BlockSpec((None, block_k, d), lambda hi, j: (hi, j, 0))

    dq = pl.pallas_call(
        functools.partial(_vl_dq_kernel, scale=scale, block_k=block_k,
                          causal=causal, seq_q=seq_q_real,
                          seq_k=seq_k_real),
        grid=(h, pl.cdiv(tq, block_q)),
        in_specs=[q_spec, full_k, full_k, q_spec, lse_spec, q_spec,
                  segq_blk, segk_full],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((h, tq, d), qh.dtype),
        interpret=_interpret(),
        compiler_params=_dimsem(),
        name="varlen_attention_dq",
    )(qh, kh, vh, ot, lse, dot, seg_q, seg_k)

    dk, dv = pl.pallas_call(
        functools.partial(_vl_dkv_kernel, scale=scale, block_q=block_q,
                          causal=causal, seq_q=seq_q_real,
                          seq_k=seq_k_real),
        grid=(h, pl.cdiv(tk, block_k)),
        in_specs=[full_q, kv_spec, kv_spec, full_q, full_lse, full_q,
                  segq_full, segk_blk],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((h, tk, d), kh.dtype),
                   jax.ShapeDtypeStruct((h, tk, d), vh.dtype)],
        interpret=_interpret(),
        compiler_params=_dimsem(),
        name="varlen_attention_dkdv",
    )(qh, kh, vh, ot, lse, dot, seg_q, seg_k)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _vl_core(qh, kh, vh, seg_q, seg_k, causal, block_q, block_k,
             seq_q_real, seq_k_real):
    out, _ = _vl_fwd(qh, kh, vh, seg_q, seg_k, causal, block_q, block_k,
                     seq_q_real, seq_k_real)
    return out


def _vl_core_fwd(qh, kh, vh, seg_q, seg_k, causal, block_q, block_k,
                 seq_q_real, seq_k_real):
    out, lse = _vl_fwd(qh, kh, vh, seg_q, seg_k, causal, block_q,
                       block_k, seq_q_real, seq_k_real)
    return out, (qh, kh, vh, out, lse, seg_q, seg_k)


def _vl_core_bwd(causal, block_q, block_k, seq_q_real, seq_k_real, res,
                 g):
    qh, kh, vh, out, lse, seg_q, seg_k = res
    dq, dk, dv = _vl_bwd(qh, kh, vh, out, lse, g, seg_q, seg_k, causal,
                         block_q, block_k, seq_q_real, seq_k_real)
    return dq, dk, dv, jnp.zeros_like(seg_q), jnp.zeros_like(seg_k)


_vl_core.defvjp(_vl_core_fwd, _vl_core_bwd)


def varlen_attention(q, k, v, cu_seqlens_q, cu_seqlens_k, scale=None,
                     causal=False, block_q=256, block_k=512):
    """Packed ragged-batch attention on raw jax values.

    q: [Tq, H, D]; k/v: [Tk, H, D]; cu_seqlens_*: [n+1] cumulative
    lengths. Returns [Tq, H, D]. Segment-masked Pallas kernels; with
    `causal`, cu_seqlens_q and cu_seqlens_k must describe the same
    packing (per-sequence causal needs aligned positions)."""
    tq, h, d = q.shape
    tk = k.shape[0]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    seg_q = segment_ids_from_cu_seqlens(cu_seqlens_q, tq)
    seg_k = segment_ids_from_cu_seqlens(cu_seqlens_k, tk)
    # fold an explicit scale into q so the kernels' 1/sqrt(d) nets out
    q = q * jnp.asarray(scale * math.sqrt(d), q.dtype)

    pad_q = (-tq) % 8
    pad_k = (-tk) % 8
    qh = jnp.swapaxes(jnp.pad(q, ((0, pad_q), (0, 0), (0, 0))), 0, 1)
    kh = jnp.swapaxes(jnp.pad(k, ((0, pad_k), (0, 0), (0, 0))), 0, 1)
    vh = jnp.swapaxes(jnp.pad(v, ((0, pad_k), (0, 0), (0, 0))), 0, 1)
    # sentinel segment ids on the padded tail: -1 (q) never equals -2 (k)
    sq = jnp.pad(seg_q.astype(jnp.float32), (0, pad_q),
                 constant_values=-1.0)[:, None]
    sk = jnp.pad(seg_k.astype(jnp.float32), (0, pad_k),
                 constant_values=-2.0)[:, None]
    out = _vl_core(qh, kh, vh, sq, sk, bool(causal), block_q, block_k,
                   tq, tk)
    return jnp.swapaxes(out, 0, 1)[:tq]
