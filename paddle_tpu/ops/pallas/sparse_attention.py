"""Attention over a SELECTED set of keys (learned sparse attention,
docs/ATTENTION.md "Learned sparse attention") — Pallas kernels for TPU.

A query attends the keys its row of `mask` marks ([B, T, T] int8, 1 =
selected; the causal bound is part of the selection).  The mask is one for
all heads, and a group of `rep` query heads shares one key/value head, so a
kernel instance works on the tile of ALL `rep` heads of a group: the
`[rep * block_q, d]` queries meet a `[block_k, d]` key block in one matrix
product, the mask block is read once a group, and dK/dV sum over the
group's heads inside the contraction.  K and V are never expanded.

    grid (batch, kv_head, q_block, k_block)      forward; fused backward
    grid (batch, kv_head, q_block, k_block)      split pair: dQ
    grid (batch, kv_head, k_block, q_block)      split pair: dK/dV
    grid (batch, q_block, k_block, kv_head)      head-mean probabilities

The backward is ONE kernel wherever its VMEM fits (`_bwd_vmem_bytes`
within `_BWD_VMEM_LIMIT`; 8192 x 128 does, with room): each tile makes
its logits, probabilities and dP once; dQ accumulates over the key blocks
of its query block (ascending), and dK/dV over the query blocks
(ascending) into the key/value head's sequence-long [T, d] float32
scratch — 8 MiB at 8192 x 128 however many query heads share it, where
a head's sequence-long dQ would be that per query head.  Past the budget
the split dQ + dK/dV pair runs, which makes the logits twice.  Both
orders are the split pair's, so the two give the same bits;
`sparse_attn.backward{kind=fused|split}` counts which was traced.

This is a DENSE CAUSAL PASS that masks: a grid step whose key block lies
wholly after its query block is skipped (its index maps repeat the last
block needed, so nothing is fetched), every other step multiplies its whole
tile, selected or not.  `computed_pairs` says how many (query, key) pairs
that is; the selection's own count is the mask's sum.  A kernel that
visits only selected keys would read the same mask.

The softmax statistics live in HBM as lane-dense rows [B, Hkv, rep, T]
(a [T, 1] column pads 128-fold) and cross to the column a tile needs by a
diagonal select inside the kernel (`_rows_to_col`, flash's `_col_to_row`
the other way).

Off the TPU (`available()` false) `reference()` and `reference_probs()`
compute the same values in jax.numpy; the tests run the kernels through the Pallas interpreter.
"""
from __future__ import annotations

import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .flash_attention import (NEG_INF, _ARB, _PLL, _bwd_name, _col_to_row,
                              _fwd_name, _interpret, _kept, _layout_swap)

__all__ = ["sparse_attention", "head_mean_probs", "computed_pairs",
           "available", "reference", "reference_probs", "DEFAULT_BLOCKS"]

# (block_q, block_k): a tile of rep * block_q = 1024 query rows at 8 heads
# a group against 1024 keys — 4 MB of float32 logits.  At the benchmark's
# shape (2 x 8192, 32:4 heads of 128; PERF.md, PR 35) forward + backward
# take 38.8 ms a layer, 48.3 at (128, 512), 42.4 at (256, 512); (256, 1024)
# does not fit the kernels' VMEM
DEFAULT_BLOCKS = (128, 1024)


def available(q) -> bool:
    """The kernels run on the TPU (and under `force_tpu_lowering`), at
    head sizes the MXU takes."""
    return q.ndim == 4 and q.shape[-1] % 64 == 0 and not _interpret()


def _blocks(t, blocks, default=None):
    """(block_q, block_k) for t positions: the call's, else the default,
    cut to t; both divide t and block_q divides block_k."""
    bq, bk = blocks or default or DEFAULT_BLOCKS
    bq, bk = min(bq, t), min(bk, t)
    if t % bq or t % bk or bk % bq or bq % 8:
        raise ValueError(f"sparse attention: {t} positions do not divide "
                         f"into blocks {(bq, bk)}")
    return bq, bk


def _last_k(qi, bq, bk):
    """The last key block a query block can see."""
    return (qi * bq + bq - 1) // bk


def _first_q(ki, bq, bk):
    """The first query block that sees a key block (bk % bq == 0)."""
    return ki * bk // bq


def computed_pairs(batch, t, blocks=None):
    """(query, key) pairs the kernels multiply in one pass over a
    [batch, t] call: the whole tiles of every grid step not skipped."""
    bq, bk = _blocks(t, blocks)
    return batch * sum((_last_k(qi, bq, bk) + 1) * bk * bq
                       for qi in range(t // bq))


def _params(sem, vmem_limit_bytes=None):
    if _interpret():
        return None
    if vmem_limit_bytes is None:
        return pltpu.CompilerParams(dimension_semantics=sem)
    return pltpu.CompilerParams(dimension_semantics=sem,
                                vmem_limit_bytes=int(vmem_limit_bytes))


def _rows_to_col(rows):
    """[r, n] lane-dense rows -> the [r * n, 1] column, row by row,
    exactly: 128 at a time, select the diagonal of the sublane-broadcast
    piece and sum over lanes (one value and zeros)."""
    r, n = rows.shape
    cols = []
    for i in range(r):
        for lo in range(0, n, 128):
            c = min(128, n - lo)
            eye = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) ==
                   jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
            cols.append(jnp.sum(
                jnp.where(eye, rows[i:i + 1, lo:lo + c], 0.0), axis=1,
                keepdims=True))
    return cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=0)


def _logits(q, k, sel, scale, rep):
    """[rep * bq, bk] float32 scaled logits of a group's tile, NEG_INF
    where the (shared) selection `sel` [bq, bk] is false."""
    n, bk = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(sel[None], s.reshape(rep, n // rep, bk), NEG_INF)
    return s.reshape(n, bk)


def _selected(m_ref):
    return m_ref[...].astype(jnp.float32) > 0.0


# ------------------------------ forward ------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, m_ref, o_ref, lse_ref, m_sc, l_sc,
                acc_sc, *, scale, bq, bk, rep):
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = _last_k(qi, bq, bk)
    d = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    @pl.when(ki <= last)
    def _():
        v = v_ref[...]
        s = _logits(q_ref[...].reshape(rep * bq, d), k_ref[...],
                    _selected(m_ref), scale, rep)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no selected key so far carries p = 1 garbage; its
        # first real key arrives with alpha = exp(-1e30 - m) = 0
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_sc[...] = acc_sc[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_sc[...] = m_new

    @pl.when(ki == last)
    def _():
        l = jnp.maximum(l_sc[...], 1e-30)
        o_ref[...] = (acc_sc[...] / l).reshape(rep, bq, d).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)
        for r in range(rep):
            lse_ref[r:r + 1, :] = _col_to_row(lse[r * bq:(r + 1) * bq])


def _specs(rep, bq, bk, d, order):
    """BlockSpecs over qt [B, Hkv, rep, T, d], kt/vt [B, Hkv, T, d], mask
    [B, T, T], stats [B, Hkv, rep, T] for a grid (b, h, qi, ki)
    (`order` "qk") or (b, h, ki, qi) ("kq").  A skipped step repeats the
    block of the nearest step that runs."""
    if order == "qk":
        qk = lambda a, b: (a, jnp.minimum(b, _last_k(a, bq, bk)))
    else:
        qk = lambda a, b: (jnp.maximum(b, _first_q(a, bq, bk)), a)

    def at(f):
        return lambda bi, hi, a, b: f(bi, hi, *qk(a, b))

    q = pl.BlockSpec((None, None, rep, bq, d),
                     at(lambda bi, hi, qi, ki: (bi, hi, 0, qi, 0)))
    kv = pl.BlockSpec((None, None, bk, d),
                      at(lambda bi, hi, qi, ki: (bi, hi, ki, 0)))
    m = pl.BlockSpec((None, bq, bk),
                     at(lambda bi, hi, qi, ki: (bi, qi, ki)))
    st = pl.BlockSpec((None, None, rep, bq),
                      at(lambda bi, hi, qi, ki: (bi, hi, 0, qi)))
    return q, kv, m, st


def _fwd(qt, kt, vt, mask, blocks, diff=False):
    b, hkv, rep, t, d = qt.shape
    bq, bk = blocks
    q, kv, m, st = _specs(rep, bq, bk, d, "qk")
    n = rep * bq
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                          bk=bk, rep=rep),
        grid=(b, hkv, t // bq, t // bk),
        in_specs=[q, kv, kv, m],
        out_specs=[q, st],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct((b, hkv, rep, t), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, 1), jnp.float32),
                        pltpu.VMEM((n, 1), jnp.float32),
                        pltpu.VMEM((n, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _PLL, _ARB)),
        name=_fwd_name("sparse_attn_fwd", diff),
    )(qt, kt, vt, mask)


# ------------------------------ backward -----------------------------------

def _p_ds(q, k, v, do, sel, lse, delta, scale, rep):
    """A tile's probabilities and logit gradients, both [rep * bq, bk]."""
    s = _logits(q, k, sel, scale, rep)
    p = jnp.exp(s - lse)           # exp(-1e30 - lse) = 0 where not selected
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    return p, (p * (dp - delta) * scale).astype(q.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, dl_ref, dq_ref,
               acc_sc, lse_sc, dl_sc, *, scale, bq, bk, rep):
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = _last_k(qi, bq, bk)
    d = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _():
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        lse_sc[...] = _rows_to_col(lse_ref[...])
        dl_sc[...] = _rows_to_col(dl_ref[...])

    @pl.when(ki <= last)
    def _():
        k = k_ref[...]
        _, ds = _p_ds(q_ref[...].reshape(rep * bq, d), k, v_ref[...],
                      do_ref[...].reshape(rep * bq, d), _selected(m_ref),
                      lse_sc[...], dl_sc[...], scale, rep)
        acc_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last)
    def _():
        dq_ref[...] = acc_sc[...].reshape(rep, bq, d).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, dl_ref, dk_ref,
                dv_ref, dk_sc, dv_sc, *, scale, bq, bk, rep):
    ki, qi = pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]

    @pl.when(qi == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(qi >= _first_q(ki, bq, bk))
    def _():
        q = q_ref[...].reshape(rep * bq, d)
        do = do_ref[...].reshape(rep * bq, d)
        p, ds = _p_ds(q, k_ref[...], v_ref[...], do, _selected(m_ref),
                      _rows_to_col(lse_ref[...]), _rows_to_col(dl_ref[...]),
                      scale, rep)
        # the contraction runs over the group's rep * bq rows: the heads
        # that share this key block sum here
        dv_sc[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_sc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(3) - 1)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, m_ref, do_ref, lse_ref, dl_ref, dq_ref,
                dk_ref, dv_ref, dq_sc, lse_sc, dl_sc, dk_sc, dv_sc, *, scale,
                bq, bk, rep):
    """dQ, dK and dV on one visit of each tile: dQ accumulates over the
    key blocks of a query block (as `_dq_kernel`), dK and dV over the
    query blocks into the KV head's sequence-long [T, d] float32
    scratch at the key block's rows (as `_dkv_kernel`, same order).
    It takes the split pair's dK/dV name, `transpose(jvp(sparse_attn_dkdv))`,
    and also makes dQ: a device trace counts a layer's backward by that
    name (`benchmark/readers/cost_sparse_attn.py`)."""
    qi, ki = pl.program_id(2), pl.program_id(3)
    last = _last_k(qi, bq, bk)
    d = q_ref.shape[-1]
    rows = pl.ds(pl.multiple_of(ki * bk, bk), bk)

    @pl.when((qi == 0) & (ki == 0))
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)
        dv_sc[...] = jnp.zeros(dv_sc.shape, jnp.float32)

    @pl.when(ki == 0)
    def _():
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)
        lse_sc[...] = _rows_to_col(lse_ref[...])
        dl_sc[...] = _rows_to_col(dl_ref[...])

    @pl.when(ki <= last)
    def _():
        q = q_ref[...].reshape(rep * bq, d)
        do = do_ref[...].reshape(rep * bq, d)
        k = k_ref[...]
        p, ds = _p_ds(q, k, v_ref[...], do, _selected(m_ref), lse_sc[...],
                      dl_sc[...], scale, rep)
        dq_sc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dv_sc[rows, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_sc[rows, :] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last)
    def _():
        dq_ref[...] = dq_sc[...].reshape(rep, bq, d).astype(dq_ref.dtype)

    # the last query block sees every key block: there each block's dK
    # and dV are final, and its output block is the one the grid is on
    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_sc[rows, :].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[rows, :].astype(dv_ref.dtype)


# scoped VMEM the fused backward may ask for, per kernel (as flash's
# _flat_compiler_params: a program-wide raise starves XLA's own ops);
# past it the split pair runs.  v5e has 128 MiB
_BWD_VMEM_LIMIT = 48 * 1024 * 1024


def _bwd_vmem_bytes(t, rep, d, bq, bk, esz) -> int:
    """Scoped VMEM of the fused backward: the KV head's resident float32
    dK and dV, beside the double-buffered q, do, dq, k, v, dk, dv, mask
    and stat blocks, the dQ accumulator, the lse and delta columns (each
    value pads to 128 lanes) and the logits-sized temporaries.  Against
    the v5e compiler at blocks (128, 1024), 32:4 heads of 128 bf16:
    23.3 MiB here at 2 x 8192, where it compiles at 17.4 and not at
    17.2; 47.3 here at 1 x 32768, where it compiles at 42.3."""
    n, dp = rep * bq, _fa._up(d, 128)
    return (2 * t * dp * 4
            + 2 * (3 * n + 4 * bk) * dp * esz
            + 2 * bq * bk
            + 2 * 2 * _fa._stat_rows_bytes(1, rep, bq)
            + n * dp * 4 + 2 * n * 128 * 4
            + _fa._fused_tile_bytes(n, bk, esz))


def _bwd(qt, kt, vt, mask, ot, lse, dot, blocks):
    b, hkv, rep, t, d = qt.shape
    bq, bk = blocks
    scale = 1.0 / math.sqrt(d)
    n = rep * bq
    with jax.named_scope(_fa.LAYOUT_SCOPE):
        delta = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                        axis=-1)
    q, kv, m, st = _specs(rep, bq, bk, d, "qk")
    vmem = _bwd_vmem_bytes(t, rep, d, bq, bk, qt.dtype.itemsize)
    fused = vmem <= _BWD_VMEM_LIMIT
    _fa._metrics.inc("sparse_attn.backward",
                     kind="fused" if fused else "split")
    if fused:
        nq = t // bq
        # a key block's dK/dV block is written on the last query block's
        # walk and held at block 0 before it, which nothing writes
        dkv = pl.BlockSpec((None, None, bk, d), lambda bi, hi, qi, ki: (
            bi, hi, jnp.where(qi == nq - 1, ki, 0), 0))
        return pl.pallas_call(
            functools.partial(_bwd_kernel, scale=scale, bq=bq, bk=bk,
                              rep=rep),
            grid=(b, hkv, nq, t // bk),
            in_specs=[q, kv, kv, m, q, st, st],
            out_specs=[q, dkv, dkv],
            out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                       jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                       jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
            scratch_shapes=[pltpu.VMEM((n, d), jnp.float32),
                            pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((n, 1), jnp.float32),
                            pltpu.VMEM((t, d), jnp.float32),
                            pltpu.VMEM((t, d), jnp.float32)],
            interpret=_interpret(),
            compiler_params=_params((_PLL, _PLL, _ARB, _ARB),
                                    max(vmem, _fa._T_VMEM_LIMIT)),
            name=_bwd_name("sparse_attn_dkdv"),
        )(qt, kt, vt, mask, dot, lse, delta)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, bq=bq, bk=bk, rep=rep),
        grid=(b, hkv, t // bq, t // bk),
        in_specs=[q, kv, kv, m, q, st, st],
        out_specs=q,
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((n, d), jnp.float32),
                        pltpu.VMEM((n, 1), jnp.float32),
                        pltpu.VMEM((n, 1), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _PLL, _ARB)),
        name=_bwd_name("sparse_attn_dq"),
    )(qt, kt, vt, mask, dot, lse, delta)
    q, kv, m, st = _specs(rep, bq, bk, d, "kq")
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, bq=bq, bk=bk, rep=rep),
        grid=(b, hkv, t // bk, t // bq),
        in_specs=[q, kv, kv, m, q, st, st],
        out_specs=[kv, kv],
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _PLL, _ARB)),
        name=_bwd_name("sparse_attn_dkdv"),
    )(qt, kt, vt, mask, dot, lse, delta)
    return dq, dk, dv


# ------------------------- head-mean probabilities --------------------------

def _probs_kernel(q_ref, k_ref, m_ref, lse_ref, p_ref, *, scale, bq, bk,
                  rep, heads):
    qi, ki, hi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    d = q_ref.shape[-1]

    @pl.when(hi == 0)
    def _():
        p_ref[...] = jnp.zeros(p_ref.shape, jnp.float32)

    @pl.when(ki <= _last_k(qi, bq, bk))
    def _():
        s = _logits(q_ref[...].reshape(rep * bq, d), k_ref[...],
                    _selected(m_ref), scale, rep)
        p = jnp.exp(s - _rows_to_col(lse_ref[...]))
        p_ref[...] += jnp.sum(p.reshape(rep, bq, bk), axis=0) * (1.0 / heads)


def head_mean_probs(qt, kt, lse, mask, blocks=None):
    """P[b, t, s] = mean over ALL heads of softmax over the selected keys,
    0 where not selected: [B, T, T] float32.  qt [B, Hkv, rep, T, d], kt
    [B, Hkv, T, d], lse [B, Hkv, rep, T] as `sparse_attention` returns
    it.  No gradient (the indexer's target is detached)."""
    qt, kt, lse = (jax.lax.stop_gradient(x) for x in (qt, kt, lse))
    b, hkv, rep, t, d = qt.shape
    bq, bk = _blocks(t, blocks)
    last = lambda qi, ki: jnp.minimum(ki, _last_k(qi, bq, bk))
    return pl.pallas_call(
        functools.partial(_probs_kernel, scale=1.0 / math.sqrt(d), bq=bq,
                          bk=bk, rep=rep, heads=hkv * rep),
        grid=(b, t // bq, t // bk, hkv),
        in_specs=[
            pl.BlockSpec((None, None, rep, bq, d),
                         lambda bi, qi, ki, hi: (bi, hi, 0, qi, 0)),
            pl.BlockSpec((None, None, bk, d),
                         lambda bi, qi, ki, hi: (bi, hi, last(qi, ki), 0)),
            pl.BlockSpec((None, bq, bk),
                         lambda bi, qi, ki, hi: (bi, qi, last(qi, ki))),
            pl.BlockSpec((None, None, rep, bq),
                         lambda bi, qi, ki, hi: (bi, hi, 0, qi)),
        ],
        out_specs=pl.BlockSpec((None, bq, bk),
                               lambda bi, qi, ki, hi: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _PLL, _ARB)),
        name="sparse_attn_probs",
    )(qt, kt, mask, lse)


# ------------------------------ the call ------------------------------------

def _grouped(q, k, v):
    """[B, T, H, d] -> head-major, the query heads by group."""
    qt, kt, vt = _layout_swap(q, k, v)
    b, h, t, d = qt.shape
    return qt.reshape(b, kt.shape[1], h // kt.shape[1], t, d), kt, vt


def _ungrouped(xt):
    b, hkv, rep, t, d = xt.shape
    return _layout_swap(xt.reshape(b, hkv * rep, t, d))[0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def sparse_attention(q, k, v, mask, blocks=None):
    """q [B, T, H, d], k / v [B, T, Hkv, d], mask [B, T, T] int8 (causal
    bound included; every row selects at least one key).  Returns (out
    [B, T, H, d], (qt, kt, lse): the head-major queries and keys and the
    log-sum-exp rows `head_mean_probs` takes)."""
    return _sparse_fwd(q, k, v, mask, blocks)[0]


def _sparse_fwd(q, k, v, mask, blocks, diff=False):
    qt, kt, vt = _grouped(q, k, v)
    blocks = _blocks(q.shape[1], blocks)
    out_t, lse = _fwd(qt, kt, vt, mask, blocks, diff)
    if diff:
        out_t, lse = _kept(out_t, lse)
    return ((_ungrouped(out_t), (qt, kt, lse)),
            (qt, kt, vt, mask, out_t, lse))


def _sparse_bwd(blocks, res, cts):
    qt, kt, vt, mask, out_t, lse = res
    g = cts[0]
    b, t, h, d = g.shape
    dot = _layout_swap(g)[0].reshape(qt.shape)
    dq, dk, dv = _bwd(qt, kt, vt, mask, out_t, lse, dot,
                      _blocks(t, blocks))
    return (_ungrouped(dq), *_layout_swap(dk, dv),
            np.zeros(mask.shape, jax.dtypes.float0))


sparse_attention.defvjp(
    lambda q, k, v, mask, blocks: _sparse_fwd(q, k, v, mask, blocks, True),
    _sparse_bwd)


def _reference_softmax(q, k, mask):
    rep = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, axis=2),
                   preferred_element_type=jnp.float32)
    s = jnp.where((mask > 0)[:, None], s / math.sqrt(q.shape[-1]), NEG_INF)
    return jax.nn.softmax(s, axis=-1)


def reference(q, k, v, mask):
    """`sparse_attention`'s values in jax.numpy (any backend)."""
    p = _reference_softmax(q, k, mask)
    vv = jnp.repeat(v, q.shape[2] // v.shape[2], axis=2)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), vv,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def reference_probs(q, k, mask):
    """`head_mean_probs`' values in jax.numpy, detached."""
    p = jnp.mean(_reference_softmax(q, k, mask), axis=1)
    return jax.lax.stop_gradient(jnp.where(mask > 0, p, 0.0))
