"""The index scores of learned sparse attention (docs/ATTENTION.md) —
Pallas kernels for TPU.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])      s <= t, float32

`J` cheap heads score ONE shared key head, so an instance takes the tile of
all J heads of a query block — `[J * block_q, D]` against a `[block_k, D]`
key block in one matrix product — applies relu and the head weights and
sums the heads away: the per-head scores `[J, T, T]` exist in VMEM only.
The backward replays a tile's per-head scores the same way.

    grid (batch, q_block, k_block)    forward; dqI and dw
    grid (batch, k_block, q_block)    dkI

Tiles wholly after the diagonal are skipped (the forward writes zeros
there; nothing reads them: the selection is causal).

`select_topk` is the selection's search (`nn/functional/sparse_index.py`
has the equations) on rows held in VMEM: a block of 32 rows of scores is
read ONCE and both radix searches — 32 passes over the value's bits, then
the key's position among the ties at the threshold — run on it there,
where the jax.numpy form reads the [B, T, T] array 46 times from HBM.

`indexer_loss` is the indexer's KL on the same blocks of rows: a row's
log-sum-exp over its selected scores, its KL and — where the call is
differentiated — the gradient `softmax_selected(I) * sum(P) - P` are ONE
visit of the row in VMEM (XLA's row reductions over the masked [B, T, T]
arrays took 13 ms a pass on the chip).  The gradient is formed in the
forward pass because it is the one array the backward pass wants: kept
across a block's recomputation, it spares the replay the index scores, `P`
and this kernel.

Off the TPU `nn/functional/sparse_index.py` computes the same values in
jax.numpy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import (_ARB, _PLL, _bwd_name, _col_to_row, _fwd_name,
                              _interpret)
from .sparse_attention import (_blocks as _attn_blocks, _first_q, _last_k,
                               _params, _rows_to_col)

__all__ = ["index_scores", "select_topk", "indexer_loss", "available",
           "on_tpu",
           "DEFAULT_BLOCKS", "SELECT_ROWS"]

SELECT_ROWS = 32       # rows of scores a selection instance holds
_INT_MIN = -2 ** 31

# (block_q, block_k): 16 heads x 128 rows against 512 keys — 4 MB of
# float32 per-head scores
DEFAULT_BLOCKS = (128, 512)


def on_tpu() -> bool:
    """The kernels run compiled (on the TPU, or under
    `force_tpu_lowering`); elsewhere the jax.numpy forms do the work."""
    return not _interpret()


def available(q_idx) -> bool:
    return q_idx.ndim == 4 and q_idx.shape[-1] % 8 == 0 and on_tpu()


def _head_scores(q_ref, k_ref):
    """[J * bq, bk] float32: every head's q . k of the tile."""
    j, bq, d = q_ref.shape
    return jax.lax.dot_general(q_ref[...].reshape(j * bq, d), k_ref[...],
                               (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _fwd_kernel(q_ref, k_ref, w_ref, o_ref, w_sc, *, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    j = q_ref.shape[0]

    @pl.when(ki == 0)
    def _():
        w_sc[...] = _rows_to_col(w_ref[...].astype(jnp.float32))

    @pl.when(ki <= _last_k(qi, bq, bk))
    def _():
        r = jnp.maximum(_head_scores(q_ref, k_ref), 0.0) * w_sc[...]
        o_ref[...] = jnp.sum(r.reshape(j, bq, bk), axis=0)

    @pl.when(ki > _last_k(qi, bq, bk))
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _specs(j, d, bq, bk, order):
    """BlockSpecs over qt [B, J, T, D], k [B, T, D], w rows [B, J, T] and a
    [B, T, T] array for a grid (b, qi, ki) ("qk") or (b, ki, qi) ("kq"); a
    skipped step repeats the block of the nearest step that runs."""
    if order == "qk":
        qk = lambda a, b: (a, jnp.minimum(b, _last_k(a, bq, bk)))
    else:
        qk = lambda a, b: (jnp.maximum(b, _first_q(a, bq, bk)), a)

    def at(f):
        return lambda bi, a, b: f(bi, *qk(a, b))

    q = pl.BlockSpec((None, j, bq, d), at(lambda bi, qi, ki: (bi, 0, qi, 0)))
    k = pl.BlockSpec((None, bk, d), at(lambda bi, qi, ki: (bi, ki, 0)))
    w = pl.BlockSpec((None, j, bq), at(lambda bi, qi, ki: (bi, 0, qi)))
    tt = pl.BlockSpec((None, bq, bk), at(lambda bi, qi, ki: (bi, qi, ki)))
    return q, k, w, tt


def _fwd(qt, k, wt, blocks, diff):
    b, j, t, d = qt.shape
    bq, bk = blocks
    q, ks, w, _ = _specs(j, d, bq, bk, "qk")
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bq=bq, bk=bk),
        grid=(b, t // bq, t // bk),
        in_specs=[q, ks, w],
        # every tile is written (zeros past the diagonal): its own block
        out_specs=pl.BlockSpec((None, bq, bk), lambda bi, qi, ki: (bi, qi, ki)),
        out_shape=jax.ShapeDtypeStruct((b, t, t), jnp.float32),
        scratch_shapes=[pltpu.VMEM((j * bq, 1), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _ARB)),
        name=_fwd_name("sparse_index_fwd", diff),
    )(qt, k, wt)


def _tile_grads(q_ref, k_ref, di_ref, w_col):
    """(g [J * bq, bk] in the operands' dtype: the gradient of every head's
    q . k; relu(z) * dI [J * bq, bk] float32: the head weights' integrand)."""
    j, bq, _ = q_ref.shape
    z = _head_scores(q_ref, k_ref)
    bk = z.shape[1]
    di = jnp.broadcast_to(di_ref[...][None], (j, bq, bk)).reshape(j * bq, bk)
    g = jnp.where(z > 0.0, di * w_col, 0.0).astype(q_ref.dtype)
    return g, jnp.maximum(z, 0.0) * di


def _dq_kernel(q_ref, k_ref, w_ref, di_ref, dq_ref, dw_ref, w_sc, dq_sc,
               dw_sc, *, bq, bk):
    qi, ki = pl.program_id(1), pl.program_id(2)
    j, _, d = q_ref.shape
    last = _last_k(qi, bq, bk)

    @pl.when(ki == 0)
    def _():
        w_sc[...] = _rows_to_col(w_ref[...].astype(jnp.float32))
        dq_sc[...] = jnp.zeros(dq_sc.shape, jnp.float32)
        dw_sc[...] = jnp.zeros(dw_sc.shape, jnp.float32)

    @pl.when(ki <= last)
    def _():
        g, rdi = _tile_grads(q_ref, k_ref, di_ref, w_sc[...])
        dq_sc[...] += jax.lax.dot_general(
            g, k_ref[...], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dw_sc[...] += jnp.sum(rdi, axis=1, keepdims=True)

    @pl.when(ki == last)
    def _():
        dq_ref[...] = dq_sc[...].reshape(j, bq, d).astype(dq_ref.dtype)
        dw = dw_sc[...]
        for r in range(j):
            dw_ref[r:r + 1, :] = _col_to_row(dw[r * bq:(r + 1) * bq])


def _dk_kernel(q_ref, k_ref, w_ref, di_ref, dk_ref, dk_sc, *, bq, bk):
    ki, qi = pl.program_id(1), pl.program_id(2)
    j, _, d = q_ref.shape

    @pl.when(qi == 0)
    def _():
        dk_sc[...] = jnp.zeros(dk_sc.shape, jnp.float32)

    @pl.when(qi >= _first_q(ki, bq, bk))
    def _():
        g, _ = _tile_grads(q_ref, k_ref, di_ref,
                           _rows_to_col(w_ref[...].astype(jnp.float32)))
        dk_sc[...] += jax.lax.dot_general(
            g, q_ref[...].reshape(j * bq, d), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)


def _bwd(qt, k, wt, di, blocks):
    b, j, t, d = qt.shape
    bq, bk = blocks
    q, ks, w, tt = _specs(j, d, bq, bk, "qk")
    dq, dw = pl.pallas_call(
        functools.partial(_dq_kernel, bq=bq, bk=bk),
        grid=(b, t // bq, t // bk),
        in_specs=[q, ks, w, tt],
        out_specs=[q, w],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct(wt.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((j * bq, 1), jnp.float32),
                        pltpu.VMEM((j * bq, d), jnp.float32),
                        pltpu.VMEM((j * bq, 1), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _ARB)),
        name=_bwd_name("sparse_index_dq"),
    )(qt, k, wt, di)
    q, ks, w, tt = _specs(j, d, bq, bk, "kq")
    dk = pl.pallas_call(
        functools.partial(_dk_kernel, bq=bq, bk=bk),
        grid=(b, t // bk, t // bq),
        in_specs=[q, ks, w, tt],
        out_specs=ks,
        out_shape=jax.ShapeDtypeStruct(k.shape, k.dtype),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _PLL, _ARB)),
        name=_bwd_name("sparse_index_dk"),
    )(qt, k, wt, di)
    return dq, dk, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def index_scores(q_idx, k_idx, w, blocks=None):
    """q_idx [B, T, J, D], k_idx [B, T, D], w [B, T, J] -> I [B, T, T]
    float32, zeros where s > t's tile."""
    return _index_fwd(q_idx, k_idx, w, blocks, False)[0]


def _index_fwd(q_idx, k_idx, w, blocks, diff=True):
    qt, wt = jnp.swapaxes(q_idx, 1, 2), jnp.swapaxes(w, 1, 2)
    out = _fwd(qt, k_idx, wt, _attn_blocks(q_idx.shape[1], blocks, DEFAULT_BLOCKS), diff)
    return out, (qt, k_idx, wt)


def _index_bwd(blocks, res, di):
    qt, k, wt = res
    dq, dk, dw = _bwd(qt, k, wt, di.astype(jnp.float32),
                      _attn_blocks(qt.shape[2], blocks, DEFAULT_BLOCKS))
    return (jnp.swapaxes(dq, 1, 2), dk,
            jnp.swapaxes(dw, 1, 2).astype(wt.dtype))


index_scores.defvjp(_index_fwd, _index_bwd)


# ------------------------------ the selection -------------------------------

def _select_kernel(s_ref, m_ref, n_ref, *, topk, rows):
    x = s_ref[...]
    t = x.shape[1]
    x = jnp.where(x == 0.0, 0.0, x)                       # -0.0 ties with +0.0
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    # int32 whose (signed) order is the floats'; INT_MIN, below every
    # float, where the key lies after the query
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    s_ids = jax.lax.broadcasted_iota(jnp.int32, (rows, t), 1)
    t_ids = pl.program_id(1) * rows + jax.lax.broadcasted_iota(
        jnp.int32, (rows, t), 0)
    causal = s_ids <= t_ids
    key = jnp.where(causal, key, jnp.int32(_INT_MIN))

    def count(hit):
        return jnp.sum(hit.astype(jnp.int32), axis=1, keepdims=True)

    def value_bit(i, ans):
        # `ans` holds the bits of the threshold in UNSIGNED order (the
        # key with its sign bit flipped); the comparison flips it back
        cand = ans | (jnp.int32(1) << (31 - i))
        enough = count(key >= (cand ^ jnp.int32(_INT_MIN))) >= topk
        return jnp.where(enough, cand, ans)

    ans = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((rows, 1), jnp.int32))
    thr = ans ^ jnp.int32(_INT_MIN)
    above, tie = key > thr, key == thr
    need = topk - count(above)
    nbits = max(t.bit_length(), 1)

    def place_bit(i, m):
        cand = m | (jnp.int32(1) << (nbits - 1 - i))
        return jnp.where(count(tie & (s_ids < cand)) < need, cand, m)

    last = jax.lax.fori_loop(0, nbits, place_bit,
                             jnp.zeros((rows, 1), jnp.int32))
    chosen = causal & (above | (tie & (s_ids <= last)))
    m_ref[...] = chosen.astype(jnp.int32).astype(jnp.int8)

    # the batch row's count of selected pairs, on every element of its
    # resident block (XLA's own reduction of the int8 mask to one number
    # took 27 ms a layer on the chip: PERF.md, PR 35)
    @pl.when(pl.program_id(1) == 0)
    def _():
        n_ref[...] = jnp.zeros(n_ref.shape, jnp.int32)

    n_ref[...] += jnp.sum(count(chosen))


def select_topk(scores, topk):
    """scores [B, T, T] float32 -> (mask [B, T, T] int8: row t marks the
    min(t + 1, topk) keys s <= t of largest score, ties to the lower s;
    the mask's sum as int32)."""
    b, t, _ = scores.shape
    rows = min(SELECT_ROWS, t)
    if t % rows:
        raise ValueError(f"select_topk: {t} rows, blocks of {rows}")
    spec = pl.BlockSpec((None, rows, t), lambda bi, ri: (bi, ri, 0))
    mask, n = pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, rows=rows),
        grid=(b, t // rows),
        in_specs=[spec],
        out_specs=[spec, pl.BlockSpec((None, 8, 128), lambda bi, ri: (bi, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, t, t), jnp.int8),
                   jax.ShapeDtypeStruct((b, 8, 128), jnp.int32)],
        interpret=_interpret(),
        compiler_params=_params((_PLL, _ARB)),
        name="sparse_index_select",
    )(scores.astype(jnp.float32))
    return mask, jnp.sum(n[:, 0, 0])


# ------------------------------ the indexer's loss ---------------------------

def _wide(col):
    """[rows, 1] -> [rows, 128]: a row statistic as a lane-dense block."""
    return jnp.broadcast_to(col, (col.shape[0], 128))


def _loss_kernel(s_ref, m_ref, p_ref, kl_ref, d_ref=None):
    x, p = s_ref[...], p_ref[...]
    sel = m_ref[...].astype(jnp.float32) > 0.0
    xm = jnp.where(sel, x, -1e30)
    top = jnp.max(xm, axis=1, keepdims=True)
    lse = top + jnp.log(jnp.sum(jnp.where(sel, jnp.exp(xm - top), 0.0),
                                axis=1, keepdims=True))
    live = sel & (p > 0.0)
    kl = jnp.where(live, p * (jnp.log(jnp.where(live, p, 1.0)) - (x - lse)),
                   0.0)
    kl_ref[...] = _wide(jnp.sum(kl, axis=1, keepdims=True))
    if d_ref is not None:
        ps = jnp.sum(jnp.where(sel, p, 0.0), axis=1, keepdims=True)
        d_ref[...] = jnp.where(sel, jnp.exp(xm - lse) * ps - p, 0.0)


def indexer_loss(scores, mask, probs, diff=False):
    """(mean over the rows of sum over the selected keys of P (log P - log
    softmax_selected(I)), d); scores, probs [B, T, T] float32, mask int8.
    `d` [B, T, T] float32 is the rows' gradient by the scores,
    `softmax_selected(I) * sum(P) - P` on the selected keys and 0 elsewhere
    (the mean's 1 / (B * T) left out), written on the same visit of the
    row where `diff` is set; None otherwise, and then no [B, T, T] array
    is written (`nn/functional/sparse_index.py::indexer_loss` has the
    rule that keeps `d`)."""
    b, t, _ = scores.shape
    rows = min(SELECT_ROWS, t)
    row = pl.BlockSpec((None, rows, t), lambda bi, ri: (bi, ri, 0))
    stat = pl.BlockSpec((None, rows, 128), lambda bi, ri: (bi, ri, 0))
    out_specs = [stat]
    out_shape = [jax.ShapeDtypeStruct((b, t, 128), jnp.float32)]
    if diff:
        out_specs.append(row)
        out_shape.append(jax.ShapeDtypeStruct(scores.shape, jnp.float32))
    kl, *d = pl.pallas_call(
        _loss_kernel, grid=(b, t // rows), in_specs=[row, row, row],
        out_specs=out_specs, out_shape=out_shape,
        interpret=_interpret(), compiler_params=_params((_PLL, _PLL)),
        name=_fwd_name("sparse_index_loss", diff),
    )(scores, mask, probs)
    return jnp.sum(kl[:, :, 0]) / (b * t), (d[0] if diff else None)
