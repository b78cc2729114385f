"""The indexer of learned sparse attention (DeepSeek Sparse Attention's
"lightning indexer"; docs/ATTENTION.md "Learned sparse attention"): a few
cheap score heads against ONE shared key head decide which keys each query
of the real attention may see.

    I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])        float32
    S_t     = the min(t + 1, topk) keys s <= t of largest I[t, s],
              ties to the lower s
    L_I     = mean_t KL(P[t, .] || softmax over S_t of I[t, .])

with P the attention's own head-mean probabilities over S_t, detached: the
indexer learns to rank keys the way the attention weighs them.  The
selection is no path for a gradient.

`index_scores` runs the kernels of `ops/pallas/sparse_index.py` on the TPU;
elsewhere it walks the queries in blocks (each recomputed in the backward
pass).  Either way the per-head scores [heads, T, T] never exist in memory.
Each of the three entries counts which form it took at trace time
(`sparse_index.dispatch{op=scores|select|loss,kernel=pallas|reference}`):
a jax.numpy form inside a run that should be on the kernels is then a
counter that reads wrong, not a slow number.  `select_topk`
finds each row's topk-th largest score by a radix search over the float's
bits — 32 compare-and-count passes over [B, T, T], no sort — and settles a
tie at the threshold by a second search over the key's position, so the
set is exactly `jax.lax.top_k`'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...core.dispatch import apply
from ...distributed.recompute import keep as _keep, keeping as _keeping

__all__ = ["sparse_index_scores", "sparse_select_topk", "sparse_indexer_loss",
           "selected_pairs"]

SCORE_BLOCK = 256


def selected_pairs(batch, t, topk):
    """sum_t min(t + 1, topk) over a [batch, t] call: what `select_topk`
    selects, to the unit."""
    k = min(topk, t)
    return batch * (k * (k + 1) // 2 + (t - k) * k)


def index_scores(q_idx, k_idx, w):
    """q_idx [B, T, J, D], k_idx [B, T, D], w [B, T, J] -> I [B, T, T]
    float32 (what stands where s > t is not read: the causal bound is the
    selection's)."""
    from ...ops.pallas import sparse_index as _kernels

    if _took_kernel("scores", _kernels.available(q_idx)):
        return _kernels.index_scores(q_idx, k_idx, w)
    return _index_scores_blocked(q_idx, k_idx, w)


def _took_kernel(op, pallas):
    """Counts the form an entry takes, and says it."""
    from ...observability import metrics as _metrics

    _metrics.inc("sparse_index.dispatch", op=op,
                 kernel="pallas" if pallas else "reference")
    return pallas


def _index_scores_blocked(q_idx, k_idx, w):
    b, t, j, d = q_idx.shape
    blk = min(SCORE_BLOCK, t)
    if t % blk:
        raise ValueError(f"index_scores: {t} positions, blocks of {blk}")

    @jax.checkpoint
    def one(qb, wb):
        z = jnp.einsum("bqjd,bkd->bjqk", qb, k_idx,
                       preferred_element_type=jnp.float32)
        wj = jnp.swapaxes(wb.astype(jnp.float32), 1, 2)[..., None]
        return jnp.sum(jax.nn.relu(z) * wj, axis=1)

    blocks = lambda x: jnp.swapaxes(
        x.reshape(b, t // blk, blk, *x.shape[2:]), 0, 1)
    out = jax.lax.map(lambda a: one(*a), (blocks(q_idx), blocks(w)))
    return jnp.swapaxes(out, 0, 1).reshape(b, t, t)


def _ordered_bits(x, causal):
    """float32 -> uint32 whose order is the floats' (-0.0 as +0.0); 0,
    below every float, where not causal."""
    x = jnp.where(x == 0, 0.0, x)
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    u = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(causal, u, jnp.uint32(0))


def select_topk(scores, topk):
    """scores [B, T, T] float32 -> (mask [B, T, T] int8: row t marks the
    min(t + 1, topk) keys s <= t of largest score, ties to the lower s;
    the mask's sum, int32).  On the TPU the same two searches run on rows
    held in VMEM (`ops/pallas/sparse_index.py::select_topk`)."""
    from ...ops.pallas import sparse_index as _kernels

    if _took_kernel("select", _kernels.on_tpu()):
        return _kernels.select_topk(scores, topk)
    mask = _select_topk_passes(scores, topk)
    return mask, jnp.sum(mask, dtype=jnp.int32)


def _select_topk_passes(scores, topk):
    b, t, _ = scores.shape
    s_ids = jnp.arange(t, dtype=jnp.int32)
    causal = s_ids[None, :] <= s_ids[:, None]
    u = _ordered_bits(scores.astype(jnp.float32), causal)

    def count(hit):
        return jnp.sum(hit, axis=-1, dtype=jnp.int32)

    def value_bit(i, thr):
        cand = thr | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        return jnp.where(count(u >= cand[..., None]) >= topk, cand, thr)

    # the topk-th largest of the row (0 where the row has fewer)
    thr = jax.lax.fori_loop(0, 32, value_bit, jnp.zeros((b, t), jnp.uint32))
    above = u > thr[..., None]
    tie = u == thr[..., None]
    need = topk - count(above)
    bits = max(t.bit_length(), 1)

    def place_bit(i, m):
        cand = m | (jnp.int32(1) << (bits - 1 - i))
        return jnp.where(count(tie & (s_ids < cand[..., None])) < need,
                         cand, m)

    # the last position a tie at the threshold may have
    last = jax.lax.fori_loop(0, bits, place_bit, jnp.zeros((b, t), jnp.int32))
    return (causal & (above | (tie & (s_ids <= last[..., None])))
            ).astype(jnp.int8)


def indexer_loss(scores, mask, probs):
    """mean over the rows of sum over the selected keys of
    P (log P - log softmax_selected(I)); P is taken as given (detached).
    On the TPU a row's reductions run on the row in VMEM
    (`ops/pallas/sparse_index.py::indexer_loss`).

    The gradient is formed in the forward pass.  By the scores a row's KL
    has the closed form `d = softmax_selected(I) * sum(P) - P` on the
    selected keys, which wants nothing the backward pass brings but a
    scalar: the differentiated call makes `d` [B, T, T] float32 where the
    row is already read and holds it as its ONLY residual, marked
    `indexer_grad` (`distributed/recompute.py`).  A recomputed block then
    replays neither the index-score forward nor the attention's head-mean
    probabilities nor this loss: nothing in its backward pass reads them
    (`sparse_index.recompute_kept{what=loss_grad}`, one a call whose
    segment holds the marks).  An undifferentiated call writes no
    [B, T, T] array."""
    from ...observability import metrics as _metrics
    from ...ops.pallas import sparse_index as _kernels

    _took_kernel("loss", _kernels.on_tpu())
    if _keeping():
        _metrics.inc("sparse_index.recompute_kept", what="loss_grad")
    return _loss(scores, mask,
                 jax.lax.stop_gradient(probs).astype(scores.dtype))


def _loss_and_grad(scores, mask, probs, diff):
    """(the loss, `d` where `diff` and None otherwise), by the form the
    backend takes."""
    from ...ops.pallas import sparse_index as _kernels

    if _kernels.on_tpu():
        return _kernels.indexer_loss(scores, mask, probs, diff)
    return _indexer_loss_rows(scores, mask, probs, diff)


def _indexer_loss_rows(scores, mask, probs, diff=False):
    sel = mask > 0
    masked = jnp.where(sel, scores, -jnp.inf)
    lse = jax.nn.logsumexp(masked, axis=-1, keepdims=True)
    live = sel & (probs > 0)
    kl = jnp.where(live, probs * (jnp.log(jnp.where(live, probs, 1.0))
                                  - (scores - lse)), 0.0)
    value = jnp.sum(kl) / (scores.shape[0] * scores.shape[1])
    if not diff:
        return value, None
    ps = jnp.sum(jnp.where(sel, probs, 0.0), axis=-1, keepdims=True)
    return value, jnp.where(sel, jnp.exp(masked - lse) * ps - probs, 0.0)


@jax.custom_vjp
def _loss(scores, mask, probs):
    return _loss_and_grad(scores, mask, probs, False)[0]


def _loss_fwd(scores, mask, probs):
    value, d = _loss_and_grad(scores, mask, probs, True)
    return value, _keep(d, "indexer_grad")


def _loss_bwd(d, g):
    b, t, _ = d.shape
    return (d * (g / (b * t)), np.zeros(d.shape, jax.dtypes.float0),
            jnp.zeros_like(d))


_loss.defvjp(_loss_fwd, _loss_bwd)


# --- ops ---------------------------------------------------------------------

def sparse_index_scores(q_idx, k_idx, w):
    """Tensor op over `index_scores`."""
    return apply("sparse_index_scores", index_scores, q_idx, k_idx, w)


def sparse_select_topk(scores, topk):
    """(mask [B, T, T] int8, its sum as int32): the selection, held across
    a block's recomputation (`distributed/recompute.py`): the replay reads
    the kept mask and runs no search."""

    def f(sc):
        with jax.named_scope("attn.indexer.select"):
            mask, n = select_topk(jax.lax.stop_gradient(sc), topk)
            return _keep(mask, "sparse_select"), n

    return apply("sparse_select_topk", f, scores)


def sparse_indexer_loss(scores, mask, probs):
    return apply("sparse_indexer_loss", indexer_loss, scores, mask, probs)
