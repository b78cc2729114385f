"""Attention functionals.

Parity targets: `paddle.nn.functional.scaled_dot_product_attention` /
`flash_attention` (python/paddle/nn/functional/flash_attention.py:146, backed
by third_party/flashattn CUDA kernels) and the fused rope op
(`paddle/phi/kernels/fusion/gpu/fused_rope_kernel.cu`).

TPU-first: on TPU the flash path dispatches a Pallas blockwise-softmax kernel
(`paddle_tpu.ops.pallas.flash_attention`); elsewhere a jnp reference
implementation with identical semantics.
"""
from __future__ import annotations

import contextlib
import math

import numpy as np

import jax
import jax.numpy as jnp

from ...core.dispatch import apply, op

__all__ = [
    "scaled_dot_product_attention", "selected_attention",
    "selected_attention_probs", "selected_attention_pairs", "flash_attention",
    "flash_attn_unpadded", "sdp_kernel",
    "fused_rotary_position_embedding", "apply_rotary_pos_emb",
]

# sdp_kernel() dispatch policy (reference flash_attention.py:27): which
# backends scaled_dot_product_attention may pick. On TPU there are two
# real tiers: the Pallas flash kernel and the jnp math path (the
# mem_efficient flag maps onto flash — one fused tier owns both roles).
_sdp_policy = {"math": True, "flash": True}


@contextlib.contextmanager
def sdp_kernel(enable_math=False, enable_flash=True,
               enable_mem_efficient=True):
    """Constrain scaled_dot_product_attention's kernel choice inside the
    context (reference sdp_kernel). enable_flash/enable_mem_efficient
    both gate the fused Pallas tier; enable_math the jnp reference."""
    global _sdp_policy
    old = _sdp_policy
    _sdp_policy = {"math": bool(enable_math),
                   "flash": bool(enable_flash or enable_mem_efficient)}
    try:
        yield
    finally:
        _sdp_policy = old


def _sdpa_ref(q, k, v, attn_mask, dropout_p, is_causal, scale,
              window=None):
    # q,k,v: [B, S, H, D] (paddle flash-attention layout); GQA inputs
    # (fewer KV heads) expand here — the Pallas path reads them grouped.
    # Flat-layout spelling: the einsums contract on the native [B,S,H,D]
    # operands directly (dot_general batches over non-leading (b, h)),
    # so only the [B,H,Sq,D] -> [B,Sq,H,D] output reorder remains as an
    # explicit transpose. Same contraction order as the old swapaxes
    # form — bit-identical values; this is what the PT401 budget for
    # the CPU-audited train step measures (tools/perf_budget.json).
    from ...ops.pallas.flash_attention import _expand_gqa_kv

    q, k, v = _expand_gqa_kv(q, k, v)
    d = q.shape[-1]
    scale = scale or (1.0 / math.sqrt(d))
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    logits = logits.astype(jnp.float32)
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:   # a sliding window of `window` keys
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        logits = jnp.where(mask, logits, -1e30)
    if attn_mask is not None:
        if attn_mask.dtype == jnp.bool_:
            logits = jnp.where(attn_mask, logits, -1e30)
        else:
            logits = logits + attn_mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None, window=None):
    """Inputs [batch, seq, heads, head_dim] (reference layout).
    window (with is_causal, no mask): each query sees the `window` keys
    that end at it (see ops.pallas.flash_attention_fwd)."""
    from ...ops import pallas as _pl

    # masks that need no gradient may stream through the biased fused
    # kernels; a trainable mask (stop_gradient=False) keeps the
    # reference path, which differentiates through the bias
    # default FALSE for attribute-less masks (raw arrays/tracers):
    # routing an unknown mask to the zero-cotangent biased kernel would
    # silently kill a trainable bias's gradient
    mask_sg = attn_mask is None or bool(
        getattr(attn_mask, "stop_gradient", False))

    def f(q, k, v, m):
        if _sdp_policy["flash"] and _pl.flash_attention_available(q):
            return _pl.flash_attention_fwd(q, k, v, m, is_causal,
                                           bias_grad_safe=mask_sg,
                                           window=window)
        if _sdp_policy["flash"]:
            # flash requested but unavailable for this input/backend —
            # the dispatch-tier fallback that used to be silent
            from ...observability import metrics as _obs_metrics

            _obs_metrics.inc("flash.dispatch", tier="fallback")
            _obs_metrics.inc("flash.fallback_reason",
                             reason="unavailable")
        if not _sdp_policy["math"]:
            # math disabled and flash unavailable (or also disabled):
            # falling through to the reference path would silently
            # violate the sdp_kernel policy
            raise RuntimeError(
                "sdp_kernel: math backend disabled and the flash "
                "(Pallas) kernel is "
                + ("unavailable for this input (CPU/interpret mode or "
                   "unsupported shape/dtype)"
                   if _sdp_policy["flash"] else "also disabled"))
        if window is not None and (m is not None or not is_causal):
            raise ValueError("scaled_dot_product_attention: window= needs "
                             "is_causal=True and no mask")
        return _sdpa_ref(q, k, v, m, dropout_p, is_causal, None, window)

    return apply("scaled_dot_product_attention", f, query, key, value,
                 attn_mask)


def selected_attention(query, key, value, selected):
    """Attention over a selected set of keys (learned sparse attention;
    `F.sparse_attention` is the CSR-pattern API): query t of every head sees
    the keys s with `selected[b, t, s]` != 0 ([B, T, T] int8, the causal
    bound included — `F.sparse_select_topk` makes it from an indexer's
    scores).  Inputs [batch, seq, heads, head_dim], grouped-query heads as
    they are; the selection gets no gradient.  Returns (out, stats):
    `stats` is what `selected_attention_probs` needs to follow the call.

    The kernel follows from the arguments: the Pallas kernels of
    `ops/pallas/sparse_attention.py` on the TPU (a dense causal pass that
    masks), the jax.numpy form elsewhere (`sparse_attn.dispatch{kernel}`).
    """
    from ...observability import metrics as _obs_metrics
    from ...ops.pallas import sparse_attention as _sp

    def f(q, k, v, m):
        if _sparse_pallas(q):
            _obs_metrics.inc("sparse_attn.dispatch", kernel="pallas")
            out, stats = _sp.sparse_attention(q, k, v, m)
        else:
            _obs_metrics.inc("sparse_attn.dispatch", kernel="reference")
            out, stats = _sp.reference(q, k, v, m), (q, k)
        return out, stats

    return apply("selected_attention", f, query, key, value, selected)


def _sparse_pallas(q):
    from ...ops.pallas import sparse_attention as _sp

    return _sdp_policy["flash"] and _sp.available(q)


def selected_attention_probs(stats, selected):
    """P [B, T, T] float32: the mean over the heads of the softmax over
    the selected keys (0 elsewhere) of the `selected_attention` call that
    returned `stats`, detached — the target of the indexer's loss."""
    from ...ops.pallas import sparse_attention as _sp

    def f(st, m):
        if len(st) == 3:
            return _sp.head_mean_probs(*st, m)
        return _sp.reference_probs(*st, m)

    return apply("selected_attention_probs", f, tuple(stats), selected)


def selected_attention_pairs(query):
    """(computed, causal): the (query, key) pairs `selected_attention`
    multiplies for a call of `query`'s shape — all of the tiles its
    kernel visits, selected or not — and the causal pairs."""
    from ...ops.pallas import sparse_attention as _sp

    b, t = query.shape[0], query.shape[1]
    v = getattr(query, "_value", query)
    computed = _sp.computed_pairs(b, t) if _sparse_pallas(v) else b * t * t
    return computed, b * t * (t + 1) // 2


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None, rng_name="",
                    training=True, name=None):
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k,
                        max_seqlen_q, max_seqlen_k, scale, dropout=0.0,
                        causal=False, return_softmax=False,
                        fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Packed ragged-batch attention (reference
    `flash_attention.py:302`): query/key/value [total_seq_len, H, D],
    cu_seqlens_* [n+1] cumulative lengths. Returns (out, softmax) with
    softmax None unless return_softmax (never materialized here).

    TPU-first: segment-masked Pallas kernels
    (`ops.pallas.varlen_attention`) — the ragged batch runs
    block-diagonal with static shapes; no per-sequence loop, no T x T
    mask. Attention-probability dropout is not applied on this path
    (the fused kernel never materializes probabilities); `dropout` is
    accepted for signature parity.
    """
    if return_softmax:
        raise NotImplementedError(
            "flash_attn_unpadded: return_softmax=True would materialize "
            "the T x T probabilities the fused kernel exists to avoid")
    if dropout and training:
        raise NotImplementedError(
            "flash_attn_unpadded: attention-probability dropout is not "
            "applied on the fused path (probabilities never materialize); "
            "pass dropout=0 and regularize elsewhere, or use "
            "scaled_dot_product_attention's reference path")
    if causal:
        # per-sequence causal alignment needs IDENTICAL packings: the
        # kernel's one global diagonal offset cannot express the
        # reference's bottom-right alignment across differently-packed
        # q/k (e.g. chunked prefill) — fail loudly, never silently
        import numpy as _np

        try:
            cq = _np.asarray(cu_seqlens_q.numpy()
                             if hasattr(cu_seqlens_q, "numpy")
                             else cu_seqlens_q)
            ck = _np.asarray(cu_seqlens_k.numpy()
                             if hasattr(cu_seqlens_k, "numpy")
                             else cu_seqlens_k)
            same = cq.shape == ck.shape and bool((cq == ck).all())
        except Exception:
            same = None  # traced values: cannot validate here — a
            # jitted call with mismatched packings computes the wrong
            # causal alignment undetected (documented hole; validate
            # packings before jit, or pass concrete cu_seqlens)
        if same is False:
            raise NotImplementedError(
                "flash_attn_unpadded: causal=True requires identical "
                "cu_seqlens_q and cu_seqlens_k (per-sequence causal "
                "alignment across different packings is not supported). "
                "NOTE: this check only runs on concrete cu_seqlens — "
                "under jit the values are traced and a mismatch cannot "
                "be detected; validate before tracing.")
    from ...ops.pallas.varlen_attention import varlen_attention

    def f(q, k, v, cu_q, cu_k):
        return varlen_attention(q, k, v, cu_q, cu_k, scale=scale,
                                causal=causal)

    out = apply("flash_attn_unpadded", f, query, key, value,
                cu_seqlens_q, cu_seqlens_k)
    return out, None


def _rope_rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope_rotate_interleaved(x):
    x1 = x[..., ::2]
    x2 = x[..., 1::2]
    out = jnp.stack([-x2, x1], axis=-1)
    return out.reshape(x.shape)


@op("fused_rotary_position_embedding")
def fused_rotary_position_embedding(q, k=None, v=None, sin=None, cos=None,
                                    position_ids=None, use_neox_rotary_style=True,
                                    time_major=False, rotary_emb_base=10000.0,
                                    name=None, mrope_section=None):
    """q/k/v: [B, S, H, D]. Matches incubate.nn.functional.
    fused_rotary_position_embedding semantics (fused_rope_kernel.cu).

    mrope_section (multimodal rotary positions, with position_ids [3, B,
    S] — temporal, height, width — and no sin/cos): the D/2 rotary pairs
    are owned by the three rows in contiguous sections, e.g. (16, 24, 24):
    pair i turns by the position of the row whose section holds i.  Three
    equal rows are plain rotary positions."""
    if time_major:
        raise NotImplementedError(
            "fused_rotary_position_embedding: time_major=True ([S, B, ...]"
            " layout) is not supported — pass batch-major tensors")
    b, s, h, d = q.shape
    if sin is None or cos is None:
        inv = 1.0 / (rotary_emb_base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
        if mrope_section is not None:
            if position_ids is None or jnp.ndim(position_ids) != 3 \
                    or sum(mrope_section) != d // 2:
                raise ValueError(
                    "fused_rotary_position_embedding: mrope_section needs "
                    f"position_ids [3, B, S] and sections that sum to {d // 2}")
            pos = jnp.asarray(position_ids).astype(jnp.float32)  # [3, B, S]
            row = np.repeat(np.arange(3), mrope_section)         # [D/2]
            # the pair's own row of positions, as a select over the three
            freqs = sum(jnp.where(row == r, pos[r][:, :, None] * inv, 0.0)
                        for r in range(3))                       # [B,S,D/2]
            emb = jnp.concatenate([freqs, freqs], axis=-1)
            cos = jnp.cos(emb)[:, :, None, :]
            sin = jnp.sin(emb)[:, :, None, :]
            position_ids = None  # consumed
        elif position_ids is not None:
            # explicit positions (decode offsets): build phases per position
            pos = jnp.asarray(position_ids).astype(jnp.float32)  # [B, S]
            freqs = pos[:, :, None] * inv[None, None, :]         # [B,S,D/2]
            emb = jnp.concatenate([freqs, freqs], axis=-1)
            cos = jnp.cos(emb)[:, :, None, :]
            sin = jnp.sin(emb)[:, :, None, :]
            position_ids = None  # consumed
        else:
            t = jnp.arange(s, dtype=jnp.float32)
            freqs = jnp.outer(t, inv)  # [S, D/2]
            emb = jnp.concatenate([freqs, freqs], axis=-1)
            cos = jnp.cos(emb)[None, :, None, :]
            sin = jnp.sin(emb)[None, :, None, :]
    else:
        cos = jnp.reshape(cos, (1, -1, 1, d))
        sin = jnp.reshape(sin, (1, -1, 1, d))
    if position_ids is not None:
        cos = jnp.squeeze(cos, axis=(0, 2))[position_ids][:, :, None, :]
        sin = jnp.squeeze(sin, axis=(0, 2))[position_ids][:, :, None, :]
    cos = cos.astype(q.dtype)
    sin = sin.astype(q.dtype)

    rot = _rope_rotate_half if use_neox_rotary_style else \
        _rope_rotate_interleaved

    def emb_one(x):
        if x is None:
            return None
        if use_neox_rotary_style:
            from ...ops.pallas.rope import rope_available, rope_pallas

            if rope_available(x):
                return rope_pallas(x, cos, sin)
        return x * cos + rot(x) * sin

    return tuple(emb_one(x) for x in (q, k, v))


def apply_rotary_pos_emb(q, k, cos, sin, position_ids=None):
    out = fused_rotary_position_embedding(q, k, None, sin=sin, cos=cos,
                                          position_ids=position_ids)
    return out[0], out[1]
