"""Flash attention for TPU — Pallas VMEM-blocked kernel with custom VJP.

Role parity: third_party/flashattn + the fused attention kernels under
`paddle/phi/kernels/fusion/gpu/` (exposed as
`nn.functional.flash_attention`, flash_attention.py:146 in the reference).

Design (TPU-first, not a CUDA translation):
  * forward: grid (batch, heads, q_blocks); K/V live in VMEM per (b,h); an
    online-softmax fori_loop walks KV blocks with f32 running max/sum/acc —
    logits never materialize in HBM. Causal blocks that are fully masked are
    skipped by bounding the loop, and fully-unmasked blocks (strictly below
    the diagonal) take a mask-free body: the iota/compare/select chain only
    runs on diagonal blocks, which matters because the kernel is VPU-bound
    at head_dim 64 (PERF.md round-3 microbenchmarks).
  * dots run in the input dtype (bf16 on TPU) with f32 accumulation via
    preferred_element_type — casting operands to f32 first (round-2 design)
    forces the MXU off its bf16 path and measured 4x slower. The softmax
    scale is applied to the f32 logits, not the bf16 operands.
  * backward: recomputation-style — blocked logits are replayed from
    saved (out, logsumexp) rather than storing P; same bf16-dot +
    diagonal-only-masking treatment as forward. The transpose and flat
    cores replay them ONCE: one fused kernel (grid over kv_blocks, inner
    loop over q_blocks) makes dK/dV and accumulates dQ for the whole
    sequence in f32 VMEM, working on the transposed tile sT = k·qT so
    that lse and delta are lane-dense rows (_fused_bwd_loop: 5 block
    matmuls a tile, FlashAttention's count). Where the sequence-long
    q/o/do/dQ do not fit the kernel's VMEM (_flat_vmem_bytes,
    _t_vmem_bytes) they run the split pair the biased and varlen tiers
    keep: one kernel for dQ (grid over q_blocks, _dq_loop), one for
    dK/dV (grid over kv_blocks, _dkv_loop) — 7 matmuls and the
    per-logit chain twice. Same dots, same order: bit-identical.
  * block sizes are autotuned per signature on a fwd+bwd run (cached on
    disk; paddle/phi/kernels/autotune role). At B32 H12 S1024 D64 bf16 the
    tuned kernel measures ~4x over the 128x128 static default.
  * dtype: IO in input dtype, accumulation in f32; softmax stats rank-2
    `(block_q, 1)` f32 (rank-1 stats blocks do not lower to Mosaic);
    the VJP's forward writes lse as lane-dense rows, which the fused
    backward reads (the column pads 128-fold, in VMEM and in HBM).
  * non-TPU backends run the same kernels through the Pallas interpreter so
    CPU tests validate the exact kernel code (fake-backend strategy,
    SURVEY §4.5).

Supports is_causal; grad-free additive/boolean masks broadcastable to
[B, H, Sq, Sk] stream blockwise through the biased kernels (_flash_core_b),
trainable masks take the fused-softmax reference path.
"""
from __future__ import annotations

import contextlib
import functools
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# telemetry (stdlib-only package — safe to import at kernel-module load).
# Dispatch decisions, gate rejects, and autotune reuse all happen at
# TRACE time, so the counters cost nothing per device step; the metrics
# registry itself is a no-op until observability.attach() enables it.
from ...observability import flight as _flight
from ...observability import metrics as _metrics

_PLL = pltpu.GridDimensionSemantics.PARALLEL
_ARB = pltpu.GridDimensionSemantics.ARBITRARY
_TPUCompilerParams = pltpu.CompilerParams

DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30

_DIMSEM = (_PLL, _PLL, _ARB)

# per-kernel scoped-VMEM limit of the flat kernels (see
# _flat_compiler_params); the same number bounds the flat gate's
# estimate (_flat_vmem_bytes)
_FLAT_VMEM_LIMIT = 34 * 1024 * 1024

# Two cores run a call that carries no mask, and _choose_core picks one
# from the call's shape — no flag selects a kernel:
#   flat       everything on unpadded [B,S,H*D] views, zero transposes,
#              heads as static lane slices; needs a 128-lane-aligned
#              H*D, d % 64 == 0 and all heads' sequence-long operands
#              inside _FLAT_VMEM_LIMIT (12 x 64 at sq = 1024: yes; at
#              2048: no).
#   transpose  per-head kernels over [B,H,S,D] with layout transposes
#              around the call: every other shape, every padded length
#              and every window.
# Same recurrences, gradients bit-identical.  Masked calls take the
# biased core (_flash_core_b).  docs/ATTENTION.md "The layout story"
# tells which other layouts were tried and what the compiler refused.


# Names in the program (docs/OBSERVABILITY.md "Scopes"): every
# pallas_call is `flash_<tier>_<fwd|dq|dkdv>` (`flash_<tier>_bwd` for
# the fused backward), and the transposes,
# reshapes and pads a tier's wrapper puts around its kernels sit under
# LAYOUT_SCOPE — XLA ops, device time outside the kernel family.
LAYOUT_SCOPE = "flash.layout"


def _layout_swap(*xs):
    """[B,S,H,D] <-> [B,H,S,D] on each of `xs`: XLA transposes, named."""
    with jax.named_scope(LAYOUT_SCOPE):
        return tuple(jnp.swapaxes(x, 1, 2) for x in xs)


def _kept(out, lse):
    """A VJP forward rule's output and log-sum-exp, marked as kept across
    per-layer recomputation (`distributed/recompute.py`): the rule returns
    them as residuals, so a replay that holds them drops the forward
    kernel.  Called before anything else reads them."""
    from ...distributed.recompute import keep

    return keep(out, "flash_out"), keep(lse, "flash_lse")


def _windowed(kernel, window):
    """`flash_transpose_fwd` -> `flash_transpose_window_fwd` where the
    call has a window: windowed calls are named apart, so a device trace
    tells a banded layer from a full one."""
    if window is None:
        return kernel
    tier, _, part = kernel.rpartition("_")
    return f"{tier}_window_{part}"


def _fwd_name(kernel, diff, window=None):
    """A forward kernel's name: plain where the call is the whole story
    (inference), `jvp(<kernel>)` where it is the forward half of a
    differentiated call (the custom_vjp's fwd rule, which also saves the
    residuals).  XLA names a Mosaic custom call after the innermost
    scope of its op_name, and pallas_call makes the name that scope — so
    JAX's own transform marks, which module scopes push outward, have to
    be written here for a device trace's event to keep saying which
    direction it belongs to (`%jvp_flash_flat_fwd_.3`)."""
    kernel = _windowed(kernel, window)
    return f"jvp({kernel})" if diff else kernel


def _bwd_name(kernel, window=None):
    """A backward kernel's name, `transpose(jvp(<kernel>))`: these run
    only as the transpose of a differentiated forward (see _fwd_name)."""
    return f"transpose(jvp({_windowed(kernel, window)}))"


_FORCE_COMPILED = False  # see force_tpu_lowering()


def _interpret():
    if _FORCE_COMPILED:
        return False
    return jax.devices()[0].platform != "tpu"


def _compiler_params(vmem_limit_bytes=None):
    # dimension_semantics lets Mosaic reorder/parallelize the (b, h) grid
    # axes; the trailing q/kv-block axis stays sequential (online softmax /
    # accumulation carries). Interpreter mode rejects TPU compiler params.
    # vmem_limit_bytes: only a kernel whose estimate passes the
    # compiler's default (_T_VMEM_LIMIT) sets one.
    if _interpret():
        return None
    if vmem_limit_bytes is None:
        return _TPUCompilerParams(dimension_semantics=_DIMSEM)
    return _TPUCompilerParams(dimension_semantics=_DIMSEM,
                              vmem_limit_bytes=int(vmem_limit_bytes))


@contextlib.contextmanager
def force_tpu_lowering():
    """Trace Pallas kernels for real Mosaic lowering even on a CPU host.

    Used by the TPU-lowering CI gate (tests/test_tpu_lowering.py): under
    `jax.export(..., platforms=['tpu'])` the kernels must go through
    `pallas_call(interpret=False)` so BlockSpec/Mosaic layout errors — the
    class of failure that broke the round-2 bench on hardware — surface
    without a chip."""
    global _FORCE_COMPILED
    old = _FORCE_COMPILED
    _FORCE_COMPILED = True
    try:
        yield
    finally:
        _FORCE_COMPILED = old


def flash_attention_available(q) -> bool:
    """Pallas path policy: TPU with MXU-friendly shapes. (CPU exercises the
    same kernels through the interpreter in tests/test_pallas.py; the eager
    CPU fallback is the jnp reference.)"""
    from ...core import flags

    if not flags.pallas_enabled("flash"):
        return False
    if q.ndim != 4:
        return False
    b, s, h, d = q.shape
    # odd sequence lengths (ViT's 197, ragged NLP batches) are handled by
    # padding to a multiple of 8 with real-length masking in the entry
    # point — only the head_dim constraints gate the kernel now
    if not (d % 8 == 0 and d <= 256):
        return False
    return not _interpret()


# =========================== forward kernel ===========================

def _band_k_blocks(iq, block_q, block_k, off, window, num_full,
                   num_iters):
    """The KV blocks q block `iq` visits under a causal window (row i
    sees key j iff 0 <= i + off - j < window), as (lo, full_lo,
    full_hi): blocks [lo, full_lo) are cut by the band's LEFT edge,
    [full_lo, full_hi) lie wholly inside it for every row, and
    [full_hi, num_iters) are cut by the diagonal — the two masked runs
    meet where the window is narrower than a block.  Blocks below `lo`
    are wholly outside the band and never visited."""
    lo = jnp.clip((iq * block_q + off - window + 1) // block_k, 0,
                  num_iters)
    full_lo = jnp.clip(pl.cdiv((iq + 1) * block_q + off - window, block_k),
                       lo, num_iters)
    return lo, full_lo, jnp.clip(num_full, full_lo, num_iters)


def _band_q_blocks(jk, block_q, block_k, off, window, start_block,
                   first_full, num_iters):
    """The q blocks that see KV block `jk` under a causal window, as
    (first_full, full_hi, end): after the diagonal run [start_block,
    first_full) of _causal_q_blocks, blocks [first_full, full_hi) hold
    the KV block wholly inside every row's band, [full_hi, end) are cut
    by the band's right edge (rows >= k + window - off see nothing of
    it), and blocks from `end` on are never visited."""
    end = jnp.clip(((jk + 1) * block_k + window - 2 - off) // block_q + 1,
                   start_block, num_iters)
    first_full = jnp.clip(first_full, start_block, end)
    full_hi = jnp.clip((window + jk * block_k - off) // block_q,
                       first_full, end)
    return first_full, full_hi, end


def _online_softmax(q, load_kv, *, iq, block_q, block_k, scale, causal,
                    seq_q, seq_k, seg_q=None, load_seg_k=None,
                    load_bias=None, window=None):
    """The shared flash recurrence: walk KV blocks with f32 running
    max/sum/acc; logits never materialize in HBM. One body for BOTH
    forward kernels (per-head transpose layout and all-heads block) —
    the tests' bit-identical-forwards invariant rests on this being the
    single source of the numerics.

    q: [block_q, d] (input dtype; dots accumulate in f32 via
    preferred_element_type). load_kv(j) -> (k, v) each [block_k, d].
    Causal is bottom-right aligned like the reference (_ref_attention
    tril k=sk-sq): q row i attends k cols <= i + (seq_k - seq_q).
    Returns (out [block_q, d] f32, lse [block_q, 1] f32); stats are
    rank-2 — a rank-1 (block_q,) block does not lower to Mosaic
    (VERDICT r2 missing #2).

    seg_q/load_seg_k: varlen packed mode — segment ids ([block_q, 1] and
    per-block [block_k, 1]); positions attend only within their segment,
    so ragged batches run block-diagonal WITHOUT a T x T mask ever
    materializing (flash_attn_unpadded). Segment boundaries can cut any
    block, so every block runs the masked body in this mode.

    load_bias(j) -> [block_q, block_k] f32 additive bias (rel-pos /
    ALiBi / additive masks), added to the scaled logits before the
    running softmax — the bias streams blockwise, never a full [Sq, Sk]
    logits materialization.

    window (causal only): row i attends keys j with
    0 <= i + off - j < window.  KV blocks wholly left of the band are
    never visited and the blocks its left edge cuts run the masked body
    (_band_k_blocks).  A row whose first visited block is wholly masked
    carries p = 1 garbage until its first real key arrives with
    alpha = exp(-1e30 - m) = 0, which erases it exactly.
    """
    d = q.shape[-1]
    off = seq_k - seq_q  # causal diagonal offset (0 for self-attention)
    num_k_blocks = pl.cdiv(seq_k, block_k)
    segmented = seg_q is not None

    def make_body(masked):
        def body(j, carry):
            m, l, acc = carry
            k, v = load_kv(j)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if load_bias is not None:
                s = s + load_bias(j)
            if masked:
                q_ids = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_ids = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                valid = k_ids < seq_k
                if causal:
                    valid = jnp.logical_and(valid, q_ids + off >= k_ids)
                if window is not None:
                    valid = jnp.logical_and(valid,
                                            q_ids + off - k_ids < window)
                if segmented:
                    seg_k = load_seg_k(j)  # [block_k, 1]
                    valid = jnp.logical_and(
                        valid, seg_q == seg_k.reshape(1, block_k))
                s = jnp.where(valid, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            acc_new = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new
        return body

    carry0 = (jnp.full((block_q, 1), NEG_INF, jnp.float32),
              jnp.zeros((block_q, 1), jnp.float32),
              jnp.zeros((block_q, d), jnp.float32))
    if causal:
        # blocks with max k_id <= min q_id + off are fully unmasked:
        # mask-free body; the diagonal remainder runs the masked body.
        # (Segmented mode: boundaries cut anywhere, all blocks masked.)
        num_full = jnp.clip((iq * block_q + off + 1) // block_k,
                            0, num_k_blocks)
        num_iters = jnp.clip(pl.cdiv((iq + 1) * block_q + off, block_k),
                             num_full, num_k_blocks)
        if segmented:
            m, l, acc = jax.lax.fori_loop(0, num_iters, make_body(True),
                                          carry0)
        elif window is not None:
            lo, full_lo, full_hi = _band_k_blocks(
                iq, block_q, block_k, off, window, num_full, num_iters)
            carry = jax.lax.fori_loop(lo, full_lo, make_body(True), carry0)
            carry = jax.lax.fori_loop(full_lo, full_hi, make_body(False),
                                      carry)
            m, l, acc = jax.lax.fori_loop(full_hi, num_iters,
                                          make_body(True), carry)
        else:
            carry = jax.lax.fori_loop(0, num_full, make_body(False),
                                      carry0)
            m, l, acc = jax.lax.fori_loop(num_full, num_iters,
                                          make_body(True), carry)
    else:
        m, l, acc = jax.lax.fori_loop(
            0, num_k_blocks,
            make_body(segmented or seq_k % block_k != 0), carry0)
    l_safe = jnp.maximum(l, 1e-30)
    return acc / l_safe, m + jnp.log(l_safe)


def _col_to_row(col):
    """[n, 1] f32 column -> the same values as a lane-dense [1, n] row,
    exactly (a plain (n, 1) -> (1, n) reshape does not lower to Mosaic):
    128 at a time, select the diagonal of the lane-broadcast piece and
    sum over sublanes (one value and zeros).  VPU work on purpose: a
    transpose of the broadcast column costs the fused backward, whose
    dQ contraction already loads the transpose unit, 0.13 ms a layer
    more at the benchmark's shapes (PERF.md, PR 27)."""
    n = col.shape[0]
    rows = []
    for lo in range(0, n, 128):
        c = min(128, n - lo)
        eye = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) ==
               jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
        rows.append(jnp.sum(jnp.where(eye, col[lo:lo + c], 0.0), axis=0,
                            keepdims=True))
    return rows[0] if len(rows) == 1 else jnp.concatenate(rows, axis=1)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, block_k,
                causal, seq_q, seq_k, lse_rows=False, window=None):
    # q_ref: [block_q, d]; k_ref/v_ref: [seq_k, d]; o_ref: [block_q, d];
    # lse_ref: [block_q, 1], or the lane-dense row [1, block_q] the
    # fused backward reads (lse_rows).
    block_q = q_ref.shape[0]
    out, lse = _online_softmax(
        q_ref[:],
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(2), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        window=window)
    o_ref[:] = out.astype(o_ref.dtype)
    lse = lse.astype(jnp.float32)
    lse_ref[:] = _col_to_row(lse) if lse_rows else lse


def _fwd_kernel_bias(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref, *, scale,
                     block_k, causal, seq_q, seq_k):
    # b_ref: [block_q, seq_k] f32 additive bias row-band for this q block
    block_q = q_ref.shape[0]
    out, lse = _online_softmax(
        q_ref[:],
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(2), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        load_bias=lambda j: b_ref[:, pl.ds(j * block_k, block_k)]
        .astype(jnp.float32))
    o_ref[:] = out.astype(o_ref.dtype)
    lse_ref[:] = lse.astype(jnp.float32)


def _pick_block(seq, pref):
    """Largest multiple of 8 ≤ pref that divides seq (avoids OOB dynamic
    slices on the trailing block: refs are full-array, not pallas-padded).
    Loud on indivisible seq — a block that doesn't divide the sequence
    would read/write out of bounds and silently corrupt the tail rows
    (the dispatch gates route such shapes to the reference path; reaching
    here means _flash_core was called directly)."""
    if seq % 8 != 0:
        raise ValueError(
            f"flash attention Pallas kernel requires seq % 8 == 0, got "
            f"{seq}; use nn.functional attention entry points, which fall "
            "back to the fused-softmax reference path for such shapes")
    b = min(pref, seq)
    b -= b % 8
    while b > 8 and seq % b:
        b -= 8
    return max(b, 8)


def _win(kernel_kw, window):
    """Kernel keywords with `window` added only where there is one: an
    un-windowed call binds exactly the keywords it always did."""
    return kernel_kw if window is None else dict(kernel_kw, window=window)


def _fwd_t(qt, kt, vt, causal, block_q, block_k, seq_q_real=None,
           seq_k_real=None, diff=False, window=None):
    """Forward on head-major [B,H,S,D] operands (the kernels' native
    layout). Returns (out_t [B,H,Sq,D], lse [B,H,Sq,1]); under
    differentiation (diff) lse comes lane-dense, one row a q block:
    [B,H,Sq/block_q,1,block_q], as _bwd_t reads it (the column pads
    every value to 128 lanes, in VMEM and in HBM).

    GQA: kt/vt may carry fewer heads ([B,Hkv,S,D], Hq % Hkv == 0) — the
    K/V index maps group query heads onto their KV head (hi // rep), so
    the shrunken KV is read directly instead of materializing a
    repeat_interleave'd copy (the reference expands; on TPU that
    multiplies KV HBM traffic by the group size for nothing).

    seq_q_real/seq_k_real: logical lengths when the arrays are padded to
    a block-friendly multiple (odd ViT-style lengths, e.g. 197): the
    kernels mask on the REAL bounds (k_ids < seq_k), padded key rows
    never contribute, and the caller slices padded q rows off the
    output.

    window: see _online_softmax; K/V stay whole in VMEM (block-sliced),
    the loop visits the band's blocks only."""
    b, h, sq, d = qt.shape
    h_kv = kt.shape[1]
    assert h % h_kv == 0, (h, h_kv)
    rep = h // h_kv
    sk = kt.shape[2]
    sq_r = seq_q_real or sq
    sk_r = seq_k_real or sk
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    grid = (b, h, pl.cdiv(sq, block_q))
    if diff:
        lse_spec = pl.BlockSpec((None, None, None, 1, block_q),
                                lambda bi, hi, qi: (bi, hi, qi, 0, 0))
        lse_shape = (b, h, sq // block_q, 1, block_q)
    else:
        lse_spec = pl.BlockSpec((None, None, block_q, 1),
                                lambda bi, hi, qi: (bi, hi, qi, 0))
        lse_shape = (b, h, sq, 1)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, **_win(dict(
            scale=scale, block_k=block_k, causal=causal, seq_q=sq_r,
            seq_k=sk_r, lse_rows=diff), window)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda bi, hi, qi: (bi, hi // rep, 0, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda bi, hi, qi: (bi, hi // rep, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name=_fwd_name("flash_transpose_fwd", diff, window),
    )(qt, kt, vt)
    return out, lse


def _fwd(q, k, v, causal, block_q, block_k):
    out, lse = _fwd_t(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                      jnp.swapaxes(v, 1, 2), causal, block_q, block_k)
    return jnp.swapaxes(out, 1, 2), lse


# =========================== backward kernels ===========================

def _dq_loop(q, do, lse, delta, load_kv, *, iq, block_q, block_k, scale,
             causal, seq_q, seq_k, seg_q=None, load_seg_k=None,
             load_bias=None, window=None):
    """Shared dQ recurrence (replays blocked logits from lse; bf16 dots,
    f32 accumulation). One body for the per-head and all-heads-block dQ
    kernels. load_kv(j) -> (k, v). Returns dq [block_q, d] f32.
    seg_q/load_seg_k: varlen segment ids; load_bias: additive bias
    blocks (see _online_softmax) — the bias replays into the logits so
    p matches forward."""
    d = q.shape[-1]
    off = seq_k - seq_q
    num_k_blocks = pl.cdiv(seq_k, block_k)
    segmented = seg_q is not None

    def make_body(masked):
        def body(j, dq):
            k, v = load_kv(j)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if load_bias is not None:
                s = s + load_bias(j)
            if masked:
                q_ids = iq * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_ids = j * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                valid = k_ids < seq_k
                if causal:
                    valid = jnp.logical_and(valid, q_ids + off >= k_ids)
                if window is not None:
                    valid = jnp.logical_and(valid,
                                            q_ids + off - k_ids < window)
                if segmented:
                    seg_k = load_seg_k(j)
                    valid = jnp.logical_and(
                        valid, seg_q == seg_k.reshape(1, block_k))
                s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            return dq + jax.lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return body

    dq0 = jnp.zeros((block_q, d), jnp.float32)
    if causal:
        num_full = jnp.clip((iq * block_q + off + 1) // block_k,
                            0, num_k_blocks)
        num_iters = jnp.clip(pl.cdiv((iq + 1) * block_q + off, block_k),
                             num_full, num_k_blocks)
        if segmented:
            dq = jax.lax.fori_loop(0, num_iters, make_body(True), dq0)
        elif window is not None:   # the forward's three runs
            lo, full_lo, full_hi = _band_k_blocks(
                iq, block_q, block_k, off, window, num_full, num_iters)
            dq = jax.lax.fori_loop(lo, full_lo, make_body(True), dq0)
            dq = jax.lax.fori_loop(full_lo, full_hi, make_body(False), dq)
            dq = jax.lax.fori_loop(full_hi, num_iters, make_body(True), dq)
        else:
            dq = jax.lax.fori_loop(0, num_full, make_body(False), dq0)
            dq = jax.lax.fori_loop(num_full, num_iters, make_body(True),
                                   dq)
    else:
        dq = jax.lax.fori_loop(0, num_k_blocks,
                               make_body(segmented or
                                         seq_k % block_k != 0), dq0)
    return dq


def _bwd_dq_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref, *,
                   scale, block_k, causal, seq_q, seq_k, window=None):
    block_q = q_ref.shape[0]
    delta = jnp.sum(do_ref[:].astype(jnp.float32) *
                    o_ref[:].astype(jnp.float32), axis=1, keepdims=True)
    dq = _dq_loop(
        q_ref[:], do_ref[:], lse_ref[:], delta,
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(2), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        window=window)
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _bwd_dq_kernel_bias(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                        do_ref, dq_ref, *, scale, block_k, causal, seq_q,
                        seq_k):
    block_q = q_ref.shape[0]
    delta = jnp.sum(do_ref[:].astype(jnp.float32) *
                    o_ref[:].astype(jnp.float32), axis=1, keepdims=True)
    dq = _dq_loop(
        q_ref[:], do_ref[:], lse_ref[:], delta,
        lambda j: (k_ref[pl.ds(j * block_k, block_k), :],
                   v_ref[pl.ds(j * block_k, block_k), :]),
        iq=pl.program_id(2), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        load_bias=lambda j: b_ref[:, pl.ds(j * block_k, block_k)]
        .astype(jnp.float32))
    dq_ref[:] = dq.astype(dq_ref.dtype)


def _causal_q_blocks(jk, block_q, block_k, off, num_iters):
    """The q blocks that see KV block `jk` under the bottom-right
    aligned causal mask, as (start_block, first_full): KV block jk is
    seen by q rows >= jk*block_k - off; q blocks from first_full on
    (min q_id + off >= max k_id) are fully unmasked, those between run
    the masked body."""
    start_block = jnp.clip((jk * block_k - off) // block_q, 0, num_iters)
    first_full = -(-((jk + 1) * block_k - 1 - off) // block_q)  # ceil
    return start_block, jnp.clip(first_full, start_block, num_iters)


def _dkv_loop(k, v, load_q, *, jk, block_q, block_k, scale, causal,
              seq_q, seq_k, seg_k=None, load_seg_q=None, load_bias=None,
              window=None):
    """Shared dK/dV recurrence. One body for the per-head and
    all-heads-block dKV kernels. load_q(i) -> (q, do, o, lse) blocks.
    Returns (dk, dv), each [block_k, d] f32.
    seg_k/load_seg_q: varlen segment ids; load_bias(i) -> [block_q,
    block_k] additive bias (see _online_softmax)."""
    d = k.shape[-1]
    off = seq_k - seq_q
    segmented = seg_k is not None

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q, do, o, lse = load_q(i)
            delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                            axis=1, keepdims=True)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if load_bias is not None:
                s = s + load_bias(i)
            if masked:
                q_ids = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 0)
                k_ids = jk * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_q, block_k), 1)
                valid = q_ids < seq_q
                if causal:
                    valid = jnp.logical_and(valid, q_ids + off >= k_ids)
                if window is not None:
                    valid = jnp.logical_and(valid,
                                            q_ids + off - k_ids < window)
                if segmented:
                    seg_q = load_seg_q(i)  # [block_q, 1]
                    valid = jnp.logical_and(
                        valid, seg_q == seg_k.reshape(1, block_k))
                s = jnp.where(valid, s, NEG_INF)
            p = jnp.exp(s - lse)
            pc = p.astype(do.dtype)
            dv_new = dv + jax.lax.dot_general(
                pc, do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = (p * (dp - delta) * scale).astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk_new, dv_new
        return body

    num_iters = pl.cdiv(seq_q, block_q)
    carry = (jnp.zeros((block_k, d), jnp.float32),
             jnp.zeros((block_k, d), jnp.float32))
    tail_masked = segmented or seq_q % block_q != 0
    if causal:
        # (Segmented mode: boundaries cut anywhere, all blocks masked.)
        start_block, first_full = _causal_q_blocks(jk, block_q, block_k,
                                                   off, num_iters)
        if window is not None:
            first_full, full_hi, end = _band_q_blocks(
                jk, block_q, block_k, off, window, start_block, first_full,
                num_iters)
            carry = jax.lax.fori_loop(start_block, first_full,
                                      make_body(True), carry)
            carry = jax.lax.fori_loop(first_full, full_hi,
                                      make_body(tail_masked), carry)
            return jax.lax.fori_loop(full_hi, end, make_body(True), carry)
        carry = jax.lax.fori_loop(start_block, first_full, make_body(True),
                                  carry)
        return jax.lax.fori_loop(first_full, num_iters,
                                 make_body(tail_masked), carry)
    return jax.lax.fori_loop(0, num_iters, make_body(tail_masked), carry)


def _fused_bwd_loop(k, v, load_q, add_dq, *, jk, block_q, block_k, scale,
                    causal, seq_q, seq_k, window=None):
    """The fused backward recurrence: ONE replay of the logits per
    (q block, KV block) makes all three gradients, where _dq_loop and
    _dkv_loop each replay them.  Walks the q blocks that see KV block
    `jk` (the same start_block / first_full split as _dkv_loop) on the
    TRANSPOSED tile sT = k·qT [block_k, block_q]: the q index lies
    along lanes, so lse and delta are lane-dense rows, dV += pT·do and
    dK += dsT·q are plain contractions and only dQ contracts over the
    transposed side (one tile transpose where _dkv_loop has two).
    load_q(i) -> (q, do, lse_row, delta_row), rows [1, block_q] f32;
    add_dq(i, x) adds x [block_q, d] f32 into q block i of the caller's
    dQ accumulator.  The caller walks KV blocks in ascending order, so
    dQ sums in _dq_loop's order with _dq_loop's dots: bit-identical to
    the split pair on the rows that are real.  Returns (dk, dv), each
    [block_k, d] f32."""
    d = k.shape[-1]
    off = seq_k - seq_q
    kv_tail = seq_k % block_k != 0

    def make_body(masked):
        def body(i, carry):
            dk, dv = carry
            q, do, lse, delta = load_q(i)
            st = jax.lax.dot_general(k, q, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            st = st * scale
            if masked:
                k_ids = jk * block_k + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 0)
                q_ids = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, (block_k, block_q), 1)
                valid = q_ids < seq_q
                if kv_tail:  # dQ must not see padded key rows
                    valid = jnp.logical_and(valid, k_ids < seq_k)
                if causal:
                    valid = jnp.logical_and(valid, q_ids + off >= k_ids)
                if window is not None:
                    valid = jnp.logical_and(valid,
                                            q_ids + off - k_ids < window)
                st = jnp.where(valid, st, NEG_INF)
            pt = jnp.exp(st - lse)
            dv_new = dv + jax.lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())),
                                      preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta) * scale).astype(q.dtype)
            dk_new = dk + jax.lax.dot_general(
                dst, q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            add_dq(i, jax.lax.dot_general(
                dst, k, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
            return dk_new, dv_new
        return body

    num_iters = pl.cdiv(seq_q, block_q)
    carry = (jnp.zeros((block_k, d), jnp.float32),
             jnp.zeros((block_k, d), jnp.float32))
    tail_masked = kv_tail or seq_q % block_q != 0
    if causal:
        start_block, first_full = _causal_q_blocks(jk, block_q, block_k,
                                                   off, num_iters)
        if window is not None:
            first_full, full_hi, end = _band_q_blocks(
                jk, block_q, block_k, off, window, start_block, first_full,
                num_iters)
            carry = jax.lax.fori_loop(start_block, first_full,
                                      make_body(True), carry)
            carry = jax.lax.fori_loop(first_full, full_hi,
                                      make_body(tail_masked), carry)
            return jax.lax.fori_loop(full_hi, end, make_body(True), carry)
        carry = jax.lax.fori_loop(start_block, first_full, make_body(True),
                                  carry)
        return jax.lax.fori_loop(first_full, num_iters,
                                 make_body(tail_masked), carry)
    return jax.lax.fori_loop(0, num_iters, make_body(tail_masked), carry)


def _fused_prologue(first_kv, dq_acc, fill_delta, n_q_blocks):
    """At a batch row's first KV block: zero the sequence-long dQ
    accumulator and fill the lane-dense delta scratch, one q block at a
    time (delta = rowsum(do·o) as the split kernels compute it, then
    relaid; it is read back for every later KV block)."""
    @pl.when(first_kv)
    def _():
        dq_acc[...] = jnp.zeros(dq_acc.shape, dq_acc.dtype)

        def body(i, _):
            fill_delta(i)
            return 0
        jax.lax.fori_loop(0, n_q_blocks, body, 0)


def _delta_row(do, o):
    """delta = rowsum(do·o) of one q block, as a lane-dense row."""
    return _col_to_row(jnp.sum(do.astype(jnp.float32) *
                               o.astype(jnp.float32), axis=1,
                               keepdims=True))


def _bwd_fused_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dq_ref,
                      dk_ref, dv_ref, dq_acc, delta_ref, *, scale,
                      block_q, causal, seq_q, seq_k, rep, window=None):
    """Grid (b, h_kv, kv_blocks), KV axis sequential.  q/o/do/dq refs
    carry the KV head's GROUP of `rep` query heads ([rep, seq_q, d]);
    lse_ref [rep, n_q_blocks, 1, block_q] f32, lane-dense; k/v/dk/dv
    refs [block_k, d].  dq's block index does not depend on the KV axis, so
    it stays resident: dq_acc [rep, seq_q, d] f32 is zeroed at the first
    KV block and written (cast once) at the last; delta_ref is the
    lane-dense delta scratch, shaped like lse_ref."""
    block_k = k_ref.shape[0]
    jk = pl.program_id(2)

    def rows(i):
        return pl.ds(i * block_q, block_q)

    def fill_delta(i):
        for r in range(rep):
            delta_ref[r, i] = _delta_row(do_ref[r, rows(i), :],
                                         o_ref[r, rows(i), :])

    _fused_prologue(jk == 0, dq_acc, fill_delta, lse_ref.shape[1])
    k = k_ref[:]
    v = v_ref[:]
    dk_acc = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv_acc = jnp.zeros((block_k, v.shape[-1]), jnp.float32)
    for r in range(rep):
        def add_dq(i, x, r=r):
            dq_acc[r, rows(i), :] += x

        dk, dv = _fused_bwd_loop(
            k, v,
            lambda i, r=r: (q_ref[r, rows(i), :], do_ref[r, rows(i), :],
                            lse_ref[r, i], delta_ref[r, i]),
            add_dq, jk=jk, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, seq_q=seq_q, seq_k=seq_k, window=window)
        dk_acc = dk_acc + dk
        dv_acc = dv_acc + dv
    dk_ref[:] = dk_acc.astype(dk_ref.dtype)
    dv_ref[:] = dv_acc.astype(dv_ref.dtype)

    @pl.when(jk == pl.num_programs(2) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel_bias(q_ref, k_ref, v_ref, b_ref, o_ref, lse_ref,
                         do_ref, dk_ref, dv_ref, *, scale, block_q,
                         causal, seq_q, seq_k):
    # b_ref: [seq_q, block_k] f32 bias column-band for this kv block
    block_k = k_ref.shape[0]
    dk, dv = _dkv_loop(
        k_ref[:], v_ref[:],
        lambda i: (q_ref[pl.ds(i * block_q, block_q), :],
                   do_ref[pl.ds(i * block_q, block_q), :],
                   o_ref[pl.ds(i * block_q, block_q), :],
                   lse_ref[pl.ds(i * block_q, block_q), :]),
        jk=pl.program_id(2), block_q=block_q, block_k=block_k,
        scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
        load_bias=lambda i: b_ref[pl.ds(i * block_q, block_q), :]
        .astype(jnp.float32))
    dk_ref[:] = dk.astype(dk_ref.dtype)
    dv_ref[:] = dv.astype(dv_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref, dk_ref,
                    dv_ref, *, scale, block_q, causal, seq_q, seq_k, rep,
                    window=None):
    """Grid (b, h_kv, kv_blocks). q/do/o refs carry the KV head's GROUP
    of `rep` query heads ([rep, seq_q, d]; lse [rep, seq_q, 1]): dK/dV
    for a KV head sum the contributions of every query head it serves
    (rep == 1 is plain MHA)."""
    block_k = k_ref.shape[0]
    jk = pl.program_id(2)
    k = k_ref[:]
    v = v_ref[:]
    dk_acc = jnp.zeros((block_k, k.shape[-1]), jnp.float32)
    dv_acc = jnp.zeros((block_k, v.shape[-1]), jnp.float32)
    for r in range(rep):
        dk, dv = _dkv_loop(
            k, v,
            lambda i, r=r: (q_ref[r, pl.ds(i * block_q, block_q), :],
                            do_ref[r, pl.ds(i * block_q, block_q), :],
                            o_ref[r, pl.ds(i * block_q, block_q), :],
                            lse_ref[r, pl.ds(i * block_q, block_q), :]),
            jk=jk, block_q=block_q, block_k=block_k,
            scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k,
            window=window)
        dk_acc = dk_acc + dk
        dv_acc = dv_acc + dv
    dk_ref[:] = dk_acc.astype(dk_ref.dtype)
    dv_ref[:] = dv_acc.astype(dv_ref.dtype)


def _count_backward(tier, fused):
    """`flash.backward{tier,kind}`: which backward a core's VJP traced,
    the fused kernel or the split dq + dkdv pair (a cold block search's
    candidate traces count too)."""
    _metrics.inc("flash.backward", tier=tier,
                 kind="fused" if fused else "split")


def _bwd_t(qt, kt, vt, ot, lse, dot, causal, block_q, block_k,
           seq_q_real=None, seq_k_real=None, window=None):
    """Backward on head-major [B,H,S,D] operands; returns dq/dk/dv in the
    same head-major layout. The custom VJP saves residuals head-major
    (the forward already computed them), so backward only transposes the
    incoming cotangent and the outgoing grads — half the transpose HBM
    traffic of re-deriving all five operands from [B,S,H,D]
    (PERF.md: ~25 ms/step of transposes at the bench shape).  lse is
    lane-dense, as _fwd_t(diff=True) returns it.
    seq_*_real: logical lengths for padded arrays (see _fwd_t) — kernels
    bound loops/masks on the real lengths, so padded key rows contribute
    nothing and the caller slices padded grad rows off.

    One fused kernel (_bwd_fused_kernel) wherever the KV head's group of
    sequence-long q/o/do/dq fits the kernel's VMEM by _t_vmem_bytes;
    the split dq + dkdv pair past that."""
    b, h, sq, d = qt.shape
    h_kv = kt.shape[1]
    assert h % h_kv == 0, (h, h_kv)
    rep = h // h_kv
    sk = kt.shape[2]
    sq_r = seq_q_real or sq
    sk_r = seq_k_real or sk
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)

    fused = _t_vmem_bytes(sq, sk, rep, d, qt.dtype.itemsize, block_q,
                          block_k, fused=True) <= _T_VMEM_LIMIT
    _count_backward("transpose", fused)
    if fused:
        group_q = pl.BlockSpec((None, rep, sq, d),
                               lambda bi, hi, j: (bi, hi, 0, 0))
        n_q = sq // block_q
        group_lse = pl.BlockSpec((None, rep, n_q, 1, block_q),
                                 lambda bi, hi, j: (bi, hi, 0, 0, 0))
        kv_spec = pl.BlockSpec((None, None, block_k, d),
                               lambda bi, hi, j: (bi, hi, j, 0))
        return pl.pallas_call(
            functools.partial(_bwd_fused_kernel, **_win(dict(
                scale=scale, block_q=block_q, causal=causal, seq_q=sq_r,
                seq_k=sk_r, rep=rep), window)),
            grid=(b, h_kv, pl.cdiv(sk, block_k)),
            in_specs=[group_q, kv_spec, kv_spec, group_q, group_lse,
                      group_q],
            out_specs=[group_q, kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
                       jax.ShapeDtypeStruct((b, h_kv, sk, d), kt.dtype),
                       jax.ShapeDtypeStruct((b, h_kv, sk, d), vt.dtype)],
            scratch_shapes=[pltpu.VMEM((rep, sq, d), jnp.float32),
                            pltpu.VMEM((rep, n_q, 1, block_q),
                                       jnp.float32)],
            interpret=_interpret(),
            compiler_params=_compiler_params(),
            name=_bwd_name("flash_transpose_bwd", window),
        )(qt, kt, vt, ot, lse, dot)

    with jax.named_scope(LAYOUT_SCOPE):
        lse = lse.reshape(b, h, sq, 1)
    # the dK/dV kernel keeps its group's sequence-long q/o/do and the
    # lane-padded lse column resident: past the compiler's default it
    # asks for what it needs (sequences of 8192 at head size 128)
    dkdv_vmem = _t_dkdv_vmem_bytes(sq, rep, d, qt.dtype.itemsize, block_q,
                                   block_k)
    if dkdv_vmem <= _T_VMEM_LIMIT:
        dkdv_vmem = None
    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda bi, hi, i: (bi, hi, i, 0))
    k_spec_full = pl.BlockSpec((None, None, sk, d),
                               lambda bi, hi, i: (bi, hi // rep, 0, 0))
    lse_spec = pl.BlockSpec((None, None, block_q, 1),
                            lambda bi, hi, i: (bi, hi, i, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, **_win(dict(
            scale=scale, block_k=block_k, causal=causal, seq_q=sq_r,
            seq_k=sk_r), window)),
        grid=(b, h, pl.cdiv(sq, block_q)),
        in_specs=[q_spec, k_spec_full, k_spec_full, q_spec, lse_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name=_bwd_name("flash_transpose_dq", window),
    )(qt, kt, vt, ot, lse, dot)

    # dK/dV: grid over KV heads; each instance reads its whole group of
    # `rep` query heads (block dim1 = rep, block-unit index hi)
    group_q = pl.BlockSpec((None, rep, sq, d),
                           lambda bi, hi, j: (bi, hi, 0, 0))
    group_lse = pl.BlockSpec((None, rep, sq, 1),
                             lambda bi, hi, j: (bi, hi, 0, 0))
    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, j: (bi, hi, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, **_win(dict(
            scale=scale, block_q=block_q, causal=causal, seq_q=sq_r,
            seq_k=sk_r, rep=rep), window)),
        grid=(b, h_kv, pl.cdiv(sk, block_k)),
        in_specs=[group_q, kv_spec, kv_spec, group_q, group_lse, group_q],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h_kv, sk, d), kt.dtype),
                   jax.ShapeDtypeStruct((b, h_kv, sk, d), vt.dtype)],
        interpret=_interpret(),
        compiler_params=_compiler_params(dkdv_vmem),
        name=_bwd_name("flash_transpose_dkdv", window),
    )(qt, kt, vt, ot, lse, dot)

    return dq, dk, dv


def _bwd(q, k, v, out, lse, do, causal, block_q, block_k):
    b, sq, h, _ = q.shape
    bq = _pick_block(sq, block_q)
    dq, dk, dv = _bwd_t(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), jnp.swapaxes(out, 1, 2),
                        lse.reshape(b, h, sq // bq, 1, bq),
                        jnp.swapaxes(do, 1, 2), causal, block_q, block_k)
    return (jnp.swapaxes(dq, 1, 2), jnp.swapaxes(dk, 1, 2),
            jnp.swapaxes(dv, 1, 2))


# =========================== public entry ===========================

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_core(q, k, v, causal, block_q, block_k, seq_q_real=None,
                seq_k_real=None, window=None):
    qt, kt, vt = _layout_swap(q, k, v)
    out, _ = _fwd_t(qt, kt, vt, causal, block_q, block_k,
                    seq_q_real, seq_k_real, window=window)
    return _layout_swap(out)[0]


def _flash_core_fwd(q, k, v, causal, block_q, block_k, seq_q_real=None,
                    seq_k_real=None, window=None):
    # residuals saved HEAD-MAJOR: forward already computed the [B,H,S,D]
    # transposes, so backward reuses them instead of re-transposing all
    # five operands from [B,S,H,D] — only the cotangent (in) and the three
    # grads (out) cross layouts in the backward pass
    qt, kt, vt = _layout_swap(q, k, v)
    out_t, lse = _kept(*_fwd_t(qt, kt, vt, causal, block_q, block_k,
                               seq_q_real, seq_k_real, diff=True,
                               window=window))
    return _layout_swap(out_t)[0], (qt, kt, vt, out_t, lse)


def _flash_core_bwd(causal, block_q, block_k, seq_q_real, seq_k_real,
                    window, res, g):
    qt, kt, vt, ot, lse = res
    dq, dk, dv = _bwd_t(qt, kt, vt, ot, lse, _layout_swap(g)[0],
                        causal, block_q, block_k, seq_q_real, seq_k_real,
                        window)
    return _layout_swap(dq, dk, dv)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


# ================= flat-native kernels ([B, S, H*D] views) =================
#
# The deployed Mosaic accepts STATIC 64-lane slices of a flat [*, H*D]
# block as MXU dot operands and as stores (compile-proven on-chip),
# which makes head-major arrays unnecessary ALTOGETHER:
#   - q/k/v/o and all gradients stay [B, S, H*D] — the trailing dims
#     (S, 768) are tile-aligned, so none of the 2-2.7x T(8,128) padding
#     that [B,H,S,D]/[B,S,H,D] 4-D arrays with D=64 pay in HBM;
#   - zero transposes and zero relayout copies: XLA sees the same flat
#     layout the surrounding GEMMs use (the [B,S,3,H,D] reshape/unbind
#     around the qkv projection is a free bitcast);
#   - no layout-pinned custom-call boundary for XLA to insert scoped-
#     stack transpose copies around.
# Heads walk a static Python loop; per-head operands are lane slices
# hh*D:(hh+1)*D. The shared recurrences (_online_softmax, _dq_loop,
# _dkv_loop, _fused_bwd_loop) are reused as-is — numerics identical to
# the transpose core.


def _flat_compiler_params():
    # vmem_limit_bytes: the flat kernels keep all heads' loop
    # intermediates on the Mosaic stack (statically unrolled head walk)
    # and need ~20-35 MiB at training block sizes — above the 16 MiB
    # default but real headroom on v5e's 128 MiB VMEM. Raising the limit
    # PER KERNEL (instead of the program-wide
    # xla_tpu_scoped_vmem_limit_kib flag) leaves XLA's own ops on the
    # default budget — a program-wide raise makes large fusion/transpose
    # ops pick >40 MiB scoped strategies that then fail allocation
    # (observed on-chip).
    if _interpret():
        return None
    return _TPUCompilerParams(
        dimension_semantics=(_PLL, _ARB),
        vmem_limit_bytes=_FLAT_VMEM_LIMIT)


def _fwd_kernel_flat(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale,
                     block_k, causal, seq_q, seq_k, n_heads, rep, d,
                     lse_rows=False):
    # q_ref/o_ref: [block_q, H*D]; k_ref/v_ref: [seq_k, Hkv*D];
    # lse_ref: [H, block_q, 1], or lane-dense [H, block_q] (lse_rows)
    block_q = q_ref.shape[0]
    iq = pl.program_id(1)
    for hh in range(n_heads):
        lo = (hh // rep) * d
        out, lse = _online_softmax(
            q_ref[:, hh * d:(hh + 1) * d],
            lambda j, lo=lo: (
                k_ref[pl.ds(j * block_k, block_k), lo:lo + d],
                v_ref[pl.ds(j * block_k, block_k), lo:lo + d]),
            iq=iq, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, seq_q=seq_q, seq_k=seq_k)
        o_ref[:, hh * d:(hh + 1) * d] = out.astype(o_ref.dtype)
        lse = lse.astype(jnp.float32)
        if lse_rows:
            lse_ref[hh:hh + 1, :] = _col_to_row(lse)
        else:
            lse_ref[hh] = lse


def _fwd_flat(q, k, v, h, causal, block_q, block_k, diff=False):
    """Forward on flat [B,Sq,H*D] q and [B,Sk,Hkv*D] k/v.
    Returns (out [B,Sq,H*D], lse [B,H,Sq,1]); under differentiation
    (diff) lse comes lane-dense, one row a head and q block:
    [B,Sq/block_q,H,block_q], as _bwd_flat reads it."""
    b, sq, hd = q.shape
    d = hd // h
    sk, hkvd = k.shape[1], k.shape[2]
    h_kv = hkvd // d
    rep = h // h_kv
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    if diff:
        lse_spec = pl.BlockSpec((None, None, h, block_q),
                                lambda bi, qi: (bi, qi, 0, 0))
        lse_shape = (b, sq // block_q, h, block_q)
    else:
        lse_spec = pl.BlockSpec((None, h, block_q, 1),
                                lambda bi, qi: (bi, 0, qi, 0))
        lse_shape = (b, h, sq, 1)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_flat, scale=scale, block_k=block_k,
                          causal=causal, seq_q=sq, seq_k=sk, n_heads=h,
                          rep=rep, d=d, lse_rows=diff),
        grid=(b, pl.cdiv(sq, block_q)),
        in_specs=[
            pl.BlockSpec((None, block_q, hd),
                         lambda bi, qi: (bi, qi, 0)),
            pl.BlockSpec((None, sk, hkvd), lambda bi, qi: (bi, 0, 0)),
            pl.BlockSpec((None, sk, hkvd), lambda bi, qi: (bi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, hd),
                         lambda bi, qi: (bi, qi, 0)),
            lse_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
            jax.ShapeDtypeStruct(lse_shape, jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_flat_compiler_params(),
        name=_fwd_name("flash_flat_fwd", diff),
    )(q, k, v)
    return out, lse


def _bwd_dq_kernel_flat(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                        dq_ref, *, scale, block_k, causal, seq_q, seq_k,
                        n_heads, rep, d):
    block_q = q_ref.shape[0]
    iq = pl.program_id(1)
    for hh in range(n_heads):
        lo = (hh // rep) * d
        sl = slice(hh * d, (hh + 1) * d)
        do = do_ref[:, sl]
        delta = jnp.sum(do.astype(jnp.float32) *
                        o_ref[:, sl].astype(jnp.float32),
                        axis=1, keepdims=True)
        dq = _dq_loop(
            q_ref[:, sl], do, lse_ref[hh], delta,
            lambda j, lo=lo: (
                k_ref[pl.ds(j * block_k, block_k), lo:lo + d],
                v_ref[pl.ds(j * block_k, block_k), lo:lo + d]),
            iq=iq, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, seq_q=seq_q, seq_k=seq_k)
        dq_ref[:, sl] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel_flat(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                         dk_ref, dv_ref, *, scale, block_q, causal,
                         seq_q, seq_k, n_heads, rep, d):
    block_k = k_ref.shape[0]
    jk = pl.program_id(1)
    h_kv = n_heads // rep
    for hkv in range(h_kv):
        ksl = slice(hkv * d, (hkv + 1) * d)
        k = k_ref[:, ksl]
        v = v_ref[:, ksl]
        dk_acc = jnp.zeros((block_k, d), jnp.float32)
        dv_acc = jnp.zeros((block_k, d), jnp.float32)
        for r in range(rep):
            hh = hkv * rep + r
            qsl = slice(hh * d, (hh + 1) * d)
            dk, dv = _dkv_loop(
                k, v,
                lambda i, qsl=qsl, hh=hh: (
                    q_ref[pl.ds(i * block_q, block_q), qsl],
                    do_ref[pl.ds(i * block_q, block_q), qsl],
                    o_ref[pl.ds(i * block_q, block_q), qsl],
                    lse_ref[hh, pl.ds(i * block_q, block_q), :]),
                jk=jk, block_q=block_q, block_k=block_k, scale=scale,
                causal=causal, seq_q=seq_q, seq_k=seq_k)
            dk_acc = dk_acc + dk
            dv_acc = dv_acc + dv
        dk_ref[:, ksl] = dk_acc.astype(dk_ref.dtype)
        dv_ref[:, ksl] = dv_acc.astype(dv_ref.dtype)


def _bwd_fused_kernel_flat(q_ref, k_ref, v_ref, o_ref, lse_ref, do_ref,
                           dq_ref, dk_ref, dv_ref, dq_acc, delta_ref, *,
                           scale, block_q, causal, seq_q, seq_k, n_heads,
                           rep, d):
    """Grid (b, kv_blocks), KV axis sequential; the fused recurrence on
    flat operands (see _bwd_fused_kernel).  q/o/do/dq refs: [seq_q,
    H*D], dq resident over the KV axis; lse_ref [n_q_blocks, H, block_q]
    f32; k/v/dk/dv refs [block_k, Hkv*D]; dq_acc [seq_q, H*D] f32 and
    delta_ref (shaped like lse_ref) scratch.  Heads walk a static loop
    over 64-lane slices, as in the split flat kernels."""
    block_k = k_ref.shape[0]
    jk = pl.program_id(1)

    def rows(i):
        return pl.ds(i * block_q, block_q)

    def fill_delta(i):
        for hh in range(n_heads):
            sl = slice(hh * d, (hh + 1) * d)
            delta_ref[i, hh:hh + 1, :] = _delta_row(do_ref[rows(i), sl],
                                                    o_ref[rows(i), sl])

    _fused_prologue(jk == 0, dq_acc, fill_delta, lse_ref.shape[0])
    for hkv in range(n_heads // rep):
        ksl = slice(hkv * d, (hkv + 1) * d)
        k = k_ref[:, ksl]
        v = v_ref[:, ksl]
        dk_acc = jnp.zeros((block_k, d), jnp.float32)
        dv_acc = jnp.zeros((block_k, d), jnp.float32)
        for r in range(rep):
            hh = hkv * rep + r
            qsl = slice(hh * d, (hh + 1) * d)

            def add_dq(i, x, qsl=qsl):
                dq_acc[rows(i), qsl] += x

            dk, dv = _fused_bwd_loop(
                k, v,
                lambda i, qsl=qsl, hh=hh: (
                    q_ref[rows(i), qsl], do_ref[rows(i), qsl],
                    lse_ref[i, hh:hh + 1, :], delta_ref[i, hh:hh + 1, :]),
                add_dq, jk=jk, block_q=block_q, block_k=block_k,
                scale=scale, causal=causal, seq_q=seq_q, seq_k=seq_k)
            dk_acc = dk_acc + dk
            dv_acc = dv_acc + dv
        dk_ref[:, ksl] = dk_acc.astype(dk_ref.dtype)
        dv_ref[:, ksl] = dv_acc.astype(dv_ref.dtype)

    @pl.when(jk == pl.num_programs(1) - 1)
    def _():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _bwd_flat(q, k, v, out, lse, do, h, causal, block_q, block_k):
    """Backward companion of _fwd_flat: everything stays [B,S,H*D]; lse
    is lane-dense, as _fwd_flat(diff=True) returns it.  One fused kernel
    where _flat_vmem_bytes says the sequence-long dQ fits beside q/o/do,
    the split pair where only theirs does."""
    b, sq, hd = q.shape
    d = hd // h
    sk, hkvd = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    rep = hd // hkvd

    fused = _flat_vmem_bytes(sq, sk, h, h // rep, d, q.dtype.itemsize,
                           block_q, block_k,
                           fused=True) <= _FLAT_VMEM_LIMIT
    _count_backward("flat", fused)
    if fused:
        q_full = pl.BlockSpec((None, sq, hd), lambda bi, kj: (bi, 0, 0))
        n_q = sq // block_q
        lse_full = pl.BlockSpec((None, n_q, h, block_q),
                                lambda bi, kj: (bi, 0, 0, 0))
        kv_spec = pl.BlockSpec((None, block_k, hkvd),
                               lambda bi, kj: (bi, kj, 0))
        return pl.pallas_call(
            functools.partial(_bwd_fused_kernel_flat, scale=scale,
                              block_q=block_q, causal=causal, seq_q=sq,
                              seq_k=sk, n_heads=h, rep=rep, d=d),
            grid=(b, pl.cdiv(sk, block_k)),
            in_specs=[q_full, kv_spec, kv_spec, q_full, lse_full, q_full],
            out_specs=[q_full, kv_spec, kv_spec],
            out_shape=[jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
                       jax.ShapeDtypeStruct((b, sk, hkvd), k.dtype),
                       jax.ShapeDtypeStruct((b, sk, hkvd), v.dtype)],
            scratch_shapes=[pltpu.VMEM((sq, hd), jnp.float32),
                            pltpu.VMEM((n_q, h, block_q), jnp.float32)],
            interpret=_interpret(),
            compiler_params=_flat_compiler_params(),
            name=_bwd_name("flash_flat_bwd"),
        )(q, k, v, out, lse, do)

    with jax.named_scope(LAYOUT_SCOPE):
        lse = jnp.swapaxes(lse, 1, 2).reshape(b, h, sq, 1)
    q_spec = pl.BlockSpec((None, block_q, hd), lambda bi, qi: (bi, qi, 0))
    lse_spec = pl.BlockSpec((None, h, block_q, 1),
                            lambda bi, qi: (bi, 0, qi, 0))
    kv_full = pl.BlockSpec((None, sk, hkvd), lambda bi, qi: (bi, 0, 0))
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_flat, scale=scale,
                          block_k=block_k, causal=causal, seq_q=sq,
                          seq_k=sk, n_heads=h, rep=rep, d=d),
        grid=(b, pl.cdiv(sq, block_q)),
        in_specs=[q_spec, kv_full, kv_full, q_spec, lse_spec, q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, sq, hd), q.dtype),
        interpret=_interpret(),
        compiler_params=_flat_compiler_params(),
        name=_bwd_name("flash_flat_dq"),
    )(q, k, v, out, lse, do)

    q_full = pl.BlockSpec((None, sq, hd), lambda bi, kj: (bi, 0, 0))
    lse_full = pl.BlockSpec((None, h, sq, 1), lambda bi, kj: (bi, 0, 0, 0))
    kv_spec = pl.BlockSpec((None, block_k, hkvd),
                           lambda bi, kj: (bi, kj, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_flat, scale=scale,
                          block_q=block_q, causal=causal, seq_q=sq,
                          seq_k=sk, n_heads=h, rep=rep, d=d),
        grid=(b, pl.cdiv(sk, block_k)),
        in_specs=[q_full, kv_spec, kv_spec, q_full, lse_full, q_full],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, sk, hkvd), k.dtype),
                   jax.ShapeDtypeStruct((b, sk, hkvd), v.dtype)],
        interpret=_interpret(),
        compiler_params=_flat_compiler_params(),
        name=_bwd_name("flash_flat_dkdv"),
    )(q, k, v, out, lse, do)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_core_flat(q, k, v, causal, block_q, block_k):
    """Flat-native core: public [B,S,H,D] in/out, but every kernel
    operand rides an unpadded [B,S,H*D] view (free reshape). Zero
    transposes, zero relayouts, zero padded arrays. Numerics are the
    shared flash loops — identical to _flash_core."""
    b, sq, h, d = q.shape
    with jax.named_scope(LAYOUT_SCOPE):
        qf = q.reshape(b, sq, h * d)
        kf = k.reshape(b, k.shape[1], -1)
        vf = v.reshape(b, v.shape[1], -1)
    out, _ = _fwd_flat(qf, kf, vf, h, causal, block_q, block_k)
    with jax.named_scope(LAYOUT_SCOPE):
        return out.reshape(b, sq, h, d)


def _flash_core_flat_fwd(q, k, v, causal, block_q, block_k):
    b, sq, h, d = q.shape
    with jax.named_scope(LAYOUT_SCOPE):
        qf = q.reshape(b, sq, h * d)
        kf = k.reshape(b, k.shape[1], -1)
        vf = v.reshape(b, v.shape[1], -1)
    out, lse = _kept(*_fwd_flat(qf, kf, vf, h, causal, block_q, block_k,
                                diff=True))
    with jax.named_scope(LAYOUT_SCOPE):
        return out.reshape(b, sq, h, d), (qf, kf, vf, out, lse, h, d)


def _flash_core_flat_bwd(causal, block_q, block_k, res, g):
    qf, kf, vf, out, lse, h, d = res
    b, sq, hd = qf.shape
    with jax.named_scope(LAYOUT_SCOPE):
        gf = g.reshape(b, sq, hd)
    dq, dk, dv = _bwd_flat(qf, kf, vf, out, lse, gf, h, causal,
                           block_q, block_k)
    with jax.named_scope(LAYOUT_SCOPE):
        return (dq.reshape(b, sq, h, d),
                dk.reshape(b, kf.shape[1], -1, d),
                dv.reshape(b, vf.shape[1], -1, d))


_flash_core_flat.defvjp(_flash_core_flat_fwd, _flash_core_flat_bwd)


def _count_dispatch(tier: str, block_q, block_k, window=None) -> None:
    """`flash.dispatch{tier}` plus the blocks that tier runs with
    (`flash.blocks{tier,block_q,block_k}`) — trace-time counters.  A
    windowed call adds the label `window=<keys>` to both.  Inside a
    recomputed segment that holds the cores' marked residuals (`_kept`):
    `flash.recompute_kept{what=out_lse}`, one a call."""
    from ...distributed.recompute import keeping

    extra = {} if window is None else {"window": window}
    _metrics.inc("flash.dispatch", tier=tier, **extra)
    _metrics.inc("flash.blocks", tier=tier, block_q=block_q,
                 block_k=block_k, **extra)
    if keeping():
        _metrics.inc("flash.recompute_kept", what="out_lse")


def _gate_reject(gate: str, reason: str, q, k, blocks) -> None:
    """Counter + flight-recorder evidence for a kernel-tier gate reject:
    the silent-fallback class of failure (ADVICE r5) becomes a metric
    (`flash.gate_reject{gate,reason}`) and a ring event carrying the
    shapes and the blocks the gate actually estimated."""
    _metrics.inc("flash.gate_reject", gate=gate, reason=reason)
    _flight.record("flash.gate_reject", gate=gate, reason=reason,
                   q_shape=list(q.shape), kv_shape=list(k.shape),
                   blocks=list(blocks))


def _up(n, m):
    return -(-n // m) * m


def _stat_rows_bytes(lead, sub, bq) -> int:
    """VMEM of one lane-dense stats block [lead, sub, bq] f32 (lse or
    delta of the fused backward): sublanes pad to 8, lanes to 128."""
    return lead * _up(sub, 8) * _up(bq, 128) * 4


def _flat_vmem_bytes(sq, sk, h, h_kv, d, esz, bq, bk, fused=False) -> int:
    """Scoped-VMEM estimate of the flat kernels at blocks (bq, bk): the
    larger of the forward (full K+V per batch row) and the backward's
    KV-grid kernel, which keeps full-sequence q/o/do resident for the
    head walk.  Pipelined operands count twice (double buffering); the
    f32 logits-sized temporaries (s, p, dp, ds) count once.  The split
    pair's dKV kernel holds the column lse, lane-padded.  Checked
    against what the v5e compiler accepts and refuses at
    [32,1024,12,64] bf16 (tests/test_chip_compile.py): (512,512) and
    (256,512) compile, the backward at (512,1024) and (1024,1024) is
    RESOURCE_EXHAUSTED.

    This, the split pair's estimate, is what the flat gate and its
    candidate list hold to _FLAT_VMEM_LIMIT: the tier's reach is the
    split pair's.  fused=True describes the flat tier's fused
    backward, which holds lane-dense lse and delta instead, plus the
    sequence-long dQ (its output block and the f32 accumulator);
    _bwd_flat runs it inside that reach where it fits (19.4–20.2 MiB by
    the compiler at [32,1024,12,64] (512,512), 23.7 here).  It is no
    gate: at [16,2048,12,64] it reads 33.97 MiB for (256,256), under
    the limit, and the chip's compiler refuses that kernel."""
    fwd = (2 * (2 * sk * h_kv * d + 2 * bq * h * d) * esz
           + 2 * bq * bk * 4)
    bwd = (2 * 3 * sq * h * d * esz       # q, o, do
           + 2 * 4 * bk * h_kv * d * esz)  # k, v, dk, dv blocks
    if fused:
        bwd += (sq * h * d * (2 * esz + 4)          # dq block + f32 acc
                + 3 * _stat_rows_bytes(sq // bq, h, bq)  # lse (x2), delta
                + _fused_tile_bytes(bq, bk, esz))
    else:
        bwd += (2 * h * sq * 128 * 4      # lse [h, sq, 1] f32, lanes pad to 128
                + 4 * bq * bk * 4)        # s, p, dp, ds
    return max(fwd, bwd)


def _fused_tile_bytes(bq, bk, esz) -> int:
    """The fused backward's logits-sized temporaries: sT and dpT in f32
    and one operand-dtype tile, not four f32 tiles — the v5e compiler's
    footprint grows by 8.1–10 bytes a logit from (512, 512) to
    (1024, 1024) blocks at [16,2048,12,64] bf16 (read by lowering
    vmem_limit_bytes until the compile is refused, PERF.md PR 27)."""
    return (8 + esz) * bq * bk


# scoped VMEM of a kernel that sets no limit of its own (the transpose
# core's): the compiler's default on v5e
_T_VMEM_LIMIT = 16 * 1024 * 1024


def _t_vmem_bytes(sq, sk, rep, d, esz, bq, bk, biased=False,
                  fused=False) -> int:
    """Scoped-VMEM estimate of the transpose core at blocks (bq, bk).

    fused=False, the forward and the split pair, which the block search
    holds to 12 MB (_tuned_blocks): f32 logits block (s and p live
    together) + full K/V + q/o/acc. GQA: the grouped dK/dV kernel
    additionally keeps rep x seq_q x d of q/o/do resident (block-size
    independent, but it eats the same budget the logits compete for).
    Biased kernels hold an f32 bias band: [bq, sk] (fwd/dQ) or [sq, bk]
    (dKV) — the larger.

    fused=True, the fused backward, which _bwd_t holds to _T_VMEM_LIMIT:
    one KV head's group of `rep` query heads keeps sequence-long q/o/do,
    the dQ block and its f32 accumulator resident (head size pads to
    128 lanes), beside the k/v/dk/dv blocks, the lane-dense lse and
    delta and the logits-sized tiles (_fused_tile_bytes).  Against the
    compiler at [16,2048,12,64] bf16: 8.7 / 12.2 / 17.2 MiB here for
    (512,512) / (512,1024) / (1024,1024), 8.0–8.3 / 12.0–12.3 /
    15.0–15.3 MiB there."""
    if not fused:
        group = 3 * rep * sq * d * esz if rep > 1 else 0
        bias_band = max(bq * sk, sq * bk) * 4 if biased else 0
        return (2 * bq * bk * 4 + 2 * sk * d * esz + 2 * bq * d * esz
                + bq * d * 4 + group + bias_band)
    group = rep * sq * _up(d, 128)
    return (2 * 3 * group * esz            # q, o, do
            + group * (2 * esz + 4)        # dq block + f32 acc
            + 2 * 4 * bk * _up(d, 128) * esz
            + 3 * _stat_rows_bytes(rep * (sq // bq), 1, bq)
            + _fused_tile_bytes(bq, bk, esz))


def _t_dkdv_vmem_bytes(sq, rep, d, esz, bq, bk) -> int:
    """Scoped VMEM the transpose core's split dK/dV kernel asks for: its
    KV head's group of sequence-long q, o, do (pipelined: twice) and the
    lse column, whose every value pads to 128 lanes, beside the
    k/v/dk/dv blocks and four logits-sized f32 tiles, plus a sixth for
    what the estimate leaves out.  At [2,8192,32,128] bf16 (512, 512)
    the v5e compiler counts 21.0 MiB where this says 26.5; a window
    changes none of it (the band bounds the loop, not the blocks)."""
    dp = _up(d, 128)
    need = (2 * 3 * rep * sq * dp * esz + 2 * rep * sq * 128 * 4
            + 2 * 4 * bk * dp * esz + 4 * bq * bk * 4)
    return need + need // 6


def _flat_static_ok(q, k) -> bool:
    """Block-INDEPENDENT flat eligibility: lane alignment — the flat
    kernels slice per-head lane windows out of an [*, H*D] block and
    were real-compile-proven only with the flat width a multiple of the
    128-lane tile — AND per-head slice width ``d % 64 == 0`` (the only
    compile-proven head width; off-64 widths shape-cast inside the lane
    slice and the deployed Mosaic rejects them).  The dispatch site
    checks this BEFORE layout-tagged block tuning, so an ineligible
    shape never launches an autotune search timing the flat core it can
    never run.  Rejects surface through the flight recorder."""
    h, d = q.shape[2], q.shape[3]
    h_kv = k.shape[2]
    if (h * d) % 128 != 0 or (h_kv * d) % 128 != 0:
        _gate_reject("flat", "lane_align", q, k, ())
        return False
    if d % 64 != 0:
        _gate_reject("flat", "head_width", q, k, ())
        return False
    return True


def _flat_native_ok(q, k, block_q=512, block_k=512) -> bool:
    """The flat tier's one gate: the block-independent part
    (_flat_static_ok, which counts nothing where it passes), then VMEM
    feasibility — past the per-kernel limit the transpose core
    (block-sliced K/V) is the safe path.

    block_q/block_k are the blocks that will REALLY run (the dispatch
    site passes the tuned values), resolved through _pick_block exactly
    as the kernels will resolve them."""
    if not _flat_static_ok(q, k):
        return False
    b, sq, h, d = q.shape
    sk, h_kv = k.shape[1], k.shape[2]
    if sq % 8 != 0 or sk % 8 != 0:
        # off-8 lengths run padded through the transpose core (the
        # dispatch pads before gating); a direct probe gets False, not
        # the _pick_block ValueError
        return False
    bq = _pick_block(sq, block_q)
    bk = _pick_block(sk, block_k)
    if _flat_vmem_bytes(sq, sk, h, h_kv, d, q.dtype.itemsize, bq,
                        bk) > _FLAT_VMEM_LIMIT:
        _gate_reject("flat", "vmem", q, k, (bq, bk))
        return False
    return True


# ===================== biased (additive-mask) core =====================

def _bias_idx(bias_shape, b_dims):
    """Index map for a broadcastable [Bb, Hb, ., .] bias: size-1 batch /
    head dims pin to block 0."""
    has_b = 1 if bias_shape[0] != 1 else 0
    has_h = 1 if bias_shape[1] != 1 else 0
    if b_dims == "q":  # fwd/dq: [block_q, sk] row band, idx by q block
        return lambda bi, hi, i: (bi * has_b, hi * has_h, i, 0)
    return lambda bi, hi, j: (bi * has_b, hi * has_h, 0, j)  # dkv band


def _fwd_tb(qt, kt, vt, bias, causal, block_q, block_k,
            diff=False):
    """Biased forward, head-major operands; bias [Bb, Hb, Sq, Sk] f32
    (Bb/Hb broadcastable). Returns (out_t, lse)."""
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_bias, scale=scale, block_k=block_k,
                          causal=causal, seq_q=sq, seq_k=sk),
        grid=(b, h, pl.cdiv(sq, block_q)),
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, sk, d),
                         lambda bi, hi, qi: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, block_q, sk),
                         _bias_idx(bias.shape, "q")),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, 1),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name=_fwd_name("flash_biased_fwd", diff),
    )(qt, kt, vt, bias)
    return out, lse


def _bwd_tb(qt, kt, vt, bias, ot, lse, dot, causal, block_q, block_k):
    b, h, sq, d = qt.shape
    sk = kt.shape[2]
    scale = 1.0 / math.sqrt(d)
    block_q = _pick_block(sq, block_q)
    block_k = _pick_block(sk, block_k)

    q_spec = pl.BlockSpec((None, None, block_q, d),
                          lambda bi, hi, i: (bi, hi, i, 0))
    full_q = pl.BlockSpec((None, None, sq, d),
                          lambda bi, hi, i: (bi, hi, 0, 0))
    full_lse = pl.BlockSpec((None, None, sq, 1),
                            lambda bi, hi, i: (bi, hi, 0, 0))
    k_full = pl.BlockSpec((None, None, sk, d),
                          lambda bi, hi, i: (bi, hi, 0, 0))
    lse_spec = pl.BlockSpec((None, None, block_q, 1),
                            lambda bi, hi, i: (bi, hi, i, 0))
    bias_q = pl.BlockSpec((None, None, block_q, sk),
                          _bias_idx(bias.shape, "q"))
    bias_k = pl.BlockSpec((None, None, sq, block_k),
                          _bias_idx(bias.shape, "k"))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_bias, scale=scale,
                          block_k=block_k, causal=causal, seq_q=sq,
                          seq_k=sk),
        grid=(b, h, pl.cdiv(sq, block_q)),
        in_specs=[q_spec, k_full, k_full, bias_q, q_spec, lse_spec,
                  q_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), qt.dtype),
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name=_bwd_name("flash_biased_dq"),
    )(qt, kt, vt, bias, ot, lse, dot)

    kv_spec = pl.BlockSpec((None, None, block_k, d),
                           lambda bi, hi, j: (bi, hi, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_bias, scale=scale,
                          block_q=block_q, causal=causal, seq_q=sq,
                          seq_k=sk),
        grid=(b, h, pl.cdiv(sk, block_k)),
        in_specs=[full_q, kv_spec, kv_spec, bias_k, full_q, full_lse,
                  full_q],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, sk, d), kt.dtype),
                   jax.ShapeDtypeStruct((b, h, sk, d), vt.dtype)],
        interpret=_interpret(),
        compiler_params=_compiler_params(),
        name=_bwd_name("flash_biased_dkdv"),
    )(qt, kt, vt, bias, ot, lse, dot)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_core_b(q, k, v, bias, causal, block_q, block_k):
    """Additive-bias core (rel-pos bias, ALiBi, additive/boolean masks on
    the fused tier): bias streams blockwise into the logits — the
    [Sq, Sk] score matrix never materializes. The bias itself receives NO
    gradient (zero cotangent): the entry only routes stop-gradient masks
    here; trainable biases take the reference path."""
    qt, kt, vt = _layout_swap(q, k, v)
    out, _ = _fwd_tb(qt, kt, vt, bias, causal, block_q, block_k)
    return _layout_swap(out)[0]


def _flash_core_b_fwd(q, k, v, bias, causal, block_q, block_k):
    qt, kt, vt = _layout_swap(q, k, v)
    out_t, lse = _kept(*_fwd_tb(qt, kt, vt, bias, causal, block_q, block_k,
                                diff=True))
    return _layout_swap(out_t)[0], (qt, kt, vt, bias, out_t, lse)


def _flash_core_b_bwd(causal, block_q, block_k, res, g):
    qt, kt, vt, bias, ot, lse = res
    dq, dk, dv = _bwd_tb(qt, kt, vt, bias, ot, lse, _layout_swap(g)[0],
                         causal, block_q, block_k)
    return _layout_swap(dq, dk, dv) + (jnp.zeros_like(bias),)


_flash_core_b.defvjp(_flash_core_b_fwd, _flash_core_b_bwd)


def _biased_flash_ok(q, k, mask) -> bool:
    """Gate for the biased kernel path: MHA only (the grouped dKV kernel
    has no bias plumbing), block-friendly lengths (the dKV bias band's
    trailing block dim must tile to 128), rank-4 broadcastable mask."""
    if k.shape[2] != q.shape[2]:
        return False
    sq, sk = q.shape[1], k.shape[1]
    if sq % 8 != 0 or sk % 128 != 0:
        return False
    if getattr(mask, "ndim", 0) != 4:
        return False
    mb, mh, msq, msk = mask.shape
    return (mb in (1, q.shape[0]) and mh in (1, q.shape[2])
            and msq == sq and msk == sk)


def _expand_gqa_kv(q, k, v):
    """Expand GQA KV heads to the query head count (consecutive-group
    semantics, matching the kernels' `hi // rep` maps). The ONE shared
    expansion used by every non-grouped path."""
    if k.shape[2] != q.shape[2]:
        assert q.shape[2] % k.shape[2] == 0, (q.shape, k.shape)
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return q, k, v


def _ref_attention(q, k, v, mask, is_causal, window=None):
    # flat-layout reference: the einsums contract directly on the native
    # [B,S,H,D] operands (dot_general batches over non-leading (b, h) —
    # no operand relayout), so the only explicit transpose left is the
    # [B,H,Sq,D] -> [B,Sq,H,D] output reorder. Same contraction order as
    # the old swapaxes spelling — bit-identical values, 4x fewer
    # stablehlo.transpose ops (PT401; measured on the audit proxy).
    d = q.shape[-1]
    q, k, v = _expand_gqa_kv(q, k, v)
    scale = 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:   # 0 <= i + (sk - sq) - j < window
            cm &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        logits = jnp.where(cm, logits, NEG_INF)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, NEG_INF)
        else:
            logits = logits + mask.astype(logits.dtype)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# the block search's cache key. Re-keyed from "flash_fwdbwd" when the
# transpose and flat cores' backward became one fused kernel: a pair
# tuned on the split backward is not reused.
_AUTOTUNE_OP = "flash_fwd_fusedbwd"


def _tuned_blocks(b, sq, sk, h, d, dtype, causal, h_kv=None,
                  biased=False, layout=None, window=None):
    """Autotuned (block_q, block_k) for this attention signature
    (paddle/phi/kernels/autotune role; cached per signature on disk).

    Tuned on a fwd+bwd run — training is the dominant workload and the
    same (block_q, block_k) pair parameterizes both directions through
    the custom VJP. Measured at B32 H12 S1024 D64 bf16: tuned (1024,1024)
    fwd ≈ 1.3 ms vs 128x128 ≈ 6.0 ms (PERF.md).

    layout: "flat" where the flat core will consume the blocks, None
    for the transpose core.  Flat tunes under its OWN cache signature
    (``|Lflat``): its VMEM geometry differs from the transpose core's,
    so silently reusing transpose-tuned blocks is wrong.  A
    transpose-tuned entry existing while the flat entry is cold is
    counted as `autotune.cross_layout_reject` (the refusal is deliberate
    and visible).  The transpose core keeps the bare signature.

    window: a windowed call (transpose core only) tunes under its own
    signature (`|w<keys>`): the band moves the best pair, and an
    un-windowed signature and its cached winner stay as they were."""
    from . import autotune

    # curated candidate pairs, preference-ordered by the round-5 hardware
    # sweep (PERF.md: (512, 1024) wins fwd+bwd at BOTH the GPT-125M bench
    # shape, 3.18 ms vs 4.23 for the old (256, 512) default, and the
    # LLaMA-class B8 H16 S2048 D128 shape). The full {128..1024}^2 grid
    # costs ~16 TPU compiles of fwd+bwd per new signature; these six
    # cover the measured-good region
    pairs = ((512, 1024), (1024, 1024), (512, 512), (256, 512),
             (256, 256), (128, 128))

    assert layout in ("flat", None), layout
    itemsize = jnp.dtype(dtype).itemsize

    def fits(bq, bk, tight=False):
        if layout == "flat":
            # the dispatch gate's own arithmetic (_flat_native_ok): a
            # pair it would reject is never a candidate
            return _flat_vmem_bytes(
                sq, sk, h, h_kv or h, d, itemsize, bq, bk) <= (
                    0.9 if tight else 1.0) * _FLAT_VMEM_LIMIT
        # must leave headroom in the ~16 MB/core VMEM budget; a pair
        # whose fused backward does not fit runs the split pair
        # (_bwd_t), so the fused kernel never narrows the candidates
        return _t_vmem_bytes(
            sq, sk, h // (h_kv or h), d, itemsize, bq, bk,
            biased) <= (8 if tight else 12) * 1024 * 1024

    cands = [(bq, bk)
             for bq, bk in pairs
             if sq % bq == 0 and sk % bk == 0 and bq <= sq and bk <= sk
             and fits(bq, bk)]
    # static default = best measured pair that FITS this shape (pairs are
    # preference-ordered and vmem-filtered above), so an autotune-cold run
    # (fresh checkout, FLAGS_use_autotune off) still gets a good pair
    # instead of a conservative constant.  The default is also what a
    # failed tuning run falls back to, and it runs UNVALIDATED — so it
    # gets a tighter bound (8 of 12 MB for the transpose core, whose
    # estimate omits backward-only accumulators; 0.9 of the flat
    # limit), falling back to the smallest fitting pair rather than the
    # most aggressive one
    default = next(
        (c for c in cands if fits(*c, tight=True)),
        cands[-1] if cands else (_pick_block(sq, DEFAULT_BLOCK_Q),
                                 _pick_block(sk, DEFAULT_BLOCK_K)))
    if len(cands) <= 1:
        return default

    def run(cfg):
        # concrete dummy data, same signature; the returned (f, x) pair
        # chains fwd+bwd inside autotune's one-dispatch timing loop: the
        # gradients of (q, k, v) are (q, k, v)-shaped, so y = f(y)
        # composes.  All three: a gradient of q alone lets XLA drop a
        # split backward's dkdv call as dead code, and that pair is then
        # timed as fwd + dq against fused candidates' fwd + whole bwd
        rs = np.random.RandomState(0)
        hk = h_kv or h
        qv = jnp.asarray(rs.randn(b, sq, h, d), dtype)
        kv = jnp.asarray(rs.randn(b, sk, hk, d), dtype)
        vv = jnp.asarray(rs.randn(b, sk, hk, d), dtype)

        if biased:  # benchmark the kernel that will actually run
            bias_v = jnp.zeros((1, 1, sq, sk), jnp.float32)

            def loss(qv, kv, vv):
                return _flash_core_b(qv, kv, vv, bias_v, causal, cfg[0],
                                     cfg[1]).astype(jnp.float32).sum()
        else:
            # the flat signature times the flat core — caching
            # transpose-core timings under its key would be the same
            # silent mismatch the layout tag exists to prevent
            core = _flash_core_flat if layout == "flat" else _flash_core

            wargs = () if window is None else (None, None, window)

            def loss(qv, kv, vv):
                return core(qv, kv, vv, causal, cfg[0], cfg[1],
                            *wargs).astype(jnp.float32).sum()

        grads = jax.grad(loss, argnums=(0, 1, 2))
        return (lambda qkv: grads(*qkv)), (qv, kv, vv)

    sig = (f"{b}x{sq}x{sk}x{h}x{d}|{jnp.dtype(dtype).name}|c{int(causal)}"
           + (f"|kv{h_kv}" if h_kv and h_kv != h else "")
           + ("|bias" if biased else "")
           + (f"|w{window}" if window is not None else ""))
    if layout == "flat":
        # layout-tagged signature; a transpose-tuned winner for the same
        # shape is NOT reused (it was measured on different kernels) —
        # count the refusal so cold layout caches are visible
        lsig = sig + "|Lflat"
        if autotune.cached_config(_AUTOTUNE_OP, lsig) is None and \
                autotune.cached_config(_AUTOTUNE_OP, sig) is not None:
            _metrics.inc("autotune.cross_layout_reject", layout="flat")
            _flight.record("autotune.cross_layout_reject", layout="flat",
                           signature=sig)
        sig = lsig
    return autotune.pick(_AUTOTUNE_OP, sig, cands, run, default)


# What the grouped dK/dV kernel may keep resident in VMEM for one KV
# head: its query group's sequence-long q, o and do,
# 3 · rep · sq · d · itemsize.  Past it a GQA call runs the MHA kernels
# on K and V expanded rep-fold (correct, without the KV-traffic saving)
# rather than compile an infeasible kernel.
_GQA_GROUP_BYTES_MAX = 8 * 1024 * 1024


def _choose_core(q, k, causal, padded, window, block_q, block_k):
    """The core an unmasked call runs on and the blocks it runs with, as
    ("flat" | "transpose", block_q, block_k) — decided here and nowhere
    else, from the shape: a padded or windowed call takes the transpose
    core; a shape the flat tier's static gates admit takes the flat core
    at the flat signature's tuned blocks unless its VMEM bound refuses
    them; everything else the transpose core at the transpose
    signature's.  block_q/block_k: the caller's, kept where given.

    The static gates run BEFORE the flat block search: an off-gate shape
    must not launch an autotune search that times (and on TPU,
    Mosaic-compiles) the flat core it can never run.  The VMEM gate
    estimates with the blocks that will REALLY run."""
    def blocks(layout):
        if block_q is not None and block_k is not None:
            return block_q, block_k
        bq, bk = _tuned_blocks(q.shape[0], q.shape[1], k.shape[1],
                               q.shape[2], q.shape[3], q.dtype, causal,
                               h_kv=k.shape[2], layout=layout,
                               window=window)
        return (block_q if block_q is not None else bq,
                block_k if block_k is not None else bk)

    if not padded and window is None and _flat_static_ok(q, k):
        bq, bk = blocks("flat")
        if _flat_native_ok(q, k, bq, bk):
            return "flat", bq, bk
    return ("transpose",) + blocks(None)


def _per_shard(mesh, q, k, v, mask, **kw):
    """The dispatch under a multi-device SPMD mesh.  The partitioner
    cannot split a Mosaic kernel ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map" —
    raised at lowering for ANY pallas_call in a multi-device program),
    so the kernels run per shard: batch over ``dp`` and heads over
    ``mp`` where they divide, every other dim whole."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from ...distributed import topology as topo_mod

    b, h, h_kv = q.shape[0], q.shape[2], k.shape[2]
    dp, mp = mesh.shape.get("dp", 1), mesh.shape.get("mp", 1)
    ax_b = "dp" if dp > 1 and b % dp == 0 else None
    ax_h = "mp" if mp > 1 and h % mp == 0 and h_kv % mp == 0 else None
    spec = P(ax_b, None, ax_h, None)
    args, specs = [q, k, v], [spec, spec, spec]
    if mask is not None:
        args.append(mask)
        specs.append(P(ax_b if mask.shape[0] == b else None,
                       ax_h if mask.shape[1] == h else None, None, None))

    def body(q, k, v, *m):
        with topo_mod.use_spmd_mesh(None):  # the mesh is consumed here
            return flash_attention_fwd(q, k, v, m[0] if m else None, **kw)

    return shard_map(body, mesh=mesh, in_specs=tuple(specs),
                     out_specs=spec, check_vma=False)(*args)


def flash_attention_fwd(q, k, v, mask=None, is_causal=False,
                        block_q=None, block_k=None,
                        bias_grad_safe=False, window=None):
    """[B, S, H, D] in/out. Pallas kernel for causal/full. Block sizes
    are autotuned per signature unless passed explicitly. Odd sequence
    lengths (ViT's 197, ragged batches) run zero-padded to a multiple of
    8 with real-length masking inside the kernels — padded keys never
    contribute, padded query rows are sliced off (gradients included,
    via the custom VJP's real-length bounds).

    Masks: with bias_grad_safe=True (the caller vouches the mask needs
    no gradient — scaled_dot_product_attention checks stop_gradient),
    additive/boolean masks stream blockwise through the biased kernels
    ([Sq, Sk] scores never materialize); otherwise the fused-softmax
    reference path runs.

    window (causal, no mask): query i sees key j iff
    0 <= i + (Sk - Sq) - j < window — a sliding window of `window` keys
    ending at the query.  The transpose core runs it (KV blocks outside
    the band never visited, the band's two edges masked, kernels named
    `flash_transpose_window_*`); a window that covers the whole causal
    half is the un-windowed call."""
    from ...distributed import topology as topo_mod

    if window is not None:
        if mask is not None or not is_causal:
            raise ValueError("flash attention: window= needs is_causal=True "
                             "and no mask")
        window = int(window)
        if window < 1:
            raise ValueError(f"flash attention: window={window}")
        if window >= k.shape[1]:
            window = None          # the band is the whole causal half
    mesh = topo_mod.traced_spmd_mesh()
    if mesh is not None and mesh.size > 1 and flash_attention_available(q) \
            and getattr(mask, "ndim", 4) == 4:
        return _per_shard(mesh, q, k, v, mask, is_causal=is_causal,
                          block_q=block_q, block_k=block_k,
                          bias_grad_safe=bias_grad_safe, window=window)
    if mask is not None:
        if not (flash_attention_available(q) and bias_grad_safe
                and _biased_flash_ok(q, k, mask)):
            _metrics.inc("flash.dispatch", tier="fallback")
            _metrics.inc("flash.fallback_reason", reason="biased_gate")
            return _ref_attention(q, k, v, mask, is_causal)
        bias = mask
        if bias.dtype == jnp.bool_:
            bias = jnp.where(bias, 0.0, NEG_INF)
        bias = bias.astype(jnp.float32)
        if block_q is None or block_k is None:
            bq, bk = _tuned_blocks(q.shape[0], q.shape[1], k.shape[1],
                                   q.shape[2], q.shape[3], q.dtype,
                                   bool(is_causal), h_kv=k.shape[2],
                                   biased=True)
            block_q = block_q or bq
            block_k = block_k or bk
        # validate the FINAL block_k (after _pick_block shrinking): the
        # dKV bias band's trailing block dim must tile to 128 or equal sk
        sk_arr = k.shape[1]
        final_bk = _pick_block(sk_arr, block_k)
        if final_bk % 128 != 0 and final_bk != sk_arr:
            _gate_reject("biased", "bias_block_k", q, k,
                         (block_q, final_bk))
            _metrics.inc("flash.dispatch", tier="fallback")
            _metrics.inc("flash.fallback_reason", reason="bias_block_k")
            return _ref_attention(q, k, v, mask, is_causal)
        _count_dispatch("biased", block_q, final_bk)
        return _flash_core_b(q, k, v, bias, bool(is_causal), block_q,
                             final_bk)
    if not flash_attention_available(q):
        _metrics.inc("flash.dispatch", tier="fallback")
        _metrics.inc("flash.fallback_reason", reason="unavailable")
        return _ref_attention(q, k, v, mask, is_causal, window)
    if window is not None and (q.shape[1] % 8 or k.shape[1] % 8):
        # the kernels' real-length bounds and the band's were never put
        # together, and no O(S^2) reference stands in for them
        raise ValueError("flash attention: window= needs sequence lengths "
                         f"that are multiples of 8, got {q.shape[1]} and "
                         f"{k.shape[1]}")
    if k.shape[2] != q.shape[2]:
        rep = q.shape[2] // k.shape[2]
        group_bytes = 3 * rep * q.shape[1] * q.shape[3] * q.dtype.itemsize
        if group_bytes > _GQA_GROUP_BYTES_MAX:
            # the grouped path refused: K and V grow rep-fold in HBM
            _metrics.inc("flash.gqa_expand", reason="group_bytes")
            q, k, v = _expand_gqa_kv(q, k, v)
    sq, sk = q.shape[1], k.shape[1]
    pad_q = (-sq) % 8
    pad_k = (-sk) % 8
    padded = bool(pad_q or pad_k)
    if padded:
        widths = lambda p: ((0, 0), (0, p), (0, 0), (0, 0))
        with jax.named_scope(LAYOUT_SCOPE):
            q = jnp.pad(q, widths(pad_q))
            k = jnp.pad(k, widths(pad_k))
            v = jnp.pad(v, widths(pad_k))
    core, block_q, block_k = _choose_core(q, k, bool(is_causal), padded,
                                          window, block_q, block_k)
    _count_dispatch(core, block_q, block_k, window)
    if core == "flat":
        # unpadded [B,S,H*D] views, zero transposes
        return _flash_core_flat(q, k, v, bool(is_causal), block_q, block_k)
    real = (sq, sk) if padded else (None, None)
    out = _flash_core(q, k, v, bool(is_causal), block_q, block_k, *real,
                      window)
    if padded:
        with jax.named_scope(LAYOUT_SCOPE):
            return out[:, :sq]
    return out
