"""Ring attention: context parallelism for sequences longer than one chip's
memory (SURVEY §5 long-context note: the reference has NO ring attention —
this is the capability-parity-plus point; its SEP axis only does Ulysses-
style alltoall).

Design: inside `shard_map` over the `sep` mesh axis, each device holds its
local Q/K/V sequence shard; K/V blocks rotate around the ring via
`lax.ppermute` while blockwise-softmax partial results fold in each visiting
block. The per-block attention is the Pallas flash kernel (flash_attention
._fwd/._bwd), so logits live in VMEM — local memory stays O(s_local·d), not
O(s_local²), which is what makes >HBM sequence lengths reachable. Forward
K/V rotate in the input dtype (bf16 on TPU), halving ICI bytes vs an f32
ring. Backward deliberately rotates the dK/dV running sums in f32 (2x the
forward ring's bytes): each hop would otherwise round the accumulator to
bf16, compounding error with ring size — the K/V blocks traveling alongside
still ride in bf16. Communication overlaps compute: each ppermute is issued
with the block math of the previous step still in flight (XLA schedules the
async collective-permute).

Differentiation is a custom VJP: forward saves (out, lse); backward runs a
second ring pass where each step computes the flash dQ/dK/dV for the block
currently held (three lax.switch branches: empty / causal-diagonal / full,
mirroring forward's block classification against the ring offset).

Blocks strictly above the causal diagonal never compute (empty branch), so
causal ring attention does ~half the flops, same as the single-chip kernel.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import flash_attention as FA

NEG_INF = -1e30


def _merge(acc, lse_acc, out_i, lse_i):
    """Fold one block's normalized partial (out_i, lse_i) into the running
    (acc f32 [b,sl,h,d], lse_acc f32 [b,h,sl,1]) via blockwise softmax."""
    new_lse = jnp.logaddexp(lse_acc, lse_i)
    # both operands can sit at the finite NEG_INF floor (fully masked row):
    # the subtraction stays finite, weights ~0.5 each, acc stays 0
    w_old = jnp.swapaxes(jnp.exp(lse_acc - new_lse), 1, 2)  # [b,sl,h,1]
    w_new = jnp.swapaxes(jnp.exp(lse_i - new_lse), 1, 2)
    acc = acc * w_old + out_i.astype(jnp.float32) * w_new
    return acc, new_lse


def _fwd_local(q, k, v, causal, block_q, block_k, axis_name):
    """Per-shard forward. q/k/v: [b, sl, h, d] locals. Returns (out, lse)."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sl, h, d = q.shape

    def flash_full(args):
        q_, k_, v_ = args
        return FA._fwd(q_, k_, v_, False, block_q, block_k)

    def flash_causal(args):
        q_, k_, v_ = args
        return FA._fwd(q_, k_, v_, True, block_q, block_k)

    def empty(args):
        q_, _, _ = args
        return (jnp.zeros_like(q_),
                jnp.full((b, h, sl, 1), NEG_INF, jnp.float32))

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        acc, lse_acc, kc, vc = carry
        src = jnp.mod(my - i, n)  # origin shard of the kv block we hold
        if causal:
            # src > my: strictly above the diagonal — skip entirely
            branch = jnp.where(src == my, 1, jnp.where(src < my, 2, 0))
            out_i, lse_i = jax.lax.switch(
                branch, [empty, flash_causal, flash_full], (q, kc, vc))
        else:
            out_i, lse_i = flash_full((q, kc, vc))
        acc, lse_acc = _merge(acc, lse_acc, out_i, lse_i)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        return acc, lse_acc, kc, vc

    acc0 = jnp.zeros((b, sl, h, d), jnp.float32)
    lse0 = jnp.full((b, h, sl, 1), NEG_INF, jnp.float32)
    acc, lse, _, _ = jax.lax.fori_loop(0, n, body, (acc0, lse0, k, v))
    return acc.astype(q.dtype), lse


def _bwd_local(q, k, v, out, lse, do, causal, block_q, block_k, axis_name):
    """Second ring pass: dK/dV accumulators travel WITH their kv blocks, so
    after the full cycle each lands back on its home shard."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)

    def bwd_full(args):
        q_, kc, vc = args
        return FA._bwd(q_, kc, vc, out, lse, do, False, block_q, block_k)

    def bwd_causal(args):
        q_, kc, vc = args
        return FA._bwd(q_, kc, vc, out, lse, do, True, block_q, block_k)

    def bwd_empty(args):
        q_, kc, vc = args
        return (jnp.zeros_like(q_), jnp.zeros_like(kc), jnp.zeros_like(vc))

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        dq, kc, vc, dkc, dvc = carry
        src = jnp.mod(my - i, n)
        if causal:
            branch = jnp.where(src == my, 1, jnp.where(src < my, 2, 0))
            dq_i, dk_i, dv_i = jax.lax.switch(
                branch, [bwd_empty, bwd_causal, bwd_full], (q, kc, vc))
        else:
            dq_i, dk_i, dv_i = bwd_full((q, kc, vc))
        dq = dq + dq_i.astype(jnp.float32)
        dkc = dkc + dk_i.astype(jnp.float32)
        dvc = dvc + dv_i.astype(jnp.float32)
        kc = jax.lax.ppermute(kc, axis_name, perm)
        vc = jax.lax.ppermute(vc, axis_name, perm)
        dkc = jax.lax.ppermute(dkc, axis_name, perm)
        dvc = jax.lax.ppermute(dvc, axis_name, perm)
        return dq, kc, vc, dkc, dvc

    dq0 = jnp.zeros(q.shape, jnp.float32)
    dq, _, _, dk, dv = jax.lax.fori_loop(
        0, n, body, (dq0, k, v, jnp.zeros(k.shape, jnp.float32),
                     jnp.zeros(v.shape, jnp.float32)))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _ring_core(q, k, v, causal, block_q, block_k, axis_name):
    out, _ = _fwd_local(q, k, v, causal, block_q, block_k, axis_name)
    return out


def _ring_core_fwd(q, k, v, causal, block_q, block_k, axis_name):
    out, lse = _fwd_local(q, k, v, causal, block_q, block_k, axis_name)
    return out, (q, k, v, out, lse)


def _ring_core_bwd(causal, block_q, block_k, axis_name, res, g):
    q, k, v, out, lse = res
    return _bwd_local(q, k, v, out, lse, g, causal, block_q, block_k,
                      axis_name)


_ring_core.defvjp(_ring_core_fwd, _ring_core_bwd)


# ---------- jnp fallback body (shapes the kernel can't tile) ----------

def _local_ring_attention_jnp(q, k, v, *, axis_name, causal):
    """Materialized-logits fallback for shard shapes the flash kernel
    rejects (s_local % 8 != 0); O(s_local²) memory."""
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    scale = 1.0 / math.sqrt(d)

    qh = jnp.swapaxes(q, 1, 2)  # [b,h,sl,d]

    m0 = jnp.full((b, h, sl), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sl), jnp.float32)
    acc0 = jnp.zeros((b, h, sl, d), jnp.float32)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def body(i, carry):
        m, l, acc, kc, vc = carry
        src = jnp.mod(my - i, n)
        s = jnp.einsum("bhqd,bhkd->bhqk", qh, jnp.swapaxes(kc, 1, 2),
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = my * sl + jax.lax.broadcasted_iota(
                jnp.int32, (sl, sl), 0)
            k_pos = src * sl + jax.lax.broadcasted_iota(
                jnp.int32, (sl, sl), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bhkd->bhqd", p.astype(vc.dtype),
            jnp.swapaxes(vc, 1, 2), preferred_element_type=jnp.float32)
        kc_next = jax.lax.ppermute(kc, axis_name, perm)
        vc_next = jax.lax.ppermute(vc, axis_name, perm)
        return m_new, l_new, acc_new, kc_next, vc_next

    m, l, acc, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, acc0, k, v))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def ring_attention(q, k, v, mesh=None, seq_axis="sep", causal=True,
                   batch_axis="dp", head_axis="mp", block_q=None,
                   block_k=None, use_flash=None):
    """[B, S, H, D] global arrays (or tracers); S sharded over `seq_axis`.
    Falls back to a single-shard flash/ref path when the mesh has no seq
    axis. use_flash: None = platform policy (Pallas ring on real TPU, jnp
    body elsewhere), True/False = force (tests exercise the Pallas ring
    through the interpreter on CPU meshes with True)."""
    from jax import shard_map

    from ...distributed import topology as topo_mod

    if mesh is None:
        mesh = topo_mod.current_spmd_mesh()
    if seq_axis not in mesh.shape or mesh.shape[seq_axis] == 1:
        return FA.flash_attention_fwd(q, k, v, None, causal)

    h = q.shape[2]
    use_head = head_axis in mesh.shape and h % mesh.shape[head_axis] == 0
    use_batch = batch_axis in mesh.shape and \
        q.shape[0] % mesh.shape[batch_axis] == 0
    spec = P(batch_axis if use_batch else None, seq_axis,
             head_axis if use_head else None, None)

    sl = q.shape[1] // mesh.shape[seq_axis]
    d = q.shape[3]
    from ...core import flags

    # same policy as the single-chip flash gate: kill-switch flag honored,
    # Pallas only where it compiles (real TPU) — the interpreter would run
    # the kernels in Python per grid point; CPU meshes take the jnp body
    tileable = sl % 8 == 0 and d % 8 == 0 and d <= 256
    if use_flash is None:
        use_flash = flags.pallas_enabled("flash") and not FA._interpret()
    if use_flash and tileable:
        bq = FA._pick_block(sl, block_q or FA.DEFAULT_BLOCK_Q)
        bk = FA._pick_block(sl, block_k or FA.DEFAULT_BLOCK_K)

        def body(q_, k_, v_):
            # nondiff args positional: custom_vjp rejects keywords
            return _ring_core(q_, k_, v_, bool(causal), bq, bk, seq_axis)
    else:
        body = functools.partial(_local_ring_attention_jnp,
                                 axis_name=seq_axis, causal=causal)

    fn = shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
