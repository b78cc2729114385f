"""Plain reference of Ouro (ByteDance's looped language model): forward,
the loss over the exit distribution, gradients and AdamW in `jax.numpy`.

Written from the configuration file and the equations it states (`assumed`
lists every point the published `config.json` has no key for).  It imports
nothing of `paddle_tpu`; the plain pieces it shares with the Trinity
reference (matrix product, RMS norm, rotary positions, attention in query
blocks, SwiGLU, AdamW, the controls' rounding) are taken from
`reference/afmoe.py`.  Everything is float32 and every matmul runs at
`highest` precision unless a `quant` hook is given (the control).

    h <- E[x]
    for t = 1..T (total_ut_steps), with the SAME weights every pass:
        for l = 1..L:
            h <- h + N2_l(Attn_l(N1_l(h)));  h <- h + N4_l(MLP_l(N3_l(h)))
        h <- N_f(h)                     (the normed state goes on)
        CE_t per token from h W_head^T;  lambda_t = sigmoid(h w_g + b_g)
    p_t = lambda_t prod_{j<t}(1 - lambda_j) (t < T), p_T = prod_{j<T}(1 - lambda_j)
    loss = mean over tokens of [sum_t p_t CE_t - beta H(p)]

Attention runs in query blocks, each layer APPLICATION under
`jax.checkpoint`, and the head in slices of HEAD_ROWS positions, each
recomputed in the backward pass, so that 24 applications and four
read-outs of 12,288 tokens fit beside the weights.  `FAULTS` break the
mathematics as a wrong program would: `loop_short` runs T - 1 passes,
`exit_detached` puts the exit distribution under `stop_gradient` (the gate
learns nothing), `sandwich_dropped` leaves N2 and N4 out.

Parameter names are the framework-neutral ones of `drivers/train_ouro.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness import common

_afmoe = common.load_module("reference", "afmoe")
HIGHEST = _afmoe.HIGHEST
HEAD_ROWS = 2048
FAULTS = ("loop_short", "exit_detached", "sandwich_dropped")
int8_fake_quant, fp8_fake_quant = _afmoe.int8_fake_quant, _afmoe.fp8_fake_quant
_mm, _rms, _rope = _afmoe._mm, _afmoe._rms, _afmoe._rope
adamw, leaf_norms = _afmoe.adamw, _afmoe.leaf_norms


def _block(cfg, p, i, x, quant, fault=None):
    b, s, _ = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pre = f"h.{i}."
    sandwich = fault != "sandwich_dropped"
    y = _rms(x, p[pre + "input_norm"], eps)
    q = _rope(_mm(y, p[pre + "q"], quant).reshape(b, s, nh, d), theta)
    k = _rope(_mm(y, p[pre + "k"], quant).reshape(b, s, nkv, d), theta)
    v = _mm(y, p[pre + "v"], quant).reshape(b, s, nkv, d)
    a = _mm(_afmoe._attention(q, k, v, None).reshape(b, s, nh * d),
            p[pre + "o"], quant)
    x = x + (_rms(a, p[pre + "input_norm_2"], eps) if sandwich else a)
    y = _rms(x, p[pre + "post_attn_norm"], eps)
    m = _afmoe._swiglu(y, p[pre + "mlp.gate"], p[pre + "mlp.up"],
                       p[pre + "mlp.down"], quant)
    return x + (_rms(m, p[pre + "post_attn_norm_2"], eps) if sandwich else m)


def passes(cfg, p, ids, quant=None, remat=False, fault=None):
    """ids [b, s] -> (each pass's normed state [b, s, h], a list of T;
    the gate's logits [T - 1, b, s])."""
    steps = cfg["total_ut_steps"] - (fault == "loop_short")
    x = p["wte"][ids]
    states = []
    for _ in range(steps):
        for i in range(cfg["num_hidden_layers"]):
            f = lambda pp, xx, i=i: _block(cfg, pp, i, xx, quant, fault)
            x = jax.checkpoint(f)(p, x) if remat else f(p, x)
        x = _rms(x, p["norm"], cfg["rms_norm_eps"])
        states.append(x)
    z = jnp.stack([_mm(h, p["gate.w"], quant)[..., 0] + p["gate.b"][0]
                   for h in states[:-1]]) if steps > 1 \
        else jnp.zeros((0,) + ids.shape, jnp.float32)
    return states, z


def exit_distribution(z):
    """z [T - 1, ...] -> (p [T, ...], entropy [...]), by the products."""
    lam = jax.nn.sigmoid(z)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    one = jnp.ones((1,) + z.shape[1:], z.dtype)
    p = jnp.concatenate([lam, one], 0) * jnp.concatenate([one, stay], 0)
    return p, -jnp.sum(p * jnp.log(p), axis=0)


def token_ce(cfg, p, h, labels, quant=None):
    """Each token's cross entropy [b * s] of one pass's state h [b, s, h];
    the head and the softmax in slices of HEAD_ROWS positions, each
    recomputed in the backward pass."""
    h = h.reshape(-1, h.shape[-1])
    rows = min(HEAD_ROWS, h.shape[0])
    assert h.shape[0] % rows == 0
    w = p["lm_head"].T
    if quant is not None:       # per tensor, as _mm would: once, not a slice
        w = quant(w)

    @jax.checkpoint
    def one(hh, ll):
        lg = jnp.matmul(hh if quant is None else quant(hh), w,
                        precision=HIGHEST)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return lse - jnp.take_along_axis(lg, ll[:, None], axis=-1)[:, 0]

    return jax.lax.map(lambda a: one(*a), (
        h.reshape(-1, rows, h.shape[-1]), labels.reshape(-1, rows))).reshape(-1)


def loss_terms(cfg, p, ids, labels, quant=None, fault=None, remat=True):
    """(sum over tokens of sum_t p_t CE_t, sum over tokens of H(p), the exit
    distribution [T, b, s], each pass's CE [T, b * s]) of a block of rows."""
    states, z = passes(cfg, p, ids, quant, remat, fault)
    pe, ent = exit_distribution(z)
    if fault == "exit_detached":
        pe, ent = jax.lax.stop_gradient((pe, ent))
    ce = jnp.stack([token_ce(cfg, p, h, labels, quant) for h in states])
    return jnp.sum(pe.reshape(ce.shape) * ce), jnp.sum(ent), pe, ce


def loss_sum(cfg, p, ids, labels, quant=None, fault=None):
    expected, ent, _, _ = loss_terms(cfg, p, ids, labels, quant, fault)
    return expected - cfg["entropy_beta"] * ent


def loss_and_grads(cfg, p, ids, labels, quant=None, fault=None):
    """Mean loss and its gradients over a batch given in blocks of rows
    (`ids`, `labels`: [blocks, rows, seq]): the blocks' gradients are
    accumulated in a scan (one block: no accumulator)."""
    denom = jnp.float32(ids.shape[0] * ids.shape[1] * ids.shape[2])

    def one(blk):
        return jax.value_and_grad(
            lambda pp: loss_sum(cfg, pp, blk[0], blk[1], quant, fault))(p)

    if ids.shape[0] == 1:
        l, g = one((ids[0], labels[0]))
    else:
        def body(acc, blk):
            l, g = one(blk)
            return (acc[0] + l,
                    jax.tree_util.tree_map(jnp.add, acc[1], g)), None

        zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, p))
        (l, g), _ = jax.lax.scan(body, zero, (ids, labels))
    return l / denom, jax.tree_util.tree_map(lambda x: x / denom, g)


def train_readings(cfg, opt, p0, batches, rows_per_block, quant=None,
                   leaves=lambda t: t, fault=None):
    """Follow the first `len(batches)` steps: the losses, the first
    gradient's norm per leaf and the norm of each leaf's change after them.
    `p0` is the tree of weights or a function that makes it (called twice:
    no second copy is held while the steps run; AdamW's moments wait on the
    host while a gradient is computed, as in `reference/afmoe.py`)."""
    import numpy as np

    make_p0 = p0 if callable(p0) else (lambda: p0)
    step = jax.jit(lambda p, ids, labels: loss_and_grads(cfg, p, ids, labels,
                                                         quant, fault))
    upd = jax.jit(lambda p, g, m, v, t: adamw(opt, p, g, m, v, t),
                  donate_argnums=(0, 1, 2, 3))
    norms = jax.jit(lambda t: leaf_norms(leaves(t)))
    delta = jax.jit(lambda a, b: leaf_norms(leaves(
        {n: a[n] - b[n] for n in a})))
    p = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(make_p0())
    m = v = None                    # on the host between the steps
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        blocked = [np.asarray(x).reshape(-1, rows_per_block, x.shape[-1])
                   for x in (ids, labels)]
        loss, g = step(p, *blocked)
        if grad_norms is None:
            grad_norms = {n: float(x) for n, x in norms(g).items()}
        losses.append(float(loss))
        if m is None:
            m = v = {n: np.zeros(x.shape, np.float32) for n, x in p.items()}
        p, m, v = upd(p, g, m, v, jnp.float32(t))
        del g
        if t < len(batches):
            m, v = jax.device_get((m, v))
    del m, v
    change = {n: float(x) for n, x in delta(p, make_p0()).items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
