"""Plain GPT reference: forward, loss, gradients and AdamW in `jax.numpy`.

Written from the configuration file alone (Brown et al. 2020 / GPT-2 block:
pre-LayerNorm, fused QKV, learned positions, tanh-GELU, tied output head).
It imports nothing of `paddle_tpu`.  Everything is float32 and every matmul
runs at `highest` precision unless a `quant` hook is given (the control).

Parameter names are the framework-neutral ones of `harness/weights.py`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def int8_fake_quant(x):
    """Per-tensor absmax int8 round trip with a straight-through gradient:
    the control's precision (one step below bfloat16)."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(x / scale), -127, 127) * scale
    return x + jax.lax.stop_gradient(q - x)


def fp8_fake_quant(x):
    """Per-tensor scaled float8 (e4m3) round trip, straight-through
    gradient: the other control one step below bfloat16."""
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x))) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(x, w, quant):
    if quant is not None:
        x, w = quant(x), quant(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        0.7978845608028654 * (x + 0.044715 * x * x * x)))


def _block(cfg, p, i, x, quant):
    b, s, h = x.shape
    nh = cfg["num_attention_heads"]
    hd = h // nh
    pre = f"h.{i}."
    y = _layer_norm(x, p[pre + "ln_1.w"], p[pre + "ln_1.b"], cfg["layer_norm_eps"])
    qkv = _mm(y, p[pre + "qkv.w"], quant) + p[pre + "qkv.b"]
    qkv = qkv.reshape(b, s, 3, nh, hd)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    att = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / jnp.sqrt(
        jnp.float32(hd))
    mask = jnp.tril(jnp.ones((s, s), bool))
    att = jax.nn.softmax(jnp.where(mask, att, -1e30), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=HIGHEST).reshape(b, s, h)
    x = x + _mm(o, p[pre + "out.w"], quant) + p[pre + "out.b"]
    y = _layer_norm(x, p[pre + "ln_2.w"], p[pre + "ln_2.b"], cfg["layer_norm_eps"])
    y = _gelu_tanh(_mm(y, p[pre + "up.w"], quant) + p[pre + "up.b"])
    return x + _mm(y, p[pre + "down.w"], quant) + p[pre + "down.b"]


def hidden(cfg, p, ids, quant=None, remat=False):
    """ids [b, s] int32 -> final-norm hidden states [b, s, h] (float32)."""
    s = ids.shape[1]
    x = p["wte"][ids] + p["wpe"][jnp.arange(s)]
    for i in range(cfg["num_hidden_layers"]):
        blk = functools.partial(_block, cfg, quant=quant)
        if remat:
            x = jax.checkpoint(lambda pp, xx, i=i: _block(cfg, pp, i, xx, quant))(p, x)
        else:
            x = blk(p, i, x)
    return _layer_norm(x, p["ln_f.w"], p["ln_f.b"], cfg["layer_norm_eps"])


def loss_sum(cfg, p, ids, labels, quant=None):
    """Summed next-token cross entropy over a block of rows."""
    h = hidden(cfg, p, ids, quant, remat=True)
    lg = _mm(h, p["wte"].T, quant)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - picked)


def loss_and_grads(cfg, p, ids, labels, quant=None):
    """Mean loss and its gradients over a batch given in blocks of rows
    (`ids`, `labels`: [blocks, rows, seq]) so that the float32 activations
    fit: a scan over the blocks that accumulates the gradients."""
    denom = jnp.float32(ids.shape[0] * ids.shape[1] * ids.shape[2])

    def body(acc, blk):
        l, g = jax.value_and_grad(
            lambda pp: loss_sum(cfg, pp, blk[0], blk[1], quant))(p)
        return (acc[0] + l, jax.tree_util.tree_map(jnp.add, acc[1], g)), None

    zero = (jnp.float32(0), jax.tree_util.tree_map(jnp.zeros_like, p))
    (l, g), _ = jax.lax.scan(body, zero, (ids, labels))
    return l / denom, jax.tree_util.tree_map(lambda x: x / denom, g)


def adamw(opt, p, g, m, v, t):
    """One decoupled-decay AdamW step on every leaf (`t` counts from 1)."""
    lr, b1, b2 = opt["learning_rate"], opt["beta1"], opt["beta2"]
    eps, wd = opt["epsilon"], opt["weight_decay"]
    out_p, out_m, out_v = {}, {}, {}
    for n in p:
        m1 = b1 * m[n] + (1 - b1) * g[n]
        v1 = b2 * v[n] + (1 - b2) * g[n] * g[n]
        mhat = m1 / (1 - b1 ** t)
        vhat = v1 / (1 - b2 ** t)
        out_p[n] = p[n] * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        out_m[n], out_v[n] = m1, v1
    return out_p, out_m, out_v


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


def train_readings(cfg, opt, p0, batches, rows_per_block, quant=None,
                   leaves=lambda t: t):
    """Follow the first `len(batches)` steps.  Returns the losses, the first
    gradient's norm per leaf and the norm of each leaf's change after them.
    `leaves` maps a tree onto the leaves that are compared."""
    import numpy as np

    step = jax.jit(lambda p, ids, labels: loss_and_grads(cfg, p, ids, labels, quant))
    upd = jax.jit(lambda p, g, m, v, t: adamw(opt, p, g, m, v, t),
                  donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda t: leaf_norms(leaves(t)))
    delta = jax.jit(lambda a, b: leaf_norms(leaves(
        {n: a[n] - b[n] for n in a})))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    p = jax.jit(lambda t: jax.tree_util.tree_map(jnp.copy, t))(p0)
    m, v = zeros(p0), zeros(p0)
    losses, grad_norms = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        blocked = [np.asarray(x).reshape(-1, rows_per_block, x.shape[-1])
                   for x in (ids, labels)]
        loss, g = step(p, *blocked)
        if grad_norms is None:
            grad_norms = {n: float(x) for n, x in norms(g).items()}
        losses.append(float(loss))
        p, m, v = upd(p, g, m, v, jnp.float32(t))
        del g
    change = {n: float(x) for n, x in delta(p, p0).items()}
    return {"losses": losses, "grad_norms": grad_norms, "change_norms": change}
