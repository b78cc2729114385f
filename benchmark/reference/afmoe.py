"""Plain AFMoE (Arcee Trinity) reference: forward, loss, gradients and AdamW
in `jax.numpy`.

Written from the configuration file and the layer equations it states
(`assumed` lists every point the published `config.json` has no key for).
It imports nothing of `paddle_tpu`.  Everything is float32 and every matmul
runs at `highest` precision unless a `quant` hook is given (the control).

  * embedding times sqrt(hidden) (`mup_enabled`), untied head;
  * `h += post_attn_norm(attn(input_norm(h)))`,
    `h += post_mlp_norm(mlp(pre_mlp_norm(h)))`, RMS norms with a weight;
  * attention: 32 query heads over 4 key/value heads, RMS norm over each
    head of q and k, rotary positions (rotate-half) on `sliding_attention`
    layers only, query i sees key j iff 0 <= i - j < window there and iff
    j <= i on `full_attention` layers, `out = (attn * sigmoid(x Wg)) Wo`;
    computed in query blocks, so a [heads, S, S] score tensor never exists;
  * dense SwiGLU MLP on the layers below `num_dense_layers`; on the others
    `s = sigmoid(x Wr)` over `router_width` experts, the top
    `num_experts_per_tok` of `s + expert_bias` (zero), weights `s` there
    over their sum + 1e-20 times `route_scale`, and
    `y = shared(x) + sum over the experts HELD HERE of w_e expert_e(x)`:
    the reference is given the same share as the program (experts
    `expert_start` .. + `num_experts`), each held expert applied densely to
    every token with its weight zero where it was not chosen.  What the
    absent experts would have added is left out.

Parameter names are the framework-neutral ones of `drivers/train_afmoe.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512
HEAD_ROWS = 2048


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


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(x, theta):
    """x [b, s, heads, d]: rotate-half pairing (i, i + d/2)."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(q, k, v, window):
    """q [b, s, H, d], k/v [b, s, Hkv, d] -> [b, s, H, d]; causal, and a
    sliding window of `window` keys where given.  Query blocks of Q_BLOCK
    rows, each recomputed in the backward pass."""
    b, s, nh, d = q.shape
    rep = nh // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    blk = min(Q_BLOCK, s)
    assert s % blk == 0
    j = jnp.arange(s)[None, :]

    @jax.checkpoint
    def one(q_blk, i0):
        att = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k,
                         precision=HIGHEST) * d ** -0.5
        i = i0 + jnp.arange(blk)[:, None]
        see = j <= i
        if window is not None:
            see &= i - j < window
        att = jax.nn.softmax(jnp.where(see, att, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=HIGHEST)

    qb = q.reshape(b, s // blk, blk, nh, d).swapaxes(0, 1)
    out = jax.lax.map(lambda a: one(*a), (qb, jnp.arange(0, s, blk)))
    return out.swapaxes(0, 1).reshape(b, s, nh, d)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(cfg, x, w_router, quant=None):
    """(chosen experts [.., k], their weights [.., k])."""
    s = jax.nn.sigmoid(_mm(x, w_router, quant))
    _, idx = jax.lax.top_k(s, cfg["num_experts_per_tok"])  # expert_bias 0
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return idx, w * cfg["route_scale"]


def experts_held(cfg, p, pre, x, quant=None, start=None, count=None,
                 skip=None):
    """The routed part that experts [start, start + count) give (default:
    the share the configuration holds), each applied densely to every
    token with its weight zero where it was not chosen (a scan over the
    experts, each recomputed in the backward pass); `skip` leaves one of
    them out (the `expert_dropped` fault)."""
    start = cfg.get("expert_start", 0) if start is None else start
    count = cfg["num_experts"] if count is None else count
    idx, w = route(cfg, x, p[pre + "router"], quant)
    keep = jnp.ones((count,), jnp.float32)
    if skip is not None:
        keep = keep.at[skip].set(0.0)

    @jax.checkpoint
    def one(y, e):
        number, gate, up, down, kept = e
        we = jnp.sum(jnp.where(idx == number, w, 0.0), -1, keepdims=True)
        return y + kept * we * _swiglu(x, gate, up, down, quant), None

    first = start - cfg.get("expert_start", 0)   # within the weights held
    held = lambda name: p[pre + name][first:first + count]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        start + jnp.arange(count), held("experts.gate"), held("experts.up"),
        held("experts.down"), keep))
    return y


def shared_expert(p, pre, x, quant=None):
    return _swiglu(x, p[pre + "shared.gate"], p[pre + "shared.up"],
                   p[pre + "shared.down"], quant)


def layer_types(cfg):
    """The kinds of the layers held, in order."""
    held = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return [cfg["layer_types"][i] for i in held]


def _block(cfg, p, i, x, quant, fault=None):
    b, s, h = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    pre = f"h.{i}."
    sliding = layer_types(cfg)[i] == "sliding_attention"
    y = _rms(x, p[pre + "input_norm"], eps)
    q = _rms(_mm(y, p[pre + "q"], quant).reshape(b, s, nh, d),
             p[pre + "q_norm"], eps)
    k = _rms(_mm(y, p[pre + "k"], quant).reshape(b, s, nkv, d),
             p[pre + "k_norm"], eps)
    v = _mm(y, p[pre + "v"], quant).reshape(b, s, nkv, d)
    if sliding:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    window = cfg["sliding_window"] if sliding else None
    if fault == "window_ignored":
        window = None
    o = _attention(q, k, v, window).reshape(b, s, nh * d)
    o = o * jax.nn.sigmoid(_mm(y, p[pre + "gate"], quant))
    x = x + _rms(_mm(o, p[pre + "o"], quant), p[pre + "post_attn_norm"], eps)
    y = _rms(x, p[pre + "pre_mlp_norm"], eps)
    if i < cfg["num_dense_layers"]:
        m = _swiglu(y, p[pre + "mlp.gate"], p[pre + "mlp.up"],
                    p[pre + "mlp.down"], quant)
    else:
        m = shared_expert(p, pre, y, quant) + experts_held(
            cfg, p, pre, y, quant,
            skip=0 if fault == "expert_dropped" else None)
    return x + _rms(m, p[pre + "post_mlp_norm"], eps)


def hidden(cfg, p, ids, quant=None, remat=False, fault=None):
    """ids [b, s] int32 -> final-norm hidden states [b, s, h] (float32)."""
    x = p["wte"][ids]
    if cfg["mup_enabled"]:
        x = x * cfg["hidden_size"] ** 0.5
    for i in range(cfg["num_hidden_layers"]):
        f = lambda pp, xx, i=i: _block(cfg, pp, i, xx, quant, fault)
        x = jax.checkpoint(f)(p, x) if remat else f(p, x)
    return _rms(x, p["norm"], cfg["rms_norm_eps"])


def logits(cfg, p, ids, quant=None):
    return _mm(hidden(cfg, p, ids, quant), p["lm_head"].T, quant)


def loss_sum(cfg, p, ids, labels, quant=None, fault=None):
    """Summed next-token cross entropy over a block of rows; the head and
    the softmax in slices of HEAD_ROWS positions, each recomputed in the
    backward pass (the float32 logits of 16,384 positions are 1.6 GB)."""
    h = hidden(cfg, p, ids, quant, remat=True, fault=fault)
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
        return jnp.sum(lse - jnp.take_along_axis(lg, ll[:, None], axis=-1)[:, 0])

    parts = jax.lax.map(lambda a: one(*a), (
        h.reshape(-1, rows, h.shape[-1]), labels.reshape(-1, rows)))
    return jnp.sum(parts)


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
                   leaves=lambda t: t, fault=None):
    """Follow the first `len(batches)` steps.  Returns the losses, the first
    gradient's norm per leaf and the norm of each leaf's change after them.
    `p0` is the tree of weights or a function that makes it (called twice:
    no second copy is held while the steps run — at 0.7 B parameters the
    weights, both moments and the gradients fill a 16 GB chip, so the
    moments also wait on the host while a gradient is computed).  `leaves`
    maps a tree onto the leaves that are compared; `fault`
    (`window_ignored`, `expert_dropped`) breaks the mathematics the way a
    wrong program would."""
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
