"""Plain reference of Keye-VL-2.0's language model (Qwen3-MoE-shaped decoder
with DeepSeek-Sparse-Attention's indexer): forward, both loss terms,
gradients and AdamW in `jax.numpy`.

Written from the configuration file and the seven steps it states (`assumed`
lists every point the published `config.json` has no key for).  It imports
nothing of `paddle_tpu`.  Everything is float32 and every matmul runs at
`highest` precision unless a `quant` hook is given (the control).

For layer input h [T, hidden] and positions pos [3, T]:

  1. x = rms(h; input_norm).
  2. q = rms_head(x Wq), k = rms_head(x Wk), v = x Wv; M-RoPE on q and k:
     rotary pair i (rotate-half pairing, frequency theta^(-i/64)) turns by
     pos[0] for i < 16, pos[1] for 16 <= i < 40, pos[2] for the rest.
  3. Indexer, on stop_gradient(x): qI = x WqI [T, 16, 64]; kI =
     layer_norm(x WkI) [T, 64]; rotary on all 32 pairs of both by pos[0];
     w = (x Ww) 16^-1/2 64^-1/2;  I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s]).
  4. S_t = the min(t + 1, topk) keys s <= t of largest I[t, s], ties to the
     lower s (-0.0 ties with +0.0): by a SORT of the row and a running count
     of the ties at the threshold.
  5. a = softmax over S_t of q . k / sqrt(128), times v; h += a Wo.
  6. y = rms(h; post_attn_norm); p = softmax(y Wr) over `router_width`; the
     `num_experts_per_tok` largest, p there over their sum; h += the HELD
     experts' part (experts `expert_start` .. + `num_experts`, each applied
     densely to every token with its weight zero where it was not chosen).
  7. L_I = mean_t sum_{s in S_t} P (log P - log softmax_{S_t}(I)), P the
     mean over the 32 heads of step 5's softmax, detached.

Loss = next-token cross entropy + the sum over the layers of L_I.  Attention
and indexer run in query blocks (each recomputed in the backward pass), so
neither a [heads, T, T] nor an indexer [16, T, T] tensor exists.

Parameter names are the framework-neutral ones of `drivers/train_keye.py`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
HEAD_ROWS = 2048
FAULTS = ("selection_ignored", "indexer_loss_dropped")


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


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def rope(x, pos, theta, sections=None):
    """x [b, s, heads, d], rotate-half pairing (i, i + d/2).  pos [b, s], or
    with `sections` [3, b, s]: pair i turns by the row whose contiguous
    section holds it."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    pos = pos.astype(jnp.float32)
    if sections is None:
        ang = pos[..., None] * inv
    else:
        row = jnp.repeat(jnp.arange(3), jnp.asarray(sections),
                         total_repeat_length=d // 2)
        ang = jnp.take_along_axis(pos[..., None] * inv,      # [3, b, s, d/2]
                                  row[None, None, None, :], axis=0)[0]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def select(scores, t_ids, topk):
    """scores [.., rows, keys] of the queries at positions t_ids [rows] ->
    bool: the min(t + 1, topk) keys s <= t of largest score, ties to the
    lower s."""
    keys = scores.shape[-1]
    causal = jnp.arange(keys)[None, :] <= t_ids[:, None]
    x = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    if topk >= keys:
        return jnp.broadcast_to(causal, x.shape)
    thr = -jnp.sort(-x, axis=-1)[..., topk - 1:topk]     # the topk-th largest
    above, tie = x > thr, x == thr
    need = topk - jnp.sum(above, -1, keepdims=True)
    return causal & (above | (tie & (jnp.cumsum(tie, -1) <= need)))


def index_scores(cfg, p, pre, x, pos, quant=None):
    """(qI [b, s, J, D], kI [b, s, D], w [b, s, J]) of the indexer on x."""
    sa = cfg["sa_config"]
    j, d = sa["indexer_num_heads"], sa["indexer_head_dim"]
    b, s, _ = x.shape
    q = _mm(x, p[pre + "idx.q"], quant).reshape(b, s, j, d)
    k = _layer_norm(_mm(x, p[pre + "idx.k"], quant), p[pre + "idx.k_norm.w"],
                    p[pre + "idx.k_norm.b"], cfg["rms_norm_eps"])
    q = rope(q, pos[0], cfg["rope_theta"])
    k = rope(k[:, :, None, :], pos[0], cfg["rope_theta"])[:, :, 0]
    w = _mm(x, p[pre + "idx.w"], quant) * (j ** -0.5 * d ** -0.5)
    return q, k, w


def sparse_attention(cfg, q, k, v, qi, ki, wi, fault=None):
    """Steps 3-5 and 7 over query blocks: (a [b, s, H, d], the summed
    indexer KL over the rows, pairs selected)."""
    b, s, nh, d = q.shape
    rep = nh // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    topk = cfg["sa_config"]["topk"]
    blk = min(Q_BLOCK, s)
    assert s % blk == 0

    @jax.checkpoint
    def one(q_blk, qi_blk, wi_blk, i0):
        t_ids = i0 + jnp.arange(blk)
        z = jnp.einsum("bqjd,bkd->bqjk", qi_blk, ki, precision=HIGHEST)
        scores = jnp.sum(jax.nn.relu(z) * wi_blk[..., None], axis=2)
        sel = select(jax.lax.stop_gradient(scores), t_ids,
                     s if fault == "selection_ignored" else topk)
        att = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k,
                         precision=HIGHEST) * d ** -0.5
        att = jax.nn.softmax(jnp.where(sel[:, None], att, -1e30), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", att, v, precision=HIGHEST)
        target = jax.lax.stop_gradient(jnp.mean(att, axis=1))
        log_i = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
        live = sel & (target > 0)
        kl = jnp.where(live, target * (jnp.log(jnp.where(live, target, 1.0))
                                       - jnp.where(live, log_i, 0.0)), 0.0)
        return out, jnp.sum(kl), jnp.sum(sel)

    blocks = lambda x: x.reshape(b, s // blk, blk, *x.shape[2:]).swapaxes(0, 1)
    out, kl, n = jax.lax.map(lambda a: one(*a), (
        blocks(q), blocks(qi), blocks(wi), jnp.arange(0, s, blk)))
    return out.swapaxes(0, 1).reshape(b, s, nh, d), jnp.sum(kl), jnp.sum(n)


def _swiglu(x, gate, up, down, quant):
    return _mm(jax.nn.silu(_mm(x, gate, quant)) * _mm(x, up, quant), down,
               quant)


def route(cfg, x, w_router, quant=None):
    """(chosen experts [.., k], their weights [.., k])."""
    pr = jax.nn.softmax(_mm(x, w_router, quant), axis=-1)
    _, idx = jax.lax.top_k(pr, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(pr, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


def experts_held(cfg, p, pre, x, quant=None, start=None, count=None):
    """The routed part that experts [start, start + count) give (default:
    the share the configuration holds), each applied densely to every
    token with its weight zero where it was not chosen (a scan over the
    experts, each recomputed in the backward pass)."""
    start = cfg.get("expert_start", 0) if start is None else start
    count = cfg["num_experts"] if count is None else count
    idx, w = route(cfg, x, p[pre + "router"], quant)

    @jax.checkpoint
    def one(y, e):
        number, gate, up, down = e
        we = jnp.sum(jnp.where(idx == number, w, 0.0), -1, keepdims=True)
        return y + we * _swiglu(x, gate, up, down, quant), None

    first = start - cfg.get("expert_start", 0)   # within the weights held
    held = lambda name: p[pre + name][first:first + count]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        start + jnp.arange(count), held("experts.gate"), held("experts.up"),
        held("experts.down")))
    return y


def attention_part(cfg, p, i, x, pos, quant=None, fault=None):
    """Steps 1-5 and 7 of layer i: (a Wo [b, s, hidden], summed KL, pairs
    selected)."""
    b, s, h = x.shape
    nh, nkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, pre = cfg["rms_norm_eps"], f"h.{i}."
    sections = cfg["rope_scaling"]["mrope_section"]
    y = _rms(x, p[pre + "input_norm"], eps)
    q = _rms(_mm(y, p[pre + "q"], quant).reshape(b, s, nh, d),
             p[pre + "q_norm"], eps)
    k = _rms(_mm(y, p[pre + "k"], quant).reshape(b, s, nkv, d),
             p[pre + "k_norm"], eps)
    v = _mm(y, p[pre + "v"], quant).reshape(b, s, nkv, d)
    q = rope(q, pos, cfg["rope_theta"], sections)
    k = rope(k, pos, cfg["rope_theta"], sections)
    qi, ki, wi = index_scores(cfg, p, pre, jax.lax.stop_gradient(y), pos,
                              quant)
    a, kl, n = sparse_attention(cfg, q, k, v, qi, ki, wi, fault)
    return _mm(a.reshape(b, s, nh * d), p[pre + "o"], quant), kl, n


def _block(cfg, p, i, x, pos, quant, fault=None):
    a, kl, _ = attention_part(cfg, p, i, x, pos, quant, fault)
    x = x + a
    y = _rms(x, p[f"h.{i}.post_attn_norm"], cfg["rms_norm_eps"])
    return x + experts_held(cfg, p, f"h.{i}.", y, quant), kl


def text_positions(ids):
    return jnp.broadcast_to(jnp.arange(ids.shape[1]), (3,) + ids.shape)


def hidden(cfg, p, ids, pos=None, quant=None, remat=False, fault=None):
    """ids [b, s] int32 -> (final-norm hidden states [b, s, h], the layers'
    indexer KL summed over rows and layers)."""
    pos = text_positions(ids) if pos is None else pos
    x, kl = p["wte"][ids], jnp.float32(0)
    for i in range(cfg["num_hidden_layers"]):
        f = lambda pp, xx, i=i: _block(cfg, pp, i, xx, pos, quant, fault)
        x, k = jax.checkpoint(f)(p, x) if remat else f(p, x)
        kl = kl + k
    return _rms(x, p["norm"], cfg["rms_norm_eps"]), kl


def logits(cfg, p, ids, pos=None, quant=None):
    return _mm(hidden(cfg, p, ids, pos, quant)[0], p["lm_head"].T, quant)


def loss_sums(cfg, p, ids, labels, quant=None, fault=None, pos=None):
    """(summed next-token cross entropy, summed indexer KL) over a block of
    rows; the head and the softmax in slices of HEAD_ROWS positions, each
    recomputed in the backward pass."""
    h, kl = hidden(cfg, p, ids, pos, quant, remat=True, fault=fault)
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
    return jnp.sum(parts), kl


def loss_sum(cfg, p, ids, labels, quant=None, fault=None, pos=None):
    ce, kl = loss_sums(cfg, p, ids, labels, quant, fault, pos)
    return ce if fault == "indexer_loss_dropped" else ce + kl


def loss_and_grads(cfg, p, ids, labels, quant=None, fault=None):
    """Mean loss (both terms: each is a sum over rows, over the same count
    of tokens) and its gradients over a batch given in blocks of rows
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
    no second copy is held while the steps run; the moments wait on the
    host while a gradient is computed).  `leaves` maps a tree onto the
    leaves that are compared; `fault` (one of FAULTS) breaks the mathematics
    the way a wrong program would."""
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
