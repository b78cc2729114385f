"""GPT family — the flagship pretrain model (BASELINE configs 2/3: GPT-3
1.3B / 6.7B under DP+sharding / TP).

Role parity: the reference's Fleet GPT fixture (`test/auto_parallel/
get_gpt_model.py` + PaddleNLP-style mpu usage, SURVEY §3.3). Built from
`distributed.mpu` layers so dp/mp/sep sharding falls out of annotations;
`use_rope=True` + RMSNorm + SwiGLU gives the LLaMA variant (config 4).

TPU-first choices: bf16-friendly module defaults, flash attention via the
Pallas path ([B,S,H,D] layout), `lax`-free python (everything traces into
one XLA program), optional per-block recompute (jax rematerialization).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from .. import nn
from ..distributed import mpu
from ..distributed.recompute import recompute as _recompute
from ..nn import functional as F
from ..observability import metrics as _metrics
from .generation import GenerationMixin, _static_cache_attention

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion", "gpt_tiny", "gpt_1p3b", "gpt_6p7b",
           "llama_7b"]


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12,
                 num_heads=12, max_seq_len=1024, ffn_hidden=None,
                 dropout=0.0, attn_dropout=0.0, use_rope=False,
                 use_rmsnorm=False, use_swiglu=False, tie_embeddings=True,
                 recompute=False, recompute_policy=None,
                 sequence_parallel=False,
                 context_parallel=False, layer_norm_eps=1e-5,
                 fused_head_ce=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.max_seq_len = max_seq_len
        self.ffn_hidden = ffn_hidden or (
            int(8 * hidden_size / 3 / 128 + 1) * 128 if use_swiglu
            else 4 * hidden_size)
        self.dropout = dropout
        self.attn_dropout = attn_dropout
        self.use_rope = use_rope
        self.use_rmsnorm = use_rmsnorm
        self.use_swiglu = use_swiglu
        self.tie_embeddings = tie_embeddings
        self.recompute = recompute
        # named remat policy: None (replay all but the marked values:
        # distributed/recompute.py) | 'full' | 'dots' | 'dots_no_batch'
        self.recompute_policy = recompute_policy
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel
        self.layer_norm_eps = layer_norm_eps
        # training returns hidden states; GPTPretrainingCriterion fuses
        # the LM-head projection into the chunked CE ("cut cross
        # entropy" — the [B,S,V] logits never materialize)
        self.fused_head_ce = fused_head_ce


def _in_trace():
    from ..core import flags

    return flags.in_trace()


def _norm(cfg):
    if cfg.use_rmsnorm:
        return nn.RMSNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)
    return nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_eps)


class GPTAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        # fused qkv: column-parallel over heads
        self.qkv_proj = mpu.ColumnParallelLinear(
            cfg.hidden_size, 3 * cfg.hidden_size, gather_output=False)
        self.out_proj = mpu.RowParallelLinear(
            cfg.hidden_size, cfg.hidden_size, input_is_parallel=True)

    def forward(self, x, cache=None, kv_cache=None, cache_pos=None,
                attn_start=None):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)
        qkv = qkv.reshape([b, s, 3, self.num_heads, self.head_dim])
        q, k, v = qkv.unbind(axis=2)
        if self.cfg.use_rope:
            position_ids = None
            if kv_cache is not None:
                # static-cache path: phases continue from the traced
                # offset; left-padded rows shift so their first REAL
                # token sits at rotary position 0
                from .generation import decode_position_ids

                position_ids = decode_position_ids(cache_pos, b, s,
                                                   attn_start)
            elif cache is not None:
                # legacy concat cache: offset is a host int
                import numpy as _np

                offset = cache[0].shape[1]
                position_ids = _np.arange(offset, offset + s)[None, :].repeat(
                    b, axis=0)
            q, k, _ = F.fused_rotary_position_embedding(
                q, k, None, position_ids=position_ids)
        if kv_cache is not None:
            out, new_cache = _static_cache_attention(
                q, k, v, kv_cache, cache_pos, attn_start)
            out = out.reshape([b, s, h])
            out = self.out_proj(out)
            return out, new_cache
        if cache is not None:
            pk, pv = cache
            from .. import ops

            k = ops.concat([pk, k], axis=1)
            v = ops.concat([pv, v], axis=1)
            cache = (k, v)
        if self.cfg.context_parallel and _in_trace():
            # ring attention over the sep axis (long-context path)
            from ..core.dispatch import apply
            from ..ops.pallas.ring_attention import ring_attention

            out = apply(
                "ring_attention",
                lambda qv, kv, vv: ring_attention(qv, kv, vv, causal=True),
                q, k, v)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.cfg.attn_dropout if self.training else 0.0,
                training=self.training)
        out = out.reshape([b, s, h])
        out = self.out_proj(out)
        if cache is not None:
            return out, cache
        return out


class GPTMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        if cfg.use_swiglu:
            self.gate_up_proj = mpu.ColumnParallelLinear(
                cfg.hidden_size, 2 * cfg.ffn_hidden, gather_output=False)
        else:
            self.up_proj = mpu.ColumnParallelLinear(
                cfg.hidden_size, cfg.ffn_hidden, gather_output=False)
        self.down_proj = mpu.RowParallelLinear(
            cfg.ffn_hidden, cfg.hidden_size, input_is_parallel=True)

    def forward(self, x):
        if self.cfg.use_swiglu:
            x = F.swiglu(self.gate_up_proj(x))
        else:
            x = F.gelu(self.up_proj(x), approximate=True)
        return self.down_proj(x)


class GPTBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.ln_1 = _norm(cfg)
        self.attn = GPTAttention(cfg)
        self.ln_2 = _norm(cfg)
        self.mlp = GPTMLP(cfg)
        self.drop = nn.Dropout(cfg.dropout)

    def _body(self, x):
        if self.cfg.sequence_parallel:
            x = mpu.sequence_parallel_constraint(x)
        x = x + self.drop(self.attn(self.ln_1(x)))
        x = x + self.drop(self.mlp(self.ln_2(x)))
        return x

    def forward(self, x, kv_cache=None, cache_pos=None, attn_start=None):
        if kv_cache is not None:
            a, new_cache = self.attn(self.ln_1(x), kv_cache=kv_cache,
                                     cache_pos=cache_pos,
                                     attn_start=attn_start)
            x = x + a
            x = x + self.mlp(self.ln_2(x))
            return x, new_cache
        if self.cfg.recompute and self.training:
            return _recompute(self._body, x,
                              policy=self.cfg.recompute_policy)
        return self._body(x)


class GPTModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.wte = mpu.VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        if not cfg.use_rope:
            self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.h = nn.LayerList([GPTBlock(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = _norm(cfg)

    def forward(self, input_ids, kv_caches=None, cache_pos=None,
                attn_start=None):
        from .. import ops

        x = self.wte(input_ids)
        if not self.cfg.use_rope:
            if kv_caches is not None:
                from .generation import decode_position_ids

                pos = decode_position_ids(
                    cache_pos, input_ids.shape[0], input_ids.shape[1],
                    attn_start)
            else:
                pos = ops.arange(0, input_ids.shape[1], dtype="int32")
            x = x + self.wpe(pos)
        x = self.drop(x)
        if kv_caches is not None:
            new_caches = []
            for block, kc in zip(self.h, kv_caches):
                x, nc = block(x, kv_cache=kc, cache_pos=cache_pos,
                              attn_start=attn_start)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        for block in self.h:
            x = block(x)
        return self.ln_f(x)


class GPTForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_embeddings:
            self.lm_head = mpu.ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, has_bias=False)

    def fused_head_weight(self):
        """The [vocab, hidden] weight `GPTPretrainingCriterion(model=)`
        projects with: the tied embedding (live: the train step binds
        it).  The untied head is held [hidden, vocab] and has no fused
        path."""
        assert self.cfg.tie_embeddings, \
            "fused head+CE currently requires tied embeddings"
        return self.gpt.wte.weight

    def forward(self, input_ids, kv_caches=None, cache_pos=None,
                attn_start=None):
        if kv_caches is not None:
            x, new_caches = self.gpt(input_ids, kv_caches=kv_caches,
                                     cache_pos=cache_pos,
                                     attn_start=attn_start)
        else:
            x = self.gpt(input_ids)
        if self.cfg.fused_head_ce and self.training and kv_caches is None:
            # hidden states out; GPTPretrainingCriterion(model=...) owns
            # the projection (fused with the CE — no [B,S,V] logits).
            # The marker (via the Tensor's name slot) makes a
            # mismatched plain criterion fail loudly instead of treating
            # hidden states as logits.
            x.name = "fused_head_hidden"
            return x
        # the head is no sublayer of its own when the embedding is tied:
        # an explicit scope names its work in the program
        with jax.named_scope("head"):
            if self.cfg.tie_embeddings:
                logits = x.matmul(self.gpt.wte.weight, transpose_y=True)
            else:
                logits = self.lm_head(x)
        if kv_caches is not None:
            return logits, new_caches
        return logits

    def init_kv_caches(self, batch, max_len):
        from .generation import init_kv_caches

        cfg = self.cfg
        dtype = self.gpt.wte.weight.dtype
        return init_kv_caches(cfg.num_layers, batch, cfg.num_heads,
                              cfg.hidden_size // cfg.num_heads, max_len,
                              dtype)


def _ce_fwd_chunk(carry, blk, base, safe_labels, chunk):
    """One online-logsumexp CE step over a [N, chunk] f32 logits block:
    the running max/sum/picked math of `_chunked_softmax_ce`."""
    m, l, picked = carry
    bm = jnp.max(blk, axis=1)
    m_new = jnp.maximum(m, bm)
    l_new = l * jnp.exp(m - m_new) + \
        jnp.sum(jnp.exp(blk - m_new[:, None]), axis=1)
    in_chunk = (safe_labels >= base) & (safe_labels < base + chunk)
    idx = jnp.clip(safe_labels - base, 0, chunk - 1)
    val = jnp.take_along_axis(blk, idx[:, None], axis=1)[:, 0]
    picked = jnp.where(in_chunk, val, picked)
    return (m_new, l_new, picked)


def _ce_bwd_chunk(blk, base, lse, safe_labels, valid, chunk):
    """d(loss)/d(logits block): softmax recompute minus the one-hot,
    masked to valid tokens (`_chunked_softmax_ce`'s backward scan)."""
    p = jnp.exp(blk - lse[:, None])
    idx = safe_labels - base
    onehot = (jnp.arange(chunk)[None, :] == idx[:, None])
    return (p - onehot) * valid[:, None]


def _chunked_softmax_ce(logits, labels, ignore_index, n_chunks=8):
    """Cross entropy over a large vocab without materializing float32
    logits: an online-logsumexp `lax.scan` over vocab chunks (flash-style
    running max/sum) reads the bf16 logits once; the backward recomputes
    the per-chunk softmax and emits d(logits) in the input dtype. Cuts
    the f32 [B*S, V] intermediates (several GB at GPT vocab) out of the
    loss — HBM-bandwidth relief on TPU.

    Returns (total_loss_f32, valid_count_f32) over non-ignored tokens.
    """
    import jax

    n, v = logits.shape
    # pad vocab to a multiple of n_chunks with -inf columns
    chunk = -(-v // n_chunks)
    pad = chunk * n_chunks - v

    def pad_logits(lg):
        if pad:
            return jnp.concatenate(
                [lg, jnp.full((n, pad), -1e30, lg.dtype)], axis=1)
        return lg

    valid = labels != ignore_index
    safe_labels = jnp.where(valid, labels, 0).astype(jnp.int32)

    def fwd_scan(lg):
        lgp = pad_logits(lg).reshape(n, n_chunks, chunk)

        def body(carry, ci):
            blk = lgp[:, ci, :].astype(jnp.float32)
            return _ce_fwd_chunk(carry, blk, ci * chunk, safe_labels,
                                 chunk), None

        init = (jnp.full((n,), -1e30, jnp.float32),
                jnp.zeros((n,), jnp.float32),
                jnp.zeros((n,), jnp.float32))
        (m, l, picked), _ = jax.lax.scan(body, init,
                                         jnp.arange(n_chunks))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        per_tok = jnp.where(valid, lse - picked, 0.0)
        return per_tok.sum(), lse

    @jax.custom_vjp
    def core(lg):
        return fwd_scan(lg)[0]

    def core_f(lg):
        total, lse = fwd_scan(lg)
        return total, (lg, lse)

    def core_b(res, g):
        lg, lse = res
        lgp = pad_logits(lg).reshape(n, n_chunks, chunk)

        def body(_, ci):
            blk = lgp[:, ci, :].astype(jnp.float32)
            d = _ce_bwd_chunk(blk, ci * chunk, lse, safe_labels, valid,
                              chunk)
            return None, (g * d).astype(lg.dtype)

        _, dchunks = jax.lax.scan(body, None, jnp.arange(n_chunks))
        dl = jnp.moveaxis(dchunks, 0, 1).reshape(n, n_chunks * chunk)
        return (dl[:, :v],)

    core.defvjp(core_f, core_b)
    return core(logits), valid.astype(jnp.float32).sum()


# the most float32 logits one slice of the head + CE scan may hold
_HEAD_CE_CHUNK_BYTES = 512 << 20


def _token_slices(b, s, v):
    """How `_fused_linear_ce` cuts the sequence, from the shape alone:
    ``(positions a slice, slices)``.  A slice holds all `b` rows of the
    batch over whole-vocabulary float32 logits; it aims at 1/16 of the
    tokens, fewer where that would pass `_HEAD_CE_CHUNK_BYTES`, and
    takes the largest divisor of `s` at or under its aim.  A length
    whose divisors all lie under half the aim (a prime) keeps the aim:
    the last slice is then padded with ignored labels."""
    rows = min(b * s // 16, _HEAD_CE_CHUNK_BYTES // (4 * v))
    aim = min(max(rows // b, 1), s)
    sc = next(c for c in range(aim, 0, -1) if s % c == 0)
    if 2 * sc < aim:
        sc = aim
    return sc, -(-s // sc)


def _fused_linear_ce(h, w, labels, ignore_index, weights=None):
    """Cross entropy fused WITH the LM-head projection ("cut cross
    entropy"): the [B, S, V] logits never exist.  A `lax.scan` over
    slices of the SEQUENCE (`_token_slices`) computes `h_c @ w.T` on the
    MXU in float32; a slice holds its rows' logits over the whole
    vocabulary, so its log-sum-exp, its loss and `d = (softmax - onehot)
    * valid` are complete inside the iteration.  Differentiated, the
    forward rule therefore forms the gradient itself, three matrix
    products a slice (logits, `dh_c = d @ w`, `dW += d.T @ h_c` into one
    float32 carry) and no replay; the backward rule only scales the two
    residuals by the cotangent.  Un-differentiated it runs the logits
    product and the loss alone.

    The batch axis stays whole inside every slice: under dp the train
    step shards it, and a scan along it would walk the sharded axis.

    h: [B, S, Hd]; w: [V, Hd] (tied-embedding layout); labels: [B, S].
    Returns (total_loss_f32, valid_count_f32).

    weights (per-token weights, e.g. a looped model's exit distribution):
    h is then [P * B, S, Hd], P read-outs of the same B rows stacked P
    major, and weights [P * B, S]; labels [B, S] serve all P.  The total
    is sum over read-outs and valid tokens of weight x CE, the count that
    of B x S's valid tokens; `d` is scaled by the weight, and the weights'
    own gradient — each token's CE — is formed in the forward scan too, a
    third residual.  One call carries ONE dW for all P read-outs.  With
    weights None the program is the unweighted one, op for op."""
    pb, s, hd = h.shape
    b = labels.shape[0]
    if pb % b or (weights is None and pb != b):
        raise ValueError(f"hidden rows {pb} are not P read-outs of the "
                         f"labels' {b} rows (P > 1 needs weights)")
    reps = pb // b
    v = w.shape[0]
    sc, n = _token_slices(pb, s, v)
    _metrics.inc("head_ce.scan", axis="tokens", chunks=n)
    if weights is not None:
        _metrics.inc("head_ce.weights", kind="per_token")
    pad = n * sc - s
    if pad:
        labels = jnp.pad(labels, ((0, 0), (0, pad)),
                         constant_values=ignore_index)

    def padded(hh):
        return jnp.pad(hh, ((0, 0), (0, pad), (0, 0))) if pad else hh

    def chunk(hp, ww, wp, i):
        hc = jax.lax.dynamic_slice_in_dim(hp, i * sc, sc, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(labels, i * sc, sc, axis=1)
        # the slice's rows as ONE axis, B major (the merge keeps a dp
        # sharding of B): on the chip the scan runs up to 7 % faster over
        # [B * sc, V] logits than over [B, sc, V]
        hc, lc = hc.reshape(pb * sc, hd), lc.reshape(b * sc)
        valid = lc != ignore_index
        safe = jnp.where(valid, lc, 0).astype(jnp.int32)
        rows_valid, rows_safe = valid, safe
        if reps > 1:         # the P read-outs' rows share their labels
            rows_valid, rows_safe = jnp.tile(valid, reps), jnp.tile(safe, reps)
        logits = jax.lax.dot_general(
            hc, ww, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)            # [B * sc, V]
        m = jnp.max(logits, axis=1)
        l = jnp.sum(jnp.exp(logits - m[:, None]), axis=1)
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        picked = jnp.take_along_axis(logits, rows_safe[:, None], axis=1)[:, 0]
        ce = jnp.where(rows_valid, lse - picked, 0.0)
        wc = None
        if wp is not None:
            wc = jax.lax.dynamic_slice_in_dim(
                wp, i * sc, sc, axis=1).reshape(pb * sc)
        # [summed loss, valid rows]: the count rides the scan so that,
        # under dp, its reduction over the shards FOLLOWS the loop's (the
        # CPU backend leaves dW's all-reduce inside the loop, and two
        # collectives free to start in either order deadlock there)
        sums = ((ce if wc is None else wc * ce).sum(),
                valid.astype(jnp.float32).sum())
        return sums, (hc, logits, lse, rows_safe, rows_valid, ce, wc)

    def padded_weights(wt):
        if wt is None or not pad:
            return wt
        return jnp.pad(wt, ((0, 0), (0, pad)))

    @jax.custom_vjp
    def core(hh, ww, wt):
        hp, wp = padded(hh), padded_weights(wt)

        def body(sums, i):
            return jax.tree.map(jnp.add, sums, chunk(hp, ww, wp, i)[0]), None

        sums, _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32),) * 2,
                               jnp.arange(n))
        return sums

    def core_f(hh, ww, wt):
        _metrics.inc("head_ce.grad", where="forward")
        hp, wp = padded(hh), padded_weights(wt)

        def body(carry, i):
            sums, dh, dw, dwt = carry
            part, (hc, logits, lse, safe, valid, ce, wc) = chunk(hp, ww, wp, i)
            # XLA fuses `d` into both products as their operand's
            # producer; materialised once in bf16 (an optimization
            # barrier) the scan is 6-10 ms a step slower on the chip
            onehot = jnp.arange(v) == safe[:, None]
            d = ((jnp.exp(logits - lse[:, None]) - onehot)
                 * (valid[:, None] if wc is None
                    else jnp.where(valid, wc, 0.0)[:, None])
                 ).astype(hh.dtype)                        # [B * sc, V]
            dhc = jax.lax.dot_general(
                d, ww, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [B * sc, Hd]
            dw = dw + jax.lax.dot_general(
                d, hc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [V, Hd]
            dh = jax.lax.dynamic_update_slice_in_dim(
                dh, dhc.reshape(pb, sc, hd), i * sc, axis=1)
            if dwt is not None:     # d(total)/d(weight) = the token's CE
                dwt = jax.lax.dynamic_update_slice_in_dim(
                    dwt, ce.reshape(pb, sc), i * sc, axis=1)
            return (jax.tree.map(jnp.add, sums, part), dh, dw, dwt), None

        init = ((jnp.zeros((), jnp.float32),) * 2,
                jnp.zeros((pb, n * sc, hd), jnp.float32),
                jnp.zeros((v, hd), jnp.float32),
                None if wt is None else jnp.zeros((pb, n * sc), jnp.float32))
        (sums, dh, dw, dwt), _ = jax.lax.scan(body, init, jnp.arange(n))
        return sums, (dh[:, :s], dw, None if dwt is None else dwt[:, :s])

    def core_b(res, g):
        dh, dw, dwt = res        # of the summed loss; the count has none
        return ((g[0] * dh).astype(h.dtype), (g[0] * dw).astype(w.dtype),
                None if dwt is None else (g[0] * dwt).astype(weights.dtype))

    core.defvjp(core_f, core_b)
    return core(h, w, weights)


class GPTPretrainingCriterion(nn.Layer):
    """Token-level LM loss with masked mean (parity: the Fleet GPT criterion;
    vocab-parallel CE comes from the logits' mp annotation).

    fused=True (default for large vocabs) uses the chunked online-
    logsumexp CE above; fused=False is the plain F.cross_entropy path.
    Both produce identical values (tested to 1e-5).

    model= (with cfg.fused_head_ce=True on the model): the criterion
    receives HIDDEN states and fuses the LM-head projection into the
    CE (`_fused_linear_ce`, a scan over slices of the sequence) — the
    [B,S,V] logits and their cotangent never exist. Reads the model's
    [vocab, hidden] head weight
    through `model.fused_head_weight()` (GPT: the tied embedding;
    models/afmoe.py: an untied head) — the live parameter, so the train
    step's bind_state makes it differentiable like any other param.

    A second loss term: where the model given as model= has a method
    `pop_aux_loss()` (models/keye.py: the sum of its layers' indexer
    losses, registered by the forward pass that made `logits`), the
    criterion takes that scalar and adds it to the token loss.  The same
    door may hand a pair `(weights, scalar)` (models/ouro.py: the exit
    distribution [P, B, S] over the P read-outs the model stacked into
    its hidden states, and the entropy term): each read-out's token CE is
    then scaled by its weight in the one scan (`_fused_linear_ce`'s
    `weights`, under the same scope `head_ce`) before the scalar is
    added."""

    def __init__(self, ignore_index=-100, fused=True, model=None):
        super().__init__()
        self.ignore_index = ignore_index
        self.fused = fused
        self._model = model
        if model is not None:
            model.fused_head_weight()   # refuses a model without one

    def forward(self, logits, labels):
        pop = getattr(self._model, "pop_aux_loss", None)
        aux = pop() if pop is not None else None
        weights = None
        if isinstance(aux, tuple):
            weights, aux = aux
        loss = self._token_loss(logits, labels, weights)
        return loss if aux is None else loss + aux

    def _token_loss(self, logits, labels, weights=None):
        lv = logits._value if hasattr(logits, "_value") else logits
        yv = labels._value if hasattr(labels, "_value") else labels
        is_hidden = getattr(logits, "name", None) == "fused_head_hidden"
        if is_hidden and (self._model is None or not self.fused):
            # either mismatch silently scores hidden states as logits
            raise RuntimeError(
                "model was built with cfg.fused_head_ce=True (returns "
                "hidden states in training) but the criterion cannot fuse "
                "— construct GPTPretrainingCriterion(model=model) with "
                "fused=True (got model="
                f"{'set' if self._model is not None else 'None'}, "
                f"fused={self.fused})")
        if self._model is not None and self.fused and is_hidden:
            from ..core.dispatch import apply

            w = self._model.fused_head_weight()  # live (bindable) param

            def f(hh, lb, wv, wt=None):
                # [B, S, Hd] as the model hands it: the scan cuts S and
                # keeps B (the axis dp shards) whole in every slice
                s, hd = hh.shape[-2:]
                total, count = _fused_linear_ce(
                    hh.reshape(-1, s, hd), wv, lb.reshape(-1, s),
                    self.ignore_index,
                    None if wt is None else wt.reshape(-1, s))
                return total / jnp.maximum(count, 1.0)

            # head projection + CE in one scan over slices of the
            # sequence: one scope, `head_ce`
            args = (logits, labels, w) + (() if weights is None else (weights,))
            with jax.named_scope("head_ce"):
                return apply("fused_linear_ce", f, *args)
        if weights is not None:
            raise RuntimeError("per-token weights are read by the fused head "
                               "+ CE scan only: the model must hand it its "
                               "hidden states")
        if self.fused and lv.shape[-1] >= 8192:
            from ..core.dispatch import apply

            def f(lg, lb):
                n = 1
                for d in lg.shape[:-1]:
                    n *= d
                total, count = _chunked_softmax_ce(
                    lg.reshape(n, lg.shape[-1]), lb.reshape(n),
                    self.ignore_index)
                return total / jnp.maximum(count, 1.0)

            with jax.named_scope("ce"):
                return apply("fused_softmax_ce", f, logits, labels)
        with jax.named_scope("ce"):
            return F.cross_entropy(logits, labels, reduction="mean",
                                   ignore_index=self.ignore_index)


class GPTEmbeddingStage(nn.Layer):
    """First pipeline stage: token (+position) embedding."""

    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.wte = mpu.VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        if not cfg.use_rope:
            self.wpe = nn.Embedding(cfg.max_seq_len, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids):
        from .. import ops

        x = self.wte(input_ids)
        if not self.cfg.use_rope:
            pos = ops.arange(0, input_ids.shape[1], dtype="int32")
            x = x + self.wpe(pos)
        return self.drop(x)


class GPTHeadStage(nn.Layer):
    """Last pipeline stage: final norm + LM head."""

    def __init__(self, cfg):
        super().__init__()
        self.ln_f = _norm(cfg)
        self.lm_head = mpu.ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, has_bias=False)

    def forward(self, x):
        return self.lm_head(self.ln_f(x))


def gpt_pipe_layers(cfg):
    """Flat layer list for PipelineLayer (GPTForCausalLMPipe role; pipeline
    requires untied embeddings — the reference shares them via
    SharedLayerDesc + grad allreduce, planned for the interleaved milestone)."""
    assert not cfg.tie_embeddings, "pipeline GPT needs tie_embeddings=False"
    return ([GPTEmbeddingStage(cfg)] +
            [GPTBlock(cfg) for _ in range(cfg.num_layers)] +
            [GPTHeadStage(cfg)])


def gpt_tiny(**kw):
    kw.setdefault("vocab_size", 1024)
    kw.setdefault("hidden_size", 128)
    kw.setdefault("num_layers", 2)
    kw.setdefault("num_heads", 4)
    kw.setdefault("max_seq_len", 128)
    return GPTConfig(**kw)


def gpt_1p3b(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_heads=16, max_seq_len=2048, **kw)


def gpt_6p7b(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                     num_heads=32, max_seq_len=2048, **kw)


def llama_7b(**kw):
    kw.setdefault("use_rope", True)
    kw.setdefault("use_rmsnorm", True)
    kw.setdefault("use_swiglu", True)
    kw.setdefault("tie_embeddings", False)
    return GPTConfig(vocab_size=32000, hidden_size=4096, num_layers=32,
                     num_heads=32, max_seq_len=2048, **kw)
