"""The language model of Keye-VL-2.0 (`model_type: KeyeVL2`, Kwai-Keye): a
Qwen3-MoE-shaped decoder whose attention sees, for each query, only the
`topk` keys that a learned indexer selects (DeepSeek Sparse Attention).

The layer (benchmark/configs/keye-vl2-ep8.json lists what the public
`config.json` has no key for):

  * embedding unscaled, untied head, pre-norm residuals with two RMS norms
    a layer: `h += attn(input_norm(h))`, `h += moe(post_attn_norm(h))`;
  * grouped-query attention, an RMS norm over each head of q and k, M-RoPE
    (three position rows — temporal, height, width — own contiguous
    sections of the rotary pairs; equal rows for text), no biases;
  * an indexer (`nn.SparseIndexer`) scores every causal key with 16 cheap
    heads against one shared key head; query t attends the min(t + 1,
    topk) keys of largest score (`F.sparse_select_topk`,
    `F.selected_attention`), and the indexer learns from its own loss — the
    KL from the attention's head-mean probabilities over the selected keys
    to the softmax of its scores there — which reaches no other parameter;
  * every layer's MLP is the routed-expert layer
    (`incubate...routed_moe.RoutedMoELayer`, `score_func="softmax"`:
    softmax over all experts, `num_experts_per_tok` of them renormalised,
    no shared expert) — told which experts this chip holds.

The model's loss is the language-model loss plus the sum over the layers of
the indexer's.  ONE mechanism carries the second term: a forward pass
REGISTERS it on the model, and `GPTPretrainingCriterion(model=...)` takes it
from there (`pop_aux_loss()`: once, so no stale value outlives its step) and
adds it to the token loss.

The vision tower is not here: the published configuration gives it no
widths.  `forward(input_ids, position_ids)` takes the three position rows
an image tower would supply.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed import mpu
from ..distributed.recompute import keeping as _keeping
from ..distributed.recompute import recompute as _recompute
from ..incubate.distributed.models.routed_moe import RoutedMoELayer
from ..nn import functional as F
from ..nn.initializer import Normal
from ..observability import metrics as _metrics
from .afmoe import _linear

__all__ = ["KeyeConfig", "KeyeModel", "KeyeForCausalLM", "keye_tiny",
           "pair_counters", "PAIR_KINDS"]

# what a layer's attention counted in its last step, one int32 vector a
# layer: (query, key) pairs selected, pairs its kernel multiplied, causal
# pairs — `sparse_attn.pairs{kind=selected|computed|causal}`
PAIR_KINDS = ("selected", "computed", "causal")


class KeyeConfig:
    def __init__(self, vocab_size=151936, hidden_size=2048, num_layers=48,
                 num_heads=32, num_kv_heads=4, head_dim=128,
                 moe_intermediate_size=768, num_experts=128,
                 num_experts_held=None, expert_start=0,
                 num_experts_per_tok=8, norm_topk_prob=True,
                 rope_theta=1e7, mrope_section=(16, 24, 24), rms_eps=1e-6,
                 indexer_heads=16, indexer_head_dim=64, indexer_topk=2048,
                 initializer_range=0.02, recompute=False,
                 fused_head_ce=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.moe_intermediate_size = moe_intermediate_size
        # the router's width, and the experts of it that live here
        self.num_experts = num_experts
        self.num_experts_held = (num_experts if num_experts_held is None
                                 else num_experts_held)
        self.expert_start = expert_start
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.rope_theta = rope_theta
        self.mrope_section = tuple(mrope_section)
        self.rms_eps = rms_eps
        self.indexer_heads = indexer_heads
        self.indexer_head_dim = indexer_head_dim
        self.indexer_topk = indexer_topk
        self.initializer_range = initializer_range
        self.recompute = recompute
        self.fused_head_ce = fused_head_ce
        if sum(self.mrope_section) != head_dim // 2:
            raise ValueError(f"keye: mrope_section {self.mrope_section} does "
                             f"not cover {head_dim // 2} rotary pairs")


class KeyeAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_heads * d, True)
        self.k_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.v_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.o_proj = _linear(cfg, cfg.num_heads * d, h, False)
        self.q_norm = nn.RMSNorm(d, epsilon=cfg.rms_eps)
        self.k_norm = nn.RMSNorm(d, epsilon=cfg.rms_eps)
        self.indexer = nn.SparseIndexer(
            h, cfg.indexer_heads, cfg.indexer_head_dim,
            rope_theta=cfg.rope_theta, epsilon=cfg.rms_eps,
            initializer_range=cfg.initializer_range)
        self.register_buffer("pair_counts",
                             jnp.zeros((len(PAIR_KINDS),), jnp.int32))

    def compute(self, x, pos):
        """x [B, T, hidden], pos [3, B, T] -> (out [B, T, hidden], the
        indexer's loss, pair counts [3]); nothing written to the buffer
        (see `RoutedMoELayer.compute`)."""
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        if _keeping():
            _metrics.inc("sparse_attn.recompute_kept", what="selection")
        with jax.named_scope("attn.sparse"):
            q = self.q_norm(self.q_proj(x).reshape([b, s, cfg.num_heads, d]))
            k = self.k_norm(
                self.k_proj(x).reshape([b, s, cfg.num_kv_heads, d]))
            v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, d])
            q, k, _ = F.fused_rotary_position_embedding(
                q, k, None, position_ids=pos, rotary_emb_base=cfg.rope_theta,
                mrope_section=cfg.mrope_section)
        with jax.named_scope("attn.indexer"):
            scores = self.indexer(x, pos[0])
            selected, n_selected = F.sparse_select_topk(scores,
                                                        cfg.indexer_topk)
        with jax.named_scope("attn.sparse"):
            out, stats = F.selected_attention(q, k, v, selected)
            out = self.o_proj(out.reshape([b, s, cfg.num_heads * d]))
        with jax.named_scope("attn.indexer"):
            probs = F.selected_attention_probs(stats, selected)
            loss = F.sparse_indexer_loss(scores, selected, probs)
        computed, causal = F.selected_attention_pairs(q)
        counts = jnp.stack([n_selected._value, jnp.int32(computed),
                            jnp.int32(causal)])
        return out, loss, Tensor(counts)

    def note(self, counts):
        self.pair_counts._value = getattr(counts, "_value", counts)


class KeyeBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        self.input_norm = nn.RMSNorm(h, epsilon=cfg.rms_eps)
        self.attn = KeyeAttention(cfg)
        self.post_attn_norm = nn.RMSNorm(h, epsilon=cfg.rms_eps)
        self.moe = RoutedMoELayer(
            h, cfg.moe_intermediate_size, cfg.num_experts,
            cfg.num_experts_per_tok, num_held=cfg.num_experts_held,
            expert_start=cfg.expert_start, shared_width=None,
            route_norm=cfg.norm_topk_prob, score_func="softmax",
            initializer_range=cfg.initializer_range)

    def _body(self, x, pos):
        """(h, the indexer's loss, pair counts, per-expert rows, row
        counts): the counters leave the (possibly recomputed) body as
        values and reach the layers' buffers outside it."""
        a, loss, pairs = self.attn.compute(self.input_norm(x), pos)
        x = x + a
        with jax.named_scope("moe"):
            m, sizes, counts = self.moe.compute(self.post_attn_norm(x))
        return x + m, loss, pairs, sizes, counts

    def forward(self, x, pos):
        if self.cfg.recompute and self.training:
            out = _recompute(self._body, x, pos)
        else:
            out = self._body(x, pos)
        x, loss, pairs, sizes, counts = out
        self.attn.note(pairs)
        self.moe.note(sizes, counts)
        return x, loss


class KeyeModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = mpu.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList([KeyeBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids, position_ids=None):
        """(final-norm hidden states, the sum of the layers' indexer
        losses).  position_ids [3, B, T]; text positions by default."""
        b, t = input_ids.shape
        if position_ids is None:
            position_ids = Tensor(jnp.broadcast_to(
                jnp.arange(t, dtype=jnp.int32), (3, b, t)))
        x = self.embed_tokens(input_ids)
        aux = None
        for blk in self.layers:
            x, loss = blk(x, position_ids)
            aux = loss if aux is None else aux + loss
        return self.norm(x), aux


class KeyeForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = KeyeModel(cfg)
        # untied head, held [vocab, hidden] (see AfmoeForCausalLM)
        self.lm_head = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size],
            default_initializer=Normal(0.0, cfg.initializer_range))
        self._aux_loss = None

    def fused_head_weight(self):
        """The [vocab, hidden] head weight `GPTPretrainingCriterion`
        projects with (live: the train step binds it)."""
        return self.lm_head

    def pop_aux_loss(self):
        """The last forward pass's second loss term (the layers' indexer
        losses summed), handed out once; None where none is waiting."""
        aux, self._aux_loss = self._aux_loss, None
        return aux

    def forward(self, input_ids, position_ids=None):
        x, self._aux_loss = self.model(input_ids, position_ids)
        if self.cfg.fused_head_ce and self.training:
            x.name = "fused_head_hidden"   # see GPTForCausalLM.forward
            return x
        with jax.named_scope("head"):
            return x.matmul(self.lm_head, transpose_y=True)


def pair_counters(buffers):
    """{layer: {"selected": n, "computed": n, "causal": n}} from a name ->
    array dict of buffers (a train step's `_state["buffers"]`, a model's
    `named_buffers`)."""
    import numpy as np

    out = {}
    for name, v in buffers.items():
        layer, _, leaf = name.rpartition(".")
        if leaf == "pair_counts":
            out[layer] = dict(zip(PAIR_KINDS, (int(n) for n in np.asarray(
                getattr(v, "_value", v)))))
    return out


def keye_tiny(**kw):
    d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
             num_kv_heads=2, head_dim=16, moe_intermediate_size=32,
             num_experts=8, num_experts_held=8, num_experts_per_tok=2,
             mrope_section=(2, 3, 3), indexer_heads=4, indexer_head_dim=8,
             indexer_topk=16)
    d.update(kw)
    return KeyeConfig(**d)
