"""AFMoE family (`model_type: afmoe`, Arcee Trinity) — decoder-only with
window and full attention mixed, gated attention, and routed experts beside
a shared one.

The layer, from the family's public `config.json` and modeling file:

  * embedding scaled by sqrt(hidden) (`mup_enabled`), untied output head;
  * four RMS norms a layer: `h += post_attn_norm(attn(input_norm(h)))`,
    `h += post_mlp_norm(mlp(pre_mlp_norm(h)))`;
  * grouped-query attention with an RMS norm over each head of q and k,
    rotary positions on the `sliding_attention` layers ONLY (the
    `full_attention` layers carry no positions), a sigmoid gate on the
    attention's output (`out = (attn * sigmoid(x Wg)) Wo`), no biases;
  * the first `num_dense_layers` layers have a dense SwiGLU MLP, the rest
    the routed-expert layer (`incubate...routed_moe.RoutedMoELayer`: sigmoid
    router over all experts, `num_experts_per_tok` of them, one shared
    expert, no token dropped) — told which experts this chip holds.

Shares with the other models: GQA / RoPE / RMSNorm / SwiGLU pieces
(`models/llama.py`'s), the flash dispatch (`window=` on the sliding
layers), and `models/gpt.py`'s fused head + cross-entropy scan
(`GPTPretrainingCriterion(model=...)` reads `fused_head_weight()`).
"""
from __future__ import annotations

import math

import jax

from .. import nn
from ..distributed import mpu
from ..distributed.recompute import recompute as _recompute
from ..incubate.distributed.models.routed_moe import RoutedMoELayer
from ..nn import functional as F
from ..nn.initializer import Normal

__all__ = ["AfmoeConfig", "AfmoeModel", "AfmoeForCausalLM", "afmoe_tiny"]

SLIDING, FULL = "sliding_attention", "full_attention"


class AfmoeConfig:
    def __init__(self, vocab_size=200192, hidden_size=2048,
                 layer_types=(SLIDING, SLIDING, SLIDING, FULL),
                 num_dense_layers=0, num_heads=32, num_kv_heads=4,
                 head_dim=128, intermediate_size=6144,
                 moe_intermediate_size=1024, num_experts=128,
                 num_experts_held=None, expert_start=0,
                 num_experts_per_tok=8, num_shared_experts=1,
                 sliding_window=2048, rope_theta=10000.0, rms_eps=1e-5,
                 route_scale=2.826, route_norm=True, mup_enabled=True,
                 initializer_range=0.02, recompute=False,
                 fused_head_ce=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        # one entry a layer HELD: "sliding_attention" | "full_attention"
        self.layer_types = tuple(layer_types)
        self.num_layers = len(self.layer_types)
        self.num_dense_layers = num_dense_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        # the router's width, and the experts of it that live here
        self.num_experts = num_experts
        self.num_experts_held = (num_experts if num_experts_held is None
                                 else num_experts_held)
        self.expert_start = expert_start
        self.num_experts_per_tok = num_experts_per_tok
        self.num_shared_experts = num_shared_experts
        self.sliding_window = sliding_window
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.route_scale = route_scale
        self.route_norm = route_norm
        self.mup_enabled = mup_enabled
        self.initializer_range = initializer_range
        self.recompute = recompute
        # training returns hidden states; GPTPretrainingCriterion(model=)
        # fuses the head's projection into the chunked cross entropy
        self.fused_head_ce = fused_head_ce
        for t in self.layer_types:
            if t not in (SLIDING, FULL):
                raise ValueError(f"afmoe: layer type {t!r}")


def _linear(cfg, n_in, n_out, column):
    init = nn.ParamAttr(initializer=Normal(0.0, cfg.initializer_range))
    if column:
        return mpu.ColumnParallelLinear(n_in, n_out, gather_output=False,
                                        has_bias=False, weight_attr=init)
    return mpu.RowParallelLinear(n_in, n_out, input_is_parallel=True,
                                 has_bias=False, weight_attr=init)


class AfmoeAttention(nn.Layer):
    def __init__(self, cfg, layer_type):
        super().__init__()
        self.cfg = cfg
        self.sliding = layer_type == SLIDING
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_heads * d, True)
        self.k_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.v_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.gate_proj = _linear(cfg, h, cfg.num_heads * d, True)
        self.o_proj = _linear(cfg, cfg.num_heads * d, h, False)
        self.q_norm = nn.RMSNorm(d, epsilon=cfg.rms_eps)
        self.k_norm = nn.RMSNorm(d, epsilon=cfg.rms_eps)

    def forward(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        # one scope a KIND of layer: a device trace tells the banded
        # layers' time from the full ones'
        with jax.named_scope("attn.window" if self.sliding else "attn.full"):
            q = self.q_norm(self.q_proj(x).reshape([b, s, cfg.num_heads, d]))
            k = self.k_norm(
                self.k_proj(x).reshape([b, s, cfg.num_kv_heads, d]))
            v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, d])
            if self.sliding:    # positions on the window layers only
                q, k, _ = F.fused_rotary_position_embedding(
                    q, k, None, rotary_emb_base=cfg.rope_theta)
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, training=self.training,
                window=cfg.sliding_window if self.sliding else None)
            out = out.reshape([b, s, cfg.num_heads * d])
            out = out * F.sigmoid(self.gate_proj(x))
            return self.o_proj(out)


class AfmoeMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = _linear(cfg, h, f, True)
        self.up_proj = _linear(cfg, h, f, True)
        self.down_proj = _linear(cfg, f, h, False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class AfmoeBlock(nn.Layer):
    def __init__(self, cfg, index):
        super().__init__()
        self.cfg = cfg
        h = cfg.hidden_size
        norm = lambda: nn.RMSNorm(h, epsilon=cfg.rms_eps)
        self.input_norm = norm()
        self.attn = AfmoeAttention(cfg, cfg.layer_types[index])
        self.post_attn_norm = norm()
        self.pre_mlp_norm = norm()
        self.dense = index < cfg.num_dense_layers
        if self.dense:
            self.mlp = AfmoeMLP(cfg)
        else:
            self.moe = RoutedMoELayer(
                h, cfg.moe_intermediate_size, cfg.num_experts,
                cfg.num_experts_per_tok, num_held=cfg.num_experts_held,
                expert_start=cfg.expert_start,
                shared_width=(cfg.num_shared_experts
                              * cfg.moe_intermediate_size),
                route_scale=cfg.route_scale, route_norm=cfg.route_norm,
                initializer_range=cfg.initializer_range)
        self.post_mlp_norm = norm()

    def _body(self, x):
        """(h, per-expert rows, row counts): the counters leave the
        (possibly recomputed) body as values and reach the expert layer's
        buffers outside it."""
        x = x + self.post_attn_norm(self.attn(self.input_norm(x)))
        y = self.pre_mlp_norm(x)
        if self.dense:
            return x + self.post_mlp_norm(self.mlp(y)), None, None
        # the expert layer's own module scope (`.../moe/...`), though its
        # `compute` is called and not the layer
        with jax.named_scope("moe"):
            m, sizes, counts = self.moe.compute(y)
        return x + self.post_mlp_norm(m), sizes, counts

    def forward(self, x):
        if self.cfg.recompute and self.training:
            out = _recompute(self._body, x)
        else:
            out = self._body(x)
        x, sizes, counts = out
        if not self.dense:
            self.moe.note(sizes, counts)
        return x


class AfmoeModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = mpu.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size,
            weight_attr=nn.ParamAttr(
                initializer=Normal(0.0, cfg.initializer_range)))
        self.layers = nn.LayerList([AfmoeBlock(cfg, i)
                                    for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.cfg.mup_enabled:
            x = x * math.sqrt(self.cfg.hidden_size)
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class AfmoeForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = AfmoeModel(cfg)
        # untied head, held [vocab, hidden] as the embedding is: the layout
        # the fused head + cross-entropy scan (`gpt._fused_linear_ce`) takes
        self.lm_head = self.create_parameter(
            [cfg.vocab_size, cfg.hidden_size],
            default_initializer=Normal(0.0, cfg.initializer_range))

    def fused_head_weight(self):
        """The [vocab, hidden] head weight `GPTPretrainingCriterion`
        projects with (live: the train step binds it)."""
        return self.lm_head

    def forward(self, input_ids):
        x = self.model(input_ids)
        if self.cfg.fused_head_ce and self.training:
            x.name = "fused_head_hidden"   # see GPTForCausalLM.forward
            return x
        with jax.named_scope("head"):
            return x.matmul(self.lm_head, transpose_y=True)


def afmoe_tiny(**kw):
    d = dict(vocab_size=512, hidden_size=64,
             layer_types=(SLIDING, SLIDING, FULL), num_dense_layers=1,
             num_heads=4, num_kv_heads=2, head_dim=16, intermediate_size=128,
             moe_intermediate_size=32, num_experts=8, num_experts_held=8,
             num_experts_per_tok=2, sliding_window=16)
    d.update(kw)
    return AfmoeConfig(**d)
