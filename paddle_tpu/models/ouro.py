"""Ouro (`model_type: ouro`, ByteDance's looped language model, "LoopLM",
arXiv:2510.25741) — a decoder whose whole layer stack runs `total_ut_steps`
times with the same weights, with a learned exit gate after each pass.

The layer and the loop (benchmark/configs/ouro-2.6b-pp8.json lists what the
public `config.json` has no key for, under `assumed`):

    h <- E[x]                                   (unscaled; untied head)
    for t = 1..T  (T = total_ut_steps):
        for l = 1..L:
            h <- h + N2_l(Attn_l(N1_l(h)))
            h <- h + N4_l(MLP_l(N3_l(h)))
        h <- N_f(h)            # one final RMS norm shared by all passes;
                               # the normed state is the next pass's input
        l_t = CE(h W_head^T, y)            per token, whole vocabulary
        lambda_t = sigmoid(h . w_g + b_g)  per token (Linear(hidden -> 1))

  * four RMS norms a layer ("sandwich": one before and one after each
    sub-layer; `input_layernorm`, `input_layernorm_2`,
    `post_attention_layernorm`, `post_attention_layernorm_2`), eps
    `rms_eps`, learned weight;
  * attention: q, k, v, o without biases, `num_kv_heads` key/value heads
    (16 of 16 in the 2.6B), rotary positions (theta `rope_theta`,
    rotate-half over the whole head), causal softmax(q k^T / sqrt(d)) v;
  * MLP: down(silu(gate(h)) * up(h)), width `intermediate_size`;
  * the exit distribution, per token: p_t = lambda_t prod_{j<t}(1 -
    lambda_j) for t < T, p_T = prod_{j<T}(1 - lambda_j) (the last pass's
    gate is not used);
  * loss = mean over tokens of [sum_t p_t l_t - beta H(p)], H(p) = -sum_t
    p_t log p_t: the expected loss under the exit distribution plus an
    entropy term (the report's first-stage objective; beta
    `entropy_beta`).  The gate learns only through p.

Departures: the entropy is averaged over every position, ignored labels
included (the benchmark's traffic ignores none); inference runs all T
passes (`early_exit_threshold` 1) and returns the last pass's logits.

How it trains here.  The loop is unrolled in Python: every layer
APPLICATION is its own `recompute()` segment (L x T a step), each pass's
blocks run under `jax.named_scope("loop.block")` and its exit (final norm,
gate, exit distribution) under `"loop.exit"`.  In training the forward
hands `GPTPretrainingCriterion(model=...)` the T passes' normed states
stacked [T, B, S, hidden], and registers the exit distribution [T, B, S]
and the entropy term through the criterion's one door for a second loss
term (`pop_aux_loss()`, here a pair): ONE weighted head + CE scan (the
criterion's scope `head_ce`) reads the T passes out with one dW
(`gpt._fused_linear_ce`'s `weights`), its gradient by the weights — each
token's CE — formed in the forward scan.  Trace-time counters: `loop.apply{ut=k}` one a block
application, `loop.exit{weights=exit_dist}` one a pass; the buffer
`model.exit_probs` [T] holds the last step's mean exit probability of each
pass.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .. import nn
from ..core.dispatch import apply
from ..distributed import mpu
from ..distributed.recompute import recompute as _recompute
from ..nn import functional as F
from ..nn.initializer import Normal
from ..observability import metrics as _metrics
from .afmoe import AfmoeMLP, _linear

__all__ = ["OuroConfig", "OuroModel", "OuroForCausalLM", "ouro_tiny",
           "exit_distribution"]


class OuroConfig:
    def __init__(self, vocab_size=49152, hidden_size=2048, num_layers=48,
                 num_heads=16, num_kv_heads=16, head_dim=128,
                 intermediate_size=5632, rope_theta=1e6, rms_eps=1e-6,
                 total_ut_steps=4, entropy_beta=0.1, initializer_range=0.02,
                 recompute=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.intermediate_size = intermediate_size
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        # passes of the whole layer stack a step, all with the same weights
        self.total_ut_steps = total_ut_steps
        self.entropy_beta = entropy_beta
        self.initializer_range = initializer_range
        self.recompute = recompute


class OuroAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h, d = cfg.hidden_size, cfg.head_dim
        self.q_proj = _linear(cfg, h, cfg.num_heads * d, True)
        self.k_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.v_proj = _linear(cfg, h, cfg.num_kv_heads * d, True)
        self.o_proj = _linear(cfg, cfg.num_heads * d, h, False)

    def forward(self, x):
        cfg = self.cfg
        b, s, _ = x.shape
        d = cfg.head_dim
        q = self.q_proj(x).reshape([b, s, cfg.num_heads, d])
        k = self.k_proj(x).reshape([b, s, cfg.num_kv_heads, d])
        v = self.v_proj(x).reshape([b, s, cfg.num_kv_heads, d])
        q, k, _ = F.fused_rotary_position_embedding(
            q, k, None, rotary_emb_base=cfg.rope_theta)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        return self.o_proj(out.reshape([b, s, cfg.num_heads * d]))


class OuroBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        norm = lambda: nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.input_layernorm = norm()
        self.attn = OuroAttention(cfg)
        self.input_layernorm_2 = norm()
        self.post_attention_layernorm = norm()
        self.mlp = AfmoeMLP(cfg)
        self.post_attention_layernorm_2 = norm()

    def _body(self, x):
        x = x + self.input_layernorm_2(self.attn(self.input_layernorm(x)))
        return x + self.post_attention_layernorm_2(
            self.mlp(self.post_attention_layernorm(x)))

    def forward(self, x):
        if self.cfg.recompute and self.training:
            return _recompute(self._body, x)
        return self._body(x)


def exit_distribution(z):
    """Gate logits z [T - 1, ...] (float32) -> (p [T, ...], H [...]): the
    exit distribution over the T passes and its entropy, in log space
    (log sigmoid), so that neither p log p nor its gradient meets log 0."""
    log_exit = jax.nn.log_sigmoid(z)            # log lambda_t
    stayed = jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)  # sum_{j<=t} log(1-l)
    zero = jnp.zeros((1,) + z.shape[1:], z.dtype)
    log_p = (jnp.concatenate([log_exit, zero], axis=0)
             + jnp.concatenate([zero, stayed], axis=0))
    p = jnp.exp(log_p)
    return p, -jnp.sum(p * log_p, axis=0)


class OuroModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        init = nn.ParamAttr(initializer=Normal(0.0, cfg.initializer_range))
        self.embed_tokens = mpu.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size, weight_attr=init)
        self.layers = nn.LayerList([OuroBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.early_exit_gate = nn.Linear(cfg.hidden_size, 1, weight_attr=init)
        self.register_buffer("exit_probs", jnp.zeros((cfg.total_ut_steps,),
                                                     jnp.float32))

    def forward(self, input_ids):
        """(the T passes' normed states [T, B, S, hidden], the exit
        distribution [T, B, S], its entropy [B, S]); the buffer
        `exit_probs` takes p's mean over the tokens."""
        steps = self.cfg.total_ut_steps
        x = self.embed_tokens(input_ids)
        states = []
        for t in range(steps):
            with jax.named_scope("loop.block"):
                for blk in self.layers:
                    _metrics.inc("loop.apply", ut=t + 1)
                    x = blk(x)
            with jax.named_scope("loop.exit"):
                _metrics.inc("loop.exit", weights="exit_dist")
                x = self.norm(x)
                states.append(x)

        def exits(*vals):
            *hs, w, bias = vals
            b, s = hs[0].shape[:2]
            z = jnp.zeros((0, b, s), jnp.float32)
            if steps > 1:   # the last pass's gate is not used
                # the gate's logits in float32 from the (rounded) operands
                z = jnp.stack([jnp.einsum(
                    "bsh,h->bs", g, w[:, 0],
                    preferred_element_type=jnp.float32) for g in hs[:-1]])
                z = z + bias.astype(jnp.float32)
            p, ent = exit_distribution(z)
            return jnp.stack(hs), p, ent

        with jax.named_scope("loop.exit"):
            stacked, p, ent = apply("loop_exit", exits, *states,
                                    self.early_exit_gate.weight,
                                    self.early_exit_gate.bias)
        self.exit_probs._value = jax.lax.stop_gradient(
            jnp.mean(p._value, axis=(1, 2)))
        return stacked, p, ent


class OuroForCausalLM(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = OuroModel(cfg)
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
        """The last training forward's loss terms for the criterion, once:
        (the exit distribution [T, B, S], -beta x the mean entropy); None
        where none is waiting."""
        aux, self._aux_loss = self._aux_loss, None
        return aux

    def forward(self, input_ids):
        states, p, ent = self.model(input_ids)
        if self.training:
            self._aux_loss = (p, ent.mean() * -self.cfg.entropy_beta)
            states.name = "fused_head_hidden"   # see GPTForCausalLM.forward
            return states
        with jax.named_scope("head"):
            return states[-1].matmul(self.lm_head, transpose_y=True)


def ouro_tiny(**kw):
    d = dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
             num_kv_heads=4, head_dim=16, intermediate_size=128,
             total_ut_steps=4)
    d.update(kw)
    return OuroConfig(**d)
