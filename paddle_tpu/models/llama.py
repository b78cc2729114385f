"""LLaMA family — decoder-only with GQA (BASELINE config 4: LLaMA-7B PP).

Role parity: the reference trains LLaMA through the same Fleet mpu stack as
GPT (PaddleNLP-style usage of `fleet/layers/mpu/`, SURVEY §2.5); the fused
ops it leans on — `fused_rms_norm`, `fused_rotary_position_embedding`,
`swiglu` (`python/paddle/incubate/nn/functional/`) — map to this module's
RMSNorm/RoPE/SwiGLU blocks backed by the Pallas/XLA fused paths.

Beyond the GPT module, this adds grouped-query attention (num_kv_heads <
num_heads): KV projections shrink to the KV-head count and are repeated at
attention time — under TP the KV heads shard over the mp axis like Q heads.
Pipeline stages are exported for the 1F1B/interleaved schedules.
"""
from __future__ import annotations

from .. import nn
from ..distributed import mpu
from ..distributed.recompute import recompute as _recompute
from ..nn import functional as F
from .generation import GenerationMixin, _static_cache_attention

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaPretrainingCriterion", "llama_pipe_layers",
           "llama_tiny", "llama_7b", "llama_13b", "llama2_70b_shapes"]


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096, num_layers=32,
                 num_heads=32, num_kv_heads=None, max_seq_len=2048,
                 ffn_hidden=11008, rope_theta=10000.0, rms_eps=1e-6,
                 dropout=0.0, tie_embeddings=False, recompute=False,
                 recompute_policy=None, sequence_parallel=False,
                 context_parallel=False):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.max_seq_len = max_seq_len
        self.ffn_hidden = ffn_hidden
        self.rope_theta = rope_theta
        self.rms_eps = rms_eps
        self.dropout = dropout
        self.tie_embeddings = tie_embeddings
        self.recompute = recompute
        # named remat policy: None (replay all but the marked values:
        # distributed/recompute.py) | 'full' | 'dots' | 'dots_no_batch'
        self.recompute_policy = recompute_policy
        self.sequence_parallel = sequence_parallel
        self.context_parallel = context_parallel


class LlamaAttention(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_heads
        self.num_kv_heads = cfg.num_kv_heads
        self.head_dim = cfg.hidden_size // cfg.num_heads
        q_size = cfg.num_heads * self.head_dim
        kv_size = cfg.num_kv_heads * self.head_dim
        # fused qkv column-parallel: [q | k | v] heads shard together
        self.qkv_proj = mpu.ColumnParallelLinear(
            cfg.hidden_size, q_size + 2 * kv_size, gather_output=False,
            has_bias=False)
        self.out_proj = mpu.RowParallelLinear(
            q_size, cfg.hidden_size, input_is_parallel=True, has_bias=False)

    def forward(self, x, cache=None, kv_cache=None, cache_pos=None,
                attn_start=None):
        from .. import ops

        b, s, _ = x.shape
        hd = self.head_dim
        qkv = self.qkv_proj(x)
        q_size = self.num_heads * hd
        kv_size = self.num_kv_heads * hd
        q, k, v = ops.split(qkv, [q_size, kv_size, kv_size], axis=-1)
        q = q.reshape([b, s, self.num_heads, hd])
        k = k.reshape([b, s, self.num_kv_heads, hd])
        v = v.reshape([b, s, self.num_kv_heads, hd])
        position_ids = None
        if kv_cache is not None:
            # static-cache decode: phases continue from the traced offset;
            # left-padded rows start rotary position 0 at their first
            # real token
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
            q, k, None, position_ids=position_ids,
            rotary_emb_base=self.cfg.rope_theta)
        if cache is not None:
            pk, pv = cache
            k = ops.concat([pk, k], axis=1)
            v = ops.concat([pv, v], axis=1)
            cache = (k, v)
        if kv_cache is not None:
            # GQA-native static cache: k/v stay at num_kv_heads; the decode
            # kernel groups Hq/Hkv queries per KV head so the cache is read
            # once per KV head (GQA's decode-bandwidth advantage)
            out, new_cache = _static_cache_attention(
                q, k, v, kv_cache, cache_pos, attn_start)
            out = self.out_proj(out.reshape([b, s, q_size]))
            return out, new_cache
        if self.num_kv_heads != self.num_heads and \
                self.cfg.context_parallel:
            # ring attention still needs expanded KV; the flash/SDPA path
            # reads GQA heads natively (grouped index maps — KV never
            # expands in HBM, saving Hq/Hkv x of KV traffic)
            rep = self.num_heads // self.num_kv_heads
            k = ops.repeat_interleave(k, rep, axis=2)
            v = ops.repeat_interleave(v, rep, axis=2)
        if self.cfg.context_parallel:
            from ..core.dispatch import apply
            from ..ops.pallas.ring_attention import ring_attention

            out = apply(
                "ring_attention",
                lambda qv, kv, vv: ring_attention(qv, kv, vv, causal=True),
                q, k, v)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.cfg.dropout if self.training else 0.0,
                training=self.training)
        out = self.out_proj(out.reshape([b, s, q_size]))
        if cache is not None:
            return out, cache
        return out


class LlamaMLP(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.gate_up_proj = mpu.ColumnParallelLinear(
            cfg.hidden_size, 2 * cfg.ffn_hidden, gather_output=False,
            has_bias=False)
        self.down_proj = mpu.RowParallelLinear(
            cfg.ffn_hidden, cfg.hidden_size, input_is_parallel=True,
            has_bias=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_up_proj(x)))


class LlamaBlock(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.input_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.attn = LlamaAttention(cfg)
        self.post_norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.mlp = LlamaMLP(cfg)

    def _body(self, x):
        if self.cfg.sequence_parallel:
            x = mpu.sequence_parallel_constraint(x)
        x = x + self.attn(self.input_norm(x))
        return x + self.mlp(self.post_norm(x))

    def forward(self, x, kv_cache=None, cache_pos=None, attn_start=None):
        if kv_cache is not None:
            a, new_cache = self.attn(self.input_norm(x), kv_cache=kv_cache,
                                     cache_pos=cache_pos,
                                     attn_start=attn_start)
            x = x + a
            return x + self.mlp(self.post_norm(x)), new_cache
        if self.cfg.recompute and self.training:
            return _recompute(self._body, x,
                              policy=self.cfg.recompute_policy)
        return self._body(x)


class LlamaModel(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.embed_tokens = mpu.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size)
        self.layers = nn.LayerList([LlamaBlock(cfg)
                                    for _ in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)

    def forward(self, input_ids, kv_caches=None, cache_pos=None,
                attn_start=None):
        x = self.embed_tokens(input_ids)
        if kv_caches is not None:
            new_caches = []
            for blk, kc in zip(self.layers, kv_caches):
                x, nc = blk(x, kv_cache=kc, cache_pos=cache_pos,
                            attn_start=attn_start)
                new_caches.append(nc)
            return self.norm(x), new_caches
        for blk in self.layers:
            x = blk(x)
        return self.norm(x)


class LlamaForCausalLM(nn.Layer, GenerationMixin):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if cfg.tie_embeddings:
            self.lm_head = None
        else:
            self.lm_head = mpu.ColumnParallelLinear(
                cfg.hidden_size, cfg.vocab_size, gather_output=True,
                has_bias=False)

    def forward(self, input_ids, kv_caches=None, cache_pos=None,
                attn_start=None):
        from .. import ops

        if kv_caches is not None:
            h, new_caches = self.model(input_ids, kv_caches=kv_caches,
                                       cache_pos=cache_pos,
                                       attn_start=attn_start)
        else:
            h = self.model(input_ids)
        if self.lm_head is None:
            w = self.model.embed_tokens.weight
            logits = ops.matmul(h, w, transpose_y=True)
        else:
            logits = self.lm_head(h)
        if kv_caches is not None:
            return logits, new_caches
        return logits

    def init_kv_caches(self, batch, max_len):
        from .generation import init_kv_caches

        cfg = self.cfg
        # KV heads only (GQA-native cache; see LlamaAttention.forward)
        return init_kv_caches(cfg.num_layers, batch, cfg.num_kv_heads,
                              cfg.hidden_size // cfg.num_heads, max_len,
                              self.model.embed_tokens.weight.dtype)


class LlamaPretrainingCriterion(nn.Layer):
    def __init__(self, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        loss = F.cross_entropy(
            logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]),
            ignore_index=self.ignore_index, reduction="mean")
        return loss


class LlamaEmbeddingStage(nn.Layer):
    """Pipeline stage 0 (parity: PipelineLayer LayerDesc split)."""

    def __init__(self, cfg):
        super().__init__()
        self.embed_tokens = mpu.VocabParallelEmbedding(
            cfg.vocab_size, cfg.hidden_size)

    def forward(self, input_ids):
        return self.embed_tokens(input_ids)


class LlamaHeadStage(nn.Layer):
    def __init__(self, cfg):
        super().__init__()
        self.norm = nn.RMSNorm(cfg.hidden_size, epsilon=cfg.rms_eps)
        self.lm_head = mpu.ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, gather_output=True,
            has_bias=False)

    def forward(self, x):
        return self.lm_head(self.norm(x))


def llama_pipe_layers(cfg):
    """Layer list for PipelineModule segmentation (1F1B / interleaved)."""
    return ([LlamaEmbeddingStage(cfg)]
            + [LlamaBlock(cfg) for _ in range(cfg.num_layers)]
            + [LlamaHeadStage(cfg)])


def llama_tiny(**kw):
    d = dict(vocab_size=512, hidden_size=128, num_layers=2, num_heads=4,
             num_kv_heads=2, max_seq_len=128, ffn_hidden=256)
    d.update(kw)
    return LlamaConfig(**d)


def llama_7b(**kw):
    d = dict(vocab_size=32000, hidden_size=4096, num_layers=32,
             num_heads=32, max_seq_len=2048, ffn_hidden=11008)
    d.update(kw)
    return LlamaConfig(**d)


def llama_13b(**kw):
    d = dict(vocab_size=32000, hidden_size=5120, num_layers=40,
             num_heads=40, max_seq_len=2048, ffn_hidden=13824)
    d.update(kw)
    return LlamaConfig(**d)


def llama2_70b_shapes(**kw):
    d = dict(vocab_size=32000, hidden_size=8192, num_layers=80,
             num_heads=64, num_kv_heads=8, max_seq_len=4096,
             ffn_hidden=28672)
    d.update(kw)
    return LlamaConfig(**d)
