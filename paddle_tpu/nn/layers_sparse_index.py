"""The indexer of learned sparse attention as a layer (the math:
`nn/functional/sparse_index.py`; docs/ATTENTION.md)."""
from __future__ import annotations

import jax

from ..core.dispatch import apply
from . import functional as F
from .initializer import Normal
from .layer_base import Layer
from .layers_common import Linear
from .layers_norm import LayerNorm

__all__ = ["SparseIndexer"]


class SparseIndexer(Layer):
    """hidden states -> index scores I [B, T, T] float32.

    `num_heads` score heads of `head_dim` (`q_proj`) against ONE key head
    (`k_proj`, then a LayerNorm with weight and bias), rotary positions on
    both (the whole head, rotate-half pairing), a learned weight a head
    (`w_proj`, scaled by num_heads^-1/2 head_dim^-1/2):

        I[t, s] = sum_j w[t, j] * relu(qI[t, j] . kI[s])

    The input is taken DETACHED: the indexer's parameters learn from the
    indexer's own loss (`F.sparse_indexer_loss`) and the model's loss sees
    the indexer through its discrete selection only.
    """

    def __init__(self, hidden_size, num_heads=16, head_dim=64,
                 rope_theta=10000.0, epsilon=1e-6, initializer_range=0.02):
        super().__init__()
        self.num_heads, self.head_dim = num_heads, head_dim
        self.rope_theta = rope_theta
        from . import ParamAttr

        def linear(n_out):
            return Linear(hidden_size, n_out, bias_attr=False,
                          weight_attr=ParamAttr(
                              initializer=Normal(0.0, initializer_range)))

        self.q_proj = linear(num_heads * head_dim)
        self.k_proj = linear(head_dim)
        self.k_norm = LayerNorm(head_dim, epsilon=epsilon)
        self.w_proj = linear(num_heads)

    def forward(self, x, position_ids=None):
        """x [B, T, hidden]; position_ids [B, T] (default 0..T-1)."""
        b, t, _ = x.shape
        j, d = self.num_heads, self.head_dim
        x = apply("stop_gradient", jax.lax.stop_gradient, x)
        q = self.q_proj(x).reshape([b, t, j, d])
        k = self.k_norm(self.k_proj(x)).reshape([b, t, 1, d])
        q, k, _ = F.fused_rotary_position_embedding(
            q, k, None, position_ids=position_ids,
            rotary_emb_base=self.rope_theta)
        w = self.w_proj(x) * (j ** -0.5 * d ** -0.5)
        return F.sparse_index_scores(q, k.reshape([b, t, d]), w)
