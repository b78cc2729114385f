"""Operations a forward (and backward) pass of Ouro REQUIRES per token,
from the configuration's shapes: every one of the L x T layer APPLICATIONS
(the stack runs `total_ut_steps` times) with its projections, its causal
attention over (seq + 1) / 2 keys a query on average and its MLP, and T
read-outs through the head; the gate's products are left out (2 x hidden a
token a pass).  No recomputation, no lookups, no padding."""


def forward_flops_per_token(cfg, seq):
    """{part: FLOPs a token} of one forward pass."""
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    steps = cfg["total_ut_steps"]
    apps = cfg["num_hidden_layers"] * steps
    return {
        "proj": apps * (2 * h * (nq + 2 * nkv) + 2 * nq * h),  # q k v, o
        "attn": apps * 2 * 2 * nq * (seq + 1) / 2,             # qk^T, pv
        "mlp": apps * 3 * 2 * h * f,
        "head": steps * 2 * h * cfg["vocab_size"],
    }


def train_flops_per_token(cfg, seq, state=None):
    """Forward + backward (backward is twice forward) per trained token."""
    return 3.0 * sum(forward_flops_per_token(cfg, seq).values())
