"""Operations a GPT forward (and backward) pass REQUIRES, from the
configuration's shapes.  Matmuls of the blocks and the head; causal
attention over the pairs really attended; no recomputation, no lookups."""


def _block_matmul_flops_per_token(cfg):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 2 * h * 3 * h + 2 * h * h + 2 * 2 * h * f      # qkv, out, up+down


def train_flops_per_token(cfg, seq):
    """Forward + backward (backward is twice forward) per trained token."""
    h = cfg["hidden_size"]
    attn = 2 * 2 * h * (seq + 1) / 2          # QK^T and PV over the causal half
    fwd = cfg["num_hidden_layers"] * (_block_matmul_flops_per_token(cfg) + attn) \
        + 2 * h * cfg["vocab_size"]
    return 3.0 * fwd
