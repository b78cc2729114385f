"""Operations and bytes one flash-attention layer pass (forward + backward)
needs, from its shapes.  Causal: half the square.  Forward is 2 matmuls
(QK^T, PV); backward 5 (S again, dV, dP, dQ, dK), the FlashAttention count."""


def per_pass(batch, seq, heads, head_dim, itemsize=2):
    square = batch * heads * seq * seq * head_dim          # one full matmul / 2
    flops = (2 + 5) * 2 * square / 2
    tensor = batch * seq * heads * head_dim * itemsize
    lse = batch * heads * seq * 4
    bytes_ = (4 * tensor + lse) + (8 * tensor + lse)       # fwd: q k v o; bwd: q k v o do dq dk dv
    return flops, bytes_


def window_cost(run, n_passes):
    cfg, job = run["config"], run["cell"]["job"]
    local_batch = job["global_batch"] // run["state"]["chips"]
    flops, bytes_ = per_pass(local_batch, job["sequence_length"],
                             cfg["num_attention_heads"], cfg["head_dim"])
    return n_passes * flops, n_passes * bytes_
