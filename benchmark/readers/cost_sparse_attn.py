"""Operations and bytes the sparse-attention calls of a layer pass need,
from the pairs the indexer SELECTS (min(t + 1, topk) keys a query),
whatever tiles the kernels visit: 2 matmuls forward and 5 backward (the
FlashAttention count) and 1 for the head-mean probabilities, which the
indexer's loss takes as its target.  A layer pass is counted by its call
that makes dK and dV; the probabilities run again in the block's replay,
which is in the time and not in the work.  Bytes: q, o, do, dq at the
query heads and k, v, dk, dv at the key/value heads (not expanded), the
log-sum-exp rows, the selection (one byte a causal pair, read by each of
the four kernels) and the selected probabilities in float32."""
import re

from harness import common

cost_keye = common.load_module("readers", "cost_keye")
PASS = re.compile(r"^%transpose_jvp_sparse_attn_dkdv_")


def per_pass(batch, seq, heads, kv_heads, head_dim, topk, itemsize=2):
    pairs = batch * cost_keye.selected_pairs(seq, topk)
    flops = (2 + 5 + 1) * 2 * heads * pairs * head_dim
    tq = batch * seq * heads * head_dim * itemsize
    tkv = batch * seq * kv_heads * head_dim * itemsize
    lse = batch * heads * seq * 4
    mask = batch * cost_keye.causal_pairs(seq)
    return flops, (2 * tq + 2 * tkv + lse) + (4 * tq + 4 * tkv + lse) \
        + (tq + tkv + lse) + 4 * mask + 4 * pairs


def window_cost(run, events):
    cfg, job = run["config"], run["cell"]["job"]
    local_batch = job["global_batch"] // run["state"]["chips"]
    passes = sum(1 for name, _, _ in events if PASS.search(name))
    f, b = per_pass(local_batch, job["sequence_length"],
                    cfg["num_attention_heads"], cfg["num_key_value_heads"],
                    cfg["head_dim"], cfg["sa_config"]["topk"])
    return passes * f, passes * b
