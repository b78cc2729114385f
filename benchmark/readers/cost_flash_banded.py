"""Operations and bytes the flash calls of a model with window and full
layers need, from the pairs each layer REALLY attends: a layer pass is 2
matmuls forward and 5 backward (the FlashAttention count, whatever kernels
ran) over `attended_pairs`, the band on a windowed call and the causal half
on a full one.  A layer pass is counted by its backward call that makes
dK and dV (`..._dkdv__` of the split pair, `..._bwd__` of the fused
kernel); a recomputed forward is the same pass.  Bytes: q, o, do, dq at the
query heads and k, v, dk, dv at the key/value heads (not expanded), lse."""
import re

from harness import common

cost_afmoe = common.load_module("readers", "cost_afmoe")
PASS = re.compile(r"^%transpose_jvp_flash_\w+?_(dkdv|bwd)__")


def per_pass(batch, seq, heads, kv_heads, head_dim, window=None, itemsize=2):
    pairs = cost_afmoe.attended_pairs(seq, window)
    flops = (2 + 5) * 2 * batch * heads * pairs * head_dim
    tq = batch * seq * heads * head_dim * itemsize
    tkv = batch * seq * kv_heads * head_dim * itemsize
    lse = batch * heads * seq * 4
    return flops, (2 * tq + 2 * tkv + lse) + (4 * tq + 4 * tkv + lse)


def window_cost(run, events):
    cfg, job = run["config"], run["cell"]["job"]
    local_batch = job["global_batch"] // run["state"]["chips"]
    flops = bytes_ = 0
    for name, _, _ in events:
        if not PASS.search(name):
            continue
        window = cfg["sliding_window"] if "_window_" in name.split(" = ")[0] else None
        f, b = per_pass(local_batch, job["sequence_length"],
                        cfg["num_attention_heads"], cfg["num_key_value_heads"],
                        cfg["head_dim"], window)
        flops, bytes_ = flops + f, bytes_ + b
    return flops, bytes_
