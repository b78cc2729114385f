"""Operations and bytes the index-score calls of a layer pass need, from
the CAUSAL pairs (the indexer scores every key at or before the query):
one matrix product forward and two backward (dqI, dkI) of `heads x
head_dim` a pair; the backward's replay of the per-head scores, and the
forward run again in the block's replay, are in the time and not in the
work.  A layer pass is counted by its dk call.  Bytes: the scores written
and their gradient read once, float32 a causal pair, and the indexer's
q, k and head weights with their gradients."""
import re

from harness import common

cost_keye = common.load_module("readers", "cost_keye")
PASS = re.compile(r"^%transpose_jvp_sparse_index_dk_")


def per_pass(batch, seq, heads, head_dim, itemsize=2):
    pairs = batch * cost_keye.causal_pairs(seq)
    flops = 3 * 2 * heads * head_dim * pairs
    small = batch * seq * (heads * head_dim + head_dim + heads) * itemsize
    return flops, 2 * 4 * pairs + 2 * small


def window_cost(run, events):
    cfg, job = run["config"], run["cell"]["job"]
    sa = cfg["sa_config"]
    local_batch = job["global_batch"] // run["state"]["chips"]
    passes = sum(1 for name, _, _ in events if PASS.search(name))
    f, b = per_pass(local_batch, job["sequence_length"],
                    sa["indexer_num_heads"], sa["indexer_head_dim"])
    return passes * f, passes * b
