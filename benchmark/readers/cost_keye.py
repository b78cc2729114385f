"""Operations a forward (and backward) pass of Keye-VL-2.0's language model
REQUIRES per token, from the configuration's shapes: the projections, the
indexer's projections and its scores over the CAUSAL pairs (every causal
key is scored), the attention over the SELECTED pairs only (min(t + 1,
topk) keys a query), the router, the routed experts HELD HERE at the rows
the router REALLY sent them (the expert layers count them; without a count,
a balanced router's), and the head.  No recomputation, no lookups, no
padding, and nothing for the search that finds the selection (it multiplies
nothing)."""


def causal_pairs(seq):
    return seq * (seq + 1) // 2


def selected_pairs(seq, topk):
    """sum over the queries of one sequence of min(t + 1, topk)."""
    k = min(topk, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def forward_flops_per_token(cfg, seq, routed_rows_per_token=None):
    """{part: FLOPs a token} of one forward pass.  `routed_rows_per_token`:
    rows a token sent to held experts, summed over the layers, as counted;
    None counts a balanced router's."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    fe, layers = cfg["moe_intermediate_size"], cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    rows = routed_rows_per_token
    if rows is None:
        rows = layers * (cfg["num_experts_per_tok"] * cfg["num_experts"]
                         / cfg["router_width"])
    return {
        "proj": layers * (2 * h * (nq + 2 * nkv) + 2 * nq * h),  # q k v, o
        "indexer_proj": layers * 2 * h * (j * di + di + j),
        "indexer_scores": layers * 2 * j * di * causal_pairs(seq) / seq,
        "attn_selected": layers * 2 * 2 * nq * selected_pairs(seq, sa["topk"]) / seq,
        "router": layers * 2 * h * cfg["router_width"],
        "routed": rows * 3 * 2 * h * fe,
        "head": 2 * h * cfg["vocab_size"],
    }


def train_flops_per_token(cfg, seq, state=None):
    """Forward + backward (backward is twice forward) per trained token.
    `state`: the driver's, whose `moe_window` holds the rows routed to
    held experts and the tokens trained over the whole window."""
    moe = (state or {}).get("moe_window")
    rows = moe["routed"] / moe["tokens"] if moe else None
    return 3.0 * sum(forward_flops_per_token(cfg, seq, rows).values())
