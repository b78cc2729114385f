"""Operations an AFMoE forward (and backward) pass REQUIRES per token, from
the configuration's shapes: the projections, the attention over the pairs
really attended (the band of `sliding_window` keys on sliding layers, the
causal half on full ones), the dense MLP or the expert layer (router,
shared expert, and the routed experts HELD HERE at the rows the router
REALLY sent them, which the expert layers count; without a count, at a
balanced router's: experts per token x held / router width), and the
head.  No recomputation, no lookups, no padding."""


def attended_pairs(seq, window=None):
    """(query, key) pairs of one causal sequence; with a window each query
    sees at most `window` keys."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def layer_kinds(cfg):
    held = cfg.get("layers_held") or range(cfg["num_hidden_layers"])
    return [cfg["layer_types"][i] for i in held][:cfg["num_hidden_layers"]]


def forward_flops_per_token(cfg, seq, routed_rows_per_token=None):
    """{part: FLOPs a token} of one forward pass.  `routed_rows_per_token`:
    rows a token sent to held experts, summed over the expert layers, as
    counted; None counts a balanced router's."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    fe = cfg["moe_intermediate_size"]
    out = {"proj": 0.0, "attn_window": 0.0, "attn_full": 0.0, "dense_mlp": 0.0,
           "shared": 0.0, "router": 0.0, "routed": 0.0}
    for i, kind in enumerate(layer_kinds(cfg)):
        out["proj"] += 2 * h * (2 * nq + 2 * nkv) + 2 * nq * h   # q k v gate, o
        sliding = kind == "sliding_attention"
        pairs = attended_pairs(seq, cfg["sliding_window"] if sliding else None)
        out["attn_window" if sliding else "attn_full"] += 2 * 2 * nq * pairs / seq
        if i < cfg["num_dense_layers"]:
            out["dense_mlp"] += 3 * 2 * h * cfg["intermediate_size"]
        else:
            out["shared"] += cfg["num_shared_experts"] * 3 * 2 * h * fe
            out["router"] += 2 * h * cfg["router_width"]
            out["routed"] += (cfg["num_experts_per_tok"] * cfg["num_experts"]
                              / cfg["router_width"]) * 3 * 2 * h * fe
    if routed_rows_per_token is not None:
        out["routed"] = routed_rows_per_token * 3 * 2 * h * fe
    out["head"] = 2 * h * cfg["vocab_size"]
    return out


def train_flops_per_token(cfg, seq, state=None):
    """Forward + backward (backward is twice forward) per trained token.
    `state`: the driver's, whose `moe_window` holds the rows routed to
    held experts and the tokens trained over the whole window."""
    moe = (state or {}).get("moe_window")
    rows = moe["routed"] / moe["tokens"] if moe else None
    return 3.0 * sum(forward_flops_per_token(cfg, seq, rows).values())
