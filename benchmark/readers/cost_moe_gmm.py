"""Operations and bytes the grouped products of the expert layers need for
the rows REALLY routed to the held experts in the traced steps (the
driver's `moe_traced`: the layers' row counters before and after them):
forward 3 and backward 6 products of rows x hidden x expert width; each
reads or writes its activations once and the held experts' weights once a
layer and step."""


def per_rows(rows, hidden, width, experts, layer_steps, itemsize=2):
    flops = 9 * 2 * rows * hidden * width
    acts = 9 * rows * (hidden + width) * itemsize
    weights = 9 * layer_steps * experts * hidden * width * itemsize
    return flops, acts + weights


def window_cost(run, events):
    moe = run["state"].get("moe_traced")
    if not moe:
        return 0, 0
    cfg = run["config"]
    return per_rows(moe["routed"], cfg["hidden_size"],
                    cfg["moe_intermediate_size"], cfg["num_experts"],
                    moe["layers"] * moe["steps"])
