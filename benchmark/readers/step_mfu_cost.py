"""FLOPs forward + backward require per token (the cost module the metric
names under `"cost"`: `train_flops_per_token(config, sequence_length,
state)`, which may read what the driver counted in the window) x tokens/s
of the untraced part of the window over chips x peak."""
from harness import common


def read(run, spec):
    st = run["state"]
    cost = common.load_module("readers", spec["cost"])
    per_token = cost.train_flops_per_token(
        run["config"], run["cell"]["job"]["sequence_length"], st)
    return 100.0 * per_token * st["tokens_per_s"] / (
        st["chips"] * run["peaks"]["bf16_flops_per_s"])
