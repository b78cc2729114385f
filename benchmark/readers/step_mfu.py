"""FLOPs forward + backward require per token x tokens/s over chips x peak."""
from harness import common


def read(run, spec):
    st = run["state"]
    cost = common.load_module("readers", "cost_gpt")
    per_token = cost.train_flops_per_token(run["config"],
                                           run["cell"]["job"]["sequence_length"])
    return 100.0 * per_token * st["tokens_per_s"] / (
        st["chips"] * run["peaks"]["bf16_flops_per_s"])
