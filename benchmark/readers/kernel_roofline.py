"""A kernel family's share of its roofline: the least time the chip could
take for the family's calls of the traced window (the larger of operations
over peak FLOP/s and bytes over peak bytes/s, from shapes) over their summed
device time.  The family's file (kernels/<family>.json) names the patterns
that find its events and the module that counts operations and bytes."""
from harness import common, tracing


def read(run, spec):
    fam = common.load_json("kernels", spec["kernel"] + ".json")
    t = run["trace"]
    events = tracing.family_events(t["events"][t["fullest"]], fam["events"])
    if not events:
        return None
    cost = common.load_module("readers", fam["cost"])
    n = None
    if "passes" in fam:
        n = len(tracing.family_events(events, fam["passes"]))
        if not n:
            return None
        flops, bytes_ = cost.window_cost(run, n)
    else:
        flops, bytes_ = cost.window_cost(run, events)
    pk = run["peaks"]
    t_flops, t_bytes = flops / pk["bf16_flops_per_s"], bytes_ / pk["hbm_bytes_per_s"]
    device_s = sum(d for _, _, d in events) / 1e9
    common.log("kernel", {"family": spec["kernel"], "events": len(events),
                          "passes": n, "device_s": device_s, "least_s_flops": t_flops,
                          "least_s_bytes": t_bytes,
                          "bound": "compute" if t_flops >= t_bytes else "memory"})
    return 100.0 * max(t_flops, t_bytes) / device_s
