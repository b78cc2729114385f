"""Driver `train`: the fleet train step, one `step(ids, labels)` call per
step with a new batch each step, the window closed by a value fetch.

Set-up builds ONE object (the compiled step with its state), drives it from
the seed through its first three steps (those are the steps the reference
follows) and hands that same object to the window.
"""
from __future__ import annotations

import time

import numpy as np

from harness import check, common, tracing, traffic, weights
from harness.common import log

FIRST_STEPS = check.FIRST_STEPS


def build(ctx):
    """The program's own entry points, as chip_smoke.py builds them."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import (GPTConfig, GPTForCausalLM,
                                       GPTPretrainingCriterion)

    cfg, cell, devices = ctx["config"], ctx["cell"], ctx["devices"]
    opts = cell["options"]
    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = dict(opts["hybrid_configs"])
    fleet.init(is_collective=True, strategy=strategy)
    topo = topology.HybridTopology(dp=opts["hybrid_configs"]["dp_degree"],
                                   devices=devices)
    topology.set_topology(topo)
    P.seed(ctx["seed"] & 0x7FFFFFFF)
    inner = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        ffn_hidden=cfg["intermediate_size"],
        layer_norm_eps=cfg["layer_norm_eps"],
        **opts["model"]))
    weights.load_into(inner, weights.make(cfg, ctx["seed"], "float32"))
    model = fleet.distributed_model(inner)
    o = cfg["training"]["optimizer"]
    opt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=o["learning_rate"],
        beta1=o["beta1"], beta2=o["beta2"], epsilon=o["epsilon"],
        weight_decay=o["weight_decay"]))
    crit = GPTPretrainingCriterion(model=inner)
    step = model.build_train_step(opt, crit, topo=topo,
                                  **opts["build_train_step"])
    step.init_state()
    step.sync_to_model()   # the model now points at the (sharded) state
    return step, P


def _readers(ctx, step):
    """Jitted per-leaf norms read from the step's own state."""
    import jax
    import jax.numpy as jnp

    cfg = ctx["config"]
    b1 = cfg["training"]["optimizer"]["beta1"]
    names = {n: weights.program_name(n) for n in weights.shapes(cfg)}

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    def norms(tree):
        return {n: norm(x) for n, x in weights.logical_leaves(tree).items()}

    grad_norms = jax.jit(lambda slots: norms(
        {n: slots[pn]["moment1"] / (1.0 - b1) for n, pn in names.items()}))

    def change(params, key):
        p0 = weights._generate(cfg, key, jnp.float32)
        return norms({n: params[pn] - p0[n] for n, pn in names.items()})

    return grad_norms, jax.jit(change)


def run(ctx):
    cfg, cell = ctx["config"], ctx["cell"]
    job, seed, seconds = cell["job"], ctx["seed"], ctx["seconds"]
    vocab = cfg["vocab_size"]
    compiles = common.CompileCounter()
    from paddle_tpu.observability import metrics

    metrics.enable()
    c0 = dict(metrics.snapshot()["counters"])
    step, P = build(ctx)
    if ctx.get("plant"):
        step = check.plant_train_fault(step, ctx["plant"])
    grad_norms_fn, change_fn = _readers(ctx, step)

    def feed(i):
        ids, labels = traffic.train_batch(job, vocab, seed, i)
        return P.to_tensor(ids, "int32"), P.to_tensor(labels, "int32")

    # --- the first steps, through the window's own call and feed ----------
    got = {"losses": []}
    for i in range(FIRST_STEPS):
        got["losses"].append(float(step(*feed(i))))
        if i == 0:
            got["grad_norms"] = {n: float(v) for n, v in grad_norms_fn(
                step._state["opt"]["slots"]).items()}
    got["change_norms"] = {n: float(v) for n, v in change_fn(
        step._state["params"], weights.key_of(seed)).items()}
    float(step(*feed(FIRST_STEPS)))       # step 4 keeps that state; warm
    c1 = dict(metrics.snapshot()["counters"])
    log("dispatch", common.counters_delta(c0, c1, ("flash.", "autotune.", "head_ce.")))
    compiled_before = compiles.n

    # --- the window -------------------------------------------------------
    fetch_every = job["fetch_loss_every"]
    tr = tracing.Tracer() if ctx["trace"] else None
    setup_s = time.time() - common.T_PROCESS_START
    t_start = time.perf_counter()
    n, last, traced = 0, None, False
    fetched = []      # seconds into the window at which each loss fetch returned
    pause_s, pause_steps = 0.0, 0
    while time.perf_counter() - t_start < seconds:
        if tr and not traced and time.perf_counter() - t_start > 0.4 * seconds:
            float(last) if last is not None else None
            t_pause = time.perf_counter()
            tr.start()
            for _ in range(cell["trace"]["steps"]):
                with tr.span("bench.make_batch"):
                    b = feed(FIRST_STEPS + 1 + n)
                with tr.span("bench.dispatch"):
                    last = step(*b)
                n += 1
            with tr.span("bench.fetch_loss"):
                float(last)
            tr.stop()
            traced = True
            pause_s = time.perf_counter() - t_pause
            pause_steps = cell["trace"]["steps"]
            continue
        last = step(*feed(FIRST_STEPS + 1 + n))
        n += 1
        if n % fetch_every == 0:
            float(last)
            fetched.append(round(time.perf_counter() - t_start, 3))
    final_loss = float(last)              # the value fetch closes the window
    window = time.perf_counter() - t_start
    in_window = compiles.n - compiled_before
    log("window", {"steps": n, "seconds": window, "final_loss": final_loss,
                   "compilations_in_window": in_window,
                   "fetched_at_s": fetched})
    held, reserved = common.memory_peak_parts(ctx["devices"])
    mem = held + reserved
    log("memory", {"peak_bytes_in_use": held, "peak_bytes_reserved": reserved})
    per_step = job["global_batch"] * job["sequence_length"]
    tps = n * per_step / window
    # the traced run pauses for the profiler: its rate is that of the rest
    tps_untraced = (n - pause_steps) * per_step / (window - pause_s)
    state = {"tokens_per_s": tps_untraced, "memory_peak_bytes": mem,
             "chips": len(ctx["devices"])}

    # --- free the program, then the reference ------------------------------
    step._state = None
    del step, grad_norms_fn, change_fn
    import gc

    gc.collect()
    checks, detail = check.train_checks(ctx, got, np.isfinite(final_loss)
                                        and in_window == 0)
    for line in detail:
        log(line)
    e2e = {"train_tokens_per_s": (tps, "tokens/s"), "setup_s": (setup_s, "s")}
    return {"e2e": e2e, "state": state, "tracer": tr, "checks": checks,
            "attempted": n, "failed": 0 if np.isfinite(final_loss) else n,
            "memory_peak_bytes": mem}
