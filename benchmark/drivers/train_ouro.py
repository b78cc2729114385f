"""Driver `train_ouro`: the fleet train step over `OuroForCausalLM` (a layer
stack run `total_ut_steps` times a step, a learned exit gate after each
pass, a loss over the exit distribution), driven as `drivers/train_afmoe.py`
drives its model — one `step(ids, labels)` call per step with a new batch
each step, the window closed by a value fetch, ONE compiled step for set-up
and window — with this model's weights from the seed, its three faults, and
the loop's trace-time counters held to the configuration under `sane`.
An `exit {...}` line gives the model's buffer of the last step's mean exit
probability per pass, after the first step and after the window.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from harness import check, common, tracing, traffic
from harness.common import log
from harness.weights import key_of      # a PRNG key from any whole number

FIRST_STEPS = check.FIRST_STEPS
FAULTS = ("loop_short", "exit_detached", "sandwich_dropped")   # this model's own
KEPT = "flash.recompute_kept{what=out_lse}"


# --- weights from the seed, framework-neutral names -----------------------

def shapes(cfg):
    """name -> (shape, kind); kind is 'matrix', 'norm' (about one) or
    'bias' (about nought)."""
    h, d, f = cfg["hidden_size"], cfg["head_dim"], cfg["intermediate_size"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    out = {"wte": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"h.{i}."
        out.update({
            pre + "input_norm": ((h,), "norm"),
            pre + "q": ((h, nq), "matrix"), pre + "k": ((h, nkv), "matrix"),
            pre + "v": ((h, nkv), "matrix"), pre + "o": ((nq, h), "matrix"),
            pre + "input_norm_2": ((h,), "norm"),
            pre + "post_attn_norm": ((h,), "norm"),
            pre + "mlp.gate": ((h, f), "matrix"),
            pre + "mlp.up": ((h, f), "matrix"),
            pre + "mlp.down": ((f, h), "matrix"),
            pre + "post_attn_norm_2": ((h,), "norm")})
    out.update({"norm": ((h,), "norm"), "gate.w": ((h, 1), "matrix"),
                "gate.b": ((1,), "bias"),
                "lm_head": ((cfg["vocab_size"], h), "matrix")})
    return out


def _generate(cfg, key, dtype):
    import jax
    import jax.numpy as jnp

    std = cfg["initializer_range"]
    tree = {}
    for i, (name, (shape, kind)) in enumerate(shapes(cfg).items()):
        x = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                    jnp.float32)
        if kind == "norm":
            x = 1.0 + x
        tree[name] = x.astype(dtype).astype(jnp.float32)
    return tree


def make(cfg, seed, dtype="float32"):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda k: _generate(cfg, k, jnp.dtype(dtype)))(key_of(seed))


_PART = {"input_norm": "input_layernorm.weight",
         "input_norm_2": "input_layernorm_2.weight",
         "post_attn_norm": "post_attention_layernorm.weight",
         "post_attn_norm_2": "post_attention_layernorm_2.weight",
         "q": "attn.q_proj.weight", "k": "attn.k_proj.weight",
         "v": "attn.v_proj.weight", "o": "attn.o_proj.weight",
         "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
         "mlp.down": "mlp.down_proj.weight"}


def program_name(name):
    """Onto `paddle_tpu.models.ouro` parameter names."""
    top = {"wte": "model.embed_tokens.weight", "norm": "model.norm.weight",
           "gate.w": "model.early_exit_gate.weight",
           "gate.b": "model.early_exit_gate.bias", "lm_head": "lm_head"}
    if name in top:
        return top[name]
    _, i, part = name.split(".", 2)
    return f"model.layers.{i}." + _PART[part]


def load_into(model, tree):
    """Leaf for leaf; any leaf without a partner is an error."""
    w = {program_name(n): v for n, v in tree.items()}
    for name, p in model.named_parameters():
        if name not in w or p._value.shape != w[name].shape:
            raise RuntimeError(f"weights: no leaf of shape {p._value.shape} for {name}")
        p._value = w.pop(name)
    if w:
        raise RuntimeError(f"weights: the model lacks {sorted(w)}")


# --- the program ------------------------------------------------------------

def model_config(cfg, opts, steps=None):
    """`OuroConfig` from the configuration file's keys."""
    from paddle_tpu.models.ouro import OuroConfig

    return OuroConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        total_ut_steps=cfg["total_ut_steps"] if steps is None else steps,
        entropy_beta=cfg["entropy_beta"],
        initializer_range=cfg["initializer_range"], **opts)


def build(ctx):
    """The program's own entry points, as drivers/train.py builds them."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    from paddle_tpu.models.ouro import OuroForCausalLM

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
    steps = None
    if ctx.get("plant") == "loop_short":        # one pass fewer
        steps = cfg["total_ut_steps"] - 1
    inner = OuroForCausalLM(model_config(cfg, opts["model"], steps))
    if ctx.get("plant") == "sandwich_dropped":  # N2 and N4 pass h through
        for blk in inner.model.layers:
            blk.input_layernorm_2.forward = lambda x: x
            blk.post_attention_layernorm_2.forward = lambda x: x
    load_into(inner, make(cfg, ctx["seed"], "float32"))
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


def _plant(ctx, step):
    """`half_batch` and `state_unchanged` break the step underneath the
    driver (harness/check.py); `loop_short` and `sandwich_dropped` were
    built into the model; `exit_detached` puts the exit distribution under
    `stop_gradient` while the step is traced.  Returns the step and what
    undoes the last."""
    fault = ctx.get("plant")
    if fault == "exit_detached":
        import jax
        from paddle_tpu.models import ouro

        exits = ouro.exit_distribution
        ouro.exit_distribution = lambda z: jax.lax.stop_gradient(exits(z))

        def undo():
            ouro.exit_distribution = exits

        return step, undo
    if fault and fault not in FAULTS:
        step = check.plant_train_fault(step, fault)
    return step, lambda: None


def _readers(ctx, step):
    """Jitted per-leaf norms read from the step's own state."""
    import jax
    import jax.numpy as jnp

    cfg = ctx["config"]
    b1 = cfg["training"]["optimizer"]["beta1"]
    names = {n: program_name(n) for n in shapes(cfg)}

    def norm(x):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))

    grad_norms = jax.jit(lambda slots: {
        n: norm(slots[pn]["moment1"] / (1.0 - b1)) for n, pn in names.items()})

    def change(params, key):
        p0 = _generate(cfg, key, jnp.float32)
        return {n: norm(params[pn] - p0[n]) for n, pn in names.items()}

    return grad_norms, jax.jit(change)


def _exit_probs(step):
    return [round(float(x), 6) for x in
            np.asarray(step._state["buffers"]["model.exit_probs"])]


def _loop_sane(ctx, dispatch):
    """The loop's trace-time counters against the configuration: one
    `loop.apply{ut=k}` a layer for each of the T passes, T exits, ONE
    weighted head + CE scan; on a TPU, where flash runs its kernels, one
    kept output + lse a layer APPLICATION (L x T recomputed segments)."""
    cfg = ctx["config"]
    layers, steps = cfg["num_hidden_layers"], cfg["total_ut_steps"]
    want = {f"loop.apply{{ut={k}}}": layers for k in range(1, steps + 1)}
    want.update({"loop.exit{weights=exit_dist}": steps,
                 "head_ce.weights{kind=per_token}": 1})
    if ctx["devices"][0].platform == "tpu":
        want[KEPT] = layers * steps
    got = {k: dispatch.get(k, 0) for k in want}
    applied = sum(v for k, v in dispatch.items() if k.startswith("loop.apply"))
    ok = got == want and applied == layers * steps
    log("sane", {"loop_counters": got, "want": want,
                 "loop_apply_total": applied, "ok": ok})
    return ok


# --- the comparison ---------------------------------------------------------

def _checks(ctx, got, sane):
    """Reference over the first three steps, then each number beside its
    limit (harness/check.py's numbers and verdict).  `ctx["readings"]`
    (tools/calibrate.py) adds the controls and the faults, put in the
    program's place and held to the same limits."""
    ref_mod = common.load_module("reference", ctx["config"]["reference"])
    cfg, cell = ctx["config"], ctx["cell"]
    rows = cell["reference"]["rows_per_block"]
    batches = [traffic.train_batch(cell["job"], cfg["vocab_size"], ctx["seed"], i)
               for i in range(FIRST_STEPS)]
    follow = functools.partial(
        ref_mod.train_readings, cfg, cfg["training"]["optimizer"],
        lambda: make(cfg, ctx["seed"], "float32"))
    ref = follow(batches, rows)
    numbers, where = check.train_numbers(got, ref)
    detail = [f"reference {{'losses': {ref['losses']}, 'program_losses': "
              f"{got['losses']}, 'numbers': {numbers}, 'where': {where}}}"]
    checks = check.with_limits(numbers, cell["limits"], sane)
    if ctx.get("readings"):
        half = [(i[: i.shape[0] // 2], l[: l.shape[0] // 2]) for i, l in batches]
        half_rows = min(rows, half[0][0].shape[0])
        runs = {"control_fp8": lambda: follow(batches, rows, quant=ref_mod.fp8_fake_quant),
                "control_int8": lambda: follow(batches, rows, quant=ref_mod.int8_fake_quant),
                "fault_half_batch": lambda: follow(half, half_rows)}
        runs.update({"fault_" + f: functools.partial(follow, batches, rows, fault=f)
                     for f in FAULTS})
        detail.append(f"readings program {numbers} correct={check.verdict(checks)[0]}")
        for name, run_ in runs.items():
            n = check.train_numbers(run_(), ref)[0]
            ok, failing = check.verdict(check.with_limits(n, cell["limits"], True))
            detail.append(f"readings {name} {n} correct={ok} failing={failing}")
    return checks, detail


def run(ctx):
    cfg, cell = ctx["config"], ctx["cell"]
    job, seed, seconds = cell["job"], ctx["seed"], ctx["seconds"]
    vocab = cfg["vocab_size"]
    compiles = common.CompileCounter()
    from paddle_tpu.observability import metrics

    metrics.enable()
    c0 = dict(metrics.snapshot()["counters"])
    step, P = build(ctx)
    step, undo = _plant(ctx, step)
    grad_norms_fn, change_fn = _readers(ctx, step)
    per_step = job["global_batch"] * job["sequence_length"]

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
            exit_first = _exit_probs(step)
    got["change_norms"] = {n: float(v) for n, v in change_fn(
        step._state["params"], key_of(seed)).items()}
    float(step(*feed(FIRST_STEPS)))       # step 4 keeps that state; warm
    undo()
    c1 = dict(metrics.snapshot()["counters"])
    dispatch = common.counters_delta(
        c0, c1, ("flash.", "autotune.", "head_ce.", "loop."))
    log("dispatch", dispatch)
    loop_ok = _loop_sane(ctx, dispatch)
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
    log("exit", {"mean_exit_probability_by_pass": {
        "first_step": exit_first, "last_step": _exit_probs(step)}})
    held, reserved = common.memory_peak_parts(ctx["devices"])
    mem = held + reserved
    log("memory", {"peak_bytes_in_use": held, "peak_bytes_reserved": reserved})
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
    sane = np.isfinite(final_loss) and in_window == 0 and loop_ok
    checks, detail = _checks(ctx, got, sane)
    for line in detail:
        log(line)
    e2e = {"train_tokens_per_s": (tps, "tokens/s"), "setup_s": (setup_s, "s")}
    return {"e2e": e2e, "state": state, "tracer": tr, "checks": checks,
            "attempted": n, "failed": 0 if np.isfinite(final_loss) else n,
            "memory_peak_bytes": mem}
