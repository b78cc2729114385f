"""Driver `train_afmoe`: the fleet train step over `AfmoeForCausalLM`, one
`step(ids, labels)` call per step with a new batch each step, the window
closed by a value fetch — `drivers/train.py`'s run for a model that is not a
GPT: its own weights from the seed, its own comparison (the same numbers and
verdict, `harness/check.py`'s), two faults of this model's own, and the
expert layers' per-step row counters summed over the steps it dispatches
(the window's expert load is held to the cell's band).

Set-up builds ONE object (the compiled step with its state), drives it from
the seed through its first three steps (those are the steps the reference
follows) and hands that same object to the window.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from harness import check, common, tracing, traffic
from harness.common import log
from harness.weights import key_of      # a PRNG key from any whole number

FIRST_STEPS = check.FIRST_STEPS
FAULTS = ("window_ignored", "expert_dropped")   # this model's own


# --- weights from the seed, framework-neutral names -----------------------

def shapes(cfg):
    """name -> (shape, kind); kind is 'matrix' or 'norm' (about one)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    f, fe, e = (cfg["intermediate_size"], cfg["moe_intermediate_size"],
                cfg["num_experts"])
    out = {"wte": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"h.{i}."
        out.update({
            pre + "input_norm": ((h,), "norm"),
            pre + "q": ((h, nq), "matrix"), pre + "k": ((h, nkv), "matrix"),
            pre + "v": ((h, nkv), "matrix"), pre + "gate": ((h, nq), "matrix"),
            pre + "o": ((nq, h), "matrix"),
            pre + "q_norm": ((d,), "norm"), pre + "k_norm": ((d,), "norm"),
            pre + "post_attn_norm": ((h,), "norm"),
            pre + "pre_mlp_norm": ((h,), "norm"),
        })
        if i < cfg["num_dense_layers"]:
            out.update({pre + "mlp.gate": ((h, f), "matrix"),
                        pre + "mlp.up": ((h, f), "matrix"),
                        pre + "mlp.down": ((f, h), "matrix")})
        else:
            out.update({
                pre + "router": ((h, cfg["router_width"]), "matrix"),
                pre + "experts.gate": ((e, h, fe), "matrix"),
                pre + "experts.up": ((e, h, fe), "matrix"),
                pre + "experts.down": ((e, fe, h), "matrix"),
                pre + "shared.gate": ((h, fe), "matrix"),
                pre + "shared.up": ((h, fe), "matrix"),
                pre + "shared.down": ((fe, h), "matrix")})
        out[pre + "post_mlp_norm"] = ((h,), "norm")
    out.update({"norm": ((h,), "norm"),
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


_PART = {"q": "attn.q_proj.weight", "k": "attn.k_proj.weight",
         "v": "attn.v_proj.weight", "gate": "attn.gate_proj.weight",
         "o": "attn.o_proj.weight", "q_norm": "attn.q_norm.weight",
         "k_norm": "attn.k_norm.weight",
         "mlp.gate": "mlp.gate_proj.weight", "mlp.up": "mlp.up_proj.weight",
         "mlp.down": "mlp.down_proj.weight", "router": "moe.router",
         "experts.gate": "moe.w_gate", "experts.up": "moe.w_up",
         "experts.down": "moe.w_down", "shared.gate": "moe.shared_gate",
         "shared.up": "moe.shared_up", "shared.down": "moe.shared_down"}


def program_name(name):
    """Onto `paddle_tpu.models.afmoe` parameter names."""
    top = {"wte": "model.embed_tokens.weight", "norm": "model.norm.weight",
           "lm_head": "lm_head"}
    if name in top:
        return top[name]
    _, i, part = name.split(".", 2)
    return f"model.layers.{i}." + _PART.get(part, part + ".weight")


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

def build(ctx):
    """The program's own entry points, as drivers/train.py builds them."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

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
    window = cfg["sliding_window"]
    if ctx.get("plant") == "window_ignored":   # every layer the causal half
        window = cell["job"]["sequence_length"]
    types = [cfg["layer_types"][i] for i in cfg["layers_held"]]
    inner = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=types[:cfg["num_hidden_layers"]],
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"], num_experts_held=cfg["num_experts"],
        expert_start=cfg["expert_start"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"], sliding_window=window,
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        route_scale=cfg["route_scale"], route_norm=cfg["route_norm"],
        mup_enabled=cfg["mup_enabled"],
        initializer_range=cfg["initializer_range"], **opts["model"]))
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
    driver (harness/check.py); `window_ignored` was built into the model;
    `expert_dropped` leaves the first held expert's output out."""
    fault = ctx.get("plant")
    if fault == "expert_dropped":
        import jax.numpy as jnp
        from paddle_tpu.incubate.distributed.models import routed_moe

        route, first = routed_moe.sigmoid_topk_route, ctx["config"]["expert_start"]

        def dropped(*a, **kw):
            idx, w = route(*a, **kw)
            return idx, jnp.where(idx == first, 0.0, w)

        routed_moe.sigmoid_topk_route = dropped
    elif fault and fault not in FAULTS:
        step = check.plant_train_fault(step, fault)
    return step


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


class _RowSums:
    """The expert layers' buffers hold what the LAST step counted
    (`row_counts`, `expert_rows`); this sums them on the device after each
    step the driver dispatches, one tiny program compiled in set-up.  A
    run's sums stay far inside int32; `read` is a fetch, outside the
    timing."""

    def __init__(self, step):
        import jax
        import jax.numpy as jnp

        self._names = [n for n in step._state["buffers"]
                       if n.rpartition(".")[2] in ("row_counts", "expert_rows")]
        self._sum = {n: jnp.zeros_like(step._state["buffers"][n])
                     for n in self._names}
        self._add = jax.jit(lambda acc, new: jax.tree_util.tree_map(
            jnp.add, acc, new))

    def add(self, step):
        self._sum = self._add(
            self._sum, {n: step._state["buffers"][n] for n in self._names})

    def read(self):
        """{layer: {routed, computed, dropped, expert_rows}} so far."""
        from paddle_tpu.incubate.distributed.models import routed_moe

        return routed_moe.row_counters(self._sum)


def _moe_delta(before, after, steps, tokens_per_step):
    """What the expert layers did between two readings of the sums."""
    rows = {k: sum(after[l][k] - before[l][k] for l in after)
            for k in ("routed", "computed", "dropped")}
    load = [(a - b) / steps for l in after for a, b in
            zip(after[l]["expert_rows"], before[l]["expert_rows"])]
    return {"steps": steps, "tokens": steps * tokens_per_step,
            "layers": len(after), **rows,
            "expert_load": {"min": min(load), "max": max(load),
                            "mean": sum(load) / len(load)} if load else None}


def _load_in_band(ctx, moe):
    """The window's rows an expert a step (min, mean, max over the held
    experts of every layer) against the cell's band, in units of a balanced
    router's load (tokens a step x experts per token / router width): a
    router that drifts fails the run and does not quietly speed it up."""
    cfg, band = ctx["config"], ctx["cell"]["sane"]["expert_load"]
    balanced = (moe["tokens"] / moe["steps"] * cfg["num_experts_per_tok"]
                / cfg["router_width"])
    load = {k: v / balanced for k, v in moe["expert_load"].items()}
    ok = all(lo <= load[k] <= hi for k, (lo, hi) in band.items())
    log("sane", {"expert_load_over_balanced": load, "band": band, "ok": ok})
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
    step = _plant(ctx, step)
    grad_norms_fn, change_fn = _readers(ctx, step)
    sums = _RowSums(step)
    per_step = job["global_batch"] * job["sequence_length"]

    def feed(i):
        ids, labels = traffic.train_batch(job, vocab, seed, i)
        return P.to_tensor(ids, "int32"), P.to_tensor(labels, "int32")

    def one(batch):
        loss = step(*batch)
        sums.add(step)
        return loss

    # --- the first steps, through the window's own call and feed ----------
    got = {"losses": []}
    for i in range(FIRST_STEPS):
        got["losses"].append(float(one(feed(i))))
        if i == 0:
            got["grad_norms"] = {n: float(v) for n, v in grad_norms_fn(
                step._state["opt"]["slots"]).items()}
    got["change_norms"] = {n: float(v) for n, v in change_fn(
        step._state["params"], key_of(seed)).items()}
    float(one(feed(FIRST_STEPS)))         # step 4 keeps that state; warm
    c1 = dict(metrics.snapshot()["counters"])
    moe0 = sums.read()
    dispatch = common.counters_delta(c0, c1, ("flash.", "autotune.", "moe.", "head_ce."))
    dispatch.update({f"moe.rows{{kind={k}}}": sum(l[k] for l in moe0.values())
                     for k in ("routed", "computed", "dropped")})
    log("dispatch", dispatch)
    compiled_before = compiles.n

    # --- the window -------------------------------------------------------
    fetch_every = job["fetch_loss_every"]
    tr = tracing.Tracer() if ctx["trace"] else None
    setup_s = time.time() - common.T_PROCESS_START
    t_start = time.perf_counter()
    n, last, traced = 0, None, False
    fetched = []      # seconds into the window at which each loss fetch returned
    pause_s, pause_steps = 0.0, 0
    moe_traced = None
    while time.perf_counter() - t_start < seconds:
        if tr and not traced and time.perf_counter() - t_start > 0.4 * seconds:
            float(last) if last is not None else None
            t_pause = time.perf_counter()
            before = sums.read()
            tr.start()
            for _ in range(cell["trace"]["steps"]):
                with tr.span("bench.make_batch"):
                    b = feed(FIRST_STEPS + 1 + n)
                with tr.span("bench.dispatch"):
                    last = one(b)
                n += 1
            with tr.span("bench.fetch_loss"):
                float(last)
            tr.stop()
            moe_traced = _moe_delta(before, sums.read(),
                                    cell["trace"]["steps"], per_step)
            log("expert_load", moe_traced)
            traced = True
            pause_s = time.perf_counter() - t_pause
            pause_steps = cell["trace"]["steps"]
            continue
        last = one(feed(FIRST_STEPS + 1 + n))
        n += 1
        if n % fetch_every == 0:
            float(last)
            fetched.append(round(time.perf_counter() - t_start, 3))
    final_loss = float(last)              # the value fetch closes the window
    window = time.perf_counter() - t_start
    in_window = compiles.n - compiled_before
    moe_all = _moe_delta(moe0, sums.read(), n, per_step)
    log("window", {"steps": n, "seconds": window, "final_loss": final_loss,
                   "compilations_in_window": in_window,
                   "fetched_at_s": fetched, "moe_rows": moe_all})
    held, reserved = common.memory_peak_parts(ctx["devices"])
    mem = held + reserved
    log("memory", {"peak_bytes_in_use": held, "peak_bytes_reserved": reserved})
    tps = n * per_step / window
    # the traced run pauses for the profiler: its rate is that of the rest
    tps_untraced = (n - pause_steps) * per_step / (window - pause_s)
    state = {"tokens_per_s": tps_untraced, "memory_peak_bytes": mem,
             "chips": len(ctx["devices"]), "moe_traced": moe_traced,
             "moe_window": moe_all}

    # --- free the program, then the reference ------------------------------
    step._state = None
    del step, grad_norms_fn, change_fn, sums
    import gc

    gc.collect()
    sane = (np.isfinite(final_loss) and in_window == 0
            and moe_all["dropped"] == 0 and _load_in_band(ctx, moe_all))
    checks, detail = _checks(ctx, got, sane)
    for line in detail:
        log(line)
    e2e = {"train_tokens_per_s": (tps, "tokens/s"), "setup_s": (setup_s, "s")}
    return {"e2e": e2e, "state": state, "tracer": tr, "checks": checks,
            "attempted": n, "failed": 0 if np.isfinite(final_loss) else n,
            "memory_peak_bytes": mem}
