"""Driver `train_keye`: the fleet train step over `KeyeForCausalLM` (learned
sparse attention, softmax-routed experts), driven as `drivers/train_afmoe.py`
drives its model — one `step(ids, labels)` call per step with a new batch
each step, the window closed by a value fetch, ONE compiled step for set-up
and window — with this model's weights from the seed, its faults, and two
sets of per-layer device counters summed over the steps it dispatches: the
expert layers' rows (`train_afmoe`'s sums) and the attention's (query, key)
pairs (`sparse_attn.pairs{kind=selected|computed|causal}`; the selected
pairs are held to their closed form in EVERY step).
"""
from __future__ import annotations

import functools
import time

import numpy as np

from harness import check, common, tracing, traffic
from harness.common import log
from harness.weights import key_of      # a PRNG key from any whole number

afmoe = common.load_module("drivers", "train_afmoe")   # the row sums, the band

FIRST_STEPS = check.FIRST_STEPS
FAULTS = ("selection_ignored", "indexer_loss_dropped")   # this model's own
LIMB = 20       # the pair sums' low limb, in bits (a run's sums pass int32)
# the trace-time counters of the four entries that have a jax.numpy form
# beside their Pallas kernels: attention, index scores, search, loss
KERNEL_ENTRIES = ("sparse_attn.dispatch{kernel=%s}",
                  "sparse_index.dispatch{kernel=%s,op=scores}",
                  "sparse_index.dispatch{kernel=%s,op=select}",
                  "sparse_index.dispatch{kernel=%s,op=loss}")


# --- weights from the seed, framework-neutral names -----------------------

def shapes(cfg):
    """name -> (shape, kind); kind is 'matrix', 'norm' (about one) or
    'bias' (about nought)."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    fe, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    sa = cfg["sa_config"]
    j, di = sa["indexer_num_heads"], sa["indexer_head_dim"]
    out = {"wte": ((cfg["vocab_size"], h), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        pre = f"h.{i}."
        out.update({
            pre + "input_norm": ((h,), "norm"),
            pre + "q": ((h, nq), "matrix"), pre + "k": ((h, nkv), "matrix"),
            pre + "v": ((h, nkv), "matrix"), pre + "o": ((nq, h), "matrix"),
            pre + "q_norm": ((d,), "norm"), pre + "k_norm": ((d,), "norm"),
            pre + "idx.q": ((h, j * di), "matrix"),
            pre + "idx.k": ((h, di), "matrix"),
            pre + "idx.k_norm.w": ((di,), "norm"),
            pre + "idx.k_norm.b": ((di,), "bias"),
            pre + "idx.w": ((h, j), "matrix"),
            pre + "post_attn_norm": ((h,), "norm"),
            pre + "router": ((h, cfg["router_width"]), "matrix"),
            pre + "experts.gate": ((e, h, fe), "matrix"),
            pre + "experts.up": ((e, h, fe), "matrix"),
            pre + "experts.down": ((e, fe, h), "matrix")})
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
         "v": "attn.v_proj.weight", "o": "attn.o_proj.weight",
         "q_norm": "attn.q_norm.weight", "k_norm": "attn.k_norm.weight",
         "idx.q": "attn.indexer.q_proj.weight",
         "idx.k": "attn.indexer.k_proj.weight",
         "idx.k_norm.w": "attn.indexer.k_norm.weight",
         "idx.k_norm.b": "attn.indexer.k_norm.bias",
         "idx.w": "attn.indexer.w_proj.weight", "router": "moe.router",
         "experts.gate": "moe.w_gate", "experts.up": "moe.w_up",
         "experts.down": "moe.w_down"}


def program_name(name):
    """Onto `paddle_tpu.models.keye` parameter names."""
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

def model_config(cfg, opts, topk=None):
    """`KeyeConfig` from the configuration file's keys."""
    from paddle_tpu.models.keye import KeyeConfig

    sa = cfg["sa_config"]
    return KeyeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"], num_experts_held=cfg["num_experts"],
        expert_start=cfg["expert_start"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rope_theta=float(cfg["rope_theta"]),
        mrope_section=cfg["rope_scaling"]["mrope_section"],
        rms_eps=cfg["rms_norm_eps"], indexer_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"],
        indexer_topk=sa["topk"] if topk is None else topk,
        initializer_range=cfg["initializer_range"], **opts)


def build(ctx):
    """The program's own entry points, as drivers/train.py builds them."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    from paddle_tpu.models.keye import KeyeForCausalLM

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
    topk = None
    if ctx.get("plant") == "selection_ignored":   # every causal key attended
        topk = cell["job"]["sequence_length"]
    inner = KeyeForCausalLM(model_config(cfg, opts["model"], topk))
    if ctx.get("plant") == "indexer_loss_dropped":
        inner.pop_aux_loss = lambda: None
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
    driver (harness/check.py); this model's own faults were built in."""
    fault = ctx.get("plant")
    if fault and fault not in FAULTS:
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


def selected_pairs(batch, seq, topk):
    """sum over the queries of min(t + 1, topk), a batch's worth."""
    return batch * common.load_module("readers", "cost_keye").selected_pairs(
        seq, topk)


class _PairSums:
    """The attention layers' `pair_counts` buffers hold what the LAST step
    counted; this sums them on the device after each step the driver
    dispatches (two int32 limbs: a window's pairs pass 2^31) and counts the
    layer-steps whose selected pairs were not `expected`.  One tiny
    program, compiled in set-up; `read` is a fetch, outside the timing."""

    def __init__(self, step, expected):
        import jax
        import jax.numpy as jnp

        self._names = sorted(n for n in step._state["buffers"]
                             if n.endswith(".pair_counts"))
        zero = jnp.zeros((len(self._names), 3), jnp.int32)
        self._acc = (zero, zero, jnp.int32(0))

        def add(acc, new):
            lo, hi, off = acc
            x = jnp.stack(new)
            lo = lo + (x & ((1 << LIMB) - 1))
            hi = hi + (x >> LIMB) + (lo >> LIMB)
            return (lo & ((1 << LIMB) - 1), hi,
                    off + jnp.sum(x[:, 0] != expected, dtype=jnp.int32))

        self._add = jax.jit(add)

    def add(self, step):
        self._acc = self._add(
            self._acc, [step._state["buffers"][n] for n in self._names])

    def read(self):
        """{selected, computed, causal: sums over layers and steps so far,
        off_form: layer-steps whose selection missed its closed form}."""
        lo, hi, off = (np.asarray(a).astype(object) for a in self._acc)
        total = (hi * (1 << LIMB) + lo).sum(axis=0) if len(self._names) \
            else [0, 0, 0]
        return {"selected": int(total[0]), "computed": int(total[1]),
                "causal": int(total[2]), "off_form": int(off)}


def _delta(before, after):
    return {k: after[k] - before[k] for k in after}


def _on_kernels(ctx, dispatch):
    """On a TPU each of the four entries took its Pallas kernels once a
    layer when the step was traced, and its jax.numpy form never: a
    fallback inside a measured run then reads `correct: false` and not a
    slow number (PERF.md section 6, PR 35: a predicate on the wrong shape
    once took two of them off the kernels, seen only in a trace)."""
    if ctx["devices"][0].platform != "tpu":
        return True
    layers = ctx["config"]["num_hidden_layers"]
    took = {e % "*": [dispatch.get(e % "pallas", 0),
                      dispatch.get(e % "reference", 0)]
            for e in KERNEL_ENTRIES}
    ok = all(v == [layers, 0] for v in took.values())
    log("sane", {"kernel_dispatch_pallas_reference": took, "layers": layers,
                 "ok": ok})
    return ok


def _selection_gap(ctx, ref_mod):
    """Share of the (query, key) pairs of LAYER 0's selection, first batch,
    on which the program (its own indexer, norm and search on bfloat16
    weights and activations, as the step runs them) and the float32
    reference differ: the selection is a discrete choice, and this is how
    far rounding moves it.  Later layers' inputs already differ."""
    import jax
    import jax.numpy as jnp
    import paddle_tpu as P
    from paddle_tpu import nn
    from paddle_tpu.nn import functional as F

    cfg, cell = ctx["config"], ctx["cell"]
    sa = cfg["sa_config"]
    ids, _ = traffic.train_batch(cell["job"], cfg["vocab_size"], ctx["seed"], 0)
    tree = make(cfg, ctx["seed"], "float32")
    amp = cell["options"]["build_train_step"].get("amp_dtype") or "float32"
    cast = lambda n: P.to_tensor(tree[n].astype(amp))
    norm = nn.RMSNorm(cfg["hidden_size"], epsilon=cfg["rms_norm_eps"])
    idx = nn.SparseIndexer(cfg["hidden_size"], sa["indexer_num_heads"],
                           sa["indexer_head_dim"],
                           rope_theta=float(cfg["rope_theta"]),
                           epsilon=cfg["rms_norm_eps"])
    for layer, name in ((norm, "input_norm"), (idx.q_proj, "idx.q"),
                        (idx.k_proj, "idx.k"), (idx.w_proj, "idx.w"),
                        (idx.k_norm, "idx.k_norm.w")):
        layer.weight._value = cast("h.0." + name)._value
    idx.k_norm.bias._value = cast("h.0.idx.k_norm.b")._value
    x = P.to_tensor(tree["wte"].astype(amp)[jnp.asarray(ids)])
    got = F.sparse_select_topk(idx(norm(x)), sa["topk"])[0]._value > 0

    @jax.jit
    def want(p, ids):
        y = ref_mod._rms(p["wte"][ids], p["h.0.input_norm"], cfg["rms_norm_eps"])
        q, k, w = ref_mod.index_scores(cfg, p, "h.0.", y,
                                       ref_mod.text_positions(ids))
        b, t = ids.shape
        rows = min(ref_mod.Q_BLOCK, t)

        def block(a):
            qb, wb, i0 = a
            z = jnp.einsum("bqjd,bkd->bqjk", qb, k, precision=ref_mod.HIGHEST)
            sc = jnp.sum(jax.nn.relu(z) * wb[..., None], axis=2)
            return ref_mod.select(sc, i0 + jnp.arange(rows), sa["topk"])

        cut = lambda a: a.reshape(b, t // rows, rows, *a.shape[2:]).swapaxes(0, 1)
        out = jax.lax.map(block, (cut(q), cut(w), jnp.arange(0, t, rows)))
        return out.swapaxes(0, 1).reshape(b, t, t)

    ref_mask = want(tree, jnp.asarray(ids))
    differ = int(jnp.sum(got != ref_mask, dtype=jnp.int32))
    selected = int(jnp.sum(ref_mask, dtype=jnp.int32))
    return {"layer": 0, "pairs_selected": selected, "pairs_differ": differ,
            "share": differ / selected}


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
              f"{got['losses']}, 'numbers': {numbers}, 'where': {where}, "
              f"'selection': {_selection_gap(ctx, ref_mod)}}}"]
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
    per_step = job["global_batch"] * job["sequence_length"]
    expected = selected_pairs(job["global_batch"], job["sequence_length"],
                              cfg["sa_config"]["topk"])
    rows, pairs = afmoe._RowSums(step), _PairSums(step, expected)

    def feed(i):
        ids, labels = traffic.train_batch(job, vocab, seed, i)
        return P.to_tensor(ids, "int32"), P.to_tensor(labels, "int32")

    def one(batch):
        loss = step(*batch)
        rows.add(step)
        pairs.add(step)
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
    moe0, pairs0 = rows.read(), pairs.read()
    dispatch = common.counters_delta(
        c0, c1, ("flash.", "autotune.", "moe.", "head_ce.", "sparse_attn.",
                 "sparse_index."))
    dispatch.update({f"moe.rows{{kind={k}}}": sum(l[k] for l in moe0.values())
                     for k in ("routed", "computed", "dropped")})
    dispatch.update({f"sparse_attn.pairs{{kind={k}}}": pairs0[k]
                     for k in ("selected", "computed", "causal")})
    log("dispatch", dispatch)
    on_kernels = _on_kernels(ctx, dispatch)
    compiled_before = compiles.n

    # --- the window -------------------------------------------------------
    fetch_every = job["fetch_loss_every"]
    tr = tracing.Tracer() if ctx["trace"] else None
    setup_s = time.time() - common.T_PROCESS_START
    t_start = time.perf_counter()
    n, last, traced = 0, None, False
    fetched = []      # seconds into the window at which each loss fetch returned
    pause_s, pause_steps = 0.0, 0
    moe_traced = pairs_traced = None
    while time.perf_counter() - t_start < seconds:
        if tr and not traced and time.perf_counter() - t_start > 0.4 * seconds:
            float(last) if last is not None else None
            t_pause = time.perf_counter()
            before, pairs_before = rows.read(), pairs.read()
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
            moe_traced = afmoe._moe_delta(before, rows.read(),
                                          cell["trace"]["steps"], per_step)
            pairs_traced = _delta(pairs_before, pairs.read())
            log("expert_load", moe_traced)
            log("pairs", pairs_traced)
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
    moe_all = afmoe._moe_delta(moe0, rows.read(), n, per_step)
    pairs_end = pairs.read()
    pairs_all = _delta(pairs0, pairs_end)
    log("window", {"steps": n, "seconds": window, "final_loss": final_loss,
                   "compilations_in_window": in_window,
                   "fetched_at_s": fetched, "moe_rows": moe_all,
                   "pairs": pairs_all})
    held, reserved = common.memory_peak_parts(ctx["devices"])
    mem = held + reserved
    log("memory", {"peak_bytes_in_use": held, "peak_bytes_reserved": reserved})
    tps = n * per_step / window
    # the traced run pauses for the profiler: its rate is that of the rest
    tps_untraced = (n - pause_steps) * per_step / (window - pause_s)
    state = {"tokens_per_s": tps_untraced, "memory_peak_bytes": mem,
             "chips": len(ctx["devices"]), "moe_traced": moe_traced,
             "moe_window": moe_all, "pairs_traced": pairs_traced,
             "pairs_window": pairs_all}

    # --- free the program, then the reference ------------------------------
    step._state = None
    del step, grad_norms_fn, change_fn, rows, pairs
    import gc

    gc.collect()
    # every step of the run, the first four among them, selected its closed
    # form (a planted `selection_ignored` is caught by the comparison and
    # by this count alike)
    on_form = pairs_end["off_form"] == 0
    log("sane", {"selected_pairs_expected_a_layer_step": expected,
                 "layer_steps_off_form": pairs_end["off_form"]})
    sane = (np.isfinite(final_loss) and in_window == 0
            and moe_all["dropped"] == 0 and afmoe._load_in_band(ctx, moe_all)
            and on_form and on_kernels)
    checks, detail = _checks(ctx, got, sane)
    for line in detail:
        log(line)
    e2e = {"train_tokens_per_s": (tps, "tokens/s"), "setup_s": (setup_s, "s")}
    return {"e2e": e2e, "state": state, "tracer": tr, "checks": checks,
            "attempted": n, "failed": 0 if np.isfinite(final_loss) else n,
            "memory_peak_bytes": mem}
