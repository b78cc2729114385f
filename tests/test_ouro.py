"""`models/ouro.py` (a layer stack run T times a step, a learned exit gate
after each pass, a loss over the exit distribution) against the benchmark's
plain reference (`benchmark/reference/ouro.py`: jax.numpy, float32, imports
nothing of paddle_tpu) at the new cell's tiny stand-in, seeded random
weights, on the CPU; the weighted head + cross-entropy scan against a plain
weighted cross entropy; and the cell's rehearsal end to end."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "ouro-2.6b-pp8.train.seq4096"
S = 64


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own loaders (found by path: `benchmark/` is no
    package), the cell's stand-in configuration, the driver and the
    reference."""
    for p in (BENCH, ROOT):
        if p not in sys.path:
            sys.path.insert(0, p)
    from harness import common

    cell = common.load_json("workloads", CELL + ".json")
    config = common.load_json("configs", cell["config"] + ".json")
    for dotted, value in cell["rehearse"].items():     # the tiny stand-in
        tree, *keys = dotted.split(".")
        node = {"cell": cell, "config": config}[tree]
        for k in keys[:-1]:
            node = node[k]
        node[keys[-1]] = value
    return (config, common.load_module("drivers", "train_ouro"),
            common.load_module("reference", "ouro"))


def _model(config, drv, seed=5, recompute=False, **over):
    import paddle_tpu as P
    from paddle_tpu.models.ouro import OuroForCausalLM

    cfg = dict(config, **over)
    P.seed(seed)
    model = OuroForCausalLM(drv.model_config(
        cfg, {"recompute": recompute}))
    tree = drv.make(cfg, seed)
    drv.load_into(model, tree)
    model.train()
    return cfg, model, tree


def _batch(cfg, seed, rows=2):
    rs = np.random.RandomState(seed)
    draw = lambda: rs.randint(0, cfg["vocab_size"], (rows, S)).astype(np.int32)
    return draw(), draw()


def _loss_fn(model, ids, labels):
    """loss(params) -> (loss, the T passes' logits [T, B, S, V])."""
    import paddle_tpu as P
    from paddle_tpu.core import flags
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    crit = GPTPretrainingCriterion(model=model)
    _, buffers = model.functional_state()

    def loss(params):
        with model.bind_state(params, buffers), flags.trace_guard():
            states = model(P.to_tensor(ids))
            value = crit(states, P.to_tensor(labels))._value
            logits = jnp.einsum("tbsh,vh->tbsv", states._value,
                                params["lm_head"])
        return value, logits

    return loss


@pytest.mark.parametrize("steps", [1, 4])
def test_passes_loss_and_gradients_match_the_reference(bench, steps):
    config, drv, ref = bench
    cfg, model, tree = _model(config, drv, total_ut_steps=steps)
    ids, labels = _batch(cfg, 1)
    params, _ = model.functional_state()
    names = {n: drv.program_name(n) for n in drv.shapes(cfg)}
    assert sorted(names.values()) == sorted(params)
    (value, got_logits), got = jax.value_and_grad(
        _loss_fn(model, ids, labels), has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        states, _ = ref.passes(cfg, tree, jnp.asarray(ids))
        want_logits = jnp.stack([h @ tree["lm_head"].T for h in states])
        want_loss, want = ref.loss_and_grads(
            cfg, tree, jnp.asarray(ids)[None], jnp.asarray(labels)[None])
    assert got_logits.shape[0] == steps
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-4)
    assert abs(float(value) - float(want_loss)) < 2e-6 * float(want_loss)
    for n, pn in names.items():
        g, w = np.asarray(got[pn]), np.asarray(want[n])
        assert np.abs(g - w).max() <= 3e-4 * max(np.abs(w).max(), 1e-6), n
    gate = float(np.abs(np.asarray(got["model.early_exit_gate.weight"])).max())
    assert (gate > 0) == (steps > 1)   # the last pass's gate is never read


def test_exit_distribution_is_the_products_and_sums_to_one(bench):
    from paddle_tpu.models.ouro import exit_distribution

    _, _, ref = bench
    z = jnp.asarray(np.random.RandomState(4).randn(3, 2, 5) * 3, jnp.float32)
    p, ent = exit_distribution(z)
    want_p, want_ent = ref.exit_distribution(z)
    np.testing.assert_allclose(p, want_p, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(ent, want_ent, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-6)
    lam = jax.nn.sigmoid(z)
    np.testing.assert_allclose(p[2], lam[2] * (1 - lam[0]) * (1 - lam[1]),
                               rtol=1e-5)
    one, none = exit_distribution(jnp.zeros((0, 2, 5)))
    assert one.shape == (1, 2, 5) and float(one.min()) == 1.0
    assert float(jnp.abs(none).max()) == 0.0


def _plain_weighted_ce(h, w, labels, weights):
    """sum over read-outs and valid tokens of weight x CE, over the valid
    tokens of ONE read-out: the [P * B, S, V] logits whole."""
    reps = h.shape[0] // labels.shape[0]
    lab = jnp.tile(labels, (reps, 1))
    valid = lab != -100
    logits = jnp.einsum("bsh,vh->bsv", h, w,
                        precision=jax.lax.Precision.HIGHEST)
    lse = jax.nn.logsumexp(logits, -1)
    picked = jnp.take_along_axis(logits, jnp.where(valid, lab, 0)[..., None],
                                 -1)[..., 0]
    ce = jnp.where(valid, lse - picked, 0.0)
    total = jnp.sum(ce if weights is None else weights * ce)
    return total / jnp.maximum(jnp.sum(labels != -100), 1)


def _labels(rs, b, s, v):
    lab = rs.randint(0, v, (b, s)).astype(np.int32)
    lab[rs.rand(b, s) < 0.2] = -100
    return jnp.asarray(lab)


@pytest.mark.parametrize("reps,b,s", [(3, 2, 40), (2, 1, 97), (4, 3, 64)])
def test_weighted_head_ce_matches_a_plain_weighted_ce(reps, b, s):
    """Value and the gradients by h, W and the weights (each token's CE),
    at P read-outs of b rows; (2, 1, 97) pads the last slice."""
    from paddle_tpu.models.gpt import _fused_linear_ce, _token_slices

    if (reps, b, s) == (2, 1, 97):
        sc, n = _token_slices(reps * b, s, 251)
        assert n * sc > s                        # the padded case
    rs = np.random.RandomState(reps * 100 + s)
    hd, v = 16, 251
    h = jnp.asarray(rs.randn(reps * b, s, hd), jnp.float32)
    w = jnp.asarray(0.3 * rs.randn(v, hd), jnp.float32)
    labels = _labels(rs, b, s, v)
    weights = jnp.asarray(rs.rand(reps * b, s), jnp.float32)

    def fused(h, w, wt):
        total, count = _fused_linear_ce(h, w, labels, -100, wt)
        return total / jnp.maximum(count, 1.0)

    got = jax.value_and_grad(fused, (0, 1, 2))(h, w, weights)
    want = jax.value_and_grad(
        lambda h, w, wt: _plain_weighted_ce(h, w, labels, wt),
        (0, 1, 2))(h, w, weights)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, r in zip(got[1], want[1]):
        np.testing.assert_allclose(a, r, rtol=1e-4, atol=1e-6)


def test_unweighted_head_ce_is_the_weighted_one_at_unit_weights():
    """weights=None: the plain cross entropy, and the weighted scan at one
    read-out with every weight 1 to rounding."""
    from paddle_tpu.models.gpt import _fused_linear_ce

    rs = np.random.RandomState(7)
    h = jnp.asarray(rs.randn(4, 48, 16), jnp.float32)
    w = jnp.asarray(0.3 * rs.randn(317, 16), jnp.float32)
    labels = _labels(rs, 4, 48, 317)

    def fused(h, w, wt=None):
        total, count = _fused_linear_ce(h, w, labels, -100, wt)
        return total / jnp.maximum(count, 1.0)

    plain = jax.value_and_grad(fused, (0, 1))(h, w)
    want = jax.value_and_grad(
        lambda h, w: _plain_weighted_ce(h, w, labels, None), (0, 1))(h, w)
    unit = jax.value_and_grad(fused, (0, 1))(h, w, jnp.ones((4, 48)))
    for other in (want, unit):
        np.testing.assert_allclose(plain[0], other[0], rtol=1e-6)
        for a, r in zip(plain[1], other[1]):
            np.testing.assert_allclose(a, r, rtol=1e-5, atol=1e-7)


@pytest.fixture
def flash_on(monkeypatch):
    """The flash cores on the CPU (interpret mode), as on the chip."""
    from paddle_tpu.ops import pallas as pallas_pkg
    from paddle_tpu.ops.pallas import flash_attention as fa

    for mod in (fa, pallas_pkg):
        monkeypatch.setattr(mod, "flash_attention_available", lambda q: True)


def test_recomputation_changes_no_digit_and_counts_each_application(
        bench, flash_on):
    """Every layer APPLICATION is a recomputed segment that keeps its
    flash output + lse: L x T kept a step, `loop.apply{ut=k}` L for each
    pass, T exits, one weighted scan; the loss the same with recomputation
    on and off, exactly (equation by equation, no jit), and every gradient
    to float32 rounding."""
    from paddle_tpu.observability import metrics

    config, drv, _ = bench
    layers, steps = config["num_hidden_layers"], 4
    results, counted = {}, {}
    was = metrics.enabled()
    metrics.enable()
    try:
        for recompute in (False, True):
            cfg, model, _ = _model(config, drv, recompute=recompute)
            ids, labels = _batch(cfg, 2, rows=8)   # the test mesh: dp = 8
            loss = _loss_fn(model, ids, labels)
            params, _ = model.functional_state()
            before = dict(metrics.snapshot()["counters"])
            jax.make_jaxpr(jax.grad(lambda p: loss(p)[0]))(params)
            now = metrics.snapshot()["counters"]
            counted[recompute] = {k: v - before.get(k, 0)
                                  for k, v in now.items()
                                  if v - before.get(k, 0)}
            results[recompute] = jax.value_and_grad(
                lambda p: loss(p)[0])(params)
    finally:
        if not was:
            metrics.disable()
    for recompute, added in counted.items():
        for k in range(1, steps + 1):
            assert added[f"loop.apply{{ut={k}}}"] == layers
        assert added["loop.exit{weights=exit_dist}"] == steps
        assert added["head_ce.weights{kind=per_token}"] == 1
        assert added.get("flash.recompute_kept{what=out_lse}", 0) == \
            (layers * steps if recompute else 0)
    (v0, g0), (v1, g1) = results[False], results[True]
    assert float(v0) == float(v1)
    for name in g0:     # the replay sums a leaf's four passes in its own order
        a, r = np.asarray(g1[name]), np.asarray(g0[name])
        np.testing.assert_allclose(a, r, rtol=1e-5,
                                   atol=1e-6 * np.abs(r).max(), err_msg=name)


def test_eval_returns_the_last_pass_logits(bench):
    """Out of training the model runs all T passes and returns the last
    pass's logits, registering no loss term."""
    import paddle_tpu as P

    config, drv, ref = bench
    cfg, model, tree = _model(config, drv)
    ids, _ = _batch(cfg, 3)
    model.eval()
    logits = model(P.to_tensor(ids))
    assert model.pop_aux_loss() is None
    with jax.default_matmul_precision("highest"):
        states, _ = ref.passes(cfg, tree, jnp.asarray(ids))
        want = states[-1] @ tree["lm_head"].T
    assert len(states) == cfg["total_ut_steps"]
    np.testing.assert_allclose(np.asarray(logits._value), want, atol=2e-4)


@pytest.mark.parametrize("case", ["rows", "unweighted_read_outs", "unfused"])
def test_weights_are_refused_where_they_cannot_apply(case):
    """Hidden rows that are not whole read-outs of the labels' rows, P > 1
    read-outs without weights, and weights on the unfused path raise."""
    import paddle_tpu as P
    from paddle_tpu.models.gpt import GPTPretrainingCriterion, _fused_linear_ce

    rs = np.random.RandomState(11)
    labels = _labels(rs, 2, 16, 64)
    w = jnp.asarray(rs.randn(64, 8), jnp.float32)
    if case == "unfused":
        crit = GPTPretrainingCriterion()
        with pytest.raises(RuntimeError, match="fused head"):
            crit._token_loss(P.to_tensor(np.zeros((2, 16, 64), np.float32)),
                             P.to_tensor(np.asarray(labels)),
                             jnp.ones((2, 16)))
        return
    rows = 5 if case == "rows" else 4
    h = jnp.asarray(rs.randn(rows, 16, 8), jnp.float32)
    weights = jnp.ones((rows, 16)) if case == "rows" else None
    with pytest.raises(ValueError, match="read-outs"):
        _fused_linear_ce(h, w, labels, -100, weights)


def test_published_parameter_count(bench):
    """The 48-layer model by `eval_shape` (nothing allocated): the catalog
    row's widths give 2,667,974,657 parameters with the gate's bias."""
    import paddle_tpu  # noqa: F401
    from paddle_tpu.core import rng
    from paddle_tpu.models.ouro import OuroForCausalLM
    from harness import common

    _, drv, _ = bench
    cfg = common.load_json("configs", "ouro-2.6b-pp8.json")
    layers = cfg["published"]["num_hidden_layers"]
    state = rng.default_generator.get_state()

    def build(key):
        rng.default_generator.set_state(key)
        try:
            m = OuroForCausalLM(drv.model_config(
                dict(cfg, num_hidden_layers=layers), {}))
            return {n: p._value for n, p in m.named_parameters()}
        finally:
            rng.default_generator.set_state(state)

    shapes = jax.eval_shape(build, state)
    total = sum(math.prod(s.shape) for s in shapes.values())
    h, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    layer = h * (q + 2 * kv) + q * h + 3 * h * f + 4 * h
    assert layer == 51_388_416
    assert total == layers * layer + 2 * v * h + h + h + 1 == 2_667_974_657


def test_cost_ouro_by_hand(bench):
    """The cost reader at the cell's size: 11.0 GFLOP a token trained,
    the exits 21.9 % of it at 6 layers and 3.4 % at 48."""
    from harness import common

    cost = common.load_module("readers", "cost_ouro")
    cfg = common.load_json("configs", "ouro-2.6b-pp8.json")
    parts = cost.forward_flops_per_token(cfg, 4096)
    assert parts["proj"] + parts["mlp"] == 24 * 2 * 51_380_224
    assert parts["attn"] == 24 * 4 * 2048 * 4097 / 2
    assert parts["head"] == 4 * 2 * 2048 * 49152
    assert round(cost.train_flops_per_token(cfg, 4096) / 1e8) == 110
    assert round(100 * parts["head"] / sum(parts.values()), 1) == 21.9
    deep = cost.forward_flops_per_token(dict(cfg, num_hidden_layers=48), 4096)
    assert round(100 * deep["head"] / sum(deep.values()), 1) == 3.4


def test_new_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000011", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"rehearsed": True, "correct": True}
    sane = next(ln for ln in lines if ln.startswith("sane "))
    assert "'ok': True" in sane and "'loop_apply_total': 8" in sane
    assert any(ln.startswith("exit ") for ln in lines)
    would = json.loads(next(ln for ln in lines if ln.startswith(
        "would_print "))[len("would_print "):])
    for name in ("step_mfu.train.loop", "loop_exit_ms.train",
                 "loop_block_ms.train", "device_idle_share.train"):
        assert name in would["metrics"], name
    assert "step_mfu.train" not in would["metrics"]
