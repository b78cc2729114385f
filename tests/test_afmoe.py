"""`models/afmoe.py` against the benchmark's plain reference
(`benchmark/reference/afmoe.py`: jax.numpy, float32, imports nothing of
paddle_tpu) at the new cell's tiny stand-in, seeded random weights, on the
CPU; and the cell's rehearsal end to end."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "trinity-mini-ep8.train.seq8192"


@pytest.fixture(scope="module")
def bench():
    """The benchmark's own loaders (found by path: `benchmark/` is no
    package), the driver's weights and the reference."""
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
    return (config, common.load_module("drivers", "train_afmoe"),
            common.load_module("reference", "afmoe"))


def _model(config, drv, seed=5, **over):
    import paddle_tpu as P
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

    cfg = dict(config, **over)
    P.seed(seed)
    types = [cfg["layer_types"][i] for i in cfg["layers_held"]]
    model = AfmoeForCausalLM(AfmoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        layer_types=types, num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["router_width"], num_experts_held=cfg["num_experts"],
        expert_start=cfg["expert_start"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        sliding_window=cfg["sliding_window"],
        rope_theta=float(cfg["rope_theta"]), rms_eps=cfg["rms_norm_eps"],
        route_scale=cfg["route_scale"], route_norm=cfg["route_norm"]))
    tree = drv.make(cfg, seed)
    drv.load_into(model, tree)
    return cfg, model, tree


@pytest.mark.parametrize("held,start", [(2, 0), (2, 4), (8, 0)])
def test_forward_loss_and_gradients_match_the_reference(bench, held, start):
    config, drv, ref = bench
    cfg, model, tree = _model(config, drv, num_experts=held,
                              expert_start=start)
    rs = np.random.RandomState(1)
    ids = rs.randint(0, cfg["vocab_size"], (2, 64)).astype(np.int32)
    labels = rs.randint(0, cfg["vocab_size"], (2, 64)).astype(np.int32)
    params, buffers = model.functional_state()
    names = {n: drv.program_name(n) for n in drv.shapes(cfg)}
    assert sorted(names.values()) == sorted(params)

    def loss(params):
        logits, _ = model.functional_call(params, buffers, jnp.asarray(ids))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.mean(lse - picked), logits

    (got_loss, got_logits), got = jax.value_and_grad(loss, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        want_logits = ref.logits(cfg, tree, jnp.asarray(ids))
        want_loss, want = ref.loss_and_grads(
            cfg, tree, jnp.asarray(ids)[None], jnp.asarray(labels)[None])
    # float32 on both sides: what is left is the order of summation, grown
    # by the embedding's scale (sqrt(64) = 8) and five layers of norms
    np.testing.assert_allclose(got_logits, want_logits, atol=5e-4)
    assert abs(float(got_loss) - float(want_loss)) < 2e-6 * float(want_loss)
    for n, pn in names.items():
        g, w = np.asarray(got[pn]), np.asarray(want[n])
        # by the leaf's largest entry: a router's gradient is tiny beside it
        assert np.abs(g - w).max() <= 2e-4 * max(np.abs(w).max(), 1e-6), n


def test_window_reaches_the_sliding_layers_only(bench):
    """The sliding layers see `sliding_window` keys and carry rotary
    positions; the full layer sees the whole causal half and carries none:
    a window as long as the sequence changes the logits, and the
    reference's `window_ignored` fault is that very change."""
    config, drv, ref = bench
    cfg, model, tree = _model(config, drv)
    ids = jnp.asarray(np.random.RandomState(2).randint(
        0, cfg["vocab_size"], (1, 64)).astype(np.int32))
    model.eval()
    base = np.asarray(model(ids)._value)
    _, wide, _ = _model(config, drv, sliding_window=64)
    wide.eval()
    widened = np.asarray(wide(ids)._value)
    assert np.abs(widened[:, :32] - base[:, :32]).max() < 5e-4   # inside
    assert np.abs(widened[:, 40:] - base[:, 40:]).max() > 1e-2   # past it
    h_fault = ref.hidden(cfg, tree, ids, fault="window_ignored")
    h_wide = ref.hidden(dict(cfg, sliding_window=64), tree, ids)
    np.testing.assert_allclose(h_fault, h_wide, atol=1e-5)


def test_fused_head_is_gpts_scan_and_counts_rows_under_recompute(bench):
    """Training with `fused_head_ce` hands hidden states to
    `GPTPretrainingCriterion(model=...)`, which projects with the model's
    untied head; per-layer recompute leaves loss and row counters as they
    are."""
    import paddle_tpu as P
    from paddle_tpu.incubate.distributed.models import routed_moe
    from paddle_tpu.models.gpt import GPTPretrainingCriterion

    config, drv, ref = bench
    rs = np.random.RandomState(3)   # 8 rows: the test mesh has dp = 8
    ids = rs.randint(0, config["vocab_size"], (8, 64)).astype(np.int32)
    labels = rs.randint(0, config["vocab_size"], (8, 64)).astype(np.int32)
    losses, rows = [], []
    for recompute in (False, True):
        cfg, model, tree = _model(config, drv)
        model.cfg.fused_head_ce, model.cfg.recompute = True, recompute
        model.train()
        crit = GPTPretrainingCriterion(model=model)
        params, buffers = model.functional_state()

        def loss(params):
            with model.bind_state(params, buffers) as (_, nb):
                from paddle_tpu.core import flags
                with flags.trace_guard():
                    out = model(P.to_tensor(ids))
                    value = crit(out, P.to_tensor(labels))._value
                return value, {n: b._value for n, b in nb.items()}

        (value, new_buffers), grads = jax.value_and_grad(
            loss, has_aux=True)(params)
        losses.append(float(value))
        rows.append(routed_moe.row_counters(new_buffers))
        assert all(np.isfinite(np.asarray(g)).all() for g in grads.values())
    with jax.default_matmul_precision("highest"):
        want, _ = ref.loss_and_grads(cfg, tree, jnp.asarray(ids)[None],
                                     jnp.asarray(labels)[None])
    assert abs(losses[0] - float(want)) < 2e-6 * float(want)
    assert losses[0] == losses[1] and rows[0] == rows[1]
    assert len(rows[0]) == 4 and all(r["dropped"] == 0 and r["routed"] > 0
                                     for r in rows[0].values())


def test_cost_afmoe_by_hand(bench):
    """ISSUE 28's arithmetic at the published widths: 738 MFLOP a token
    forward, 2.21 GFLOP trained."""
    sys.path.insert(0, BENCH)
    from harness import common

    cost = common.load_module("readers", "cost_afmoe")
    cfg = common.load_json("configs", "trinity-mini-ep8.json")
    parts = cost.forward_flops_per_token(cfg, 8192)
    assert cost.attended_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048
    assert parts["proj"] == 5 * (2 * 2048 * (8192 + 1024) + 2 * 4096 * 2048)
    assert parts["dense_mlp"] == 6 * 2048 * 6144
    assert parts["routed"] == 4 * 6 * 2048 * 1024    # one expert a token here
    assert parts["head"] == 2 * 2048 * 25024
    assert round(sum(parts.values()) / 1e6) == 738
    assert round(cost.train_flops_per_token(cfg, 8192) / 1e7) == 221
    # the rows the window COUNTED take the balanced router's place: one a
    # token a layer is the same number, a sixth of it is less work
    counted = lambda routed: cost.train_flops_per_token(
        cfg, 8192, {"moe_window": {"routed": routed, "tokens": 6000}})
    assert counted(4 * 6000) == cost.train_flops_per_token(cfg, 8192)
    assert (counted(4 * 6000) - counted(4 * 1000)
            == 3 * 4 * (5 / 6) * 6 * 2048 * 1024)
    banded = common.load_module("readers", "cost_flash_banded")
    flops, _ = banded.per_pass(2, 8192, 32, 4, 128, window=2048)
    assert flops == 7 * 2 * 2 * 32 * cost.attended_pairs(8192, 2048) * 128


def test_new_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2740003011", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"rehearsed": True, "correct": True}
    dispatch = next(ln for ln in lines if ln.startswith("dispatch "))
    assert "'moe.rows{kind=dropped}': 0" in dispatch
    would = json.loads(next(ln for ln in lines if ln.startswith(
        "would_print "))[len("would_print "):])
    for name in ("step_mfu.train.moe", "attn_window_ms.train",
                 "attn_full_ms.train", "moe_ms.train", "moe_dispatch_ms.train",
                 "moe_real_rows_share.train", "fwd_ms.train",
                 "head_ce_ms.train"):
        assert name in would["metrics"], name
    assert "step_mfu.train" not in would["metrics"]
