"""`models/keye.py` (learned sparse attention, its indexer and its loss, a
softmax route) against the benchmark's plain reference
(`benchmark/reference/keye.py`: jax.numpy, float32, imports nothing of
paddle_tpu) at the new cell's tiny stand-in, seeded random weights, on the
CPU, at T > topk so that the selection is live; the Pallas kernels through
the interpreter; and the cell's rehearsal end to end."""
import importlib
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
CELL = "keye-vl2-ep8.train.seq8192"
T = 64      # the stand-in's topk is 32


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
    return (config, common.load_module("drivers", "train_keye"),
            common.load_module("reference", "keye"))


def _model(config, drv, seed=5, opts=None, **over):
    import paddle_tpu as P
    from paddle_tpu.models.keye import KeyeForCausalLM

    cfg = dict(config, **over)
    P.seed(seed)
    model = KeyeForCausalLM(drv.model_config(cfg, opts or {}))
    tree = drv.make(cfg, seed)
    drv.load_into(model, tree)
    return cfg, model, tree


def _batch(cfg, seed, rows=2):
    rs = np.random.RandomState(seed)
    draw = lambda: rs.randint(0, cfg["vocab_size"], (rows, T)).astype(np.int32)
    return draw(), draw()


def _terms(model, params, buffers, ids, labels, pos=None):
    """(token loss, indexer loss, logits) of the program, functionally."""
    from paddle_tpu.core import flags
    import paddle_tpu as P

    with model.bind_state(params, buffers), flags.trace_guard():
        args = (P.to_tensor(ids),) + ((P.to_tensor(pos),) if pos is not None
                                      else ())
        logits = model(*args)._value
        aux = model.pop_aux_loss()._value
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.mean(lse - picked), aux, logits


@pytest.mark.parametrize("held,start", [(2, 0), (2, 4), (8, 0)])
def test_forward_both_losses_and_gradients_match_the_reference(bench, held,
                                                               start):
    config, drv, ref = bench
    cfg, model, tree = _model(config, drv, num_experts=held,
                              expert_start=start)
    assert T > cfg["sa_config"]["topk"]
    ids, labels = _batch(cfg, 1)
    params, buffers = model.functional_state()
    names = {n: drv.program_name(n) for n in drv.shapes(cfg)}
    assert sorted(names.values()) == sorted(params)

    def loss(params):
        lm, aux, logits = _terms(model, params, buffers, ids, labels)
        return lm + aux, (lm, aux, logits)

    (_, (lm, aux, got_logits)), got = jax.value_and_grad(
        loss, has_aux=True)(params)
    with jax.default_matmul_precision("highest"):
        want_logits = ref.logits(cfg, tree, jnp.asarray(ids))
        ce, kl = ref.loss_sums(cfg, tree, jnp.asarray(ids), jnp.asarray(labels))
        want_loss, want = ref.loss_and_grads(
            cfg, tree, jnp.asarray(ids)[None], jnp.asarray(labels)[None])
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-4)
    assert abs(float(lm) - float(ce) / ids.size) < 2e-6 * float(lm)
    assert float(kl) > 0
    assert abs(float(aux) - float(kl) / ids.size) < 1e-5 * float(aux)
    assert abs(float(lm + aux) - float(want_loss)) < 2e-6 * float(want_loss)
    for n, pn in names.items():
        g, w = np.asarray(got[pn]), np.asarray(want[n])
        assert np.abs(g - w).max() <= 3e-4 * max(np.abs(w).max(), 1e-6), n


def test_each_loss_reaches_its_own_parameters_only(bench):
    """The indexer's parameters get no gradient from the token loss and
    nothing else gets one from the indexer's."""
    config, drv, ref = bench
    cfg, model, _ = _model(config, drv)
    ids, labels = _batch(cfg, 2)
    params, buffers = model.functional_state()
    g_lm = jax.grad(lambda p: _terms(model, p, buffers, ids, labels)[0])(params)
    g_ix = jax.grad(lambda p: _terms(model, p, buffers, ids, labels)[1])(params)
    for name in params:
        lm, ix = (float(jnp.abs(g[name]).max()) for g in (g_lm, g_ix))
        if ".indexer." in name:
            assert lm == 0.0 and ix > 0.0, name
        else:
            assert ix == 0.0 and lm > 0.0, name


def test_mrope_rows_own_their_sections(bench):
    """Three unequal position rows against the reference; three equal rows
    are plain rotary positions."""
    from paddle_tpu.nn import functional as F

    config, drv, ref = bench
    rs = np.random.RandomState(3)
    sections = (2, 3, 3)
    q = jnp.asarray(rs.randn(2, T, 4, 16), jnp.float32)
    k = jnp.asarray(rs.randn(2, T, 2, 16), jnp.float32)
    pos = jnp.asarray(rs.randint(0, 500, (3, 2, T)), jnp.int32)
    got_q, got_k, _ = F.fused_rotary_position_embedding(
        q, k, None, position_ids=pos, rotary_emb_base=1e7,
        mrope_section=sections)
    np.testing.assert_allclose(got_q._value, ref.rope(q, pos, 1e7, sections),
                               atol=1e-5)
    np.testing.assert_allclose(got_k._value, ref.rope(k, pos, 1e7, sections),
                               atol=1e-5)
    # a row matters exactly on its own pairs
    moved = pos.at[1].add(7)
    other = F.fused_rotary_position_embedding(
        q, None, None, position_ids=moved, rotary_emb_base=1e7,
        mrope_section=sections)[0]._value
    same = np.isclose(other, got_q._value, atol=1e-6).all(axis=(0, 1, 2))
    assert list(same) == [True] * 2 + [False] * 3 + [True] * 5 \
        + [False] * 3 + [True] * 3
    equal = jnp.broadcast_to(pos[0], pos.shape)
    plain = F.fused_rotary_position_embedding(
        q, None, None, position_ids=pos[0], rotary_emb_base=1e7)[0]._value
    mrope = F.fused_rotary_position_embedding(
        q, None, None, position_ids=equal, rotary_emb_base=1e7,
        mrope_section=sections)[0]._value
    np.testing.assert_array_equal(np.asarray(mrope), np.asarray(plain))
    with pytest.raises(ValueError, match="mrope_section"):
        F.fused_rotary_position_embedding(q, None, None, position_ids=pos,
                                          mrope_section=(2, 3, 2))


def test_model_takes_unequal_position_rows(bench):
    config, drv, ref = bench
    cfg, model, tree = _model(config, drv)
    ids, labels = _batch(cfg, 4)
    pos = np.random.RandomState(4).randint(0, 300, (3, 2, T)).astype(np.int32)
    pos[0] = np.arange(T)       # the selection's causal order stays time's
    params, buffers = model.functional_state()
    _, _, got = _terms(model, params, buffers, ids, labels, pos)
    with jax.default_matmul_precision("highest"):
        want = ref.logits(cfg, tree, jnp.asarray(ids), jnp.asarray(pos))
        text = ref.logits(cfg, tree, jnp.asarray(ids))
    np.testing.assert_allclose(got, want, atol=2e-4)
    assert float(jnp.abs(want - text).max()) > 1e-2


@pytest.mark.parametrize("shape,topk", [((2, 96), 32), ((1, 64), 64),
                                        ((3, 40), 100), ((2, 128), 1)])
def test_selection_is_top_k_with_ties_to_the_lower_key(shape, topk):
    """`select_topk` against `jax.lax.top_k` row by row, on scores full of
    ties (rounded to quarters, zeros of both signs): the same set, and the
    closed-form count."""
    from paddle_tpu.nn.functional import sparse_index as si

    b, t = shape
    rs = np.random.RandomState(topk)
    sc = np.round(rs.randn(b, t, t) * 4) / 4
    sc[:, :, ::7] = -0.0
    sc = jnp.asarray(sc, jnp.float32)
    mask, n = jax.jit(lambda s: si.select_topk(s, topk))(sc)
    mask = np.asarray(mask)
    causal = np.tril(np.ones((t, t), bool))
    x = jnp.where(causal[None], jnp.where(sc == 0, 0.0, sc), -jnp.inf)
    _, idx = jax.lax.top_k(x, min(topk, t))
    want = np.zeros((b, t, t), np.int8)
    np.put_along_axis(want, np.asarray(idx), 1, axis=-1)
    want *= causal[None]
    np.testing.assert_array_equal(mask, want)
    assert int(n) == int(mask.sum()) == si.selected_pairs(b, t, topk)


def test_selected_pairs_are_the_closed_form_and_the_reference_agrees(bench):
    from paddle_tpu.models.keye import pair_counters
    from paddle_tpu.nn.functional.sparse_index import selected_pairs

    config, drv, ref = bench
    cfg, model, tree = _model(config, drv)
    ids, labels = _batch(cfg, 5)
    model.train()
    import paddle_tpu as P

    model(P.to_tensor(ids))
    want = selected_pairs(2, T, cfg["sa_config"]["topk"])
    assert want == 2 * (32 * 33 // 2 + (T - 32) * 32)
    assert drv.selected_pairs(2, T, cfg["sa_config"]["topk"]) == want
    counted = pair_counters(dict(model.named_buffers()))
    assert len(counted) == cfg["num_hidden_layers"]
    for c in counted.values():
        assert c == {"selected": want, "computed": 2 * T * T,
                     "causal": 2 * T * (T + 1) // 2}
    n = ref.attention_part(cfg, tree, 0, tree["wte"][jnp.asarray(ids)],
                           ref.text_positions(jnp.asarray(ids)))[2]
    assert int(n) == want


def test_eight_shares_add_up_to_the_uncut_layer(bench):
    """One layer of the program at each of the 8 shares of the experts:
    the expert parts summed, attention counted once, are the uncut
    reference's layer (all experts held)."""
    config, drv, ref = bench
    width = config["router_width"]
    cfg, whole, tree = _model(config, drv, num_hidden_layers=1,
                              num_experts=width, expert_start=0)
    ids, _ = _batch(cfg, 6)
    x = tree["wte"][jnp.asarray(ids)]
    pos = ref.text_positions(jnp.asarray(ids))
    with jax.default_matmul_precision("highest"):
        want, _ = ref._block(cfg, tree, 0, x, pos, None)
        attn, _, _ = ref.attention_part(cfg, tree, 0, x, pos)
    parts = []
    per = width // 8
    for rank in range(8):
        c, model, _ = _model(config, drv, num_hidden_layers=1,
                             num_experts=per, expert_start=rank * per)
        blk = model.model.layers[0]
        share = {n: v[rank * per:(rank + 1) * per]
                 for n, v in tree.items() if ".experts." in n}
        drv.load_into(model, {**tree, **share})
        import paddle_tpu as P

        out, _ = blk(P.to_tensor(x), P.to_tensor(pos))
        parts.append(out._value - x - attn)      # the held experts' part
    np.testing.assert_allclose(x + attn + sum(parts), want, atol=2e-5)
    assert float(jnp.abs(parts[0]).max()) > 1e-4


_PROGRAMS = {}


def _grad_program(recompute, policy=None, monkeypatch=None, kernels=False):
    """(text of the tiny step's gradient jaxpr, the trace-time counters it
    added, the loss, the gradients), made once a session.  With `kernels`
    the sparse entries take their Pallas forms (through the interpreter) as
    on the chip, and the program is traced, not run: no loss, no
    gradients."""
    key = (recompute, policy, kernels)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _make_grad_program(recompute, policy, monkeypatch,
                                            kernels)
    return _PROGRAMS[key]


def _make_grad_program(recompute, policy, monkeypatch, kernels):
    import functools

    import paddle_tpu as P
    from paddle_tpu.core import flags
    from paddle_tpu.models.gpt import GPTPretrainingCriterion
    from paddle_tpu.observability import metrics

    keye = importlib.import_module("paddle_tpu.models.keye")
    rc = importlib.import_module("paddle_tpu.distributed.recompute")
    if policy:
        monkeypatch.setattr(keye, "_recompute",
                            functools.partial(rc.recompute, policy=policy))
    if kernels:
        from paddle_tpu.ops.pallas import sparse_attention as sa
        from paddle_tpu.ops.pallas import sparse_index as sx

        monkeypatch.setattr(sa, "available", lambda q: True)
        monkeypatch.setattr(sx, "on_tpu", lambda: True)
    P.seed(0)
    model = keye.KeyeForCausalLM(keye.keye_tiny(
        recompute=recompute, fused_head_ce=True))
    model.train()
    crit = GPTPretrainingCriterion(model=model)
    params, buffers = model.functional_state()
    rs = np.random.RandomState(3)
    ids = rs.randint(0, 512, (8, 32)).astype(np.int32)

    def loss(params):
        with model.bind_state(params, buffers), flags.trace_guard():
            return crit(model(P.to_tensor(ids)), P.to_tensor(ids))._value

    was = metrics.enabled()
    metrics.enable()
    try:
        before = dict(metrics.snapshot()["counters"])
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        value, grads = (None, None) if kernels \
            else jax.value_and_grad(loss)(params)
        now = metrics.snapshot()["counters"]
    finally:
        if not was:
            metrics.disable()
    added = {k: v - before.get(k, 0) for k, v in now.items()
             if v - before.get(k, 0)}
    return text, added, value if kernels else float(value), grads


def test_selection_is_kept_under_recomputation(monkeypatch):
    """keye_tiny, 2 layers: the search for the threshold (the only
    `bitcast_convert_type` of the program) stands once a layer under the
    default policy and twice under "full"; the mark and the counters say
    so; loss and gradients do not move."""
    searches = lambda text: len(re.findall(r"bitcast_convert_type\[", text))
    off, _, value_off, grads_off = _grad_program(False)
    kept, added, value, grads = _grad_program(True)
    full, added_full, _, _ = _grad_program(True, "full", monkeypatch)
    assert searches(off) == searches(kept) == 2 and searches(full) == 4
    assert "name=sparse_select]" in kept
    # traced twice (the jaxpr, then the gradient): 2 layers each
    assert added["sparse_attn.recompute_kept{what=selection}"] == 4
    assert "sparse_attn.recompute_kept{what=selection}" not in added_full
    assert added["moe.route{score=softmax}"] == 4
    assert added["sparse_attn.dispatch{kernel=reference}"] == 4
    # each of the indexer's three entries says which form it took; the
    # search is traced once a layer even where the scores are replayed
    for op in ("scores", "select", "loss"):
        assert added[f"sparse_index.dispatch{{kernel=reference,op={op}}}"] == 4
    assert not any("kernel=pallas" in k for k in added)
    assert value == value_off
    for n, g in grads.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(grads_off[n]),
                                   atol=1e-6, err_msg=n)


def test_indexer_loss_gradient_is_kept_under_recomputation(monkeypatch):
    """keye_tiny, 2 layers: the indexer's loss forms its gradient by the
    scores in its forward rule and keeps it (`indexer_grad`), so under the
    default policy the index-score forward, the head-mean probabilities and
    the loss stand ONCE a layer in the gradient's program — as without
    recomputation — and twice under "full".  Read on the kernels' names
    (the Pallas forms through the interpreter, as the chip's trace names
    them) and, in the jax.numpy forms the CPU runs, on the index scores'
    `relu`; the mark and the counter say so; loss and gradients do not
    move."""
    names = ("jvp(sparse_index_fwd)", "sparse_attn_probs",
             "jvp(sparse_index_loss)", "jvp(sparse_attn_fwd)")
    calls = lambda text: [len(re.findall(rf"name={re.escape(n)}(?!\w)", text))
                          for n in names]
    (kept, added, _, _), (off, added_off, _, _), (full, added_full, _, _) = (
        _grad_program(*mode, monkeypatch, kernels=True)
        for mode in ((True, None), (False, None), (True, "full")))
    assert calls(off) == calls(kept) == [2, 2, 2, 2]
    assert calls(full) == [4, 4, 4, 4]
    for text in (kept, off, full):           # the backward's own: once
        # (the fused attention backward makes dQ under the dK/dV name)
        for n in ("sparse_index_dq", "sparse_index_dk", "sparse_attn_dkdv"):
            assert len(re.findall(rf"name=transpose\(jvp\({n}\)\)",
                                  text)) == 2, n
        assert "sparse_attn_dq" not in text
        assert "sparse_index_loss_bwd" not in text
        assert "name=indexer_grad]" in text  # only a policy reads the mark
    for got in (added, added_off, added_full):
        assert got["sparse_attn.backward{kind=fused}"] == 2
        assert "sparse_attn.backward{kind=split}" not in got
    counter = "sparse_index.recompute_kept{what=loss_grad}"
    assert added[counter] == 2               # traced once: 2 layers
    assert counter not in added_off and counter not in added_full
    assert added["sparse_index.dispatch{kernel=pallas,op=loss}"] == 2
    assert not any("kernel=reference" in k for k in added)
    # the jax.numpy forms: the same programs as the CPU runs them
    relus = lambda text: len(re.findall(r"name=relu\b", text))
    text_off, jnp_off, value_off, grads_off = _grad_program(False)
    text_kept, jnp_kept, value, grads = _grad_program(True)
    text_full, jnp_full, value_full, grads_full = _grad_program(
        True, "full", monkeypatch)
    assert relus(text_off) == relus(text_kept) < relus(text_full)
    assert "name=indexer_grad]" in text_kept
    assert jnp_kept[counter] == 4            # the jaxpr, then the gradient
    assert counter not in jnp_off and counter not in jnp_full
    assert value == value_off == value_full
    for n, g in grads.items():
        for other in (grads_off, grads_full):
            np.testing.assert_allclose(np.asarray(g), np.asarray(other[n]),
                                       atol=1e-6, err_msg=n)


@pytest.mark.parametrize("blocks", [(64, 128), (32, 64)])
def test_pallas_kernels_match_the_jnp_form(blocks):
    """Forward, the three gradients and the head-mean probabilities of the
    masked grouped-query kernels (through the interpreter) against
    `reference()`, on a random selection."""
    from paddle_tpu.ops.pallas import sparse_attention as sa

    b, t, h, hkv, d = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    causal = jnp.tril(jnp.ones((t, t), bool))
    some = jax.random.uniform(ks[3], (b, t, t)) < 0.4
    mask = (causal & (some | jnp.eye(t, dtype=bool))).astype(jnp.int8)
    g = jax.random.normal(ks[4], q.shape)
    out, (qt, kt, lse) = sa.sparse_attention(q, k, v, mask, blocks)
    np.testing.assert_allclose(out, sa.reference(q, k, v, mask), atol=2e-5)
    probs = sa.head_mean_probs(qt, kt, lse, mask, blocks)
    np.testing.assert_allclose(probs, sa.reference_probs(q, k, mask),
                               atol=1e-6)
    np.testing.assert_allclose(probs.sum(-1), 1.0, atol=1e-5)
    got = jax.grad(lambda *a: (sa.sparse_attention(*a, mask, blocks)[0]
                               * g).sum(), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: (sa.reference(*a, mask) * g).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for a, w in zip(got, want):
        np.testing.assert_allclose(a, w, atol=5e-5)
    bq, bk = blocks
    assert sa.computed_pairs(b, t, blocks) == b * sum(
        ((qi * bq + bq - 1) // bk + 1) * bk * bq for qi in range(t // bq))
    with pytest.raises(ValueError, match="do not divide"):
        sa.computed_pairs(1, 200, blocks)


def _selection(case, b, t, key):
    """A causal [B, T, T] int8 selection: random, with the first rows
    whole (t < topk there), or with rows of exactly one key."""
    causal = jnp.tril(jnp.ones((t, t), bool))
    some = (jax.random.uniform(key, (b, t, t)) < 0.3) | jnp.eye(t, dtype=bool)
    if case == "first_rows_whole":
        some = some | (jnp.arange(t) < 96)[:, None]
    if case == "one_key":
        rows = jnp.arange(t)[:, None]
        one = jnp.arange(t)[None] == jnp.where(rows % 3 == 0, rows // 2, rows)
        some = jnp.where(rows % 2 == 0, one, some)
    return (causal & some).astype(jnp.int8)


@pytest.mark.parametrize("case,t,h,blocks", [
    ("random", 256, 4, (64, 128)),
    ("random", 256, 8, (32, 64)),          # rep 4, 8 x 4 blocks
    ("first_rows_whole", 256, 4, (32, 128)),
    ("one_key", 128, 4, (32, 32)),
    ("random", 64, 2, (64, 64)),           # one query and one key block
])
def test_fused_backward_equals_the_split_pair(monkeypatch, case, t, h,
                                              blocks):
    """The fused backward kernel (one visit of each tile: dQ over the key
    blocks, dK/dV of the key/value head resident over the query blocks)
    against the split dq + dkdv pair, which a VMEM budget lowered to
    nothing takes: dQ, dK and dV bit for bit, both at `reference()`'s
    gradients; `sparse_attn.backward{kind}` says which ran."""
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.pallas import sparse_attention as sa

    b, hkv, d = 2, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(t + h), 5)
    q = jax.random.normal(ks[0], (b, t, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, hkv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, hkv, d), jnp.float32)
    mask = _selection(case, b, t, ks[3])
    g = jax.random.normal(ks[4], q.shape)
    loss = lambda *a: (sa.sparse_attention(*a, mask, blocks)[0] * g).sum()

    def grads():
        before = dict(metrics.snapshot()["counters"])
        got = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        now = metrics.snapshot()["counters"]
        return got, {c: now[c] - before.get(c, 0) for c in now
                     if c.startswith("sparse_attn.backward")
                     and now[c] - before.get(c, 0)}

    was = metrics.enabled()
    metrics.enable()
    try:
        fused, took = grads()
        assert took == {"sparse_attn.backward{kind=fused}": 1}
        monkeypatch.setattr(sa, "_BWD_VMEM_LIMIT", 0)
        split, took = grads()
        assert took == {"sparse_attn.backward{kind=split}": 1}
    finally:
        if not was:
            metrics.disable()
    want = jax.grad(lambda *a: (sa.reference(*a, mask) * g).sum(),
                    argnums=(0, 1, 2))(q, k, v)
    for a, s, w in zip(fused, split, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(s))
        np.testing.assert_allclose(a, w, atol=5e-5)


def test_index_kernels_match_the_jnp_form():
    """The index-score kernels (forward and the three gradients) and the
    selection's search on rows in VMEM, through the interpreter, against
    the jax.numpy forms the CPU runs."""
    from paddle_tpu.nn.functional import sparse_index as si
    from paddle_tpu.ops.pallas import sparse_index as sx

    b, t, j, d = 2, 256, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (b, t, j, d))
    k = jax.random.normal(ks[1], (b, t, d))
    w = jax.random.normal(ks[2], (b, t, j))
    causal = jnp.tril(jnp.ones((t, t), bool))
    blocks = (64, 128)
    got = sx.index_scores(q, k, w, blocks)
    want = si._index_scores_blocked(q, k, w)
    np.testing.assert_allclose(jnp.where(causal, got, 0),
                               jnp.where(causal, want, 0), atol=1e-5)
    g = jnp.where(causal, jax.random.normal(ks[3], (b, t, t)), 0.0)
    grads = lambda f: jax.grad(lambda *a: (f(*a) * g).sum(),
                               argnums=(0, 1, 2))(q, k, w)
    for a, r in zip(grads(lambda *a: sx.index_scores(*a, blocks)),
                    grads(si._index_scores_blocked)):
        np.testing.assert_allclose(a, r, atol=2e-4)
    for topk in (32, 1, 300):
        sc = jnp.round(got * 2) / 2                      # full of ties
        sc = sc.at[:, :, ::5].set(-0.0)
        mask, n = sx.select_topk(sc, topk)
        np.testing.assert_array_equal(
            np.asarray(mask), np.asarray(si._select_topk_passes(sc, topk)))
        assert int(n) == si.selected_pairs(b, t, topk)
    # the loss and its gradient, rows in VMEM against jax.numpy's
    mask = si._select_topk_passes(got, 32)
    probs = jax.nn.softmax(jnp.where(mask > 0, g, -jnp.inf), -1)
    probs = jnp.where(probs < 1e-3, 0.0, probs)          # some exact zeros
    probs = probs.at[:, 7].set(0.0)                      # and a whole row
    want_value, want_d = jax.value_and_grad(
        lambda s: si._indexer_loss_rows(s, mask, probs)[0])(got)
    want_d = want_d * (b * t)                            # the mean undone
    # the fused kernel: the value and `d` on one visit of the row
    value, d = sx.indexer_loss(got, mask, probs, True)
    assert d.dtype == jnp.float32 and d.shape == got.shape
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    np.testing.assert_allclose(d, want_d, atol=1e-7 * b * t)
    assert not np.asarray(d)[np.asarray(mask) == 0].any()
    # the jax.numpy form's closed-form `d` is autodiff's
    value, d = si._indexer_loss_rows(got, mask, probs, True)
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    np.testing.assert_allclose(d, want_d, atol=1e-7 * b * t)
    # the rule of the functional entry over either form
    for on_tpu in (True, False):
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(sx, "on_tpu", lambda: on_tpu)
            value, grad = jax.value_and_grad(
                lambda s: 3.0 * si.indexer_loss(s, mask, probs))(got)
            np.testing.assert_allclose(value, 3.0 * want_value, rtol=1e-6)
            np.testing.assert_allclose(grad, 3.0 * want_d / (b * t),
                                       atol=3e-7)
            plain = jax.make_jaxpr(si.indexer_loss)(got, mask, probs)
            assert "indexer_grad" not in str(plain)
    # undifferentiated (evaluation): the statistics only, no [B, T, T] output
    value, d = sx.indexer_loss(got, mask, probs)
    assert d is None
    np.testing.assert_allclose(value, want_value, rtol=1e-6)
    written = lambda diff: [v.aval.shape for v in _pallas_calls(jax.make_jaxpr(
        lambda *a: sx.indexer_loss(*a, diff)[0])(got, mask, probs).jaxpr)[0]
        .outvars]
    assert written(False) == [(b, t, 128)]
    assert written(True) == [(b, t, 128), (b, t, t)]


def _pallas_calls(jaxpr):
    """The pallas_call equations of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _pallas_calls(sub)
    return found


def test_cost_keye_by_hand():
    """ISSUE 35's arithmetic at the published widths: 528 MFLOP a token
    forward at a balanced router."""
    sys.path.insert(0, BENCH)
    from harness import common

    cost = common.load_module("readers", "cost_keye")
    cfg = common.load_json("configs", "keye-vl2-ep8.json")
    parts = cost.forward_flops_per_token(cfg, 8192)
    assert cost.selected_pairs(8192, 2048) == 2048 * 2049 // 2 + 6144 * 2048 \
        == 14_681_088
    assert cost.causal_pairs(8192) == 33_558_528
    assert parts["proj"] == 5 * (2 * 2048 * (4096 + 1024) + 2 * 4096 * 2048)
    assert parts["indexer_proj"] == 5 * 2 * 2048 * (1024 + 64 + 16)
    assert parts["indexer_scores"] == 5 * 2 * 16 * 64 * 33_558_528 / 8192
    assert parts["attn_selected"] == 5 * 4 * 4096 * 14_681_088 / 8192
    assert parts["routed"] == 5 * 6 * 2048 * 768     # one expert a token here
    assert parts["head"] == 2 * 2048 * 18992
    assert round(sum(parts.values()) / 1e6) == 528
    counted = lambda routed: cost.train_flops_per_token(
        cfg, 8192, {"moe_window": {"routed": routed, "tokens": 6000}})
    assert counted(5 * 6000) == cost.train_flops_per_token(cfg, 8192)
    assert counted(5 * 6000) - counted(5 * 1000) \
        == 3 * 5 * (5 / 6) * 6 * 2048 * 768
    kern = common.load_module("readers", "cost_sparse_attn")
    flops, bytes_ = kern.per_pass(2, 8192, 32, 4, 128, 2048)
    assert flops == 8 * 2 * 32 * 2 * 14_681_088 * 128
    assert bytes_ > 4 * 2 * 33_558_528
    idx = common.load_module("readers", "cost_sparse_index")
    assert idx.per_pass(2, 8192, 16, 64)[0] == 3 * 2 * 16 * 64 * 2 * 33_558_528
    share = common.load_module("readers", "pairs_share")
    assert share.read({"state": {}}, {}) is None
    assert share.read({"state": {"pairs_traced": {
        "selected": 7, "computed": 16}}}, {}) == 43.75


def test_configuration_file_keeps_the_published_widths():
    cfg = json.load(open(os.path.join(BENCH, "configs", "keye-vl2-ep8.json")))
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                "vocab_size": 151936}
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["moe_intermediate_size"], cfg["router_width"],
            cfg["num_experts_per_tok"]) == (2048, 32, 4, 128, 768, 128, 8)
    assert cfg["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512, "q_chunk_size": 512,
        "topk": 2048}
    assert cfg["rope_scaling"]["mrope_section"] == [16, 24, 24]
    for key in ("indexer", "indexer_rotary", "sa_config.chunks",
                "indexer_loss", "mrope", "attention", "expert_layer", "loss",
                "initializer_range", "vision_tower", "training.optimizer"):
        assert key in cfg["assumed"], key


def test_new_cells_rehearsal_is_correct():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3500003011", "--seconds", "1", "--trace", "1",
         "--rehearse"], capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {"rehearsed": True, "correct": True}
    dispatch = next(ln for ln in lines if ln.startswith("dispatch "))
    assert "'moe.rows{kind=dropped}': 0" in dispatch
    assert "'moe.route{score=softmax}': 2" in dispatch
    assert "'sparse_attn.recompute_kept{what=selection}': 2" in dispatch
    sane = next(ln for ln in lines if ln.startswith("sane "))
    assert "'layer_steps_off_form': 0" in sane
    would = json.loads(next(ln for ln in lines if ln.startswith(
        "would_print "))[len("would_print "):])
    for name in ("step_mfu.train.dsa", "attn_sparse_ms.train",
                 "indexer_ms.train", "indexer_select_ms.train",
                 "attn_selected_share.train", "device_idle_share.train"):
        assert name in would["metrics"], name
    assert "step_mfu.train.moe" not in would["metrics"]
