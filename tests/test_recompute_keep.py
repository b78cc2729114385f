"""What per-layer recomputation keeps (`distributed/recompute.py`): under the
default policy a recomputed block holds the flash cores' output + log-sum-exp
and the routed expert layer's result across its replay; `"full"` replays
everything.  The numbers are the same either way; the backward's program is
shorter by one flash forward and one grouped forward a layer."""
import functools
import importlib
import re

import jax
import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.ops.pallas as pallas_pkg
from paddle_tpu.core import flags
from paddle_tpu.models.gpt import GPTPretrainingCriterion
from paddle_tpu.observability import metrics
from paddle_tpu.ops.pallas import flash_attention as fa

# `paddle_tpu.distributed.recompute` the attribute is the function
rc = importlib.import_module("paddle_tpu.distributed.recompute")
afmoe = importlib.import_module("paddle_tpu.models.afmoe")

MODES = (False, True, "full")
# what only learned sparse attention marks (models/keye.py)
SPARSE_MARKS = ("sparse_select", "indexer_grad")
KEPT_COUNTERS = ("flash.recompute_kept{what=out_lse}",
                 "moe.recompute_kept{what=out}")


@pytest.fixture
def flash_on(monkeypatch):
    """The flash cores on the CPU (interpret mode), as on the chip."""
    for mod in (fa, pallas_pkg):
        monkeypatch.setattr(mod, "flash_attention_available", lambda q: True)


def _afmoe(mode, monkeypatch):
    # the afmoe config has no policy key: "full" is handed to the block's
    # own call of recompute()
    if mode == "full":
        monkeypatch.setattr(afmoe, "_recompute",
                            functools.partial(rc.recompute, policy="full"))
    cfg = afmoe.afmoe_tiny(recompute=bool(mode), fused_head_ce=True)
    return afmoe.AfmoeForCausalLM(cfg), cfg.vocab_size


def _gpt(mode, monkeypatch):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    cfg = gpt_tiny(recompute=bool(mode), dropout=0.0, fused_head_ce=True,
                   recompute_policy=mode if isinstance(mode, str) else None)
    return GPTForCausalLM(cfg), cfg.vocab_size


def _loss_fn(build, mode, monkeypatch):
    """(loss(params) of one seeded batch through the model, its params)."""
    P.seed(0)
    model, vocab = build(mode, monkeypatch)
    model.train()
    crit = GPTPretrainingCriterion(model=model)
    params, buffers = model.functional_state()
    rs = np.random.RandomState(3)    # 8 rows: the test mesh has dp = 8
    ids = rs.randint(0, vocab, (8, 32)).astype(np.int32)
    labels = rs.randint(0, vocab, (8, 32)).astype(np.int32)

    def loss(params):
        with model.bind_state(params, buffers), flags.trace_guard():
            return crit(model(P.to_tensor(ids)), P.to_tensor(labels))._value

    return loss, params


@pytest.mark.parametrize("build", [_afmoe, _gpt], ids=["afmoe_tiny", "gpt_tiny"])
def test_kept_values_change_no_digit(build, flash_on, monkeypatch):
    """Loss and every gradient leaf under the default policy are those
    without recomputation and those under "full", exactly.  Evaluated
    equation by equation (no jit around it): what is compared is the
    mathematics, not how one compiler fuses three different programs."""
    got = {}
    for mode in MODES:
        loss, params = _loss_fn(build, mode, monkeypatch)
        got[mode] = jax.value_and_grad(loss)(params)
    want_loss, want = got[False]
    assert np.isfinite(float(want_loss))
    for mode in (True, "full"):
        value, grads = got[mode]
        assert float(value) == float(want_loss), mode
        assert grads.keys() == want.keys()
        for name, g in grads.items():
            np.testing.assert_array_equal(np.asarray(g), np.asarray(want[name]),
                                          err_msg=f"{mode} {name}")


def _program(mode, monkeypatch):
    """(text of the afmoe_tiny step's gradient jaxpr, the `*.recompute_kept`
    counters its trace added)."""
    was = metrics.enabled()
    metrics.enable()
    try:
        before = dict(metrics.snapshot()["counters"])
        loss, params = _loss_fn(_afmoe, mode, monkeypatch)
        text = str(jax.make_jaxpr(jax.grad(loss))(params))
        now = metrics.snapshot()["counters"]
    finally:
        if not was:
            metrics.disable()
    return text, {k: now.get(k, 0) - before.get(k, 0) for k in KEPT_COUNTERS}


def _calls(text):
    fwd = len(re.findall(r"name=jvp\(flash_transpose(?:_window)?_fwd\)", text))
    bwd = len(re.findall(r"name=transpose\(jvp\(flash_transpose", text))
    return (fwd, bwd, len(re.findall(r"\bragged_dot(?:_general)?\[", text)),
            len(re.findall(r"= (?:top_k\[|jit\[name=argsort )", text)))


def test_backward_replays_neither_flash_nor_grouped_forward(flash_on,
                                                            monkeypatch):
    """afmoe_tiny: 3 layers with attention, 2 of them with experts.  The
    flash forward kernel stands once a layer under the default and twice
    under "full"; the grouped forward's three products (chunk 0 and the
    loop's body: 6 equations a layer) likewise, and the router's `top_k`
    and the argsort; the counters read one a layer, and nothing without
    recomputation."""
    (text, kept), (text_off, kept_off), (text_full, kept_full) = (
        _program(mode, monkeypatch) for mode in (True, False, "full"))
    layers, expert_layers = 3, 2
    fwd, bwd, ragged, sorts = _calls(text)
    assert (fwd, bwd, sorts) == (layers, layers, 2 * expert_layers)
    assert _calls(text_off) == (fwd, bwd, ragged, sorts)  # nothing twice
    fwd_full, bwd_full, ragged_full, sorts_full = _calls(text_full)
    assert (fwd_full, bwd_full) == (2 * layers, layers)
    assert ragged_full == ragged + 6 * expert_layers
    assert sorts_full == 2 * sorts
    # the marks are in every program; only a policy reads them (this
    # model marks no selection and no indexer's gradient: tests/test_keye.py
    # has those two)
    for t in (text, text_off, text_full):
        for n in rc.KEPT:
            assert (f"name={n}]" in t) == (n not in SPARSE_MARKS), n
    assert kept == dict(zip(KEPT_COUNTERS, (layers, expert_layers)))
    assert kept_off == kept_full == dict.fromkeys(KEPT_COUNTERS, 0)


def test_keep_takes_only_the_modules_names():
    assert rc.KEPT == ("flash_out", "flash_lse", "moe_out", "moe_sort",
                       *SPARSE_MARKS)
    x = jax.numpy.ones((2,))
    for name in rc.KEPT:
        assert rc.keep(x, name) is x         # outside a trace: an identity
    with pytest.raises(ValueError, match="not one of"):
        rc.keep(x, "anything_else")
    assert not rc.keeping()


@pytest.mark.parametrize("policy,kept", [(None, True), ("full", False),
                                         ("dots", False),
                                         ("dots_no_batch", False)])
def test_keeping_says_whether_the_segments_policy_holds_the_marks(policy,
                                                                   kept):
    seen = []

    def body(x):
        seen.append(rc.keeping())
        return x * 2

    with flags.trace_guard():
        rc.recompute(body, P.ones([2]), policy=policy)
    assert seen == [kept] and not rc.keeping()
