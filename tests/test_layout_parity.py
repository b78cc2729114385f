"""Layout-parity suite (ISSUE 10): the transpose-free FLAT attention
core runs wherever its gates admit the shape — these tests hold it
bit-identical to the transpose core at the kernel level AND at the real
model call sites (GPT causal MHA, LLaMA GQA+RoPE, ERNIE bidirectional +
additive mask), so which core a shape takes can never silently change
training numerics — and hold the dispatch to choosing the core from the
shape alone.

All kernels run through the Pallas interpreter on CPU (the fake-backend
strategy, SURVEY §4.5): every layout executes the same shared
recurrences (_online_softmax/_dq_loop/_dkv_loop) on the same block
shapes, so equality is exact — asserted with array_equal, not
allclose."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.ops.pallas import flash_attention as fa


def _rand(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape),
                       jnp.float32)


def _loss(core, q, k, v, causal, bq, bk):
    return core(q, k, v, causal, bq, bk).astype(jnp.float32).sum()


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
def test_flat_vs_transpose_core_bit_identical(hq, hkv):
    """Forward AND all three gradients of the flat core are bit-equal to
    the transpose core (MHA and GQA) at shared block sizes — the
    acceptance bar for making flat the default layout."""
    B, S, D = 2, 64, 64
    q = _rand((B, S, hq, D), 0)
    k = _rand((B, S, hkv, D), 1)
    v = _rand((B, S, hkv, D), 2)
    for causal in (False, True):
        out_t = fa._flash_core(q, k, v, causal, 32, 32)
        out_f = fa._flash_core_flat(q, k, v, causal, 32, 32)
        assert np.array_equal(np.asarray(out_t), np.asarray(out_f)), \
            f"flat fwd differs from transpose (causal={causal})"
        g_t = jax.grad(lambda *a: _loss(fa._flash_core, *a, causal,
                                        32, 32),
                       argnums=(0, 1, 2))(q, k, v)
        g_f = jax.grad(lambda *a: _loss(fa._flash_core_flat, *a, causal,
                                        32, 32),
                       argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g_t, g_f):
            assert np.array_equal(np.asarray(a), np.asarray(b)), \
                f"d{name} differs between layouts (causal={causal})"


_FLAT_STATIC_OK = fa._flat_static_ok


def _force_core(monkeypatch, layout):
    """`flat` leaves the choice to the shape (these call sites' widths
    pass the static gates where their head counts allow); the
    `transpose` side of a comparison refuses every shape there."""
    monkeypatch.setattr(fa, "_flat_static_ok", {
        "flat": _FLAT_STATIC_OK,
        "transpose": lambda q_, k_: False}[layout])


def test_default_layout_is_flat(monkeypatch):
    """Eligible shapes route to the flat core and match the reference;
    a shape the static gates refuse lands on transpose."""
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    B, S, H, D = 2, 64, 2, 64
    q = _rand((B, S, H, D))
    called = {}
    orig = fa._flash_core_flat

    def spy(*a, **kw):
        called["flat"] = True
        return orig(*a, **kw)

    monkeypatch.setattr(fa, "_flash_core_flat", spy)
    out = fa.flash_attention_fwd(q, q, q, is_causal=True)
    assert called.get("flat"), \
        "default layout did not route an eligible shape to the flat core"
    ref = fa._ref_attention(q, q, q, None, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)
    # ineligible head width (d % 64 != 0) still lands on transpose
    q2 = _rand((2, 64, 4, 32))
    called2 = {}
    orig_t = fa._flash_core

    def spy_t(*a, **kw):
        called2["transpose"] = True
        return orig_t(*a, **kw)

    monkeypatch.setattr(fa, "_flash_core", spy_t)
    fa.flash_attention_fwd(q2, q2, q2, is_causal=True)
    assert called2.get("transpose"), \
        "gate-rejected shape did not fall back to the transpose core"


def _blocks(tier, bq, bk, **extra):
    labels = dict(block_k=bk, block_q=bq, tier=tier, **extra)
    return "flash.blocks{%s}" % ",".join(
        f"{k}={v}" for k, v in sorted(labels.items()))


# One case per arm of _choose_core and of the GQA expansion rule:
# (q shape, KV heads, call keywords, what the case sets up) ->
# (core reached, KV heads it sees, every flash.* counter of the trace).
_DISPATCH = {
    "flat": (
        (2, 128, 12, 64), 12, {}, None,
        "flat", 12, {"flash.dispatch{tier=flat}": 1,
                     _blocks("flat", 128, 128): 1}),
    "head_width": (   # 4 x 32 = 128 lanes, but a 32-lane head slice
        (2, 128, 4, 32), 4, {}, None,
        "transpose", 4, {"flash.gate_reject{gate=flat,reason=head_width}": 1,
                         "flash.dispatch{tier=transpose}": 1,
                         _blocks("transpose", 128, 128): 1}),
    "lane_align": (   # 3 x 64 = 192: off the 128-lane tile
        (2, 128, 3, 64), 3, {}, None,
        "transpose", 3, {"flash.gate_reject{gate=flat,reason=lane_align}": 1,
                         "flash.dispatch{tier=transpose}": 1,
                         _blocks("transpose", 128, 128): 1}),
    "vmem": (         # gpt3-125m.train.seq2048's call, at its blocks
        (16, 2048, 12, 64), 12, dict(block_q=512, block_k=512), None,
        "transpose", 12, {"flash.gate_reject{gate=flat,reason=vmem}": 1,
                          "flash.dispatch{tier=transpose}": 1,
                          _blocks("transpose", 512, 512): 1}),
    "padded": (       # ViT's 197 runs padded to 200: no flat gate is asked
        (2, 197, 12, 64), 12, {}, None,
        "transpose", 12, {"flash.dispatch{tier=transpose}": 1,
                          _blocks("transpose", 200, 200): 1}),
    "window": (
        (2, 256, 2, 64), 2, dict(window=64), None,
        "transpose", 2, {"flash.dispatch{tier=transpose,window=64}": 1,
                         _blocks("transpose", 256, 256, window=64): 1}),
    "gqa_grouped": (  # the group fits: the core reads the 2 KV heads
        (2, 128, 4, 64), 2, {}, None,
        "flat", 2, {"flash.dispatch{tier=flat}": 1,
                    _blocks("flat", 128, 128): 1}),
    "gqa_expanded": (  # 3 * 2 * 128 * 64 * 2 bytes a group, bound one under
        (2, 128, 4, 64), 2, {}, "group_bytes",
        "flat", 4, {"flash.gqa_expand{reason=group_bytes}": 1,
                    "flash.dispatch{tier=flat}": 1,
                    _blocks("flat", 128, 128): 1}),
    "env_ignored": (  # the variable that once chose a core is not read
        (2, 128, 12, 64), 12, {}, "env",
        "flat", 12, {"flash.dispatch{tier=flat}": 1,
                     _blocks("flat", 128, 128): 1}),
}


@pytest.mark.parametrize("case", list(_DISPATCH))
def test_dispatch_chooses_core_from_shape(monkeypatch, case):
    """The dispatch decides the core from what it can observe — shape,
    padding, window — and counts what it decided.  Traced only
    (`jax.eval_shape`): the counters are trace-time, no kernel runs."""
    from paddle_tpu.observability import metrics

    shape, h_kv, kw, setup, core, kv_heads, counters = _DISPATCH[case]
    if setup == "env":
        monkeypatch.setenv("FLAGS_flash_layout", "kv")
    elif setup == "group_bytes":
        monkeypatch.setattr(fa, "_GQA_GROUP_BYTES_MAX",
                            3 * 2 * 128 * 64 * 2 - 1)
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    reached = []
    for name, attr in (("transpose", "_flash_core"),
                       ("flat", "_flash_core_flat")):
        def spy(q_, k_, *a, _name=name, _orig=getattr(fa, attr)):
            reached.append((_name, k_.shape[2]))
            return _orig(q_, k_, *a)

        monkeypatch.setattr(fa, attr, spy)
    b, s, _, d = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    k = jax.ShapeDtypeStruct((b, s, h_kv, d), jnp.bfloat16)
    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])
    try:
        out = jax.eval_shape(
            lambda q_, k_, v_: fa.flash_attention_fwd(
                q_, k_, v_, is_causal=True, **kw), q, k, k)
        now = metrics.snapshot()["counters"]
    finally:
        if not was:
            metrics.disable()
    assert out.shape == shape
    assert reached == [(core, kv_heads)]
    delta = {n: v - before.get(n, 0) for n, v in now.items()
             if n.startswith("flash.") and v - before.get(n, 0)}
    assert delta == counters


def _llama_attention_grads(monkeypatch, layout):
    """One LLaMA attention call site (GQA + RoPE + row/col projections)
    forward + backward under the given layout; returns (out, dx, dw)."""
    import paddle_tpu.ops.pallas as _pl
    from paddle_tpu.models.llama import LlamaAttention, LlamaConfig

    _force_core(monkeypatch, layout)
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    monkeypatch.setattr(_pl, "flash_attention_available",
                        lambda q_: True)
    P.seed(7)
    cfg = LlamaConfig(vocab_size=128, hidden_size=128, num_layers=1,
                      num_heads=2, num_kv_heads=1, max_seq_len=32,
                      ffn_hidden=128)
    attn = LlamaAttention(cfg)
    x = P.to_tensor(np.random.RandomState(5)
                    .randn(2, 32, 128).astype(np.float32))
    x.stop_gradient = False
    out = attn(x)
    P.sum(out).backward()
    return (out.numpy(), x.grad.numpy(),
            attn.qkv_proj.weight.grad.numpy())


def test_llama_call_site_flat_bit_identical(monkeypatch):
    """The REAL LLaMA attention call site (fused qkv split, RoPE, GQA
    with Hkv < Hq, out projection): forward, input grad, and qkv weight
    grad are bit-identical between the transpose and flat layouts."""
    out_t, dx_t, dw_t = _llama_attention_grads(monkeypatch, "transpose")
    out_f, dx_f, dw_f = _llama_attention_grads(monkeypatch, "flat")
    assert np.array_equal(out_t, out_f)
    assert np.array_equal(dx_t, dx_f)
    assert np.array_equal(dw_t, dw_f)


def _gpt_attention_grads(monkeypatch, layout):
    import paddle_tpu.ops.pallas as _pl
    from paddle_tpu.models.gpt import GPTAttention, GPTConfig

    _force_core(monkeypatch, layout)
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    monkeypatch.setattr(_pl, "flash_attention_available",
                        lambda q_: True)
    P.seed(9)
    cfg = GPTConfig(vocab_size=128, hidden_size=128, num_layers=1,
                    num_heads=2, max_seq_len=32)
    attn = GPTAttention(cfg)
    x = P.to_tensor(np.random.RandomState(6)
                    .randn(2, 32, 128).astype(np.float32))
    x.stop_gradient = False
    out = attn(x)
    P.sum(out).backward()
    return (out.numpy(), x.grad.numpy(),
            attn.qkv_proj.weight.grad.numpy())


def test_gpt_call_site_flat_bit_identical(monkeypatch):
    """The REAL GPT attention call site (fused qkv unbind, causal MHA,
    out projection): forward + grads bit-identical across layouts."""
    out_t, dx_t, dw_t = _gpt_attention_grads(monkeypatch, "transpose")
    out_f, dx_f, dw_f = _gpt_attention_grads(monkeypatch, "flat")
    assert np.array_equal(out_t, out_f)
    assert np.array_equal(dx_t, dx_f)
    assert np.array_equal(dw_t, dw_f)


def _ernie_encoder_grads(monkeypatch, layout):
    """One ERNIE encoder forward + backward (bidirectional attention
    with an additive padding-mask bias — the biased, NON-causal flash
    path) under the given layout; returns (seq_out, d_word_emb)."""
    import paddle_tpu.ops.pallas as _pl
    from paddle_tpu.models.ernie import ErnieConfig, ErnieModel

    _force_core(monkeypatch, layout)
    monkeypatch.setattr(fa, "flash_attention_available", lambda q_: True)
    monkeypatch.setattr(_pl, "flash_attention_available",
                        lambda q_: True)
    P.seed(11)
    cfg = ErnieConfig(vocab_size=128, hidden_size=128, num_layers=1,
                      num_heads=2, ffn_hidden=128, dropout=0.0)
    model = ErnieModel(cfg)
    rs = np.random.RandomState(3)
    ids = P.to_tensor(rs.randint(1, 128, (2, 32)), "int32")
    mask = np.ones((2, 32), np.float32)
    mask[:, 24:] = 0.0  # padded tail: the additive bias band is live
    seq, pooled = model(ids, attention_mask=P.to_tensor(mask))
    (P.sum(seq) + P.sum(pooled)).backward()
    return (seq.numpy(),
            model.embeddings.word_embeddings.weight.grad.numpy())


def test_ernie_call_site_flat_bit_identical(monkeypatch):
    """The REAL ERNIE call site (bidirectional attention + additive
    stop-gradient padding mask through the biased flash tier): forward
    and embedding grads bit-identical between layouts — the third
    attention family (after causal-MHA GPT and GQA+RoPE LLaMA) the
    default flip must not perturb."""
    out_t, demb_t = _ernie_encoder_grads(monkeypatch, "transpose")
    out_f, demb_f = _ernie_encoder_grads(monkeypatch, "flat")
    assert np.array_equal(out_t, out_f)
    assert np.array_equal(demb_t, demb_f)


def test_window_partition_reverse_roundtrip():
    """window_reverse(window_partition(x)) == x for every (H, W, ws)
    tiling — the property the fused Swin kernel's in-kernel partition
    rests on — and partition produces row-major window order."""
    from paddle_tpu.ops.pallas.window_attention import (
        window_partition, window_reverse,
    )

    rs = np.random.RandomState(0)
    for (H, W, ws, C) in ((8, 8, 4, 6), (12, 8, 4, 3), (14, 14, 7, 5),
                          (4, 4, 4, 2)):
        x = jnp.asarray(rs.randn(2, H, W, C), jnp.float32)
        wins = window_partition(x, ws)
        assert wins.shape == (2 * (H // ws) * (W // ws), ws * ws, C)
        back = window_reverse(wins, ws, H, W)
        assert np.array_equal(np.asarray(back), np.asarray(x))
        # first window is the top-left tile, row-major
        assert np.array_equal(
            np.asarray(wins[0].reshape(ws, ws, C)),
            np.asarray(x[0, :ws, :ws, :]))
