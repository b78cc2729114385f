"""Compile rehearsal for a DESCRIBED v5e: the kernels of the chip_smoke
path (GPT-3 125M trainer + serving engine), at real widths, through the
chip's own compiler — no chip attached, nothing runs.

`tests/test_tpu_lowering.py` only exports for TPU (BlockSpec checks at
lowering); the VMEM refusals of the chip's compiler show only here.  The
topology is described inside a module-scoped fixture (never at import:
one process may load the TPU library, and under xdist every worker
imports this file), and every compile happens in the test's own process.
Keep these tests in this ONE file.
"""
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from paddle_tpu.ops.pallas import autotune
from paddle_tpu.ops.pallas import flash_attention as fa
from paddle_tpu.ops.pallas.decode_attention import decode_attention
from paddle_tpu.ops.pallas.paged_attention import paged_attention

# GPT-3 125M attention widths (chip_smoke.py / bench.py)
S, H, D = 1024, 12, 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(one_chip, f, *shapes):
    """Compile f for the described chip; the Pallas kernel must be in
    the compiled program (no silent interpreter / reference fallback)."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    with fa.force_tpu_lowering():
        text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.fixture
def flash_counters():
    """Counter deltas of the flash dispatch, read after the test body
    traced it: {"flash.dispatch{tier=...}": n, "flash.blocks{...}": n}."""
    from paddle_tpu.observability import metrics

    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])

    def delta():
        now = metrics.snapshot()["counters"]
        return {k: v - before.get(k, 0) for k, v in now.items()
                if k.startswith("flash.") and v - before.get(k, 0)}

    yield delta
    if not was:
        metrics.disable()


@pytest.mark.parametrize("tier,blocks", [
    ("flat", (256, 512)),          # what GPT-125M trains with
    ("transpose", (512, 1024)),    # the same shape, refused at the gate
])
@pytest.mark.parametrize("b", [8, 32])
def test_flash_cold_default_compiles_fwd_bwd(one_chip, monkeypatch,
                                             flash_counters, b, tier,
                                             blocks):
    """What `scaled_dot_product_attention` runs in the train step: the
    real dispatch (`flash_attention_fwd`), cold autotune cache (the
    static default blocks), forward and backward, on either core."""
    if tier == "transpose":
        monkeypatch.setattr(fa, "_flat_static_ok", lambda q, k: False)
    monkeypatch.setattr(autotune, "_enabled", lambda: False)

    def fwd(q, k, v):
        return fa.flash_attention_fwd(q, k, v, is_causal=True)

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    qkv = [((b, S, H, D), jnp.bfloat16)] * 3
    _compile(one_chip, fwd, *qkv)
    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)), *qkv)
    bq, bk = blocks
    assert flash_counters() == {
        f"flash.dispatch{{tier={tier}}}": 2,
        f"flash.blocks{{block_k={bk},block_q={bq},tier={tier}}}": 2,
        f"flash.backward{{kind=fused,tier={tier}}}": 1}


@pytest.mark.parametrize("tier,shape,blocks", [
    ("flat", (32, 1024, 12, 64), (512, 512)),    # gpt3-125m.train.seq1024
    ("transpose", (16, 2048, 12, 64), (512, 512)),   # ...train.seq2048
    ("transpose", (16, 2048, 12, 64), (512, 1024)),  # its largest fused
])
def test_fused_backward_compiles_at_the_cells_shapes(one_chip,
                                                     flash_counters, tier,
                                                     shape, blocks):
    """The two benchmark cells' attention at their tuned blocks
    (512, 512) and at the largest pair whose fused backward the gates
    admit, forward and backward: the fused backward fits the chip's
    VMEM, and the compiled program holds what the benchmark's kernel
    family (`benchmark/kernels/flash_train.json`) looks for: two flash
    calls a layer pass, ONE of them a backward call that returns a tuple
    (it counts the passes), named after the tier."""
    import json
    import re
    from pathlib import Path

    core = {"flat": fa._flash_core_flat, "transpose": fa._flash_core}[tier]

    def loss(q, k, v):
        with jax.named_scope("attn"):   # as a module scope names it
            return core(q, k, v, True, *blocks).astype(jnp.float32).sum()

    text = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                    *[(shape, jnp.bfloat16)] * 3)
    assert flash_counters() == {
        f"flash.backward{{kind=fused,tier={tier}}}": 1}
    family = json.loads((Path(__file__).parents[1] / "benchmark" /
                         "kernels" / "flash_train.json").read_text())
    lines = [ln.strip().removeprefix("ROOT ") for ln in text.splitlines()]
    events = [ln for ln in lines if re.search(family["events"], ln)]
    passes = [ln for ln in events if re.search(family["passes"], ln)]
    assert len(events) == 2 and len(passes) == 1, events
    assert passes[0].startswith(f"%transpose_jvp_flash_{tier}_bwd__")


@pytest.mark.parametrize("q_shape,kv_heads,causal,block", [
    ((8, 197, 12, 64), 12, False, None),  # ViT: padded, one block of 200
    ((4, 1024, 32, 128), 8, True, 512),   # LLaMA-class GQA, groups of four
])
def test_fused_backward_compiles_padded_and_grouped(one_chip, monkeypatch,
                                                    flash_counters,
                                                    q_shape, kv_heads,
                                                    causal, block):
    """The transpose core's other callers through the real dispatch: an
    odd length (stats rows of 200 lanes, delta relaid by the diagonal
    sum) and a KV head's group of four query heads resident at once
    (at 512 x 512; the cold default (512, 1024) needs 17.8 MB there and
    takes the split pair)."""
    monkeypatch.setattr(autotune, "_enabled", lambda: False)
    monkeypatch.setattr(fa, "_flat_static_ok", lambda q, k: False)
    b, s, _, d = q_shape

    def loss(q, k, v):
        return fa.flash_attention_fwd(
            q, k, v, is_causal=causal, block_q=block,
            block_k=block).astype(jnp.float32).sum()

    _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
             (q_shape, jnp.bfloat16), *[((b, s, kv_heads, d),
                                         jnp.bfloat16)] * 2)
    assert flash_counters()[
        "flash.backward{kind=fused,tier=transpose}"] == 1


# --- the trinity-mini-ep8.train.seq8192 cell's kernels ----------------------
# 2 x 8192 positions, 32 query heads over 4 key/value heads of 128


@pytest.mark.parametrize("window", [2048, None])
def test_windowed_flash_compiles_at_the_moe_cells_shape(one_chip, monkeypatch,
                                                        flash_counters,
                                                        window):
    """The new cell's attention through the real dispatch, cold autotune
    cache, forward and backward: the grouped path's resident group is
    50 MB, so K and V are expanded eightfold (`flash.gqa_expand`); the
    flat gate refuses 8192 positions; the transpose core's fused backward
    does not fit (sequence-long q/o/do/dQ of one head), so the split pair
    runs, its dK/dV kernel asking for more scoped VMEM than the compiler's
    default (`_t_dkdv_vmem_bytes`; the parent's kernel was refused here:
    21.0 MiB of 16).  A windowed call is named apart."""
    monkeypatch.setattr(autotune, "_enabled", lambda: False)

    def loss(q, k, v):
        with jax.named_scope("attn"):   # as a module scope names it
            return fa.flash_attention_fwd(
                q, k, v, is_causal=True,
                window=window).astype(jnp.float32).sum()

    text = _compile(one_chip, jax.grad(loss, argnums=(0, 1, 2)),
                    ((2, 8192, 32, 128), jnp.bfloat16),
                    *[((2, 8192, 4, 128), jnp.bfloat16)] * 2)
    tag = "transpose_window" if window else "transpose"
    for kernel in ("jvp_flash_%s_fwd_", "transpose_jvp_flash_%s_dq__",
                   "transpose_jvp_flash_%s_dkdv__"):
        assert "%" + kernel % tag in text, kernel % tag
    label = f",window={window}" if window else ""
    got = flash_counters()
    assert got[f"flash.dispatch{{tier=transpose{label}}}"] == 1
    assert got["flash.gqa_expand{reason=group_bytes}"] == 1
    assert got["flash.backward{kind=split,tier=transpose}"] == 1
    assert ("flash.gate_reject{gate=flat,reason=vmem}" in got) == (not window)
    assert fa._t_dkdv_vmem_bytes(8192, 1, 128, 2, 512, 512) > fa._T_VMEM_LIMIT
    # the GPT cells' split pair stays under the default and sets no limit
    assert fa._t_dkdv_vmem_bytes(2048, 1, 64, 2, 512, 512) <= fa._T_VMEM_LIMIT


def test_grouped_expert_products_compile_to_the_chips_own_kernel(one_chip):
    """The expert layer's routed part at the new cell's size, forward and
    backward: every `ragged_dot` (and both transposes of it) becomes the
    TPU compiler's grouped Mosaic kernel — none is left as a dense
    per-expert expansion."""
    import math
    import re

    from paddle_tpu.incubate.distributed.models import routed_moe

    t, h, f, e, held, k = 16384, 2048, 1024, 128, 16, 8

    def loss(x, wr, wg, wu, wd):
        y, sizes, counts = routed_moe._routed_part(
            x, wr, jnp.zeros((e,), jnp.float32), wg, wu, wd, top_k=k,
            route_scale=2.826, route_norm=True, expert_start=0)
        return (y.astype(jnp.float32) ** 2).sum(), (sizes, counts)

    bf = jnp.bfloat16
    text = _compile(one_chip, jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True),
        ((t, h), bf), ((h, e), bf), ((held, h, f), bf), ((held, h, f), bf),
        ((held, f, h), bf))
    assert len(re.findall(r"%ragged-dot-none[.\d]* = ", text)) >= 11
    assert not re.search(r" ragged-dot\(", text)
    # the index work: no gather or scatter reads an index operand as long
    # as the tokens x top_k assignments (XLA's run milliseconds each on
    # the chip) — a chunk's own are the longest
    first, later = routed_moe.default_rows_per_chunk(t, k, held, e)
    size = {m.group(1): math.prod(int(d) for d in m.group(2).split(","))
            for m in re.finditer(r"(%[\w.\-]+) = \w+\[([\d,]+)\]", text)}
    spans = [size[m.group(1)] for m in re.finditer(
        r" (?:gather|scatter)\(%[\w.\-]+, (%[\w.\-]+)[,)]", text)]
    assert spans and max(spans) <= max(first, later) < t * k


@pytest.mark.parametrize("recomputed", [False, True],
                         ids=["plain", "recomputed"])
def test_learned_sparse_attention_compiles_at_the_keye_cells_shape(
        one_chip, recomputed):
    """keye-vl2-ep8.train.seq8192: 2 x 8192, 32:4 heads of 128, an indexer
    of 16 heads of 64, topk 2048 — the index-score kernels (forward and
    backward), the selection's search, the indexer's loss (ONE kernel: the
    value and the gradient by the scores), the masked grouped-query
    attention (forward and ONE backward kernel: dQ, dK and dV, its VMEM
    limit from `_bwd_vmem_bytes`) and the head-mean probabilities, at the
    default blocks, through the chip's compiler.  The layer goes through
    its functional entries: each must take its kernel when compiled for
    the chip (a predicate on the wrong shape once sent two of them to
    their jax.numpy forms in the whole model).  Every kernel stands ONCE
    in the compiled gradient, also where the layer is recomputed: what its
    backward pass wants of the forward kernels is kept
    (`distributed/recompute.py`), so the replay runs none of them."""
    import importlib
    import re

    import paddle_tpu as P
    from paddle_tpu.core import flags
    from paddle_tpu.nn import functional as F
    from paddle_tpu.observability import metrics

    rc = importlib.import_module("paddle_tpu.distributed.recompute")
    b, t, h, hkv, d, j, di = 2, 8192, 32, 4, 128, 16, 64
    bf = jnp.bfloat16

    def layer(q, k, v, qi, ki, w):
        scores = F.sparse_index_scores(qi, ki, w)
        mask, _ = F.sparse_select_topk(scores, 2048)
        out, stats = F.selected_attention(q, k, v, mask)
        probs = F.selected_attention_probs(stats, mask)
        return out, F.sparse_indexer_loss(scores, mask, probs)

    def step(g, *args):
        def loss(*args):
            with flags.trace_guard():
                tensors = [P.to_tensor(a) for a in args]
                for x in tensors:
                    x.stop_gradient = False
                out, aux = (rc.recompute(layer, *tensors) if recomputed
                            else layer(*tensors))
            return (out._value.astype(jnp.float32) * g).sum() + aux._value
        return jax.grad(loss, argnums=(0, 1, 2, 3, 4, 5))(*args)

    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])
    try:
        text = _compile(one_chip, step, ((b, t, h, d), jnp.float32),
                        ((b, t, h, d), bf), ((b, t, hkv, d), bf),
                        ((b, t, hkv, d), bf), ((b, t, j, di), bf),
                        ((b, t, di), bf), ((b, t, j), bf))
        now = metrics.snapshot()["counters"]
    finally:
        if not was:
            metrics.disable()
    backward = {k: now[k] - before.get(k, 0) for k in now
                if k.startswith("sparse_attn.backward")}
    assert backward == {"sparse_attn.backward{kind=fused}": 1}
    # XLA names a Mosaic call after its kernel (`%jvp_sparse_index_fwd_.1`);
    # the fused backward makes dQ under the dK/dV kernel's name
    calls = re.findall(r"(%[\w.\-]+) = [^\n]*tpu_custom_call", text)
    for kernel in ("sparse_index_fwd", "sparse_index_dq", "sparse_index_dk",
                   "sparse_index_select", "sparse_index_loss",
                   "sparse_attn_fwd", "sparse_attn_dkdv",
                   "sparse_attn_probs"):
        assert sum(kernel in c for c in calls) == 1, kernel
    assert len(calls) == 8 and "sparse_index_loss_bwd" not in text
    assert "sparse_attn_dq" not in text


def test_flash_runs_per_shard_on_a_four_chip_mesh(topo, one_chip,
                                                  monkeypatch):
    """A Mosaic kernel inside a multi-device program is refused at
    lowering ("cannot be automatically partitioned"); under the mesh the
    train step announces (`use_spmd_mesh`) the dispatch runs per shard.
    dp=4 over the host's four chips, global batch 8, fwd + bwd."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed import topology

    monkeypatch.setattr(autotune, "_enabled", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("dp", "sep", "mp"))
    q = jax.ShapeDtypeStruct((8, S, H, D), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("dp")))

    def loss(q, k, v):
        with topology.use_spmd_mesh(mesh):
            return fa.flash_attention_fwd(
                q, k, v, is_causal=True).astype(jnp.float32).sum()

    with fa.force_tpu_lowering():
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            q, q, q).compile().as_text()
    assert "tpu_custom_call" in text


def _head_ce_loss(h, w, labels):
    from paddle_tpu.models.gpt import _fused_linear_ce

    with jax.named_scope("head_ce"):
        total, count = _fused_linear_ce(h, w, labels, -100)
    return total / jnp.maximum(count, 1.0)


def _computations(text):
    """{name: body text} of a compiled module's computations."""
    import re

    parts = re.split(r"\n(?=(?:ENTRY )?%?[\w.\-]+ \(.*\) -> .* \{)", text)
    return {("ENTRY" if p.startswith("ENTRY") else p.split(" ", 1)[0]): p
            for p in parts}


@pytest.mark.parametrize("b,s,hd,v,temp_gb", [
    (32, 1024, 768, 50304, 1.0),     # gpt3-125m.train.seq1024
    (16, 2048, 768, 50304, 1.0),     # gpt3-125m.train.seq2048
    (2, 8192, 2048, 25024, 0.36),    # trinity-mini-ep8.train.seq8192
])
def test_head_ce_compiles_at_the_cells_shapes(one_chip, b, s, hd, v,
                                              temp_gb):
    """The head + cross-entropy scan, differentiated, at the three cells'
    shapes: 16 slices of the sequence, one while loop (the gradient is
    formed in the forward scan; no second loop replays it), and
    temporaries that leave `peak_hbm_share.train` where it is."""
    import re

    from paddle_tpu.models.gpt import _token_slices

    assert _token_slices(b, s, v) == (s // 16, 16)
    h = jax.ShapeDtypeStruct((b, s, hd), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((v, hd), jnp.bfloat16, sharding=one_chip)
    labels = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)
    compiled = jax.jit(jax.value_and_grad(_head_ce_loss, (0, 1))).lower(
        h, w, labels).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= temp_gb * 1e9
    text = compiled.as_text()
    assert len(re.findall(r" while\(", text)) == 1
    assert f"f32[{b * s},{v}]" not in text and f"f32[{b},{s},{v}]" not in text


def test_head_ce_reduces_dw_once_under_dp(topo, one_chip):
    """B on a dp axis of 4 (what the ZeRO-1 step of four chips shards):
    the scan cuts the sequence, so no slice crosses a shard — the loop
    body holds no collective, and the head's dW (a partial sum a chip)
    is reduced ONCE, after the loop.  The chip's own compiler decides
    where the reduction goes; this pins what it decided."""
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    b, s, hd, v = 32, 1024, 768, 50304
    mesh = Mesh(np.array(topo.devices).reshape(4, 1, 1),
                ("dp", "sep", "mp"))

    def shaped(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    text = jax.jit(jax.value_and_grad(_head_ce_loss, (0, 1))).lower(
        shaped((b, s, hd), jnp.bfloat16, P("dp")),
        shaped((v, hd), jnp.bfloat16, P()),
        shaped((b, s), jnp.int32, P("dp"))).compile().as_text()
    comps = _computations(text)
    collective = re.compile(
        r" (all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective-permute)(-start)?\(")
    bodies = set(re.findall(r"body=(%?[\w.\-]+)", text))
    assert len(bodies) == 1
    loops = [c for name, c in comps.items() if name in bodies]
    assert loops and not any(collective.search(c) for c in loops)
    # a slice's logits cover a chip's rows only
    assert f"f32[{b // 4 * s // 16},{v}]" in loops[0]
    dw_reduces = re.findall(rf"f32\[{v},{hd}\]\S* all-reduce\(", text)
    assert len(dw_reduces) == 1 and dw_reduces[0] in comps["ENTRY"]


def test_head_ce_values_on_a_cpu_mesh():
    """The same sharding on the CPU's virtual devices, for the VALUES:
    loss, dh and dW with B over dp = 4 equal the one-device call's."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices("cpu")
    if len(devices) < 4:
        pytest.skip("needs four CPU devices")
    mesh = Mesh(np.array(devices[:4]).reshape(4, 1, 1), ("dp", "sep", "mp"))
    rs = np.random.RandomState(9)
    h = jnp.asarray(rs.randn(8, 32, 16), jnp.float32)
    w = jnp.asarray(0.3 * rs.randn(317, 16), jnp.float32)
    labels = rs.randint(0, 317, (8, 32)).astype(np.int32)
    labels[rs.rand(8, 32) < 0.2] = -100
    labels = jnp.asarray(labels)
    f = jax.jit(jax.value_and_grad(_head_ce_loss, (0, 1)))
    want = f(h, w, labels)
    got = f(jax.device_put(h, NamedSharding(mesh, P("dp"))),
            jax.device_put(w, NamedSharding(mesh, P())),
            jax.device_put(labels, NamedSharding(mesh, P("dp"))))
    for a, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5,
                                   atol=1e-6)


def test_flash_candidates_fit_the_gate(monkeypatch):
    """The flat candidate list holds no pair the dispatch gate's own
    arithmetic rejects (the compiler refuses the split pair's
    (512,1024) and (1024,1024) backward at this shape).  The tier's
    reach is the split pair's estimate; inside it the fused backward
    fits with room to spare.  And the two cells' searches are keyed as
    they always were: a winner cached on disk is still found."""
    seen = {}

    def spy(op, sig, cands, run, default):
        seen[op, sig] = (list(cands), default)
        return default

    monkeypatch.setattr(autotune, "pick", spy)
    q = jax.ShapeDtypeStruct((32, S, H, D), jnp.bfloat16)
    fa._tuned_blocks(32, S, S, H, D, q.dtype, True, layout="flat")
    fa._tuned_blocks(16, 2048, 2048, H, D, q.dtype, True)
    flat_key = ("flash_fwd_fusedbwd", "32x1024x1024x12x64|bfloat16|c1|Lflat")
    assert list(seen) == [
        flat_key, ("flash_fwd_fusedbwd", "16x2048x2048x12x64|bfloat16|c1")]
    cands, default = seen[flat_key]
    assert default in cands
    assert (512, 1024) not in cands and (1024, 1024) not in cands
    assert all(fa._flat_native_ok(q, q, *c) for c in cands)
    assert all(fa._flat_vmem_bytes(S, S, H, H, D, 2, *c, fused=True)
               < fa._flat_vmem_bytes(S, S, H, H, D, 2, *c) for c in cands)
    # the flat gate still refuses the seq2048 cell's shape at EVERY
    # candidate (the fused estimate alone would let (256, 256) through,
    # and the chip's compiler refuses that kernel): that cell exists to
    # run the transpose core
    q2 = jax.ShapeDtypeStruct((16, 2048, H, D), jnp.bfloat16)
    pairs = ((512, 1024), (1024, 1024), (512, 512), (256, 512),
             (256, 256), (128, 128))
    assert not any(fa._flat_native_ok(q2, q2, *c) for c in pairs)


def _engine_shapes():
    """The decode shapes of chip_smoke's serve phase: default
    EngineConfig over GPT-3 125M (max_seq_len 1024)."""
    from paddle_tpu.inference.engine import EngineConfig

    cfg = EngineConfig()
    pages_per_seq = -(-1024 // cfg.page_size)
    return (cfg.max_slots, pages_per_seq,
            (cfg.max_slots * pages_per_seq + 1, H, cfg.page_size, D))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_compiles_at_engine_shapes(one_chip, dtype):
    slots, pages_per_seq, pool = _engine_shapes()
    _compile(one_chip, paged_attention,
             ((slots, H, D), dtype), (pool, dtype), (pool, dtype),
             ((slots, pages_per_seq), jnp.int32), ((slots,), jnp.int32))


@pytest.mark.parametrize("b,cap,dtype", [
    (8, 1024, jnp.bfloat16),    # a full-context batch
    (1, 128, jnp.float32),      # generate() as chip_smoke calls it
])
def test_decode_attention_compiles(one_chip, b, cap, dtype):
    _compile(one_chip, decode_attention,
             ((b, H, D), dtype), ((b, H, cap, D), dtype),
             ((b, H, cap, D), dtype), ((b,), jnp.int32))


def test_masked_prefill_compiles(one_chip, monkeypatch):
    """The engine's left-padded prefill bucket that passes the biased
    gate (128 keys): additive mask streamed through the biased core."""
    monkeypatch.setattr(autotune, "_enabled", lambda: False)

    def prefill(q, k, v, mask):
        return fa.flash_attention_fwd(q, k, v, mask=mask,
                                      bias_grad_safe=True)

    qkv = [((1, 128, H, D), jnp.float32)] * 3
    _compile(one_chip, prefill, *qkv, ((1, 1, 128, 128), jnp.float32))


# ------------- the program ledger's account of a step's bytes -------------

def _two_block_step(marks):
    """A step over two `recompute()` blocks; each block marks the first
    `marks` of its two matrix products with `keep()`."""
    import importlib

    import paddle_tpu as P
    from paddle_tpu.core import flags

    rc = importlib.import_module("paddle_tpu.distributed.recompute")

    def block(x, wa, wb, wc):
        h = x._value @ wa._value
        if marks >= 1:
            h = rc.keep(h, "flash_out")
        g = jnp.tanh(h) @ wb._value
        if marks >= 2:
            g = rc.keep(g, "moe_out")
        return P.Tensor(jnp.sin(g) @ wc._value)

    def loss_of(weights, x):
        with flags.trace_guard():
            h = P.Tensor(x)
            for ws in weights:
                h = rc.recompute(block, h, *[P.Tensor(w) for w in ws])
        return jnp.mean(jnp.square(h._value))

    def step(weights, x):
        with jax.named_scope("train_step.loss"):
            loss, grads = jax.value_and_grad(loss_of)(weights, x)
        with jax.named_scope("train_step.update"):
            return loss, jax.tree_util.tree_map(
                lambda w, g: w - 0.1 * g, weights, grads)

    return jax.jit(step)


def test_a_kept_value_costs_exactly_its_bytes_a_block(one_chip):
    """The ledger's liveness sweep (`xla_cost.buffer_sweep`) on the chip's
    own schedule of a two-block `recompute()` model, at rows enough that
    nothing fits VMEM: what the forward holds for the backward grows by
    exactly one array a block when one more value is `keep()`-marked, the
    peak lies in the backward, and the sweep's peak is the compiler's
    `temp_size_in_bytes` to a percent."""
    from paddle_tpu.observability import metrics, xla_cost

    rows = 524288
    h_bytes, g_bytes = rows * 256 * 4, rows * 384 * 4

    def shaped(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    weights = [(shaped(128, 256), shaped(256, 384), shaped(384, 128))] * 2
    was = metrics.enabled()
    metrics.enable()
    try:
        swept = {}
        for marks in (0, 1, 2):
            label = f"chip_blocks{marks}"
            xla_cost.instrument(_two_block_step(marks), label).aot_compile(
                weights, shaped(rows, 128))
            entry = xla_cost.program_ledger(label)
            swept[marks] = entry["bytes"]
            temp = entry["memory"]["temp_bytes"]
            assert abs(swept[marks]["peak_bytes"] - temp) <= 0.01 * temp
    finally:
        if not was:
            metrics.disable()
    assert swept[1]["residual_bytes"] - swept[0]["residual_bytes"] \
        == 2 * h_bytes
    assert swept[2]["residual_bytes"] - swept[1]["residual_bytes"] \
        == 2 * g_bytes
    for got in swept.values():
        assert got["peak_at"]["phase"] in ("replay", "bwd")
        assert got["backward_at"]["phase"] in ("replay", "bwd")


def test_sweep_is_held_to_the_compiler_on_a_two_layer_afmoe_step(topo,
                                                                 one_chip):
    """`tools/step_bytes.py` on the Trinity cell cut to its first two layers
    (a dense and an expert layer, both windowed, recomputed, 2 x 8192): the
    whole train step through the chip's compiler and the program ledger.
    The sweep never passes the compiler's `temp_size_in_bytes` (a naive one
    read 3 x it) and stays within a quarter under it — not the 15 % ISSUE 37
    asked: that total holds 0.3-1.0 GB more than the compiler's own buffer
    assignment puts in the HBM heap (3.684 GB here, which the sweep reads to
    -3 %, and which is what the chip's runtime reserves; PERF.md section 6).
    The peak lies in a layer's backward or replay, and the Mosaic calls of
    the path are in the compiled text."""
    import importlib.util
    from pathlib import Path

    from paddle_tpu.distributed import topology
    from paddle_tpu.observability import metrics, xla_cost

    path = Path(__file__).parents[1] / "tools" / "step_bytes.py"
    spec = importlib.util.spec_from_file_location("step_bytes", path)
    step_bytes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_bytes)
    was = metrics.enabled()
    try:
        compiled = step_bytes.compile_step("trinity-mini-ep8.train.seq8192",
                                           topo.devices[0], layers=2)
        entry = xla_cost.program_ledger("train_step")
    finally:
        topology.reset_topology()
        if not was:
            metrics.disable()
    peak, temp = entry["bytes"]["peak_bytes"], entry["memory"]["temp_bytes"]
    assert 0.75 * temp <= peak <= temp, entry["bytes"]["peak_at"]
    assert entry["bytes"]["peak_at"]["phase"] in ("replay", "bwd")
    assert "layers." in entry["bytes"]["peak_at"]["op_name"]
    assert entry["bytes"]["residual_bytes"] < peak
    assert entry["bytes"]["n_containers"] >= 1       # the head's scan
    calls = step_bytes.mosaic_calls(compiled.as_text())
    assert calls["jvp_flash_transpose_window_fwd_"] == 2
    assert calls["transpose_jvp_flash_transpose_window_dq__"] == 2


def test_looped_step_compiles_at_the_ouro_cells_shape(topo, one_chip):
    """`tools/step_bytes.py` on the Ouro cell cut to its first layer (3 x
    4096, 16 heads of 128, the whole 49,152-row head, the stack run FOUR
    times): the whole train step through the chip's compiler.  Every
    layer APPLICATION is a recomputed segment that keeps its flash output
    + lse, so the forward kernel stands once an application (4) and no
    replay runs it again; the four passes' read-outs are ONE weighted
    head + CE scan; the sweep stays under the compiler's temporaries."""
    import importlib.util
    from pathlib import Path

    from paddle_tpu.distributed import topology
    from paddle_tpu.observability import metrics, xla_cost

    path = Path(__file__).parents[1] / "tools" / "step_bytes.py"
    spec = importlib.util.spec_from_file_location("step_bytes", path)
    step_bytes = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(step_bytes)
    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])
    try:
        compiled = step_bytes.compile_step("ouro-2.6b-pp8.train.seq4096",
                                           topo.devices[0], layers=1)
        entry = xla_cost.program_ledger("train_step")
        now = metrics.snapshot()["counters"]
    finally:
        topology.reset_topology()
        if not was:
            metrics.disable()
    added = {k: v - before.get(k, 0) for k, v in now.items()
             if v - before.get(k, 0)}
    assert added["flash.recompute_kept{what=out_lse}"] == 4
    assert [added[f"loop.apply{{ut={k}}}"] for k in (1, 2, 3, 4)] == [1] * 4
    assert added["head_ce.weights{kind=per_token}"] == 1
    assert added["flash.dispatch{tier=transpose}"] == 4
    peak, temp = entry["bytes"]["peak_bytes"], entry["memory"]["temp_bytes"]
    assert peak <= temp
    assert "loop." in entry["bytes"]["peak_at"]["op_name"]
    calls = step_bytes.mosaic_calls(compiled.as_text())
    assert calls["jvp_flash_transpose_fwd_"] == 4
    assert calls["transpose_jvp_flash_transpose_dkdv__"] == 4
