"""Model zoo: LLaMA (GQA), ViT, and the extra vision families.

Parity model: reference model-zoo smoke tests (`test/legacy_test/
test_vision_models.py` style — construct, forward, shape-check) plus a
train-step check on the flagship language models.
"""
import os

import numpy as np
import pytest

import paddle_tpu as P
from paddle_tpu.models import (LlamaConfig, LlamaForCausalLM,
                               LlamaPretrainingCriterion, llama_pipe_layers,
                               llama_tiny)
from paddle_tpu.vision import models as V


@pytest.mark.slow
def test_llama_forward_and_train_step():
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    crit = LlamaPretrainingCriterion()
    rng = np.random.RandomState(0)
    ids = P.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)), dtype="int64")
    labels = P.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16)),
                         dtype="int64")
    logits = model(ids)
    assert logits.shape == [2, 16, cfg.vocab_size]
    loss = crit(logits, labels)
    loss.backward()
    opt = P.optimizer.AdamW(1e-3, parameters=list(model.parameters()))
    opt.step()
    opt.clear_grad()
    loss2 = crit(model(ids), labels)
    assert float(loss2.numpy()) < float(loss.numpy())


def test_llama_gqa_heads():
    cfg = llama_tiny(num_heads=4, num_kv_heads=2)
    model = LlamaForCausalLM(cfg)
    hd = cfg.hidden_size // cfg.num_heads
    qkv_w = model.model.layers[0].attn.qkv_proj.weight
    # fused qkv: q (4 heads) + k (2) + v (2)
    assert qkv_w.shape[-1] == (4 + 2 + 2) * hd
    ids = P.to_tensor(np.zeros((1, 8), np.int64))
    out = model(ids)
    assert out.shape == [1, 8, cfg.vocab_size]


def test_llama_pipe_layers_compose():
    cfg = llama_tiny()
    layers = llama_pipe_layers(cfg)
    assert len(layers) == cfg.num_layers + 2
    x = P.to_tensor(np.zeros((1, 8), np.int64))
    h = layers[0](x)
    for blk in layers[1:-1]:
        h = blk(h)
    out = layers[-1](h)
    assert out.shape == [1, 8, cfg.vocab_size]


def test_llama_jit_parity():
    cfg = llama_tiny()
    model = LlamaForCausalLM(cfg)
    model.eval()
    ids = P.to_tensor(np.arange(16, dtype=np.int64).reshape(1, 16) % 100)
    eager = model(ids)
    st = P.jit.to_static(model)
    jit_out = st(ids)
    np.testing.assert_allclose(eager.numpy(), jit_out.numpy(), rtol=2e-5,
                               atol=1e-5)


@pytest.mark.slow
def test_llama_incremental_decode_matches_full():
    """KV-cache decode must equal full-sequence attention (RoPE offsets)."""
    from paddle_tpu.models.llama import LlamaAttention

    cfg = llama_tiny(num_heads=4, num_kv_heads=2)
    attn = LlamaAttention(cfg)
    attn.eval()
    rng = np.random.RandomState(0)
    x_full = P.to_tensor(rng.rand(1, 6, cfg.hidden_size).astype(np.float32))
    full_out = attn(x_full)
    hd = cfg.hidden_size // cfg.num_heads
    cache = (P.to_tensor(np.zeros((1, 0, cfg.num_kv_heads, hd), np.float32)),
             P.to_tensor(np.zeros((1, 0, cfg.num_kv_heads, hd), np.float32)))
    outs = []
    for t in range(6):
        xt = P.to_tensor(x_full.numpy()[:, t:t + 1])
        out_t, cache = attn(xt, cache=cache)
        outs.append(out_t.numpy()[:, 0])
    np.testing.assert_allclose(np.stack(outs, axis=1), full_out.numpy(),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_vit_forward():
    m = V.VisionTransformer(img_size=32, patch_size=8, embed_dim=64,
                            depth=2, num_heads=4, num_classes=10)
    x = P.to_tensor(np.random.RandomState(0).rand(2, 3, 32, 32)
                    .astype(np.float32))
    out = m(x)
    assert out.shape == [2, 10]
    loss = P.mean(P.square(out))
    loss.backward()
    assert m.blocks[0].attn.qkv.weight.grad is not None


@pytest.mark.parametrize("ctor,img", [
    (lambda: V.AlexNet(num_classes=10), 224),
    (lambda: V.SqueezeNet("1.1", num_classes=10), 224),
    (lambda: V.DenseNet((2, 2), growth=8, num_classes=10, init_ch=16), 64),
    (lambda: V.ShuffleNetV2(0.5, num_classes=10), 64),
    (lambda: V.GoogLeNet(num_classes=10), 64),
])
@pytest.mark.slow
def test_vision_zoo_smoke(ctor, img):
    m = ctor()
    m.eval()
    x = P.to_tensor(np.random.RandomState(1).rand(1, 3, img, img)
                    .astype(np.float32))
    out = m(x)
    assert out.shape == [1, 10]


@pytest.mark.slow
def test_fused_chunked_ce_matches_plain():
    """The chunked online-logsumexp CE must match F.cross_entropy in value
    AND gradient (it is the default GPT loss for large vocabs)."""
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import _chunked_softmax_ce
    import paddle_tpu.nn.functional as F

    rs = np.random.RandomState(4)
    n, v = 64, 9001  # odd vocab: exercises padding
    logits = rs.randn(n, v).astype(np.float32)
    labels = rs.randint(0, v, (n,)).astype(np.int32)
    labels[:5] = -100  # ignore_index tokens

    def fused(lg):
        total, count = _chunked_softmax_ce(lg, jnp.asarray(labels), -100)
        return total / count

    def plain(lg):
        return F.cross_entropy(
            P.Tensor(lg), P.Tensor(jnp.asarray(labels)),
            reduction="mean", ignore_index=-100)._value

    import jax

    f_val, f_grad = jax.value_and_grad(fused)(jnp.asarray(logits))
    p_val, p_grad = jax.value_and_grad(plain)(jnp.asarray(logits))
    np.testing.assert_allclose(float(f_val), float(p_val), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(f_grad), np.asarray(p_grad),
                               rtol=1e-4, atol=1e-6)

    # bf16 logits leg (the dtype the GPT head actually produces)
    lb = jnp.asarray(logits, jnp.bfloat16)
    fb = jax.value_and_grad(fused)(lb)
    assert np.isfinite(float(fb[0]))
    assert fb[1].dtype == jnp.bfloat16


@pytest.mark.parametrize("ctor,img", [
    ("mobilenet_v1", 64), ("mobilenet_v3_small", 64),
    ("mobilenet_v3_large", 64), ("resnext50_32x4d", 64),
    ("wide_resnet50_2", 64), ("densenet169", 64), ("inception_v3", 128),
    ("shufflenet_v2_x0_5", 64),
])
@pytest.mark.slow
def test_vision_zoo_extended_forward(ctor, img):
    """New zoo families: forward shape + grads flow (tiny inputs)."""
    from paddle_tpu.vision import models as V

    P.seed(0)
    m = getattr(V, ctor)(num_classes=7)
    m.eval()
    x = P.to_tensor(np.random.RandomState(0)
                    .randn(2, 3, img, img).astype(np.float32))
    out = m(x)
    assert out.shape == [2, 7]
    assert np.isfinite(out.numpy()).all()


@pytest.mark.slow
def test_gpt_generate_matches_full_forward_loop():
    """generate() (static KV cache + decode kernel path) must produce the
    same greedy tokens as re-running the full forward every step."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(7)
    cfg = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(0)
    prompt = rs.randint(0, cfg.vocab_size, (2, 5))

    # naive: full forward each step, greedy
    ids = prompt.copy()
    for _ in range(6):
        logits = model(P.to_tensor(ids, "int32")).numpy()
        ids = np.concatenate([ids, logits[:, -1].argmax(-1)[:, None]
                              .astype(ids.dtype)], axis=1)

    out = model.generate(P.to_tensor(prompt, "int32"), max_new_tokens=6)
    np.testing.assert_array_equal(np.asarray(out._value), ids)


@pytest.mark.slow
def test_llama_generate_gqa_matches_full_forward_loop():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    P.seed(11)
    cfg = LlamaConfig(vocab_size=89, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64,
                      ffn_hidden=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, cfg.vocab_size, (2, 4))

    ids = prompt.copy()
    for _ in range(5):
        logits = model(P.to_tensor(ids, "int32")).numpy()
        ids = np.concatenate([ids, logits[:, -1].argmax(-1)[:, None]
                              .astype(ids.dtype)], axis=1)

    out = model.generate(P.to_tensor(prompt, "int32"), max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(out._value), ids)


def test_generate_eos_stops_early_and_sampling_runs():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(3)
    cfg = GPTConfig(vocab_size=31, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt = P.to_tensor(np.zeros((1, 3), np.int64), "int32")
    out = model.generate(prompt, max_new_tokens=8, do_sample=True,
                         temperature=0.9, top_k=5, seed=0)
    arr = np.asarray(out._value)
    assert arr.shape[0] == 1 and 4 <= arr.shape[1] <= 11
    # eos: greedy emits SOME token t at step1; using it as eos stops at 1
    g = model.generate(prompt, max_new_tokens=8)
    first = int(np.asarray(g._value)[0, 3])
    g2 = model.generate(prompt, max_new_tokens=8, eos_token_id=first)
    assert np.asarray(g2._value).shape[1] == 4


def test_generate_per_row_eos_freezes_rows():
    """Rows that emit eos are frozen to eos while other rows continue
    (r3 review finding: all() only stopped on simultaneous finish)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(5)
    cfg = GPTConfig(vocab_size=23, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt_np = np.array([[1, 2, 3], [4, 5, 6]])
    base = model.generate(P.to_tensor(prompt_np, "int32"), max_new_tokens=6)
    arr = np.asarray(base._value)
    # pick row 0's first generated token as eos: row 0 freezes immediately
    eos = int(arr[0, 3])
    out = np.asarray(model.generate(P.to_tensor(prompt_np, "int32"),
                                    max_new_tokens=6,
                                    eos_token_id=eos)._value)
    assert (out[0, 3:] == eos).all()  # frozen row: eos-padded


def test_generate_program_cache_reused():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(6)
    cfg = GPTConfig(vocab_size=19, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt = P.to_tensor(np.ones((1, 3), np.int64), "int32")
    # each signature caches a (prefill, decode) pair + the chunked-scan
    # decode program
    model.generate(prompt, max_new_tokens=2)
    assert len(model._gen_cache) == 2
    model.generate(prompt, max_new_tokens=2)   # same sig -> cache hit
    assert len(model._gen_cache) == 2
    model.generate(prompt, max_new_tokens=2, do_sample=True, seed=0)
    assert len(model._gen_cache) == 4


def test_generate_chunked_decode_crosses_boundaries(monkeypatch):
    """The scanned-decode fast path must be bit-identical across chunk
    boundaries (token stream, PRNG order, eos trim) to a 1-token-per-
    dispatch run — shrink DECODE_CHUNK so a short generate spans several
    scans, and compare against CHUNK=1 which degenerates to the
    single-step sequence."""
    from paddle_tpu.models import generation
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=23, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=64, use_rope=True)
    prompt_np = np.ones((2, 3), np.int64)

    def run(chunk, **kw):
        monkeypatch.setattr(generation, "DECODE_CHUNK", chunk)
        P.seed(6)
        model = GPTForCausalLM(cfg)
        model.eval()
        return model.generate(P.to_tensor(prompt_np, "int32"),
                              max_new_tokens=11, **kw).numpy()

    # greedy, sampling (same seed -> same key stream), and eos trim
    np.testing.assert_array_equal(run(4), run(1))
    np.testing.assert_array_equal(run(4, do_sample=True, seed=3),
                                  run(1, do_sample=True, seed=3))
    a = run(4, eos_token_id=5)
    b = run(1, eos_token_id=5)
    np.testing.assert_array_equal(a, b)


def test_llama_gqa_cache_stores_kv_heads_only():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=31, hidden_size=32, num_layers=1,
                      num_heads=4, num_kv_heads=2, max_seq_len=64,
                      ffn_hidden=64)
    model = LlamaForCausalLM(cfg)
    caches = model.init_kv_caches(2, 10)
    k, v = caches[0]
    assert k.shape[1] == 2  # kv heads, not 4 query heads


def test_generate_left_padded_ragged_batch():
    """Ragged prompts via attention_mask: every row must generate the SAME
    tokens as running it alone unpadded (pad slots masked out of
    attention, rotary positions shifted per row)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(21)
    cfg = GPTConfig(vocab_size=83, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(2)
    row_a = rs.randint(1, cfg.vocab_size, (6,))   # length 6
    row_b = rs.randint(1, cfg.vocab_size, (3,))   # length 3

    # solo references (no padding)
    ref_a = np.asarray(model.generate(
        P.to_tensor(row_a[None], "int32"), max_new_tokens=4)._value)[0, 6:]
    ref_b = np.asarray(model.generate(
        P.to_tensor(row_b[None], "int32"), max_new_tokens=4)._value)[0, 3:]

    # left-padded ragged batch
    ids = np.zeros((2, 6), np.int64)
    mask = np.zeros((2, 6), np.int64)
    ids[0] = row_a; mask[0] = 1
    ids[1, 3:] = row_b; mask[1, 3:] = 1
    out = np.asarray(model.generate(
        P.to_tensor(ids, "int32"), max_new_tokens=4,
        attention_mask=P.to_tensor(mask, "int32"))._value)
    np.testing.assert_array_equal(out[0, 6:], ref_a)
    np.testing.assert_array_equal(out[1, 6:], ref_b)


def test_generate_left_padded_gqa_llama():
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    P.seed(23)
    cfg = LlamaConfig(vocab_size=71, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64,
                      ffn_hidden=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(3)
    row = rs.randint(1, cfg.vocab_size, (4,))
    ref = np.asarray(model.generate(
        P.to_tensor(row[None], "int32"), max_new_tokens=3)._value)[0, 4:]
    ids = np.zeros((2, 7), np.int64)
    mask = np.zeros((2, 7), np.int64)
    ids[0, 3:] = row; mask[0, 3:] = 1
    ids[1] = rs.randint(1, cfg.vocab_size, (7,)); mask[1] = 1
    out = np.asarray(model.generate(
        P.to_tensor(ids, "int32"), max_new_tokens=3,
        attention_mask=P.to_tensor(mask, "int32"))._value)
    np.testing.assert_array_equal(out[0, 7:], ref)


def test_generate_left_padded_learned_positions():
    """Non-rope GPT (learned wpe positions): the per-row position shift in
    GPTModel.forward must make padded rows match solo generation."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(27)
    cfg = GPTConfig(vocab_size=67, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, use_rope=False)
    model = GPTForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(6)
    row = rs.randint(1, cfg.vocab_size, (3,))
    ref = np.asarray(model.generate(
        P.to_tensor(row[None], "int32"), max_new_tokens=4)._value)[0, 3:]
    ids = np.zeros((2, 6), np.int64); mask = np.zeros((2, 6), np.int64)
    ids[0, 3:] = row; mask[0, 3:] = 1
    ids[1] = rs.randint(1, cfg.vocab_size, (6,)); mask[1] = 1
    out = np.asarray(model.generate(
        P.to_tensor(ids, "int32"), max_new_tokens=4,
        attention_mask=P.to_tensor(mask, "int32"))._value)
    np.testing.assert_array_equal(out[0, 6:], ref)


def test_generate_rejects_bad_masks():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    cfg = GPTConfig(vocab_size=31, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = P.to_tensor(np.ones((1, 4), np.int64), "int32")
    with pytest.raises(ValueError, match="LEFT-padded"):
        model.generate(ids, max_new_tokens=2,
                       attention_mask=P.to_tensor(
                           np.array([[1, 1, 1, 0]]), "int32"))
    with pytest.raises(ValueError, match="contiguous"):
        model.generate(ids, max_new_tokens=2,
                       attention_mask=P.to_tensor(
                           np.array([[1, 0, 1, 1]]), "int32"))


@pytest.mark.slow
def test_beam_search_beats_or_equals_greedy():
    """num_beams=1 == greedy exactly; wider beams find a sequence whose
    total log-prob is >= greedy's (the point of beam search)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(31)
    cfg = GPTConfig(vocab_size=43, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=64, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt_np = np.array([[7, 9, 11]])
    prompt = P.to_tensor(prompt_np, "int32")

    greedy = np.asarray(model.generate(prompt, max_new_tokens=5)._value)
    beam1 = np.asarray(model.generate(prompt, max_new_tokens=5,
                                      num_beams=1)._value)
    np.testing.assert_array_equal(greedy, beam1)

    beam4 = np.asarray(model.generate(prompt, max_new_tokens=5,
                                      num_beams=4)._value)
    assert beam4.shape == greedy.shape

    def seq_logprob(full):
        ids = P.to_tensor(full[:, :-1], "int32")
        logits = np.asarray(model(ids)._value, np.float32)
        lp = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), -1))
        tot = 0.0
        for t in range(prompt_np.shape[1] - 1, full.shape[1] - 1):
            tot += lp[0, t, full[0, t + 1]]
        return tot

    assert seq_logprob(beam4) >= seq_logprob(greedy) - 1e-4


def test_beam_search_eos_and_errors():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(33)
    cfg = GPTConfig(vocab_size=29, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt = P.to_tensor(np.array([[1, 2]]), "int32")
    out = np.asarray(model.generate(prompt, max_new_tokens=6, num_beams=3,
                                    eos_token_id=5)._value)
    assert out.shape[1] <= 8
    gen = out[0, 2:]
    if (gen == 5).any():  # once eos appears, only eos follows (pool tail)
        first = int(np.argmax(gen == 5))
        assert (gen[first:] == 5).all()
    with pytest.raises(ValueError, match="do_sample"):
        model.generate(prompt, max_new_tokens=2, num_beams=2,
                       do_sample=True)


def test_beam_search_keeps_finished_hypothesis():
    """A hypothesis that ends with eos must stay selectable even when live
    continuations out-score it in the raw beam (finished pool, r3 review
    finding): with a length_penalty strongly favoring short outputs, a
    finished short hypothesis must win over full-length live beams when
    its normalized score is higher."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(37)
    cfg = GPTConfig(vocab_size=23, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=32, use_rope=True)
    model = GPTForCausalLM(cfg)
    model.eval()
    prompt = P.to_tensor(np.array([[1, 2, 3]]), "int32")
    # pick the greedy second token as eos so SOME beam finishes early
    base = np.asarray(model.generate(prompt, max_new_tokens=2)._value)
    eos = int(base[0, 4])
    out = np.asarray(model.generate(
        prompt, max_new_tokens=8, num_beams=4, eos_token_id=eos,
        length_penalty=0.0)._value)
    gen = out[0, 3:]
    if (gen == eos).any():
        first = int(np.argmax(gen == eos))
        assert (gen[first:] == eos).all()


def test_fused_head_ce_matches_unfused():
    """cfg.fused_head_ce + GPTPretrainingCriterion(model=...): the
    projection fuses into the chunked CE (no [B,S,V] logits). Losses and
    parameter updates (incl. the tied embedding, which now gets its
    head-side gradient through the fused VJP) must match the unfused
    path step for step."""
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    kw = dict(vocab_size=317, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=32, dropout=0.0)
    losses = {}
    for fused in (False, True):
        topology.reset_topology()
        strategy = fleet.DistributedStrategy()
        strategy.hybrid_configs = {
            "dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
            "sep_degree": 1, "sharding_degree": 1}
        fleet.init(is_collective=True, strategy=strategy)
        P.seed(7)
        model = GPTForCausalLM(GPTConfig(fused_head_ce=fused, **kw))
        crit = GPTPretrainingCriterion(model=model if fused else None)
        dm = fleet.distributed_model(model)
        opt = fleet.distributed_optimizer(
            P.optimizer.SGD(parameters=model.parameters(),
                            learning_rate=0.1))
        step = dm.build_train_step(opt, crit)
        rs = np.random.RandomState(0)
        ids = P.to_tensor(rs.randint(0, 317, (2, 32)), "int32")
        lab = P.to_tensor(rs.randint(0, 317, (2, 32)), "int32")
        losses[fused] = [float(step(ids, lab)) for _ in range(3)]
    np.testing.assert_allclose(losses[False], losses[True], rtol=2e-5)


def _head_ce_case(b, s, v, hd, ignore, seed=5):
    """Hidden states, a [V, Hd] head and labels; `ignore` picks the rows
    that carry `ignore_index`."""
    rs = np.random.RandomState(seed)
    h = rs.randn(b, s, hd).astype(np.float32)
    w = (0.3 * rs.randn(v, hd)).astype(np.float32)
    labels = rs.randint(0, v, (b, s)).astype(np.int32)
    if ignore == "scattered":
        labels[rs.rand(b, s) < 0.3] = -100
    elif ignore == "first_slice":      # every row of the scan's first slice
        from paddle_tpu.models.gpt import _token_slices
        labels[:, :_token_slices(b, s, v)[0]] = -100
    elif ignore == "all":
        labels[:] = -100
    return h, w, labels


def _unfused_head_ce(h, w, labels):
    """The plain path: the [B, S, V] logits, then `F.cross_entropy`."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.nn.functional as F

    def loss(hh, ww):
        logits = jnp.einsum("bsh,vh->bsv", hh, ww,
                            precision=jax.lax.Precision.HIGHEST)
        return F.cross_entropy(P.Tensor(logits), P.Tensor(jnp.asarray(labels)),
                               reduction="mean", ignore_index=-100)._value

    return jax.value_and_grad(loss, (0, 1))(jnp.asarray(h), jnp.asarray(w))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,ignore", [
    ((2, 32, 317, 16), None),           # V no multiple of 128, 16 slices
    ((2, 32, 317, 16), "scattered"),
    ((2, 32, 317, 16), "first_slice"),  # a slice with no valid row
    ((2, 65, 317, 16), "scattered"),    # 65 = 5 x 13: the last slice padded
    ((3, 31, 200, 8), None),            # a prime length: slices of one
    ((1, 48, 1031, 24), "scattered"),   # batch of one, V prime
    ((2, 32, 317, 16), "all"),          # nothing valid: loss 0, no NaN
])
def test_fused_linear_ce_token_scan_matches_unfused_head(shape, ignore,
                                                         dtype):
    """Loss, dh and dW of the sequence scan against the unfused head +
    `F.cross_entropy`: to 1e-5 in float32; in bfloat16 (what amp hands
    it) to the rounding of `d` and of the two results, 2**-8 each."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import _fused_linear_ce

    h, w, labels = _head_ce_case(*shape, ignore)
    h, w = jnp.asarray(h, dtype), jnp.asarray(w, dtype)

    def fused(hh, ww):
        total, count = _fused_linear_ce(hh, ww, jnp.asarray(labels), -100)
        return total / jnp.maximum(count, 1.0)

    got, (dh, dw) = jax.value_and_grad(fused, (0, 1))(h, w)
    assert dh.dtype == h.dtype and dw.dtype == w.dtype
    want, (rh, rw) = _unfused_head_ce(h.astype(jnp.float32),
                                      w.astype(jnp.float32), labels)
    if ignore == "all":
        assert float(got) == 0.0 and not np.asarray(dh, np.float32).any() \
            and not np.asarray(dw, np.float32).any()
        return
    tol = 1e-5 if dtype == "float32" else 3 * 2.0 ** -8
    np.testing.assert_allclose(float(got), float(want), rtol=1e-5)
    for a, r in ((dh, rh), (dw, rw)):
        r = np.asarray(r)
        np.testing.assert_allclose(np.asarray(a, np.float32), r, rtol=tol,
                                   atol=tol * np.abs(r).max())
    # the evaluation loss (no gradient asked) is the same number
    assert abs(float(fused(h, w)) - float(got)) <= 1e-6 * abs(float(got))


@pytest.mark.parametrize("bsv,slices", [
    ((32, 1024, 50304), (64, 16)),     # the three cells: 1/16 of the tokens
    ((16, 2048, 50304), (128, 16)),
    ((2, 8192, 25024), (512, 16)),
    ((64, 2048, 50304), (32, 64)),     # 1/16 would pass the byte cap
    ((1, 1000, 50304), (50, 20)),      # the largest divisor under the aim
    ((2, 65, 317), (4, 17)),           # none near it: 17 x 4 = 68, padded
    ((3, 31, 200), (1, 31)),
    ((4, 8, 317), (1, 8)),             # fewer than 16 positions
])
def test_token_slices_come_from_the_shape(bsv, slices):
    from paddle_tpu.models.gpt import _token_slices

    assert _token_slices(*bsv) == slices


@pytest.mark.parametrize("differentiated,products", [(True, 3), (False, 1)])
def test_fused_linear_ce_runs_three_products_and_counts_them(differentiated,
                                                             products):
    """Differentiated, the scan's body holds the logits, dh and dW
    products and the backward rule none (nothing is replayed); the plain
    call holds the logits product alone.  The trace-time counters say
    how the scan was cut and where the gradient was formed."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models.gpt import _fused_linear_ce
    from paddle_tpu.observability import metrics

    h, w, labels = _head_ce_case(2, 32, 317, 16, "scattered")

    def fused(hh, ww):
        with jax.named_scope("head_ce"):
            total, count = _fused_linear_ce(hh, ww, jnp.asarray(labels), -100)
        return total / jnp.maximum(count, 1.0)

    was = metrics.enabled()
    metrics.enable()
    before = dict(metrics.snapshot()["counters"])
    try:
        f = jax.value_and_grad(fused, (0, 1)) if differentiated else fused
        jaxpr = jax.make_jaxpr(f)(jnp.asarray(h), jnp.asarray(w))
        now = metrics.snapshot()["counters"]
    finally:
        if not was:
            metrics.disable()
    def eqns(jp, scope=""):    # every equation with its whole name stack
        for e in jp.eqns:
            inner = f"{scope}/{e.source_info.name_stack}"
            yield e, inner
            for sub in jax.core.jaxprs_in_params(e.params):
                yield from eqns(sub, inner)

    found = list(eqns(jaxpr.jaxpr))
    scans = [e for e, _ in found if e.primitive.name == "scan"]
    assert len(scans) == 1
    assert str(scans[0].params["jaxpr"]).count("dot_general") == products
    dots = [scope for e, scope in found if e.primitive.name == "dot_general"]
    assert len(dots) == products and all("head_ce" in d for d in dots)
    delta = {k: c - before.get(k, 0) for k, c in now.items()
             if k.startswith("head_ce.") and c - before.get(k, 0)}
    want = {"head_ce.scan{axis=tokens,chunks=16}": 1}
    if differentiated:
        want["head_ce.grad{where=forward}"] = 1
    assert delta == want


@pytest.mark.parametrize("family", ["gpt_tied", "afmoe_untied"])
def test_fused_head_ce_gradients_match_unfused_model(family):
    """The tied GPT head (the embedding gets its head-side gradient from
    the scan's dW carry) and afmoe's untied [V, Hd] head: every
    parameter's gradient as the unfused model's, eager tape."""
    from paddle_tpu.models import afmoe
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    rs = np.random.RandomState(2)
    labels = rs.randint(0, 317, (2, 32))
    labels[:, :3] = -100
    ids = P.to_tensor(rs.randint(0, 317, (2, 32)), "int32")
    grads = {}
    for fused in (False, True):
        P.seed(11)
        if family == "gpt_tied":
            model = GPTForCausalLM(GPTConfig(
                vocab_size=317, hidden_size=32, num_layers=1, num_heads=2,
                max_seq_len=32, dropout=0.0, fused_head_ce=fused))
        else:
            model = afmoe.AfmoeForCausalLM(afmoe.afmoe_tiny(
                vocab_size=317, fused_head_ce=fused))
        model.train()
        crit = GPTPretrainingCriterion(model=model if fused else None,
                                       fused=fused)
        loss = crit(model(ids), P.to_tensor(labels, "int32"))
        loss.backward()
        grads[fused] = (float(loss), {
            n: np.asarray(p.grad._value) for n, p in model.named_parameters()
            if p.grad is not None})
    assert abs(grads[True][0] - grads[False][0]) < 1e-5 * grads[False][0]
    assert grads[True][1].keys() == grads[False][1].keys()
    for name, g in grads[False][1].items():
        np.testing.assert_allclose(grads[True][1][name], g, rtol=1e-4,
                                   atol=1e-5 * np.abs(g).max(), err_msg=name)


def test_fused_head_ce_mismatched_criterion_raises():
    """A fused_head_ce model paired with a PLAIN criterion must fail
    loudly — hidden states silently scored as logits was the failure
    mode (r4 review)."""
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    P.seed(0)
    cfg = GPTConfig(vocab_size=256, hidden_size=32, num_layers=1,
                    num_heads=2, max_seq_len=16, fused_head_ce=True)
    model = GPTForCausalLM(cfg)
    model.train()
    ids = P.randint(0, 256, [2, 16])
    out = model(ids)
    crit = GPTPretrainingCriterion()  # no model= — mismatch
    with pytest.raises(RuntimeError, match="fused_head_ce"):
        crit(out, ids)
    # fused=False with model= is the same mismatch (r4 ADVICE): hidden
    # states would silently fall through to the plain-CE path
    crit2 = GPTPretrainingCriterion(model=model, fused=False)
    with pytest.raises(RuntimeError, match="fused_head_ce"):
        crit2(out, ids)


@pytest.mark.slow
def test_fused_head_ce_cuts_xla_temp_buffers():
    """The memory claim behind cut-CE (VERDICT r4 Next #4), chip-free:
    XLA's buffer assignment for the compiled train step must shrink by at
    least the [B,S,V] logits+cotangent when the head fuses into the
    chunked CE. tools/memory_report.py prints the full table."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from memory_report import step_memory

    base = dict(vocab_size=50304, hidden_size=64, num_layers=2,
                num_heads=4, max_seq_len=128, dropout=0.0)
    batch, seq = 4, 128
    plain = step_memory(dict(base, fused_head_ce=False), batch, seq)
    fused = step_memory(dict(base, fused_head_ce=True), batch, seq)
    # [B,S,V] f32 logits alone: 4*128*50304*4 ≈ 98 MiB. XLA keeps parts
    # of the logits chain in bf16, so demand 0.75x of the f32 size —
    # still only satisfiable if the [B,S,V] buffers actually vanished
    # (measured: 95 MiB saved here; 1,809 MiB at B8 S512 h256, PERF.md)
    logits_mb = batch * seq * 50304 * 4 / 2**20
    assert plain["temp_mb"] - fused["temp_mb"] >= 0.75 * logits_mb, (
        plain, fused)


@pytest.mark.slow
def test_train_step_has_no_f32_operand_gemms():
    """MFU guard (tools/hlo_audit.py): every dot in the bf16 AMP train
    step must take bf16 OPERANDS (f32 accumulation via
    preferred_element_type is the full-rate MXU mode; an f32-operand dot
    runs at quarter rate). The round-5 audit measured 40/40 bf16 — this
    pins it."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    from hlo_audit import audit_hlo, train_step_hlo

    report = audit_hlo(train_step_hlo(batch=2, seq=256, layers=2))
    assert report["dot_counts"]["f32_operands"] == 0, report
    assert report["dot_counts"]["mixed"] == 0, report
    assert not report["big_non_bf16_dots"], report
    assert report["dot_counts"]["bf16_operands"] > 0, report


# =============================== ERNIE ===============================


def _ernie_batch(cfg, B=4, S=32, seed=0):
    rs = np.random.RandomState(seed)
    ids = rs.randint(1, cfg.vocab_size, (B, S))
    ids[:, -4:] = cfg.pad_token_id
    labels = np.full((B, S), -100)
    labels[:, 2:6] = rs.randint(1, cfg.vocab_size, (B, 4))
    nsp = rs.randint(0, 2, (B,))
    return ids, labels, nsp


def test_ernie_pretraining_overfits():
    """ERNIE encoder family (BASELINE config 4's named model): MLM+NSP
    objective over the nn.TransformerEncoder stack must optimize."""
    from paddle_tpu.models import (
        ErnieForPretraining, ErniePretrainingCriterion, ernie_tiny,
    )

    P.seed(0)
    cfg = ernie_tiny(dropout=0.0)
    m = ErnieForPretraining(cfg)
    crit = ErniePretrainingCriterion()
    ids_np, labels_np, nsp_np = _ernie_batch(cfg)
    ids = P.to_tensor(ids_np, "int32")
    labels = P.to_tensor(labels_np, "int64")
    nsp = P.to_tensor(nsp_np, "int64")
    opt = P.optimizer.AdamW(parameters=m.parameters(), learning_rate=5e-3)
    losses = []
    for _ in range(8):
        logits, nsp_logits = m(ids)
        loss = crit(logits, nsp_logits, labels, nsp)
        loss.backward()
        opt.step()
        opt.clear_grad()
        losses.append(float(np.asarray(loss._value)))
    assert losses[-1] < losses[0] * 0.7, losses
    # MLM-only mode (no NSP labels) returns just the masked-CE term of
    # the same total, so it is strictly below MLM+NSP
    solo = crit(logits, nsp_logits, labels)
    assert float(solo) < losses[-1] + 1e-6
    assert np.isfinite(float(solo))


def test_ernie_padding_tokens_do_not_leak():
    """The [B,S] 1/0 attention mask becomes a stop-gradient additive
    bias: changing a PADDING token's id must not change any real token's
    logits (the bias path the fused biased-flash tier streams on TPU)."""
    from paddle_tpu.models import ErnieForPretraining, ernie_tiny

    P.seed(1)
    cfg = ernie_tiny(dropout=0.0)
    m = ErnieForPretraining(cfg)
    m.eval()
    ids_np, _, _ = _ernie_batch(cfg, seed=2)
    mask = P.to_tensor((ids_np != cfg.pad_token_id).astype(np.float32))
    ids2_np = ids_np.copy()
    ids2_np[0, -1] = 7  # mutate a padded slot
    lg1, _ = m(P.to_tensor(ids_np, "int32"), attention_mask=mask)
    lg2, _ = m(P.to_tensor(ids2_np, "int32"), attention_mask=mask)
    real = np.s_[:, :-4]
    np.testing.assert_allclose(np.asarray(lg1._value)[real],
                               np.asarray(lg2._value)[real], atol=1e-4)
