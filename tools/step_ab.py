"""Layout / fused-kernel A/B harness: one real train (or decode) step
per variant, perf_gate-compatible rows out.

Usage:
    python tools/step_ab.py [VARIANT] [--model {gpt,swin,resnet}]
                            [--smoke] [--decode] [--iters N]

VARIANT:
  * --model gpt (default): a flash attention layout —
    transpose|kv|flat|mh|auto (FLAGS_flash_layout). Default: transpose.
  * --model swin/resnet: `fused` (Pallas vision kernels on) or
    `fallback` (FLAGS_disable_pallas_window_attn/conv_norm) — the
    vision A/B axis is kernels-vs-composed-ops, not attention layout.

For gpt: batch 32, seq 1024, autotune off, 8 scanned steps; and prints ONE human line per program:
    AB layout=<variant> tokens/s=<v> mfu=<v> loss=<v>
followed by a perf_gate-compatible JSON row
    {"metric": "step_ab_<model>_<variant>_<program>", "value": ...}
(rows are marked degraded off-TPU, so a CPU run never gates against an
on-chip floor). Run once per variant and compare — the chained-kernel
slope A/B cannot decide layouts because back-to-back swapaxes cancel
inside the timing loop; only the real step sees the transpose cost
(docs/ATTENTION.md "The layout story").

--smoke: CPU mode at proxy shapes — the harness itself is exercised in
tier-1 (tests/test_step_ab.py) instead of only on the chip.
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _parse_args(argv):
    p = argparse.ArgumentParser(prog="step_ab", description=__doc__)
    p.add_argument("variant", nargs="?", default="transpose",
                   help="gpt: flash layout (transpose|kv|flat|mh|auto); "
                        "swin/resnet: fused|fallback")
    p.add_argument("--model", default="gpt",
                   choices=("gpt", "swin", "resnet"))
    p.add_argument("--smoke", action="store_true",
                   help="CPU proxy shapes (tier-1 harness smoke)")
    p.add_argument("--decode", action="store_true",
                   help="also A/B the gpt decode program")
    p.add_argument("--iters", type=int, default=None)
    return p.parse_args(argv)


def _emit(model, variant, program, value, unit, extra=None,
          degraded=False):
    row = {"metric": f"step_ab_{model}_{variant}_{program}",
           "value": round(value, 1), "unit": unit}
    if degraded:
        row["degraded"] = True
    if extra:
        row.update(extra)
    sys.stdout.flush()
    print(json.dumps(row))
    sys.stdout.flush()


def _on_accel():
    import jax

    return jax.devices()[0].platform == "tpu"


def _init_fleet():
    from paddle_tpu.distributed import fleet, topology

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    return fleet


def run_gpt_train(variant, smoke, iters=None):
    """One GPT train-step A/B point at FLAGS_flash_layout=variant.
    Returns (tokens_per_sec, mfu, final_loss)."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    _flags.set_flags({"FLAGS_use_autotune": 0})
    if smoke:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, fused_head_ce=True)
        batch, seq, iters = 2, 128, iters or 2
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024,
                        fused_head_ce=True)
        batch, seq, iters = 32, 1024, iters or 8
    fleet = _init_fleet()
    rs = np.random.RandomState(0)
    P.seed(0)
    inner = GPTForCausalLM(cfg)
    model = fleet.distributed_model(inner)
    opt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-4))
    step = model.build_train_step(opt, GPTPretrainingCriterion(model=inner),
                                  amp_dtype="bfloat16")
    ids = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    labels = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                         "int32")
    losses = step.run_steps(ids, labels, repeat=iters)
    final = float(np.asarray(losses._value[-1]))
    best = 0.0
    for _ in range(2 if smoke else 3):
        t0 = time.perf_counter()
        losses = step.run_steps(ids, labels, repeat=iters)
        final = float(np.asarray(losses._value[-1]))
        dt = time.perf_counter() - t0
        best = max(best, batch * seq * iters / dt)
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    mfu = best * 6 * n_params / 197e12
    return best, mfu, final


def run_gpt_decode(smoke):
    """Decode-program A/B point (static-KV generate) at the layout the
    caller already applied via FLAGS_flash_layout — the decode kernels'
    Q/O views ride the same flag. Returns tokens/s."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    if smoke:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=64)
        B, S0, NEW = 2, 8, 8
    else:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=512)
        B, S0, NEW = 8, 128, 128
    P.seed(0)
    model = GPTForCausalLM(cfg)
    if not smoke:
        model.to(dtype="bfloat16")
    model.eval()
    rs = np.random.RandomState(0)
    prompt = P.to_tensor(rs.randint(0, cfg.vocab_size, (B, S0)), "int32")
    out = model.generate(prompt, max_new_tokens=NEW)  # compile+warm
    np.asarray(out._value)
    t0 = time.perf_counter()
    out = model.generate(prompt, max_new_tokens=NEW)
    np.asarray(out._value)
    return B * NEW / (time.perf_counter() - t0)


def run_vision_train(model_name, variant, smoke, iters=None):
    """Vision train-step A/B point: `fused` (Pallas vision kernels
    eligible) vs `fallback` (kernels disabled). Returns images/s."""
    import numpy as np

    import paddle_tpu as P
    from paddle_tpu.core import flags as _flags
    from paddle_tpu.vision import models as V

    _flags.set_flags({"FLAGS_use_autotune": 0})
    if variant == "fallback":
        _flags.set_flags({"FLAGS_disable_pallas_window_attn": True,
                          "FLAGS_disable_pallas_conv_norm": True})
    if smoke:
        batch, img, iters = 2, 32, iters or 2
        build = (lambda: V.SwinTransformer(
            img_size=32, patch_size=4, embed_dim=24, depths=(2, 2),
            num_heads=(2, 4), window_size=4, num_classes=8)) \
            if model_name == "swin" else \
            (lambda: V.resnet18(num_classes=8))
    else:
        batch, img, iters = 64, 224, iters or 8
        build = (lambda: V.swin_t(num_classes=1000)) \
            if model_name == "swin" else \
            (lambda: V.resnet50(num_classes=1000))
    fleet = _init_fleet()
    rs = np.random.RandomState(0)
    P.seed(0)
    model = fleet.distributed_model(build())
    opt = fleet.distributed_optimizer(P.optimizer.Momentum(
        parameters=model.parameters(), learning_rate=1e-3, momentum=0.9))
    step = model.build_train_step(opt, P.nn.CrossEntropyLoss(),
                                  amp_dtype="bfloat16")
    imgs = P.to_tensor(rs.rand(batch, 3, img, img).astype(np.float32))
    labels = P.to_tensor(rs.randint(0, 8 if smoke else 1000, (batch,)),
                         "int32")
    losses = step.run_steps(imgs, labels, repeat=iters)  # warm
    float(np.asarray(losses._value[-1]))
    t0 = time.perf_counter()
    losses = step.run_steps(imgs, labels, repeat=iters)
    final = float(np.asarray(losses._value[-1]))
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise RuntimeError(f"non-finite loss {final}")
    return batch * iters / dt


def main(argv=None):
    args = _parse_args(list(sys.argv[1:] if argv is None else argv))
    variant = args.variant

    if args.model == "gpt":
        # validate BEFORE writing the flag: the flash dispatcher treats
        # an unknown layout as "transpose", so a typo'd variant would
        # silently measure the transpose core yet label the perf_gate
        # row with the bogus name — a mislabeled chip-window datapoint
        if variant not in ("transpose", "kv", "flat", "mh", "auto"):
            print(f"step_ab: gpt variant must be transpose|kv|flat|mh|"
                  f"auto, got {variant!r}", file=sys.stderr)
            return 1
        os.environ["FLAGS_flash_layout"] = variant
    elif variant not in ("fused", "fallback"):
        print(f"step_ab: vision variant must be fused|fallback, got "
              f"{variant!r}", file=sys.stderr)
        return 1

    from paddle_tpu.backend_guard import enable_compile_cache

    enable_compile_cache(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
    if args.smoke:
        from paddle_tpu.backend_guard import force_cpu_mesh

        force_cpu_mesh(1)
    degraded = not _on_accel()

    if args.model == "gpt":
        tps, mfu, loss = run_gpt_train(variant, args.smoke, args.iters)
        print(f"AB layout={variant} tokens/s={tps:.1f} mfu={mfu:.4f} "
              f"loss={loss:.4f}")
        _emit("gpt", variant, "train_tokens_per_sec", tps, "tokens/s",
              extra={"mfu": round(mfu, 4)}, degraded=degraded)
        if args.decode:
            dtps = run_gpt_decode(args.smoke)
            print(f"AB layout={variant} decode_tokens/s={dtps:.1f}")
            _emit("gpt", variant, "decode_tokens_per_sec", dtps,
                  "tokens/s", degraded=degraded)
    else:
        ips = run_vision_train(args.model, variant, args.smoke,
                               args.iters)
        print(f"AB layout={variant} model={args.model} "
              f"images/s={ips:.1f}")
        _emit(args.model, variant, "train_images_per_sec", ips,
              "images/s", degraded=degraded)
    return 0


if __name__ == "__main__":
    sys.exit(main())
