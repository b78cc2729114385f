"""Single-chip GPT pretrain throughput benchmark.

Prints ONE JSON line (last line of stdout):
    {"metric", "value", "unit", "vs_baseline", ...}
Metric: tokens/sec/chip on a GPT-125M-shape training step (fwd+bwd+AdamW),
bf16 compute. vs_baseline = achieved MFU / 0.45 (the BASELINE.md north-star
MFU target; the reference publishes no absolute numbers — BASELINE.md).

Runs on the device jax finds and exits non-zero on any failure; it never
falls back to another backend on its own.  `--force-cpu` is the explicit
CPU proxy (tiny shapes, every row marked ``"degraded": true``).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np

# bf16 peak FLOP/s of one chip, keyed by jax's `device_kind`.  A TPU kind
# that is not listed is an error, not a default.
_PEAK_BF16_FLOPS = {
    "TPU v5 lite": 197e12,  # v5e: Google Cloud documentation, "TPU v5e"
}
_CPU_PROXY_PEAK = 1e12  # nominal; --force-cpu rows are degraded anyway


def _peak_flops() -> float:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return _CPU_PROXY_PEAK
    if dev.device_kind not in _PEAK_BF16_FLOPS:
        raise KeyError(
            f"no bf16 peak recorded for device_kind {dev.device_kind!r}: "
            "add it to bench._PEAK_BF16_FLOPS with its source")
    return _PEAK_BF16_FLOPS[dev.device_kind]

_TELEMETRY_FLAG = "--telemetry"


def _telemetry_requested() -> bool:
    return _TELEMETRY_FLAG in sys.argv[1:]


def _attach_telemetry():
    """Enable the observability stack for this bench process.  The
    metrics snapshot is embedded in the emitted bench JSON
    (`"telemetry"` key), so every BENCH_*.json line carries its own
    provenance: which flash tiers actually dispatched, autotune
    hits/misses, retraces, per-step walls — the antidote to round-5's
    "stale reused number with no provenance" headline."""
    from paddle_tpu import observability as obs

    obs.attach()
    return obs


def run_bench(degraded: bool = False, note: str = "",
              telemetry: bool = False) -> dict:
    import jax

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import (
        GPTConfig, GPTForCausalLM, GPTPretrainingCriterion,
    )

    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"

    # GPT-125M shape on TPU; tiny proxy on CPU so the script always
    # completes. fused_head_ce: the LM-head projection fuses into the
    # chunked CE — the [B,S,V] logits (~3.3 GB bf16 at batch 32, plus
    # their cotangent) never materialize; identical numerics (tested)
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024,
                        fused_head_ce=True)
        batch_candidates, seq, iters = [64, 32, 16, 8], 1024, 20
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128, fused_head_ce=True)
        batch_candidates, seq, iters = [2], 128, 3

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1, "pp_degree": 1,
                               "sep_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)

    trace_dir = os.environ.get("BENCH_XPROF_DIR")

    obs = timer = None
    if telemetry:
        obs = _attach_telemetry()

    rs = np.random.RandomState(0)
    tps = None
    model = opt = crit = step = ids = labels = loss = None
    last_exc = None
    for batch in batch_candidates:  # biggest batch that fits wins (MXU util)
        # release the previous attempt's device buffers BEFORE reallocating
        model = opt = crit = step = ids = labels = loss = None
        import gc

        gc.collect()
        try:
            # fresh model/opt/step per attempt: a failed donated step leaves
            # state unusable.  The StepTimer is fresh per attempt too —
            # a failed larger-batch attempt's walls must not pollute the
            # winning batch's telemetry summary (tokens_per_step would
            # misprice them)
            if obs is not None:
                timer = obs.StepTimer(run_id=f"bench_gpt125m_b{batch}",
                                      sink=os.environ.get("BENCH_STEP_LOG"))
            P.seed(0)
            inner = GPTForCausalLM(cfg)
            model = fleet.distributed_model(inner)
            opt = fleet.distributed_optimizer(
                P.optimizer.AdamW(parameters=model.parameters(),
                                  learning_rate=1e-4))
            crit = GPTPretrainingCriterion(model=inner)
            step = model.build_train_step(opt, crit, amp_dtype="bfloat16")
            ids = P.to_tensor(
                rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
            labels = P.to_tensor(
                rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
            # warmup/compile — two calls: the first call's inputs are fresh
            # device_puts; the second proves the steady-state executable is
            # reused (train_step pins state shardings so there is no
            # second-call retrace).  Under --telemetry the first wall is
            # the compile-ledger entry (trace+compile+step), and the
            # input upload bytes are the host->device transfer estimate.
            t_first = time.perf_counter()
            loss = step(ids, labels)
            loss.block_until_ready()
            if timer is not None:
                timer.tokens_per_step = batch * seq
                timer.record(time.perf_counter() - t_first,
                             compile_step=True,
                             transfer_bytes=2 * batch * seq * 4)
            loss = step(ids, labels)
            loss.block_until_ready()

            # multi-step program: all timed steps run inside ONE compiled
            # lax.scan, so per-dispatch host gaps are out of the loop
            # entirely
            losses = step.run_steps(ids, labels, repeat=iters)  # warmup
            float(np.asarray(losses._value[-1]))

            if trace_dir:
                jax.profiler.start_trace(trace_dir)
            try:
                # Timing: dispatch the N-step program once, then FETCH the
                # final loss: a D2H value read is a true synchronization,
                # and the last loss depends on every prior step's param
                # update, so the fetch waits for the whole scan.
                t0 = time.perf_counter()
                losses = step.run_steps(ids, labels, repeat=iters)
                final_loss = float(np.asarray(losses._value[-1]))
                dt = time.perf_counter() - t0
                if timer is not None:
                    # one compiled N-step scan: one record, walls
                    # divided per step
                    timer.record(dt, n_steps=iters)
            finally:
                if trace_dir:
                    jax.profiler.stop_trace()
            if not np.isfinite(final_loss):
                raise RuntimeError(f"non-finite loss {final_loss}")
            tokens = batch * seq * iters
            tps = tokens / dt
            break
        except Exception as e:
            last_exc = e
            print(f"batch={batch} failed ({type(e).__name__}: {e})",
                  file=sys.stderr)
    if tps is None:
        raise RuntimeError("all batch sizes failed") from last_exc

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_per_token = 6 * n_params  # fwd+bwd matmul flops
    peak = _peak_flops()
    mfu = tps * flops_per_token / peak
    result = {
        "metric": "gpt125m_train_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
    }
    peak_mem = P.device.max_memory_allocated()
    if peak_mem:
        result["peak_memory_bytes"] = int(peak_mem)
    if degraded or not on_tpu:
        result["degraded"] = True
    if note:
        result["note"] = note
    if timer is not None:
        # MFU rates use the same FLOPs accounting as the headline metric
        timer.flops_per_step = flops_per_token * batch * seq
        timer.peak_flops = peak
        # goodput partition (ISSUE 7): productive step wall vs lost
        # (compile/rollback/retry/drain) from the run's own step
        # records + flight events; gauges land in the metrics snapshot
        # below, rows are emitted for tools/perf_gate.py
        goodput_report = None
        try:
            goodput_report = obs.goodput.from_live(timer)
            obs.goodput.publish(goodput_report)
        except Exception as e:
            print(f"goodput-accounting-failed: {e}", file=sys.stderr)
        result["telemetry"] = {
            "metrics": obs.metrics.snapshot(),
            "step_stats": timer.summary(),
        }
        if goodput_report is not None:
            result["telemetry"]["goodput"] = goodput_report
            for row in obs.goodput.metric_rows(
                    goodput_report,
                    degraded=bool(degraded or not on_tpu)):
                _emit(row)
        # merged Perfetto timeline: the tracer buffer already correlates
        # compile spans (cost_analysis-annotated), flight instants, and
        # step frames — one export IS the merged trace (ISSUE 2
        # acceptance).  Opt-in via env so plain --telemetry runs stay
        # single-file JSON.
        trace_path = os.environ.get("BENCH_TRACE")
        if trace_path:
            try:
                result["trace_file"] = obs.trace.export(trace_path)
            except OSError as e:
                print(f"trace-export-failed: {e}", file=sys.stderr)
    return result


def _bench_vision_model(build_model, metric, flops_per_image,
                        batch_candidates, img_size=224, iters=10,
                        degraded=False) -> dict:
    """Shared secondary-bench body (BASELINE configs 1 and 5): image-model
    train step (fwd+bwd+optimizer, bf16 AMP), chained-fetch timing.
    degraded=True marks the emitted line (CPU-proxy trend data) and the
    caller is expected to shrink batch/iters accordingly."""
    import gc

    import jax

    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    rs = np.random.RandomState(0)
    last_exc = None
    for batch in batch_candidates:
        model = opt = crit = step = None
        gc.collect()
        try:
            P.seed(0)
            model = fleet.distributed_model(build_model())
            opt = fleet.distributed_optimizer(
                P.optimizer.Momentum(parameters=model.parameters(),
                                     learning_rate=1e-3, momentum=0.9))
            crit = P.nn.CrossEntropyLoss()
            step = model.build_train_step(opt, crit, amp_dtype="bfloat16")
            imgs = P.to_tensor(
                rs.randn(batch, 3, img_size, img_size).astype(np.float32))
            labels = P.to_tensor(rs.randint(0, 1000, (batch,)), "int32")
            # scanned multi-step program (one dispatch, repeat= avoids
            # stacking iters copies of the image batch); no single-step
            # warmup — only the scanned program is ever timed, so its
            # compile would be pure waste
            losses = step.run_steps(imgs, labels, repeat=iters)  # warmup
            final = float(np.asarray(losses._value[-1]))
            t0 = time.perf_counter()
            losses = step.run_steps(imgs, labels, repeat=iters)
            final = float(np.asarray(losses._value[-1]))
            dt = time.perf_counter() - t0
            if not np.isfinite(final):
                raise RuntimeError(f"non-finite loss {final}")
            ips = batch * iters / dt
            mfu = ips * flops_per_image / _peak_flops()
            result = {"metric": metric, "value": round(ips, 1),
                      "unit": "images/s",
                      "vs_baseline": round(mfu / 0.45, 4)}
            if degraded:
                result["degraded"] = True
            return result
        except Exception as e:
            last_exc = e
            print(f"{metric}: batch={batch} failed "
                  f"({type(e).__name__}: {e})", file=sys.stderr)
    return {"metric": metric, "value": 0.0, "unit": "images/s",
            "vs_baseline": 0.0, "degraded": True,
            "note": f"failed: {type(last_exc).__name__}: {last_exc}"}


def _bench_decode(degraded: bool) -> dict:
    """Serving decode throughput (VERDICT r3 Next #4): GPT-125M
    static-KV generate(), tokens/s at batch 8."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=512)
        B, S0, NEW = 8, 128, 128
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=64)
        B, S0, NEW = 2, 8, 8
    P.seed(0)
    model = GPTForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    rs = np.random.RandomState(0)
    prompt = P.to_tensor(rs.randint(0, cfg.vocab_size, (B, S0)), "int32")
    out = model.generate(prompt, max_new_tokens=NEW)  # compile+warm
    np.asarray(out._value)
    t0 = time.perf_counter()
    out = model.generate(prompt, max_new_tokens=NEW)
    np.asarray(out._value)
    dt = time.perf_counter() - t0
    result = {"metric": "gpt125m_decode_tokens_per_sec",
              "value": round(B * NEW / dt, 1), "unit": "tokens/s",
              # decode is HBM-bound: score vs streaming the bf16 weights
              # once per token at ~80% of v5e's ~819 GB/s
              "vs_baseline": round(
                  (sum(int(np.prod(p.shape)) for p in model.parameters())
                   * 2 * (NEW / dt) / 1e9) / (0.8 * 819), 4)}
    if degraded or not on_tpu:
        result["degraded"] = True
    return result


def _bench_serving_decode(degraded: bool) -> dict:
    """Multi-client continuous-batching decode (ISSUE 8): N concurrent
    sequences with STAGGERED arrival and MIXED prompt lengths stream
    through the paged-KV `InferenceEngine`; value = total generated
    tokens / wall from first submission to last completion.  The same
    run measures single-stream sequential `generate()` on the same
    model/prompts — the line carries that number and the batching
    speedup, so the claim "continuous batching beats the predictor-lock
    serving loop" ships with its own evidence."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=512)
        n_clients, new_tokens = 16, 96
        lens = (32, 64, 96, 128)
        # prefix_cache off: this row measures DECODE throughput; warm
        # -prefill compiles inside the timed burst would skew it (the
        # cache has its own serving_prefix_* rows)
        ecfg = EngineConfig(page_size=32, max_slots=8, decode_chunk=8,
                            max_seq_len=512, prefix_cache=False)
        stagger = 0.01
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128)
        n_clients, new_tokens = 8, 24
        lens = (4, 8, 12, 20)
        ecfg = EngineConfig(page_size=8, max_slots=4, decode_chunk=4,
                            max_seq_len=128, prefix_cache=False)
        stagger = 0.002
    P.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size,
                          (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_clients)]

    # single-stream sequential reference: the predictor-lock serving
    # model — one generate() at a time.  Warm each distinct prompt
    # shape first so compiles stay out of both timings.
    for s0 in sorted({p.size for p in prompts}):
        out = model.generate(P.to_tensor(
            prompts[[p.size for p in prompts].index(s0)][None, :],
            "int32"), max_new_tokens=new_tokens)
        np.asarray(out._value)
    t0 = time.perf_counter()
    seq_tokens = 0
    for p in prompts:
        out = model.generate(P.to_tensor(p[None, :], "int32"),
                             max_new_tokens=new_tokens)
        seq_tokens += np.asarray(out._value).shape[1] - p.size
    seq_dt = time.perf_counter() - t0
    seq_tps = seq_tokens / seq_dt

    # engine warm: compile the prefill buckets + the decode program
    engine = InferenceEngine(model, ecfg)
    engine.generate(prompts[:len(lens)], max_new_tokens=2)

    engine.start()
    handles = []

    t0 = time.perf_counter()
    for p in prompts:           # staggered arrival, mixed lengths
        handles.append(engine.submit(p, max_new_tokens=new_tokens))
        time.sleep(stagger)
    for h in handles:
        h.result(timeout=600.0)
    dt = time.perf_counter() - t0
    engine.stop()
    eng_tokens = sum(len(h.tokens) for h in handles)
    eng_tps = eng_tokens / dt

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    result = {
        "metric": "serving_decode_tokens_per_sec",
        "value": round(eng_tps, 1), "unit": "tokens/s",
        # aggregate decode is HBM-bound like the single-stream line:
        # score vs streaming the bf16 weights once per STEP (batching
        # amortizes the stream across the batch) at ~80% of v5e BW
        "vs_baseline": round(
            (n_params * 2 * (eng_tps / max(1, ecfg.max_slots)) / 1e9)
            / (0.8 * 819), 4),
        "sequential_tokens_per_sec": round(seq_tps, 1),
        "batching_speedup": round(eng_tps / seq_tps, 2),
        "clients": n_clients,
    }
    if degraded or not on_tpu:
        result["degraded"] = True
    return result


def _bench_quantized_decode(degraded: bool) -> list:
    """Quantized-decode tier rows (ISSUE 12): the SAME staggered
    multi-client burst through four engines over one model family —
    bf16 baseline, int8 weight-only, int8 KV pool, and draft-model
    speculative decoding — plus the single-stream sequential reference,
    all measured in the same run.  Emits one gateable row per tier
    carrying the same-run baselines, so every speedup claim ships with
    its own evidence.

    The spec-decode draft here is SYNTHETIC-AGREEING (upper bound): the
    draft is the target's first layer(s) and the target's extra layers
    have their residual projections zeroed, so target ≡ draft bit-exactly
    and every proposal is accepted — the row measures the MECHANICAL
    ceiling of the spec pipeline (pass overhead at acceptance 1.0), with
    `tokens_per_pass` reported so nothing hides.  Real-model acceptance
    depends on the trained draft and is a hardware-window measurement.
    """
    import jax

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        dims = dict(vocab_size=50304, hidden_size=768, num_heads=12,
                    max_seq_len=512)
        layers, draft_layers = 12, 2
        n_clients, new_tokens, spec_k = 16, 96, 4
        lens = (32, 64, 96, 128)
        # prefix_cache off: decode-tier rows, same rationale as
        # _bench_serving_decode
        ecfg = dict(page_size=32, max_slots=8, decode_chunk=8,
                    max_seq_len=512, prefix_cache=False)
        stagger = 0.01
    else:
        dims = dict(vocab_size=1024, hidden_size=128, num_heads=4,
                    max_seq_len=128)
        layers, draft_layers = 2, 1
        n_clients, new_tokens, spec_k = 8, 24, 4
        lens = (4, 8, 12, 20)
        ecfg = dict(page_size=8, max_slots=4, decode_chunk=4,
                    max_seq_len=128, prefix_cache=False)
        stagger = 0.002
    P.seed(0)
    model = GPTForCausalLM(GPTConfig(num_layers=layers, **dims))
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    # synthetic fully-agreeing draft: copy the shared prefix of the
    # target's weights, zero the target's EXTRA layers' residual
    # projections (out_proj/down_proj weight+bias) — those blocks become
    # exact identities, so target logits == draft logits bit-for-bit
    P.seed(0)
    draft = GPTForCausalLM(GPTConfig(num_layers=draft_layers, **dims))
    if on_tpu:
        draft.to(dtype="bfloat16")
    draft.eval()
    tstate = {n: p for n, p in model.named_parameters()}
    for name, p in draft.named_parameters():
        p.set_value(tstate[name]._value)
    for li in range(draft_layers, layers):
        blk = model.gpt.h[li]
        for lin in (blk.attn.out_proj, blk.mlp.down_proj):
            lin.weight.set_value(np.zeros(lin.weight.shape, np.float32))
            if lin.bias is not None:
                lin.bias.set_value(np.zeros(lin.bias.shape, np.float32))

    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, dims["vocab_size"],
                          (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_clients)]

    # single-stream sequential reference (the predictor-lock serving
    # model), warmed per prompt shape
    for s0 in sorted({p.size for p in prompts}):
        out = model.generate(P.to_tensor(
            prompts[[p.size for p in prompts].index(s0)][None, :],
            "int32"), max_new_tokens=new_tokens)
        np.asarray(out._value)
    t0 = time.perf_counter()
    seq_tokens = 0
    for p in prompts:
        out = model.generate(P.to_tensor(p[None, :], "int32"),
                             max_new_tokens=new_tokens)
        seq_tokens += np.asarray(out._value).shape[1] - p.size
    seq_tps = seq_tokens / (time.perf_counter() - t0)

    def engine_tps(tier_kw, draft_model=None):
        engine = InferenceEngine(
            model, EngineConfig(**ecfg, **tier_kw),
            draft_model=draft_model)
        engine.generate(prompts[:len(lens)], max_new_tokens=2)  # warm
        steps0 = engine.steps   # warm-up steps stay out of the ratio
        engine.start()
        handles = []
        t0 = time.perf_counter()
        for p in prompts:
            handles.append(engine.submit(p, max_new_tokens=new_tokens))
            time.sleep(stagger)
        for h in handles:
            h.result(timeout=600.0)
        dt = time.perf_counter() - t0
        engine.stop()
        toks = sum(len(h.tokens) for h in handles)
        return toks / dt, toks / max(1, engine.steps - steps0)

    bf16_tps, _ = engine_tps({})
    int8w_tps, _ = engine_tps({"weight_precision": "int8"})
    kv_tps, _ = engine_tps({"kv_precision": "int8"})
    spec_tps, tokens_per_pass = engine_tps(
        {"spec_tokens": spec_k}, draft_model=draft)

    rows = []
    for metric, tps, extra in (
            ("serving_decode_int8w_tokens_per_sec", int8w_tps, {}),
            ("serving_decode_kvint8_tokens_per_sec", kv_tps, {}),
            ("serving_decode_spec_tokens_per_sec", spec_tps, {
                "spec_tokens": spec_k,
                "tokens_per_pass": round(tokens_per_pass, 2),
                "draft_layers": draft_layers,
                "note": "synthetic fully-agreeing draft (acceptance "
                        "1.0 upper bound; pass overhead is what is "
                        "measured)"})):
        row = {
            "metric": metric,
            "value": round(tps, 1), "unit": "tokens/s",
            "bf16_engine_tokens_per_sec": round(bf16_tps, 1),
            "sequential_tokens_per_sec": round(seq_tps, 1),
            "speedup_vs_bf16_engine": round(tps / bf16_tps, 2)
            if bf16_tps > 0 else 0.0,
            "speedup_vs_sequential": round(tps / seq_tps, 2)
            if seq_tps > 0 else 0.0,
            "vs_baseline": 0.0,
        }
        row.update(extra)
        if degraded or not on_tpu:
            row["degraded"] = True
        rows.append(row)
    return rows


def _bench_prefix_cache(degraded: bool) -> list:
    """Shared-prefix serving workload (ISSUE 13): N requests over a
    small TENANT population — every tenant has a common system prompt,
    each request appends a unique user suffix — first through a
    prefix-cache-enabled engine, then the SAME requests through a
    cache-disabled engine built from the same model in the same run.
    Three gateable rows ship with their own evidence:

      * serving_prefix_cache_hit_rate        — admission hits / total
      * serving_ttft_warm_vs_cold_speedup    — mean cold TTFT / mean
        warm-HIT TTFT (per-request time to FIRST token, measured at the
        handle; compiles warmed out of both sides)
      * serving_prefill_tokens_saved_frac    — prompt tokens NOT
        re-prefilled / total prompt tokens

    CPU proxy numbers are degraded-marked; the RATIOS are the claim
    (the cache removes prefill compute on both platforms)."""
    import jax

    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        dims = dict(vocab_size=50304, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=512)
        page, sys_pages, n_tenants, n_reqs = 32, 8, 4, 24
        sfx_len, new_tokens = 17, 8
        ecfg = dict(page_size=page, max_slots=4, max_seq_len=512,
                    prefill_bucket=page)
    else:
        dims = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128)
        page, sys_pages, n_tenants, n_reqs = 8, 6, 4, 16
        sfx_len, new_tokens = 5, 4
        ecfg = dict(page_size=page, max_slots=4, max_seq_len=128,
                    prefill_bucket=page)
    P.seed(0)
    model = GPTForCausalLM(GPTConfig(**dims))
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    rs = np.random.RandomState(0)
    sys_len = page * sys_pages
    tenants = [rs.randint(0, dims["vocab_size"],
                          (sys_len,)).astype(np.int32)
               for _ in range(n_tenants)]
    reqs = [np.concatenate([
        tenants[i % n_tenants],
        rs.randint(0, dims["vocab_size"], (sfx_len,)).astype(np.int32)])
        for i in range(n_reqs)]
    # warmup tenant (same shapes, never measured): compiles the cold
    # prefill bucket, the warm (sb, npp) program, pack, and decode on
    # BOTH engines so no timed request pays a compile
    wt = rs.randint(0, dims["vocab_size"], (sys_len,)).astype(np.int32)
    warm_reqs = [np.concatenate([
        wt, rs.randint(0, dims["vocab_size"],
                       (sfx_len,)).astype(np.int32)])
        for _ in range(2)]

    def run(prefix_cache):
        eng = InferenceEngine(model, EngineConfig(
            **ecfg, prefix_cache=prefix_cache))
        for w in warm_reqs:
            eng.generate([w], max_new_tokens=new_tokens)
        eng.clear_prefix_cache()
        base = eng.prefix_cache_stats()
        eng.start()
        ttfts = []
        try:
            for p in reqs:
                t0 = time.perf_counter()
                h = eng.submit(p, max_new_tokens=new_tokens)
                it = h.stream(timeout=600.0)
                next(it)                     # block for the FIRST token
                ttfts.append((time.perf_counter() - t0,
                              h.cache_state))
                for _ in it:                 # drain the rest
                    pass
        finally:
            eng.stop()
        st = eng.prefix_cache_stats()
        eng.clear_prefix_cache()
        # delta vs the post-warmup ledger: only the measured burst
        st = {k: st[k] - base[k] if isinstance(st.get(k), (int, float))
              and isinstance(base.get(k), (int, float)) else st.get(k)
              for k in st}
        return ttfts, st

    warm_ttfts, wstats = run(True)
    cold_ttfts, _ = run(False)
    hits = sum(1 for _, c in warm_ttfts if c in ("hit", "partial"))
    hit_rate = hits / max(1, len(warm_ttfts))
    warm_hit_mean = float(np.mean([t for t, c in warm_ttfts
                                   if c in ("hit", "partial")] or [0.0]))
    cold_mean = float(np.mean([t for t, _ in cold_ttfts] or [0.0]))
    speedup = (cold_mean / warm_hit_mean) if warm_hit_mean > 0 else 0.0
    saved_frac = (wstats.get("prefill_tokens_saved", 0)
                  / max(1, wstats.get("prefill_tokens_total", 0)))
    shared = dict(
        tenants=n_tenants, requests=n_reqs, system_prompt_tokens=sys_len,
        suffix_tokens=sfx_len,
        cold_ttft_ms=round(float(cold_mean) * 1e3, 2),
        warm_hit_ttft_ms=round(float(warm_hit_mean) * 1e3, 2))
    rows = []
    for metric, value, unit in (
            ("serving_prefix_cache_hit_rate", round(hit_rate, 4),
             "frac"),
            ("serving_ttft_warm_vs_cold_speedup", round(speedup, 2),
             "x"),
            ("serving_prefill_tokens_saved_frac", round(saved_frac, 4),
             "frac")):
        row = {"metric": metric, "value": value, "unit": unit,
               "vs_baseline": 0.0}
        row.update(shared)
        if degraded or not on_tpu:
            row["degraded"] = True
        rows.append(row)
    return rows


def _bench_fleet_decode(degraded: bool) -> dict:
    """Horizontal serving scale-out (ISSUE 9, reworked under ISSUE 14):
    the `tools/loadgen.py` SHARED-PREFIX tenant workload — the same
    definition the surge chaos scenario drives — runs as an open-loop
    burst of /generate streams through the admission-aware `Router`
    over a TWO-replica `ReplicaFleet` (each replica a real paged-KV
    `InferenceEngine` with its prefix cache on, requests carrying
    `X-Prefix-Fingerprint` so prefix-AFFINITY routing is active);
    value = total generated tokens / wall.  The same run measures the
    same workload against ONE replica directly — the line carries that
    number and the fleet speedup, so the claim "a second replica buys
    real aggregate decode throughput" ships with its own evidence.
    Replica processes run the CPU proxy until per-replica chip-slice
    assignment lands, so the line is degraded-marked off-TPU either
    way."""
    from paddle_tpu.inference.fleet import ReplicaFleet
    from paddle_tpu.inference.serving import InferenceClient

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    n_reqs, new_tokens = 12, 24
    # 16-token system prompts = 2 full engine pages (page_size=8):
    # page-aligned by construction, so tenants share committed prefix
    # pages AND fingerprint alike (granule 16 — affinity active)
    workload = loadgen.SharedPrefixWorkload(
        seed=0, tenants=3, system_prompt_tokens=16,
        suffix_tokens=(3, 8), vocab=256, generate_frac=1.0,
        max_new_tokens=new_tokens)
    fleet = ReplicaFleet(num_replicas=2, kind="gpt",
                         launch_timeout=300, request_timeout=120.0)
    fleet.start()
    try:
        addrs = [info["address"] for info in
                 fleet.describe()["replicas"].values()]

        def burst(address):
            # a FRESH workload per burst: same seed → bit-identical
            # request specs against the single replica and the fleet
            # (the comparison is apples-to-apples by construction)
            wl = loadgen.SharedPrefixWorkload(
                seed=0, tenants=3, system_prompt_tokens=16,
                suffix_tokens=(3, 8), vocab=256, generate_frac=1.0,
                max_new_tokens=new_tokens)
            runner = loadgen.OpenLoopRunner(
                address, wl, timeout=300.0, max_retries=2,
                max_retry_wait=1.0)
            report = runner.run(
                schedule=wl.schedule_burst(n_reqs, window_s=0.25))
            return report.summary()

        # warm EVERY replica with EVERY request the schedule will send
        # (2 tokens each): compiles (all prefill buckets + the decode
        # program) stay out of both timings AND every tenant's prefix
        # pages are committed in every replica's cache BEFORE either
        # burst — without this the run ORDER biases the comparison
        # (the single burst would warm r0's prefix cache for the fleet
        # burst's bit-identical prompts).  Both bursts measure fully
        # warm serving.
        probe = [s for _, s in workload.schedule_burst(n_reqs, 0.25)]
        for addr in addrs:
            cli = InferenceClient(addr, timeout=300.0, retries=1)
            for s in probe:
                cli.generate(s["prompt"], max_new_tokens=2)
        single = burst(addrs[0])                 # one replica, direct
        via_fleet = burst(fleet.router.address)  # via the router
    finally:
        fleet.stop()
    single_tps = single["tokens_per_sec"]
    fleet_tps = via_fleet["tokens_per_sec"]
    result = {
        "metric": "fleet_decode_tokens_per_sec",
        "value": round(fleet_tps, 1), "unit": "tokens/s",
        # fraction of ideal linear scaling over the measured single
        # replica: 1.0 would be a perfect 2x
        "vs_baseline": round(fleet_tps / (2.0 * single_tps), 4)
        if single_tps > 0 else 0.0,
        "single_replica_tokens_per_sec": round(single_tps, 1),
        "fleet_speedup": round(fleet_tps / single_tps, 2)
        if single_tps > 0 else 0.0,
        "clients": n_reqs, "replicas": 2,
        "completed": [single["ok"], via_fleet["ok"]],
        "admitted_failures": [single["admitted_failures"],
                              via_fleet["admitted_failures"]],
        "workload": "loadgen shared-prefix (3 tenants, affinity on)",
    }
    result["degraded"] = True  # CPU-proxy replicas (see docstring)
    result["note"] = ("replicas share one CPU host on the proxy, so "
                      "scale-out cannot exceed 1x there; the line "
                      "exists for trend + router-overhead tracking "
                      "until per-replica chip slices land")
    return result


def _bench_fleet_cold_start(degraded: bool) -> dict:
    """Replica cold start (ISSUE 17, ROADMAP item 5's baseline): a REAL
    `add_replica()` on a running 1-replica toy fleet, measured by the
    lifecycle plane — value = spawn -> first_probe_up wall ms (what the
    autoscaler's predictive signal actually buys), with the per-phase
    breakdown (imports / weight_load / warmup+compile / announce /
    probe / other) riding the row so the cold-start PR knows WHERE the
    time goes before optimizing it.  Toy replicas on the CPU proxy:
    weight_load and compile are ~0 but ATTRIBUTED (named phases, not
    folded into `other`) — the row is degraded-marked either way."""
    import time as _time

    from paddle_tpu.inference.fleet import ReplicaFleet
    from paddle_tpu.observability import lifecycle as _lc

    fleet = ReplicaFleet(num_replicas=1, kind="toy", token_time=0.02,
                         service_time=0.02, max_slots=4,
                         launch_timeout=60, monitor_interval=0.1)
    fleet.start()
    try:
        rank = fleet.add_replica()
        if rank is None:
            raise RuntimeError("add_replica failed")
        deadline = _time.monotonic() + 30.0
        while _time.monotonic() < deadline and \
                fleet.router.routable_count() < 2:
            _time.sleep(0.05)
        if fleet.router.routable_count() < 2:
            raise RuntimeError("scale-up never became routable")
        rec = next((r for r in fleet.lifecycle.records()
                    if r.get("rank") == rank), None)
        if rec is None or "total_ms" not in rec:
            raise RuntimeError("no joined lifecycle record for the "
                               "scale-up")
        problems = _lc.validate_record(rec)
        observed = fleet.observed_spawn_ms()
    finally:
        fleet.stop()
    result = {
        "metric": "fleet_replica_cold_start_ms",
        "value": round(float(rec["total_ms"]), 1), "unit": "ms",
        "lower_better": True, "vs_baseline": 0.0,
        "phases_ms": {k: round(float(v), 2)
                      for k, v in sorted(rec["phases_ms"].items())},
        "observed_spawn_ms": (None if observed is None
                              else round(observed, 1)),
        "replicas": "1->2", "kind": "toy", "rank": rank,
        "record_problems": problems,
    }
    result["degraded"] = True  # CPU-proxy toy replica (see docstring)
    result["note"] = ("toy replica on the CPU proxy: spawn cost is "
                      "fork+imports; weight_load/compile ~0 but "
                      "attributed — the gpt-replica cold start adds "
                      "real weight_load + per-program compile_ms "
                      "(lifecycle.compile_ms) on top")
    return result


def _bench_qos_paid_p99(degraded: bool) -> dict:
    """Paid-tier isolation under surge (ISSUE 18):
    `serving_qos_paid_p99_ratio` = the paid class's ok-latency p99
    under a two-class (50/50 paid/free) surge, over the p99 of the
    IDENTICAL surge with no class differentiation — what a paid
    request pays for sharing the fleet with free traffic.  QoS holding
    means the ratio sits well under 1.0 (class-weighted admission
    sheds free first, strict-priority dequeue keeps paid moving); 1.0
    means the classes bought nothing.  Toy replicas on the CPU proxy —
    queueing dynamics, not chip throughput, are the claim — so the row
    is degraded-marked either way."""
    from paddle_tpu.inference.fleet import ReplicaFleet, toy_token

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "tools"))
    try:
        import loadgen
    finally:
        sys.path.pop(0)

    def surge(class_split):
        fleet = ReplicaFleet(num_replicas=1, kind="toy",
                             token_time=0.02, service_time=0.02,
                             max_slots=4, launch_timeout=60,
                             monitor_interval=0.1)
        fleet.start()
        try:
            wl = loadgen.SharedPrefixWorkload(
                seed=0, tenants=4, system_prompt_tokens=16,
                suffix_tokens=(3, 6), generate_frac=1.0,
                max_new_tokens=16, class_split=class_split)
            phases = loadgen.surge_phases(
                base_rps=3.0, surge_mult=8.0, warm_s=1.0,
                surge_s=4.0, cool_s=1.0)
            runner = loadgen.OpenLoopRunner(
                fleet.router.address, wl, phases, seed=0,
                expected_token=toy_token, timeout=30.0, max_retries=2)
            return runner.run().summary()
        finally:
            fleet.stop()

    two = surge({"paid": 0.5, "free": 0.5})   # classes on
    flat = surge(None)                        # same surge, no classes
    paid = (two.get("classes") or {}).get("paid") or {}
    free = (two.get("classes") or {}).get("free") or {}
    paid_p99 = (paid.get("latency_ms") or {}).get("p99")
    base_p99 = (flat.get("latency_ms", {}).get("generate") or {}).get(
        "p99")
    if not paid_p99 or not base_p99:
        raise RuntimeError(
            f"missing p99 (paid={paid_p99}, baseline={base_p99})")
    result = {
        "metric": "serving_qos_paid_p99_ratio",
        "value": round(paid_p99 / base_p99, 3), "unit": "ratio",
        "lower_better": True, "vs_baseline": 0.0,
        "paid_p99_ms": round(paid_p99, 1),
        "single_class_p99_ms": round(base_p99, 1),
        "paid_shed": paid.get("shed", 0),
        "free_shed": free.get("shed", 0),
        "paid_admitted_failures": paid.get("admitted_failures", 0),
        "workload": "loadgen shared-prefix surge (4 tenants, "
                    "50/50 paid/free vs single-class)",
    }
    result["degraded"] = True  # CPU-proxy toy replicas (see docstring)
    result["note"] = ("toy-replica queueing proxy: the ratio claims "
                      "scheduling policy, not chip throughput")
    return result


def _bench_stream_resume_gap(degraded: bool) -> dict:
    """Mid-stream failover seam cost (ISSUE 20):
    `serving_stream_resume_gap_ms` = router-measured wall between the
    last token a dying replica delivered and the survivor's first
    post-verify token (`router.resume_gap_ms` p50) — the one latency
    blip a client sees when a replica dies under it.  Measured for
    real: a 2-replica GPT fleet, a concurrent stream burst, kill -9 on
    the replica carrying the most streams one second in; the broken
    streams must finish OK via router resume and stay bit-exact
    against a local same-seed reference engine, or the row is a
    failure.  The gap is dominated by the survivor's tail re-prefill,
    so prefix caches are warmed first (the deployed shape).  GPT
    replicas on the CPU proxy: prefill walls are CPU walls, so the
    row is degraded-marked off-TPU."""
    import threading

    from paddle_tpu import observability as obs
    from paddle_tpu.inference.fleet import (
        ReplicaFleet, _build_gpt_engine,
    )
    from paddle_tpu.inference.serving import InferenceClient
    from paddle_tpu.observability import metrics as _metrics

    n_streams, new_tokens, attempts = 6, 72, 3
    was_enabled = _metrics.enabled()
    obs.attach(crash_hook=False)
    fleet = ReplicaFleet(num_replicas=2, kind="gpt", max_slots=4,
                         launch_timeout=300, request_timeout=120.0)
    fleet.start()
    try:
        rs = np.random.RandomState(0)
        sysp = rs.randint(0, 250, (16,)).tolist()
        prompts = [sysp + rs.randint(0, 250, (3 + i % 5,)).tolist()
                   for i in range(n_streams)]
        # the greedy-determinism oracle: same seed as the replicas
        ref = _build_gpt_engine(seed=0)
        exps = []
        for p in prompts:
            out = ref.generate([np.asarray(p, np.int32)],
                               max_new_tokens=new_tokens)[0]
            exps.append([int(t) for t in np.asarray(out)[len(p):]])
        # warm both replicas' prefix caches + compiles directly (the
        # resume leg's tail re-prefill rides the survivor's cache)
        for view in fleet.router.replica_views():
            cli = InferenceClient(view["address"], timeout=120,
                                  retries=1)
            for p in prompts:
                cli.generate(p, max_new_tokens=2)

        results = []
        lock = threading.Lock()
        delivered_counts = [0] * n_streams

        def _note_token(i):
            with lock:
                delivered_counts[i] += 1

        def one(i):
            cli = InferenceClient(fleet.router.address, timeout=120,
                                  retries=1)
            try:
                r = cli.generate(prompts[i],
                                 max_new_tokens=new_tokens,
                                 on_token=lambda _t: _note_token(i))
                row = (r["tokens"] == exps[i],
                       int(r.get("resumed", 0) or 0))
            except Exception:  # noqa: BLE001 — a broken stream is
                row = (False, 0)  # simply a failed measurement
            with lock:
                results.append(row)

        def busiest_rank(fallback):
            best, best_n = fallback, -1
            for v in fleet.router.replica_views():
                n = sum((v.get("inflight") or {}).values())
                if n > best_n:
                    best, best_n = int(v["id"][1:]), n
            return best

        exact = resumed = 0
        for attempt in range(attempts):
            results.clear()
            with lock:
                delivered_counts[:] = [0] * n_streams
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n_streams)]
            for t in threads:
                t.start()
                time.sleep(0.02)
            # wait until the burst is OBSERVABLY flowing (half the
            # streams past their second token) so the kill lands
            # MID-stream — a zero-delivered break would take the plain
            # failover path and measure nothing
            flow_deadline = time.monotonic() + 60.0
            while time.monotonic() < flow_deadline:
                with lock:
                    flowing = sum(1 for c in delivered_counts
                                  if c >= 2)
                if flowing >= n_streams // 2:
                    break
                time.sleep(0.02)
            fleet.kill_replica(busiest_rank(attempt % 2))
            for t in threads:
                t.join(timeout=240)
            fleet.wait_ready(n=2, timeout=120)
            exact = sum(1 for ok, _ in results if ok)
            resumed = sum(1 for _, r in results if r > 0)
            if resumed >= 1:
                break
        gap = _metrics.snapshot()["histograms"].get(
            "router.resume_gap_ms") or {}
        if resumed < 1 or not gap.get("count"):
            raise RuntimeError(
                f"no mid-stream resume landed in {attempts} attempts "
                f"(exact={exact}/{len(results)})")
        if exact != len(results):
            raise RuntimeError(
                f"resumed burst not bit-exact: {exact}/{len(results)}")
    finally:
        fleet.stop()
        if not was_enabled:
            obs.detach()
    result = {
        "metric": "serving_stream_resume_gap_ms",
        "value": round(gap["p50"], 1), "unit": "ms",
        "lower_better": True, "vs_baseline": 0.0,
        # seam-blip noise (scheduler + respawn timing) swamps small
        # deltas; gate on real regressions, not jitter
        "tolerance": 1.0,
        "resumes": int(gap["count"]),
        "gap_p95_ms": round(gap.get("p95", gap["p50"]), 1),
        "streams": n_streams, "resumed_streams": resumed,
        "bit_exact": exact,
        "workload": "2-replica gpt fleet, kill -9 mid-burst, "
                    "router resume (shared 16-token prefix)",
    }
    result["degraded"] = True  # CPU-proxy gpt replicas (see docstring)
    result["note"] = ("gpt replicas on the CPU proxy: the gap is "
                      "CPU re-prefill wall; trend-only until "
                      "per-replica chip slices land")
    return result


def _multichip_sharded_probe() -> None:
    """``--multichip-sharded-probe`` (run in a SUBPROCESS on a forced
    8-virtual-device CPU mesh): train a tiny GPT under the default
    multi-chip configuration — dp=8, fleet ``sharding_degree`` wiring,
    auto ZeRO-1 (ISSUE 11) — and print ONE JSON line of dryrun
    evidence: scanned-step throughput, the real sharded-placement proof
    (largest parameter's full/shard byte ratio, must equal dp), and the
    PT403 replicated-argument audit of the lowered program (must be
    ~zero).  This is the MULTICHIP placement proof bench.py can emit
    without a hardware window."""
    from paddle_tpu.backend_guard import force_cpu_mesh

    force_cpu_mesh(8)

    import paddle_tpu as P
    from paddle_tpu.analysis.perf_audit import (
        build_default_multichip_step, replicated_args,
    )
    from paddle_tpu.models.gpt import GPTConfig

    # the SAME configuration the static audit gates (one definition of
    # "default multi-chip" — perf_audit.build_default_multichip_step),
    # at a slightly larger proxy so the throughput trend means something
    cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, fused_head_ce=True)
    step, cfg = build_default_multichip_step(model_cfg=cfg, dp=8)
    batch, seq, iters = 16, 128, 4
    rs = np.random.RandomState(0)
    ids = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    labels = P.to_tensor(
        rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    losses = step.run_steps(ids, labels, repeat=iters)  # warm/compile
    float(np.asarray(losses._value[-1]))
    t0 = time.perf_counter()
    losses = step.run_steps(ids, labels, repeat=iters)
    final = float(np.asarray(losses._value[-1]))
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise RuntimeError(f"non-finite loss {final}")
    # placement proof 1: the biggest parameter really lives in dp shards
    big = max(step._state["params"].values(), key=lambda v: v.nbytes)
    ratio = big.nbytes / big.addressable_shards[0].data.nbytes
    # placement proof 2: PT403 over the lowered program — no big
    # replicated arguments survive the sharded weight update
    pt403 = replicated_args(step.lower(ids, labels).as_text())
    _emit({
        "probe": "multichip_sharded",
        "tokens_per_sec": round(batch * seq * iters / dt, 1),
        "param_shard_ratio": round(float(ratio), 2),
        "replicated_arg_mbytes": pt403["pt403_replicated_mbytes"],
        "replicated_arg_count": pt403["pt403_replicated_count"],
        "dp": 8, "sharding_stage": step.sharding_stage,
        "final_loss": round(final, 4),
    })


def _bench_multichip_sharded(degraded: bool) -> dict | None:
    """ZeRO-1 pod-training dryrun rows (ISSUE 11): spawn the
    8-virtual-device probe in a fresh subprocess (this process's jax is
    pinned to 1 device on the CPU path) and emit two rows —

      multichip_sharded_train_tokens_per_sec   CPU-proxy trend (always
                                               degraded-marked: 8
                                               virtual devices share
                                               one host's cores)
      multichip_sharded_param_shard_ratio      the placement PROOF, not
                                               a speed number: largest
                                               param full/shard bytes,
                                               8.0 under ZeRO-1 over
                                               dp=8.  NOT degraded — a
                                               regression to a
                                               replicated update reads
                                               1.0 and fails the
                                               perf_gate baseline.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--multichip-sharded-probe"],
        capture_output=True, text=True, timeout=900, env=env)
    probe = None
    for line in reversed(r.stdout.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            probe = json.loads(line)
            break
    if probe is None:
        raise RuntimeError(
            f"probe produced no JSON (rc={r.returncode}): "
            f"{r.stderr[-400:]}")
    _emit({
        "metric": "multichip_sharded_train_tokens_per_sec",
        "value": probe["tokens_per_sec"], "unit": "tokens/s",
        "vs_baseline": 0.0, "degraded": True,
        "dp": probe["dp"], "sharding_stage": probe["sharding_stage"],
        "note": "8-virtual-device CPU-mesh ZeRO-1 dryrun (trend only; "
                "virtual devices share one host's cores)",
    })
    row = {
        "metric": "multichip_sharded_param_shard_ratio",
        "value": probe["param_shard_ratio"], "unit": "x",
        "vs_baseline": round(probe["param_shard_ratio"] / probe["dp"], 4),
        "replicated_arg_mbytes": probe["replicated_arg_mbytes"],
        "replicated_arg_count": probe["replicated_arg_count"],
        "dp": probe["dp"], "sharding_stage": probe["sharding_stage"],
    }
    if degraded:
        # only mark the proof row degraded when the WHOLE bench run is a
        # forced fallback; the ratio itself is backend-independent
        row["note"] = "emitted during a degraded bench run"
    _emit(row)
    return row


def _bench_telemetry_overhead(degraded: bool) -> dict:
    """Telemetry-overhead honesty row (ISSUE 15): decode tokens/s with
    the FULL observability plane on (metrics registry + schema, flight,
    timeseries sampler at a fast interval, per-request timelines, and —
    ISSUE 16 — the per-tenant ledger, which the engine constructs
    whenever the registry is live, billing every decode token, slot-ms
    and page-second on this arm) vs
    the same engine shape with `PADDLE_TPU_METRICS=off` semantics
    (registry disabled, timelines off) — measured SAME-RUN on the same
    model and prompts.  Value = (off - on)/off, LOWER better, ~0 when
    the plane is free.  The observability stack must prove it is not
    the perf regression; this row makes a telemetry-induced decode tax
    fail `perf_gate` like any other regression."""
    import jax

    import paddle_tpu as P
    from paddle_tpu import observability as obs
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    from paddle_tpu.observability import metrics as _metrics
    from paddle_tpu.observability import timeseries as _tsmod

    on_tpu = jax.devices()[0].platform == "tpu"
    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=512)
        n_clients, new_tokens = 8, 64
        ecfg_kw = dict(page_size=32, max_slots=8, decode_chunk=8,
                       max_seq_len=512, prefix_cache=False)
    else:
        cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                        num_heads=4, max_seq_len=128)
        n_clients, new_tokens = 6, 24
        ecfg_kw = dict(page_size=8, max_slots=4, decode_chunk=4,
                       max_seq_len=128, prefix_cache=False)
    P.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        model.to(dtype="bfloat16")
    model.eval()
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, cfg.vocab_size, (8,)).astype(np.int32)
               for _ in range(n_clients)]

    ledger_armed = []  # the on-arm engine's ledger must actually exist

    def measure(telemetry_on: bool) -> float:
        prev_cap = os.environ.get("PADDLE_TPU_ITL_TIMELINE_CAP")
        sampler = None
        engine = None
        try:
            if telemetry_on:
                obs.attach(crash_hook=False)
            else:
                # the PADDLE_TPU_METRICS=off shape: registry AND span
                # tracer disabled (detach — a tracer left buffering
                # would depress the off baseline and underreport the
                # tax), timelines off — what a telemetry-averse
                # deployment would run
                obs.detach()
                os.environ["PADDLE_TPU_ITL_TIMELINE_CAP"] = "0"
            engine = InferenceEngine(model, EngineConfig(**ecfg_kw))
            if telemetry_on:
                ledger_armed.append(engine.tenant_ledger is not None)
            engine.generate(prompts[:1], max_new_tokens=2)  # warm
            if telemetry_on:
                sampler = _tsmod.TimeSeriesSampler(
                    names=("engine.tokens", "engine.batch_occupancy",
                           "engine.page_utilization"),
                    interval_s=0.05)
                sampler.start()
            engine.start()
            t0 = time.perf_counter()
            handles = [engine.submit(p, max_new_tokens=new_tokens)
                       for p in prompts]
            for h in handles:
                h.result(timeout=600.0)
            dt = time.perf_counter() - t0
            return sum(len(h.tokens) for h in handles) / dt
        finally:
            if engine is not None:
                engine.stop()  # a leaked loop thread would compete
                # with every later measurement
            if sampler is not None:
                sampler.stop()
            if prev_cap is None:
                os.environ.pop("PADDLE_TPU_ITL_TIMELINE_CAP", None)
            else:
                os.environ["PADDLE_TPU_ITL_TIMELINE_CAP"] = prev_cap

    was_enabled = _metrics.enabled()
    try:
        tps_on = measure(True)
        tps_off = measure(False)
    finally:
        # leave the stack as this bench found it even when a measure
        # raises (run_secondary_benches catches and keeps going — the
        # later benches must not inherit a flipped registry)
        if was_enabled:
            obs.attach(crash_hook=False)
        else:
            obs.detach()
    frac = (tps_off - tps_on) / tps_off if tps_off > 0 else 0.0
    result = {
        "metric": "serving_telemetry_overhead_frac",
        "value": round(max(frac, 1e-4), 4),  # >0 so --update keeps it
        "unit": "frac",
        "lower_better": True,
        # relative tolerance vs a small baseline fraction is noisy by
        # nature: a generous row-level tolerance keeps the gate about
        # real regressions (2x the baseline tax), not jitter
        "tolerance": 1.0,
        "tokens_per_sec_on": round(tps_on, 1),
        "tokens_per_sec_off": round(tps_off, 1),
        # honesty flag: the "on" arm really carried the tenant ledger
        # (False would mean this row measures less plane than deployed)
        "tenant_ledger_on": bool(ledger_armed and all(ledger_armed)),
        "vs_baseline": 0.0,
    }
    if degraded or not on_tpu:
        result["degraded"] = True
    return result


def run_secondary_benches(degraded: bool = False) -> None:
    """BASELINE configs 1 (ResNet50) and 5 (ViT attention shapes) plus
    the serving decode metric: emit one JSON line each BEFORE the primary
    GPT line (the driver reads the last line as the headline metric).
    With degraded=True (CPU proxy) the lines are emitted for trend data
    with shrunken batch/iters, marked accordingly (VERDICT r3 Weak #7:
    secondaries must not vanish on fallback). Every metric emits a line
    even on failure (zero value + note) — absence is the one outcome
    this function never produces."""
    from paddle_tpu.vision import models as V

    kw = {} if not degraded else {"iters": 2}  # CPU proxy: trend only
    # config 1: ResNet50 single-chip (PHI conv-kernel parity).
    # 224x224 fwd ~4.1 GFLOPs/img; train ~3x.
    _emit(_bench_vision_model(
        lambda: V.resnet50(num_classes=1000),
        "resnet50_train_images_per_sec_per_chip",
        flops_per_image=3 * 4.09e9, degraded=degraded,
        batch_candidates=[256, 128, 64] if not degraded else [2], **kw))
    # config 5: ViT-B/16 (flash-attention path at vision shapes).
    # 224x224 fwd ~17.6 GFLOPs/img; train ~3x.
    _emit(_bench_vision_model(
        lambda: V.vit_b_16(num_classes=1000),
        "vit_b16_train_images_per_sec_per_chip",
        flops_per_image=3 * 17.6e9, degraded=degraded,
        batch_candidates=[128, 64, 32] if not degraded else [2], **kw))
    # config 5 (second model family): Swin-T windowed attention.
    # 224x224 fwd ~4.5 GFLOPs/img; train ~3x.
    _emit(_bench_vision_model(
        lambda: V.swin_t(num_classes=1000),
        "swin_t_train_images_per_sec_per_chip",
        flops_per_image=3 * 4.5e9, degraded=degraded,
        batch_candidates=[128, 64, 32] if not degraded else [2], **kw))
    try:
        _emit(_bench_decode(degraded))
    except Exception as e:
        print(f"decode-bench-failed: {e}", file=sys.stderr)
        _emit({"metric": "gpt125m_decode_tokens_per_sec", "value": 0.0,
               "unit": "tokens/s", "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_serving_decode(degraded))
    except Exception as e:
        print(f"serving-decode-bench-failed: {e}", file=sys.stderr)
        _emit({"metric": "serving_decode_tokens_per_sec", "value": 0.0,
               "unit": "tokens/s", "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        for row in _bench_quantized_decode(degraded):
            _emit(row)
    except Exception as e:
        print(f"quantized-decode-bench-failed: {e}", file=sys.stderr)
        for metric in ("serving_decode_int8w_tokens_per_sec",
                       "serving_decode_kvint8_tokens_per_sec",
                       "serving_decode_spec_tokens_per_sec"):
            _emit({"metric": metric, "value": 0.0, "unit": "tokens/s",
                   "vs_baseline": 0.0, "degraded": True,
                   "note": f"failed: {type(e).__name__}: {e}"})
    try:
        for row in _bench_prefix_cache(degraded):
            _emit(row)
    except Exception as e:
        print(f"prefix-cache-bench-failed: {e}", file=sys.stderr)
        # failure emits degraded 0-rows, never absence (a vanished row
        # reads as "nothing regressed" to the gate)
        for metric in ("serving_prefix_cache_hit_rate",
                       "serving_ttft_warm_vs_cold_speedup",
                       "serving_prefill_tokens_saved_frac"):
            _emit({"metric": metric, "value": 0.0, "unit": "frac",
                   "vs_baseline": 0.0, "degraded": True,
                   "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_fleet_decode(degraded))
    except Exception as e:
        print(f"fleet-decode-bench-failed: {e}", file=sys.stderr)
        _emit({"metric": "fleet_decode_tokens_per_sec", "value": 0.0,
               "unit": "tokens/s", "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_telemetry_overhead(degraded))
    except Exception as e:
        print(f"telemetry-overhead-bench-failed: {e}", file=sys.stderr)
        # a failed measurement must not read as "telemetry is free":
        # the honesty row goes out degraded with a loud note, never
        # silently absent
        _emit({"metric": "serving_telemetry_overhead_frac",
               "value": 0.0, "unit": "frac", "lower_better": True,
               "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_fleet_cold_start(degraded))
    except Exception as e:
        print(f"fleet-cold-start-bench-failed: {e}", file=sys.stderr)
        # the cold-start row is ROADMAP item 5's baseline — a failed
        # measurement goes out degraded with a loud note, never absent
        _emit({"metric": "fleet_replica_cold_start_ms", "value": 0.0,
               "unit": "ms", "lower_better": True, "vs_baseline": 0.0,
               "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_qos_paid_p99(degraded))
    except Exception as e:
        print(f"qos-paid-p99-bench-failed: {e}", file=sys.stderr)
        # a failed measurement must not read as "QoS holds": the row
        # goes out degraded with a loud note, never silently absent
        _emit({"metric": "serving_qos_paid_p99_ratio", "value": 0.0,
               "unit": "ratio", "lower_better": True,
               "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _emit(_bench_stream_resume_gap(degraded))
    except Exception as e:
        print(f"stream-resume-gap-bench-failed: {e}", file=sys.stderr)
        # a failed measurement must not read as "failover is free":
        # the seam-cost row goes out degraded with a loud note, never
        # silently absent
        _emit({"metric": "serving_stream_resume_gap_ms", "value": 0.0,
               "unit": "ms", "lower_better": True,
               "vs_baseline": 0.0, "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})
    try:
        _bench_multichip_sharded(degraded)
    except Exception as e:
        print(f"multichip-sharded-bench-failed: {e}", file=sys.stderr)
        # a failed probe must not read as "sharding fine": the proof row
        # goes out degraded (never gates) with value 0, not silently
        # absent and not a fake healthy ratio
        _emit({"metric": "multichip_sharded_param_shard_ratio",
               "value": 0.0, "unit": "x", "vs_baseline": 0.0,
               "degraded": True,
               "note": f"failed: {type(e).__name__}: {e}"})


def _emit_secondaries_degraded() -> None:
    """CPU-proxy secondary lines; never raises (one shared call site for
    the two fallback paths in main())."""
    try:
        run_secondary_benches(degraded=True)
    except Exception as e:
        print(f"secondary-benches-failed: {e}", file=sys.stderr)


def _emit(result: dict) -> None:
    sys.stdout.flush()
    print(json.dumps(result))
    sys.stdout.flush()


_CACHE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          ".jax_cache")


def main() -> None:
    if "--multichip-sharded-probe" in sys.argv[1:]:
        # subprocess entry: forced 8-virtual-device CPU mesh, one JSON
        # line of ZeRO-1 dryrun evidence (see _multichip_sharded_probe)
        _multichip_sharded_probe()
        return
    from paddle_tpu.backend_guard import enable_compile_cache

    enable_compile_cache(_CACHE_DIR)
    telemetry = _telemetry_requested()
    if "--force-cpu" in sys.argv[1:]:
        from paddle_tpu.backend_guard import force_cpu_mesh

        force_cpu_mesh(1)
        result = run_bench(degraded=True, note="forced-cpu",
                           telemetry=telemetry)
        _emit_secondaries_degraded()
        _emit(result)
        return

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu":
        sys.exit(f"bench.py: needs a TPU, found platform {platform!r} "
                 "(the explicit CPU proxy is --force-cpu)")
    # one process holds the chip: everything runs here, and any failure
    # exits non-zero
    result = run_bench(telemetry=telemetry)
    run_secondary_benches()
    _emit(result)


if __name__ == "__main__":
    main()
