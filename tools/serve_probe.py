"""serve_probe — what the serving engine says about itself, on the chip.

GPT-3 XL (Brown et al. 2020, Table 2.1: 24 layers, 2048 wide) in bfloat16
behind `InferenceServer`, the engine as PR 32 ran it (32 slots, a 9216 MiB
page pool, pages of 16, prefill buckets of 256).  The heads DEPART from
that table: its XL row prints 24 heads of 128, which is 3072 and not the
2048 it states beside them; 16 heads of 128 is the layout that multiplies
out, and the one PR 32 ran.  A configuration file that cites the table
has to list the head count as changed.
Outside the benchmark: it judges nothing, it prints.  Two phases, one JSON
line each per seed:

    python tools/serve_probe.py --phase witness --seed 3300000101 --seeds 2
    python tools/serve_probe.py --phase gap --seed 3300000201 --seeds 10
    python tools/serve_probe.py --phase gap --tier int8 --seeds 5 ...
    JAX_PLATFORMS=cpu python tools/serve_probe.py --rehearse --phase ...

`witness` repeats PERF.md §7 item 0: 32 requests posted together, 28 more
one a second, outputs capped at 64, every request run to its end.  It
reads ONLY the program's own counters, histograms and spans: how long
`submit()` kept an arrival (`engine.submit_wait_ms`), how long the queue
did (`engine.admit_wait_ms`), how long the loop stood before the step
lock (`engine.lock_wait_ms{who=loop}`), whether any `schedule()` left a
request waiting beside a free slot, slots and live tokens a decode step
(against what the request sizes imply), the decode step's time and the
phase's tokens/s.  On a program without those histograms (the parent of
PR 33) it says so and times the public `engine.submit` with a stopwatch
instead.

`gap` serves 16 requests with `"logprobs": true`, closes the engine and
follows the delivered tokens with the benchmark's plain reference
(`benchmark/reference/gpt.py`: float32, `highest`, the same
bfloat16-rounded weights, teacher-forced): |lp_program - lp_reference|
per delivered position, in nats and in units of that row's standard
deviation `s`.  `--tier int8` serves through the engine's own
`weight_precision="int8"`; `--controls N` also puts, on the first N
seeds, the reference in the program's place with its matmul weights
rounded on the host through int8 and through float8
(`lax.reduce_precision`), one scale an output channel.

Weights, prompts and the order of requests come from the seed; the 256
(prompt, output) sizes are one constant multiset (lognormal, prompts
64-1792 around 1024, outputs 16-256 around 128).  Exits 1 without a TPU
unless `--rehearse` (a tiny model, no claim about any time).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.join(REPO, "benchmark")]

XL = {"vocab_size": 50304, "hidden_size": 2048, "num_hidden_layers": 24,
      "num_attention_heads": 16, "intermediate_size": 8192,
      "max_position_embeddings": 2048, "layer_norm_eps": 1e-5,
      "initializer_range": 0.02}
XL_ENGINE = {"page_size": 16, "max_slots": 32, "decode_chunk": 1,
             "prefill_bucket": 256, "pool_hbm_mb": 9216}
XL_SIZES = {"prompt": (1024, 0.8, 64, 1792), "output": (128, 0.7, 16, 256)}
TINY = {"vocab_size": 512, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 256,
        "max_position_embeddings": 256, "layer_norm_eps": 1e-5,
        "initializer_range": 0.02}
TINY_ENGINE = {"page_size": 16, "max_slots": 8, "decode_chunk": 1,
               "prefill_bucket": 32, "pool_hbm_mb": 2}
TINY_SIZES = {"prompt": (64, 0.8, 8, 200), "output": (16, 0.7, 4, 32)}
SIZES_SEED, N_SIZES = 20260932, 256


def emit(**fields):
    print(json.dumps(fields), flush=True)


def sizes(spec):
    """The constant multiset of (prompt, output) lengths."""
    rng = np.random.default_rng([SIZES_SEED, 0x51])

    def draw(median, sigma, lo, hi):
        x = np.exp(np.log(median) + sigma * rng.standard_normal(N_SIZES))
        return np.clip(np.rint(x), lo, hi).astype(np.int64)

    return np.stack([draw(*spec["prompt"]), draw(*spec["output"])], axis=1)


def requests(cfg, spec, seed, n, cap):
    """`n` requests of the seed: pairs of the multiset in the seed's order,
    ids uniform over the vocabulary, outputs capped at `cap`."""
    rng = np.random.default_rng([int(seed), 0x7b])
    pairs = sizes(spec)[rng.permutation(N_SIZES)[:n]]
    return [(rng.integers(0, cfg["vocab_size"], int(p)).astype(np.int32),
             int(min(o, cap))) for p, o in pairs]


# --- the program ---------------------------------------------------------

def build_model(cfg):
    """The model object, once: float32 as the framework builds it, cast
    to bfloat16.  Each seed's weights are loaded into it."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        max_seq_len=cfg["max_position_embeddings"],
        ffn_hidden=cfg["intermediate_size"],
        layer_norm_eps=cfg["layer_norm_eps"]))
    model.eval()
    model.bfloat16()
    return model


def load_weights(model, cfg, seed):
    """The benchmark's weights of the seed, every leaf rounded once to
    bfloat16 and held so."""
    import jax
    import jax.numpy as jnp
    from harness import weights

    tree = weights.make(cfg, seed, "bfloat16")     # float32 holding bf16
    tree = jax.jit(lambda t: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), t), donate_argnums=0)(tree)
    weights.load_into(model, tree)


def serve(model, engine_opts, tier=None):
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.inference.serving import InferenceServer

    # the deployment's settings: the edge hands every request to the
    # engine's own queue and lets it wait there
    os.environ["PADDLE_TPU_QUEUE_TIMEOUT"] = "3600"
    engine = InferenceEngine(model, EngineConfig(
        weight_precision=tier, **engine_opts))
    server = InferenceServer(engine=engine, port=0, request_timeout=3600.0,
                             queue_depth=4096).start()
    server.gen_admission.set_capacity(4096)
    return engine, server


def post_all(address, reqs, due_s, logprobs=False):
    """One client thread a request, started when it is due; returns the
    final records in the requests' order once every stream has ended."""
    from paddle_tpu.inference.serving import InferenceClient

    out, errors = [None] * len(reqs), []
    t0 = time.perf_counter()

    def one(i):
        wait = t0 + due_s[i] - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        try:
            cli = InferenceClient(address, timeout=3600.0, retries=0)
            kw = {"logprobs": True} if logprobs else {}
            out[i] = cli.generate(reqs[i][0], max_new_tokens=reqs[i][1],
                                  **kw)
        except Exception as e:
            errors.append(f"{i}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=one, args=(i,), daemon=True)
               for i in range(len(reqs))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(1800)
    if errors or any(o is None for o in out):
        raise RuntimeError(f"serve_probe: requests failed: {errors[:3]}")
    return out


def warm(engine, server, cfg, reqs, seed):
    """Every prefill bucket the requests can use and the decode program,
    through /generate, before anything is read."""
    step = engine.config.prefill_bucket
    # (a sequence evicted under page pressure prefills prompt +
    # generated again)
    top = max(p.size + o for p, o in reqs)
    rng = np.random.default_rng([int(seed), 0x7c])
    for b in range(step, -(-top // step) * step + 1, step):
        n = min(b, cfg["max_position_embeddings"] - 3)
        t0 = time.perf_counter()
        post_all(server.address, [(rng.integers(
            0, cfg["vocab_size"], n).astype(np.int32), 2)], [0.0])
        emit(phase="warm", bucket=b, seconds=time.perf_counter() - t0)


# --- the witness -----------------------------------------------------------

def _pcts(values):
    v = sorted(values)
    if not v:
        return None
    return {"n": len(v), "p50": statistics.median(v), "p100": v[-1],
            "over_50ms": sum(x > 50 for x in v),
            "over_1s": sum(x > 1000 for x in v)}


def _hist(snap, name, label=""):
    """A histogram of the program's: the label sets that hold `label`,
    merged."""
    hs = [h for k, h in snap["histograms"].items()
          if k.split("{")[0] == name and label in k and h["count"]]
    if not hs:
        return None
    return {"n": sum(h["count"] for h in hs),
            "p50": statistics.median(h["p50"] for h in hs),
            "p100": max(h["max"] for h in hs)}


def witness(engine, server, cfg, spec, seed, wave, later, rate, cap,
            stopwatch):
    from paddle_tpu.observability import metrics, trace, xla_cost

    reqs = requests(cfg, spec, seed, wave + later, cap)
    due = [0.0] * wave + [(i + 1) / rate for i in range(later)]
    metrics.reset()
    trace.clear()
    del stopwatch[:]
    compiled = xla_cost.process_compile_totals()
    post_all(server.address, reqs, due)
    compiled = {k: v - compiled[k]
                for k, v in xla_cost.process_compile_totals().items()
                if k.endswith("_n")}
    snap = metrics.snapshot()
    c = snap["counters"]
    ev = [e for e in trace.events() if e.get("ph") == "X"]

    def spans(name):
        return sorted((e for e in ev if e["name"] == name),
                      key=lambda e: e["ts"])

    gen, dec = spans("serving.generate"), spans("engine.decode")
    t0 = gen[0]["ts"]
    t_end = max(e["ts"] + e["dur"] for e in gen)
    last_arrival = max(e["ts"] for e in gen)
    slots = [e["args"]["batch"] for e in dec]
    full = [e for e, s in zip(dec, slots)
            if s == engine.config.max_slots]
    under = [e["ts"] for e, s in zip(dec, slots)
             if s < engine.config.max_slots and e["ts"] > full[0]["ts"]] \
        if full else []
    # a step's decode: dispatch (`engine.decode`) to the end of the
    # fetch and accept loop that wait for it (`engine.detokenize`)
    ends = [e["ts"] + e["dur"] for e in spans("engine.detokenize")]

    def step_ms(e):
        done = next((t for t in ends if t >= e["ts"] + e["dur"]), None)
        return None if done is None else (done - e["ts"]) / 1e3

    sched = [e["args"] for e in spans("engine.schedule")]
    steps = c.get("engine.steps{kind=decode}", 0)
    outs = [o for _, o in reqs]
    emit(phase="witness", seed=seed, wave=wave, later=later, rate_rps=rate,
         cap=cap, done=len(reqs), traced_lowered_compiled=compiled,
         # what an arrival waited for
         submit_wait_ms=_hist(snap, "engine.submit_wait_ms"),
         submit_stopwatch_ms=_pcts(stopwatch) if stopwatch else None,
         admit_wait_ms=_hist(snap, "engine.admit_wait_ms"),
         loop_lock_wait_ms=_hist(snap, "engine.lock_wait_ms", "who=loop"),
         # was anyone left waiting beside a free slot
         schedule_calls=len(sched),
         schedule_left_waiting_beside_free_slot=(
             sum(1 for a in sched if a["waiting"] and a["free_slots"])
             if sched and "waiting" in sched[0] else None),
         # what a step ran
         decode_steps={"spans": len(dec), "counter": steps},
         slots_a_step=(c.get("engine.decode_slots", 0) / steps
                       if steps else None),
         live_tokens_a_step=(c.get("engine.decode_live_tokens", 0) / steps
                             if steps else None),
         decode_slots_sum={"counter": c.get("engine.decode_slots"),
                           "implied": sum(o - 1 for o in outs)},
         decode_live_tokens_sum={
             "counter": c.get("engine.decode_live_tokens"),
             "implied": sum((o - 1) * p.size + (o - 1) * (o - 2) // 2
                            for p, o in reqs)},
         prefill_tokens={k: v for k, v in c.items()
                         if k.startswith("engine.prefill_tokens")},
         evicted=c.get("engine.sequences{event=evicted}", 0),
         slots_first_step=slots[0] if slots else None,
         first_step_under_full_at_s=((min(under) - t0) / 1e6
                                     if under else None),
         last_arrival_at_s=(last_arrival - t0) / 1e6,
         # (the span's own field: what a step of THAT length attended)
         live_tokens_p50_at_full_slots=(
             statistics.median(e["args"]["live_tokens"] for e in full)
             if full and "live_tokens" in full[0]["args"] else None),
         decode_dispatch_ms_p50_at_full_slots=(
             statistics.median(e["dur"] / 1e3 for e in full)
             if full else None),
         decode_to_fetched_ms_at_full_slots=_pcts(
             [m for m in map(step_ms, full) if m is not None]),
         decode_start_to_start_ms_p50_at_full_slots=(
             statistics.median((b["ts"] - a["ts"]) / 1e3
                               for a, b in zip(full, full[1:]))
             if len(full) > 1 else None),
         tokens=c.get("engine.tokens"),
         all_done_after_s=(t_end - t0) / 1e6,
         tokens_per_s=c.get("engine.tokens", 0) / ((t_end - t0) / 1e6))


def run_witness(args, cfg, spec, engine_opts):
    from paddle_tpu.observability import metrics, trace

    metrics.enable()
    trace.enable()
    model = build_model(cfg)
    load_weights(model, cfg, args.seed)
    engine, server = serve(model, engine_opts)
    wave = engine.config.max_slots
    later = wave - wave // 8
    rate, cap = (5.0, 8) if args.rehearse else (1.0, 64)
    warm(engine, server, cfg,
         requests(cfg, spec, args.seed, N_SIZES, cap), args.seed)
    stopwatch = []
    if _hist(metrics.snapshot(), "engine.submit_wait_ms") is None:
        # the program has no such histogram (the parent of PR 33): time
        # the public call instead, and say so
        emit(phase="note", submit_wait="the program has no "
             "engine.submit_wait_ms; submit() is timed with a stopwatch")
        submit = engine.submit

        def timed(*a, **kw):
            t0 = time.perf_counter()
            try:
                return submit(*a, **kw)
            finally:
                stopwatch.append((time.perf_counter() - t0) * 1e3)

        engine.submit = timed
    for i in range(args.seeds):
        witness(engine, server, cfg, spec, args.seed + i, wave, later,
                rate, cap, stopwatch)
    server.shutdown(drain_timeout=5.0)


# --- the log-probability gap ------------------------------------------------

def int8_channels(w, axis):
    """Weight-only int8 round trip, one absmax scale an output channel
    (`axis` is the contraction axis)."""
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def fp8_channels(w, axis):
    """Weight-only float8 (4 exponent, 3 mantissa bits) round trip, one
    scale an output channel; `reduce_precision`, because the TPU compiler
    elides a pair of converts inside one jit."""
    import jax
    import jax.numpy as jnp

    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 240.0 + 1e-30
    return jax.lax.reduce_precision(w / scale, exponent_bits=4,
                                    mantissa_bits=3) * scale


def round_matmul_weights(p, fn):
    """Every matmul weight through `fn`: the block projections
    ([in, out]) and the tied embedding, whose rows are the head's output
    channels."""
    out = dict(p)
    for n, w in p.items():
        if n.endswith((".qkv.w", ".out.w", ".up.w", ".down.w")):
            out[n] = fn(w, 0)
    out["wte"] = fn(p["wte"], 1)
    return out


def reference_rows(cfg):
    """jitted (weights, ids [1, S], first, tokens [N]) -> the reference's
    log-probability of each delivered token, whether it is the
    reference's first choice, and the row's standard deviation."""
    import jax
    import jax.numpy as jnp
    from reference import gpt as ref

    def rows(p, ids, first, tok):
        h = ref.hidden(cfg, p, ids)[0]
        pos = jnp.clip(first + jnp.arange(tok.shape[0]), 0,
                       ids.shape[1] - 1)
        lg = jnp.matmul(h[pos], p["wte"].T, precision=ref.HIGHEST)
        at = jnp.take_along_axis(lg, tok[:, None], axis=-1)[:, 0]
        return (at - jax.nn.logsumexp(lg, axis=-1),
                at >= jnp.max(lg, axis=-1), jnp.std(lg, axis=-1))

    return jax.jit(rows)


def follow(rows, cfg, p, served, cap):
    """The reference over every served request, padded to one shape."""
    width = cfg["max_position_embeddings"]
    lp, first, std = [], [], []
    for prompt, rec in served:
        n = len(rec["tokens"])
        seq = np.concatenate([prompt, rec["tokens"]])[:width]
        ids = np.zeros((1, width), np.int32)
        ids[0, :seq.size] = seq
        tok = np.zeros((cap,), np.int32)
        tok[:n] = rec["tokens"]
        a, b, c = rows(p, ids, np.int32(prompt.size - 1), tok)
        lp.append(np.asarray(a)[:n])
        first.append(np.asarray(b)[:n])
        std.append(np.asarray(c)[:n])
    return (np.concatenate(lp), np.concatenate(first), np.concatenate(std))


def _gap(lp, ref_lp, std):
    d = np.abs(lp - ref_lp)
    return {"mean": float(d.mean()), "p99": float(np.quantile(d, 0.99)),
            "max": float(d.max()), "mean_over_s": float((d / std).mean()),
            "max_over_s": float((d / std).max())}


def gap(model, rows, cfg, spec, engine_opts, seed, tier, controls, n, cap):
    import jax
    from harness import weights

    load_weights(model, cfg, seed)
    engine, server = serve(model, engine_opts, tier)
    reqs = requests(cfg, spec, seed, n, cap)
    recs = post_all(server.address, reqs, [0.0] * n, logprobs=True)
    server.shutdown(drain_timeout=5.0)
    engine.close()      # the reference needs the chip's memory
    served = [(p, r) for (p, _), r in zip(reqs, recs)]
    lp = np.concatenate([np.asarray(r["logprobs"], np.float64)
                         for r in recs])
    p = weights.make(cfg, seed, "bfloat16")
    ref_lp, first, std = follow(rows, cfg, p, served, cap)
    line = {"phase": "gap", "seed": seed, "tier": tier or "bfloat16",
            "requests": n, "positions": int(lp.size),
            "not_the_references_first_choice": int((~first).sum()),
            "row_std_mean": float(std.mean()),
            "program": _gap(lp, ref_lp, std)}
    if controls:
        for name, fn in (("control_int8", int8_channels),
                         ("control_fp8", fp8_channels)):
            cp = jax.jit(lambda t: round_matmul_weights(t, fn),
                         donate_argnums=0)(p)
            line[name] = _gap(follow(rows, cfg, cp, served, cap)[0],
                              ref_lp, std)
            del cp
            p = weights.make(cfg, seed, "bfloat16")
    emit(**line)


def run_gap(args, cfg, spec, engine_opts):
    model = build_model(cfg)
    rows = reference_rows(cfg)
    n, cap = (6, 8) if args.rehearse else (args.requests, 64)
    for i in range(args.seeds):
        gap(model, rows, cfg, spec, engine_opts, args.seed + i,
            None if args.tier == "bfloat16" else args.tier,
            i < args.controls, n, cap)


def main(argv=None):
    ap = argparse.ArgumentParser(prog="serve_probe")
    ap.add_argument("--phase", choices=("witness", "gap"), required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=1,
                    help="how many seeds, counted up from --seed")
    ap.add_argument("--tier", choices=("bfloat16", "int8"),
                    default="bfloat16")
    ap.add_argument("--controls", type=int, default=0,
                    help="how many of the seeds also read the controls")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax

    from paddle_tpu import backend_guard

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print("serve_probe: no TPU (use --rehearse on the CPU)",
              file=sys.stderr)
        return 1
    backend_guard.enable_compile_cache(os.path.join(REPO, ".jax_cache"))
    emit(phase="start", device=dev.device_kind, platform=dev.platform,
         rehearse=args.rehearse, argv=sys.argv[1:])
    cfg, spec, engine_opts = ((TINY, TINY_SIZES, TINY_ENGINE)
                              if args.rehearse
                              else (XL, XL_SIZES, XL_ENGINE))
    (run_witness if args.phase == "witness" else run_gap)(
        args, cfg, spec, engine_opts)
    return 0


if __name__ == "__main__":
    sys.exit(main())
