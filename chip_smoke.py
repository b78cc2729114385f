"""First light on one TPU chip: the trainer and the serving engine at
GPT-3 125M, through the entry points a user calls.

    python chip_smoke.py              # one chip: train phase + serve phase
    python chip_smoke.py --chips 4    # four chips: dp=4 ZeRO-1 vs dp=1, only
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny CPU rehearsal

A smoke, not a benchmark: it prints one JSON line per phase (compile wall,
dispatch counters) and, as the LAST line of stdout on success,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed check raises, so the exit code is non-zero and no success line
is printed.  Without an accelerator it exits 1 before building anything;
``--rehearse`` lifts that (tiny model, Pallas interpreter) and never
prints the success line.  One process, no child: a chip belongs to one
process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

_PALLAS_FLASH = ("flat", "transpose")


def _require(ok, what):
    """A failed check ends the smoke (`assert` would vanish under -O)."""
    if not ok:
        raise RuntimeError(f"chip_smoke: check failed: {what}")


def _counters(metrics):
    return dict(metrics.snapshot()["counters"])


def _delta(before, after):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


def _sub(d, prefix):
    return {k: v for k, v in d.items() if k.startswith(prefix)}


def _emit(phase, d, **fields):
    """One JSON line per phase: its fields, the autotune counters it
    moved, and where it ran."""
    import jax

    print(json.dumps({
        "phase": phase, **fields, "autotune": _sub(d, "autotune."),
        "jax": jax.__version__,
        "device_kind": jax.devices()[0].device_kind}), flush=True)


def _check_dispatch(d, rehearse):
    """Counters no phase may show: the kernel tier thinking it is not on
    a TPU, or an autotune search in which every candidate failed."""
    if d.get("autotune.search_failed"):
        from paddle_tpu.observability import flight

        why = [e for e in flight.events()
               if e["kind"] == "autotune.candidate_failed"][:3]
        _require(False, f"autotune search failed: {d} {why}")
    if not rehearse:  # the interpreter run has no TPU by construction
        _require(not d.get("flash.fallback_reason{reason=unavailable}"), d)


def _gpt_config(rehearse):
    from paddle_tpu.models.gpt import GPTConfig

    if rehearse:
        return GPTConfig(vocab_size=512, hidden_size=128, num_layers=2,
                         num_heads=2, max_seq_len=256, fused_head_ce=True)
    # GPT-3 125M at its published widths (bench.py's shape)
    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_seq_len=1024, fused_head_ce=True)


def _train_losses(cfg, seed, devices, batch, seq, steps=5):
    """`steps` calls of the fleet train step, data-parallel over
    `devices`, on one repeated batch.  Returns (losses, first-call wall,
    rest wall, the step object)."""
    import paddle_tpu as P
    from paddle_tpu.distributed import fleet, topology
    from paddle_tpu.models.gpt import GPTForCausalLM, GPTPretrainingCriterion

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    # sharding_degree = dp degree is how fleet asks for ZeRO-1
    # (docs/SHARDING.md); on one device every degree is 1
    strategy.hybrid_configs = {"dp_degree": len(devices), "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": len(devices)}
    fleet.init(is_collective=True, strategy=strategy)
    # fleet.init meshes over every device jax reports; this run is held
    # to `devices` (one chip unless --chips 4 says otherwise)
    topo = topology.HybridTopology(dp=len(devices), devices=devices)
    topology.set_topology(topo)
    P.seed(seed)
    inner = GPTForCausalLM(cfg)
    model = fleet.distributed_model(inner)
    opt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=1e-4))
    crit = GPTPretrainingCriterion(model=inner)
    step = model.build_train_step(opt, crit, amp_dtype="bfloat16",
                                  topo=topo)
    rs = np.random.RandomState(seed)
    ids = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)), "int32")
    labels = P.to_tensor(rs.randint(0, cfg.vocab_size, (batch, seq)),
                         "int32")
    losses, walls = [], []
    for _ in range(steps):
        t0 = time.perf_counter()
        losses.append(float(step(ids, labels)))  # the fetch closes the step
        walls.append(time.perf_counter() - t0)
    _require(all(np.isfinite(losses)) and losses[-1] < losses[0], losses)
    return losses, walls[0], sum(walls[1:]), step


def _check_train_dispatch(d, rehearse):
    """The train step's attention ran a Pallas flash tier, never the
    reference.  Returns the flash counters for the phase line."""
    tiers = _sub(d, "flash.")
    if not rehearse:  # the interpreter has no kernel tier to dispatch
        _require(any(d.get(f"flash.dispatch{{tier={t}}}")
                     for t in _PALLAS_FLASH), tiers)
        _require(not d.get("flash.dispatch{tier=fallback}"), tiers)
    _check_dispatch(d, rehearse)
    return tiers


def train_phase(cfg, seed, rehearse, metrics):
    import jax

    before = _counters(metrics)
    batch, seq = (2, 128) if rehearse else (8, 1024)
    losses, first, rest, _ = _train_losses(cfg, seed, jax.devices()[:1],
                                           batch, seq)
    d = _delta(before, _counters(metrics))
    tiers = _check_train_dispatch(d, rehearse)
    _emit("train", d, batch=batch, seq=seq, losses=losses,
          first_call_s=first, rest_s=rest, flash=tiers)


def serve_phase(cfg, seed, rehearse, metrics):
    import paddle_tpu as P
    from paddle_tpu.inference.engine import EngineConfig, InferenceEngine
    from paddle_tpu.inference.serving import InferenceClient, InferenceServer
    from paddle_tpu.models.gpt import GPTForCausalLM

    before = _counters(metrics)
    new = 8 if rehearse else 32
    P.seed(seed)
    model = GPTForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(seed + 1)
    lens = (16, 23, 40, 64) if rehearse else (16, 57, 128, 200)
    prompts = [rs.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in lens]
    # default tiers: weights as the model has them, no int8, no draft
    engine = InferenceEngine(model, EngineConfig())
    server = InferenceServer(engine=engine, port=0,
                             request_timeout=600.0).start()
    outs, walls = [None] * 4, [0.0] * 4

    def ask(i):
        t0 = time.perf_counter()
        outs[i] = InferenceClient(server.address, timeout=600.0).generate(
            prompts[i], max_new_tokens=new)
        walls[i] = time.perf_counter() - t0

    ask(0)                                   # first call: compiles
    ask(1)
    pair = [threading.Thread(target=ask, args=(i,)) for i in (2, 3)]
    for t in pair:
        t.start()
    for t in pair:
        t.join()
    server.shutdown()
    for o in outs:
        _require(o is not None, "a /generate request did not return")
        toks = o["tokens"]
        _require(len(toks) == new, (len(toks), o["finish_reason"]))
        _require(all(0 <= t < cfg.vocab_size for t in toks), toks)
    ref = np.asarray(model.generate(
        P.to_tensor(prompts[1][None, :], "int32"),
        max_new_tokens=new)._value)[0, len(prompts[1]):]
    got = np.asarray(outs[1]["tokens"])
    _require(got[0] == ref[0], (got.tolist(), ref.tolist()))
    agree = (got == ref)
    prefix = int(new if agree.all() else agree.argmin())
    d = _delta(before, _counters(metrics))
    paged = _sub(d, "paged.")
    if not rehearse:
        _require(d.get("paged.dispatch{tier=pallas}", 0) > 0, paged)
        _require(not d.get("paged.dispatch{tier=fallback}"), paged)
    _check_dispatch(d, rehearse)
    # the masked prefill may take the biased tier or the reference for a
    # gate reason by design: its flash counters are information only
    _emit("serve", d, requests=4, new_tokens=new, prompt_lens=list(lens),
          first_call_s=walls[0], rest_s=sum(walls[1:]),
          common_prefix_with_generate=prefix, paged=paged,
          flash=_sub(d, "flash."))


def four_chip_phase(cfg, seed, rehearse, metrics):
    """dp=4 ZeRO-1 train step against the dp=1 step on one device: same
    seed, same global batch."""
    import jax

    devs = jax.devices()
    _require(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    batch, seq = (4, 128) if rehearse else (8, 1024)
    ref, _, _, _ = _train_losses(cfg, seed, devs[:1], batch, seq)
    before = _counters(metrics)
    got, first, rest, step = _train_losses(cfg, seed, devs[:4], batch, seq)
    d = _delta(before, _counters(metrics))
    tiers = _check_train_dispatch(d, rehearse)
    np.testing.assert_allclose(got, ref, rtol=2e-2)
    state = step._state
    spanned = set()
    for v in state["params"].values():
        spanned |= {s.device for s in v.addressable_shards}
    _require(len(spanned) == 4, spanned)
    slots = jax.tree_util.tree_leaves(state["opt"]["slots"])
    total = sum(v.nbytes for v in slots)
    per_dev = {}
    for v in slots:
        for s in v.addressable_shards:
            per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    share = {k: v / total for k, v in per_dev.items()}
    _require(len(share) == 4
             and all(0.2 < f < 0.3 for f in share.values()), share)
    _emit("train_dp4", d, batch=batch, seq=seq, losses=got, losses_dp1=ref,
          first_call_s=first, rest_s=rest, flash=tiers,
          param_devices=len(spanned),
          opt_state_share_per_device=sorted(share.values()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on whatever backend there is (CPU "
                         "rehearsal); never prints the success line")
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r} "
              "(the CPU form is --rehearse)", file=sys.stderr)
        return 1

    from paddle_tpu import backend_guard, observability as obs
    from paddle_tpu.observability import metrics

    backend_guard.enable_compile_cache(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".jax_cache"))
    obs.attach(crash_hook=False)
    cfg = _gpt_config(args.rehearse)
    if args.chips == 4:
        four_chip_phase(cfg, args.seed, args.rehearse, metrics)
    else:
        train_phase(cfg, args.seed, args.rehearse, metrics)
        serve_phase(cfg, args.seed, args.rehearse, metrics)
    if args.rehearse:
        print(json.dumps({"rehearsed": True, "platform": dev.platform}))
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
