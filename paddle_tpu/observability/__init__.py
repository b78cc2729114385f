"""paddle_tpu.observability — framework-wide runtime telemetry.

Three pillars (docs/OBSERVABILITY.md):
  * `metrics`    — process-local counters / gauges / histograms with
    labels; disabled by default, near-zero cost when disabled; JSONL
    snapshot + Prometheus text export.  Wired into flash-attention
    dispatch (tier + gate-reject counters), the autotune cache,
    `jit.to_static` trace cache / retraces, collectives, and the
    allocator peak.
  * `step_stats` — `StepTimer` for train/serve loops and bench.py:
    per-step wall, tokens/s, MFU, compile-time ledger, transfer bytes,
    streamed as chip-session-compatible JSONL.
  * `flight`     — bounded ring of recent structured events (dispatch
    decisions, gate rejects, retraces) dumped on crash or on demand.
  * `trace`      — unified span timeline (Chrome trace-event / Perfetto
    export): RecordEvent scopes, flight events, StepTimer frames,
    collective/pipeline-stage spans, and compile spans annotated by
    `xla_cost` all land in ONE correlated buffer.
  * `xla_cost`   — compile-time `cost_analysis()`/`memory_analysis()`
    capture: FLOPs/bytes per compiled program as span metadata + gauges.

`attach()` turns the whole stack on with a stable snapshot schema —
what `bench.py --telemetry` calls.
"""
from __future__ import annotations

from . import (  # noqa: F401
    export, flight, goodput, lifecycle, metrics, request_trace, slo,
    step_stats, tenant_ledger, timeseries, trace, xla_cost,
)
from .step_stats import StepTimer  # noqa: F401

if metrics.enabled() or trace.enabled():
    # telemetry switched on by the environment (PADDLE_TPU_METRICS /
    # PADDLE_TPU_TRACE): the compile totals start with it, as they do
    # behind metrics.enable() / trace.enable()
    xla_cost.watch_process_compiles()

__all__ = ["metrics", "flight", "step_stats", "trace", "xla_cost",
           "request_trace", "slo", "export", "goodput", "tenant_ledger",
           "timeseries", "lifecycle", "StepTimer", "attach", "detach"]

# The snapshot-schema floor `attach()` guarantees: these counters exist
# (at 0) in every telemetry snapshot even when the path never fired in
# this process — a CPU bench run still reports autotune.hit == 0 rather
# than omitting the key (ISSUE 1 acceptance schema).  Every entry here
# carries EXACTLY the label set its live increment site uses, so the
# declared key is the key that counts (zeros never sit next to the real
# series under a different label set).
_SCHEMA_COUNTERS = tuple(
    [("flash.dispatch", {"tier": t})
     for t in ("transpose", "flat", "fallback", "biased")]
    + [("autotune.hit", {}), ("autotune.miss", {})]
    + [("autotune.cross_layout_reject", {"layout": "flat"})]
    + [("jit.trace_cache.hit", {}), ("jit.trace_cache.miss", {}),
       ("jit.retrace", {})]
    + [("collective.calls", {"kind": k})
       for k in ("all_reduce", "all_gather", "reduce_scatter", "alltoall",
                 "alltoall_single", "broadcast", "send", "barrier")]
    # EQuARX quantized-collective tier (ISSUE 11, docs/SHARDING.md):
    # which additive syncs rode the wire quantized, by payload codec
    + [("collective.quantized", {"kind": k, "precision": p})
       for k in ("all_reduce", "reduce_scatter")
       for p in ("bf16", "int8")]
    + [("collective.quantized_tier", {"precision": p})
       for p in ("bf16", "int8")]
    # resilience subsystem (ISSUE 3): fault injections, retry traffic,
    # guard skips, checkpoint/guard rollbacks, watchdog trips — declared
    # so a clean run reports zeros instead of omitting the keys
    + [("resilience.faults", {"point": p})
       for p in ("checkpoint.write", "collective.call", "dataloader.batch",
                 "jit.compile", "train.step", "serving.request",
                 "store.op", "router.forward", "router.stream_read",
                 "router.resume_verify", "replica.crash")]
    + [("resilience.retries", {"policy": p})
       for p in ("collective", "elastic.heartbeat", "serving",
                 "dataloader", "jit.compile")]
    + [("resilience.giveups", {"policy": p})
       for p in ("collective", "elastic.heartbeat", "serving",
                 "dataloader", "jit.compile")]
    + [("resilience.circuit_open", {"policy": p})
       for p in ("collective", "elastic.heartbeat", "serving")]
    + [("resilience.skipped_steps", {"source": s})
       for s in ("guard", "amp", "amp_floor")]
    + [("resilience.rollbacks", {}), ("resilience.watchdog_trips", {}),
       ("resilience.degraded_batches", {})]
    # overload/preemption runtime (ISSUE 5): admission sheds by reason,
    # preemption signals by name, emergency checkpoints, serving drains
    + [("resilience.shed_requests", {"reason": r})
       for r in ("queue_full", "queue_timeout", "deadline", "draining",
                 "no_replicas", "deadline_exceeded")]
    # multi-tenant QoS (ISSUE 18): per-class shed and preemption
    # counters — the class set mirrors inference.qos.CLASSES (hardcoded
    # here: observability stays standalone, same discipline as
    # request_trace's header validation set)
    + [("qos.shed", {"class": c}) for c in ("paid", "free", "batch")]
    + [("qos.preemptions", {"class": c})
       for c in ("paid", "free", "batch")]
    + [("preemption.signals", {"signal": s})
       for s in ("SIGTERM", "SIGINT")]
    + [("preemption.maintenance_events", {}),
       ("preemption.checkpoints", {}), ("preemption.drains", {}),
       ("preemption.callback_errors", {})]
    # request-level serving telemetry (ISSUE 7): per-status request
    # counters on both sides of the hop — a fresh server reports zeros
    # for every status class instead of omitting the keys
    + [("serving.requests", {"status": s})
       for s in ("ok", "client_error", "shed", "timeout", "error")]
    + [("client.requests", {"status": s})
       for s in ("ok", "shed_retry", "error")]
    # continuous-batching engine (ISSUE 8): sequence lifecycle events,
    # accepted tokens, and the paged-attention dispatch tier — a fresh
    # engine reports zeros instead of omitting the keys
    + [("engine.sequences", {"event": e})
       for e in ("submitted", "admitted", "completed", "cancelled",
                 "evicted")]
    + [("engine.tokens", {})]
    # what a step ran (ISSUE 33): decode dispatches (plain or
    # speculative alike) and — summed over them — the slots that ran
    # and the cached positions they attended (delta over delta of
    # engine.steps{kind=decode} is the mean a step); prompt positions
    # COMPUTED by prefills, by the prefix cache's outcome for the
    # sequence
    + [("engine.steps", {"kind": "decode"}),
       ("engine.decode_slots", {}), ("engine.decode_live_tokens", {})]
    + [("engine.prefill_tokens", {"cache": c})
       for c in ("hit", "partial", "miss")]
    + [("paged.dispatch", {"tier": t}) for t in ("pallas", "fallback")]
    # speculative decoding (ISSUE 12): per-pass draft-token outcomes —
    # accepted counts committed draft proposals, rejected the discarded
    # tail (the acceptance rate is accepted/(accepted+rejected))
    + [("engine.spec_decode", {"result": r})
       for r in ("accepted", "rejected")]
    # fleet router (ISSUE 9): failure-triggered failovers, replica
    # ejections/re-admissions, and per-endpoint routed-request outcomes
    # — a fresh router reports zeros instead of omitting the keys
    + [("router.failovers", {}), ("router.ejections", {}),
       ("router.readmissions", {})]
    + [("router.requests", {"endpoint": ep, "status": s})
       for ep in ("predict", "generate")
       for s in ("ok", "client_error", "shed", "interrupted", "error")]
    # mid-stream failover (ISSUE 20): router-side resume outcomes and
    # the replica-side resume-prefill cache attribution — a healthy
    # fleet shows zeros, never absent keys
    + [("router.stream_resumes", {"outcome": o})
       for o in ("ok", "diverged", "exhausted")]
    + [("serving.resume_prefill", {"cache": c})
       for c in ("hit", "partial", "miss")]
    # prefix caching (ISSUE 13): admission-time cache outcomes and LRU
    # reclaims on the engine side, affinity pick outcomes on the router
    # side (counted only for fingerprinted /generate requests)
    + [("engine.prefix_cache", {"event": e})
       for e in ("hit", "miss", "evict")]
    + [("router.affinity", {"outcome": o})
       for o in ("affine", "least_loaded")]
    # autoscaler (ISSUE 14): one decision per control tick — a healthy
    # steady-state fleet shows a growing `hold` count next to zero
    # up/down, which is itself the signal the loop is alive.
    # `up_predictive` (ISSUE 15) is a scale-up fired by the timeseries
    # plane's queue-growth derivative BEFORE burn/occupancy thresholds
    # crossed — the leading-vs-lagging split is first-class telemetry
    + [("autoscaler.decisions", {"action": a})
       for a in ("up", "down", "hold", "up_predictive")]
    # anomaly watchdog (ISSUE 15): rolling-baseline latency-regression
    # detections by kind — zero on a healthy server, never absent
    + [("telemetry.anomalies", {"kind": k})
       for k in ("ttft", "itl")]
    # tenant metering (ISSUE 16): bounded-cardinality aggregate mirror
    # of the ledger — the per-tenant top-K table itself lives ONLY in
    # /debug/tenants and telemetry dumps, never the metrics registry
    + [("tenant.requests", {"status": s})
       for s in ("ok", "shed", "client_error", "error")]
    # replica lifecycle (ISSUE 17): spawn count + strict-stamp
    # violations — bounded, per-process (supervisor and replica each
    # count their own view of a spawn)
    + [("lifecycle.spawns", {}), ("lifecycle.double_stamps", {})]
)

# Gauges attach() zeroes so the admission-control state is always
# present in a snapshot (a server that never saw traffic still reports
# inflight=0 rather than omitting the key).  Entries are either a bare
# name or a (name, labels) pair for labeled gauge series.
_SCHEMA_GAUGES = ("serving.inflight", "serving.queue_depth",
                  "serving.admission_limit",
                  # engine state (ISSUE 8): live batch + page pool
                  "engine.active_sequences", "engine.waiting_sequences",
                  "engine.batch_occupancy", "engine.page_utilization",
                  # quantized decode (ISSUE 12): draft proposal length
                  "engine.spec_tokens",
                  # prefix cache (ISSUE 13): radix-index size + lifetime
                  # hit rate — the /ready payload's gauge pair
                  "engine.prefix_cached_tokens",
                  "engine.prefix_cache_hit_rate",
                  # tenant ledger (ISSUE 16): sketch occupancy + overflow
                  # mass — the only per-registry trace of the top-K table
                  "tenant.tracked", "tenant.other_tokens") \
    + tuple(("telemetry.timeseries_samples", {"sampler": s})
            # timeseries sampler health (ISSUE 15): total samples per
            # sampler — a flat-lined value is that sampler's own
            # outage alarm (labeled: a router and a server in one
            # process must not hide behind each other's count)
            for s in ("serving", "router")) \
    + tuple(("router.replicas", {"state": s})
            for s in ("up", "draining", "ejected", "down")) \
    + tuple(("router.capacity", {"endpoint": ep})
            for ep in ("predict", "generate")) \
    + tuple(("autoscaler.replicas", {"state": s})
            for s in ("target", "actual")) \
    + tuple(("engine.weight_precision", {"precision": p})
            for p in ("full", "bf16", "int8")) \
    + tuple(("paged.pool_precision", {"precision": p})
            for p in ("full", "int8")) \
    + tuple(("lifecycle.phase_ms", {"phase": p})
            # replica lifecycle (ISSUE 17): ms of the just-closed phase;
            # proc_spawn is the anchor so it never closes a phase.  The
            # per-program lifecycle.compile_ms series is bounded by the
            # ledger's label cap; only the ~total sum is pre-declared
            for p in lifecycle.PHASES[1:]) \
    + (("lifecycle.compile_ms", {"program": "~total"}),
       # autoscaler's observed spawn->routable estimate (ISSUE 17):
       # 0 until the first spawn completes, then the fleet median
       "autoscaler.observed_spawn_ms") \
    + tuple(("slo.burn_rate", {"endpoint": ep, "class": c})
            # per-class SLO burn (ISSUE 18): zero before traffic, so a
            # dashboard watching the paid tier has its key from boot
            for ep in ("predict", "generate")
            for c in ("paid", "free", "batch"))


# Histograms attach() pre-registers EMPTY (full bucket ladder, count 0)
# so a fresh server's /metrics and snapshot expose the series before
# the first observation — the ITL acceptance surface (ISSUE 15).
_SCHEMA_HISTS = (
    ("serving.itl_ms", {"endpoint": "generate"}),
    # mid-stream failover (ISSUE 20): the client-visible gap between
    # the last token the dead replica delivered and the first token
    # the resume replica delivered — THE latency cost of a resume
    ("router.resume_gap_ms", {}),
    # what an arrival waits for inside the engine (ISSUE 33): entry of
    # submit() -> queued at the scheduler; queued -> its prefill
    # begins; and the loop asking for the step lock -> holding it
    # (maintenance — defrag, cache clear, close — is what it can wait
    # behind)
    ("engine.submit_wait_ms", {}),
    ("engine.admit_wait_ms", {}),
    ("engine.lock_wait_ms", {"who": "loop"}),
)


def attach(crash_hook: bool = True):
    """Enable the full telemetry stack: metrics registry on, schema
    counters pre-declared, flight recorder on (+ crash-dump excepthook),
    span tracer buffering.  Returns the metrics registry (snapshot() it
    at the end of the run; `trace.export(path)` writes the timeline)."""
    metrics.enable()
    for name, labels in _SCHEMA_COUNTERS:
        metrics.declare(name, **labels)
    for entry in _SCHEMA_GAUGES:
        if isinstance(entry, tuple):
            metrics.set_gauge(entry[0], 0, **entry[1])
        else:
            metrics.set_gauge(entry, 0)
    for name, labels in _SCHEMA_HISTS:
        metrics.declare_hist(name, **labels)
    flight.get_recorder().enabled = True
    trace.enable()
    if crash_hook:
        flight.install_crash_hook()
    return metrics.get_registry()


def detach():
    """Disable metric recording and span buffering (flight stays on — it
    is cheap and the crash evidence is the point).  Does not clear
    collected data."""
    metrics.disable()
    trace.disable()
