"""Continuous-batching inference engine + paged KV cache (ISSUE 8).

Three layers of coverage, all CPU tier-1 unless marked:

  * unit: the page-pool allocator and the scheduler's admission/
    completion/eviction ordering under an injectable clock;
  * kernel: the ragged paged-attention Pallas kernel (interpret mode)
    against its jnp reference and the dense decode kernel;
  * engine: token-identical equivalence with sequential `generate()`
    greedy decoding under ragged batching, page-boundary crossings,
    chunked decode, slot reuse, eviction-with-recompute, eos, GQA
    (llama), and the serving `/generate` stream with the one-request-id
    retry discipline.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as P
from paddle_tpu.inference.engine import (
    EngineConfig, InferenceEngine, OutOfPages, PagePool, Scheduler,
    Sequence,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def assert_drained(eng):
    """A drained engine holds ONLY prefix-cache pages (each at exactly
    one reference — the cache's own); clearing the cache must return
    the pool to EMPTY.  This is the PR 8 zero-leak assertion, made
    aware of ISSUE 13's prefix cache deliberately retaining committed
    prompt pages across requests."""
    st = eng.pool.stats()
    assert st["logical_pages"] == st["used"], st   # no live-seq refs
    eng.clear_prefix_cache()
    assert eng.pool.used_pages == 0, eng.pool.stats()


def _gpt(max_len=64, seed=0):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(seed)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=4, max_seq_len=max_len)
    model = GPTForCausalLM(cfg)
    model.eval()
    return model


@pytest.fixture(scope="module")
def gpt_model():
    return _gpt()


_PROMPT_LENS = (3, 9, 17, 5, 12)


@pytest.fixture(scope="module")
def prompts():
    rs = np.random.RandomState(0)
    return [rs.randint(0, 128, (n,)).astype(np.int32)
            for n in _PROMPT_LENS]


@pytest.fixture(scope="module")
def refs(gpt_model, prompts):
    """Sequential solo generate() per prompt — the ground truth every
    engine configuration must reproduce token-for-token."""
    return [np.asarray(gpt_model.generate(
        P.to_tensor(p[None, :], "int32"), max_new_tokens=10)._value)[0]
        for p in prompts]


# ------------------------------ page pool ------------------------------

def test_page_pool_alloc_free_oom():
    pool = PagePool(num_pages=6, page_size=8)
    assert pool.capacity == 5          # page 0 reserved
    a = pool.alloc(3)
    assert len(set(a)) == 3 and 0 not in a
    assert pool.used_pages == 3 and pool.free_pages == 2
    with pytest.raises(OutOfPages):
        pool.alloc(3)
    assert pool.used_pages == 3        # failed alloc grants nothing
    pool.free(a)
    assert pool.used_pages == 0
    assert pool.utilization() == 0.0
    b = pool.alloc(5)
    assert pool.stats()["peak_used"] == 5
    pool.free(b)


def test_page_pool_double_free_and_scratch_guard():
    pool = PagePool(num_pages=4, page_size=8)
    a = pool.alloc(2)
    pool.free(a)
    with pytest.raises(ValueError):
        pool.free([a[0]])              # double free
    with pytest.raises(ValueError):
        pool.free([0])                 # scratch page


def test_page_pool_defrag_compacts():
    pool = PagePool(num_pages=10, page_size=8)
    a = pool.alloc(3)
    b = pool.alloc(3)
    pool.free(a)                       # holes at the bottom
    moves = pool.defrag()
    # b's three pages must now occupy {1, 2, 3}; every move src > dst
    assert set(moves.values()) <= {1, 2, 3}
    assert all(src > dst for src, dst in moves.items())
    assert pool.used_pages == 3
    c = pool.alloc(6)                  # full capacity usable again
    assert len(c) == 6
    assert pool.defrag() == {}         # already compact


# ------------------------------ scheduler ------------------------------

def _seq(n, max_new=4, rid=None):
    return Sequence(np.arange(1, n + 1, dtype=np.int32), max_new,
                    request_id=rid)


def test_scheduler_fifo_admission_and_slot_fill():
    clock = [0.0]
    pool = PagePool(num_pages=64, page_size=4)
    sch = Scheduler(2, pool, max_pages_per_seq=8,
                    clock=lambda: clock[0])
    a, b, c = _seq(4, rid="a"), _seq(4, rid="b"), _seq(4, rid="c")
    for s in (a, b, c):
        sch.submit(s)
        clock[0] += 1.0
    out = sch.schedule()
    # FIFO: a and b admitted (2 slots), c waits
    assert [s.request_id for s in out.prefills] == ["a", "b"]
    assert {s.slot for s in out.prefills} == {0, 1}
    assert sch.waiting_sequences == 1
    assert all(s.pages for s in out.prefills)


def test_scheduler_completion_frees_slot_for_next_waiting():
    pool = PagePool(num_pages=64, page_size=4)
    sch = Scheduler(1, pool, max_pages_per_seq=8)
    a, b = _seq(4, rid="a"), _seq(4, rid="b")
    sch.submit(a)
    sch.submit(b)
    out = sch.schedule()
    assert [s.request_id for s in out.prefills] == ["a"]
    sch.finish(a, "length")
    out = sch.schedule()
    # the SAME schedule() that releases a admits b into its slot
    assert [s.request_id for s in out.prefills] == ["b"]
    assert b.slot == 0
    assert a.pages == [] and pool.used_pages == len(b.pages)


def test_scheduler_eviction_youngest_on_page_pressure():
    # pool sized so two sequences fit only while short
    pool = PagePool(num_pages=5, page_size=4)   # 4 allocatable pages
    sch = Scheduler(2, pool, max_pages_per_seq=4)
    a, b = _seq(6, max_new=8, rid="old"), _seq(6, max_new=8, rid="young")
    sch.submit(a)
    sch.submit(b)
    out = sch.schedule()
    assert len(out.prefills) == 2
    a.length, b.length = 6, 6
    # both need a 3rd page for the next 4 tokens: only 0 free ->
    # the YOUNGEST is evicted back to the waiting queue's front
    out = sch.schedule(chunk=4)
    assert [s.request_id for s in out.evicted] == ["young"]
    assert b.state == "waiting" and b.pages == [] and b.length == 0
    assert b.evictions == 1
    assert a.slot is not None            # the older request kept going
    assert sch.waiting_sequences == 1


def test_scheduler_growth_clamped_to_sequence_total():
    """Page demand near a sequence's finish line is clamped to what it
    can EVER use (prompt+max_new): a decode_chunk reaching past the end
    must not demand pages for scratch-bound tokens — that once evicted
    a fitting sequence into a permanent re-admission stall."""
    pool = PagePool(num_pages=3, page_size=8)     # capacity: 2 pages
    sch = Scheduler(1, pool, max_pages_per_seq=8)
    seq = Sequence(np.arange(1, 9, dtype=np.int32), 8)  # 16 = 2 pages
    sch.submit(seq)
    out = sch.schedule(chunk=5)
    assert out.prefills == [seq]
    seq.length = 13                                # 6 tokens generated
    out = sch.schedule(chunk=5)                    # 13+5 > 16: clamped
    assert out.evicted == [] and seq.slot is not None
    assert len(seq.pages) == 2                     # never needs a 3rd


def test_scheduler_youngest_self_preempts():
    """When the sequence that needs pages IS the youngest, it preempts
    itself rather than throwing away an older request's longer KV."""
    pool = PagePool(num_pages=5, page_size=4)      # 4 allocatable
    sch = Scheduler(2, pool, max_pages_per_seq=8)
    old = Sequence(np.arange(1, 5, dtype=np.int32), 12, request_id="old")
    young = Sequence(np.arange(1, 5, dtype=np.int32), 12,
                     request_id="young")
    sch.submit(old)
    sch.submit(young)
    sch.schedule(chunk=1)                          # both admitted, 2+2
    old.length, young.length = 4, 7                # only young grows
    out = sch.schedule(chunk=4)
    assert [s.request_id for s in out.evicted] == ["young"]
    assert old.slot is not None and old.pages     # the elder undisturbed


def test_scheduler_cancel_waiting_and_running():
    pool = PagePool(num_pages=64, page_size=4)
    sch = Scheduler(1, pool, max_pages_per_seq=8)
    a, b = _seq(4, rid="a"), _seq(4, rid="b")
    sch.submit(a)
    sch.submit(b)
    sch.schedule()
    assert sch.cancel("a") and sch.cancel("b")
    assert not sch.cancel("a")           # already done
    assert not sch.cancel("nope")
    out = sch.schedule()
    assert {s.request_id for s in out.finished} == {"a", "b"}
    assert pool.used_pages == 0 and sch.active_sequences == 0


def test_scheduler_rejects_oversized_and_duplicate():
    pool = PagePool(num_pages=64, page_size=4)
    sch = Scheduler(1, pool, max_pages_per_seq=2)   # 8-token cap
    with pytest.raises(ValueError):
        sch.submit(_seq(6, max_new=4))   # 10 > 8
    a = _seq(2, rid="dup")
    sch.submit(a)
    with pytest.raises(ValueError):
        sch.submit(_seq(2, rid="dup"))


def test_scheduler_sheds_expired_deadline_at_admission(monkeypatch):
    """Deadline shedding (ISSUE 20 satellite): a waiting sequence whose
    deadline passed while queued is shed AT ADMISSION with the honest
    `deadline_exceeded` finish reason — it never takes a slot or burns
    a prefill the nobody-is-waiting-for answer would waste — while
    sequences with live (or no) deadlines admit normally."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics

    metrics.reset()
    obs.attach(crash_hook=False)
    try:
        clock = [0.0]
        pool = PagePool(num_pages=64, page_size=4)
        sch = Scheduler(2, pool, max_pages_per_seq=8,
                        clock=lambda: clock[0])
        late = Sequence(np.arange(1, 5, dtype=np.int32), 4,
                        request_id="late", deadline=2.0)
        live = Sequence(np.arange(1, 5, dtype=np.int32), 4,
                        request_id="live", deadline=50.0)
        plain = Sequence(np.arange(1, 5, dtype=np.int32), 4,
                         request_id="plain")
        for s in (late, live, plain):
            sch.submit(s)
        clock[0] = 5.0              # the queue outlived late's deadline
        out = sch.schedule()
        assert [s.request_id for s in out.prefills] == \
            ["live", "plain"]
        (shed,) = out.finished
        assert shed.request_id == "late"
        assert shed.finish_reason == "deadline_exceeded"
        assert sch.waiting_sequences == 0
        snap = metrics.snapshot()["counters"]
        assert snap[
            "resilience.shed_requests{reason=deadline_exceeded}"] == 1
    finally:
        obs.detach()
        metrics.reset()


def test_engine_deadline_shed_closes_handle(gpt_model):
    """End to end through the engine: an expired-deadline submission
    comes back as a finished handle with `deadline_exceeded` — a clean
    final record for the serving layer, not a hang or a decode."""
    import time as _time

    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    h = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=4,
                   request_id="expired",
                   deadline=_time.monotonic() - 1.0)
    for _ in range(50):
        eng.step()
        if h.done.is_set():
            break
    assert h.done.is_set()
    assert h.finish_reason == "deadline_exceeded"
    assert h.tokens == []           # no token was ever decoded
    assert_drained(eng)


def test_ledger_conservation_across_resume(gpt_model):
    """Exactly-once billing across a mid-stream resume (ISSUE 20): the
    dying replica's book keeps the tokens it delivered, the resume
    replica bills only NEW tokens (its re-derived verify token rides in
    prebilled — billed nowhere), and the fleet merge conserves decode
    tokens and KV page-seconds — while the resumed output stays
    bit-exact with the uninterrupted reference (greedy determinism)."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics
    from paddle_tpu.observability import tenant_ledger as tl

    metrics.reset()
    obs.attach(crash_hook=False)
    try:
        total = 8
        prompt = np.arange(1, 9, dtype=np.int32)
        ref = np.asarray(gpt_model.generate(
            P.to_tensor(prompt[None, :], "int32"),
            max_new_tokens=total)._value)[0]

        # leg 1: "replica A" delivers a few tokens, then dies (cancel
        # stands in for the kill — billing-wise identical)
        eng_a = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=2, max_seq_len=64))
        assert eng_a.tenant_ledger is not None
        h1 = eng_a.submit(prompt, max_new_tokens=total,
                          tenant_id="t0", request_id="r1")
        while len(h1.tokens) < 3 and not h1.done.is_set():
            eng_a.step()
        delivered = list(h1.tokens)
        assert 3 <= len(delivered) < total
        eng_a.cancel("r1")
        eng_a.step()               # slot/pages release, books close

        # leg 2: "replica B" tail-prefills prompt+delivered[:-1] and
        # re-derives delivered[-1] as its first (prebilled) token
        eng_b = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=2, max_seq_len=64))
        ids = np.concatenate(
            [prompt, np.asarray(delivered[:-1], np.int32)])
        h2 = eng_b.submit(ids,
                          max_new_tokens=total - len(delivered) + 1,
                          tenant_id="t0", request_id="r1",
                          prebilled_tokens=1)
        for _ in range(500):
            eng_b.step()
            if h2.done.is_set():
                break
        assert h2.done.is_set()
        assert h2.tokens[0] == delivered[-1]    # the verify token
        assert np.array_equal(h2.result(), ref)  # bit-exact splice

        sa = eng_a.tenant_ledger.snapshot()
        sb = eng_b.tenant_ledger.snapshot()
        # each book billed its own leg; the verify token nowhere
        assert sa["totals"]["decode_tokens"] == len(delivered)
        assert sb["totals"]["decode_tokens"] == total - len(delivered)
        fleet = tl.merge_snapshots([sa, sb])
        assert fleet["totals"]["decode_tokens"] == total
        assert fleet["tenants"]["t0"]["decode_tokens"] == total
        assert tl.conservation_delta(fleet) == {}
        # KV page-seconds accrued on BOTH legs; the merge is the sum
        assert sa["totals"]["kv_page_seconds"] > 0
        assert sb["totals"]["kv_page_seconds"] > 0
        assert fleet["totals"]["kv_page_seconds"] == pytest.approx(
            sa["totals"]["kv_page_seconds"]
            + sb["totals"]["kv_page_seconds"])
        # engine.tokens (both books share the process counter) agrees
        assert metrics.snapshot()["counters"].get(
            "engine.tokens", 0) == total
    finally:
        obs.detach()
        metrics.reset()


# ------------------------------ kernel ------------------------------

def test_paged_attention_kernel_matches_reference():
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention, paged_attention_reference,
    )

    rs = np.random.RandomState(1)
    b, hq, hkv, d, ps, npool, p = 4, 8, 2, 16, 8, 12, 4
    q = jnp.asarray(rs.randn(b, hq, d), jnp.float32)
    kp = jnp.asarray(rs.randn(npool, hkv, ps, d), jnp.float32)
    vp = jnp.asarray(rs.randn(npool, hkv, ps, d), jnp.float32)
    pt = jnp.asarray([[1, 2, 3, 4], [5, 6, 0, 0], [7, 0, 0, 0],
                      [8, 9, 10, 11]], jnp.int32)
    # ragged: page-boundary crossing (25), exact boundary (15), single
    # token (0), full table (31)
    pos = jnp.asarray([25, 15, 0, 31], jnp.int32)
    ref = paged_attention_reference(q, kp, vp, pt, pos)
    for block_k in (ps, 8):
        out = paged_attention(q, kp, vp, pt, pos, block_k=block_k,
                              interpret=True)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


def test_paged_attention_matches_dense_decode_kernel():
    """Gathering each sequence's pages into a dense cache and running
    the existing decode kernel must agree — the paged kernel is the
    same attention, addressed through a page table."""
    from paddle_tpu.ops.pallas.decode_attention import decode_attention
    from paddle_tpu.ops.pallas.paged_attention import paged_attention

    rs = np.random.RandomState(2)
    b, hq, hkv, d, ps, npool, p = 2, 4, 4, 8, 8, 8, 2
    q = jnp.asarray(rs.randn(b, hq, d), jnp.float32)
    kp = jnp.asarray(rs.randn(npool, hkv, ps, d), jnp.float32)
    vp = jnp.asarray(rs.randn(npool, hkv, ps, d), jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([11, 6], jnp.int32)
    k = jnp.moveaxis(kp[pt], 2, 1).reshape(b, hkv, p * ps, d)
    v = jnp.moveaxis(vp[pt], 2, 1).reshape(b, hkv, p * ps, d)
    dense = decode_attention(q, k, v, pos, interpret=True)
    paged = paged_attention(q, kp, vp, pt, pos, interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               rtol=2e-5, atol=2e-5)


def test_paged_attention_available_gating():
    from paddle_tpu.core import flags
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_available,
    )

    # CPU (interpret) never claims the compiled kernel
    assert not paged_attention_available((8, 2, 8, 16))
    old = flags.get_flags("FLAGS_disable_pallas_paged")
    flags.set_flags({"FLAGS_disable_pallas_paged": 1})
    try:
        assert not paged_attention_available((8, 2, 8, 16))
    finally:
        flags.set_flags(old)


# ------------------------------ engine equivalence ------------------------------

@pytest.mark.parametrize("page_size,slots,chunk", [
    (4, 2, 1),     # tiny pages: every sequence crosses many boundaries
    (8, 3, 1),     # mid batch
    (8, 3, 4),     # chunked scanned decode
    (16, 5, 8),    # whole batch resident, big chunks
])
def test_engine_matches_sequential_generate(gpt_model, prompts, refs,
                                            page_size, slots, chunk):
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=page_size, max_slots=slots, decode_chunk=chunk,
        max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=10)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o), (r.tolist(), o.tolist())
    assert_drained(eng)               # drained engine leaks nothing


def test_engine_page_boundary_exact_crossings(gpt_model):
    """Prompt+generation lengths landing exactly ON page boundaries
    (the off-by-one habitat: len % ps == 0 means the next token opens
    a fresh page)."""
    ps = 4
    prompts = [np.arange(1, n + 1, dtype=np.int32) % 127 + 1
               for n in (4, 8, 3, 5)]       # 4 and 8 are exact pages
    refs = [np.asarray(gpt_model.generate(
        P.to_tensor(p[None, :], "int32"), max_new_tokens=9)._value)[0]
        for p in prompts]
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=ps, max_slots=4, max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=9)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o)


def test_engine_slot_reuse_after_completion(gpt_model, prompts, refs):
    """More requests than slots: completed sequences' slots (and
    pages) are reused by later admissions, and every stream still
    matches its solo reference."""
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=10)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o)
    assert_drained(eng)
    # 5 sequences through 2 slots: slots were genuinely reused
    assert eng.scheduler.stats()["running"] == 0


def test_engine_eviction_recompute_identical(gpt_model, prompts, refs):
    """A pool too small for the batch forces mid-flight eviction; the
    preempted sequence re-prefills from prompt+generated and must
    continue the greedy stream token-identically."""
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=4, max_slots=2, num_pages=10, max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=10)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o)
    assert_drained(eng)


def test_engine_eos_matches_generate(gpt_model, prompts):
    eos = 7
    refs = [np.asarray(gpt_model.generate(
        P.to_tensor(p[None, :], "int32"), max_new_tokens=10,
        eos_token_id=eos)._value)[0] for p in prompts]
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=3, decode_chunk=4, max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=10, eos_token_id=eos)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o)


def test_engine_continuous_admission_mid_flight(gpt_model, prompts,
                                                refs):
    """Sequences submitted WHILE others are decoding enter freed/idle
    slots on the next step — continuous batching, not batch-boundary
    batching — and the late arrivals' outputs are unaffected by who
    they shared the batch with."""
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    first = [eng.submit(p, max_new_tokens=10) for p in prompts[:2]]
    for _ in range(3):
        eng.step()                      # mid-decode
    late = [eng.submit(p, max_new_tokens=10) for p in prompts[2:]]
    idle = 0
    handles = first + late
    while any(not h.done.is_set() for h in handles):
        idle = 0 if eng.step() else idle + 1
        assert idle < 1000, "engine stalled"
    for h, r in zip(handles, refs):
        assert np.array_equal(h.result(timeout=1.0), r)
    assert_drained(eng)


def test_engine_cancel_mid_decode_survivors_identical(gpt_model,
                                                      prompts, refs):
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=3, max_seq_len=64))
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts]
    for _ in range(2):
        eng.step()
    assert eng.cancel(handles[1].request_id)
    idle = 0
    while any(not h.done.is_set() for h in handles):
        idle = 0 if eng.step() else idle + 1
        assert idle < 1000, "engine stalled"
    assert handles[1].cancelled
    for i, h in enumerate(handles):
        if i != 1:
            assert np.array_equal(h.result(timeout=1.0), refs[i])
    assert_drained(eng)


def test_engine_defrag_mid_flight_preserves_streams(gpt_model, prompts,
                                                    refs):
    """Compacting the page pool between steps (device copies + table
    rewrite) must be invisible to the token streams."""
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=4, max_slots=3, max_seq_len=64))
    handles = [eng.submit(p, max_new_tokens=10) for p in prompts[:3]]
    for _ in range(2):
        eng.step()
    # finish one so its freed pages leave holes, then compact
    eng.cancel(handles[0].request_id)
    eng.step()
    moved = eng.defrag()
    assert moved >= 0                   # compaction ran
    idle = 0
    while any(not h.done.is_set() for h in handles[1:]):
        idle = 0 if eng.step() else idle + 1
        assert idle < 1000, "engine stalled"
    for i in (1, 2):
        assert np.array_equal(handles[i].result(timeout=1.0), refs[i])
    eng.clear_prefix_cache()
    assert eng.defrag() == 0 or eng.pool.used_pages == 0


def test_engine_tight_pool_near_finish_line_completes(gpt_model):
    """End-to-end regression for the growth-clamp stall: a pool holding
    exactly one sequence's lifetime pages, with a decode chunk that
    overshoots the finish line, must run to completion (and still match
    sequential generate())."""
    p = np.arange(1, 9, dtype=np.int32)            # 8 + 8 = 2x8 pages
    ref = np.asarray(gpt_model.generate(
        P.to_tensor(p[None, :], "int32"), max_new_tokens=8)._value)[0]
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, num_pages=3, max_slots=1, decode_chunk=5,
        max_seq_len=64))
    out = eng.generate([p], max_new_tokens=8)[0]
    assert np.array_equal(out, ref)
    assert_drained(eng)


def test_engine_cancel_drops_handle_and_config_not_mutated(gpt_model,
                                                           prompts):
    """Cancelled requests must not leak their handles (one per client
    disconnect on a long-running server), and a config object reused
    across engines must not carry the first engine's resolution."""
    cfg = EngineConfig(page_size=8, max_slots=2)
    eng = InferenceEngine(gpt_model, cfg)
    assert cfg.max_seq_len == 0 and cfg.num_pages == 0  # caller's copy
    assert eng.config.max_seq_len == 64                 # engine's own
    handles = [eng.submit(p, max_new_tokens=8) for p in prompts[:3]]
    eng.step()
    for h in handles:
        eng.cancel(h.request_id)
    eng.step()
    assert eng._handles == {}
    assert_drained(eng)
    # completed (non-cancelled) requests are dropped too
    out = eng.generate([prompts[0]], max_new_tokens=4)
    assert eng._handles == {} and len(out) == 1


def test_engine_llama_gqa_matches_generate():
    """GQA coverage: llama with num_kv_heads < num_heads runs the
    grouped paged kernel path (and rope over per-row vector
    positions)."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    P.seed(3)
    cfg = LlamaConfig(vocab_size=128, hidden_size=32, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=64,
                      ffn_hidden=64)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, (n,)).astype(np.int32)
               for n in (4, 11, 7)]
    refs = [np.asarray(model.generate(
        P.to_tensor(p[None, :], "int32"), max_new_tokens=8)._value)[0]
        for p in prompts]
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    outs = eng.generate(prompts, max_new_tokens=8)
    for r, o in zip(refs, outs):
        assert np.array_equal(r, o)


def test_engine_gauges_spans_and_counters(gpt_model, prompts):
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics, trace

    obs.attach(crash_hook=False)
    metrics.reset()
    obs.attach(crash_hook=False)        # re-declare schema after reset
    try:
        eng = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=2, max_seq_len=64))
        eng.generate(prompts[:3], max_new_tokens=4)
        snap = metrics.snapshot()
        c = snap["counters"]
        assert c.get("engine.sequences{event=submitted}") == 3
        assert c.get("engine.sequences{event=admitted}") == 3
        assert c.get("engine.sequences{event=completed}") == 3
        assert c.get("engine.tokens") == 12
        g = snap["gauges"]
        assert g.get("engine.active_sequences") == 0
        # the prefix cache deliberately retains committed prompt pages
        # across requests (ISSUE 13): the published utilization matches
        # the pool's cache-held view, and clearing the cache empties it
        assert g.get("engine.page_utilization") == eng.pool.utilization()
        assert_drained(eng)
        assert eng.pool.utilization() == 0
        names = {e.get("name") for e in trace.events()}
        for phase in ("engine.schedule", "engine.prefill",
                      "engine.decode", "engine.detokenize"):
            assert phase in names, names
    finally:
        obs.detach()


# ------------------------------ serving ------------------------------

@pytest.fixture()
def gen_server(gpt_model):
    from paddle_tpu.inference.serving import InferenceServer

    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    srv = InferenceServer(engine=eng, request_timeout=60.0,
                          queue_depth=0).start()
    yield srv
    srv.shutdown()


def test_generate_endpoint_streams_and_matches(gen_server, prompts,
                                               refs):
    from paddle_tpu.inference.serving import InferenceClient

    cli = InferenceClient(gen_server.address, timeout=60.0)
    streamed = []
    r = cli.generate(prompts[0], max_new_tokens=10,
                     on_token=streamed.append)
    assert np.array_equal(r["output_ids"], refs[0])
    assert streamed == r["tokens"] and len(streamed) == 10
    assert r["finish_reason"] == "length"
    # concurrent clients, mixed lengths, same answers
    outs = [None] * 3

    def one(i):
        c = InferenceClient(gen_server.address, timeout=60.0)
        outs[i] = c.generate(prompts[i], max_new_tokens=10)

    ts = [threading.Thread(target=one, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    for i in range(3):
        assert np.array_equal(outs[i]["output_ids"], refs[i])
    assert_drained(gen_server.engine)


def test_generate_endpoint_eos_and_bad_body(gen_server, prompts):
    import urllib.error
    import urllib.request

    from paddle_tpu.inference.serving import InferenceClient

    cli = InferenceClient(gen_server.address, timeout=60.0)
    r = cli.generate(prompts[0], max_new_tokens=10, eos_token_id=7)
    if 7 in r["tokens"]:
        assert r["finish_reason"] == "eos"
        assert r["tokens"][-1] == 7
    # undecodable body -> 400 with the request id echoed
    req = urllib.request.Request(
        gen_server.address + "/generate", data=b"not json",
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "bad-body-req"})
    with pytest.raises(urllib.error.HTTPError) as ei:
        urllib.request.urlopen(req, timeout=10)
    assert ei.value.code == 400
    assert ei.value.headers.get("X-Request-Id") == "bad-body-req"


def test_generate_shed_retries_same_request_id(gpt_model, prompts):
    """Saturate the engine's admission (slots busy, queue 0), then a
    retrying client must shed with 429+Retry-After and succeed on a
    later attempt under the SAME X-Request-Id (the PR 7 discipline)."""
    from paddle_tpu.inference.serving import (
        InferenceClient, InferenceServer,
    )

    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=1, max_seq_len=64))
    # warm the compiled programs: the blocker must hold the slot for
    # its DECODE time, not for a first-call XLA compile, or the shed
    # client exhausts its retry budget against the compiler
    eng.generate([prompts[2]], max_new_tokens=2)
    srv = InferenceServer(engine=eng, request_timeout=60.0,
                          queue_depth=0).start()
    try:
        seen_ids = []
        orig_submit = eng.submit

        def spy(ids, **kw):
            seen_ids.append(kw.get("request_id"))
            return orig_submit(ids, **kw)

        eng.submit = spy
        blocker = InferenceClient(srv.address, timeout=60.0)
        done = threading.Event()

        def long_one():
            blocker.generate(prompts[1], max_new_tokens=16)
            done.set()

        t = threading.Thread(target=long_one)
        t.start()
        # wait until the blocker owns the only admission slot
        for _ in range(200):
            if srv.gen_admission.stats()["inflight"] >= 1:
                break
            import time as _t
            _t.sleep(0.005)
        # the shed Retry-After is ~0 until the first completion seeds
        # the latency EWMA, so each retry waits the client-side 50 ms
        # floor — budget enough of them to outlast the blocker's decode
        cli = InferenceClient(srv.address, timeout=60.0, retries=60,
                              max_retry_wait=0.5)
        r = cli.generate(prompts[0], max_new_tokens=4)
        t.join(timeout=60)
        assert done.is_set()
        assert len(r["tokens"]) == 4
        # the successful attempt reused the id of the shed attempts:
        # exactly one engine submission, and the client counted sheds
        assert r["request_id"] in seen_ids
        from paddle_tpu.observability import metrics
        # the shed is visible in the SLO ledger under its reason
        slo = srv.slo.report(publish_gauges=False)
        gen_ep = slo.get("endpoints", {}).get("generate", {})
        sheds = {k: v for k, v in
                 gen_ep.get("errors_by_reason", {}).items()
                 if k.startswith("shed:")}
        assert sum(sheds.values()) >= 1, slo
    finally:
        srv.shutdown()


# ------------------------------ satellites ------------------------------

def test_perf_smoke_paged_decode_within_budget():
    """Tier-1 perf-audit gate for the NEW hot program: the paged decode
    step audits cleanly (no PT400 blindness) and every metric holds the
    committed tools/perf_budget.json ceiling — a layout/transpose
    regression in the paged path fails here before any hardware run."""
    from paddle_tpu import analysis as A
    from paddle_tpu.analysis import perf_audit

    violations, metrics = perf_audit.audit_perf(
        programs=("paged_decode_step",), repo_root=REPO)
    assert not [v for v in violations if v.rule == "PT400"], \
        A.render_report(violations)
    m = metrics["gpt_paged_decode_step"]
    assert m["pt405_loop_host_syncs"] == 0   # the scan stays on device
    budget = A.load_budget(
        os.path.join(REPO, "tools", "perf_budget.json"))
    reg, _imp, _ = A.diff_against_budget(metrics, budget)
    assert reg == [], A.render_budget_diff(reg, [])


def test_bench_serving_decode_emits_and_beats_sequential():
    """The multi-client continuous-batching bench line: emitted with
    the degraded mark on the CPU proxy, and the engine beats
    single-stream sequential decode on the same proxy by batching
    alone (the ISSUE 8 acceptance comparison, measured in-process)."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.pop(0)
    r = bench._bench_serving_decode(True)
    assert r["metric"] == "serving_decode_tokens_per_sec"
    assert r["value"] > 0 and r["degraded"]
    assert r["sequential_tokens_per_sec"] > 0
    assert r["batching_speedup"] > 1.0, r


def test_perf_gate_serving_metric_round_trip(tmp_path):
    """serving_decode_tokens_per_sec is gateable: --update registers a
    non-degraded row in the baseline, an equal rerun passes, a drop
    beyond tolerance exits 2."""
    gate = os.path.join(REPO, "tools", "perf_gate.py")
    base = tmp_path / "baseline.jsonl"
    res = tmp_path / "results.json"
    row = {"metric": "serving_decode_tokens_per_sec", "value": 1000.0,
           "unit": "tokens/s", "sequential_tokens_per_sec": 300.0,
           "batching_speedup": 3.3}
    base.write_text(json.dumps(row) + "\n")

    def run(value):
        res.write_text(json.dumps(dict(row, value=value)) + "\n")
        return subprocess.run(
            [sys.executable, gate, str(res), "--baseline", str(base),
             "--static-budget", ""],
            capture_output=True, text=True)

    assert run(1000.0).returncode == 0
    assert run(990.0).returncode == 0        # within 10% tolerance
    p = run(500.0)
    assert p.returncode == 2 and "regression" in p.stderr
    # --update rolls the floor forward after a win
    res.write_text(json.dumps(dict(row, value=1500.0)) + "\n")
    p = subprocess.run(
        [sys.executable, gate, str(res), "--baseline", str(base),
         "--static-budget", "", "--update"],
        capture_output=True, text=True)
    assert p.returncode == 0 and "updated" in p.stdout
    assert run(1400.0).returncode == 0       # new floor active
    assert run(1000.0).returncode == 2


# ------------- ISSUE 33: what the engine says about itself -------------

def _run(eng, handles):
    """Step inline until every handle is done."""
    idle = 0
    while any(not h.done.is_set() for h in handles):
        idle = 0 if eng.step() else idle + 1
        assert idle < 1000, "engine made no progress"


def _reference_logprobs(model, out, n_prompt):
    """Teacher-forced: one full float32 forward over prompt + delivered
    tokens, a plain log_softmax, read at the delivered tokens."""
    logits = np.asarray(
        model(P.to_tensor(out[None, :], "int32"))._value, np.float32)[0]
    ref = np.asarray(jax.nn.log_softmax(jnp.asarray(logits), axis=-1))
    return np.array([ref[n_prompt - 1 + i, out[n_prompt + i]]
                     for i in range(out.size - n_prompt)])


def _draft():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    P.seed(7)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=128, hidden_size=16, num_layers=1, num_heads=2,
        max_seq_len=64))
    model.eval()
    return model


def _lp_cold(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    hs = [eng.submit(prompts[1], max_new_tokens=1)]
    _run(eng, hs)
    assert hs[0].cache_state == "miss" and len(hs[0].logprobs) == 1
    return hs


def _lp_chunk(chunk):
    def case(model, prompts):
        eng = InferenceEngine(model, EngineConfig(
            page_size=8, max_slots=3, decode_chunk=chunk,
            max_seq_len=64))
        hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
        _run(eng, hs)
        return hs
    return case


def _lp_warm(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    first = [eng.submit(prompts[2], max_new_tokens=4)]
    _run(eng, first)
    # shares prompts[2]'s two committed pages, then goes its own way
    again = np.concatenate([prompts[2][:16], prompts[4]])
    hs = [eng.submit(again, max_new_tokens=6)]
    _run(eng, hs)
    assert hs[0].cache_state in ("hit", "partial")
    assert hs[0]._seq.shared_len == 16
    return first + hs


def _lp_preempted(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=4, max_slots=2, num_pages=10, max_seq_len=64))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _run(eng, hs)
    assert any(h._seq.evictions for h in hs)   # someone was resumed
    return hs


def _lp_spec(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=3, max_seq_len=64, spec_tokens=3),
        draft_model=_draft())
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _run(eng, hs)
    return hs


def _lp_kv_int8(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=3, decode_chunk=2, max_seq_len=64,
        kv_precision="int8"))
    hs = [eng.submit(p, max_new_tokens=10) for p in prompts]
    _run(eng, hs)
    return hs


def _lp_two_lengths(model, prompts):
    eng = InferenceEngine(model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    hs = [eng.submit(prompts[0], max_new_tokens=8),     # 3 tokens
          eng.submit(prompts[2], max_new_tokens=8)]     # 17 tokens
    eng.step()
    assert eng.scheduler.stats()["running"] == 2    # one batch
    _run(eng, hs)
    return hs


@pytest.mark.parametrize("case,tol", [
    (_lp_cold, 1e-4), (_lp_chunk(1), 1e-4), (_lp_chunk(4), 1e-4),
    (_lp_warm, 1e-4), (_lp_preempted, 1e-4), (_lp_spec, 1e-4),
    # the int8-KV tier's stated tolerance: its pages round each K/V
    # vector to 8 bits, read 3.0e-4 at the most here
    (_lp_kv_int8, 2e-3), (_lp_two_lengths, 1e-4),
], ids=["cold_first_token", "decode_chunk1", "decode_chunk4",
        "warm_prefill", "preempted_resumed", "speculative", "kv_int8",
        "two_lengths_one_batch"])
def test_delivered_logprobs_match_teacher_forced_reference(
        gpt_model, prompts, case, tol):
    """(a) Every delivered token carries `logit[token] -
    logsumexp(logits)` of the program that chose it: equal to a plain
    float32 forward over prompt + delivered tokens on every path a
    token can take to the stream."""
    for h in case(gpt_model, prompts):
        out = h.result()
        n0 = out.size - len(h.tokens)
        assert len(h.logprobs) == len(h.tokens) > 0
        assert list(h.stream(timeout=1, with_logprobs=True)) == \
            list(zip(h.tokens, h.logprobs))
        want = _reference_logprobs(gpt_model, out, n0)
        np.testing.assert_allclose(h.logprobs, want, rtol=0, atol=tol)


@pytest.mark.parametrize("seed", [11, 12, 13])
def test_logprob_gap_sees_int8_weights_under_bfloat16(prompts, seed):
    """(b) What a serve cell's `correct` will lean on, at CPU size: on
    a model cast to bfloat16, the mean |log-probability - float32
    reference over the same bfloat16-rounded weights| is larger with
    the engine's int8 weight tier than without.  Read here (120
    positions a seed): 5.7e-4 / 8.1e-4, 6.0e-4 / 9.7e-4, 6.0e-4 /
    9.5e-4 — factors 1.43, 1.60, 1.57; 1.2 is asked."""
    served = _gpt(seed=seed)
    served.bfloat16()
    ref = _gpt(seed=seed)
    for (_, p), (_, q) in zip(ref.named_parameters(),
                              served.named_parameters()):
        p._value = q._value.astype(jnp.float32)
    gap = {}
    for tier in (None, "int8"):
        eng = InferenceEngine(served, EngineConfig(
            page_size=8, max_slots=3, max_seq_len=64,
            weight_precision=tier))
        hs = [eng.submit(p, max_new_tokens=24) for p in prompts]
        _run(eng, hs)
        gap[tier] = np.mean(np.concatenate([
            np.abs(_reference_logprobs(ref, h.result(), p.size)
                   - np.array(h.logprobs))
            for h, p in zip(hs, prompts)]))
    assert gap["int8"] > 1.2 * gap[None], gap


def _raw_generate(address, body):
    """POST /generate and return the stream's bytes, line by line."""
    import urllib.request

    req = urllib.request.Request(
        address + "/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json",
                 "X-Request-Id": "raw-" + str(len(body))})
    with urllib.request.urlopen(req, timeout=60) as r:
        return r.read().splitlines(keepends=True)


def test_generate_stream_without_logprobs_is_byte_for_byte(
        gen_server, gpt_model, prompts, refs):
    """(f) A request that does not ask sees exactly the old stream."""
    lines = _raw_generate(gen_server.address, {
        "input_ids": prompts[1].tolist(), "max_new_tokens": 10})
    toks = refs[1][prompts[1].size:]
    want = [json.dumps({"token": int(t)}).encode() + b"\n" for t in toks]
    want.append(json.dumps({
        "done": True, "request_id": "raw-2", "finish_reason": "length",
        "output_ids": [int(x) for x in refs[1]]}).encode() + b"\n")
    assert lines == want


def test_generate_stream_with_logprobs_carries_every_value(
        gen_server, gpt_model, prompts, refs):
    """(f) With `"logprobs": true` every token event and the final body
    carry the value, and the client hands them back."""
    from paddle_tpu.inference.serving import InferenceClient

    lines = [json.loads(x) for x in _raw_generate(gen_server.address, {
        "input_ids": prompts[1].tolist(), "max_new_tokens": 10,
        "logprobs": True})]
    events, final = lines[:-1], lines[-1]
    assert [e["token"] for e in events] == \
        [int(t) for t in refs[1][prompts[1].size:]]
    assert all(set(e) == {"token", "logprob"} for e in events)
    assert final["logprobs"] == [e["logprob"] for e in events]
    want = _reference_logprobs(gpt_model, refs[1], prompts[1].size)
    np.testing.assert_allclose(final["logprobs"], want, rtol=0,
                               atol=1e-4)
    cli = InferenceClient(gen_server.address, timeout=60.0)
    r = cli.generate(prompts[1], max_new_tokens=10, logprobs=True)
    assert r["logprobs"] == final["logprobs"]
    assert "logprobs" not in cli.generate(prompts[1], max_new_tokens=2)


def test_generate_logprobs_refused_by_an_engine_without_logits():
    """An engine duck-type that has no logits answers 400 to
    `"logprobs": true` and streams as ever without it."""
    import urllib.error

    from paddle_tpu.inference.fleet import ToyEngine
    from paddle_tpu.inference.serving import InferenceServer

    srv = InferenceServer(engine=ToyEngine(max_slots=2, token_time=0.0),
                          request_timeout=30.0).start()
    try:
        body = {"input_ids": [1, 2, 3], "max_new_tokens": 3}
        assert len(_raw_generate(srv.address, body)) == 4
        with pytest.raises(urllib.error.HTTPError) as ei:
            _raw_generate(srv.address, dict(body, logprobs=True))
        assert ei.value.code == 400
        assert "log-probabilities" in ei.value.read().decode()
    finally:
        srv.shutdown()


def test_same_programs_with_and_without_logprobs(gpt_model, prompts):
    """(c) The log-probability is always computed: asking for it
    compiles nothing, one decode program and the same prefill programs
    either way."""
    from paddle_tpu.inference.serving import (
        InferenceClient, InferenceServer,
    )

    programs = {}
    for ask in (False, True):
        eng = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=2, max_seq_len=64))
        srv = InferenceServer(engine=eng, request_timeout=60.0).start()
        try:
            cli = InferenceClient(srv.address, timeout=60.0)
            for p in prompts[:3]:
                cli.generate(p, max_new_tokens=6, logprobs=ask)
        finally:
            srv.shutdown()
        programs[ask] = sorted(eng._programs, key=repr)
    assert programs[False] == programs[True]
    kinds = [k[0] for k in programs[True]]
    assert kinds.count("decode") == 1 and kinds.count("prefill") == 2


def test_step_accounting_matches_a_scripted_run(gpt_model, prompts):
    """(d) `batch` / `live_tokens` on `engine.decode`, the prefill
    span's fields and the counters, against a run small enough to
    count by hand: prompts of 3, 9 and 17 tokens wanting 2, 5 and 5,
    pages of 8.  One step prefills all three (a token each), then four
    decode steps; the short one finishes after the first."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics, trace

    obs.attach(crash_hook=False)
    metrics.reset()
    obs.attach(crash_hook=False)
    trace.clear()
    try:
        eng = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=3, max_seq_len=64,
            prefill_bucket=16))
        hs = [eng.submit(prompts[i], max_new_tokens=n)
              for i, n in ((0, 2), (1, 5), (2, 5))]
        truth = []          # the scheduler's own view, step by step
        while any(not h.done.is_set() for h in hs):
            eng.step()
            truth.append(sorted(len(h.tokens) for h in hs))
        assert truth == [[2, 2, 2], [2, 3, 3], [2, 4, 4], [2, 5, 5]]
        assert not eng.step()                       # one idle step
        ev = [e for e in trace.events() if e.get("ph") == "X"]
        dec = [e["args"] for e in ev if e["name"] == "engine.decode"]
        assert [a["batch"] for a in dec] == [3, 2, 2, 2]
        # cached positions at each step's start: 3+9+17, 10+18, ...
        assert [a["live_tokens"] for a in dec] == [29, 28, 30, 32]
        pre = [e["args"] for e in ev if e["name"] == "engine.prefill"]
        assert [(a["tokens"], a["cached_tokens"]) for a in pre] == \
            [(3, 0), (9, 0), (17, 0)]
        sch = [e["args"] for e in ev if e["name"] == "engine.schedule"]
        assert (sch[0]["admitted"], sch[0]["waiting"],
                sch[0]["free_slots"]) == (3, 0, 0)
        assert sch[1]["free_slots"] == 1            # the short one left
        c = metrics.snapshot()["counters"]
        assert c["engine.steps{kind=decode}"] == 4
        assert c["engine.decode_slots"] == 3 + 2 + 2 + 2
        assert c["engine.decode_live_tokens"] == 29 + 28 + 30 + 32
        assert c["engine.prefill_tokens{cache=miss}"] == 3 + 9 + 17
        assert c["engine.prefill_tokens{cache=hit}"] == 0
        h = metrics.snapshot()["histograms"]
        assert h["engine.submit_wait_ms"]["count"] == 3
        assert h["engine.admit_wait_ms"]["count"] == 3
        assert h["engine.lock_wait_ms{who=loop}"]["count"] == 5
        kinds = [e["kind"] for e in
                 eng.request_debug(hs[0].request_id)["events"]]
        assert kinds[:4] == ["submitted", "queued", "admitted",
                             "prefill_start"]
    finally:
        obs.detach()


def test_step_accounting_counts_speculative_passes(gpt_model, prompts):
    """(d) with a draft model: one `engine.steps{kind=decode}` a
    speculative pass, so the documented means (`engine.decode_slots`
    and `engine.decode_live_tokens` over that count) hold in either
    mode.  How many tokens a pass commits depends on the draft, so the
    truth is read off the sequences before each step."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics, trace

    obs.attach(crash_hook=False)
    metrics.reset()
    obs.attach(crash_hook=False)
    trace.clear()
    try:
        eng = InferenceEngine(gpt_model, EngineConfig(
            page_size=8, max_slots=3, max_seq_len=64, spec_tokens=2),
            draft_model=_draft())
        hs = [eng.submit(prompts[i], max_new_tokens=n)
              for i, n in ((0, 2), (1, 7), (2, 7))]
        truth = [(3, 3 + 9 + 17)]     # the first step prefills all
        eng.step()
        while any(not h.done.is_set() for h in hs):
            live = [h._seq for h in hs if not h._seq.done]
            truth.append((len(live), sum(s.length for s in live)))
            eng.step()
        assert len(truth) >= 3 and truth[1][0] == 2
        dec = [e["args"] for e in trace.events()
               if e.get("ph") == "X" and e["name"] == "engine.decode"]
        assert all(a["spec"] for a in dec)
        assert [(a["batch"], a["live_tokens"]) for a in dec] == truth
        c = metrics.snapshot()["counters"]
        assert c["engine.steps{kind=decode}"] == len(truth)
        assert c["engine.decode_slots"] == sum(b for b, _ in truth)
        assert c["engine.decode_live_tokens"] == sum(
            n for _, n in truth)
    finally:
        obs.detach()


def test_submit_and_cancel_do_not_wait_for_a_step(gpt_model, prompts):
    """(e) No timing: a decode program that blocks on an Event holds a
    step (and the step lock) open; submit() and cancel() from another
    thread return, and the new sequence stands in the scheduler's
    queue, while the step is still blocked.  On the parent both calls
    sat in `with self._lock` until the step ended."""
    from paddle_tpu import observability as obs
    from paddle_tpu.observability import metrics

    obs.attach(crash_hook=False)
    metrics.reset()
    obs.attach(crash_hook=False)
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64))
    entered, release = threading.Event(), threading.Event()
    real = eng._decode_program(1)

    def blocked(*args):
        entered.set()
        assert release.wait(60)
        return real(*args)

    eng._programs[("decode", 1, False)] = blocked
    try:
        first = eng.submit(prompts[0], max_new_tokens=4)
        eng.start()
        assert entered.wait(60)             # a step is open, lock held
        got = {}

        def arrive():
            got["kept"] = eng.submit(prompts[1], max_new_tokens=4)
            got["dropped"] = eng.submit(prompts[3], max_new_tokens=4)
            got["cancel"] = eng.cancel(got["dropped"].request_id)

        t = threading.Thread(target=arrive, daemon=True)
        t.start()
        t.join(5)
        assert not t.is_alive(), "submit()/cancel() waited for the step"
        assert not release.is_set() and not first.done.is_set()
        assert eng.scheduler.waiting_sequences == 2     # queued NOW
        assert got["cancel"] and got["dropped"].cancelled
        h = metrics.snapshot()["histograms"]
        assert h["engine.submit_wait_ms"]["count"] == 3  # one a call
        release.set()
        assert len(got["kept"].result(timeout=60)) == prompts[1].size + 4
        assert len(first.result(timeout=60)) == prompts[0].size + 4
    finally:
        release.set()
        eng.stop()
        obs.detach()


def test_close_frees_device_state_and_is_idempotent(gpt_model, prompts):
    """(g) close() stops the loop, finishes what is in flight as
    cancelled, drops pools, weights and programs; again is a no-op;
    nothing can be submitted afterwards; the host view stays."""
    eng = InferenceEngine(gpt_model, EngineConfig(
        page_size=8, max_slots=2, max_seq_len=64, spec_tokens=2),
        draft_model=_draft())
    eng.generate(prompts[:2], max_new_tokens=3)
    assert eng._programs and eng._k_pools is not None
    pending = eng.submit(prompts[2], max_new_tokens=4)
    eng.step()
    eng.start()
    eng.close()
    eng.close()
    assert pending.done.is_set() and pending.cancelled
    assert eng._thread is None and not eng._programs
    for name in ("_k_pools", "_v_pools", "_k_scales", "_v_scales",
                 "_params", "_buffers", "_draft"):
        assert getattr(eng, name) is None, name
    assert eng.scheduler.stats()["running"] == 0
    assert eng.stats()["pages"]["used"] == eng.pool.used_pages
    assert not eng.step()
    with pytest.raises(RuntimeError, match="closed"):
        eng.submit(prompts[0], max_new_tokens=2)
    with pytest.raises(RuntimeError, match="closed"):
        eng.start()


@pytest.mark.parametrize("phase,extra", [
    ("witness", []), ("gap", ["--controls", "1"])])
def test_serve_probe_rehearses(phase, extra):
    """tools/serve_probe.py runs end to end at its tiny CPU size: the
    witness reads the engine's own counters equal to what its request
    sizes imply; the gap phase reads the log-probability gap after
    `close()` freed the engine."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "serve_probe.py"),
         "--rehearse", "--phase", phase, "--seed", "3300000101", *extra],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["phase"] == phase
    if phase == "witness":
        assert line["done"] == 15
        assert line["submit_wait_ms"]["n"] == 15
        assert line["schedule_left_waiting_beside_free_slot"] == 0
        for key in ("decode_slots_sum", "decode_live_tokens_sum"):
            assert line[key]["counter"] == line[key]["implied"] > 0
    else:
        assert line["positions"] > 0
        # float8 weights read further from the reference than the
        # bfloat16 program does, even at this size
        assert line["control_fp8"]["mean"] > line["program"]["mean"] > 0


@pytest.mark.chaos
def test_engine_chaos_scenario():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import chaos_check
    finally:
        sys.path.pop(0)
    report = chaos_check.run_engine_chaos(seed=0)
    assert report["recovered"], report
