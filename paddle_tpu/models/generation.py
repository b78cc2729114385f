"""Auto-regressive generation over static KV caches.

Role parity: the reference's decode serving path — `AnalysisPredictor` +
`masked_multihead_attention`/`block_multi_head_attention` decode kernels
(`paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu`) and
the generation loops its ecosystem builds on them.

TPU-first design: the naive concat KV cache grows the sequence axis every
token — a new shape per step, so XLA recompiles per token. Here the cache
is a FIXED-shape buffer `[B, H, max_len, D]` per layer written with
`lax.dynamic_update_slice` at a traced position, so generation compiles
exactly twice (one prefill program, one decode-step program) regardless
of length. The decode step attends with the Pallas `decode_attention`
kernel on TPU (position-masked paged read, logits never materialized) and
tokens stay on device between steps — the host loop dispatches
asynchronously and fetches once at the end (or per step only when
`eos_token_id` needs checking).
"""
from __future__ import annotations

import contextlib
import functools
import threading

import numpy as np

import jax
import jax.numpy as jnp

from ..core import flags, rng
from ..core.tensor import Tensor

# decode steps per compiled lax.scan dispatch (generate's fast path): the
# host leaves the token loop for this many steps at a time
DECODE_CHUNK = 32

# --- warm (cached-prefix) tail prefill -------------------------------------
# Trace-time switch for prefix caching (inference/engine, ISSUE 13): a
# multi-token dense forward normally assumes cache_pos == 0 and attends
# only its own fresh K/V (cold prefill).  Inside `warm_prefill_guard(P)`
# the same forward is a WARM TAIL PREFILL: the dense cache buffers
# arrive pre-loaded with a cached prefix at [0, P) (P is a TRACED
# page-aligned scalar), the fresh tokens write at [P, P+S), and every
# query attends the prefix plus the causal fresh span.  A thread-local
# rather than a model kwarg: the flag is static PER TRACE (the engine
# enters the guard inside its jitted cached-prefill program), so no
# model-family forward signature has to grow a parameter.
_WARM_PREFILL = threading.local()


@contextlib.contextmanager
def warm_prefill_guard(prefix_len):
    """`prefix_len`: traced int32 scalar — the number of cached prefix
    tokens already sitting in the dense cache buffers at [0, P)."""
    prev = getattr(_WARM_PREFILL, "value", None)
    _WARM_PREFILL.value = prefix_len
    try:
        yield
    finally:
        _WARM_PREFILL.value = prev


def _static_cache_attention(q, k, v, kv_cache, cache_pos, attn_start=None):
    """Shared attention-over-static-cache body for the model families.

    q: [B, S, Hq, D]; k/v: [B, S, Hkv, D] (GQA: Hkv may divide Hq — the
    cache stores KV heads, NOT expanded query heads, so GQA's decode
    bandwidth advantage survives); kv_cache: (k_buf, v_buf) Tensors
    [B, Hkv, max_len, D]; cache_pos: scalar int Tensor — write offset of
    this call's tokens; attn_start: optional [B] int Tensor — first
    NON-PAD position per row (left-padded ragged prompts). Prefill
    (S > 1) assumes cache_pos == 0 and runs causal attention over the
    fresh K/V (with pad columns masked); decode (S == 1) reads the cache
    through the Pallas `decode_attention` kernel (grouped queries per KV
    head), masked to attn_start <= j <= cache_pos.
    Returns (out [B, S, Hq, D], (k_buf, v_buf)).

    Paged tier (inference/engine): a 3-tuple kv_cache
    ``(k_pages, v_pages, page_table)`` with a per-row [B] cache_pos
    vector routes to `_paged_cache_attention` — per-sequence ragged
    positions over a shared page pool instead of the lockstep dense
    buffers."""
    import importlib

    from .. import ops
    from ..core.dispatch import apply
    from ..nn import functional as F

    if isinstance(kv_cache, (tuple, list)) and len(kv_cache) in (3, 5):
        return _paged_cache_attention(q, k, v, kv_cache, cache_pos)

    DA = importlib.import_module("paddle_tpu.ops.pallas.decode_attention")

    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if s == 1:
        # decode step: [B,1,Hkv,D] -> [B,Hkv,1,D] is a pure reshape
        # (identical element order) — the cache write stays
        # transpose-free on the per-token hot path (PT401 budget on
        # the scanned decode program holds this at zero new relayouts)
        kt = ops.reshape(k, [b, hkv, 1, d])
        vt = ops.reshape(v, [b, hkv, 1, d])
    else:
        kt = ops.transpose(k, [0, 2, 1, 3])
        vt = ops.transpose(v, [0, 2, 1, 3])
    kb, vb = kv_cache

    def upd(buf, new, p):
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0, 0, p, 0))

    kb = apply("kv_cache_update", upd, kb, kt, cache_pos)
    vb = apply("kv_cache_update", upd, vb, vt, cache_pos)
    if s == 1:
        def dec(q1, kb_, vb_, p, st):
            pos = jnp.broadcast_to(p, (q1.shape[0],))
            return DA.decode_attention(q1, kb_, vb_, pos, start=st)

        q1 = q.reshape([b, hq, d])
        out = apply("decode_attention", dec, q1, kb, vb, cache_pos,
                    attn_start)
        out = out.reshape([b, 1, hq, d])
    else:
        wp = getattr(_WARM_PREFILL, "value", None)
        if wp is not None:
            # WARM tail prefill (prefix caching): keys/values come from
            # the CACHE BUFFER — cached prefix at [0, P) plus the fresh
            # tail this call just wrote at [P, P+S) — not from the
            # fresh K/V alone.  Query row i (real iff i >= attn_start)
            # holds absolute position P + i - start; it attends every
            # prefix key (j < P, all real: committed pages carry no
            # padding) and the causal fresh span (start <= j-P <= i).
            # Keys in [P_real, buffer_cap) beyond the written span stay
            # masked, so a bucketed prefix capacity never leaks
            # garbage into the softmax.
            cap = kb.shape[2]
            kk = ops.transpose(kb, [0, 2, 1, 3])      # [B, cap, Hkv, D]
            vv = ops.transpose(vb, [0, 2, 1, 3])
            if hkv != hq:
                rep = hq // hkv
                kk = ops.repeat_interleave(kk, rep, axis=2)
                vv = ops.repeat_interleave(vv, rep, axis=2)
            st = attn_start if attn_start is not None \
                else ops.zeros([b], dtype="int32")

            def build_warm_mask(st_, p_):
                j = jnp.arange(cap)[None, None, :]    # key column
                i = jnp.arange(s)[None, :, None]      # query row
                jj = j - p_                           # fresh-span index
                valid = (j < p_) | ((jj >= st_[:, None, None])
                                    & (jj <= i))
                return jnp.where(valid[:, None], 0.0, -1e30)

            mask = apply("warm_prefill_mask", build_warm_mask, st,
                         wp if isinstance(wp, Tensor) else Tensor(wp))
            out = F.scaled_dot_product_attention(
                q, kk, vv, attn_mask=mask, dropout_p=0.0,
                training=False)
            return out, (kb, vb)
        if hkv != hq:
            rep = hq // hkv
            k = ops.repeat_interleave(k, rep, axis=2)
            v = ops.repeat_interleave(v, rep, axis=2)
        mask = None
        if attn_start is not None:
            def build_mask(st):
                j = jnp.arange(s)[None, :]                    # key pos
                i = jnp.arange(s)[:, None]                    # query pos
                valid = (j <= i)[None] & (j[None] >= st[:, None, None])
                return jnp.where(valid[:, None], 0.0, -1e30)  # [B,1,S,S]

            mask = apply("prefill_pad_mask", build_mask, attn_start)
            out = F.scaled_dot_product_attention(
                q, k, v, attn_mask=mask, dropout_p=0.0, training=False)
        else:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, dropout_p=0.0, training=False)
    return out, (kb, vb)


def _paged_cache_attention(q, k, v, kv_cache, cache_pos):
    """Paged decode attention (inference/engine tier).

    q: [B, 1, Hq, D]; k/v: [B, 1, Hkv, D]; kv_cache:
    ``(k_pages, v_pages, page_table)`` Tensors — pools
    [num_pages, Hkv, page_size, D] shared across sequences, page_table
    [B, P] int32 (unused tail entries point at the reserved scratch
    page 0); cache_pos: [B] int32 Tensor — each row's write index (==
    its current length).  The current token's K/V scatters into the
    row's live page at (page_table[b, pos//ps], pos % ps), then the
    ragged paged-attention kernel attends 0..pos[b] per row.  Free/dead
    batch slots ride along with pos=0 and an all-scratch page table —
    their writes land in page 0 and their outputs are discarded by the
    engine, so the compiled shape never changes with occupancy.

    Quantized KV tier (ISSUE 12): a 5-tuple
    ``(k_pages, v_pages, page_table, k_scales, v_scales)`` with int8
    pools and per-token-per-head scale tables
    [num_pages, Hkv, page_size].  The write path quantizes each fresh
    K/V head-vector independently (`ops.quant.quantize_vectors` — no
    neighbour requantization, so page writes stay single-slot
    scatters), stores int8 + scale, and the attention dequantizes in
    VMEM.  Returns (out [B, 1, Hq, D], new kv_cache of the same
    arity)."""
    import importlib

    from ..core.dispatch import apply

    PA = importlib.import_module("paddle_tpu.ops.pallas.paged_attention")

    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if s != 1:
        raise ValueError(
            "paged KV cache serves single-token decode steps; prefill "
            "runs the dense path and packs into pages afterwards")
    quantized = len(kv_cache) == 5
    if quantized:
        kp, vp, pt, ks, vs = kv_cache
    else:
        kp, vp, pt = kv_cache
        ks = vs = None
    ps = kp.shape[2]

    def write(pool, new, pt_, pos_):
        page_ids = pt_[jnp.arange(b), pos_ // ps]       # [B]
        slots = pos_ % ps
        return pool.at[page_ids, :, slots, :].set(new.astype(pool.dtype))

    def write_q(pool, scales, new, pt_, pos_):
        from ..ops.quant import quantize_vectors

        page_ids = pt_[jnp.arange(b), pos_ // ps]       # [B]
        slots = pos_ % ps
        qv, sv = quantize_vectors(new)                  # [B,Hkv,D]/[B,Hkv]
        pool = pool.at[page_ids, :, slots, :].set(qv)
        scales = scales.at[page_ids, :, slots].set(sv)
        return pool, scales

    k1 = k.reshape([b, hkv, d])
    v1 = v.reshape([b, hkv, d])
    if quantized:
        kp, ks = apply("paged_kv_update", write_q, kp, ks, k1, pt,
                       cache_pos)
        vp, vs = apply("paged_kv_update", write_q, vp, vs, v1, pt,
                       cache_pos)
    else:
        kp = apply("paged_kv_update", write, kp, k1, pt, cache_pos)
        vp = apply("paged_kv_update", write, vp, v1, pt, cache_pos)

    def attend(q1, kp_, vp_, pt_, pos_, ks_, vs_):
        return PA.paged_attention_dispatch(q1, kp_, vp_, pt_, pos_,
                                           k_scales=ks_, v_scales=vs_)

    out = apply("paged_attention", attend, q.reshape([b, hq, d]), kp, vp,
                pt, cache_pos, ks, vs)
    new_cache = (kp, vp, pt, ks, vs) if quantized else (kp, vp, pt)
    return out.reshape([b, 1, hq, d]), new_cache


def decode_position_ids(cache_pos, b, s, attn_start=None):
    """[B, S] position ids for a cached forward.  cache_pos is a scalar
    Tensor (dense lockstep cache: every row at the same offset) or a
    per-row [B] vector (paged ragged cache: each sequence at its own
    length).  Applies the left-pad `shift_positions` when attn_start is
    given.  Shared by the model families' rope/learned-position
    branches."""
    from .. import ops

    pos = ops.arange(0, s, dtype="int32")
    if len(cache_pos.shape) == 1:
        position_ids = cache_pos.unsqueeze(1) + pos.unsqueeze(0)
    else:
        row = pos + cache_pos
        position_ids = ops.broadcast_to(row.unsqueeze(0), [b, s])
    return shift_positions(position_ids, attn_start)


def shift_positions(position_ids, attn_start):
    """Per-row position shift for left-padded prompts: each row's first
    real token sits at position 0 (pad rows clip to 0). Shared by the
    model families' rope/learned-position branches."""
    from .. import ops

    if attn_start is None:
        return position_ids
    return ops.clip(position_ids - attn_start.unsqueeze(1), min=0)


def init_kv_caches(num_layers, batch, num_heads, head_dim, max_len,
                   dtype="float32"):
    """Fixed-shape per-layer KV buffers; capacity rounds up to a multiple
    of 128 so the decode kernel's block sizes always divide it (the tail
    is masked by position)."""
    cap = -(-int(max_len) // 128) * 128
    return [(jnp.zeros((batch, num_heads, cap, head_dim), dtype),
             jnp.zeros((batch, num_heads, cap, head_dim), dtype))
            for _ in range(num_layers)]


def _sample(logits, key, do_sample, temperature, top_k):
    """logits: [B, V] f32. Returns [B] int32 next tokens."""
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature != 1.0:
        logits = logits / max(float(temperature), 1e-6)
    if top_k:
        # clamp: top_k >= vocab would index past the sorted axis (jnp wraps
        # negative OOB to 0, silently disabling the filter) — k == vocab
        # keeps every logit, which is the correct no-op
        k = min(int(top_k), logits.shape[-1])
        kth = jnp.sort(logits, axis=-1)[:, -k][:, None]
        logits = jnp.where(logits < kth, -1e30, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


class GenerationMixin:
    """Mixed into *ForCausalLM models that implement
    `init_kv_caches(batch, max_len)` and
    `forward(ids, kv_caches=, cache_pos=) -> (logits, new_caches)`."""

    def _model_run(self, params, buffers, step_ids, caches, pos,
                   start):
        """One cached-forward model invocation on raw jax values (shared
        by the greedy/sampling and beam program builders — the model-call
        contract lives in exactly one place)."""
        with flags.no_grad_guard(), flags.trace_guard():
            with self.bind_state(params, buffers):
                logits, new_caches = self(
                    Tensor(step_ids),
                    kv_caches=[(Tensor(k), Tensor(v)) for k, v in caches],
                    cache_pos=Tensor(pos),
                    attn_start=(None if start is None else Tensor(start)))
        return (logits._value,
                [(k._value, v._value) for k, v in new_caches])

    def _gen_programs(self, b, s0, cap, do_sample, temperature, top_k,
                      has_mask):
        """Compiled prefill program, cached per signature — a serving
        loop calling generate() repeatedly must not pay the XLA compile
        per call. (Decode runs through `_decode_chunk_program`.)"""
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        sig = (b, s0, cap, bool(do_sample), float(temperature), int(top_k),
               bool(has_mask))
        hit = cache.get(sig)
        if hit is not None:
            return hit

        run = self._model_run

        @jax.jit
        def prefill(params, buffers, ids, caches, start):
            logits, caches = run(params, buffers, ids, caches,
                                 jnp.zeros((), jnp.int32), start)
            return logits[:, -1, :], caches

        cache[sig] = prefill
        return cache[sig]

    def _decode_chunk_program(self, n, b, cap, do_sample, temperature,
                              top_k, has_mask, eos_token_id):
        """n decode steps inside ONE compiled lax.scan (TPU-first: the
        per-token python loop pays a per-dispatch host gap per token
        while the kernel itself is ~1 ms; the
        scan removes the host from the loop entirely). Bit-identical to
        n iterations of the single-step path: the PRNG split order, eos
        freezing, and cache updates follow the same sequence. Caches are
        donated: each step overwrites one position per buffer, and
        donation lets XLA update in place instead of copying
        ~2*L*B*H*max*D bytes every token."""
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        sig = ("chunk", n, b, cap, bool(do_sample), float(temperature),
               int(top_k), bool(has_mask),
               -1 if eos_token_id is None else int(eos_token_id))
        hit = cache.get(sig)
        if hit is not None:
            return hit
        run = self._model_run

        @functools.partial(jax.jit, donate_argnums=(3,))
        def decode_n(params, buffers, tok, caches, pos0, key, start,
                     finished):
            def body(carry, i):
                tok, caches, key, finished = carry
                key, sub = jax.random.split(key)
                logits, caches = run(params, buffers, tok[:, None],
                                     caches, pos0 + i, start)
                nxt = _sample(logits[:, -1, :], sub, do_sample,
                              temperature, top_k)
                if eos_token_id is not None:
                    # frozen rows keep emitting eos, not live continuations
                    nxt = jnp.where(finished, eos_token_id, nxt)
                    finished = finished | (nxt == eos_token_id)
                return (nxt, caches, key, finished), (nxt, finished.all())

            (tok, caches, key, finished), (toks, fin_all) = jax.lax.scan(
                body, (tok, caches, key, finished),
                jnp.arange(n, dtype=jnp.int32))
            return toks.T, tok, caches, key, finished, fin_all

        cache[sig] = decode_n
        return decode_n

    # ---- beam search ----
    def _beam_programs(self, b, n, s0, cap, eos_id, length_penalty):
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        sig = ("beam", b, n, s0, cap, eos_id, float(length_penalty))
        hit = cache.get(sig)
        if hit is not None:
            return hit

        run = self._model_run

        @jax.jit
        def beam_prefill(params, buffers, ids, caches):
            logits, caches = run(params, buffers, ids, caches,
                                 jnp.zeros((), jnp.int32), None)
            logp = jax.nn.log_softmax(
                logits[:, -1, :].astype(jnp.float32), axis=-1)
            scores, toks = jax.lax.top_k(logp, n)        # [B, N]
            # tile each row's cache N times: beam i of row b at b*N+i
            caches = [(jnp.repeat(k, n, axis=0), jnp.repeat(v, n, axis=0))
                      for k, v in caches]
            return toks.astype(jnp.int32), scores, caches

        def pool_update(step_idx, tok, scores, lengths, pool):
            """Move hypotheses that just emitted eos into the per-row
            finished pool (best-so-far by length-normalized score), and
            knock their beam slots out of the live search."""
            fin_norm, fin_step, fin_beam = pool
            done = tok == eos_id                              # [B, N]
            norm = scores / (jnp.maximum(lengths, 1.0) ** length_penalty)
            cand = jnp.where(done, norm, -jnp.inf)
            best_c = jnp.argmax(cand, axis=1)                 # [B]
            best_v = jnp.take_along_axis(cand, best_c[:, None], 1)[:, 0]
            better = best_v > fin_norm
            fin_norm = jnp.where(better, best_v, fin_norm)
            fin_step = jnp.where(better, step_idx, fin_step)
            fin_beam = jnp.where(better, best_c.astype(jnp.int32),
                                 fin_beam)
            scores = jnp.where(done, -1e30, scores)   # slot leaves the beam
            return scores, (fin_norm, fin_step, fin_beam)

        def beam_step(params, buffers, tok, caches, pos, scores, lengths,
                      pool, step_idx):
            # plain traceable body — jitted by the scanned program below
            # (the whole beam loop runs in ONE dispatch; see
            # _beam_scan_program)
            # tok: [B, N]; scores: [B, N] running log-probs (finished
            # slots already at -1e30); lengths: [B, N] tokens generated
            logits, caches = run(params, buffers,
                                 tok.reshape(b * n)[:, None], caches, pos,
                                 None)
            logp = jax.nn.log_softmax(
                logits[:, -1, :].astype(jnp.float32), axis=-1)
            v = logp.shape[-1]
            total = scores[:, :, None] + logp.reshape(b, n, v)
            new_scores, flat = jax.lax.top_k(total.reshape(b, n * v), n)
            parent = (flat // v).astype(jnp.int32)            # [B, N]
            new_tok = (flat % v).astype(jnp.int32)
            # reorder caches to the chosen parents
            gather = (jnp.arange(b)[:, None] * n + parent).reshape(-1)
            caches = [(k[gather], v_[gather]) for k, v_ in caches]
            new_lengths = jnp.take_along_axis(lengths, parent, axis=1) + 1.0
            if eos_id is not None:
                new_scores, pool = pool_update(
                    step_idx, new_tok, new_scores, new_lengths, pool)
            return new_tok, new_scores, parent, new_lengths, pool, caches

        cache[sig] = (beam_prefill, beam_step, pool_update)
        return cache[sig]

    def _beam_scan_program(self, steps, b, n, s0, cap, eos_id,
                           length_penalty):
        """steps-1 beam steps inside ONE compiled lax.scan (the beam loop
        has no early exit, so the entire search after prefill is a single
        dispatch; the per-step (tok, parent) history for backtracking is
        the scan's stacked output). Caches donated, as in greedy decode."""
        cache = getattr(self, "_gen_cache", None)
        if cache is None:
            cache = self._gen_cache = {}
        sig = ("beamscan", steps, b, n, cap,
               -1 if eos_id is None else int(eos_id),
               float(length_penalty))
        hit = cache.get(sig)
        if hit is not None:
            return hit
        _, beam_step, _ = self._beam_programs(b, n, s0, cap, eos_id,
                                              length_penalty)

        @functools.partial(jax.jit, donate_argnums=(3,))
        def beam_scan(params, buffers, tok, caches, pos0, scores, lengths,
                      pool):
            def body(carry, i):
                tok, scores, lengths, pool, caches = carry
                tok, scores, parent, lengths, pool, caches = beam_step(
                    params, buffers, tok, caches, pos0 + i - 1, scores,
                    lengths, pool, i)
                return (tok, scores, lengths, pool, caches), (tok, parent)

            carry, hist = jax.lax.scan(
                body, (tok, scores, lengths, pool, caches),
                jnp.arange(1, steps, dtype=jnp.int32))
            tok, scores, lengths, pool, caches = carry
            return tok, scores, lengths, pool, caches, hist

        cache[sig] = beam_scan
        return beam_scan

    def _beam_search(self, ids, max_new_tokens, num_beams, eos_token_id,
                     length_penalty):
        b, s0 = ids.shape
        n = num_beams
        params, buffers = self.functional_state()
        caches = self.init_kv_caches(b, s0 + max_new_tokens)
        # prefill at batch B (tiling N identical prefills would waste N-1x)
        cap = caches[0][0].shape[2]
        beam_prefill, _, pool_update = self._beam_programs(
            b, n, s0, cap, eos_token_id, length_penalty)

        tok, scores, caches = beam_prefill(params, buffers, ids, caches)
        lengths = jnp.ones((b, n), jnp.float32)  # 1 generated token so far
        # finished-hypothesis pool: best length-normalized score per row
        # plus the (step, beam) to backtrack from — a completed sequence
        # is never evicted by live continuations (review r3 finding)
        pool = (jnp.full((b,), -jnp.inf),
                jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32))
        if eos_token_id is not None:
            scores, pool = pool_update(0, tok, scores, lengths, pool)
        tok0 = tok
        par0 = jnp.tile(jnp.arange(n), (b, 1))
        if max_new_tokens > 1:
            beam_scan = self._beam_scan_program(
                max_new_tokens, b, n, s0, cap, eos_token_id,
                length_penalty)
            tok, scores, lengths, pool, caches, (toks_s, pars_s) = \
                beam_scan(params, buffers, tok, caches,
                          jnp.asarray(s0, jnp.int32), scores, lengths,
                          pool)
            toks_all = np.concatenate(
                [np.asarray(jax.device_get(tok0))[None],
                 np.asarray(jax.device_get(toks_s))])
            parents_all = np.concatenate(
                [np.asarray(jax.device_get(par0))[None],
                 np.asarray(jax.device_get(pars_s))])
        else:
            toks_all = np.asarray(jax.device_get(tok0))[None]
            parents_all = np.asarray(jax.device_get(par0))[None]
        # pick per row: best finished hypothesis vs best live beam
        steps = max_new_tokens
        live_norm = scores / (jnp.maximum(lengths, 1.0) ** length_penalty)
        live_best = jnp.argmax(live_norm, axis=1)
        live_val = jnp.take_along_axis(live_norm, live_best[:, None],
                                       1)[:, 0]
        fin_norm, fin_step, fin_beam = pool
        use_fin = fin_norm >= live_val
        sel_step = np.asarray(jax.device_get(
            jnp.where(use_fin, fin_step, steps - 1)))
        sel_beam = np.asarray(jax.device_get(
            jnp.where(use_fin, fin_beam, live_best.astype(jnp.int32))))
        # rows whose winner finished at sel_step keep an eos-filled tail
        # (rectangular output)
        eos_fill = eos_token_id if eos_token_id is not None else 0
        out = np.full((b, steps), eos_fill, np.int32)
        beam = sel_beam.copy()
        rows = np.arange(b)
        for t in range(steps - 1, -1, -1):
            take = t <= sel_step
            out[take, t] = toks_all[t][rows[take], beam[take]]
            beam[take] = parents_all[t][rows[take], beam[take]]
        return Tensor(jnp.concatenate([ids, jnp.asarray(out)], axis=1))

    def generate(self, input_ids, max_new_tokens=32, do_sample=False,
                 temperature=1.0, top_k=0, eos_token_id=None, seed=None,
                 attention_mask=None, num_beams=1, length_penalty=1.0):
        """input_ids: [B, S0] int Tensor/array. Returns an int32 Tensor
        [B, S0 + n_generated]. With eos_token_id set, rows that emit eos
        are frozen (their remaining positions fill with eos) and the loop
        stops once every row has finished. attention_mask: optional
        [B, S0] 0/1 mask for LEFT-padded ragged prompts — pad positions
        never contribute to attention and rotary/learned positions start
        at each row's first real token. num_beams > 1 switches to beam
        search (greedy scoring only; finished hypotheses live in a pool
        and the best length_penalty-normalized sequence wins; incompatible
        with do_sample and attention_mask)."""
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        ids = ids.astype(jnp.int32)
        b, s0 = ids.shape
        if max_new_tokens <= 0:
            return Tensor(ids)
        if num_beams > 1:
            if do_sample:
                raise ValueError("beam search with do_sample is not "
                                 "supported; use num_beams=1 for sampling")
            if attention_mask is not None:
                raise ValueError("beam search over left-padded ragged "
                                 "batches is not supported yet")
            was_training = self.training
            self.eval()
            try:
                return self._beam_search(ids, max_new_tokens, num_beams,
                                         eos_token_id, length_penalty)
            finally:
                if was_training:
                    self.train()
        start = None
        if attention_mask is not None:
            m = attention_mask._value if isinstance(attention_mask, Tensor) \
                else jnp.asarray(attention_mask)
            m = m.astype(jnp.int32)
            if m.shape != (b, s0):
                raise ValueError(
                    f"attention_mask must be [B, S0]={b, s0}, "
                    f"got {tuple(m.shape)}")
            mh = np.asarray(jax.device_get(m))
            if not (mh[:, -1] == 1).all():
                raise ValueError(
                    "attention_mask must be LEFT-padded (last column all "
                    "ones): right padding would put a pad token at the "
                    "next-token prediction position")
            starts_h = mh.argmax(axis=1)
            rows = np.arange(b)[:, None]
            if not ((np.arange(s0)[None, :] >= starts_h[:, None])
                    == mh[rows, np.arange(s0)[None, :]].astype(bool)).all():
                raise ValueError(
                    "attention_mask must be contiguous left padding "
                    "(zeros then ones per row)")
            # left-padding: first real token = number of leading zeros
            start = jnp.asarray(starts_h, jnp.int32)
        max_len = s0 + max_new_tokens
        was_training = self.training
        self.eval()
        try:
            params, buffers = self.functional_state()
            caches = self.init_kv_caches(b, max_len)
            cap = caches[0][0].shape[2]
            prefill = self._gen_programs(
                b, s0, cap, do_sample, temperature, top_k,
                start is not None)
            key = (jax.random.PRNGKey(seed) if seed is not None
                   else rng.default_generator.split())

            last_logits, caches = prefill(params, buffers, ids, caches,
                                          start)
            key, sub = jax.random.split(key)
            tok = _sample(last_logits, sub, do_sample, temperature, top_k)
            finished = jnp.zeros((b,), bool)
            if eos_token_id is not None:
                finished = tok == eos_token_id
            # chunked scanned decode: CHUNK tokens per host dispatch (the
            # per-token loop paid one per-dispatch host gap per ~1 ms
            # kernel). Token stream, PRNG order, and eos
            # freezing are bit-identical to the single-step path; the
            # all-finished early-exit is checked once per chunk and the
            # exact per-token stop length restored by the trim below.
            # Without an eos there is nothing to check between chunks —
            # the decode runs as ONE scanned dispatch for lengths up to
            # 128 (same recurrence, larger n, identical token/PRNG
            # stream). The 128 cap bounds per-length program compiles: a
            # caller sweeping long lengths reuses the n=128 program for
            # full chunks (tail-chunk programs were always per-length).
            CHUNK = (DECODE_CHUNK if eos_token_id is not None
                     else max(1, min(max_new_tokens - 1, 128)))
            chunks = [tok[:, None]]
            fin_alls = [finished.all()[None]]
            i = 1
            while i < max_new_tokens:
                if eos_token_id is not None and bool(
                        np.asarray(jax.device_get(finished.all()))):
                    break
                n = min(CHUNK, max_new_tokens - i)
                decode_n = self._decode_chunk_program(
                    n, b, cap, do_sample, temperature, top_k,
                    start is not None, eos_token_id)
                toks, tok, caches, key, finished, fin_all = decode_n(
                    params, buffers, tok, caches,
                    jnp.asarray(s0 + i - 1, jnp.int32), key, start,
                    finished)
                chunks.append(toks)
                fin_alls.append(fin_all)
                i += n
            gen = jnp.concatenate(chunks, axis=1)
            if eos_token_id is not None and gen.shape[1] > 1:
                # trim to the single-step loop's stop point: it breaks
                # BEFORE step j+1 when all rows were finished after step
                # j, so keep j+1 tokens for the earliest such j
                fin_h = np.asarray(
                    jax.device_get(jnp.concatenate(fin_alls)))
                hits = np.flatnonzero(fin_h)
                if hits.size:
                    gen = gen[:, :int(hits[0]) + 1]
            return Tensor(jnp.concatenate([ids, gen], axis=1))
        finally:
            if was_training:
                self.train()
