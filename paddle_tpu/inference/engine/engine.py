"""Continuous-batching inference engine over a paged KV cache.

The serving-side answer to ROADMAP item 1: instead of one predictor
lock serving whole `generate()` calls back-to-back, the engine keeps a
FIXED compiled batch of sequence slots and advances every in-flight
sequence one (or `decode_chunk`) token(s) per step, admitting new
sequences into freed slots between steps — throughput scales with
batch occupancy, latency with queue position, and no request waits for
the longest one to finish.

Three compiled programs (per shape signature, cached):

  * **prefill** (one sequence, prompt left-padded to a bucket): the
    dense static-cache path the model families already compile —
    returns the first generated token and the dense K/V it produced.
  * **pack**: scatters the fresh dense K/V into the sequence's
    allocated pages (pools donated — in-place on TPU).
  * **decode** (the hot step): `decode_chunk` scanned steps at the
    fixed `[max_slots]` batch — each step writes every slot's current
    token into its page at `page_table[slot, len//ps], len%ps` and
    attends through `ops/pallas/paged_attention` with per-slot ragged
    lengths.  Pools donated; tokens stay on device across the scan.

Free slots ride along pointing at the reserved scratch page with
length 0: their output is discarded on the host, and the compiled
shape never changes as sequences come and go.

Env knobs (read when the matching ctor arg is None):
  PADDLE_TPU_ENGINE_PAGE_SIZE       tokens per KV page        (16)
  PADDLE_TPU_ENGINE_MAX_PAGES      pool size incl. scratch    (derived)
  PADDLE_TPU_ENGINE_MAX_SLOTS      compiled batch slots       (4)
  PADDLE_TPU_ENGINE_DECODE_CHUNK   decode steps per dispatch  (1)
  PADDLE_TPU_ENGINE_PREFILL_BUCKET prompt padding granule     (16)
  PADDLE_TPU_ENGINE_MAX_SEQ_LEN    per-sequence token cap     (model's)
  PADDLE_TPU_ENGINE_PREFIX_CACHE   prefix caching on/off      (1)
  PADDLE_TPU_ENGINE_PREFIX_CACHE_MAX_TOKENS  cache bound      (0=pool)

Observability: `engine.schedule/prefill/decode/detokenize` spans
on the request-trace timeline, `engine.*` gauges (active/waiting
sequences, page utilization, batch occupancy), counters
(`engine.sequences{event}`, `engine.tokens`, `engine.steps{kind=decode}`,
`engine.decode_slots`, `engine.decode_live_tokens`,
`engine.prefill_tokens{cache}`) and histograms (`engine.submit_wait_ms`,
`engine.admit_wait_ms`, `engine.lock_wait_ms{who=loop}`) in the attach()
schema — docs/OBSERVABILITY.md says what each one counts.

Locks: `_lock` serializes whole steps against each other and against
maintenance (defrag, cache clear, close) and is held through the
device wait; `_table_lock` guards only the handle and timeline tables,
so `submit()` and `cancel()` never wait for a step.
"""
from __future__ import annotations

import functools
import os
import queue
import threading
import time

import numpy as np

import jax
import jax.numpy as jnp

from ...observability import metrics as _metrics
from ...observability import tenant_ledger as _tledger
from ...observability import trace as _trace
from ...observability import xla_cost as _xla_cost
from ...observability.timeseries import DecisionRing, RequestTimeline
from ...resilience.overload import _env_num
from .paging import PagePool
from .prefix import PrefixIndex
from .scheduler import Scheduler, Sequence

__all__ = ["EngineConfig", "InferenceEngine", "RequestHandle"]

# completed-request timelines retained for GET /debug/requests/<id>
_TIMELINE_LRU = 128


def _precision_knob(explicit, env, valid):
    """Resolve a precision-tier knob (explicit arg wins, else env).
    Invalid values fail LOUDLY at engine build, not mid-decode —
    the same discipline as `distributed.quantized.collective_precision`."""
    raw = explicit if explicit is not None else os.environ.get(env, "")
    key = str(raw).strip().lower()
    if key not in valid:
        raise ValueError(
            f"{env}={raw!r}: expected one of "
            f"{sorted(k for k in valid if k)} (or unset for the exact "
            f"tier)")
    return valid[key]


def _choose(logits):
    """Greedy choice at the last position of `logits` [B, S, V]:
    ``(token int32 [B], logprob float32 [B])`` — the argmax over the
    float32 row and its log-probability, ``logit[token] -
    logsumexp(row)``.  EVERY program that picks a token picks it here,
    so what is delivered and the number that says how sure the model
    was come from one float32 row."""
    row = logits[:, -1, :].astype(jnp.float32)
    tok = jnp.argmax(row, axis=-1).astype(jnp.int32)
    picked = jnp.take_along_axis(row, tok[:, None], axis=-1)[:, 0]
    return tok, picked - jax.nn.logsumexp(row, axis=-1)


def _observe_since(name, t0, **labels):
    """Histogram `name` gets the milliseconds since `t0`
    (`time.perf_counter()`)."""
    _metrics.observe(name, (time.perf_counter() - t0) * 1e3, **labels)


class EngineConfig:
    """Engine sizing knobs; every ctor arg falls back to its
    PADDLE_TPU_ENGINE_* env, then the default."""

    def __init__(self, page_size=None, num_pages=None, max_slots=None,
                 decode_chunk=None, prefill_bucket=None,
                 max_seq_len=None, weight_precision=None,
                 kv_precision=None, spec_tokens=None, pool_hbm_mb=None,
                 prefix_cache=None, prefix_cache_max_tokens=None):
        self.page_size = int(page_size if page_size is not None else
                             _env_num("PADDLE_TPU_ENGINE_PAGE_SIZE", 16,
                                      int))
        self.max_slots = int(max_slots if max_slots is not None else
                             _env_num("PADDLE_TPU_ENGINE_MAX_SLOTS", 4,
                                      int))
        self.decode_chunk = int(
            decode_chunk if decode_chunk is not None else
            _env_num("PADDLE_TPU_ENGINE_DECODE_CHUNK", 1, int))
        self.prefill_bucket = int(
            prefill_bucket if prefill_bucket is not None else
            _env_num("PADDLE_TPU_ENGINE_PREFILL_BUCKET", 16, int))
        # 0 = resolve from the model's max_seq_len at engine build
        self.max_seq_len = int(
            max_seq_len if max_seq_len is not None else
            _env_num("PADDLE_TPU_ENGINE_MAX_SEQ_LEN", 0, int))
        # 0 = derived: every slot can hold a max-length sequence
        self.num_pages = int(num_pages if num_pages is not None else
                             _env_num("PADDLE_TPU_ENGINE_MAX_PAGES", 0,
                                      int))
        # quantized decode tiers (ISSUE 12, docs/INFERENCE.md):
        #   weight_precision: int8 = per-output-channel weight-only
        #     quantization of every matmul weight at engine build,
        #     dequant fused inside the decode GEMVs; bf16 = plain cast.
        #   kv_precision: int8 = the page pools store int8 with
        #     per-token-per-head scales next to the page table.
        self.weight_precision = _precision_knob(
            weight_precision, "PADDLE_TPU_ENGINE_WEIGHT_PRECISION",
            {"": None, "f32": None, "full": None, "fp32": None,
             "bf16": "bf16", "int8": "int8"})
        self.kv_precision = _precision_knob(
            kv_precision, "PADDLE_TPU_ENGINE_KV_PRECISION",
            {"": None, "f32": None, "full": None, "fp32": None,
             "int8": "int8"})
        # draft-model speculative decoding: tokens proposed per pass
        # (0 = off; needs a draft_model at engine construction)
        self.spec_tokens = int(
            spec_tokens if spec_tokens is not None else
            _env_num("PADDLE_TPU_ENGINE_SPEC_TOKENS", 0, int))
        # fixed page-pool HBM budget in MiB (0 = unset): when num_pages
        # is not given explicitly, the pool is sized to FIT this budget
        # under the active kv tier — so int8 pages buy ~2x the pages
        # (and in-flight sequences) of bf16 for the same bytes, which
        # is the capacity claim the scheduler test asserts
        self.pool_hbm_mb = float(
            pool_hbm_mb if pool_hbm_mb is not None else
            _env_num("PADDLE_TPU_ENGINE_POOL_HBM_MB", 0.0, float))
        # prefix caching (ISSUE 13, docs/INFERENCE.md "Prefix caching"):
        # committed page-aligned prompt prefixes are indexed and shared
        # into later sequences' page tables (refcounted), so prefill
        # compute and page capacity scale with UNIQUE prompt tokens.
        # ON by default — streams are proven bit-identical warm vs
        # cold; 0 disables.  The token bound caps what the radix index
        # may pin (0 = bounded only by pool pressure's LRU reclaim).
        self.prefix_cache = bool(int(
            prefix_cache if prefix_cache is not None else
            _env_num("PADDLE_TPU_ENGINE_PREFIX_CACHE", 1, int)))
        self.prefix_cache_max_tokens = int(
            prefix_cache_max_tokens
            if prefix_cache_max_tokens is not None else
            _env_num("PADDLE_TPU_ENGINE_PREFIX_CACHE_MAX_TOKENS", 0,
                     int))
        for name in ("page_size", "max_slots", "decode_chunk",
                     "prefill_bucket"):
            if getattr(self, name) < 1:
                raise ValueError(f"{name} must be >= 1, got "
                                 f"{getattr(self, name)}")
        if self.spec_tokens < 0:
            raise ValueError(
                f"spec_tokens must be >= 0, got {self.spec_tokens}")
        if self.prefix_cache_max_tokens < 0:
            raise ValueError(
                f"prefix_cache_max_tokens must be >= 0, got "
                f"{self.prefix_cache_max_tokens}")


class RequestHandle:
    """One submitted request's delivery side: a token stream plus a
    completion event.  Tokens arrive as the engine accepts them, each
    with its log-probability; `result()` blocks for the full
    prompt+generated ids."""

    def __init__(self, seq: Sequence):
        self._seq = seq
        self.request_id = seq.request_id
        self.tenant_id = getattr(seq, "tenant_id", None)
        self._q = queue.Queue()
        self.done = threading.Event()
        self.finish_reason = None

    def _push(self, tok: int, logprob: float) -> None:
        self._q.put((int(tok), float(logprob)))

    def _finish(self, reason: str) -> None:
        if self.done.is_set():
            return
        self.finish_reason = reason
        self.done.set()
        self._q.put(None)          # stream sentinel

    # --- consumer side ------------------------------------------------------
    def stream(self, timeout: float = 120.0, with_logprobs=False):
        """Yield generated tokens as they land; returns at completion.
        With `with_logprobs`, yields ``(token, logprob)`` pairs."""
        while True:
            item = self._q.get(timeout=timeout)
            if item is None:
                return
            yield item if with_logprobs else item[0]

    def result(self, timeout: float = 120.0) -> np.ndarray:
        """Blocking: full int32 [s0 + n_generated] ids (prompt
        included, like `GenerationMixin.generate`)."""
        if not self.done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished in {timeout}s")
        return self._seq.output_ids()

    @property
    def tokens(self) -> list:
        return list(self._seq.tokens)

    @property
    def logprobs(self) -> list:
        """Log-probability of each delivered token (`tokens`' twin):
        `logit[token] - logsumexp(logits)` over the float32 logits of
        the program that chose it."""
        return list(self._seq.logprobs)

    @property
    def cancelled(self) -> bool:
        return self.finish_reason == "cancelled"

    @property
    def cache_state(self) -> str:
        """Prefix-cache outcome at admission: ``hit`` (longest sharable
        prefix fully cached), ``partial``, or ``miss`` (also the answer
        while still waiting / when caching is off) — the TTFT
        histogram's `cache` label (serving.py)."""
        return self._seq.cache_state or "miss"


def _matmul_weight_names(model):
    """Param names of the model's matmul weights — the HBM stream the
    weight-only tier halves: every Linear-family 2-D weight, plus the
    tied-embedding LM head (contracted on its hidden axis).  Returns
    ``{name: contraction_axis}``."""
    from ...distributed import mpu
    from ...nn.layers_common import Embedding, Linear

    linear_types = (Linear, mpu.ColumnParallelLinear,
                    mpu.RowParallelLinear)
    emb_types = (Embedding, mpu.VocabParallelEmbedding)
    names = {}
    vocab = int(getattr(getattr(model, "cfg", None), "vocab_size", 0))
    tied = bool(getattr(getattr(model, "cfg", None), "tie_embeddings",
                        False))
    for prefix, layer in model.named_sublayers(include_self=True):
        w = getattr(layer, "weight", None)
        if w is None or w._value.ndim != 2:
            continue
        if not jnp.issubdtype(w._value.dtype, jnp.floating):
            continue
        name = f"{prefix}.weight" if prefix else "weight"
        if isinstance(layer, linear_types):
            names[name] = 0          # [in, out]: contract over in
        elif tied and isinstance(layer, emb_types) \
                and w._value.shape[0] == vocab:
            # the tied embedding doubles as the LM head
            # (`x.matmul(w, transpose_y=True)`): output channels are
            # vocab ROWS, so the scale is per row (absmax over hidden)
            # — and the embedding lookup dequantizes the same rows with
            # the same scales, so both uses stay consistent
            names[name] = 1
    return names


class InferenceEngine:
    """Continuous-batching engine over one `GenerationMixin` model
    (greedy decoding — the deterministic serving mode; sampling rides
    ROADMAP item 4).

    Quantized decode tiers (ISSUE 12):
      * ``config.weight_precision='int8'`` quantizes every matmul
        weight ONCE at construction (per-output-channel absmax scales,
        `ops/quant.py` codec); the dequant runs inside the compiled
        decode scan body so the weights stream from HBM as int8.
      * ``config.kv_precision='int8'`` stores the KV page pools as int8
        with per-token-per-head scale tables riding next to the page
        table — half the page HBM, ~2x the in-flight sequences per
        fixed ``pool_hbm_mb`` budget.
      * ``draft_model=`` + ``config.spec_tokens=k`` turns on greedy
        speculative decoding: the draft proposes k tokens per slot per
        pass, the target scores all k+1 positions in ONE batched ragged
        paged-attention pass (positions spread over the batch axis so
        each row computes exactly what a sequential step would), and
        the accepted prefix commits on device — the committed stream is
        bit-identical to sequential greedy by construction.
    """

    def __init__(self, model, config: EngineConfig = None,
                 clock=time.monotonic, draft_model=None):
        import copy

        # own copy: max_seq_len/num_pages resolve against THIS model
        # below, and mutating the caller's object would poison a config
        # reused for a second engine over a different model
        self.config = copy.copy(config) if config is not None \
            else EngineConfig()
        self._model = model
        model.eval()
        self._params, self._buffers = model.functional_state()
        cfg = self.config
        # shape probe: one layer's dense cache tells us layers/heads/dim
        probe = model.init_kv_caches(1, 1)
        self._layers = len(probe)
        _, self._hkv, _, self._hd = probe[0][0].shape
        self._dtype = probe[0][0].dtype
        del probe
        if cfg.max_seq_len <= 0:
            cfg.max_seq_len = int(getattr(model.cfg, "max_seq_len", 0)) \
                or 2048
        # --- weight-only quantization (once, at build) -------------------
        self._wq_meta = {}
        if cfg.weight_precision is not None:
            self._quantize_weights()
        # --- draft model (speculative decoding) --------------------------
        self._draft = None
        if cfg.spec_tokens > 0:
            if draft_model is None:
                raise ValueError(
                    "spec_tokens > 0 needs a draft_model at engine "
                    "construction")
            self._init_draft(draft_model)
        elif draft_model is not None:
            raise ValueError(
                "draft_model given but config.spec_tokens == 0 — set "
                "spec_tokens (or PADDLE_TPU_ENGINE_SPEC_TOKENS) to the "
                "draft proposal length")
        self.max_pages_per_seq = -(-cfg.max_seq_len // cfg.page_size)
        if cfg.num_pages <= 0:
            if cfg.pool_hbm_mb > 0:
                # size the pool to FIT the byte budget under the active
                # kv tier: int8 pages cost ~half of bf16 (+ the f32
                # scale sidecar), so the same budget admits ~2x pages
                per_page = self._page_bytes()
                cfg.num_pages = max(
                    2, int(cfg.pool_hbm_mb * 2**20) // per_page)
            else:
                cfg.num_pages = cfg.max_slots * self.max_pages_per_seq + 1
        self.pool = PagePool(cfg.num_pages, cfg.page_size)
        self._prefix = None
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_tokens_saved = 0
        self._prefix_tokens_total = 0
        if cfg.prefix_cache:
            self._prefix = PrefixIndex(
                self.pool, max_tokens=cfg.prefix_cache_max_tokens,
                clock=clock,
                on_evict=lambda n: _metrics.inc(
                    "engine.prefix_cache", n, event="evict"))
        self._clock = clock
        # per-token latency attribution (ISSUE 15): the scheduler's
        # bounded decision ring + a bounded LRU of per-request
        # timelines — what GET /debug/requests/<id> correlates.
        # PADDLE_TPU_ITL_TIMELINE_CAP=0 disables timeline stamping.
        self.decisions = DecisionRing(capacity=512, clock=clock)
        self._timeline_cap = int(_env_num(
            "PADDLE_TPU_ITL_TIMELINE_CAP", 256, int))
        self._timelines = {}       # request_id -> RequestTimeline (LRU)
        # per-tenant metering (ISSUE 16): the engine owns the process's
        # book — decode tokens bill here (`record_decode` also owns the
        # engine.tokens increment, see tenant_ledger docstring), the
        # scheduler integrates KV page-seconds against it, and serving
        # ADOPTS it so edge request billing shares the same book (the
        # conservation invariant is per-book).  None when the plane is
        # off: a detached process pays nothing, not even O(K).
        self.tenant_ledger = None
        if _tledger.enabled() and _metrics.enabled():
            self.tenant_ledger = _tledger.TenantLedger()
        self.scheduler = Scheduler(cfg.max_slots, self.pool,
                                   self.max_pages_per_seq, clock=clock,
                                   prefix_index=self._prefix,
                                   decision_ring=self.decisions,
                                   tenant_ledger=self.tenant_ledger)
        shape = (cfg.num_pages, self._hkv, cfg.page_size, self._hd)
        pool_dtype = jnp.int8 if cfg.kv_precision == "int8" \
            else self._dtype
        self._k_pools = [jnp.zeros(shape, pool_dtype)
                         for _ in range(self._layers)]
        self._v_pools = [jnp.zeros(shape, pool_dtype)
                         for _ in range(self._layers)]
        self._k_scales = self._v_scales = None
        if cfg.kv_precision == "int8":
            # scale 1 everywhere: a never-written (scratch) slot
            # decodes to exact zeros, like the bf16 pool's zeros
            sshape = shape[:3]
            self._k_scales = [jnp.ones(sshape, jnp.float32)
                              for _ in range(self._layers)]
            self._v_scales = [jnp.ones(sshape, jnp.float32)
                              for _ in range(self._layers)]
        if self._draft is not None:
            self._init_draft_pools()
        self._programs = {}
        self._handles = {}         # request_id -> RequestHandle
        # the step lock: one step (or one maintenance call) at a time,
        # held through the device wait
        self._lock = threading.RLock()
        # the table lock: `_handles` and `_timelines` only, never held
        # across anything slower than a dictionary operation — what
        # lets submit() and cancel() run beside a step
        self._table_lock = threading.Lock()
        self._work = threading.Condition()
        self._thread = None
        self._running = False
        self._closed = False
        self.steps = 0
        self._publish_tier_gauges()

    # --- quantized-tier construction ----------------------------------------
    def _page_bytes(self) -> int:  # pt-lint: ok[PT102] (_draft binding is set once at construction; only its set-once geometry keys are read here — the mutable k/v pools stay under _lock)
        """HBM bytes ONE page costs across all layers (K+V pools plus
        the scale sidecar under the int8 kv tier, plus the draft
        model's pools when speculative decoding shares the page table)
        — the unit the ``pool_hbm_mb`` budget divides."""
        cfg = self.config
        if cfg.kv_precision == "int8":
            item, scale_item = 1, 4
        else:
            item = jnp.dtype(self._dtype).itemsize
            scale_item = 0
        per_pool = self._hkv * cfg.page_size * self._hd * item \
            + self._hkv * cfg.page_size * scale_item
        total = self._layers * 2 * per_pool
        if self._draft is not None:
            d = self._draft
            total += d["layers"] * 2 * (
                d["hkv"] * cfg.page_size * d["hd"]
                * jnp.dtype(d["dtype"]).itemsize)
        return max(1, total)

    def _quantize_weights(self) -> None:
        """Swap every matmul weight in the params pytree for its
        quantized form ({"q": int8, "s": f32 broadcastable} leaves for
        int8; a plain bf16 cast for bf16).  `_dequant_params` is the
        traced inverse — running INSIDE the compiled programs, so the
        stored (and HBM-streamed) representation stays narrow."""
        from ...ops import quant as QT

        prec = self.config.weight_precision
        names = _matmul_weight_names(self._model)
        for name, axis in names.items():
            w = self._params.get(name)
            if w is None:
                continue
            if prec == "int8":
                q, s = QT.quantize_channels(w, axis=axis)
                self._params[name] = {"q": q, "s": s}
            else:
                self._params[name] = {"q": w.astype(jnp.bfloat16)}
            self._wq_meta[name] = str(w.dtype)

    def _dequant_params(self, params):
        """Traced: rebuild full-precision weights from the quantized
        leaves.  Called INSIDE every compiled program (for the decode
        scan: inside the scan body), so XLA keeps the int8->float
        convert next to the GEMV instead of materializing a
        full-precision weight copy in HBM — `perf_audit`'s
        ``gpt_quantized_decode_step`` program pins this placement."""
        if not self._wq_meta:
            return params
        from ...ops import quant as QT

        out = dict(params)
        for name, dt in self._wq_meta.items():
            leaf = params[name]
            if "s" in leaf:
                out[name] = QT.dequantize_channels(leaf["q"], leaf["s"],
                                                   dtype=dt)
            else:
                out[name] = leaf["q"].astype(dt)
        return out

    def effective_params(self):  # pt-lint: ok[PT102] (_params is bound at construction and dropped only by close())
        """The de-quantized params the engine's programs actually
        compute with (identity when no weight tier is active) — bind
        these into the model to reproduce engine streams with plain
        `generate()` (the per-tier equivalence tests do exactly that)."""
        return self._dequant_params(self._params)

    def _init_draft(self, draft_model) -> None:
        draft_model.eval()
        dparams, dbuffers = draft_model.functional_state()
        probe = draft_model.init_kv_caches(1, 1)
        tv = int(getattr(getattr(self._model, "cfg", None),
                         "vocab_size", 0))
        dv = int(getattr(getattr(draft_model, "cfg", None),
                         "vocab_size", 0))
        if tv and dv and tv != dv:
            raise ValueError(
                f"draft vocab_size {dv} != target vocab_size {tv} — "
                f"proposals would index a different token space")
        self._draft = {
            "model": draft_model,
            "params": dparams,
            "buffers": dbuffers,
            "layers": len(probe),
            "hkv": probe[0][0].shape[1],
            "hd": probe[0][0].shape[3],
            "dtype": probe[0][0].dtype,
        }
        del probe

    def _init_draft_pools(self) -> None:
        """Draft KV pools share the page table/allocator with the
        target's (same page ids, own geometry) — allocation bookkeeping
        stays single.  The draft is small, so its pools stay full
        precision."""
        d = self._draft
        cfg = self.config
        shape = (cfg.num_pages, d["hkv"], cfg.page_size, d["hd"])
        d["k_pools"] = [jnp.zeros(shape, d["dtype"])
                        for _ in range(d["layers"])]
        d["v_pools"] = [jnp.zeros(shape, d["dtype"])
                        for _ in range(d["layers"])]

    def _publish_tier_gauges(self) -> None:
        cfg = self.config
        _metrics.set_gauge("engine.weight_precision", 1,
                           precision=cfg.weight_precision or "full")
        _metrics.set_gauge("paged.pool_precision", 1,
                           precision=cfg.kv_precision or "full")
        _metrics.set_gauge("engine.spec_tokens", cfg.spec_tokens)

    # --- model invocation (raw jax values; paged or dense caches) -----------
    def _run_model(self, params, buffers, ids, caches, pos, start):
        from ...core import flags
        from ...core.tensor import Tensor

        params = self._dequant_params(params)
        with flags.no_grad_guard(), flags.trace_guard():
            with self._model.bind_state(params, buffers):
                logits, new = self._model(
                    Tensor(ids),
                    kv_caches=[tuple(Tensor(x) for x in c)
                               for c in caches],
                    cache_pos=Tensor(pos),
                    attn_start=None if start is None else Tensor(start))
        return logits._value, [tuple(x._value for x in c) for c in new]

    def _run_draft(self, params, buffers, ids, caches, pos, start):  # pt-lint: ok[PT102] (_draft binding and its "model" key are set once at construction and never rebound)
        from ...core import flags
        from ...core.tensor import Tensor

        model = self._draft["model"]
        with flags.no_grad_guard(), flags.trace_guard():
            with model.bind_state(params, buffers):
                logits, new = model(
                    Tensor(ids),
                    kv_caches=[tuple(Tensor(x) for x in c)
                               for c in caches],
                    cache_pos=Tensor(pos),
                    attn_start=None if start is None else Tensor(start))
        return logits._value, [tuple(x._value for x in c) for c in new]

    # --- compiled programs --------------------------------------------------
    def _which(self, which):  # pt-lint: ok[PT102] (_draft binding and its geometry keys are set once at construction)
        """(run_fn, layers, hkv, hd, dtype) for "target"/"draft"."""
        if which == "draft":
            d = self._draft
            return (self._run_draft, d["layers"], d["hkv"], d["hd"],
                    d["dtype"])
        return (self._run_model, self._layers, self._hkv, self._hd,
                self._dtype)

    def _caches_of(self, kps, vps, pt, kss=None, vss=None):
        """Per-layer cache tuples for the paged model path: 5-tuples
        (with scale tables) under the int8 kv tier, 3-tuples otherwise."""
        if kss:
            return [(k, v, pt, ks, vs) for k, v, ks, vs
                    in zip(kps, vps, kss, vss)]
        return [(k, v, pt) for k, v in zip(kps, vps)]

    def _prefill_program(self, sb: int, which="target"):
        """One left-padded sequence at bucket length sb: greedy first
        token, its log-probability + the dense K/V (capacity
        sb+page_size so the pack program's last page slice never
        clamps)."""
        key = ("prefill", sb, which)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        run, layers, hkv, d, dtype = self._which(which)
        cap = sb + self.config.page_size

        @jax.jit
        def prefill(params, buffers, ids, start):
            caches = [(jnp.zeros((1, hkv, cap, d), dtype),
                       jnp.zeros((1, hkv, cap, d), dtype))
                      for _ in range(layers)]
            logits, new = run(params, buffers, ids, caches,
                              jnp.zeros((), jnp.int32), start)
            tok, lp = _choose(logits)
            return tok, lp, [c[0] for c in new], [c[1] for c in new]

        label = f"prefill_s{sb}" + ("" if which == "target" else f"_{which}")
        prefill = _xla_cost.instrument(prefill, label)
        # pt-lint: ok[PT503] (benign memo race: dict set is atomic in CPython; worst case two threads jit the same program once each)
        self._programs[key] = prefill
        return prefill

    def _pack_program(self, sb: int, which="target"):
        """Scatter a prefill's dense K/V (real tokens at
        [start, start+s0)) into the sequence's pages.  Pages beyond the
        prompt's span point at the scratch page — their writes are
        discarded by construction.  Under the int8 kv tier each token's
        head-vector quantizes independently (`quantize_vectors` — the
        SAME per-vector codec the decode write applies), so the packed
        page content is bit-identical to what token-by-token writes
        would have produced."""
        quant = which == "target" and self.config.kv_precision == "int8"
        key = ("pack", sb, which, quant)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        ps = self.config.page_size
        _, _, hkv, d, _ = self._which(which)
        npb = -(-sb // ps)

        if not quant:
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def pack(k_pools, v_pools, kbufs, vbufs, pages, start):
                def put(pool, buf):
                    def body(i, pool):
                        chunk = jax.lax.dynamic_slice(
                            buf, (0, 0, start + i * ps, 0),
                            (1, hkv, ps, d))
                        return jax.lax.dynamic_update_slice(
                            pool, chunk.astype(pool.dtype),
                            (pages[i], 0, 0, 0))
                    return jax.lax.fori_loop(0, npb, body, pool)

                k_pools = [put(p, b) for p, b in zip(k_pools, kbufs)]
                v_pools = [put(p, b) for p, b in zip(v_pools, vbufs)]
                return k_pools, v_pools

            label = f"pack_s{sb}" + ("" if which == "target" else f"_{which}")
            pack = _xla_cost.instrument(pack, label)
            self._programs[key] = pack
            return pack

        from ...ops.quant import quantize_vectors

        @functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
        def pack_q(k_pools, v_pools, k_scales, v_scales, kbufs, vbufs,
                   pages, start):
            def put(pool, scales, buf):
                def body(i, carry):
                    pool, scales = carry
                    chunk = jax.lax.dynamic_slice(
                        buf, (0, 0, start + i * ps, 0),
                        (1, hkv, ps, d))[0]          # [hkv, ps, d]
                    # per-(head, token) vector scales — one absmax per
                    # d-vector, independent of neighbours
                    qv, sv = quantize_vectors(chunk)
                    pool = jax.lax.dynamic_update_slice(
                        pool, qv[None], (pages[i], 0, 0, 0))
                    scales = jax.lax.dynamic_update_slice(
                        scales, sv[None], (pages[i], 0, 0))
                    return pool, scales
                return jax.lax.fori_loop(0, npb, body, (pool, scales))

            ks, vs = list(k_scales), list(v_scales)
            kp = list(k_pools)
            vp = list(v_pools)
            for li in range(len(kp)):
                kp[li], ks[li] = put(kp[li], ks[li], kbufs[li])
                vp[li], vs[li] = put(vp[li], vs[li], vbufs[li])
            return kp, vp, ks, vs

        pack_q = _xla_cost.instrument(pack_q, f"pack_s{sb}_q")
        self._programs[key] = pack_q
        return pack_q

    def _cached_prefill_program(self, sb: int, npp: int,
                                which="target"):
        """WARM tail prefill (prefix caching, ISSUE 13): one sequence
        whose first `plen` tokens (page-aligned, `<= npp` pages) are
        already committed in the pools — only the tail (left-padded to
        bucket `sb`) runs through the model.  The cached prefix is
        gathered into a dense buffer at [0, plen) and the forward runs
        under `generation.warm_prefill_guard`, so every tail query
        attends prefix + causal tail; `cache_pos` starts at the shared
        length and the compiled shape depends only on (sb, npp) — npp
        is bucketed to a power of two by the caller, which is what the
        committed PT402 budget on `gpt_cached_prefill_step` pins.

        Exact tier (and the draft model): the prefix is gathered from
        the pools in-program — pools store full precision, so the
        gather IS the exact prefix.  int8-KV tier: the program instead
        takes per-layer EXACT prefix buffers (the radix index's commit
        -time sidecar) — a warm first token must attend the prefix at
        the same precision a cold prefill would, or warm and cold
        streams diverge beyond reduction-order noise."""
        quant = which == "target" and self.config.kv_precision == "int8"
        key = ("cprefill", sb, npp, which, quant)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        from ...models import generation as GEN

        run, layers, hkv, d, dtype = self._which(which)
        ps = self.config.page_size
        pcap = npp * ps

        def finish(logits, new):
            tok, lp = _choose(logits)
            return tok, lp, [c[0] for c in new], [c[1] for c in new]

        if quant:
            @jax.jit
            def cprefill_q(params, buffers, ids, start, plen,
                           prefix_k, prefix_v):
                def dense(buf):        # [npp, hkv, ps, d] exact sidecar
                    g = jnp.swapaxes(buf, 0, 1).reshape(hkv, pcap,
                                                        d)[None]
                    return jnp.concatenate(
                        [g.astype(dtype),
                         jnp.zeros((1, hkv, sb + ps, d), dtype)],
                        axis=2)

                caches = [(dense(prefix_k[li]), dense(prefix_v[li]))
                          for li in range(layers)]
                with GEN.warm_prefill_guard(plen):
                    logits, new = run(params, buffers, ids, caches,
                                      plen, start)
                return finish(logits, new)

            cprefill_q = _xla_cost.instrument(
                cprefill_q, f"cprefill_s{sb}_p{npp}_q")
            self._programs[key] = cprefill_q
            return cprefill_q

        @jax.jit
        def cprefill(params, buffers, ids, start, pages, plen,
                     k_pools, v_pools):
            def dense(pool):
                g = pool[pages]                    # [npp, hkv, ps, d]
                g = jnp.swapaxes(g, 0, 1).reshape(hkv, pcap, d)[None]
                return jnp.concatenate(
                    [g.astype(dtype),
                     jnp.zeros((1, hkv, sb + ps, d), dtype)], axis=2)

            caches = [(dense(k_pools[li]), dense(v_pools[li]))
                      for li in range(layers)]
            with GEN.warm_prefill_guard(plen):
                logits, new = run(params, buffers, ids, caches, plen,
                                  start)
            return finish(logits, new)

        label = f"cprefill_s{sb}_p{npp}" + (
            "" if which == "target" else f"_{which}")
        cprefill = _xla_cost.instrument(cprefill, label)
        self._programs[key] = cprefill
        return cprefill

    def _decode_program(self, n: int):
        """`n` ragged decode steps at the fixed [max_slots] batch inside
        one compiled scan.  Pools donated: each step writes one page
        slot per sequence per layer, and donation lets XLA update in
        place instead of copying the whole pool per token.  The
        weight-dequant (int8 tier) runs INSIDE the scan body via
        `_run_model`, so the int8->float convert stays fused next to
        each GEMV instead of materializing full-precision weights."""
        quant = self.config.kv_precision == "int8"
        key = ("decode", n, quant)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        run = self._run_model
        caches_of = self._caches_of

        @functools.partial(jax.jit, donate_argnums=(2, 3, 4, 5))
        def decode(params, buffers, k_pools, v_pools, k_scales,
                   v_scales, tok, pt, lengths):
            def body(carry, _):
                tok, kps, vps, kss, vss, lengths = carry
                caches = caches_of(kps, vps, pt, kss, vss)
                logits, new = run(params, buffers, tok[:, None], caches,
                                  lengths, None)
                kps = [c[0] for c in new]
                vps = [c[1] for c in new]
                if quant:
                    kss = [c[3] for c in new]
                    vss = [c[4] for c in new]
                nxt, lp = _choose(logits)
                return (nxt, kps, vps, kss, vss, lengths + 1), (nxt, lp)

            (tok, kps, vps, kss, vss, lengths), (toks, lps) = \
                jax.lax.scan(
                    body, (tok, k_pools, v_pools, k_scales, v_scales,
                           lengths), None, length=n)
            return (jnp.swapaxes(toks, 0, 1), jnp.swapaxes(lps, 0, 1),
                    kps, vps, kss, vss)

        decode = _xla_cost.instrument(decode, f"decode_n{n}")
        self._programs[key] = decode
        return decode

    def _spec_program(self, k: int):
        """One speculative-decoding pass at the fixed [max_slots]
        batch: the draft proposes ``k`` tokens (k+1 scanned single-token
        steps — the extra feed writes the last proposal's K/V so a
        fully-accepted pass leaves the draft cache complete), then the
        TARGET scores all k+1 positions in ONE batched ragged
        paged-attention pass with the positions spread over the batch
        axis — row (s, i) carries its own cache position L_s+i and
        slot s's page table, so each row computes EXACTLY what the
        sequential decode step at that position computes (same shapes,
        same masks), which is what makes the accepted stream
        bit-identical to sequential greedy.  Accept/reject runs on
        device; the host reads (g, lp, counts) and commits
        g[:, :counts] with the target's log-probabilities lp[:, :counts].
        """
        quant = self.config.kv_precision == "int8"
        key = ("spec", k, quant)
        hit = self._programs.get(key)
        if hit is not None:
            return hit
        run = self._run_model
        run_d = self._run_draft
        caches_of = self._caches_of
        s_ = self.config.max_slots

        @functools.partial(jax.jit,
                           donate_argnums=(4, 5, 6, 7, 8, 9))
        def spec(params, buffers, dparams, dbuffers, k_pools, v_pools,
                 k_scales, v_scales, dk_pools, dv_pools, tok, pt,
                 lengths, limits):
            # rows past a sequence's LIFETIME end (pos >= limits[s] =
            # prompt+max_new) are masked onto the scratch page at pos 0:
            # an unmasked overflow row's page-table gather would CLAMP
            # onto the row's last real page and its scatter would
            # overwrite a live committed position — which the same
            # pass's valid rows then attend (the batched pass writes
            # ALL rows before any row attends), silently breaking the
            # bit-identical-to-greedy contract on the final pass of a
            # table-filling sequence.  Masked rows' outputs are never
            # committed (a committed row always has pos < limit), so
            # scratch garbage is fine — the same contract free slots
            # already ride on.
            def mask_row(pos, table):
                ok = pos < limits
                return (jnp.where(ok, pos, 0),
                        jnp.where(ok[:, None], table, 0))

            # --- draft proposes (sequential tiny steps, one scan) ----
            def dbody(carry, _):
                cur, dkp, dvp, pos = carry
                pos_eff, pt_eff = mask_row(pos, pt)
                caches = caches_of(dkp, dvp, pt_eff)
                logits, new = run_d(dparams, dbuffers, cur[:, None],
                                    caches, pos_eff, None)
                dkp = [c[0] for c in new]
                dvp = [c[1] for c in new]
                nxt, _ = _choose(logits)   # the draft's own
                # log-probability is never delivered (XLA drops it)
                return (nxt, dkp, dvp, pos + 1), nxt

            (_, dkp, dvp, _), d_all = jax.lax.scan(
                dbody, (tok, dk_pools, dv_pools, lengths), None,
                length=k + 1)
            props = jnp.swapaxes(d_all[:k], 0, 1)        # [S, k]
            # --- target scores k+1 positions in one ragged pass ------
            ids = jnp.concatenate([tok[:, None], props], axis=1)
            posm = lengths[:, None] + \
                jnp.arange(k + 1, dtype=jnp.int32)[None, :]
            bp = s_ * (k + 1)
            lim_f = jnp.repeat(limits, k + 1)
            pos_f = posm.reshape(bp)
            ok_f = pos_f < lim_f
            pos_f = jnp.where(ok_f, pos_f, 0)
            pt_f = jnp.where(ok_f[:, None],
                             jnp.repeat(pt, k + 1, axis=0), 0)
            caches = caches_of(k_pools, v_pools, pt_f, k_scales,
                               v_scales)
            logits, new = run(params, buffers,
                              ids.reshape(bp)[:, None], caches,
                              pos_f, None)
            kps = [c[0] for c in new]
            vps = [c[1] for c in new]
            kss = [c[3] for c in new] if quant else k_scales
            vss = [c[4] for c in new] if quant else v_scales
            # the TARGET's choice and its log-probability at every
            # position: what a committed token carries, accepted draft
            # proposal or not
            g, lp = _choose(logits)
            g, lp = g.reshape(s_, k + 1), lp.reshape(s_, k + 1)
            # --- greedy accept: longest prefix with d_{i+1} == g_i ---
            match = (props == g[:, :k]).astype(jnp.int32)
            acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
            counts = acc + 1       # committed tokens = g[:, :acc+1]
            return g, lp, counts, kps, vps, kss, vss, dkp, dvp

        spec = _xla_cost.instrument(spec, f"spec_k{k}")
        self._programs[key] = spec
        return spec

    # --- intake -------------------------------------------------------------
    def submit(self, input_ids, max_new_tokens=32, eos_token_id=None,
               request_id=None, tenant_id=None,
               priority_class=None, deadline=None,
               prebilled_tokens=0) -> RequestHandle:
        """Enqueue one sequence; returns its `RequestHandle`.  Raises
        ValueError when the request can never fit (prompt+max_new over
        the engine's per-sequence or pool capacity) — feasibility is
        checked at the door so the scheduler never deadlocks on an
        unservable request.  `tenant_id` names who the tenant ledger
        bills for this sequence's tokens/slot-time/page-seconds
        (ISSUE 16; None books under `anon`); `priority_class` orders
        admission and preemption (ISSUE 18; None → the default class);
        `deadline` (absolute monotonic) lets admission shed a request
        whose budget expired while queued with an honest
        `deadline_exceeded` instead of prefilling dead work;
        `prebilled_tokens` marks the first N accepted tokens as
        already billed by a prior replica (ISSUE 20 mid-stream resume
        — the decode books must conserve across the failover)."""
        t_in = time.perf_counter()
        seq = Sequence(input_ids, max_new_tokens,
                       eos_token_id=eos_token_id, request_id=request_id,
                       tenant_id=tenant_id, priority_class=priority_class,
                       deadline=deadline,
                       prebilled_tokens=prebilled_tokens)
        need = -(-(seq.prompt.size + seq.max_new_tokens)
                 // self.config.page_size)
        if need > self.pool.capacity:
            raise ValueError(
                f"request needs {need} pages, pool holds "
                f"{self.pool.capacity}")
        handle = RequestHandle(seq)
        seq.handle = handle
        if self._timeline_cap > 0:
            tl = RequestTimeline(seq.request_id, clock=self._clock,
                                 token_cap=self._timeline_cap)
            tl.event("submitted", prompt_tokens=int(seq.prompt.size),
                     max_new_tokens=int(seq.max_new_tokens))
            seq.timeline = tl
        # register BEFORE the scheduler can see the sequence: with the
        # loop thread running, a short request can be admitted,
        # finished, and its handle popped before submit() returns — a
        # post-hoc insert would leave a stale entry in _handles forever
        with self._table_lock:
            if self._closed:
                raise RuntimeError("engine is closed")
            self._handles[seq.request_id] = handle
            if seq.timeline is not None:
                # the timeline map is a bounded LRU that OUTLIVES the
                # handle: /debug/requests/<id> answers for completed
                # requests too, until _TIMELINE_LRU newer ones arrive
                self._timelines.pop(seq.request_id, None)
                self._timelines[seq.request_id] = seq.timeline
                while len(self._timelines) > _TIMELINE_LRU:
                    # evict the oldest COMPLETED request first: a
                    # still-streaming request must stay debuggable
                    # exactly while its stall is happening (surge can
                    # push >128 submissions past a live stream).  All
                    # live (pathological) → the bound still wins.
                    victim = next(
                        (rid for rid in self._timelines
                         if rid not in self._handles), None)
                    if victim is None:
                        victim = next(iter(self._timelines))
                    self._timelines.pop(victim)
        try:
            # validates vs max_pages_per_seq; stamps the timeline's
            # `queued` event under the scheduler's own lock
            self.scheduler.submit(seq)
        except Exception:
            with self._table_lock:
                self._handles.pop(seq.request_id, None)
                # a refused request must not occupy a timeline slot (or
                # answer /debug/requests with a ghost 'submitted' row)
                self._timelines.pop(seq.request_id, None)
            raise
        # entry of submit() -> the sequence stands in the scheduler's
        # queue: everything an arrival waits for before it CAN be
        # admitted
        _metrics.observe("engine.submit_wait_ms",
                         (seq.queued_at - t_in) * 1e3)
        _metrics.inc("engine.sequences", event="submitted")
        with self._work:
            self._work.notify_all()
        return handle

    def cancel(self, request_id) -> bool:
        """Abandon a sequence (client gone / explicit cancel): its
        handle completes as cancelled now; slot and pages return to the
        pool at the next schedule()."""
        ok = self.scheduler.cancel(request_id)
        if ok:
            _metrics.inc("engine.sequences", event="cancelled")
            with self._table_lock:
                handle = self._handles.pop(request_id, None)
            if handle is not None:
                handle._finish("cancelled")
            with self._work:
                self._work.notify_all()
        return ok

    # --- the engine step ----------------------------------------------------
    def step(self) -> bool:
        """One engine iteration: schedule -> prefill admissions ->
        ragged decode chunk -> detokenize/deliver.  Returns True when
        any work happened."""
        t_ask = time.perf_counter()
        with self._lock:
            _observe_since("engine.lock_wait_ms", t_ask, who="loop")
            # pt-lint: ok[PT504] (close() sets _closed holding BOTH locks; a reader holds either)
            if self._closed:
                return False
            # spec mode writes up to spec_tokens+1 cache positions per
            # pass — the scheduler must provision pages for the whole
            # pass, not just the committed prefix
            chunk = (self.config.spec_tokens + 1 if self._draft
                     else self.config.decode_chunk)
            with _trace.span("engine.schedule", cat="engine") as sp:
                out = self.scheduler.schedule(chunk)
                if sp is not None:
                    # a request left waiting beside a free slot was
                    # held back by pages (or class), not by the engine
                    sp.args.update(
                        admitted=len(out.prefills), waiting=out.waiting,
                        free_slots=self.config.max_slots
                        - len(out.running))
            for seq in out.evicted:
                _metrics.inc("engine.sequences", event="evicted")
            if out.finished:
                # released this schedule (completed earlier, or
                # cancelled while waiting/running): close the handle
                # and drop the engine's reference — a long-running
                # server must not accumulate one handle per cancelled
                # request
                with self._table_lock:
                    for seq in out.finished:
                        self._handles.pop(seq.request_id, None)
                for seq in out.finished:
                    if seq.handle is not None:
                        seq.handle._finish(
                            seq.finish_reason or "finished")
            did = bool(out.finished or out.evicted)
            for seq in out.prefills:
                self._prefill(seq)
                did = True
            running = [s for s in out.running
                       if not s.done and s.slot is not None]
            if running:
                if self._draft is not None:
                    self._spec_decode(running)
                else:
                    self._decode(running)
                did = True
            # free completed sequences' slots/pages NOW, not at the
            # next schedule — a drained engine must hold zero pages
            self.scheduler.release_finished()
            if did:
                self.steps += 1
            self._publish_gauges()
        return did

    def _bucket(self, s0: int) -> int:
        b = self.config.prefill_bucket
        return -(-s0 // b) * b

    def _prefill(self, seq: Sequence) -> None:  # pt-lint: ok[PT101,PT102] (step holds _lock)
        prompt = seq.resume_prompt()
        s0 = prompt.size
        shared = int(seq.shared_len or 0)
        if not seq.evictions:
            # queued -> its FIRST prefill begins (a preempted
            # sequence's later prefills are the scheduler's doing, not
            # an arrival's wait)
            _observe_since("engine.admit_wait_ms", seq.queued_at)
        if seq.timeline is not None:
            seq.timeline.event("prefill_start", tokens=s0,
                               shared=shared,
                               resumed=bool(seq.evictions))
        # tokens = prompt positions this prefill computes; the other
        # `cached_tokens` came off prefix-cache pages
        with _trace.span("engine.prefill", cat="engine",
                         request=seq.request_id, tokens=s0 - shared,
                         cached_tokens=shared, pages=len(seq.pages)):
            if shared > 0:
                t0, lp0, kbufs, vbufs, start = self._warm_prefill(
                    seq, prompt, shared)
            else:
                t0, lp0, kbufs, vbufs, start = self._cold_prefill(
                    seq, prompt)
            self._commit_prefix(seq, kbufs, vbufs, start)
            seq.length = s0
            seq.last_token = t0
        _metrics.inc("engine.prefill_tokens", s0 - shared,
                     cache=seq.cache_state or "miss")
        if seq.timeline is not None:
            seq.timeline.event("prefill_end", tokens=s0)
        if self._prefix is not None:
            if seq.cache_state in ("hit", "partial"):
                self._prefix_hits += 1
                _metrics.inc("engine.prefix_cache", event="hit")
            else:
                self._prefix_misses += 1
                _metrics.inc("engine.prefix_cache", event="miss")
            self._prefix_tokens_saved += shared
            self._prefix_tokens_total += s0
        if self.tenant_ledger is not None:
            # attribute prefill work — and the prefix cache's savings —
            # to the tenant (ISSUE 16): `shared` tokens came off cached
            # pages instead of running the model.  A recompute resume
            # bills its replayed tail honestly as computed work.
            self.tenant_ledger.record_prefill(
                seq.tenant_id, s0 - shared, saved=shared)
        _metrics.inc("engine.sequences", event="admitted")
        self._accept(seq, t0, lp0)

    def _cold_prefill(self, seq, prompt):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        """Dense prefill from token 0 (no cached prefix): the PR 8
        path.  Returns (first_token, its log-probability, k_bufs,
        v_bufs, pad_start) — the dense buffers feed `_commit_prefix`
        (prompt token t sits at buffer offset pad_start + t)."""
        s0 = prompt.size
        sb = self._bucket(s0)
        start = sb - s0
        quant = self.config.kv_precision == "int8"
        ids = np.zeros((1, sb), np.int32)
        ids[0, start:] = prompt
        prefill = self._prefill_program(sb)
        tok, lp, kbufs, vbufs = prefill(
            self._params, self._buffers, jnp.asarray(ids),
            jnp.asarray([start], jnp.int32))
        ps = self.config.page_size
        npb = -(-sb // ps)
        pages = np.zeros((npb,), np.int32)
        n_real = min(len(seq.pages), npb)
        pages[:n_real] = seq.pages[:n_real]
        pages_j = jnp.asarray(pages)
        start_j = jnp.asarray(start, jnp.int32)
        pack = self._pack_program(sb)
        if quant:
            (self._k_pools, self._v_pools, self._k_scales,
             self._v_scales) = pack(
                self._k_pools, self._v_pools, self._k_scales,
                self._v_scales, kbufs, vbufs, pages_j, start_j)
        else:
            self._k_pools, self._v_pools = pack(
                self._k_pools, self._v_pools, kbufs, vbufs,
                pages_j, start_j)
        if self._draft is not None:
            # the draft re-prefills the same bucket into its own
            # pools (same page ids) so proposals continue from the
            # full prompt context
            dprefill = self._prefill_program(sb, "draft")
            _, _, dkb, dvb = dprefill(
                self._draft["params"], self._draft["buffers"],
                jnp.asarray(ids), jnp.asarray([start], jnp.int32))
            dpack = self._pack_program(sb, "draft")
            self._draft["k_pools"], self._draft["v_pools"] = dpack(
                self._draft["k_pools"], self._draft["v_pools"],
                dkb, dvb, pages_j, start_j)
        return (*self._first_choice(tok, lp), kbufs, vbufs, start)

    @staticmethod
    def _first_choice(tok, lp):
        """A prefill's (token, logprob) on the host, one fetch."""
        tok, lp = jax.device_get((tok, lp))
        return int(tok[0]), float(lp[0])

    @staticmethod
    def _prefix_bucket(n_pages: int) -> int:
        """Prefix page capacity bucket: next power of two.  Cached
        prefix lengths vary per hit; bucketing bounds the compiled
        (sb, npp) shape set — the PT402 recompile-hazard budget on
        `gpt_cached_prefill_step` exists to catch a per-length shape
        leak here."""
        npp = 1
        while npp < n_pages:
            npp *= 2
        return npp

    def _warm_prefill(self, seq, prompt, shared):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        """Prefill ONLY the tail [shared, s0): the cached prefix pages
        are already in the sequence's table (refcounted shares), so the
        model processes s0 - shared tokens instead of s0 — the TTFT win
        the bench gates.  The tail's K/V packs into the sequence's
        PRIVATE tail pages (the boundary page is never shared: the
        scheduler caps sharing at the last full page before s0), so no
        shared page is ever written."""
        cfg = self.config
        ps = cfg.page_size
        tail = prompt[shared:]
        sb = self._bucket(tail.size)
        start = sb - tail.size
        npa = shared // ps
        npp = self._prefix_bucket(npa)
        ids = np.zeros((1, sb), np.int32)
        ids[0, start:] = tail
        ids_j = jnp.asarray(ids)
        start_j = jnp.asarray([start], jnp.int32)
        plen = jnp.asarray(shared, jnp.int32)
        quant = cfg.kv_precision == "int8"
        pages = np.zeros((npp,), np.int32)
        pages[:npa] = seq.pages[:npa]
        pages_j = jnp.asarray(pages)
        cpre = self._cached_prefill_program(sb, npp)
        if quant:
            ek, ev = self._sidecar_prefix(seq, npa, npp)
            tok, lp, kbufs, vbufs = cpre(self._params, self._buffers,
                                         ids_j, start_j, plen, ek, ev)
        else:
            tok, lp, kbufs, vbufs = cpre(self._params, self._buffers,
                                         ids_j, start_j, pages_j, plen,
                                         self._k_pools, self._v_pools)
        # pack the tail into the PRIVATE tail pages; in the returned
        # buffers prompt token t sits at offset start + t (the write
        # landed at [shared, shared+sb), tail token j at shared+start+j)
        npb = -(-sb // ps)
        tpages = np.zeros((npb,), np.int32)
        n_tail = max(0, min(len(seq.pages) - npa, npb))
        tpages[:n_tail] = seq.pages[npa:npa + n_tail]
        tpages_j = jnp.asarray(tpages)
        pk_start = jnp.asarray(shared + start, jnp.int32)
        pack = self._pack_program(sb)
        if quant:
            (self._k_pools, self._v_pools, self._k_scales,
             self._v_scales) = pack(
                self._k_pools, self._v_pools, self._k_scales,
                self._v_scales, kbufs, vbufs, tpages_j, pk_start)
        else:
            self._k_pools, self._v_pools = pack(
                self._k_pools, self._v_pools, kbufs, vbufs,
                tpages_j, pk_start)
        if self._draft is not None:
            # warm-prefill the draft's tail over ITS pools (exact
            # precision, same page ids): the cached prefix pages hold
            # the donor's draft K/V — a pure function of the prefix
            # tokens, so they are this prompt's draft prefix too
            dcpre = self._cached_prefill_program(sb, npp, "draft")
            _, _, dkb, dvb = dcpre(
                self._draft["params"], self._draft["buffers"], ids_j,
                start_j, pages_j, plen, self._draft["k_pools"],
                self._draft["v_pools"])
            dpack = self._pack_program(sb, "draft")
            self._draft["k_pools"], self._draft["v_pools"] = dpack(
                self._draft["k_pools"], self._draft["v_pools"],
                dkb, dvb, tpages_j, pk_start)
        # commit offset contract (_commit_prefix): prompt token t sits
        # at buffer offset start + t — the fresh span landed at
        # [shared, shared+sb), so tail token j (= prompt token
        # shared+j) is at shared + start + j = start + (shared+j).
        # Returning shared+start here would shift every sidecar slice
        # one whole prefix past the real tokens.
        return (*self._first_choice(tok, lp), kbufs, vbufs, start)

    def _sidecar_prefix(self, seq, npa, npp):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        """int8-KV tier: stack the matched radix nodes' commit-time
        EXACT page copies into the warm program's per-layer prefix
        buffers ([npp, hkv, ps, d], zero-padded past npa)."""
        zero = jnp.zeros((self._hkv, self.config.page_size, self._hd),
                         self._dtype)
        ek, ev = [], []
        for li in range(self._layers):
            ks, vs = [], []
            for i in range(npa):
                ex = seq.shared_nodes[i].exact
                if ex is None:
                    raise RuntimeError(
                        "prefix-cache node without an exact sidecar "
                        "under kv_precision=int8 (commit-path bug)")
                ks.append(ex[li][0])
                vs.append(ex[li][1])
            pad = [zero] * (npp - npa)
            ek.append(jnp.stack(ks + pad))
            ev.append(jnp.stack(vs + pad))
        return ek, ev

    def _commit_prefix(self, seq, kbufs, vbufs, start):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        """Register the ORIGINAL prompt's full pages in the radix index
        (the partial tail page stays private — it is still written by
        decode).  `start` is the buffer offset of prompt token 0 in the
        just-returned dense buffers: in BOTH the cold and warm cases
        prompt token t sits at `start + t`, which is where the int8
        sidecar's exact page copies are sliced from."""
        if self._prefix is None:
            return
        ps = self.config.page_size
        n_full = min(int(seq.prompt.size) // ps, len(seq.pages))
        if n_full <= 0:
            return
        exact = None
        if self.config.kv_precision == "int8":
            shared_chunks = int(seq.shared_len or 0) // ps
            exact = []
            for i in range(n_full):
                if i < shared_chunks:
                    # node already exists (matched at admission);
                    # insert never reads this slot
                    exact.append(None)
                    continue
                lo = start + i * ps
                exact.append([
                    (kbufs[li][0, :, lo:lo + ps, :],
                     vbufs[li][0, :, lo:lo + ps, :])
                    for li in range(self._layers)])
        self._prefix.insert(seq.prompt[:n_full * ps],
                            seq.pages[:n_full], exact=exact)

    def _batch_arrays(self, running):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        """The decode programs' host inputs (last token, page table,
        cached length per slot) and, from the same arrays, what the
        dispatch runs: `live_tokens`, the cached positions its
        `len(running)` slots attend (the `engine.decode` span's field).
        Counts the dispatch — plain or speculative, one
        `engine.steps{kind=decode}` each — so the `engine.decode_*`
        sums over that count are the means a step in either mode."""
        s_, p_ = self.config.max_slots, self.max_pages_per_seq
        tok = np.zeros((s_,), np.int32)
        pt = np.zeros((s_, p_), np.int32)
        lengths = np.zeros((s_,), np.int32)
        for seq in running:
            tok[seq.slot] = seq.last_token
            pt[seq.slot, :len(seq.pages)] = seq.pages
            lengths[seq.slot] = seq.length
        live_tokens = int(lengths.sum())
        _metrics.inc("engine.steps", kind="decode")
        _metrics.inc("engine.decode_slots", len(running))
        _metrics.inc("engine.decode_live_tokens", live_tokens)
        return (jnp.asarray(tok), jnp.asarray(pt), jnp.asarray(lengths),
                live_tokens)

    def _scales_args(self):  # pt-lint: ok[PT101,PT102] (step holds _lock)
        if self._k_scales is None:
            return [], []
        return self._k_scales, self._v_scales

    def _decode(self, running) -> None:  # pt-lint: ok[PT101,PT102] (step holds _lock)
        cfg = self.config
        t_step = time.perf_counter()
        tok, pt, lengths, live = self._batch_arrays(running)
        # ALWAYS dispatch the configured chunk: shrinking the scan to
        # the batch's max remaining would compile one program per
        # distinct tail length — a compile per shape costs far more
        # than the few discarded tail tokens, and a single decode
        # program is the fixed-compiled-shape contract (the
        # log-probabilities ride the same program for the same reason:
        # always computed, never a second program for a flag)
        n = cfg.decode_chunk
        decode = self._decode_program(n)
        ks, vs = self._scales_args()
        with _trace.span("engine.decode", cat="engine", batch=len(running),
                         chunk=n, occupancy=len(running) / cfg.max_slots,
                         live_tokens=live):
            toks, lps, self._k_pools, self._v_pools, ks, vs = decode(
                self._params, self._buffers, self._k_pools,
                self._v_pools, ks, vs, tok, pt, lengths)
            if self._k_scales is not None:
                self._k_scales, self._v_scales = ks, vs
        with _trace.span("engine.detokenize", cat="engine",
                         batch=len(running), chunk=n):
            toks, lps = jax.device_get((toks, lps))
            for seq in running:
                row, lrow = toks[seq.slot], lps[seq.slot]
                for j in range(n):
                    if seq.done:
                        break  # mid-chunk finish: later tokens are the
                        # frozen-slot continuation, not output
                    self._accept(seq, int(row[j]), float(lrow[j]))
                seq.length += n
                seq.last_token = int(row[n - 1])
        self._bill_decode_slots(running, t_step)

    def _spec_decode(self, running) -> None:  # pt-lint: ok[PT101,PT102] (step holds _lock)
        cfg = self.config
        k = cfg.spec_tokens
        t_step = time.perf_counter()
        tok, pt, lengths, live = self._batch_arrays(running)
        # per-slot lifetime cap (prompt+max_new cache positions): rows
        # of the pass at or past it are masked to the scratch page
        # inside the program (free slots stay at 0 = fully masked)
        limits = np.zeros((cfg.max_slots,), np.int32)
        for seq in running:
            limits[seq.slot] = seq.prompt.size + seq.max_new_tokens
        spec = self._spec_program(k)
        ks, vs = self._scales_args()
        d = self._draft
        with _trace.span("engine.decode", cat="engine",
                         batch=len(running), chunk=k + 1, spec=True,
                         occupancy=len(running) / cfg.max_slots,
                         live_tokens=live):
            (g, lps, counts, self._k_pools, self._v_pools, ks, vs,
             d["k_pools"], d["v_pools"]) = spec(
                self._params, self._buffers, d["params"], d["buffers"],
                self._k_pools, self._v_pools, ks, vs,
                d["k_pools"], d["v_pools"], tok, pt, lengths,
                jnp.asarray(limits))
            if self._k_scales is not None:
                self._k_scales, self._v_scales = ks, vs
        with _trace.span("engine.detokenize", cat="engine",
                         batch=len(running), chunk=k + 1):
            g, lps, counts = jax.device_get((g, lps, counts))
            for seq in running:
                row, lrow = g[seq.slot], lps[seq.slot]
                cnt = int(counts[seq.slot])
                # cnt-1 draft proposals were accepted; the rest of the
                # pass's k proposals were rejected (their cache slots
                # get overwritten before any later step attends them)
                _metrics.inc("engine.spec_decode", cnt - 1,
                             result="accepted")
                _metrics.inc("engine.spec_decode", k - (cnt - 1),
                             result="rejected")
                for j in range(cnt):
                    if seq.done:
                        break  # mid-pass finish (eos): later tokens are
                        # the frozen continuation, not output
                    self._accept(seq, int(row[j]), float(lrow[j]))
                seq.length += cnt
                seq.last_token = int(row[cnt - 1])
        self._bill_decode_slots(running, t_step)

    def _bill_decode_slots(self, running, t_step) -> None:
        """Decode-slot occupancy billing (ISSUE 16): every sequence in
        the pass occupied one batch slot for the step's wall time —
        THE contended capacity unit (max_slots), so a tenant holding
        slots with long sequences shows up even at a low token rate.
        The same charge feeds the scheduler's quota/fairness meter
        (ISSUE 18) — QoS prices in the unit the ledger bills."""
        if not running:
            return
        step_ms = (time.perf_counter() - t_step) * 1e3
        for seq in running:
            if self.tenant_ledger is not None:
                self.tenant_ledger.record_decode_slot_ms(
                    seq.tenant_id, step_ms)
            self.scheduler.note_decode_slot_ms(seq.tenant_id, step_ms)

    def _accept(self, seq: Sequence, tok: int, logprob: float) -> None:
        """One generated token passes the host with its
        log-probability: record, deliver, finish on eos / length
        (mirrors generate()'s freezing: the eos itself is emitted,
        nothing after it)."""
        seq.tokens.append(int(tok))
        seq.logprobs.append(float(logprob))
        if seq.timeline is not None:
            seq.timeline.token()
        if len(seq.tokens) <= seq.prebilled_tokens:
            # resume verify token (ISSUE 20): the dead replica already
            # billed this position — re-deriving it must not double a
            # tenant's decode book (neither branch below runs, so
            # engine.tokens and the per-tenant total stay in lockstep)
            pass
        elif self.tenant_ledger is not None:
            # the ledger incs engine.tokens INSIDE its lock so the
            # counter and per-tenant decode totals move atomically (a
            # concurrent snapshot can never see them skewed)
            self.tenant_ledger.record_decode(seq.tenant_id)
        else:
            _metrics.inc("engine.tokens")
        if seq.handle is not None:
            seq.handle._push(tok, logprob)
        if seq.eos_token_id is not None and int(tok) == seq.eos_token_id:
            self._finish(seq, "eos")
        elif len(seq.tokens) >= seq.max_new_tokens:
            self._finish(seq, "length")

    def _finish(self, seq: Sequence, reason: str) -> None:
        if seq.timeline is not None:
            seq.timeline.event("finished", reason=reason,
                               generated=len(seq.tokens))
        self.scheduler.finish(seq, reason)
        # release the slot/pages BEFORE the handle signals completion:
        # a client (or test) that observes the finished stream must
        # never find the sequence's pages still held — the end-of-step
        # release would otherwise race the handler thread by however
        # long the GIL delays the step's tail
        self.scheduler.release_finished()
        _metrics.inc("engine.sequences", event="completed")
        if seq.handle is not None:
            seq.handle._finish(reason)
        with self._table_lock:
            self._handles.pop(seq.request_id, None)

    def _publish_gauges(self) -> None:  # pt-lint: ok[PT102] (_prefix set once at construction, never rebound)
        st = self.scheduler.stats()
        _metrics.set_gauge("engine.active_sequences", st["running"])
        _metrics.set_gauge("engine.waiting_sequences", st["waiting"])
        _metrics.set_gauge("engine.batch_occupancy", st["occupancy"])
        _metrics.set_gauge("engine.page_utilization",
                           self.pool.utilization())
        if self._prefix is not None:
            total = self._prefix_hits + self._prefix_misses
            _metrics.set_gauge("engine.prefix_cached_tokens",
                               self._prefix.cached_tokens)
            _metrics.set_gauge("engine.prefix_cache_hit_rate",
                               (self._prefix_hits / total) if total
                               else 0.0)

    # --- maintenance --------------------------------------------------------
    def defrag(self) -> int:
        """Compact live pages to the densest pool prefix: apply the
        allocator's moves to the device pools and every live page
        table.  Returns the number of pages moved."""
        with self._lock:
            moves = self.pool.defrag()
            if not moves:
                return 0
            self.decisions.record(
                "defrag", moves=len(moves),
                pressure=round(self.pool.utilization(), 4))
            # ascending-dst order is overwrite-safe: src > dst always,
            # and every src exceeds all earlier dsts
            for src, dst in sorted(moves.items(), key=lambda kv: kv[1]):
                self._k_pools = [p.at[dst].set(p[src])
                                 for p in self._k_pools]
                self._v_pools = [p.at[dst].set(p[src])
                                 for p in self._v_pools]
                if self._k_scales is not None:
                    self._k_scales = [s.at[dst].set(s[src])
                                      for s in self._k_scales]
                    self._v_scales = [s.at[dst].set(s[src])
                                      for s in self._v_scales]
                if self._draft is not None:
                    d = self._draft
                    d["k_pools"] = [p.at[dst].set(p[src])
                                    for p in d["k_pools"]]
                    d["v_pools"] = [p.at[dst].set(p[src])
                                    for p in d["v_pools"]]
            for seq in self.scheduler.running_seqs():
                seq.pages = [moves.get(p, p) for p in seq.pages]
            if self._prefix is not None:
                self._prefix.apply_moves(moves)
        return len(moves)

    def clear_prefix_cache(self) -> int:
        """Drop every prefix-cache reference (pages shared with live
        sequences stay live under the sequences' own refs).  Returns
        the number of cache pages released — after a full drain plus a
        clear, `pool.used_pages` must be exactly 0 (the chaos leak
        assertion)."""
        with self._lock:
            if self._prefix is None:
                return 0
            return self._prefix.clear()

    def prefix_cache_stats(self) -> dict:  # pt-lint: ok[PT102] (_prefix set once at construction; counters are monotonic snapshots)
        """Hit/miss/saved-token ledger + radix index size — rides
        `engine.stats()` into /ready and /debug/telemetry."""
        hits, misses = self._prefix_hits, self._prefix_misses
        total = hits + misses
        st = {
            "enabled": self._prefix is not None,
            "hits": hits,
            "misses": misses,
            "hit_rate": (hits / total) if total else 0.0,
            "prefill_tokens_saved": self._prefix_tokens_saved,
            "prefill_tokens_total": self._prefix_tokens_total,
            "tokens_saved_frac":
                (self._prefix_tokens_saved
                 / max(1, self._prefix_tokens_total)),
        }
        if self._prefix is not None:
            st.update(self._prefix.stats())
        return st

    # --- per-token latency attribution (ISSUE 15) ---------------------------
    def request_debug(self, request_id):
        """The answer to "why was this token slow": the request's
        timeline (events, decimated token stamps, top inter-token
        gaps), each gap annotated with the scheduler decisions that
        landed INSIDE it (admits of other sequences, recompute
        evictions, prefix reclaims, defrags — with seq ids and the
        page pressure at decision time) plus a human-readable `cause`
        line.  None for unknown / aged-out ids.  Works for completed
        requests until `_TIMELINE_LRU` newer submissions age them
        out."""
        with self._table_lock:
            tl = self._timelines.get(request_id)
        if tl is None:
            return None
        d = tl.describe()
        for gap in d["gaps"]:
            evs = self.decisions.window(gap["t_start"], gap["t_end"],
                                        pad=0.005)
            gap["events"] = evs
            causes = []
            for ev in evs:
                who = ev.get("request_id") or ev.get("for_request")
                if ev["kind"] == "evict_recompute" \
                        and ev.get("request_id") == request_id:
                    causes.append(
                        f"evicted (recompute) for "
                        f"{ev.get('for_request')}, pool at "
                        f"{ev.get('pressure', 0):.0%}")
                elif ev["kind"] == "admit" and who != request_id:
                    causes.append(
                        f"co-scheduled {ev.get('cache_state', 'cold')} "
                        f"prefill of {who}, pool at "
                        f"{ev.get('pressure', 0):.0%}")
                elif who != request_id:
                    causes.append(
                        f"co-scheduled {ev['kind']} "
                        f"({who or 'pool'}), pool at "
                        f"{ev.get('pressure', 0):.0%}")
                else:
                    causes.append(
                        f"{ev['kind']} of this request, pool at "
                        f"{ev.get('pressure', 0):.0%}")
            gap["cause"] = "; ".join(causes) if causes else None
        d["decision_ring_tail"] = self.decisions.events(limit=32)
        return d

    def recent_timelines(self, n=8) -> list:
        """Bounded per-request timeline summaries, newest last — what
        /debug/telemetry and the exporter dumps embed (full detail
        stays behind /debug/requests/<id>)."""
        with self._table_lock:
            tls = list(self._timelines.values())[-int(n):]
        return [tl.summary() for tl in tls]

    # --- loop / lifecycle ---------------------------------------------------
    def start(self):
        """Run the engine loop on a daemon thread (the serving mode);
        `step()` remains callable inline for tests."""
        with self._lock:
            if self._closed:
                raise RuntimeError("engine is closed")
            if self._thread is not None:
                return self
            self._running = True
            self._thread = threading.Thread(
                target=self._loop, daemon=True, name="paddle-tpu-engine")
            self._thread.start()
        return self

    def _loop(self):
        # _running is a stop flag: a stale read costs one extra step,
        # and step() takes the step lock itself.  submit() and cancel()
        # take only the table lock, so a loop that steps back to back
        # keeps nobody out
        waiting = None  # the open `engine.wait_request` span: ONE per
        # idle stretch (an empty batch, blocked on the next request),
        # closed before the step that serves the request begins
        while self._running:  # pt-lint: ok[PT102]
            if not self.step():
                with self._work:
                    # pt-lint: ok[PT504] (wakeup re-check: _running/scheduler are OWNED by _lock; reading them under the _work cv is the standard missed-notify guard — a stale read costs one 50ms wait)
                    if self._running and not self.scheduler.has_work():
                        if waiting is None:
                            waiting = _trace.begin("engine.wait_request",
                                                   cat="engine")
                        self._work.wait(timeout=0.05)
                    # pt-lint: ok[PT504] (same re-check as above)
                    if waiting is not None and (
                            self.scheduler.has_work() or not self._running):
                        _trace.end(waiting)
                        waiting = None
        _trace.end(waiting)

    def stop(self, timeout: float = 10.0):
        with self._lock:
            self._running = False
            thread = self._thread
            self._thread = None
        with self._work:
            self._work.notify_all()
        if thread is not None:
            thread.join(timeout=timeout)

    def close(self, timeout: float = 10.0) -> None:
        """Stop the loop and give the device back: cancels whatever is
        still in flight, then drops the page pools, their scale
        tables, the weights and every compiled program (the draft's
        too), so a caller can put something else on the chip — a
        reference, another engine — without reaching into privates.
        Host-side state (`pool`, `scheduler`, `stats()`, timelines)
        stays readable.  Idempotent; `submit()` and `start()` raise
        afterwards and `step()` does nothing."""
        self.stop(timeout=timeout)
        with self._lock:
            if self._closed:
                return
            with self._table_lock:
                self._closed = True
                live = list(self._handles.values())
                self._handles.clear()
            for handle in live:
                self.scheduler.cancel(handle.request_id)
                handle._finish("cancelled")
            self.scheduler.schedule()      # slots and pages go back
            self._k_pools = self._v_pools = None
            self._k_scales = self._v_scales = None
            self._params = self._buffers = None
            self._draft = None
        # no step runs any more, so the memo needs no lock
        self._programs.clear()

    # --- convenience (tests / bench / equivalence) --------------------------
    def generate(self, prompts, max_new_tokens=32, eos_token_id=None,
                 timeout: float = 300.0):
        """Submit every prompt and run the engine to completion
        (inline when the loop thread is not running).  Returns a list
        of int32 [s0_i + n_generated_i] arrays — `generate()`-shaped
        output for direct equivalence checks."""
        handles = [self.submit(p, max_new_tokens,
                               eos_token_id=eos_token_id)
                   for p in prompts]
        # _thread is set-once before any submit in the loop-thread
        # mode; inline callers never race it
        if self._thread is None:  # pt-lint: ok[PT102]
            idle = 0
            while any(not h.done.is_set() for h in handles):
                if self.step():
                    idle = 0
                else:
                    idle += 1
                    if idle > 1000:
                        raise RuntimeError(
                            "engine made no progress (scheduler stuck)")
        return [h.result(timeout=timeout) for h in handles]

    def stats(self) -> dict:
        st = self.scheduler.stats()
        st["pages"] = self.pool.stats()
        cfg = self.config
        # the active quantized-decode tiers ride the stats dict into
        # /health and /ready (serving.py embeds engine.stats() there)
        st["weight_precision"] = cfg.weight_precision or "full"
        st["kv_precision"] = cfg.kv_precision or "full"
        # pt-lint: ok[PT102] (None-check of the set-once _draft binding)
        st["spec_tokens"] = cfg.spec_tokens if self._draft else 0
        st["page_bytes"] = self._page_bytes()
        st["prefix_cache"] = self.prefix_cache_stats()
        # monotonic int snapshot for telemetry; a stale read is a fine
        # answer to "how many steps so far"
        st["steps"] = self.steps  # pt-lint: ok[PT102]
        return st
