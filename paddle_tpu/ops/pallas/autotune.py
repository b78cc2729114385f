"""Runtime block-size autotune for the Pallas kernel tier.

Role parity: `paddle/phi/kernels/autotune/` (`cache.h`,
`switch_autotune.cc`) — the reference times candidate kernel algorithms at
runtime and caches the winner per input signature. Here the "algorithm"
axis is Pallas block shape: on the first call for a given (op, shape,
dtype) signature on TPU, each candidate config is compiled and
slope-timed on the device with real data, and the winner is cached
in-process and on disk (so one process pays the search once per
signature, ever).

Gating: `FLAGS_use_autotune` (default on; `paddle.set_flags` or env).
Never runs in interpreter mode / off-TPU — the static default config is
used there.

Timing: value-fetch slope method — each candidate is timed by chaining N
iterations between two device-to-host fetches and dividing the
difference (a fetched value is a true synchronization).
"""
from __future__ import annotations

import json
import os
import threading
import time

import numpy as np

import jax

from ...observability import flight as _flight
from ...observability import metrics as _metrics
from ...observability import trace as _trace

# Tests point this at a scratch file; None = resolve at first use.
_CACHE_PATH = None
_cache = None
_lock = threading.Lock()


def _enabled() -> bool:
    from ...core import flags

    return bool(flags.get_flags("FLAGS_use_autotune")["FLAGS_use_autotune"])


def _cache_path():
    """Where the winners persist: ``PADDLE_TPU_AUTOTUNE_CACHE`` if set,
    else ``autotune.json`` inside jax's persistent compilation cache
    directory (``backend_guard.enable_compile_cache`` /
    ``JAX_COMPILATION_CACHE_DIR``), so one directory carries everything
    a later process can reuse.  None (in-process cache only) when the
    process configured no compile cache."""
    if _CACHE_PATH is not None:
        return _CACHE_PATH
    env = os.environ.get("PADDLE_TPU_AUTOTUNE_CACHE")
    if env:
        return env
    cache_dir = jax.config.jax_compilation_cache_dir
    return os.path.join(cache_dir, "autotune.json") if cache_dir else None


def _load() -> dict:  # pt-lint: ok[PT101,PT102] (callers hold _lock)
    global _cache
    if _cache is None:
        _cache = {}
        path = _cache_path()
        if path is not None and os.path.exists(path):
            with open(path) as f:
                _cache = json.load(f)
    return _cache


def _save() -> None:  # pt-lint: ok[PT102] (callers hold _lock)
    path = _cache_path()
    if path is None:
        return
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(_cache, f, indent=0, sort_keys=True)
        os.replace(tmp, path)
    except OSError as e:
        # cache is an optimization; never fail the op over it — but a
        # cache that silently stops persisting means every future
        # process re-pays the search
        _flight.record("autotune.cache_write_failed", path=path,
                       error=f"{type(e).__name__}: {e}")


def _sync_fetch(r):
    leaf = jax.tree_util.tree_leaves(r)[0]
    return float(np.asarray(jax.device_get(leaf.ravel()[0:1]),
                            np.float32)[0])


def _slope_time(f, x, n1=2, n2=8) -> float:
    """Per-iteration seconds of shape-preserving `f` starting from `x`.

    The whole chain runs inside ONE jitted fori_loop with a traced trip
    count: chaining separate dispatches measures the per-dispatch host
    gap, not the kernel — at sub-10 ms kernel times that is noise.
    One dispatch + one fetch per timing; the (d2-d1)/(n2-n1) difference
    cancels the constant."""
    @jax.jit
    def loop(x, n):
        return jax.lax.fori_loop(0, n, lambda i, y: f(y), x)

    _sync_fetch(loop(x, n1))  # compile + warm
    # a host stall during either timing corrupts the difference —
    # clamping a negative diff to ~0 once made the WORST candidate "win"
    # a search ((128,128) cached for 16x1024x12x64). Only positive
    # diffs count; a candidate with no valid timing in 4 tries loses.
    best = float("inf")
    valid = 0
    for _ in range(4):
        t0 = time.perf_counter()
        _sync_fetch(loop(x, n1))
        d1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        _sync_fetch(loop(x, n2))
        d2 = time.perf_counter() - t0
        if d2 > d1:
            valid += 1
            best = min(best, (d2 - d1) / (n2 - n1))
            if valid >= 2:
                break
    if valid == 0:
        raise RuntimeError("no valid timing (host stalls)")
    return best


def _devkind():
    try:
        dev = jax.devices()[0]
        if dev.platform != "tpu":
            return None
        return getattr(dev, "device_kind", dev.platform)
    except Exception:
        return None


def cached_config(op: str, signature):
    """The cached winner for (device_kind, op, signature), else None.
    Pure lookup — never searches, never counts hit/miss (dispatch sites
    use it to detect deliberate non-reuse, e.g. the flash layout tag's
    cross-layout refusal)."""
    devkind = _devkind()
    if devkind is None:
        return None
    with _lock:
        hit = _load().get(f"{devkind}|{op}|{signature}")
    if hit is None:
        return None
    cfg = hit["config"]
    return tuple(cfg) if isinstance(cfg, list) else cfg


def pick(op: str, signature, candidates, run, default):
    """Return the fastest of `candidates` for this signature.

    run(config) must return ``(f, x)`` — a shape-preserving jax function
    executing the kernel with that config and its REAL device input — so
    timing can chain f inside one compiled loop (see _slope_time).
    Results are cached under (device_kind, op, signature). Falls back to
    `default` when autotune is disabled or every candidate fails.

    Telemetry: cache reuse counts `autotune.hit`, a fresh search counts
    `autotune.miss` (the search itself and its winner land in the flight
    recorder) — the counters that make a cold or poisoned cache visible
    instead of a silent 4x kernel slowdown (PERF.md r5).
    """
    if not _enabled() or len(candidates) <= 1:
        return default
    devkind = _devkind()
    if devkind is None:
        return default
    key = f"{devkind}|{op}|{signature}"
    with _lock:
        cache = _load()
        hit = cache.get(key)
    if hit is not None:
        _metrics.inc("autotune.hit")
        cfg = hit["config"]
        return tuple(cfg) if isinstance(cfg, list) else cfg
    _metrics.inc("autotune.miss")
    _flight.record("autotune.search", op=op, signature=str(signature),
                   n_candidates=len(candidates))
    # search outside the lock: candidate compiles can take seconds each.
    # The whole search is one trace span (it can cost seconds of bench
    # wall — it must be visible as a slice, not mystery idle time), with
    # the per-candidate timings attached once the winner is known.
    best, best_t, timings = None, float("inf"), {}
    with _trace.span(f"autotune.search:{op}", cat="autotune",
                     signature=str(signature),
                     n_candidates=len(candidates)) as _sp:
        for cfg in candidates:
            try:
                # dispatch sites call pick() while an outer program is
                # being TRACED (the train step's jit): without this, the
                # candidate's ops bind into that trace, its results are
                # tracers, and the timing fetch raises — every search
                # failed that way on the chip (PERF.md, PR 23).  Not
                # ensure_compile_time_eval: its eager constant folding
                # evaluates the kernels' `program_id` and raises
                with jax.core.eval_context():
                    f, x = run(cfg)
                    t = _slope_time(f, x)
            except Exception as e:
                # a config that fails to compile just loses — counted,
                # and the error kept in the flight ring, so "every
                # candidate failed" is diagnosable instead of looking
                # like a silent default
                _metrics.inc("autotune.candidate_failed", op=op)
                _flight.record("autotune.candidate_failed", op=op,
                               config=str(cfg),
                               error=f"{type(e).__name__}: {e}"[:400])
                continue
            timings[str(cfg)] = round(t * 1e3, 4)
            if t < best_t:
                best, best_t = cfg, t
        if _sp is not None:
            _sp.args["winner"] = str(best)
            _sp.args["ms"] = timings
    if best is None:
        _metrics.inc("autotune.search_failed")
        _flight.record("autotune.search_failed", op=op,
                       signature=str(signature), default=str(default))
        return default
    _flight.record("autotune.tuned", op=op, signature=str(signature),
                   winner=str(best), ms=timings)
    with _lock:
        cache = _load()
        cache[key] = {"config": list(best) if isinstance(best, tuple)
                      else best, "ms": timings}
        _save()
    return best


def clear_cache():
    """Drop the in-process and on-disk cache (tests / re-tuning)."""
    global _cache
    with _lock:
        _cache = {}
        path = _cache_path()
        if path is not None and os.path.exists(path):
            os.remove(path)
