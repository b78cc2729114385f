"""Share of the set-up's compiles that JAX's persistent compilation cache
answered: `cache_hits` over `cache_requests` of the program's
`paddle_tpu.observability.xla_cost.process_compile_totals(until=<the traced
window's start>)`, % — the reference compiles after the window and is no
part of `setup_s`.  A request is a compile that asked the cache (every one
while a cache directory is set), a hit one that was loaded from it.  A cold
or evicted cache reads near 0 and a `setup_s` that pays every compile; a
warm one near 100.

One earlier line, `cache {...}`: the totals (`cache_requests`, `cache_hits`,
`cache_writes`, `cache_retrieval_ms`, `cache_saved_ms`: what the loaded
entries took to compile when they were written), JAX's own `compile_ms` of
every program beside them, what each LABELLED program's compiles were
(`"hit"`, `"miss"` or null: not asked) and the run's `setup_s`.

Returns None where the program counts no such thing (the parent of PR 37) or
nothing was requested (no cache directory)."""
from harness import common

CACHE_KEYS = ("cache_requests", "cache_hits", "cache_writes",
              "cache_retrieval_ms", "cache_saved_ms")


def read(run, spec):
    try:
        from paddle_tpu.observability import xla_cost
    except ImportError:
        return None
    if not hasattr(xla_cost, "process_compile_totals"):
        return None
    red = run["reduced"]
    until = (red["window"][0] - red["perf_offset_ns"]) / 1e9   # perf_counter
    totals = xla_cost.process_compile_totals(until=until)
    if "cache_requests" not in totals:
        return None
    labelled = {label: [rec.get("cache") for rec in e["compiles"]
                        if rec["at"] <= until]
                for label, e in xla_cost.program_ledger().items()}
    setup = run["e2e"].get("setup_s")
    common.log("cache", {
        **{k: round(totals[k], 1) for k in CACHE_KEYS},
        "compile_n": totals["compile_n"],
        "compile_ms": round(totals["compile_ms"], 1),
        "labelled": labelled, "setup_s": setup[0] if setup else None})
    if not totals["cache_requests"]:
        return None
    return 100.0 * totals["cache_hits"] / totals["cache_requests"]
