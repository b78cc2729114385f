"""Seconds of set-up that the program's own compile-time ledger accounts
for (`paddle_tpu.observability.xla_cost`): with `"program"` and `"fields"`,
the sum of those fields of `program_ledger(<program>)` (`trace_ms`,
`lower_ms`, `compile_ms`: wall of the three stages, summed over the label's
compiles); with `"other_programs": true`, JAX's own trace + lower + compile
durations over every program of the process (`process_compile_totals`) minus
all labelled programs, stage by stage — what the small unlabelled programs
cost (the model built op by op, the benchmark's own readers).  Both as they
stood when the traced window opened: the reference compiles after it and is
no part of `setup_s`.  None where the program keeps no ledger."""
from harness import common

STAGES = ("trace_ms", "lower_ms", "compile_ms")


def read(run, spec):
    try:
        from paddle_tpu.observability import xla_cost
    except ImportError:
        return None
    if not hasattr(xla_cost, "program_ledger"):
        return None
    red = run["reduced"]
    until = (red["window"][0] - red["perf_offset_ns"]) / 1e9   # perf_counter
    ledger = xla_cost.program_ledger()

    def before_window(entries, fields):
        return sum(rec[k] for e in entries for rec in e["compiles"]
                   if rec["at"] <= until for k in fields)

    if spec.get("other_programs"):
        totals = xla_cost.process_compile_totals(until=until)
        if not totals["compile_n"]:
            return None                 # the listener saw nothing: no reading
        labelled = {k: before_window(ledger.values(), [k]) for k in STAGES}
        common.log("compile_totals", {"process": totals, "labelled": labelled})
        return sum(max(totals[k] - labelled[k], 0.0) for k in STAGES) / 1e3
    entry = ledger.get(spec["program"])
    if entry is None:
        return None
    if "ledger_logged" not in run:
        run["ledger_logged"] = True
        common.log("ledger", {label: {k: v for k, v in e.items() if k != "ops"}
                              for label, e in ledger.items()})
    return before_window([entry], spec["fields"]) / 1e3
