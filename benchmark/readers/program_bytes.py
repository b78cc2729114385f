"""The step's BYTES as the program's own compile-time ledger accounts for
them (`paddle_tpu.observability.xla_cost.program_ledger(<program>)`): the
compiler's totals of the label's latest compile under `"memory"`
(`memory_analysis()`: `temp_bytes`, `argument_bytes`, ...) and under
`"bytes"` a liveness sweep of the scheduled program — the peak of the
temporaries in HBM and the instruction it lies at, what is live there by
scope, and what the forward holds for the backward (`residual_bytes`).

A metric names one `"field"`:

  `temp_bytes`      the compiler's temporaries over the table's HBM bytes, %
  `residual_bytes`  what the forward holds for the backward over the same, %
  `sweep_gap`       |the sweep's `peak_bytes` - `temp_bytes`| over
                    `temp_bytes`, %: the sweep's own error against the
                    compiler, as `unscoped_share.train` is for time

One earlier line, `bytes {...}`, in GB: `memory`, the witnesses side by side
(`sweep_peak`, the compiler's `temp`, the driver's `memory_peak`; the third
witness of the temporaries, the backend's `peak_bytes_reserved` at the
window's end, stands on the driver's own `memory {...}` line above — by the
time the readers run the reference's programs have reserved more), `peak_at`,
`backward_at`, `by_scope_at_peak` and `residual_by_scope` (12 rows each), the
8 largest buffers live at the peak, and what the ledger cost (`sweep_ms`
inside `ledger_ms`, summed over the label's compiles).

Returns None where the program keeps no such field (the parent of PR 37, a
backend that gave no HLO text or no memory analysis)."""
from harness import common

scope_ms = common.load_module("readers", "scope_ms")

GB = 1e9
ROWS = 12


def _gb(n):
    return round(n / GB, 4)


def _top(by_scope):
    return {k: _gb(v) for k, v in list(by_scope.items())[:ROWS]}


def account(memory, swept):
    """The ledger's `memory` and `bytes` as one printable dict, in GB (also
    what `tools/step_bytes.py` prints of a compile for a described chip)."""
    temp = memory["temp_bytes"]
    line = {"memory": {k: _gb(v) for k, v in memory.items()},
            "temp": _gb(temp)}
    if swept is not None:
        line.update({
            "sweep_peak": _gb(swept["peak_bytes"]),
            "sweep_gap_pct": round(
                100.0 * abs(swept["peak_bytes"] - temp) / temp, 2),
            "residual": _gb(swept["residual_bytes"]),
            "peak_at": swept["peak_at"],
            "backward_at": swept.get("backward_at"),
            "by_scope_at_peak": _top(swept["by_scope_at_peak"]),
            "residual_by_scope": _top(swept["residual_by_scope"]),
            "live_at_peak": [[_gb(b), name, op[-96:], phase]
                             for b, name, op, phase
                             in swept["live_at_peak"][:8]],
            "n_buffers": swept["n_buffers"],
            "n_containers": swept["n_containers"],
            "sweep_ms": round(swept["sweep_ms"], 1)})
    return line


def read(run, spec):
    entry = scope_ms.program_ledger(spec.get("program", "train_step"))
    memory = (entry or {}).get("memory")
    if not memory or not memory.get("temp_bytes"):
        return None
    swept = entry.get("bytes")
    if "bytes_logged" not in run:
        run["bytes_logged"] = True
        common.log("bytes", {
            **account(memory, swept),
            "memory_peak": _gb(run["state"]["memory_peak_bytes"]),
            "ledger_ms": round(entry["ledger_ms"], 1)})
    field, hbm = spec["field"], run["peaks"]["hbm_bytes"]
    if field == "temp_bytes":
        return 100.0 * memory["temp_bytes"] / hbm
    if swept is None:
        return None
    if field == "residual_bytes":
        return 100.0 * swept["residual_bytes"] / hbm
    if field == "sweep_gap":
        return 100.0 * abs(swept["peak_bytes"] - memory["temp_bytes"]) \
            / memory["temp_bytes"]
    raise ValueError(f"program_bytes: unknown field {field!r}")
