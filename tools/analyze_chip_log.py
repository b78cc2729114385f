"""Render a JSONL stream of run records into a markdown digest: one section per phase, latest entry per unique
key, errors listed last.  `step_stats` entries (the observability
StepTimer stream, docs/OBSERVABILITY.md) get schema validation plus a
per-run summary (compile ledger vs steady walls, tokens/s, MFU) instead
of the latest-entry-wins table; `trace_event` entries (span-tracer
`dump_jsonl` streams) get schema validation plus an event/span digest;
`telemetry_dump` entries (the per-process exporter streams,
observability/export.py) get schema validation plus a per-process dump
digest.  Exit is non-zero on any schema error in any stream (the CI
hook).
Run: python tools/analyze_chip_log.py log.jsonl
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from collections import OrderedDict

def _load_obs_module(name):
    """File-load an observability module (stdlib-only by contract) so
    this tool works without importing jax-heavy paddle_tpu."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "paddle_tpu", "observability",
                        name + ".py")
    spec = importlib.util.spec_from_file_location("_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_step_stats = _load_obs_module("step_stats")
_trace = _load_obs_module("trace")
_export = _load_obs_module("export")


def load(path):
    entries = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    entries.append(json.loads(line))
                except ValueError:
                    continue
    except OSError:
        pass
    return entries


def digest(entries, schema_errors=None, trace_errors=None,
           telemetry_errors=None):
    phases: "OrderedDict[str, OrderedDict]" = OrderedDict()
    errors = []
    step_entries = []
    trace_entries = []
    telemetry_entries = []
    for e in entries:
        ph = e.get("phase", "?")
        if "error" in e:
            errors.append((ph, e.get("t", ""), e["error"]))
            continue
        if ph == _step_stats.STEP_PHASE:
            step_entries.append(e)
            continue
        if ph == _trace.TRACE_PHASE:
            trace_entries.append(e)
            continue
        if ph == _export.TELEMETRY_PHASE:
            telemetry_entries.append(e)
            continue
        if e.get("done"):
            continue
        # latest entry wins per (phase, discriminator): sweeps key on
        # blocks/shape/variant/rung/model, single-result phases on phase
        disc = tuple(str(e.get(k)) for k in
                     ("blocks", "shape", "variant", "rung", "model",
                      "metric", "batch") if k in e)
        phases.setdefault(ph, OrderedDict())[disc] = e
    lines = []
    for ph, rows in phases.items():
        lines.append(f"\n## {ph}  ({len(rows)} rows)\n")
        for disc, e in rows.items():
            body = {k: v for k, v in e.items()
                    if k not in ("phase", "t")}
            lines.append(f"- `{e.get('t', '')}` "
                         + json.dumps(body, default=str))
    if step_entries:
        lines.append(f"\n## step_stats  ({len(step_entries)} records)\n")
        if schema_errors is None:
            schema_errors = _step_stats.validate_stream(step_entries)
        if schema_errors:
            lines.append(f"**schema errors ({len(schema_errors)}):**")
            for err in schema_errors[:20]:
                lines.append(f"- {err}")
        for run_id, s in _step_stats.summarize_stream(step_entries).items():
            lines.append(f"- **{run_id}**: " + json.dumps(s, default=str))
    if trace_entries:
        lines.append(f"\n## trace_events  ({len(trace_entries)} events)\n")
        if trace_errors is None:
            trace_errors = _trace.validate_trace_stream(trace_entries)
        if trace_errors:
            lines.append(f"**schema errors ({len(trace_errors)}):**")
            for err in trace_errors[:20]:
                lines.append(f"- {err}")
        s = _trace.summarize_trace_stream(trace_entries)
        lines.append("- " + json.dumps(s, default=str))
    if telemetry_entries:
        lines.append(f"\n## telemetry_dumps  ({len(telemetry_entries)} "
                     f"dumps)\n")
        if telemetry_errors is None:
            telemetry_errors = _export.validate_telemetry_stream(
                telemetry_entries)
        if telemetry_errors:
            lines.append(f"**schema errors ({len(telemetry_errors)}):**")
            for err in telemetry_errors[:20]:
                lines.append(f"- {err}")
        for ident, s in sorted(_export.summarize_telemetry_stream(
                telemetry_entries).items()):
            lines.append(f"- **{ident}**: " + json.dumps(s, default=str))
    if errors:
        lines.append(f"\n## errors ({len(errors)})\n")
        for ph, t, err in errors[-30:]:
            lines.append(f"- `{t}` **{ph}**: {err[:200]}")
    return "\n".join(lines) or "(log empty)"


def main(argv):
    if len(argv) < 2:
        print("usage: python tools/analyze_chip_log.py LOG.jsonl",
              file=sys.stderr)
        return 2
    entries = load(argv[1])
    # validate once; digest renders the same result and the exit code
    # makes a corrupt step-stats or trace stream fail loudly in CI
    errors = _step_stats.validate_stream(entries)
    trace_errors = _trace.validate_trace_stream(entries)
    telemetry_errors = _export.validate_telemetry_stream(entries)
    print(digest(entries, schema_errors=errors, trace_errors=trace_errors,
                 telemetry_errors=telemetry_errors))
    return 1 if (errors or trace_errors or telemetry_errors) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
