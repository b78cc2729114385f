"""What every driver shares: files found by name, the peaks table, the
device check, the compile cache and the result line."""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
T_PROCESS_START = time.time()


def log(*a):
    """Earlier lines go to standard output; the result is the last one."""
    print(*a, flush=True)


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(kind, name):
    """`benchmark/<kind>/<name>.py`, found by name."""
    key = f"bench_{kind}_{name}"
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH_DIR, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    """The cell's own file, its configuration's file, and the metrics that
    BENCHMARK.json lists for it."""
    cell = load_json("workloads", name + ".json")
    config = load_json("configs", cell["config"] + ".json")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"run.py: {name!r} is not a cell of BENCHMARK.json")
    if (entry["config"], entry["chips"]) != (cell["config"], cell["chips"]):
        raise SystemExit(f"run.py: {name!r}: BENCHMARK.json and the cell's file disagree")
    return cell, config, bench


def peaks_for(device_kind):
    table = load_json("peaks.json")
    if device_kind not in table:
        raise SystemExit(
            f"run.py: device kind {device_kind!r} is not in benchmark/peaks.json "
            f"(known: {sorted(table)}); a device without peaks is an error")
    return table[device_kind]


def require_chips(n, rehearse):
    """The cell's chips, or exit non-zero with no result."""
    import jax

    devs = jax.devices()
    if rehearse:
        return devs[:n], {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0,
                          "hbm_bytes": 1.0, "ici_bits_per_s": 1.0}
    if devs[0].platform != "tpu":
        raise SystemExit(f"run.py: needs a TPU, found platform "
                         f"{devs[0].platform!r} (the CPU form is --rehearse)")
    if len(devs) < n:
        raise SystemExit(f"run.py: the cell needs {n} chips, found {len(devs)}")
    return devs[:n], peaks_for(devs[0].device_kind)


def enable_compile_cache():
    """As chip_smoke.py: JAX_COMPILATION_CACHE_DIR wins when set, else a
    fixed directory in the checkout; the autotune cache rides with it."""
    from paddle_tpu import backend_guard

    return backend_guard.enable_compile_cache(os.path.join(ROOT, ".jax_cache"))


class CompileCounter:
    """Counts XLA backend compilations through jax.monitoring (needs no
    telemetry switch of the program)."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


def memory_peak_parts(devices):
    """(held, reserved) of the fullest device, both the backend's counters:
    `peak_bytes_in_use` counts the arrays the process holds and leaves a
    running program's temporaries out; those the TPU runtime reserves at the
    bottom of memory when it loads the program, `peak_bytes_reserved`.  The
    two regions are disjoint (tools/memory_probe.py, PERF.md section 2), so
    the peak is their sum."""
    parts = [(0, 0)]
    for d in devices:
        st = d.memory_stats() or {}
        parts.append((int(st.get("peak_bytes_in_use", 0)),
                      int(st.get("peak_bytes_reserved", 0))))
    return max(parts, key=sum)


def counters_delta(before, after, prefixes):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if k.startswith(prefixes) and v - before.get(k, 0)}


def print_checks(checks):
    """Each number compared beside its limit, last on standard error."""
    for name, (value, limit) in checks.items():
        ok = limit is not None and value <= limit
        print(f"check {name} value={value!r} limit={limit!r} "
              f"{'ok' if ok else 'FAIL'}", file=sys.stderr)
    sys.stderr.flush()


def result_line(correct, attempted, failed, metrics, devices, memory_peak,
                checks, trace=None):
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v[0], "unit": v[1]}
                        for k, v in metrics.items()},
            "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace["breakdown"]
    line["checks"] = {k: {"value": v[0], "limit": v[1]}
                      for k, v in checks.items()}
    return json.dumps(line)
