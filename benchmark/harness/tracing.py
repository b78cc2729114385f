"""Device-trace capture and its reduction to intervals.

`Tracer` wraps `jax.profiler` for a short sub-window of the traced run and
keeps the harness's own host spans (also written into the profiler's trace as
TraceAnnotations, so host and device share one clock).  `reduce_trace` turns
an `.xplane.pb` into plain lists; the arithmetic on them (`union_seconds`,
`idle_gaps`, `sum_by_name`, `family_events`) is pure Python, so that the
tests can check it on hand-made cases and on a small recorded trace.
"""
from __future__ import annotations

import contextlib
import glob
import os
import shutil
import tempfile
import time

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ANCHOR = "bench.anchor"


class Tracer:
    def __init__(self):
        self.dir = None
        self.spans = []            # (name, t0_perf_ns, t1_perf_ns)
        self.anchor_perf_ns = None
        self.t_start = self.t_stop = None
        self.reduced = None

    def start(self):
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # no per-call Python events
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.anchor_wall = time.time()
        self.anchor_perf_ns = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(ANCHOR):
            time.sleep(0.0005)
        self.t_start = time.perf_counter_ns()

    @contextlib.contextmanager
    def span(self, name):
        import jax

        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t0, time.perf_counter_ns()))

    def stop(self):
        import jax

        self.t_stop = time.perf_counter_ns()
        jax.profiler.stop_trace()

    def reduce(self, program_spans=(), keep=None, cpu_stand_in=False):
        """Read the trace once; delete it from disk."""
        files = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise RuntimeError("the profiler wrote no .xplane.pb")
        if keep:
            shutil.copy(files[-1], keep)
        red = reduce_trace(files[-1], cpu_stand_in)
        shutil.rmtree(self.dir, ignore_errors=True)
        if red["anchor_ns"] is None:
            raise RuntimeError("anchor annotation not found in the trace")
        off = red["anchor_ns"] - self.anchor_perf_ns   # perf clock -> trace clock
        red["window"] = (self.t_start + off, self.t_stop + off)
        red["perf_offset_ns"] = off
        red["anchor_wall"] = self.anchor_wall
        red["anchor_perf_ns"] = self.anchor_perf_ns
        red["host_spans"] = [(n, a + off, b + off) for n, a, b in self.spans]
        red["host_spans"] += [(n, a + off, b + off) for n, a, b in program_spans]
        self.reduced = red
        return red


def reduce_trace(path, cpu_stand_in=False):
    """`.xplane.pb` -> {"devices": {id: {"ops": [(name, start_ns, dur_ns)],
    "modules": [...]}}, "anchor_ns": start of the anchor annotation}.
    `cpu_stand_in` (rehearsals only) reads XLA:CPU's worker threads as
    device 0, so that the rest of the path can be driven without a chip."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices, anchor = {}, None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dev["ops"] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                  for e in line.events]
                elif line.name == MODULES_LINE:
                    dev["modules"] = [(e.name, int(e.start_ns), int(e.duration_ns))
                                      for e in line.events]
            devices[int(plane.name[len(DEVICE_PLANE):].split()[0])] = dev
        else:
            for line in plane.lines:
                if cpu_stand_in and line.name.startswith("tf_XLA"):
                    dev = devices.setdefault(0, {"ops": [], "modules": []})
                    dev["ops"] += [(e.name, int(e.start_ns), int(e.duration_ns))
                                   for e in line.events
                                   if e.duration_ns > 0
                                   and not e.name.startswith("Threadpool")]
                if anchor is not None:
                    continue
                for e in line.events:
                    if e.name == ANCHOR:
                        anchor = int(e.start_ns)
                        break
    return {"devices": devices, "anchor_ns": anchor}


# --- arithmetic on intervals (pure Python) --------------------------------

def clip(events, window):
    lo, hi = window
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def merged(intervals):
    """Sorted, disjoint [start, end) intervals covering the input."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_seconds(events):
    return sum(e - s for s, e in merged((s, s + d) for _, s, d in events)) / 1e9


def sum_by_name(events):
    acc = {}
    for name, _, d in events:
        acc[name] = acc.get(name, 0) + d
    return acc


def short_name(hlo_text):
    """`%fusion.12 = f32[..] fusion(...)` -> `fusion.12`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")[:80]


def family_name(hlo_text):
    """`%closed_call.124 = ...` -> `closed_call`: the call sites of one
    kernel (one per layer) are summed under one name."""
    name = short_name(hlo_text)
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def is_container(hlo_text):
    """Ops whose event spans their body's ops (the body is listed too)."""
    return any(k in hlo_text for k in (" while(", " conditional(", " call("))


def family_events(events, pattern):
    import re

    rx = re.compile(pattern)
    return [ev for ev in events if rx.search(ev[0])]


def idle_gaps(events, window, host_spans):
    """Idle intervals of one device inside the window, each attributed to the
    shortest host span that covers its midpoint (`unattributed` if none).
    Returns {name: seconds}."""
    lo, hi = window
    busy = merged((s, s + d) for _, s, d in events)
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    acc = {}
    for a, b in gaps:
        mid = (a + b) / 2
        best = None
        for name, s, e in host_spans:
            if s <= mid <= e and (best is None or e - s < best[1]):
                best = (name, e - s)
        name = best[0] if best else "unattributed"
        acc[name] = acc.get(name, 0) + (b - a)
    return {k: v / 1e9 for k, v in acc.items()}


def summarise(red):
    """busy_s (mean over the chips used), window_s and the breakdown."""
    window = red["window"]
    per_dev = {d: clip(v["ops"], window) for d, v in red["devices"].items()}
    per_dev = {d: ev for d, ev in per_dev.items() if ev}
    if not per_dev:
        raise RuntimeError("no operation ran on the device in the traced window")
    busy = {d: union_seconds(ev) for d, ev in per_dev.items()}
    fullest = max(busy, key=busy.get)
    leaf = [(family_name(n), s, d) for n, s, d in per_dev[fullest]
            if not is_container(n)]
    ops = sorted(sum_by_name(leaf).items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle_gaps(per_dev[fullest], window, red["host_spans"]).items(),
                  key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy.values()) / len(busy),
            "busy_s_fullest": busy[fullest], "fullest": fullest,
            "window_s": (window[1] - window[0]) / 1e9,
            "events": per_dev,
            "breakdown": {"device_ops": [[n, ns / 1e9] for n, ns in ops],
                          "idle_gaps": [[n, s] for n, s in gaps]}}
