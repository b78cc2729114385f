"""Flight recorder: bounded in-memory ring of recent structured events.

The black box for incidents like "the process died mid-compile":
kernel dispatch decisions, gate rejects, retraces, and
collective anomalies append tiny dicts to a ring; on crash (installed
excepthook) or on demand (`dump()`) the ring lands on disk as JSONL, so
the *last thing the process decided* survives the process.

Always-on by default: events fire at dispatch/trace frequency (not per
device step), so the cost is a dict construction and a deque append.
Set ``recorder.enabled = False`` (or env ``PADDLE_TPU_FLIGHT=0``) to
silence it entirely.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

from . import metrics as _metrics
from . import trace as _trace

__all__ = ["FlightRecorder", "get_recorder", "record", "events", "dump",
           "clear", "install_crash_hook"]

DEFAULT_CAPACITY = 512


class FlightRecorder:
    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        import collections

        self._events = collections.deque(maxlen=int(capacity))
        self._lock = threading.Lock()
        self._seq = 0
        self.enabled = os.environ.get("PADDLE_TPU_FLIGHT", "1") not in (
            "0", "false", "False")

    def record(self, kind: str, **data) -> None:
        """Append one event. `kind` is a dotted event name
        (``flash.gate_reject``, ``jit.retrace``, ...); payload values
        should be JSON-friendly (shapes as lists, not arrays)."""
        if not self.enabled:
            return
        evt = {"t": time.time(), "kind": str(kind)}
        scope = _metrics.current_scope()
        if scope is not None:
            evt["scope"] = scope
        evt.update(data)
        with self._lock:
            self._seq += 1
            evt["seq"] = self._seq
            self._events.append(evt)
        # correlate onto the span timeline: every ring event doubles as
        # an instant between the spans that caused it (only when the
        # tracer is buffering — instant() is one branch otherwise).  A
        # payload key colliding with instant()'s own parameters must not
        # sink the recording path.
        try:
            _trace.instant(kind, cat="flight", **data)
        except TypeError:
            _trace.instant(kind, cat="flight")

    def events(self) -> list:
        with self._lock:
            return list(self._events)

    def clear(self) -> None:
        with self._lock:
            self._events.clear()

    def dump(self, path: str | None = None, reason: str = "on_demand") -> str:
        """Write the ring to `path` as JSONL (one event per line, headed
        by a dump marker carrying the reason).  Default path:
        ``$PADDLE_TPU_FLIGHT_PATH`` or ``flight_<pid>.jsonl`` in cwd."""
        path = path or os.environ.get(
            "PADDLE_TPU_FLIGHT_PATH", f"flight_{os.getpid()}.jsonl")
        evts = self.events()
        header = {"t": time.time(), "kind": "flight.dump", "reason": reason,
                  "n_events": len(evts), "pid": os.getpid()}
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "a") as f:
            f.write(json.dumps(header, default=str) + "\n")
            for e in evts:
                f.write(json.dumps(e, default=str) + "\n")
        return path


_default = FlightRecorder()
_hook_installed = False


def get_recorder() -> FlightRecorder:
    return _default


def record(kind, **data):
    _default.record(kind, **data)


def events():
    return _default.events()


def dump(path=None, reason="on_demand"):
    return _default.dump(path, reason=reason)


def clear():
    _default.clear()


def install_crash_hook() -> None:
    """Chain onto sys.excepthook: an uncaught exception dumps the ring
    before the normal traceback prints.  Idempotent; the dump itself is
    guarded so a broken disk can never mask the original exception."""
    global _hook_installed
    if _hook_installed:
        return
    prev = sys.excepthook

    def hook(exc_type, exc, tb):
        try:
            if _default.events():
                p = _default.dump(reason=f"crash:{exc_type.__name__}")
                print(f"[observability] flight recorder dumped to {p}",
                      file=sys.stderr)
        except Exception as e:
            # a broken disk must never mask the original exception —
            # but the operator should know the black box is gone
            print(f"[observability] flight dump failed: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
        prev(exc_type, exc, tb)

    sys.excepthook = hook
    _hook_installed = True
