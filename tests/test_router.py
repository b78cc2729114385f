"""Fleet-serving tests (ISSUE 9): the admission-aware replica router
(least-loaded pick, heartbeat ejection, breaker skip, same-request-id
failover, stream failover semantics, fleet-level sheds), the
`ReplicaFleet` drain-before-SIGTERM ordering, the `/ready` payload
extension, the client's defensive Retry-After parse, and one real
multi-process kill/relaunch e2e.  Unit tests drive the router state
machine with fake replicas and an injectable transport/clock — no
sockets, no sleeps; the seeded 3-replica kill matrix lives under the
`chaos` marker (tools/chaos_check.py --scenario fleet).
"""
import io
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.inference.fleet import (
    EchoPredictor, ReplicaFleet, ToyEngine, toy_token,
)
from paddle_tpu.inference.router import (
    HTTPTransport, ReplicaUnreachable, Router,
)
from paddle_tpu.inference.serving import (
    InferenceClient, InferenceServer, StreamInterrupted,
)
from paddle_tpu.observability import metrics, request_trace as rtrace
from paddle_tpu.resilience.overload import ShedError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _telemetry():
    metrics.reset()
    obs.attach(crash_hook=False)
    yield
    obs.detach()
    metrics.reset()


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --------------------------------------------------------------------------
# fake replica plane: in-memory transport, no sockets
# --------------------------------------------------------------------------

class _FakeStream:
    def __init__(self, status, lines, die_after=None):
        self.status = status
        self.headers = {}
        self._lines = list(lines)
        self._die_after = die_after
        self.closed = False

    def lines(self):
        for i, line in enumerate(self._lines):
            if self._die_after is not None and i >= self._die_after:
                raise ConnectionResetError("replica died mid-stream")
            yield line
        if self._die_after is not None:
            raise ConnectionResetError("replica died mid-stream")

    def read_body(self):
        return b"".join(self._lines)

    def close(self):
        self.closed = True


class _FakeReplica:
    """In-memory stand-in: /ready signals + scripted /predict and
    /generate behavior, with a log of every request's headers."""

    def __init__(self, inflight=0, queued=0, limit=4, engine=None,
                 ready=True, reason="ok"):
        self.inflight = inflight
        self.queued = queued
        self.limit = limit
        self.engine = engine            # dict or None
        self.ready = ready
        self.reason = reason
        self.dead = False               # transport-level failure
        self.fail_next_predicts = 0     # fail N forwards, then serve
        self.shed_next = 0              # answer 429 N times
        self.requests = []              # (path, headers) log
        self.stream_tokens = 5          # tokens a /generate emits
        self.stream_die_after = None    # die after K lines (no final)

    def ready_payload(self):
        body = {"status": "ready" if self.ready else "not_ready",
                "reason": self.reason, "inflight": self.inflight,
                "queued": self.queued, "limit": self.limit,
                "admission_limit": self.limit}
        if self.engine is not None:
            body["engine"] = dict(self.engine)
        return ((200 if self.ready else 503), {},
                json.dumps(body).encode())

    def handle(self, method, path, body, headers):
        if self.dead:
            raise ReplicaUnreachable("fake replica down")
        if path == "/ready":
            return self.ready_payload()
        self.requests.append((path, dict(headers or {})))
        if path == "/predict":
            if self.fail_next_predicts > 0:
                self.fail_next_predicts -= 1
                raise ReplicaUnreachable("fake replica crashed")
            if self.shed_next > 0:
                self.shed_next -= 1
                return (429, {"Retry-After": "1"},
                        json.dumps({"error": "shed",
                                    "reason": "queue_full"}).encode())
            return 200, {"Content-Type": "application/json"}, \
                b'{"echo": true}'
        raise AssertionError(f"unexpected path {path}")

    def stream(self, path, body, headers):
        if self.dead:
            raise ReplicaUnreachable("fake replica down")
        self.requests.append((path, dict(headers or {})))
        if self.shed_next > 0:
            self.shed_next -= 1
            return _FakeStream(429, [json.dumps(
                {"error": "shed", "reason": "queue_full"}).encode()])
        prompt = json.loads(body or b"{}").get("input_ids", [])
        lines = [json.dumps({"token": toy_token(prompt, i)}).encode()
                 + b"\n" for i in range(self.stream_tokens)]
        lines.append(json.dumps({
            "done": True, "finish_reason": "length",
            "output_ids": list(prompt) + [toy_token(prompt, i)
                                          for i in
                                          range(self.stream_tokens)],
        }).encode() + b"\n")
        return _FakeStream(200, lines, die_after=self.stream_die_after)


class _FakeTransport:
    def __init__(self, replicas):
        self.replicas = dict(replicas)  # address -> _FakeReplica

    def request(self, address, method, path, body=None, headers=None,
                timeout=30.0):
        rep = self.replicas.get(address)
        if rep is None:
            raise ReplicaUnreachable(f"no fake replica at {address}")
        return rep.handle(method, path, body, headers)

    def stream(self, address, path, body, headers=None, timeout=30.0):
        rep = self.replicas.get(address)
        if rep is None:
            raise ReplicaUnreachable(f"no fake replica at {address}")
        return rep.stream(path, body, headers)


class _FakeHandler:
    """Captures what forward_generate writes to the client side."""

    class _W:
        def __init__(self):
            self.data = b""

        def write(self, b):
            self.data += b

        def flush(self):
            pass

    def __init__(self):
        self.wfile = self._W()
        self.status = None
        self.headers = []
        self._rt_ctx = None
        self.json_body = None

    def send_response(self, code):
        self.status = code

    def send_header(self, k, v):
        self.headers.append((k, v))

    def end_headers(self):
        pass

    def _json(self, code, obj, headers=()):
        self.status = code
        self.json_body = obj
        self.headers.extend(headers)

    def lines(self):
        return [json.loads(x) for x in
                self.wfile.data.splitlines() if x.strip()]


def _router(replicas, clock=None, **kw):
    """Router over fake replicas, probed once (no threads/sockets used
    by the tests beyond the constructor's unstarted listener)."""
    transport = _FakeTransport(
        {f"fake://{rid}": rep for rid, rep in replicas.items()})
    r = Router(replicas={rid: f"fake://{rid}" for rid in replicas},
               transport=transport, clock=clock or time.monotonic,
               **kw)
    r.probe_once()
    return r


def _close(router):
    router._httpd.server_close()


# --------------------------------------------------------------------------
# routing: least-loaded pick
# --------------------------------------------------------------------------

def test_pick_least_loaded_predict():
    reps = {"a": _FakeReplica(inflight=3, queued=2, limit=4),
            "b": _FakeReplica(inflight=0, queued=0, limit=4),
            "c": _FakeReplica(inflight=2, queued=0, limit=4)}
    r = _router(reps)
    try:
        assert r._pick("predict") == "b"
        assert r._pick("predict", exclude={"b"}) == "c"
        # router-side in-flight counts weigh in between probes
        for _ in range(9):
            r._begin_forward("b", "predict")
        assert r._pick("predict") == "c"
    finally:
        _close(r)


def test_pick_generate_routes_to_emptiest_engine():
    eng = dict(max_slots=4, waiting_sequences=0, active_sequences=0,
               batch_occupancy=0.0)
    reps = {
        "full": _FakeReplica(engine=dict(eng, active_sequences=4,
                                         waiting_sequences=3)),
        "half": _FakeReplica(engine=dict(eng, active_sequences=2)),
        "idle": _FakeReplica(engine=dict(eng)),
    }
    r = _router(reps)
    try:
        assert r._pick("generate") == "idle"
        assert r._pick("generate", exclude={"idle"}) == "half"
    finally:
        _close(r)


def test_capacity_tracks_routable_fleet():
    reps = {"a": _FakeReplica(limit=3,
                              engine=dict(max_slots=4)),
            "b": _FakeReplica(limit=5,
                              engine=dict(max_slots=2))}
    r = _router(reps)
    try:
        assert r.admission.max_inflight == 8
        assert r.gen_admission.max_inflight == 6
        reps["b"].dead = True
        for _ in range(r.heartbeat_miss_k):
            r.probe_once()
        assert r.admission.max_inflight == 3
        assert r.gen_admission.max_inflight == 4
    finally:
        _close(r)


# --------------------------------------------------------------------------
# ejection / re-admission: heartbeats and probes
# --------------------------------------------------------------------------

def test_ejection_on_missed_heartbeats_and_readmission():
    alive = {"a", "b"}
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, heartbeats=lambda: alive, heartbeat_miss_k=3)
    try:
        assert r.replica_summary() == {"a": "up", "b": "up"}
        before = metrics.snapshot()["counters"].get(
            "router.ejections", 0)
        alive.discard("a")  # beats stop; probes still answer
        r.probe_once()
        r.probe_once()
        assert r.replica_summary()["a"] == "up"  # below K
        r.probe_once()
        assert r.replica_summary()["a"] == "ejected"
        assert r._pick("predict") == "b"
        snap = metrics.snapshot()["counters"]
        assert snap.get("router.ejections", 0) == before + 1
        # heartbeats return → re-admitted after a clean probe
        alive.add("a")
        r.probe_once()
        assert r.replica_summary()["a"] == "up"
        assert metrics.snapshot()["counters"].get(
            "router.readmissions", 0) >= 1
        # state gauges track the table
        g = metrics.snapshot()["gauges"]
        assert g.get("router.replicas{state=up}") == 2
        assert g.get("router.replicas{state=ejected}") == 0
    finally:
        _close(r)


def test_replica_that_never_beat_is_probe_governed():
    """A replica whose heartbeat plane never came up (fleet degrades
    it to probe-only liveness) must still be admitted and must stay in
    rotation — absence from the alive set only counts against a
    replica that has beat at least once (review fix)."""
    alive = {"b"}  # "a" never registers a heartbeat
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, heartbeats=lambda: alive, heartbeat_miss_k=2)
    try:
        for _ in range(5):
            r.probe_once()
        assert r.replica_summary() == {"a": "up", "b": "up"}
        # and once it HAS beat, stopping counts again
        alive.add("a")
        r.probe_once()
        alive.discard("a")
        r.probe_once()
        r.probe_once()
        assert r.replica_summary()["a"] == "ejected"
    finally:
        _close(r)


def test_set_capacity_keeps_aimd_band_nonempty():
    """Shrinking capacity below min_limit must drag the live limit
    down with it — not leave the edge admitting min_limit concurrent
    requests against fewer slots (review fix)."""
    from paddle_tpu.resilience.overload import AdmissionController

    ctrl = AdmissionController(max_inflight=8, min_limit=4,
                               latency_target=1.0)
    ctrl.set_capacity(2)
    assert ctrl.limit <= 2 and ctrl.max_inflight == 2
    ctrl.set_capacity(6)  # growth re-opens the band
    assert ctrl.max_inflight == 6


def test_heartbeat_source_failure_does_not_eject():
    def broken():
        raise RuntimeError("store down")

    reps = {"a": _FakeReplica()}
    r = _router(reps, heartbeats=broken, heartbeat_miss_k=2)
    try:
        for _ in range(5):
            r.probe_once()
        assert r.replica_summary()["a"] == "up"  # probe liveness holds
    finally:
        _close(r)


def test_breaker_open_skips_replica_then_half_open_recovers():
    clk = _Clock()
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, clock=clk, breaker_threshold=2, breaker_reset=10.0)
    try:
        ctx = rtrace.new_context()
        reps["a"].fail_next_predicts = 100
        # drive forwards until a's breaker opens (failures land on a
        # only when the pick chooses it; force by loading b)
        reps["b"].inflight = 10
        r.probe_once()
        for _ in range(2):
            code, _h, _d, rid = r.forward_predict(b"x", ctx)
            assert code == 200 and rid == "b"  # failover served it
        with r._lock:
            assert r._replicas["a"].breaker.state == "open"
        # an open breaker is skipped at pick time entirely
        assert r._pick("predict") == "b"
        # reset window passes → half-open admits one trial again
        clk.advance(11.0)
        reps["a"].fail_next_predicts = 0
        assert r._pick("predict") == "a"
        code, _h, _d, rid = r.forward_predict(b"x", ctx)
        assert code == 200 and rid == "a"
        with r._lock:
            assert r._replicas["a"].breaker.state == "closed"
    finally:
        _close(r)


# --------------------------------------------------------------------------
# failover: same request id, shed passthrough, fleet-level sheds
# --------------------------------------------------------------------------

def test_failover_reuses_same_request_id():
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, failover_retries=2)
    try:
        ctx = rtrace.new_context()
        reps["a"].inflight = 0
        reps["b"].inflight = 5
        r.probe_once()
        reps["a"].fail_next_predicts = 1  # first attempt dies on a
        before = metrics.snapshot()["counters"].get(
            "router.failovers", 0)
        code, _h, _d, rid = r.forward_predict(b"payload", ctx)
        assert code == 200 and rid == "b"
        assert metrics.snapshot()["counters"].get(
            "router.failovers", 0) == before + 1
        # BOTH attempts carried the client's X-Request-Id (one hop ctx)
        ids = {hdrs.get("X-Request-Id")
               for rep in reps.values()
               for path, hdrs in rep.requests if path == "/predict"}
        assert ids == {ctx.request_id}
    finally:
        _close(r)


def test_replica_shed_tries_another_then_passes_honest_retry_after():
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, failover_retries=2)
    try:
        ctx = rtrace.new_context()
        reps["a"].shed_next = 5
        reps["b"].shed_next = 5
        code, hdrs, data, rid = r.forward_predict(b"x", ctx)
        assert code == 429 and rid is None
        assert hdrs.get("Retry-After") == "1"  # the replica's estimate
        # one replica shedding while the other serves → served
        reps["a"].shed_next = 5
        reps["b"].shed_next = 0
        code, _h, _d, rid = r.forward_predict(b"x", ctx)
        assert code == 200 and rid == "b"
    finally:
        _close(r)


def test_fleet_level_no_replicas_shed_labels():
    reps = {"a": _FakeReplica()}
    r = _router(reps)
    try:
        ctx = rtrace.new_context()
        reps["a"].dead = True
        for _ in range(r.heartbeat_miss_k):
            r.probe_once()
        before = metrics.snapshot()["counters"].get(
            "resilience.shed_requests{reason=no_replicas}", 0)
        with pytest.raises(ShedError) as ei:
            r.forward_predict(b"x", ctx)
        assert ei.value.reason == "no_replicas"
        assert ei.value.http_status == 503
        assert ei.value.retry_after > 0
        assert metrics.snapshot()["counters"].get(
            "resilience.shed_requests{reason=no_replicas}", 0) \
            == before + 1
        ready, reason = r.readiness()
        assert (ready, reason) == (False, "no_replicas")
    finally:
        _close(r)


def test_draining_readiness_takes_replica_out_of_rotation():
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps)
    try:
        reps["a"].ready = False
        reps["a"].reason = "draining"
        r.probe_once()
        assert r.replica_summary()["a"] == "draining"
        assert r._pick("predict") == "b"
        # replica finishes draining and comes back (relaunch-free)
        reps["a"].ready = True
        reps["a"].reason = "ok"
        r.probe_once()
        assert r.replica_summary()["a"] == "up"
    finally:
        _close(r)


def test_mark_draining_stops_picks_before_any_probe():
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps)
    try:
        reps["b"].inflight = 9  # a would win every pick
        r.probe_once()
        assert r._pick("predict") == "a"
        r.mark_draining("a")    # the fleet's pre-SIGTERM step
        assert r._pick("predict") == "b"
    finally:
        _close(r)


# --------------------------------------------------------------------------
# /generate stream failover semantics
# --------------------------------------------------------------------------

def _gen_body(prompt, n=8):
    return json.dumps({"input_ids": prompt,
                       "max_new_tokens": n}).encode()


def test_stream_zero_token_failover_is_transparent():
    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, failover_retries=2)
    try:
        ctx = rtrace.new_context()
        reps["b"].inflight = 0
        reps["a"].engine = dict(max_slots=4)
        reps["b"].engine = dict(max_slots=4)
        r.probe_once()
        # the picked replica dies before emitting ANY line
        first = r._pick("generate")
        reps[first].stream_die_after = 0
        h = _FakeHandler()
        prompt = [3, 4]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h)
        assert status == "ok"
        lines = h.lines()
        assert [ln["token"] for ln in lines[:-1]] == \
            [toy_token(prompt, i) for i in range(5)]
        assert lines[-1]["done"] is True
        assert metrics.snapshot()["counters"].get(
            "router.failovers", 0) >= 1
    finally:
        _close(r)


def test_stream_mid_failure_interrupts_with_resumable_prefix():
    reps = {"a": _FakeReplica(engine=dict(max_slots=4))}
    r = _router(reps, failover_retries=2)
    try:
        ctx = rtrace.new_context()
        reps["a"].stream_die_after = 3  # 3 tokens out, then death
        h = _FakeHandler()
        prompt = [9, 9, 1]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h)
        assert status == "interrupted"
        lines = h.lines()
        toks = [ln["token"] for ln in lines if "token" in ln]
        assert toks == [toy_token(prompt, i) for i in range(3)]
        final = lines[-1]
        assert final["interrupted"] is True
        assert final["finish_reason"] == "replica_lost"
        # the resumable prefix: prompt + delivered tokens, no replay
        assert final["output_ids"] == prompt + toks
        assert final["tokens_delivered"] == 3
        # NO other replica saw the request after tokens flowed
        assert len(reps["a"].requests) == 1
    finally:
        _close(r)


def test_stream_all_replicas_shedding_returns_clean_status():
    reps = {"a": _FakeReplica(engine=dict(max_slots=2))}
    r = _router(reps)
    try:
        ctx = rtrace.new_context()
        reps["a"].shed_next = 5
        h = _FakeHandler()
        status = r.forward_generate(_gen_body([1]), [1], ctx, h)
        assert status == "shed"
        assert h.status == 429
        assert h.json_body.get("reason") == "queue_full"
    finally:
        _close(r)


def test_client_raises_stream_interrupted_with_prefix():
    """InferenceClient.generate surfaces a router-interrupted stream
    as StreamInterrupted carrying the resumable output_ids — never a
    silent retry (which would replay tokens)."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    prompt = [5, 1]
    toks = [toy_token(prompt, i) for i in range(2)]

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            self.rfile.read(n)
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.end_headers()
            for t in toks:
                self.wfile.write(json.dumps({"token": t}).encode()
                                 + b"\n")
            self.wfile.write(json.dumps({
                "interrupted": True, "error": "replica failed",
                "finish_reason": "replica_lost",
                "output_ids": prompt + toks,
                "tokens_delivered": len(toks)}).encode() + b"\n")

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    host, port = httpd.server_address[:2]
    try:
        cli = InferenceClient(f"http://{host}:{port}", timeout=10,
                              retries=2)
        with pytest.raises(StreamInterrupted) as ei:
            cli.generate(prompt, max_new_tokens=8)
        assert ei.value.tokens == toks
        assert list(ei.value.output_ids) == prompt + toks
        assert ei.value.finish_reason == "replica_lost"
    finally:
        httpd.shutdown()
        httpd.server_close()


# --------------------------------------------------------------------------
# satellites: /ready payload, Retry-After parse, schema zeros
# --------------------------------------------------------------------------

def test_ready_payload_carries_router_signals():
    srv = InferenceServer(predictor=EchoPredictor(),
                          engine=ToyEngine(max_slots=3)).start()
    try:
        body = InferenceClient(srv.address, timeout=10).ready()
        assert body["ready"] is True
        assert body["admission_limit"] == body["limit"]
        eng = body["engine"]
        assert eng["max_slots"] == 3
        assert eng["batch_occupancy"] == 0.0
        assert eng["waiting_sequences"] == 0
        assert eng["active_sequences"] == 0
        # status semantics unchanged: draining still flips 503
        srv.admission.begin_drain()
        body = InferenceClient(srv.address, timeout=10).ready()
        assert body["ready"] is False and body["reason"] == "draining"
    finally:
        srv.shutdown()


def test_client_retry_after_parsed_defensively():
    cli = InferenceClient("http://127.0.0.1:1", max_retry_wait=5.0)
    assert cli._retry_wait({"Retry-After": "2"}) == 2.0
    assert cli._retry_wait({}) == 0.5                  # absent
    assert cli._retry_wait({"Retry-After": "abc"}) == 0.5
    assert cli._retry_wait({"Retry-After": None}) == 0.5
    # negatives clamp to 0 then take the anti-busy-spin floor
    assert cli._retry_wait({"Retry-After": "-3"}) == 0.05
    assert cli._retry_wait({"Retry-After": "0"}) == 0.05
    assert cli._retry_wait({"Retry-After": "1e9"}) == 5.0  # clamp high
    assert cli._retry_wait({"Retry-After": "inf"}) == 0.5
    # NaN must not poison the min/max clamp into sleep(nan)
    assert cli._retry_wait({"Retry-After": "nan"}) == 0.5


def test_router_schema_zeros_present_in_snapshot():
    snap = metrics.snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert "router.failovers" in c
    assert "router.ejections" in c
    assert "router.readmissions" in c
    assert "router.requests{endpoint=predict,status=ok}" in c
    for state in ("up", "draining", "ejected", "down"):
        assert f"router.replicas{{state={state}}}" in g
    assert "resilience.shed_requests{reason=no_replicas}" in c
    assert "resilience.faults{point=router.forward}" in c
    assert "resilience.faults{point=replica.crash}" in c


def test_router_forward_fault_point_triggers_failover():
    from paddle_tpu.resilience import faults

    reps = {"a": _FakeReplica(), "b": _FakeReplica()}
    r = _router(reps, failover_retries=2)
    try:
        ctx = rtrace.new_context()
        with faults.inject("router.forward", at=faults.call_count(
                "router.forward") + 1):
            code, _h, _d, rid = r.forward_predict(b"x", ctx)
        assert code == 200  # the injected fault was failed over
        assert metrics.snapshot()["counters"].get(
            "resilience.faults{point=router.forward}", 0) >= 1
    finally:
        faults.clear()
        _close(r)


# --------------------------------------------------------------------------
# ReplicaFleet: drain ordering with fake processes
# --------------------------------------------------------------------------

class _FakeProc:
    def __init__(self, record, rank):
        self.record = record
        self.rank = rank
        self.rc = None
        self.pid = 90000 + rank

    def poll(self):
        return self.rc

    def wait(self, timeout=None):
        return self.rc

    def send_signal(self, sig):
        self.record.append(("signal", self.rank, int(sig)))
        self.rc = 0

    def kill(self):
        self.record.append(("kill", self.rank))
        self.rc = -9


def test_fleet_drain_marks_router_before_sigterm(tmp_path):
    """The drain protocol's load-bearing ORDER: rotation-out and
    in-flight quiesce happen strictly before the signal (ISSUE 9 (c))."""
    record = []
    reps = {"r0": _FakeReplica(), "r1": _FakeReplica()}
    transport = _FakeTransport(
        {f"fake://{rid}": rep for rid, rep in reps.items()})
    router = Router(transport=transport, probe_interval=0.02)

    def spawner(handle, cmd, env):
        with open(handle.announce + ".tmp", "w") as f:
            json.dump({"address": f"fake://{handle.rid}",
                       "pid": 90000 + handle.rank}, f)
        os.replace(handle.announce + ".tmp", handle.announce)
        return _FakeProc(record, handle.rank)

    fleet = ReplicaFleet(num_replicas=2, router=router,
                         heartbeat=False, spawner=spawner,
                         workdir=str(tmp_path), max_restarts=0,
                         monitor_interval=0.02)
    fleet.start()
    try:
        assert router.replica_summary() == {"r0": "up", "r1": "up"}
        # hold simulated router-side in-flight traffic toward r0, then
        # drain it on a helper thread: the SIGTERM must wait for zero
        router._begin_forward("r0", "predict")
        states_at_signal = {}
        orig = _FakeProc.send_signal

        def instrumented(self, sig):
            states_at_signal["state"] = router.replica_summary()["r0"]
            states_at_signal["inflight"] = router.inflight_to("r0")
            orig(self, sig)

        _FakeProc.send_signal = instrumented
        try:
            th = threading.Thread(
                target=fleet.drain_replica, args=(0,),
                kwargs={"grace": 5.0})
            th.start()
            time.sleep(0.1)
            assert "state" not in states_at_signal  # still quiescing
            assert router.replica_summary()["r0"] == "draining"
            router._end_forward("r0", "predict")    # traffic finishes
            th.join(timeout=5)
            assert not th.is_alive()
        finally:
            _FakeProc.send_signal = orig
        # at signal time: already out of rotation, zero in-flight
        assert states_at_signal == {"state": "draining", "inflight": 0}
        kinds = [e["kind"] for e in fleet.events]
        assert kinds.index("drain_mark") < kinds.index("drain_sigterm")
        assert ("signal", 0, 15) in record
    finally:
        fleet.stop()


# --------------------------------------------------------------------------
# real multi-process e2e: kill -9 under load, failover, relaunch
# --------------------------------------------------------------------------

def test_fleet_e2e_kill_failover_relaunch():
    """Acceptance e2e (tier-1 sized): a 2-replica echo fleet keeps
    serving through a hard replica kill (same-request-id failover) and
    heals back to full capacity via supervisor relaunch."""
    fleet = ReplicaFleet(num_replicas=2, kind="echo",
                         launch_timeout=60, monitor_interval=0.1)
    fleet.start()
    try:
        cli = InferenceClient(fleet.router.address, timeout=20,
                              retries=1)
        x = np.arange(4, dtype=np.float32).reshape(2, 2)
        assert np.array_equal(cli.predict(x=x)["y"], x)
        fleet.kill_replica(0)
        # every post-kill request succeeds (failover, no 5xx window)
        for i in range(6):
            out = cli.predict(x=x + i)
            assert np.array_equal(out["y"], x + i)
        assert fleet.wait_ready(n=2, timeout=45), fleet.describe()
        snap = metrics.snapshot()["counters"]
        assert snap.get("router.ejections", 0) >= 1
        assert snap.get("router.readmissions", 0) >= 1
        views = {v["id"]: v for v in fleet.router.replica_views()}
        assert views["r0"]["generation"] >= 1  # relaunched process
    finally:
        fleet.stop()


def test_perf_gate_fleet_metric_round_trip(tmp_path):
    """fleet_decode_tokens_per_sec is gateable: --update registers the
    baseline row, an equal rerun passes, a drop beyond tolerance exits
    2, and --update rolls the floor forward (ISSUE 9 satellite)."""
    gate = os.path.join(REPO, "tools", "perf_gate.py")
    base = tmp_path / "baseline.jsonl"
    res = tmp_path / "results.json"
    row = {"metric": "fleet_decode_tokens_per_sec", "value": 800.0,
           "unit": "tokens/s", "single_replica_tokens_per_sec": 450.0,
           "fleet_speedup": 1.8, "replicas": 2}
    base.write_text(json.dumps(row) + "\n")

    def run(value):
        res.write_text(json.dumps(dict(row, value=value)) + "\n")
        return subprocess.run(
            [sys.executable, gate, str(res), "--baseline", str(base),
             "--static-budget", ""],
            capture_output=True, text=True)

    assert run(800.0).returncode == 0
    assert run(790.0).returncode == 0        # within tolerance
    p = run(300.0)
    assert p.returncode == 2 and "regression" in p.stderr
    res.write_text(json.dumps(dict(row, value=1200.0)) + "\n")
    p = subprocess.run(
        [sys.executable, gate, str(res), "--baseline", str(base),
         "--static-budget", "", "--update"],
        capture_output=True, text=True)
    assert p.returncode == 0 and "updated" in p.stdout
    assert run(1150.0).returncode == 0
    assert run(800.0).returncode == 2


@pytest.mark.chaos
def test_fleet_chaos_scenario():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import chaos_check
    finally:
        sys.path.pop(0)
    report = chaos_check.run_fleet_chaos(seed=0)
    assert report["recovered"], report


# --------------------------------------------------------------------------
# deterministic mid-stream resume (ISSUE 20)
# --------------------------------------------------------------------------

def _pos_token(prompt, i):
    """Position-only token fn: the greedy determinism contract in
    miniature — any replica handed the delivered prefix re-derives the
    SAME continuation (what the real engine guarantees via greedy
    argmax), so a resume leg's first token matches the verify token."""
    return (37 * (len(prompt) + i)) % 997


class _ContractReplica(_FakeReplica):
    """Fake replica honoring the greedy determinism contract AND the
    resume request shape: obeys max_new_tokens, logs parsed bodies."""

    def __init__(self, **kw):
        super().__init__(**kw)
        self.bodies = []

    def stream(self, path, body, headers):
        if self.dead:
            raise ReplicaUnreachable("fake replica down")
        self.requests.append((path, dict(headers or {})))
        req = json.loads(body or b"{}")
        self.bodies.append(req)
        prompt = req.get("input_ids", [])
        n = int(req.get("max_new_tokens", self.stream_tokens))
        toks = [_pos_token(prompt, i) for i in range(n)]
        lines = [json.dumps({"token": t}).encode() + b"\n"
                 for t in toks]
        lines.append(json.dumps({
            "done": True, "finish_reason": "length",
            "output_ids": list(prompt) + toks}).encode() + b"\n")
        return _FakeStream(200, lines,
                           die_after=self.stream_die_after)


def _eng(active):
    return dict(max_slots=4, waiting_sequences=0,
                active_sequences=active,
                batch_occupancy=active / 4.0)


def test_stream_mid_failure_resumes_on_survivor():
    """The tentpole: a replica dying with 3 tokens delivered becomes
    INVISIBLE — the router resubmits prompt+delivered[:-1] to the
    survivor under the same request id, swallows the re-derived verify
    token, and the client sees one seamless 8-token stream ending in a
    done record (annotated `resumed: 1`), never an interrupted one."""
    reps = {"a": _ContractReplica(engine=_eng(0)),
            "b": _ContractReplica(engine=_eng(1))}
    r = _router(reps, failover_retries=0, stream_resume_max=2)
    try:
        ctx = rtrace.new_context()
        assert r._pick("generate") == "a"   # emptiest engine first
        reps["a"].stream_die_after = 3      # 3 tokens out, then death
        h = _FakeHandler()
        prompt = [3, 4]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h,
                                    max_new_tokens=8)
        assert status == "ok"
        lines = h.lines()
        toks = [ln["token"] for ln in lines if "token" in ln]
        # the full greedy stream, exactly once: no replay, no gap
        assert toks == [_pos_token(prompt, i) for i in range(8)]
        final = lines[-1]
        assert final["done"] is True
        assert final["resumed"] == 1
        assert final["output_ids"] == prompt + toks
        assert not any(ln.get("interrupted") for ln in lines)
        # the resume leg's shape: delivered[:-1] resubmitted, budget
        # reduced (+1 verify), the verify token billed nowhere
        (leg,) = reps["b"].bodies
        assert leg["input_ids"] == prompt + toks[:2]
        assert leg["max_new_tokens"] == 8 - 3 + 1
        assert leg["prebilled_tokens"] == 1
        assert leg["resume"] == 1
        # same request id end to end
        assert reps["b"].requests[0][1]["X-Request-Id"] == \
            ctx.request_id
        snap = metrics.snapshot()
        assert snap["counters"][
            "router.stream_resumes{outcome=ok}"] == 1
        assert snap["counters"].get("router.failovers", 0) == 0
        assert snap["histograms"]["router.resume_gap_ms"]["count"] >= 1
    finally:
        _close(r)


def test_stream_resume_divergence_falls_back_loudly():
    """A resume leg whose first token does NOT re-derive delivered[-1]
    must fall back to the clean interrupted record — the wrong token is
    never streamed (replica b is toy_token-based: content-dependent, so
    it diverges from the position-only contract replica)."""
    reps = {"a": _ContractReplica(engine=_eng(0)),
            "b": _FakeReplica(engine=_eng(1))}
    r = _router(reps, failover_retries=0, stream_resume_max=2)
    try:
        ctx = rtrace.new_context()
        assert r._pick("generate") == "a"
        reps["a"].stream_die_after = 3
        h = _FakeHandler()
        prompt = [3, 4]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h,
                                    max_new_tokens=8)
        assert status == "interrupted"
        lines = h.lines()
        toks = [ln["token"] for ln in lines if "token" in ln]
        # only the verified prefix was ever streamed
        assert toks == [_pos_token(prompt, i) for i in range(3)]
        final = lines[-1]
        assert final["interrupted"] is True
        assert final["output_ids"] == prompt + toks
        assert final["tokens_delivered"] == 3
        snap = metrics.snapshot()["counters"]
        assert snap["router.stream_resumes{outcome=diverged}"] == 1
        assert snap["router.stream_resumes{outcome=ok}"] == 0
    finally:
        _close(r)


def test_resume_verify_fault_injection_forces_fallback():
    """The faults-plane divergence drill: router.resume_verify injected
    on an otherwise-healthy resume forces the loud fallback — the chaos
    harness can rehearse divergence without a broken model."""
    from paddle_tpu.resilience import faults

    reps = {"a": _ContractReplica(engine=_eng(0)),
            "b": _ContractReplica(engine=_eng(1))}
    r = _router(reps, failover_retries=0, stream_resume_max=2)
    try:
        ctx = rtrace.new_context()
        assert r._pick("generate") == "a"
        reps["a"].stream_die_after = 3
        h = _FakeHandler()
        prompt = [3, 4]
        with faults.inject("router.resume_verify"):
            status = r.forward_generate(_gen_body(prompt), prompt,
                                        ctx, h, max_new_tokens=8)
        assert status == "interrupted"
        final = h.lines()[-1]
        assert final["interrupted"] is True
        assert final["tokens_delivered"] == 3
        snap = metrics.snapshot()["counters"]
        assert snap["router.stream_resumes{outcome=diverged}"] == 1
    finally:
        faults.clear()
        _close(r)


def test_stream_resume_budget_exhausted_interrupts():
    """Bounded resumption: with stream_resume_max=1, a SECOND
    mid-stream death lands on the interrupted record carrying every
    delivered token (both legs), and no third replica is tried."""
    reps = {"a": _ContractReplica(engine=_eng(0)),
            "b": _ContractReplica(engine=_eng(1)),
            "c": _ContractReplica(engine=_eng(2))}
    r = _router(reps, failover_retries=0, stream_resume_max=1)
    try:
        ctx = rtrace.new_context()
        assert r._pick("generate") == "a"
        reps["a"].stream_die_after = 3
        reps["b"].stream_die_after = 3  # verify + 2 more, then death
        h = _FakeHandler()
        prompt = [3, 4]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h,
                                    max_new_tokens=8)
        assert status == "interrupted"
        lines = h.lines()
        toks = [ln["token"] for ln in lines if "token" in ln]
        # 3 from leg 1, verify swallowed, 2 more from leg 2 — in order
        assert toks == [_pos_token(prompt, i) for i in range(5)]
        final = lines[-1]
        assert final["interrupted"] is True
        assert final["output_ids"] == prompt + toks
        assert reps["c"].requests == []   # budget spent: no third leg
        snap = metrics.snapshot()["counters"]
        assert snap["router.stream_resumes{outcome=ok}"] == 1
        assert snap["router.stream_resumes{outcome=exhausted}"] == 1
    finally:
        _close(r)


def test_stream_resume_class_gated():
    """An operator may declare batch streams not worth the resume
    re-prefill: the class gate falls straight back to the interrupted
    record without touching another replica."""
    reps = {"a": _ContractReplica(engine=_eng(0)),
            "b": _ContractReplica(engine=_eng(1))}
    r = _router(reps, failover_retries=0, stream_resume_max=2,
                stream_resume_classes=("paid", "free"))
    try:
        ctx = rtrace.new_context(priority_class="batch")
        assert r._pick("generate") == "a"
        reps["a"].stream_die_after = 3
        h = _FakeHandler()
        prompt = [3, 4]
        status = r.forward_generate(_gen_body(prompt), prompt, ctx, h,
                                    max_new_tokens=8)
        assert status == "interrupted"
        assert reps["b"].requests == []
        snap = metrics.snapshot()["counters"]
        assert snap["router.stream_resumes{outcome=exhausted}"] == 1
    finally:
        _close(r)


def test_resume_refusal_reasons():
    clock = _Clock()
    reps = {"a": _FakeReplica()}
    r = _router(reps, clock=clock, stream_resume_max=1,
                stream_resume_classes=("paid",))
    try:
        paid = rtrace.new_context(priority_class="paid")
        assert r._resume_refusal(paid, 0, None) is None
        assert r._resume_refusal(paid, 1, None) == "budget"
        # the default class (free) is outside the configured set
        assert r._resume_refusal(rtrace.new_context(), 0, None) \
            == "class"
        clock.t = 100.0
        assert r._resume_refusal(paid, 0, 99.0) == "deadline"
        assert r._resume_refusal(paid, 0, 101.0) is None
    finally:
        _close(r)


@pytest.mark.parametrize("asked", [False, True])
def test_resume_body_repeats_the_logprobs_field(asked):
    """A resume leg asks for log-probabilities exactly when the first
    leg did (ISSUE 33): the client parses `logprob` off every line."""
    raw, verify = Router._resume_body([1, 2, 3], [7, 8], 10, None, 1,
                                      logprobs=asked)
    body = json.loads(raw)
    assert verify == 8 and body["input_ids"] == [1, 2, 3, 7]
    assert body.get("logprobs", False) is asked


def test_resume_env_knobs(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_STREAM_RESUME_MAX", "5")
    monkeypatch.setenv("PADDLE_TPU_STREAM_RESUME_CLASSES",
                       "paid, BATCH, nonsense")
    reps = {"a": _FakeReplica()}
    r = _router(reps)
    try:
        assert r.stream_resume_max == 5
        assert r.stream_resume_classes == frozenset({"paid", "batch"})
    finally:
        _close(r)


def test_resume_schema_zeros_present_in_snapshot():
    snap = metrics.snapshot()
    c = snap["counters"]
    for outcome in ("ok", "diverged", "exhausted"):
        assert f"router.stream_resumes{{outcome={outcome}}}" in c
    for cache in ("hit", "partial", "miss"):
        assert f"serving.resume_prefill{{cache={cache}}}" in c
    assert "resilience.shed_requests{reason=deadline_exceeded}" in c
    assert "resilience.faults{point=router.stream_read}" in c
    assert "resilience.faults{point=router.resume_verify}" in c
    assert "router.resume_gap_ms" in snap["histograms"]


def test_client_resume_continues_stream_same_request_id():
    """InferenceClient.generate(resume=True) turns StreamInterrupted
    into a client-side resume: the carried output_ids are resubmitted
    with the budget reduced, under the SAME X-Request-Id, and the
    caller sees one seamless result with `resumed` counted."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    prompt = [5, 1]
    leg1 = [toy_token(prompt, i) for i in range(2)]
    seen = []

    class H(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            seen.append((req, self.headers.get("X-Request-Id")))
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.end_headers()
            if len(seen) == 1:
                for t in leg1:
                    self.wfile.write(
                        json.dumps({"token": t}).encode() + b"\n")
                self.wfile.write(json.dumps({
                    "interrupted": True, "error": "replica failed",
                    "finish_reason": "replica_lost",
                    "output_ids": prompt + leg1,
                    "tokens_delivered": len(leg1)}).encode() + b"\n")
                return
            ids = list(req["input_ids"])
            leg2 = [toy_token(ids, i)
                    for i in range(req["max_new_tokens"])]
            for t in leg2:
                self.wfile.write(
                    json.dumps({"token": t}).encode() + b"\n")
            self.wfile.write(json.dumps({
                "done": True, "finish_reason": "length",
                "output_ids": ids + leg2}).encode() + b"\n")

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    host, port = httpd.server_address[:2]
    try:
        cli = InferenceClient(f"http://{host}:{port}", timeout=10,
                              retries=0)
        out = cli.generate(prompt, max_new_tokens=6, resume=True)
        assert len(seen) == 2
        req2, rid2 = seen[1]
        assert seen[0][1] == rid2                    # same request id
        assert req2["input_ids"] == prompt + leg1    # carried prefix
        assert req2["max_new_tokens"] == 6 - len(leg1)
        assert out["resumed"] == 1
        assert out["finish_reason"] == "length"
        assert out["tokens"][:2] == leg1
        assert len(out["tokens"]) == 6
        assert list(out["output_ids"]) == prompt + out["tokens"]
    finally:
        httpd.shutdown()
        httpd.server_close()


@pytest.mark.chaos
def test_resume_chaos_scenario():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import chaos_check
    finally:
        sys.path.pop(0)
    report = chaos_check.run_resume_chaos(seed=0)
    assert report["recovered"], report


def test_perf_gate_resume_gap_metric_round_trip(tmp_path):
    """serving_stream_resume_gap_ms is gateable lower-better: --update
    registers the baseline, an equal rerun passes, a blow-up beyond
    tolerance exits 2, and --update rolls the ceiling (ISSUE 20)."""
    gate = os.path.join(REPO, "tools", "perf_gate.py")
    base = tmp_path / "baseline.jsonl"
    res = tmp_path / "results.json"
    row = {"metric": "serving_stream_resume_gap_ms", "value": 40.0,
           "unit": "ms", "resumes": 4}
    base.write_text(json.dumps(row) + "\n")

    def run(value):
        res.write_text(json.dumps(dict(row, value=value)) + "\n")
        return subprocess.run(
            [sys.executable, gate, str(res), "--baseline", str(base),
             "--static-budget", ""],
            capture_output=True, text=True)

    assert run(40.0).returncode == 0
    assert run(41.0).returncode == 0         # within tolerance
    p = run(400.0)
    assert p.returncode == 2 and "regression" in p.stderr
    res.write_text(json.dumps(dict(row, value=20.0)) + "\n")
    p = subprocess.run(
        [sys.executable, gate, str(res), "--baseline", str(base),
         "--static-budget", "", "--update"],
        capture_output=True, text=True)
    assert p.returncode == 0 and "updated" in p.stdout
    assert run(21.0).returncode == 0
    assert run(40.0).returncode == 2
