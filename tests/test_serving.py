"""Zero-drop serving plane (ISSUE 14, docs/SERVING.md).

Fast battery: dynamic batcher (batch formation, explicit sheds,
deadlines, drain), hardened HTTP server (bounded handler pool +
per-request timeouts), /readyz-vs-/healthz split, in-process replica
(roundtrip, idempotency, chaos seam, hot weight swap, drain), router
(retry to a survivor, hedging a slow replica, admission shed,
exactly-once accounting), the SLO window -> slo_breach ->
autopilot-scale_out chain, and `metrics top`/`history --serving`
rendering.

Slow (serving/chaos CI tiers; tier-1 budget rule — all multiprocess
tests are slow-marked): the chaos acceptance pair — (a) SIGKILL one
replica of a 2-replica fleet under sustained closed-loop load: every
accepted request answered exactly once, fleet heals; (b) a chaos
preemption notice drains a replica (DRAINED exit, no failure
evidence) while a fresh durable commit hot-swaps — zero failed
requests, new version served.
"""

import json
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _chaos_clean():
    from horovod_tpu import chaos
    chaos.uninstall()
    yield
    chaos.uninstall()


# -- batcher ------------------------------------------------------------------
def test_batcher_forms_full_batch():
    from horovod_tpu.serving.batcher import DynamicBatcher
    b = DynamicBatcher(max_batch_size=4, max_wait_s=5.0, max_queue=16)
    reqs = [b.submit(f"r{i}", i) for i in range(4)]
    batch = b.next_batch(timeout_s=1.0)
    assert [r.id for r in batch] == ["r0", "r1", "r2", "r3"]
    for r in batch:
        r.set_result(r.payload * 10)
    b.batch_done()
    assert reqs[2].wait(timeout=1.0) == 20


def test_batcher_max_wait_bounds_latency():
    """A lone request must not wait for a full batch: the window is
    max_wait_s from the OLDEST member's enqueue."""
    from horovod_tpu.serving.batcher import DynamicBatcher
    b = DynamicBatcher(max_batch_size=64, max_wait_s=0.05, max_queue=16)
    t0 = time.monotonic()
    b.submit("solo", 1)
    batch = b.next_batch(timeout_s=1.0)
    took = time.monotonic() - t0
    assert len(batch) == 1 and took < 0.5


def test_batcher_sheds_explicitly_on_full_queue():
    from horovod_tpu.serving.batcher import DynamicBatcher, SheddedError
    b = DynamicBatcher(max_batch_size=4, max_queue=2)
    b.submit("a", 1)
    b.submit("b", 2)
    with pytest.raises(SheddedError):
        b.submit("c", 3)


def test_batcher_expired_deadline_fails_at_formation():
    from horovod_tpu.serving.batcher import DeadlineError, DynamicBatcher
    b = DynamicBatcher(max_batch_size=4, max_wait_s=0.01, max_queue=16)
    doomed = b.submit("late", 1, deadline_s=0.01)
    live = b.submit("fine", 2, deadline_s=30.0)
    time.sleep(0.05)
    batch = b.next_batch(timeout_s=1.0)
    assert [r.id for r in batch] == ["fine"]
    with pytest.raises(DeadlineError):
        doomed.wait(timeout=0.1)
    live.set_result(None)
    b.batch_done()


def test_batcher_drain_refuses_new_and_flushes_admitted():
    from horovod_tpu.serving.batcher import DrainingError, DynamicBatcher
    b = DynamicBatcher(max_batch_size=4, max_wait_s=0.01, max_queue=16)
    r1 = b.submit("pre", 1)
    b.drain()
    with pytest.raises(DrainingError):
        b.submit("post", 2)
    assert not b.drained()  # "pre" is still owed an answer
    batch = b.next_batch(timeout_s=1.0)
    assert [r.id for r in batch] == ["pre"]
    r1.set_result(None)
    b.batch_done()
    assert b.drained()
    assert b.wait_drained(timeout_s=1.0)


# -- hardened HTTP server -----------------------------------------------------
def test_http_bounded_pool_rejects_busy_and_times_out_wedged():
    """Satellite: HVD_TPU_HTTP_MAX_HANDLERS handler slots; wedged
    clients get per-request timeouts, the overflow connection gets an
    immediate 503 — and after the timeout frees the slots, the server
    answers again (one slow client can no longer pin a thread
    forever)."""
    from horovod_tpu.runner.http_kv import ThreadedHTTPServer, _KVHandler
    srv = ThreadedHTTPServer(("127.0.0.1", 0), _KVHandler,
                             max_handlers=2, handler_timeout_s=1.0)
    srv.kv, srv.kv_lock = {}, threading.Lock()
    srv.note_request = lambda *a: None
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    port = srv.server_address[1]
    try:
        wedged = []
        for _ in range(2):  # hold both slots with half-sent requests
            c = socket.create_connection(("127.0.0.1", port))
            c.sendall(b"GET /a/b HTTP/1.1\r\n")
            wedged.append(c)
        time.sleep(0.2)
        c3 = socket.create_connection(("127.0.0.1", port))
        c3.sendall(b"GET /a/b HTTP/1.0\r\n\r\n")
        assert b"503" in c3.recv(1000)
        c3.close()
        time.sleep(1.3)  # wedged clients hit the 1s request timeout
        c4 = socket.create_connection(("127.0.0.1", port))
        c4.sendall(b"GET /a/b HTTP/1.0\r\n\r\n")
        resp = c4.recv(1000)
        assert b"404" in resp  # served again (empty KV -> 404)
        c4.close()
        for c in wedged:
            c.close()
    finally:
        srv.shutdown()
        srv.server_close()


def test_kv_retry_shield_retries_busy_503_not_404():
    """Review regression: the hardened pool's inline 503 busy-reject
    must be RETRYABLE for the repo's own KV clients (it means 'again
    in a moment'), while semantic HTTP statuses (404) stay terminal."""
    from urllib.error import HTTPError
    from horovod_tpu.runner.http_kv import _with_retries
    calls = {"n": 0}

    def busy_twice():
        calls["n"] += 1
        if calls["n"] < 3:
            raise HTTPError("http://x/", 503, "busy", {}, None)
        return b"ok"

    assert _with_retries(busy_twice, attempts=4) == b"ok"
    assert calls["n"] == 3

    def not_found():
        calls["n"] += 1
        raise HTTPError("http://x/", 404, "nope", {}, None)

    calls["n"] = 0
    with pytest.raises(HTTPError):
        _with_retries(not_found, attempts=4)
    assert calls["n"] == 1  # terminal on the first answer


def test_exporter_readyz_split_from_healthz():
    """Satellite: /healthz liveness vs /readyz readiness; a ready_fn
    flip is visible to orchestrators without touching /healthz."""
    import urllib.error
    import urllib.request
    from horovod_tpu.metrics.exporter import MetricsExporter
    state = {"ready": True}
    exp = MetricsExporter(
        port=0, health_fn=lambda: {"status": "ok"},
        ready_fn=lambda: {"ready": state["ready"], "why": "test"})
    exp.start()
    try:
        base = f"http://127.0.0.1:{exp.port}"
        with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
            assert json.loads(r.read())["ready"] is True
        state["ready"] = False
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/readyz", timeout=5)
        assert ei.value.code == 503
        # liveness unaffected by readiness
        with urllib.request.urlopen(base + "/healthz", timeout=5) as r:
            assert json.loads(r.read())["status"] == "ok"
        # default derivation: no ready_fn -> ready iff healthy
        exp.set_ready_fn(None)
        with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
            assert json.loads(r.read())["ready"] is True
    finally:
        exp.stop()


# -- replica ------------------------------------------------------------------
def _post(port, doc, path="/infer", timeout=10.0):
    import urllib.error
    import urllib.request
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(doc).encode(),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


@pytest.fixture
def replica():
    from horovod_tpu.serving import ReplicaServer
    r = ReplicaServer(dim=4, replica_id="t0").start()
    yield r
    r.stop()


def test_replica_infer_roundtrip(replica):
    code, resp = _post(replica.port, {"id": "q1", "x": [4.0, 0, 0, 0]})
    assert code == 200 and resp["version"] == 0
    # demo model: w = 1/dim everywhere, b = 0 -> y_j = mean(x)
    assert np.allclose(resp["y"], [1.0] * 4)
    # a wrong-width payload is rejected at admission (400), never
    # co-batched where it would fail the whole batch
    code, resp = _post(replica.port, {"id": "q2", "x": [1.0, 2.0]})
    assert code == 400 and "shape" in resp["error"]


def test_replica_idempotent_duplicate_returns_same_answer(replica):
    """A hedged/retried duplicate (same id, even different payload)
    must return the SAME response, not recompute."""
    _, a = _post(replica.port, {"id": "dup", "x": [1.0, 0, 0, 0]})
    _, b = _post(replica.port, {"id": "dup", "x": [9.0, 9, 9, 9]})
    assert a["y"] == b["y"]
    from horovod_tpu.metrics.registry import default_registry
    c = default_registry().get("hvd_serving_duplicate_hits_total")
    assert c is not None


def test_replica_readiness_gates_on_queue_and_drain(monkeypatch):
    from horovod_tpu.serving import ReplicaServer
    # queue budget -1: any depth (incl. 0) is over budget -> not ready
    monkeypatch.setenv("HVD_TPU_SERVING_READY_QUEUE", "-1")
    r = ReplicaServer(dim=4, replica_id="t1").start()
    try:
        assert r.ready_doc()["ready"] is False
    finally:
        r.stop()
    monkeypatch.delenv("HVD_TPU_SERVING_READY_QUEUE")
    r2 = ReplicaServer(dim=4, replica_id="t2").start()
    try:
        assert r2.ready_doc()["ready"] is True
        r2.drain(source="test")
        assert r2.ready_doc()["ready"] is False
        assert r2.ready_doc()["draining"] is True
        assert r2.wait_drained(5.0)
        # draining replica refuses new work with an explicit 503
        code, resp = _post(r2.port, {"id": "late", "x": [1, 1, 1, 1]})
        assert code == 503 and "draining" in resp["error"]
    finally:
        r2.stop()


def test_replica_chaos_serving_request_seam(monkeypatch, replica):
    """The serving.request seam: shed -> explicit 429, error -> 500
    (what the router retries around), both counted as injections."""
    from horovod_tpu import chaos
    plan = json.dumps({"faults": [
        {"seam": "serving.request", "kind": "shed", "start": 0,
         "stop": 1},
        {"seam": "serving.request", "kind": "error", "start": 1,
         "stop": 2}]})
    monkeypatch.setenv("HVD_TPU_FAULT_PLAN", plan)
    chaos.install(rank=0)
    try:
        code, resp = _post(replica.port, {"id": "s1", "x": [1, 0, 0, 0]})
        assert code == 429 and "chaos" in resp["error"]
        code, _resp = _post(replica.port, {"id": "s2", "x": [1, 0, 0, 0]})
        assert code == 500
        code, _resp = _post(replica.port, {"id": "s3", "x": [1, 0, 0, 0]})
        assert code == 200
    finally:
        chaos.uninstall()


def test_replica_hot_swap_from_durable_store(tmp_path):
    """Tentpole: restore_latest reshards a fresh commit onto the
    serving mesh while the old weights keep serving; the flip is
    atomic between batches and responses name the version that
    computed them."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    r = ReplicaServer(dim=4, store_dir=str(tmp_path),
                      replica_id="swap", swap_poll_s=0.05).start()
    try:
        code, resp = _post(r.port, {"id": "v1", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1
        assert abs(resp["y"][0] - 1.0) < 1e-5
        store.save(2, {"params": demo_params(4, scale=3.0)}, wait=True)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            code, resp = _post(
                r.port, {"id": f"v2-{time.monotonic_ns()}",
                         "x": [4.0, 0, 0, 0]})
            assert code == 200  # zero failed requests THROUGH the swap
            if resp["version"] == 2:
                break
            time.sleep(0.05)
        assert resp["version"] == 2
        assert abs(resp["y"][0] - 3.0) < 1e-5
    finally:
        r.stop()
        store.close()


def _corrupt(path):
    b = bytearray(open(path, "rb").read())
    b[len(b) // 2] ^= 0xFF
    open(path, "wb").write(bytes(b))


def test_replica_swap_fallback_names_the_restored_version(tmp_path):
    """Review regression: a corrupt NEWEST commit falls back to the
    older one — the serving version must name the weights ACTUALLY
    restored (not latest_step()), the non-swap must not count as a
    swap, and a later intact commit must still go live."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    store.save(2, {"params": demo_params(4, scale=2.0)}, wait=True)
    _corrupt(str(tmp_path / "step_2" / "shard_0.npz"))
    r = ReplicaServer(dim=4, store_dir=str(tmp_path),
                      replica_id="fb", swap_poll_s=0.05).start()
    try:
        # initial load fell back to step 1 and SAYS so
        code, resp = _post(r.port, {"id": "fb1", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1
        assert abs(resp["y"][0] - 1.0) < 1e-5
        time.sleep(0.3)  # swap polls see the corrupt step 2, skip it
        assert r._version == 1
        # an intact NEWER commit still goes live
        store.save(3, {"params": demo_params(4, scale=3.0)}, wait=True)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and r._version != 3:
            time.sleep(0.05)
        code, resp = _post(r.port,
                           {"id": "fb3", "x": [4.0, 0, 0, 0]})
        assert resp["version"] == 3 and abs(resp["y"][0] - 3.0) < 1e-5
    finally:
        r.stop()
        store.close()


# -- router -------------------------------------------------------------------
class _StubServer:
    """Minimal /infer stub with a configurable delay (the slow-replica
    stand-in for hedge tests)."""

    def __init__(self, delay_s=0.0, name="stub"):
        from http.server import BaseHTTPRequestHandler
        from horovod_tpu.runner.http_kv import ThreadedHTTPServer
        stub = self

        class H(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(n))
                time.sleep(stub.delay_s)
                body = json.dumps(
                    {"id": doc["id"], "y": [0.0], "version": 0,
                     "replica": stub.name}).encode()
                self.send_response(200)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        self.delay_s = delay_s
        self.name = name
        self.httpd = ThreadedHTTPServer(("127.0.0.1", 0), H)
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()

    @property
    def port(self):
        return self.httpd.server_address[1]

    def stop(self):
        self.httpd.shutdown()
        self.httpd.server_close()


def test_router_retries_past_dead_replica(replica):
    """A dead endpoint (connection refused) costs a retry, never the
    request: the survivor answers and the accounting stays
    exactly-once."""
    from horovod_tpu.serving import Router
    dead = ("127.0.0.1", _free_port())
    router = Router([dead, ("127.0.0.1", replica.port)], hedge_ms=0)
    doc = router.submit([1.0, 0, 0, 0], req_id="retry-1")
    assert doc["replica"] == "t0"
    acct = router.accounting()
    assert acct["outcomes"].get("retried", 0) >= 1
    assert acct["accepted"] == acct["answered_ok"] == 1
    assert not acct["unanswered"] and not acct["answered_twice"]
    router.close()


def test_router_hedges_slow_replica(replica):
    """A replica that has gone silent past hedge_ms gets the request
    duplicated to a second replica; the first success wins."""
    from horovod_tpu.serving import Router
    slow = _StubServer(delay_s=2.0, name="slow")
    try:
        router = Router([("127.0.0.1", slow.port),
                         ("127.0.0.1", replica.port)],
                        hedge_ms=100)
        t0 = time.monotonic()
        doc = router.submit([1.0, 0, 0, 0], req_id="hedge-1")
        took = time.monotonic() - t0
        assert doc["replica"] == "t0"  # the fast replica won
        assert took < 1.5  # did NOT wait out the slow replica
        acct = router.accounting()
        assert acct["outcomes"].get("hedged", 0) >= 1
        router.close()
    finally:
        slow.stop()


def test_router_client_error_is_terminal_not_retried(replica):
    """Review regression: a definitive 4xx (wrong-width payload) must
    be terminal — answered with the replica's verdict, logged
    ``rejected``, never re-dispatched across the fleet, and never a
    zero-drop audit violation."""
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.router import RequestRejected
    router = Router([("127.0.0.1", replica.port)], hedge_ms=0)
    with pytest.raises(RequestRejected) as ei:
        router.submit([1.0, 2.0], req_id="badwidth")  # replica dim=4
    assert ei.value.code == 400
    acct = router.accounting()
    assert acct["outcomes"].get("rejected") == 1
    assert acct["outcomes"].get("retried", 0) == 0
    assert not acct["unanswered"]  # rejected IS a terminal answer
    router.close()


def test_router_admission_shed_is_explicit():
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.batcher import SheddedError
    slow = _StubServer(delay_s=1.0)
    try:
        router = Router([("127.0.0.1", slow.port)], max_inflight=1,
                        hedge_ms=0)
        results = []

        def first():
            results.append(router.submit([1.0], req_id="occupant"))

        t = threading.Thread(target=first, daemon=True)
        t.start()
        time.sleep(0.2)  # occupant holds the one admission slot
        with pytest.raises(SheddedError):
            router.submit([2.0], req_id="shed-me")
        t.join(timeout=10)
        assert results  # the occupant itself completed
        acct = router.accounting()
        assert acct["outcomes"].get("shed") == 1
        entries = [e for e in router.log.entries
                   if e["outcome"] == "shed"]
        assert entries and entries[0]["where"] == "admission"
        router.close()
    finally:
        slow.stop()


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# -- SLO window -> finding -> autopilot scale-out -----------------------------
def test_latency_window_publishes_percentiles_and_history_point(
        tmp_path, monkeypatch):
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.serving.metrics import LatencyWindow
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    timeseries.reset()
    try:
        w = LatencyWindow(window_s=60.0)
        for ms in (1, 2, 3, 4, 100):
            w.observe(ms / 1000.0)
        doc = w.maybe_roll(force=True)
        assert doc["requests"] == 5
        assert doc["p50_s"] == pytest.approx(0.003, abs=1e-6)
        assert doc["p99_s"] == pytest.approx(0.1, abs=1e-6)
        from horovod_tpu.metrics.registry import default_registry
        snap = default_registry().snapshot()
        assert snap["hvd_serving_p99_seconds"]["value"] == \
            pytest.approx(0.1, abs=1e-6)
        points = timeseries.read_series(str(tmp_path))
        assert any(isinstance(p.get("serving"), dict) for p in points)
    finally:
        timeseries.reset()


def test_slo_breach_finding_scales_out_fleet_under_act(monkeypatch):
    """The detection->remediation chain end to end, in-process: a
    sustained windowed p99 over SLO reports ONE slo_breach finding;
    the default serving-slo-scaleout policy under act runs the
    registered scale-out hook.  Under observe the identical decision
    is recorded and nothing runs."""
    import horovod_tpu.autopilot as autopilot
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.serving.metrics import LatencyWindow

    monkeypatch.setenv("HVD_TPU_SERVING_SLO_P99_MS", "10")
    monkeypatch.setenv("HVD_TPU_SERVING_SLO_WINDOWS", "2")

    for mode, expect_calls in (("act", 1), ("observe", 0)):
        monkeypatch.setenv("HVD_TPU_AUTOPILOT", mode)
        autopilot.reset()
        anomaly.reset()
        calls = []
        autopilot.actions.register_scale_out_hook(
            lambda: calls.append(1))
        w = LatencyWindow(window_s=0.01)
        for _ in range(2):  # two consecutive breaching windows
            w.observe(0.5)
            w.maybe_roll(force=True)
        # hysteresis: ONE finding per episode, not one per window
        w.observe(0.5)
        w.maybe_roll(force=True)
        deadline = time.monotonic() + 5
        decisions = []
        while time.monotonic() < deadline:
            decisions = [d for d in autopilot.recent_decisions()
                         if d["policy"] == "serving-slo-scaleout"]
            if decisions and (len(calls) >= expect_calls):
                if mode == "observe" or calls:
                    break
            time.sleep(0.05)
        assert len(decisions) == 1, decisions
        assert decisions[0]["outcome"] == \
            ("fired" if mode == "act" else "dry_run")
        if mode == "act":
            assert len(calls) == 1
        else:
            assert not calls
    autopilot.reset()
    anomaly.reset()


# -- CLI rendering ------------------------------------------------------------
def test_top_renders_serving_lines():
    from horovod_tpu.metrics.__main__ import render_top
    series = {
        "hvd_serving_qps": 123.4, "hvd_serving_queue_depth": 3.0,
        "hvd_serving_p50_seconds": 0.0012,
        "hvd_serving_p99_seconds": 0.0045,
        'hvd_serving_shed_total{where="queue"}': 2.0,
        "hvd_serving_hedged_total": 5.0,
        "hvd_serving_retried_total": 1.0,
        "hvd_serving_replicas_live": 1.0,
        "hvd_serving_replicas_target": 2.0,
        "hvd_serving_weight_version": 7.0,
        "hvd_serving_swaps_total": 2.0,
        "hvd_serving_replica_respawns_total": 1.0,
    }
    frame = render_top(series, "test")
    assert "SERVING" in frame and "123.4 qps" in frame
    assert "p99 4.5ms" in frame and "shed 2" in frame
    assert "hedged 5" in frame and "retried 1" in frame
    assert "replicas        : 1/2" in frame
    assert "FLEET BELOW TARGET" in frame
    # no serving series -> no serving line
    assert "SERVING" not in render_top({"hvd_steps_total": 5.0}, "t")


def test_history_serving_table(tmp_path, monkeypatch, capsys):
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.__main__ import main as metrics_main
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    timeseries.reset()
    try:
        timeseries.record_point({"serving": {
            "window_s": 5.0, "requests": 100, "qps": 20.0,
            "p50_s": 0.002, "p99_s": 0.009, "shed": 1}})
    finally:
        timeseries.reset()
    rc = metrics_main(["history", "--dir", str(tmp_path), "--serving"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "p99" in out and "20.0" in out and "serving window" in out
    # the step view must not show serving points
    rc = metrics_main(["history", "--dir", str(tmp_path)])
    assert rc == 1  # nothing but serving windows in the store


def test_chaos_plan_validates_serving_seam():
    from horovod_tpu.chaos import FaultPlanError, parse_plan
    plan = parse_plan(json.dumps({"faults": [
        {"seam": "serving.request", "kind": "shed", "count": 1},
        {"seam": "serving.request", "kind": "delay", "delay_ms": 5,
         "rank": 1},
        {"seam": "serving.request", "kind": "error", "start": 3,
         "stop": 9}]}))
    assert len(plan.rules) == 3
    with pytest.raises(FaultPlanError, match="not valid for seam"):
        parse_plan(json.dumps({"faults": [
            {"seam": "serving.request", "kind": "kill"}]}))
    with pytest.raises(FaultPlanError, match="not valid for seam"):
        parse_plan(json.dumps({"faults": [
            {"seam": "step", "kind": "shed"}]}))


# -- slow: the chaos acceptance pair ------------------------------------------
def _closed_loop(router, clients, stop, errors):
    threads = []

    def client(i):
        n = 0
        while not stop.is_set():
            n += 1
            try:
                router.submit([float(i), 1.0, 2.0, 3.0],
                              req_id=f"c{i}-{n}")
            except Exception as e:  # noqa: BLE001 - audit catches all
                errors.append(repr(e))

    for i in range(clients):
        t = threading.Thread(target=client, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    return threads


@pytest.mark.slow  # tier-1 budget rule: multiprocess tests are
#                    slow-marked; the serving/chaos CI tiers run them
def test_serving_kill_replica_zero_drop_and_heal():
    """ISSUE 14 acceptance (a): SIGKILL one replica of a 2-replica
    fleet under sustained closed-loop load — every accepted request
    gets exactly one successful response (hedged/retried to the
    survivor), zero drops, and the fleet heals to full size with the
    exit classified FAILURE."""
    from horovod_tpu.serving import ReplicaFleet, Router
    fleet = ReplicaFleet(size=2, dim=4).start(ready_timeout_s=120)
    router = Router(fleet.endpoints, hedge_ms=200, max_attempts=8)
    stop = threading.Event()
    errors = []
    threads = _closed_loop(router, 4, stop, errors)
    try:
        time.sleep(1.5)
        victim = fleet._replicas[1]
        os.kill(victim.proc.pid, signal.SIGKILL)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and fleet.live_count() < 2:
            time.sleep(0.25)
        assert fleet.live_count() == 2, "fleet did not heal"
        time.sleep(1.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        router.close()
    acct = router.accounting()
    fleet.stop()
    assert not errors, errors[:3]
    # the zero-drop audit, from request-log accounting
    assert acct["accepted"] == acct["answered_ok"] > 0
    assert not acct["unanswered"] and not acct["answered_twice"]
    assert acct["outcomes"].get("failed", 0) == 0
    # the kill was absorbed by hedge/retry, visibly
    assert acct["outcomes"].get("retried", 0) \
        + acct["outcomes"].get("hedged", 0) > 0
    # exit classified FAILURE (not drained), exactly one kill
    kills = [e for e in fleet.exits if e["outcome"] == "failure"]
    assert len(kills) == 1 and kills[0]["rc"] == -9
    from horovod_tpu.metrics.registry import default_registry
    snap = default_registry().snapshot()
    assert snap["hvd_serving_accepted_total"]["value"] >= \
        acct["accepted"]


@pytest.mark.slow
def test_serving_drain_plus_hot_swap_zero_failures(tmp_path):
    """ISSUE 14 acceptance (b): a chaos preemption notice drains one
    replica — it finishes all in-flight requests and exits DRAINED
    (exit 0, never failure evidence) — while a concurrent hot weight
    swap from a fresh durable commit serves the new version, with
    zero failed requests; proven from request-log accounting plus the
    hvd_serving_* counters and the fleet's exit classification."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaFleet, Router
    from horovod_tpu.serving.replica import demo_params
    store_dir = tmp_path / "store"
    store = ShardedCheckpointer(str(store_dir), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    # the preemption notice targets SLOT 1 only, ~1s into the run
    # (poll every 0.2s -> invocation index 5), with a marker so the
    # RESPAWNED replacement in the slot does not re-drain forever
    plan = json.dumps({"faults": [
        {"seam": "preemption", "kind": "notice", "rank": 1,
         "start": 5, "count": 1,
         "marker": str(tmp_path / "preempt_once")}]})
    fleet = ReplicaFleet(
        size=2, dim=4, store_dir=str(store_dir),
        extra_env={"HVD_TPU_FAULT_PLAN": plan,
                   "HVD_TPU_SERVING_SWAP_POLL_S": "0.1"}).start(
        ready_timeout_s=120)
    router = Router(fleet.endpoints, hedge_ms=200, max_attempts=8)
    stop = threading.Event()
    errors = []
    threads = _closed_loop(router, 4, stop, errors)
    versions = set()
    try:
        time.sleep(0.5)
        # concurrent hot swap: a fresh durable commit lands mid-drain
        store.save(2, {"params": demo_params(4, scale=3.0)}, wait=True)
        # wait for the drained exit + heal + the new version serving
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            drained = [e for e in fleet.exits
                       if e["outcome"] == "drained"]
            doc = router.submit([4.0, 0, 0, 0])
            versions.add(doc["version"])
            if drained and fleet.live_count() == 2 and 2 in versions:
                break
            time.sleep(0.2)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        router.close()
    acct = router.accounting()
    exits = list(fleet.exits)
    fleet.stop()
    store.close()
    # the doomed replica finished its in-flight work and exited
    # DRAINED; nothing was held against it and the fleet healed
    drained = [e for e in exits if e["outcome"] == "drained"]
    assert len(drained) == 1, exits
    assert drained[0]["rc"] == 0 and drained[0]["slot"] == 1
    assert "DRAINED" in drained[0]["tail"]
    assert "preemption" in drained[0]["tail"]
    assert not [e for e in exits if e["outcome"] == "failure"], exits
    # zero failed requests through drain + swap, exactly-once audit
    assert not errors, errors[:3]
    assert acct["accepted"] == acct["answered_ok"] > 0
    assert not acct["unanswered"] and not acct["answered_twice"]
    assert acct["outcomes"].get("failed", 0) == 0
    # the new version went live with zero downtime
    assert 2 in versions
