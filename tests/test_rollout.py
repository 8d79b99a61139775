"""Canary weight rollout (ISSUE 18, docs/SERVING.md "Canary rollout").

Fast battery: the rollout actions/policies in the autopilot defaults,
the verdict gate routing one rollout_verdict finding to exactly one of
the two policies, finding trace continuation, replica version pinning
(API + /pin route + pin_version restore + the weight_swap audit), the
router's deterministic crc32 version split (same id -> same arm, empty
arm falls back loudly), the per-version SLO comparator and golden
probe, the controller state machine over an in-process fleet adapter,
the fully in-process governed transition (evaluate -> autopilot ->
hooks, one trace id printed by `diagnostics trace`), and the rollout
status CLI.

Slow (serving/chaos CI tiers; tier-1 budget rule — all multiprocess
tests are slow-marked): the churn acceptance (SIGKILL the canary
replica mid-rollout: zero drop, idempotent replay stays on its arm,
the healed replacement joins at the INCUMBENT) and the ISSUE 18 chaos
acceptance — a poisoned commit canaried at N% is caught by the
per-version comparator's golden probe and auto-rolled-back by the
autopilot with ZERO failed requests, then a clean commit promotes
fleet-wide, each transition resolving to a single trace id.
"""

import json
import os
import signal
import threading
import time
import urllib.error
import urllib.request
import zlib

import pytest


@pytest.fixture(autouse=True)
def _fresh_singletons(monkeypatch):
    import horovod_tpu.autopilot as autopilot
    from horovod_tpu import chaos
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.metrics import anomaly, timeseries
    monkeypatch.delenv("HVD_TPU_AUTOPILOT", raising=False)
    monkeypatch.delenv("HVD_TPU_AUTOPILOT_POLICY", raising=False)
    monkeypatch.delenv("HVD_TPU_OBS_DIR", raising=False)
    # manufactured findings must not arm real device-trace captures
    monkeypatch.setenv("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    chaos.uninstall()
    autopilot.reset()
    anomaly.reset()
    timeseries.reset()
    recorder().clear()
    yield
    chaos.uninstall()
    autopilot.reset()
    anomaly.reset()
    timeseries.reset()


def _wait(cond, timeout=10.0, step=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(step)
    return cond()


def _post(port, doc, path="/infer", timeout=10.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(doc).encode(),
        method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


class _VersionStub:
    """Minimal replica stand-in: /infer answers with a fixed weight
    version (y = [version] * len(x)), /readyz answers 200 — the router
    and golden probe only need the wire contract, not a real model."""

    def __init__(self, version):
        from http.server import (BaseHTTPRequestHandler,
                                 ThreadingHTTPServer)
        stub = self

        class _H(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):
                pass

            def _send(self, code, doc):
                body = json.dumps(doc).encode()
                self.send_response(code)
                self.send_header("Content-Type", "application/json")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                self._send(200, {"ready": True, "version": stub.version})

            def do_POST(self):
                n = int(self.headers.get("Content-Length", 0))
                doc = json.loads(self.rfile.read(n))
                stub.hits += 1
                x = doc.get("x") or [0.0]
                self._send(200, {"id": doc.get("id"),
                                 "y": [float(stub.version)] * len(x),
                                 "version": stub.version,
                                 "replica": f"stub-v{stub.version}"})

        self.version = version
        self.hits = 0
        self._srv = ThreadingHTTPServer(("127.0.0.1", 0), _H)
        threading.Thread(target=self._srv.serve_forever,
                         daemon=True).start()

    @property
    def endpoint(self):
        return ("127.0.0.1", self._srv.server_address[1])

    def close(self):
        self._srv.shutdown()


class _FakeFleet:
    """The controller's fleet surface, in-process: records every pin
    call; version arms serve from a static endpoints-by-version map."""

    def __init__(self, slots, eps_by_version=None):
        self._slots = list(slots)
        self.eps = dict(eps_by_version or {})
        self.pin_calls = []
        self.pinned = {}

    def slots(self):
        return list(self._slots)

    def pin_slot(self, slot, version, reason="pin", heal_version=None):
        self.pin_calls.append({"slot": slot, "version": version,
                               "reason": reason, "heal": heal_version})
        if version is None:
            self.pinned.pop(slot, None)
        else:
            self.pinned[slot] = version
        return True

    def unpin_slot(self, slot):
        return self.pin_slot(slot, None, reason="unpin")

    def endpoints_at(self, version):
        return list(self.eps.get(version, []))


# -- autopilot wiring ---------------------------------------------------------
def test_rollout_policies_registered():
    from horovod_tpu.autopilot.policy import ACTIONS, default_policies
    assert "promote_rollout" in ACTIONS
    assert "rollback_rollout" in ACTIONS
    byname = {p.name: p for p in default_policies()}
    assert byname["rollout-promote"].finding == "rollout_verdict"
    assert byname["rollout-promote"].action == "promote_rollout"
    assert byname["rollout-rollback"].finding == "rollout_verdict"
    assert byname["rollout-rollback"].action == "rollback_rollout"


def test_verdict_gate_routes_to_exactly_one_policy(monkeypatch):
    """Both rollout policies subscribe to the SAME rollout_verdict
    finding; the verdict field routes it to exactly one — the other's
    decision is suppressed with the mismatched verdict recorded."""
    import horovod_tpu.autopilot as autopilot
    from horovod_tpu.autopilot import actions
    from horovod_tpu.metrics import anomaly
    for verdict, fired_policy, other_policy in (
            ("promote", "rollout-promote", "rollout-rollback"),
            ("rollback", "rollout-rollback", "rollout-promote")):
        monkeypatch.setenv("HVD_TPU_AUTOPILOT", "act")
        autopilot.reset()
        anomaly.reset()
        calls = []
        actions.register_promote_rollout_hook(
            lambda f: calls.append(("promote", f)))
        actions.register_rollback_rollout_hook(
            lambda f: calls.append(("rollback", f)))
        anomaly.report_finding("rollout_verdict", verdict=verdict,
                               reason="test", rollout_id="r-1")
        assert _wait(lambda: len(calls) == 1 and len(
            [d for d in autopilot.recent_decisions()
             if d["finding"] == "rollout_verdict"]) >= 2, timeout=5)
        ds = {d["policy"]: d for d in autopilot.recent_decisions()
              if d["finding"] == "rollout_verdict"}
        assert ds[fired_policy]["outcome"] == "fired"
        assert ds[other_policy]["outcome"] == "suppressed"
        assert ds[other_policy]["gate"]["verdict"] == verdict
        assert ds[other_policy]["gate"]["want"] != verdict
        # the hook received the FINDING (rollout_id routes staleness)
        assert calls == [(verdict, calls[0][1])]
        assert calls[0][1]["rollout_id"] == "r-1"
    autopilot.reset()
    anomaly.reset()


def test_finding_continues_supplied_traceparent():
    """A rollout_verdict carrying the controller's traceparent must
    CONTINUE that trace (child span), not root a fresh one — the whole
    governed transition is one causal tree."""
    from horovod_tpu import tracing
    from horovod_tpu.metrics import anomaly
    root = tracing.new_trace("rollout")
    f = anomaly.report_finding(
        "rollout_verdict", verdict="promote", rollout_id="r-t",
        **{tracing.TRACEPARENT: root.traceparent})
    assert f["trace"] == root.trace_id
    assert f[tracing.TRACEPARENT] != root.traceparent  # a child span
    # without a supplied traceparent the finding roots its own trace
    f2 = anomaly.report_finding("rollout_verdict", verdict="promote",
                                rollout_id="r-t2")
    assert f2["trace"] != root.trace_id


# -- replica version pinning --------------------------------------------------
def test_replica_pin_holds_against_newer_commits(tmp_path):
    """Satellite: a pinned replica never chases a newer commit; unpin
    resumes the chase; a rollback repin is a BACKWARD flip audited as
    a weight_swap event naming both endpoints and its reason."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.metrics.registry import default_registry
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    r = ReplicaServer(dim=4, store_dir=str(tmp_path), replica_id="pin0",
                      swap_poll_s=0.05).start()
    try:
        doc = r.pin(1)
        assert doc["pinned"] == 1 and doc["version"] == 1
        store.save(2, {"params": demo_params(4, scale=2.0)}, wait=True)
        time.sleep(0.3)  # several swap-poll intervals
        code, resp = _post(r.port, {"id": "p1", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1  # never chased
        r.unpin()
        assert _wait(lambda: _post(
            r.port, {"id": f"p-{time.monotonic_ns()}",
                     "x": [4.0, 0, 0, 0]})[1]["version"] == 2)
        # rollback repin: 2 -> 1 while 2 is still latest in the store
        r.pin(1, reason="rollback")
        code, resp = _post(r.port, {"id": "p2", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1
        assert abs(resp["y"][0] - 1.0) < 1e-5  # v1 math, not v2's
        swaps = [e for e in recorder().events()
                 if e.get("kind") == "weight_swap"
                 and e.get("replica") == "pin0"]
        assert any(e.get("reason") == "chase" for e in swaps)
        back = [e for e in swaps if e.get("reason") == "rollback"]
        assert back and back[-1]["from_version"] == 2
        assert back[-1]["to_version"] == 1
        c = default_registry().get("hvd_serving_weight_swaps_total",
                                   labels={"reason": "rollback"})
        assert c is not None and c.value >= 1
    finally:
        r.stop()
        store.close()


def test_pin_http_route(tmp_path):
    """The fleet manager's control seam: POST /pin pins/unpins; a
    malformed body is a 400, never a crashed replica."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    store.save(2, {"params": demo_params(4, scale=2.0)}, wait=True)
    r = ReplicaServer(dim=4, store_dir=str(tmp_path),
                      replica_id="pinhttp").start()
    try:
        assert r._version == 2  # restored latest at start
        code, doc = _post(r.port, {"version": 1, "reason": "pin"},
                          path="/pin")
        assert code == 200 and doc["pinned"] == 1 and doc["version"] == 1
        # readyz carries the observed version + pin (the fleet's
        # membership view parses exactly this doc)
        ready = r.ready_doc()
        assert ready["version"] == 1 and ready["pinned"] == 1
        code, doc = _post(r.port, {}, path="/pin")  # null version unpins
        assert code == 200 and doc["pinned"] is None
        req = urllib.request.Request(
            f"http://127.0.0.1:{r.port}/pin", data=b"{nope",
            method="POST")
        try:
            urllib.request.urlopen(req, timeout=5)
            code = 200
        except urllib.error.HTTPError as e:
            code = e.code
        assert code == 400
    finally:
        r.stop()
        store.close()


def test_replica_restores_pin_version_at_start(tmp_path):
    """A healed replacement spawned with --pin-version restores the
    pinned step DIRECTLY — it never transits through latest."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    store.save(2, {"params": demo_params(4, scale=3.0)}, wait=True)
    r = ReplicaServer(dim=4, store_dir=str(tmp_path), replica_id="heal",
                      swap_poll_s=0.05, pin_version=1).start()
    try:
        code, resp = _post(r.port, {"id": "h1", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1
        time.sleep(0.3)  # the pin holds across swap polls too
        code, resp = _post(r.port, {"id": "h2", "x": [4.0, 0, 0, 0]})
        assert resp["version"] == 1 and abs(resp["y"][0] - 1.0) < 1e-5
    finally:
        r.stop()
        store.close()


def test_pin_to_missing_version_leaves_replica_unpinned(tmp_path):
    """Regression: a failed pin restore must not commit the pin — the
    replica keeps serving its old weights UNPINNED (and keeps chasing
    commits) instead of freezing on an unloadable version that the
    swap loop would retry forever."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaServer
    from horovod_tpu.serving.replica import demo_params
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    r = ReplicaServer(dim=4, store_dir=str(tmp_path),
                      replica_id="nopin", swap_poll_s=0.05).start()
    try:
        code, doc = _post(r.port, {"version": 99}, path="/pin")
        assert code == 500
        assert r.pinned is None  # the failed pin was NOT committed
        code, resp = _post(r.port, {"id": "n1", "x": [4.0, 0, 0, 0]})
        assert code == 200 and resp["version"] == 1  # old weights serve
        # and the replica still chases the next commit — not frozen
        store.save(2, {"params": demo_params(4, scale=2.0)}, wait=True)
        assert _wait(lambda: _post(
            r.port, {"id": f"n-{time.monotonic_ns()}",
                     "x": [4.0, 0, 0, 0]})[1]["version"] == 2)
    finally:
        r.stop()
        store.close()


# -- router version split -----------------------------------------------------
def test_router_version_split_deterministic_by_request_id():
    """crc32(id) % 100 buckets the split: the assignment is exact and
    an idempotent replay of an id lands on the SAME arm — answered by
    the same version as the original."""
    from horovod_tpu.serving import Router
    canary, incumbent = _VersionStub(2), _VersionStub(1)
    router = Router(lambda: [canary.endpoint, incumbent.endpoint],
                    max_attempts=4)
    try:
        router.set_version_split(30, [canary.endpoint],
                                 [incumbent.endpoint],
                                 canary_version=2, incumbent_version=1)
        assert router.version_split() == {
            "pct": 30, "canary_version": 2, "incumbent_version": 1}
        expect, got = {}, {}
        for i in range(60):
            rid = f"s{i}"
            expect[rid] = 2 if zlib.crc32(rid.encode()) % 100 < 30 else 1
            got[rid] = router.submit([1.0, 2.0], req_id=rid)["version"]
        assert got == expect
        n_canary = sum(1 for v in expect.values() if v == 2)
        assert 0 < n_canary < 60  # both arms actually exercised
        acct = router.accounting()
        assert acct["by_version"][2] == n_canary
        assert acct["by_version"][1] == 60 - n_canary
        # replay: same id -> same arm -> same version
        assert router.submit([9.0, 9.0],
                             req_id="s0")["version"] == expect["s0"]
        router.clear_version_split()
        assert router.version_split() is None
    finally:
        router.close()
        canary.close()
        incumbent.close()


def test_router_empty_arm_falls_back_to_full_fleet():
    """Zero-drop outranks split fidelity: an empty arm (canary mid-
    heal) degrades to the full fleet, counted — never a failed
    request."""
    from horovod_tpu.metrics.registry import default_registry
    from horovod_tpu.serving import Router
    incumbent = _VersionStub(1)
    router = Router(lambda: [incumbent.endpoint], max_attempts=4)
    try:
        router.set_version_split(100, lambda: [], [incumbent.endpoint],
                                 canary_version=2, incumbent_version=1)
        before = 0.0
        c = default_registry().get(
            "hvd_serving_rollout_split_fallback_total",
            labels={"arm": "canary"})
        if c is not None:
            before = c.value
        doc = router.submit([1.0], req_id="fb-1")  # 100% canary, empty
        assert doc["version"] == 1  # answered by the incumbent instead
        c = default_registry().get(
            "hvd_serving_rollout_split_fallback_total",
            labels={"arm": "canary"})
        assert c is not None and c.value >= before + 1
    finally:
        router.close()
        incumbent.close()


def test_retry_attribution_names_arm_version_for_dead_canary():
    """Regression: a poisoned candidate that never answers 200 must
    still accrue canary errors — retried-line attribution is by
    CURRENT arm membership, not the last version observed answering
    the endpoint (which would be the incumbent's, or nothing at all,
    so the error-rate rollback could never fire)."""
    import socket
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.rollout import version_windows
    incumbent = _VersionStub(1)
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    dead = ("127.0.0.1", s.getsockname()[1])
    s.close()  # connection refused from now on: a 200-less canary
    router = Router(lambda: [dead, incumbent.endpoint],
                    max_attempts=4, hedge_ms=0)
    try:
        router.set_version_split(100, [dead], [incumbent.endpoint],
                                 canary_version=2, incumbent_version=1)
        doc = router.submit([1.0], req_id="dead-1")
        assert doc["version"] == 1  # widened to the incumbent: no drop
        retried = [e for e in router.log.entries
                   if e["outcome"] == "retried"]
        assert retried and retried[0]["after_version"] == 2
        assert retried[0]["version"] == 1  # the retry target's version
        stats = version_windows(router.log.entries, [2, 1])
        assert stats[2]["errors"] >= 1  # the canary window accrues
        assert stats[1]["ok"] == 1
    finally:
        router.close()
        incumbent.close()


def test_request_log_seq_anchor_survives_memory_trim(monkeypatch):
    """The stage-window anchor is an absolute sequence number: after
    the in-memory cap trims head entries, ``since(anchor)`` still
    returns every SURVIVING post-anchor entry (an index anchor would
    over-skip by the trimmed count)."""
    from horovod_tpu.serving.router import RequestLog
    monkeypatch.setattr(RequestLog, "MAX_MEMORY", 100)
    log = RequestLog()
    for i in range(90):
        log.note(f"a{i}", "ok", version=1)
    anchor = log.seq_now()
    assert anchor == 90
    for i in range(120):  # crosses the cap repeatedly -> trims fire
        log.note(f"b{i}", "ok", version=2)
    assert log.trimmed > 0
    assert log.seq_now() == 210
    ids = {e["id"] for e in log.since(anchor)}
    # every surviving post-anchor entry is in the window...
    for e in log.entries:
        if e["id"].startswith("b"):
            assert e["id"] in ids
    # ...and nothing from before the anchor leaks in
    assert not any(i.startswith("a") for i in ids)


# -- comparator ---------------------------------------------------------------
def _ok(version, latency_s):
    return {"outcome": "ok", "version": version, "latency_s": latency_s}


def test_comparator_version_windows_and_verdicts():
    from horovod_tpu.serving.rollout import compare, version_windows
    entries = ([_ok(2, 0.01)] * 9 + [_ok(1, 0.01)] * 20
               + [{"outcome": "retried", "after_version": 2}]
               + [{"outcome": "accepted", "id": "x"}])  # ignored
    stats = version_windows(entries, [2, 1])
    assert stats[2]["ok"] == 9 and stats[2]["errors"] == 1
    assert stats[2]["requests"] == 10
    assert stats[2]["error_rate"] == pytest.approx(0.1)
    assert stats[1] == {"version": 1, "requests": 20, "ok": 20,
                        "errors": 0, "error_rate": 0.0,
                        "p50_s": 0.01, "p99_s": 0.01}
    # insufficient traffic outranks everything: no verdict on noise
    v, reason = compare(stats[2], stats[1], min_requests=50,
                        max_p99_ratio=2.0, max_error_rate=0.05)
    assert v is None and "insufficient" in reason
    # error rate over the cap AND over the incumbent's -> rollback
    v, reason = compare(stats[2], stats[1], min_requests=10,
                        max_p99_ratio=2.0, max_error_rate=0.05)
    assert v == "rollback" and "error rate" in reason
    # p99 beyond the allowed ratio -> rollback
    slow = version_windows([_ok(2, 0.5)] * 10 + [_ok(1, 0.01)] * 10,
                           [2, 1])
    v, reason = compare(slow[2], slow[1], min_requests=10,
                        max_p99_ratio=2.0, max_error_rate=0.05)
    assert v == "rollback" and "p99" in reason
    # healthy canary -> promote
    good = version_windows([_ok(2, 0.011)] * 10 + [_ok(1, 0.01)] * 10,
                           [2, 1])
    v, reason = compare(good[2], good[1], min_requests=10,
                        max_p99_ratio=2.0, max_error_rate=0.05)
    assert v == "promote"
    # the golden probe outranks latency: a FAST canary with wrong math
    # still rolls back
    v, reason = compare(good[2], good[1], min_requests=10,
                        max_p99_ratio=2.0, max_error_rate=0.05,
                        golden_divergence=49.0, golden_max=0.5)
    assert v == "rollback" and "golden" in reason


def test_comparator_percentiles_are_fractions_not_percents():
    """Regression: percentile() takes a fraction in [0,1] — passing
    50.0/99.0 clamps to max() and both p50 and p99 become the single
    worst sample, so one slow outlier on the canary could spuriously
    roll back a healthy candidate.  On a skewed list p50 != p99."""
    from horovod_tpu.serving.rollout import version_windows
    entries = [_ok(2, 0.01)] * 9 + [_ok(2, 1.0)]  # one slow outlier
    stats = version_windows(entries, [2])
    assert stats[2]["p50_s"] == pytest.approx(0.01)
    assert stats[2]["p99_s"] == pytest.approx(1.0)
    assert stats[2]["p50_s"] != stats[2]["p99_s"]


def test_golden_set_loader_and_divergence(tmp_path):
    from horovod_tpu.serving.rollout import (golden_divergence,
                                             load_golden_set)
    p = tmp_path / "golden.json"
    p.write_text(json.dumps({"requests": [{"x": [1.0, 2.0]}]}))
    assert load_golden_set(str(p)) == [{"x": [1.0, 2.0]}]
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps([{"x": [3.0]}]))
    assert load_golden_set(str(bare)) == [{"x": [3.0]}]
    # malformed sets fail LOUDLY — a quality gate whose probe set
    # silently failed to load is a gate that never fires
    empty = tmp_path / "empty.json"
    empty.write_text("[]")
    with pytest.raises(ValueError, match="no requests"):
        load_golden_set(str(empty))
    nox = tmp_path / "nox.json"
    nox.write_text(json.dumps([{"y": [1.0]}]))
    with pytest.raises(ValueError, match="no 'x'"):
        load_golden_set(str(nox))
    # divergence: max |y_canary - y_incumbent| over the fixed set
    a, b = _VersionStub(5), _VersionStub(2)
    try:
        d = golden_divergence(a.endpoint, b.endpoint,
                              [{"x": [1.0, 2.0]}, {"x": [0.0]}])
        assert d == pytest.approx(3.0)
        assert a.hits == 2 and b.hits == 2
    finally:
        a.close()
        b.close()


# -- controller state machine -------------------------------------------------
def test_controller_state_machine_and_persisted_status(tmp_path):
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController,
                                             read_status)
    fleet = _FakeFleet([0, 1, 2])
    router = Router(lambda: [], max_attempts=2)
    cfg = RolloutConfig(canary_pct=34, expand_pct=50, window_s=60.0,
                        min_requests=5)
    ctl = RolloutController(fleet, router, cfg,
                            store_dir=str(tmp_path))
    try:
        assert ctl.state == "idle"
        assert ctl.evaluate(force=True) is None  # nothing to measure
        ctl.begin(candidate=7, incumbent=6)
        assert ctl.state == "canary"
        assert ctl.canary_slots == [0]  # 3 slots at 34% -> exactly one
        pins = {c["slot"]: c for c in fleet.pin_calls}
        # canary pinned to the candidate, HEALING at the incumbent
        assert pins[0]["version"] == 7 and pins[0]["heal"] == 6
        # the rest pinned to the incumbent (unpinned would chase the
        # candidate and silently widen the canary)
        assert pins[1]["version"] == 6 and pins[1]["heal"] is None
        assert pins[2]["version"] == 6
        assert router.version_split() == {
            "pct": 34, "canary_version": 7, "incumbent_version": 6}
        with pytest.raises(RuntimeError, match="already in progress"):
            ctl.begin(candidate=8, incumbent=7)
        # the stage window is still open -> no verdict; forcing with
        # zero traffic is still insufficient evidence
        assert ctl.evaluate() is None
        assert ctl.evaluate(force=True) is None
        # a stale finding from a previous rollout is ignored
        ctl._on_promote({"rollout_id": "rollout-999-v9"})
        assert ctl.state == "canary"
        ctl._on_promote({"rollout_id": ctl.rollout_id})
        assert ctl.state == "expanding"
        assert router.version_split()["pct"] == 50
        fleet.pin_calls.clear()
        ctl._on_promote({"rollout_id": ctl.rollout_id})
        assert ctl.state == "promoted"
        assert router.version_split() is None
        assert ctl.canary_slots == []
        # every slot flipped to the candidate, then released to chase
        for s in (0, 1, 2):
            calls = [c for c in fleet.pin_calls if c["slot"] == s]
            assert calls[0]["version"] == 7
            assert calls[-1]["version"] is None
        # durable status answers from OUTSIDE the controller process
        doc = read_status(str(tmp_path))
        assert doc["state"] == "promoted"
        assert doc["rollout_id"] == ctl.rollout_id
        assert doc["trace"] == ctl.trace.trace_id
        assert [h["to"] for h in doc["history"]] == [
            "canary", "expanding", "promoted"]
        # a fresh rollout from promoted; the rollback path
        ctl.begin(candidate=9, incumbent=7)
        fleet.pin_calls.clear()
        # the operator escape hatch takes the same path as the hook
        ctl.rollback("test")
        assert ctl.state == "rolled_back"
        assert router.version_split() is None
        # EVERY slot ends pinned to the incumbent — the poisoned
        # candidate is still the newest commit in the store
        for s in (0, 1, 2):
            last = [c for c in fleet.pin_calls if c["slot"] == s][-1]
            assert last["version"] == 7 and last["reason"] == "rollback"
        assert fleet.pinned == {0: 7, 1: 7, 2: 7}
        assert read_status(str(tmp_path))["state"] == "rolled_back"
        # rollback duplicates are idempotent no-ops
        ctl.rollback()
        assert ctl.state == "rolled_back"
    finally:
        router.close()


def test_rollout_refuses_single_slot_fleet():
    """The canary invariant is 'at least 1, never the whole fleet': a
    1-slot fleet has no incumbent arm to compare against, so begin()
    must refuse rather than pin 100% of traffic to the candidate."""
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    fleet = _FakeFleet([0])
    router = Router(lambda: [], max_attempts=2)
    try:
        ctl = RolloutController(fleet, router, RolloutConfig())
        with pytest.raises(RuntimeError, match="at least 2"):
            ctl.begin(candidate=2, incumbent=1)
        assert ctl.state == "idle"
        assert fleet.pin_calls == []  # nothing was pinned
        assert router.version_split() is None
    finally:
        router.close()


def test_controller_stage_window_survives_log_trim(monkeypatch,
                                                   tmp_path):
    """Regression: the stage window is anchored on the request log's
    absolute sequence number — when the in-memory cap trims head
    entries mid-stage, the verdict still sees every surviving
    current-stage line (an index anchor would have silently dropped
    the trimmed count from the window and starved the verdict)."""
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.router import RequestLog
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    monkeypatch.setattr(RequestLog, "MAX_MEMORY", 200)
    fleet = _FakeFleet([0, 1])
    router = Router(lambda: [], max_attempts=2)
    cfg = RolloutConfig(canary_pct=50, window_s=0.0, min_requests=60)
    ctl = RolloutController(fleet, router, cfg,
                            store_dir=str(tmp_path))
    try:
        for i in range(150):  # pre-stage traffic advances the anchor
            router.log.note(f"pre-{i}", "ok", version=1,
                            latency_s=0.01)
        ctl.begin(candidate=2, incumbent=1)
        for i in range(100):  # stage traffic crosses the cap -> trims
            router.log.note(f"c2-{i}", "ok", version=2,
                            latency_s=0.01)
            router.log.note(f"c1-{i}", "ok", version=1,
                            latency_s=0.01)
        assert router.log.trimmed > 0  # trims actually fired
        f = ctl.evaluate(force=True)
        assert f is not None and f["verdict"] == "promote"
        # both arms kept (nearly) all their surviving stage evidence
        assert f["canary_stats"]["requests"] >= 60
        assert f["incumbent_stats"]["requests"] >= 60
    finally:
        router.close()


def test_governed_rollout_end_to_end_in_process(monkeypatch, tmp_path,
                                                capsys):
    """evaluate -> rollout_verdict finding -> autopilot decision ->
    registered hook, fully in process under act: a healthy candidate
    walks canary -> expanding -> promoted, a degraded one rolls back —
    and each rollout's finding, decision and transitions share ONE
    trace id whose tree `diagnostics trace <id>` prints."""
    import horovod_tpu.autopilot as autopilot
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    monkeypatch.setenv("HVD_TPU_AUTOPILOT", "act")
    autopilot.reset()
    anomaly.reset()
    fleet = _FakeFleet([0, 1])
    router = Router(lambda: [], max_attempts=2)
    cfg = RolloutConfig(canary_pct=50, window_s=0.01, min_requests=5)
    ctl = RolloutController(fleet, router, cfg, store_dir=str(tmp_path)
                            ).register_autopilot_hooks()

    def _feed(version, latency_s, n=8):
        for i in range(n):
            router.log.note(f"f{version}-{time.monotonic_ns()}-{i}",
                            "ok", version=version, latency_s=latency_s)

    try:
        ctl.begin(candidate=2, incumbent=1)
        trace_id = ctl.trace.trace_id
        _feed(2, 0.01)
        _feed(1, 0.01)
        time.sleep(0.05)  # past the stage window
        finding = ctl.evaluate()
        assert finding is not None and finding["verdict"] == "promote"
        assert finding["trace"] == trace_id  # continues the rollout
        assert _wait(lambda: ctl.state == "expanding", timeout=5)
        # the expanding stage measures a FRESH window
        assert ctl.evaluate(force=True) is None  # no evidence yet
        _feed(2, 0.01)
        _feed(1, 0.01)
        time.sleep(1.1)  # rollout-promote cooldown between fires
        assert ctl.evaluate(force=True)["verdict"] == "promote"
        assert _wait(lambda: ctl.state == "promoted", timeout=5)
        promoted = [d for d in autopilot.recent_decisions()
                    if d["policy"] == "rollout-promote"
                    and d["outcome"] == "fired"]
        assert len(promoted) == 2
        assert all(d["trace"] == trace_id for d in promoted)

        # a poisoned candidate: degraded p99 rolls back autonomously
        ctl.begin(candidate=3, incumbent=2)
        t2 = ctl.trace.trace_id
        assert t2 != trace_id  # each rollout is its own causal tree
        _feed(3, 0.5)
        _feed(2, 0.01)
        f2 = ctl.evaluate(force=True)
        assert f2["verdict"] == "rollback" and "p99" in f2["reason"]
        assert f2["trace"] == t2
        assert _wait(lambda: ctl.state == "rolled_back", timeout=5)
        assert fleet.pinned == {0: 2, 1: 2}
        rb = [d for d in autopilot.recent_decisions()
              if d["policy"] == "rollout-rollback"
              and d["outcome"] == "fired"]
        assert len(rb) == 1 and rb[0]["trace"] == t2
        # the CLI prints the rollback's causal tree from the flight dump
        dump = tmp_path / "flight_rank0.json"
        recorder().dump_to(str(dump))
        from horovod_tpu.diagnostics.__main__ import main as diag_main
        rc = diag_main(["trace", t2, "--flight", str(dump)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "rollout" in out and "rolled_back" in out
    finally:
        router.close()
        autopilot.reset()
        anomaly.reset()


# -- CLI ----------------------------------------------------------------------
def test_rollout_status_cli(tmp_path, capsys):
    from horovod_tpu.serving import Router
    from horovod_tpu.serving.__main__ import main as serving_main
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    rc = serving_main(["rollout", "status", "--store-dir",
                       str(tmp_path)])
    assert rc == 1
    assert "no status" in capsys.readouterr().out
    router = Router(lambda: [], max_attempts=2)
    try:
        ctl = RolloutController(_FakeFleet([0, 1]), router,
                                RolloutConfig(canary_pct=50),
                                store_dir=str(tmp_path))
        ctl.begin(candidate=2, incumbent=1)
    finally:
        router.close()
    rc = serving_main(["rollout", "status", "--store-dir",
                       str(tmp_path)])
    assert rc == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["state"] == "canary" and doc["candidate"] == 2
    assert doc["split"]["pct"] == 50


# -- slow: churn + chaos acceptance -------------------------------------------
def _closed_loop(router, clients, stop, errors, dim=4):
    threads = []

    def client(i):
        n = 0
        while not stop.is_set():
            n += 1
            try:
                router.submit([float(i)] + [1.0] * (dim - 1),
                              req_id=f"c{i}-{n}")
            except Exception as e:  # noqa: BLE001 - audit catches all
                errors.append(repr(e))
            time.sleep(0.002)  # pace: the audit, not the ring, is the
            #                    point — don't flood the flight ring

    for i in range(clients):
        t = threading.Thread(target=client, args=(i,), daemon=True)
        t.start()
        threads.append(t)
    return threads


@pytest.mark.slow  # tier-1 budget rule: multiprocess tests are
#                    slow-marked; the serving/chaos CI tiers run them
def test_version_split_survives_canary_churn(tmp_path):
    """Satellite: SIGKILL the canary replica mid-rollout under load —
    zero drop, an idempotent replay is answered by the same version as
    the original, and the healed replacement joins at the INCUMBENT
    version (a crash mid-canary shrinks the canary, never re-grows
    it)."""
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.serving import ReplicaFleet, Router
    from horovod_tpu.serving.replica import demo_params
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    store = ShardedCheckpointer(str(tmp_path), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    fleet = ReplicaFleet(
        size=3, dim=4, store_dir=str(tmp_path),
        extra_env={"HVD_TPU_SERVING_SWAP_POLL_S": "0.05"}).start(
        ready_timeout_s=120)
    router = Router(fleet.endpoints, hedge_ms=200, max_attempts=8)
    # a controller that only SPLITS (windows effectively disabled):
    # this test is about the mechanics under churn, not verdicts
    cfg = RolloutConfig(canary_pct=34, window_s=3600.0,
                        min_requests=10 ** 9)
    ctl = RolloutController(fleet, router, cfg, store_dir=str(tmp_path))
    stop = threading.Event()
    errors = []
    threads = _closed_loop(router, 4, stop, errors)
    try:
        time.sleep(0.5)
        store.save(2, {"params": demo_params(4, scale=2.0)}, wait=True)
        ctl.begin(candidate=2, incumbent=1)
        [canary_slot] = ctl.canary_slots
        assert _wait(lambda: fleet.versions().get(canary_slot) == 2,
                     timeout=30)
        time.sleep(0.5)  # split traffic actually flows
        # idempotent replay: a canary-bucketed id answered twice gets
        # the same version (and, replica-side, the same cached answer)
        rid = next(f"dup-{i}" for i in range(1000)
                   if zlib.crc32(f"dup-{i}".encode()) % 100 < 34)
        a = router.submit([1.0, 1.0, 1.0, 1.0], req_id=rid)
        b = router.submit([9.0, 9.0, 9.0, 9.0], req_id=rid)
        assert a["version"] == b["version"] == 2
        assert a["y"] == b["y"]
        victim = fleet._replicas[canary_slot]
        os.kill(victim.proc.pid, signal.SIGKILL)
        assert _wait(lambda: fleet.live_count() == 3, timeout=90,
                     step=0.25), "fleet did not heal"
        # the replacement joined at the INCUMBENT (heal pin), not the
        # candidate the slot was canarying
        assert _wait(lambda: fleet.versions().get(canary_slot) == 1,
                     timeout=30)
        assert fleet.pins().get(canary_slot) == 1
        time.sleep(0.5)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        router.close()
    acct = router.accounting()
    exits = list(fleet.exits)
    fleet.stop()
    store.close()
    # the zero-drop audit across the kill + heal
    assert not errors, errors[:3]
    assert acct["accepted"] == acct["answered_ok"] > 0
    assert not acct["unanswered"] and not acct["answered_twice"]
    assert acct["outcomes"].get("failed", 0) == 0
    # both versions actually took traffic under the split
    assert acct["by_version"].get(2, 0) > 0
    assert acct["by_version"].get(1, 0) > 0
    kills = [e for e in exits if e["outcome"] == "failure"]
    assert len(kills) == 1 and kills[0]["rc"] == -9


@pytest.mark.slow
def test_chaos_poisoned_commit_rolls_back_clean_commit_promotes(
        tmp_path, monkeypatch, capsys):
    """ISSUE 18 acceptance: a poisoned commit (silently-wrong math,
    served FAST — only the golden probe can see it) is canaried at
    34%, caught by the per-version comparator, and auto-rolled-back by
    the autopilot with ZERO failed requests; a clean commit then
    promotes fleet-wide.  Both transitions each resolve to a single
    trace id whose causal tree `diagnostics trace <id>` prints."""
    import horovod_tpu.autopilot as autopilot
    from horovod_tpu.checkpoint import ShardedCheckpointer
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.serving import ReplicaFleet, Router
    from horovod_tpu.serving.replica import demo_params
    from horovod_tpu.serving.rollout import (RolloutConfig,
                                             RolloutController)
    monkeypatch.setenv("HVD_TPU_AUTOPILOT", "act")
    autopilot.reset()
    anomaly.reset()
    golden = tmp_path / "golden.json"
    golden.write_text(json.dumps(
        {"requests": [{"x": [4.0, 0.0, 0.0, 0.0]}]}))
    store_dir = tmp_path / "store"
    store = ShardedCheckpointer(str(store_dir), rank=0, world_size=1)
    store.save(1, {"params": demo_params(4, scale=1.0)}, wait=True)
    fleet = ReplicaFleet(
        size=3, dim=4, store_dir=str(store_dir),
        extra_env={"HVD_TPU_SERVING_SWAP_POLL_S": "0.05"}).start(
        ready_timeout_s=120)
    router = Router(fleet.endpoints, hedge_ms=200, max_attempts=8)
    cfg = RolloutConfig(canary_pct=34, expand_pct=50, window_s=0.3,
                        min_requests=10, golden_path=str(golden),
                        golden_max=0.5)
    ctl = RolloutController(fleet, router, cfg, store_dir=str(store_dir)
                            ).register_autopilot_hooks()
    stop = threading.Event()
    errors = []
    threads = _closed_loop(router, 4, stop, errors)
    dump_rollback = tmp_path / "flight_rollback_rank0.json"
    dump_promote = tmp_path / "flight_promote_rank0.json"
    try:
        time.sleep(0.5)
        # ---- the poisoned commit: y = 50*mean(x) instead of mean(x),
        # served exactly as fast as the incumbent
        store.save(2, {"params": demo_params(4, scale=50.0)}, wait=True)
        ctl.begin(candidate=2, incumbent=1)
        [canary_slot] = ctl.canary_slots
        poisoned_trace = ctl.trace.trace_id
        assert _wait(lambda: fleet.versions().get(canary_slot) == 2,
                     timeout=30)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline \
                and ctl.state != "rolled_back":
            ctl.evaluate()
            time.sleep(0.1)
        assert ctl.state == "rolled_back", ctl.status()
        # every replica repinned to the incumbent, although the
        # poisoned candidate is still the newest commit in the store
        assert _wait(lambda: all(
            v == 1 for v in fleet.versions().values()), timeout=30)
        assert all(v == 1 for v in fleet.pins().values())
        recorder().dump_to(str(dump_rollback))  # before ring wraps
        time.sleep(0.5)  # post-rollback traffic, all on the incumbent
        # ---- the clean commit promotes canary -> 50% -> fleet-wide
        store.save(3, {"params": demo_params(4, scale=1.0)}, wait=True)
        ctl.begin(candidate=3, incumbent=1)
        clean_trace = ctl.trace.trace_id
        assert clean_trace != poisoned_trace
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline and ctl.state != "promoted":
            ctl.evaluate()
            time.sleep(0.2)
        assert ctl.state == "promoted", ctl.status()
        assert _wait(lambda: all(
            v == 3 for v in fleet.versions().values()), timeout=30)
        recorder().dump_to(str(dump_promote))
        time.sleep(0.5)  # post-promotion traffic on the new version
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=15)
        router.close()
    acct = router.accounting()
    exits = list(fleet.exits)
    fleet.stop()
    store.close()
    # ZERO failed requests through BOTH transitions: the request-log
    # audit proves every accepted request was answered exactly once
    assert not errors, errors[:3]
    assert acct["accepted"] == acct["answered_ok"] > 0
    assert not acct["unanswered"] and not acct["answered_twice"]
    assert acct["outcomes"].get("failed", 0) == 0
    assert not [e for e in exits if e["outcome"] == "failure"], exits
    # the canary arm actually took candidate traffic before rollback
    assert acct["by_version"].get(2, 0) > 0
    assert acct["by_version"].get(3, 0) > 0
    # the AUTOPILOT (not the test) drove both transitions, and each
    # decision continues its rollout's trace
    rb = [d for d in autopilot.recent_decisions()
          if d["policy"] == "rollout-rollback"
          and d["outcome"] == "fired"]
    pr = [d for d in autopilot.recent_decisions()
          if d["policy"] == "rollout-promote"
          and d["outcome"] == "fired"]
    assert len(rb) == 1 and rb[0]["trace"] == poisoned_trace
    assert len(pr) == 2
    assert all(d["trace"] == clean_trace for d in pr)
    # each transition is ONE causal tree the CLI prints end to end
    from horovod_tpu.diagnostics.__main__ import main as diag_main
    for tid, dump, marker in (
            (poisoned_trace, dump_rollback, "rolled_back"),
            (clean_trace, dump_promote, "promoted")):
        rc = diag_main(["trace", tid, "--flight", str(dump)])
        out = capsys.readouterr().out
        assert rc == 0, out
        assert "rollout" in out and marker in out
    autopilot.reset()
    anomaly.reset()
