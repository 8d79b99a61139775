"""Serving request ledger (ISSUE 19, docs/OBSERVABILITY.md "Serving
request ledger").

Fast battery: the shared nearest-rank quantile (one implementation for
the SLO plane, the rollout comparator and the ledger's windows —
p50/p99 semantics pinned here), close_books/residual/dominant-stage
units, the bounded tail-exemplar ring + ``/debug/exemplars`` +
autopsy dump, WindowBooks window accounting, burn-rate SLO hysteresis
(one finding per episode, re-arm under 1x fast burn), the stale-gauge
idle-roll rule (stage-share gauges ZERO on an idle window, never
frozen), batch-size buckets widening past 128 with the slot count,
the ttft_drift / queue_growth detectors, books closing end-to-end
through a real router+replica pair (aggregate residual < 10%, exemplar
trace ids resolving to spans), generate-plane stage coverage including
the swap_pause bracket and the slot_wait-vs-page_wait discrimination,
and the chaos acceptance pair: injected ``serving.kv`` starvation must
surface as a ``kv_thrash`` finding naming ``page_wait``, and a clean
control run of the same length must produce none.
"""

import json
import os
import threading
import time

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _clean():
    from horovod_tpu import chaos
    from horovod_tpu.serving import ledger
    chaos.uninstall()
    ledger.reset()
    yield
    chaos.uninstall()
    ledger.reset()


# -- the one quantile ---------------------------------------------------------
def test_quantile_nearest_rank_semantics_pinned():
    """THE shared quantile: nearest-rank over a sorted sequence,
    fraction in [0, 1].  p50 of 1..100 is 51 (index round(.5*99)=50),
    p99 is 99 (index 98) — pinned so the SLO plane and the comparator
    can never drift apart on what "p99" means."""
    from horovod_tpu.serving.ledger import quantile
    assert quantile([], 0.99) == 0.0
    assert quantile([5.0], 0.5) == 5.0
    vals = [float(i) for i in range(1, 101)]
    assert quantile(vals, 0.0) == 1.0
    assert quantile(vals, 0.50) == 51.0
    assert quantile(vals, 0.99) == 99.0
    assert quantile(vals, 1.0) == 100.0
    # two points: p99 is the max, p50 the second (round half up)
    assert quantile([1.0, 9.0], 0.99) == 9.0


def test_quantile_is_shared_across_all_three_call_sites():
    """serving.metrics.percentile and the rollout comparator's
    percentile must BE ledger.quantile (not copies): with the ledger's
    own windows, the three places a p99 is computed."""
    from horovod_tpu.serving import ledger
    from horovod_tpu.serving import metrics as smetrics
    from horovod_tpu.serving.rollout import comparator
    assert smetrics.percentile is ledger.quantile
    assert comparator.percentile is ledger.quantile


# -- close_books units --------------------------------------------------------
def test_close_books_names_the_residual():
    from horovod_tpu.serving.ledger import (close_books, dominant_stage,
                                            residual_fraction)
    stages = close_books(1.0, {"queue": 0.2, "forward": 0.5})
    assert stages["unattributed"] == pytest.approx(0.3)
    assert sum(stages.values()) == pytest.approx(1.0)
    # a clock race (negative stage) is clamped, never negative time;
    # over-attribution clamps the residual at zero
    stages = close_books(0.4, {"forward": 0.5, "queue": -0.1})
    assert stages["queue"] == 0.0 and stages["unattributed"] == 0.0
    # a caller-supplied residual is recomputed, not trusted
    stages = close_books(1.0, {"forward": 0.9, "unattributed": 9.0})
    assert stages["unattributed"] == pytest.approx(0.1)
    assert residual_fraction(1.0, {"forward": 0.9}) == pytest.approx(0.1)
    assert residual_fraction(0.0, {}) == 0.0
    assert dominant_stage({"queue": 0.2, "forward": 0.5}) == "forward"
    # the residual can never be "dominant" — it is the absence of an
    # answer, not an answer
    assert dominant_stage({"unattributed": 9.0}) is None
    assert dominant_stage({}) is None


def test_stage_catalog_is_closed_and_ordered():
    from horovod_tpu.serving import ledger
    assert ledger.STAGES[-1] == ledger.RESIDUAL
    assert set(ledger.STAGES) == (set(ledger.ROUTER_STAGES)
                                  | set(ledger.REPLICA_STAGES)
                                  | set(ledger.GENERATE_STAGES)
                                  | {ledger.RESIDUAL})
    assert len(set(ledger.STAGES)) == len(ledger.STAGES)


# -- exemplar ring ------------------------------------------------------------
def test_exemplar_ring_is_bounded_oldest_evicted():
    from horovod_tpu.serving.ledger import ExemplarRing
    ring = ExemplarRing(capacity=4)
    for i in range(10):
        ring.add({"e2e_s": float(i), "req_id": f"r{i}"})
    assert len(ring) == 4
    held = {e["req_id"] for e in ring.snapshot()}
    assert held == {"r6", "r7", "r8", "r9"}  # oldest evicted first
    assert [e["req_id"] for e in ring.worst(2)] == ["r9", "r8"]
    ring.clear()
    assert len(ring) == 0


def test_exemplars_reach_debug_endpoint_and_autopsy(tmp_path, monkeypatch):
    """The process-wide ring is what ``/debug/exemplars`` serves and
    what the autopsy bundle dumps as ``exemplars_rank<r>.json``."""
    import urllib.request
    from horovod_tpu.diagnostics.autopsy import write_autopsy
    from horovod_tpu.metrics.exporter import MetricsExporter
    from horovod_tpu.serving.ledger import default_ring
    default_ring().add({"e2e_s": 0.5, "trace": "t-123",
                        "stages": {"forward": 0.4, "unattributed": 0.1},
                        "dominant_stage": "forward"})
    exp = MetricsExporter(port=0)
    exp.start()
    try:
        url = f"http://127.0.0.1:{exp.port}/debug/exemplars"
        with urllib.request.urlopen(url, timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["exemplars"][0]["trace"] == "t-123"
        assert doc["exemplars"][0]["dominant_stage"] == "forward"
    finally:
        exp.stop()
    bundle = write_autopsy(out_dir=str(tmp_path), reason="test")
    dumped = json.load(open(os.path.join(bundle, "exemplars_rank0.json")))
    assert dumped["exemplars"][0]["trace"] == "t-123"
    summary = json.load(open(os.path.join(bundle, "summary_rank0.json")))
    assert summary["exemplars"] == 1


# -- window books -------------------------------------------------------------
def test_window_books_sums_shares_ttft_and_worst():
    from horovod_tpu.serving.ledger import WindowBooks
    b = WindowBooks(exemplars_per_window=2)
    b.add(1.0, {"queue": 0.3, "forward": 0.6}, trace="fast",
          req_id="a", ttft_s=0.01)
    b.add(3.0, {"queue": 2.4, "forward": 0.3}, trace="slow",
          req_id="b", version=7, ttft_s=0.09)
    b.add(0.5, {"forward": 0.5}, trace="tiny", req_id="c", ttft_s=0.01)
    doc, worst = b.close()
    assert doc["stages"]["queue"] == pytest.approx(2.7)
    assert doc["stages"]["forward"] == pytest.approx(1.4)
    assert doc["unattributed_s"] == pytest.approx(0.4)
    assert doc["unattributed_frac"] == pytest.approx(0.4 / 4.5, abs=1e-3)
    assert sum(doc["stage_shares"].values()) == pytest.approx(1.0,
                                                              abs=1e-3)
    assert doc["dominant_stage"] == "queue"
    assert doc["ttft_p50_s"] == pytest.approx(0.01)
    assert doc["ttft_p99_s"] == pytest.approx(0.09)
    # exemplars: bounded per window, slowest first, full breakdown
    assert [e["req_id"] for e in worst] == ["b", "a"]
    assert worst[0]["trace"] == "slow" and worst[0]["version"] == 7
    assert worst[0]["dominant_stage"] == "queue"
    assert doc["worst_trace"] == "slow"
    # close() resets: an idle window reads zero, not stale
    doc2, worst2 = b.close()
    assert doc2["stages"] == {} and doc2["stage_shares"] == {}
    assert doc2["unattributed_frac"] == 0.0
    assert doc2["dominant_stage"] is None and worst2 == []


def test_stage_share_gauges_zero_on_idle_roll():
    """Stale-gauge regression (satellite): after a busy window the
    share gauges carry the breakdown; an IDLE window must publish 0.0
    for every canonical stage — a frozen share gauge would keep
    blaming a stage that stopped existing."""
    from horovod_tpu.metrics.registry import default_registry
    from horovod_tpu.serving import ledger
    from horovod_tpu.serving.metrics import LatencyWindow
    w = LatencyWindow(window_s=3600.0)
    w.observe(1.0, stages={"queue": 0.7, "forward": 0.29})
    doc = w.maybe_roll(force=True)
    assert doc["requests"] == 1 and doc["dominant_stage"] == "queue"
    reg = default_registry()
    g = reg.get("hvd_serving_stage_share", labels={"stage": "queue"})
    assert g is not None and g.value == pytest.approx(0.7, abs=1e-3)
    idle = w.maybe_roll(force=True)
    assert idle["requests"] == 0
    for stage in ledger.STAGES:
        g = reg.get("hvd_serving_stage_share", labels={"stage": stage})
        assert g is not None and g.value == 0.0, stage


# -- burn-rate SLO ------------------------------------------------------------
def test_burn_rate_one_finding_per_episode_and_rearm(monkeypatch):
    """Hysteresis: the episode opens once (fast AND slow spans over
    threshold, window itself over budget), stays silent while active,
    and re-arms only after the fast span burns under 1.0."""
    monkeypatch.setenv("HVD_TPU_ANOMALY", "0")  # unit-test the class
    from horovod_tpu.serving.ledger import BurnRateSlo
    slo = BurnRateSlo(slo_p99_s=0.01, budget=0.01, fast_windows=2,
                      slow_windows=4, threshold=10.0)
    assert slo.enabled and slo.is_bad(0.02) and not slo.is_bad(0.005)
    bad, good = (10, 10), (10, 0)
    # one breaching window is not an episode: fast span not yet filled
    assert slo.observe_window(*bad) is None
    f = slo.observe_window(*bad)
    assert f is not None and f["burn_fast"] == pytest.approx(100.0)
    # still breaching: same episode, NO second finding
    assert slo.observe_window(*bad) is None
    # one good window: fast burn 50 >= 1.0, still armed-off
    assert slo.observe_window(*good) is None and slo.active
    # second good window: fast burn 0 < 1.0 -> re-arm
    assert slo.observe_window(*good) is None and not slo.active
    # fresh breach after recovery opens a NEW episode (slow span still
    # carries the old badness: 20/40 bad = burn 50 >= threshold)
    f2 = slo.observe_window(*bad)
    assert f2 is not None
    # a recovered window can never OPEN an episode, whatever the spans
    assert slo.observe_window(*good) is None


def test_burn_rate_finding_names_the_dominant_stage(monkeypatch):
    monkeypatch.setenv("HVD_TPU_ANOMALY", "0")
    from horovod_tpu.serving.ledger import BurnRateSlo
    slo = BurnRateSlo(slo_p99_s=0.01, budget=0.01, fast_windows=1,
                      slow_windows=2, threshold=2.0)
    doc = {"p99_s": 0.5, "qps": 10.0, "dominant_stage": "page_wait",
           "stage_shares": {"page_wait": 0.8, "decode": 0.2},
           "worst_trace": "t-9"}
    f = slo.observe_window(10, 5, doc)
    assert f["dominant_stage"] == "page_wait"
    assert f["dominant_share"] == pytest.approx(0.8)
    assert f["worst_trace"] == "t-9"
    # disabled SLO (no HVD_TPU_SERVING_SLO_P99_MS) never fires
    off = BurnRateSlo(slo_p99_s=0.0)
    assert not off.enabled and off.observe_window(10, 10) is None


# -- batch-size buckets -------------------------------------------------------
def test_batch_size_buckets_widen_with_slot_count(monkeypatch):
    """Satellite: the old fixed top of 128 dumped every big decode
    batch into +Inf; buckets now derive from the configured slot
    count, power-of-two, and never shrink below the old top."""
    from horovod_tpu.serving.metrics import batch_size_buckets
    b = batch_size_buckets(top=512)
    assert b[-1] >= 512 and b[0] == 1
    assert all(b[i + 1] == 2 * b[i] for i in range(len(b) - 1))
    # back-compat floor: a tiny config still covers the old 128 top
    assert batch_size_buckets(top=8)[-1] >= 128
    monkeypatch.setenv("HVD_TPU_GEN_SLOTS", "300")
    assert batch_size_buckets()[-1] >= 300


# -- serving-window anomaly detectors -----------------------------------------
def _mk_engine(monkeypatch, **env):
    from horovod_tpu.metrics.anomaly import AnomalyEngine
    for k, v in env.items():
        monkeypatch.setenv(f"HVD_TPU_{k}", str(v))
    return AnomalyEngine()


def test_ttft_drift_detector_flags_sustained_drift(monkeypatch):
    eng = _mk_engine(monkeypatch, ANOMALY_WARMUP=2,
                     ANOMALY_CONSECUTIVE=1)
    base = {"requests": 5, "ttft_p50_s": 0.01}
    for _ in range(4):
        assert eng.observe_serving(dict(base)) == []
    out = eng.observe_serving({"requests": 5, "ttft_p50_s": 0.08,
                               "worst_trace": "t-slow"})
    assert len(out) == 1 and out[0]["kind"] == "ttft_drift"
    assert out[0]["worst_trace"] == "t-slow"
    # an idle window carries no ttft signal and no false positive
    assert eng.observe_serving({"requests": 0}) == []


def test_queue_growth_detector_streak_and_idle_reset(monkeypatch):
    eng = _mk_engine(monkeypatch, SERVING_STAGE_WINDOWS=2)
    hot = {"requests": 10,
           "stage_shares": {"queue": 0.4, "batch_wait": 0.3}}
    assert eng.observe_serving(dict(hot)) == []  # streak 1 of 2
    out = eng.observe_serving(dict(hot))
    assert len(out) == 1 and out[0]["kind"] == "queue_growth"
    assert out[0]["dominant_stage"] == "queue"
    # hysteresis: still hot -> same episode, silent
    assert eng.observe_serving(dict(hot)) == []
    # an idle window resets the episode AND the streak: the condition
    # did not survive the traffic that caused it
    assert eng.observe_serving({"requests": 0}) == []
    assert eng.observe_serving(dict(hot)) == []  # streak back to 1
    assert len(eng.observe_serving(dict(hot))) == 1


# -- books close end to end through router + replica --------------------------
def test_books_close_through_router_and_replica():
    """Acceptance: real traffic through a real router+replica pair —
    every response doc carries a closed stage ledger, the aggregate
    residual stays under the 10% gate, and the window's tail exemplars
    carry trace ids that resolve to the request's spans."""
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.serving import ReplicaServer, Router, ledger
    from horovod_tpu.tracing.reader import spans_from_events
    replica = ReplicaServer(dim=4, replica_id="lg0").start()
    router = Router([("127.0.0.1", replica.port)], hedge_ms=0)
    try:
        docs = [router.submit([float(i), 0, 0, 0],
                              req_id=f"books-{i}") for i in range(12)]
        # roll BEFORE close (close force-rolls the window as a flush)
        win = router.window.maybe_roll(force=True)
    finally:
        router.close()
        replica.stop()
    total = unattr = 0.0
    for doc in docs:
        stages = doc["stages"]
        assert "unattributed" in stages
        assert stages.get("forward", 0) > 0  # replica plane attributed
        assert stages.get("dispatch", 0) > 0  # router plane attributed
        assert set(stages) <= set(ledger.STAGES)
        assert all(v >= 0 for v in stages.values())
        total += sum(stages.values())
        unattr += stages["unattributed"]
    assert total > 0 and unattr / total < 0.10, (unattr, total)
    assert win["requests"] == 12
    assert win["unattributed_frac"] < 0.10
    assert win["dominant_stage"] in ledger.STAGES[:-1]
    assert sum(win["stage_shares"].values()) == pytest.approx(1.0,
                                                              abs=0.01)
    # the worst requests landed in the ring with resolvable traces
    worst = ledger.default_ring().worst(1)
    assert worst and worst[0].get("trace")
    spans, _ = spans_from_events(recorder().events(),
                                 trace_id=worst[0]["trace"])
    names = [s["name"] for s in spans]
    assert "request" in names and "serve" in names
    req_span = [s for s in spans if s["name"] == "request"][0]
    assert any(k.startswith("stage_") for k in req_span["attrs"])


# -- generate plane: stage coverage -------------------------------------------
def _gen_engine(**over):
    from horovod_tpu.serving.generate import (GenerateEngine,
                                              demo_gen_setup)
    params, cfg = demo_gen_setup()
    kw = dict(n_slots=2, page_bytes=4096, prefill_chunk=8)
    kw.update(over)
    return GenerateEngine(params, cfg, **kw)


def test_generate_stages_cover_swap_pause():
    """A hot weight swap mid-generation: the pause the swap bracket
    imposes on live sequences lands in the ``swap_pause`` stage, next
    to real prefill/decode time — never in the residual."""
    from horovod_tpu.serving.generate.scheduler import DONE
    eng = _gen_engine()
    req = eng.submit("swap-1", [3, 5, 7], max_new=6)
    n = 0
    while req.decode_steps < 1:  # run prefill + first decode step
        eng.step_once()
        n += 1
        assert n < 10_000, "engine failed to reach decode"
    eng.begin_swap()
    t = threading.Timer(0.08, eng.end_swap)
    t.start()
    eng.step_once()  # blocks at the swap gate; pause is charged
    t.join()
    while req.state != DONE:
        eng.step_once()
        n += 1
        assert n < 10_000, "engine failed to converge"
    result = req.pending.wait(timeout=10.0)
    stages = result["stages"]
    assert stages["swap_pause"] >= 0.05
    assert stages["prefill"] > 0 and stages["decode"] > 0
    assert set(stages) == {"slot_wait", "page_wait", "prefill",
                           "decode", "swap_pause"}


def _sched(n_slots=2, pool_pages=4, page_tokens=4):
    from horovod_tpu.serving.generate.pages import (PagePool,
                                                    plan_kv_pages)
    from horovod_tpu.serving.generate.scheduler import SlotScheduler
    plan = plan_kv_pages(1, 8, np.float32, slots=pool_pages,
                         max_ctx=page_tokens,
                         page_bytes=64 * page_tokens)
    pool = PagePool(plan)
    return SlotScheduler(n_slots, pool, 4,
                         max_ctx=pool_pages * page_tokens), pool


def test_scheduler_discriminates_slot_wait_from_page_wait():
    """The ledger must answer "waiting for WHAT": a full slot array
    charges slot_wait, an exhausted page pool charges page_wait — the
    exact discrimination kv_thrash runs on."""
    from horovod_tpu.serving.generate.scheduler import GenRequest
    # slots are the bottleneck: 1 slot, plenty of pages
    sched, _pool = _sched(n_slots=1, pool_pages=4)
    first = GenRequest("first", [1] * 4, 4)   # admits into the slot
    queued = GenRequest("queued", [1] * 4, 4)
    sched.add_waiting(first)
    sched.add_waiting(queued)
    assert [r.id for r in sched.admit()] == ["first"]
    time.sleep(0.02)
    sched.admit()
    assert queued.slot_wait_s > 0 and queued.page_wait_s == 0.0
    # pages are the bottleneck: free slots, pool too small for the head
    sched2, _pool2 = _sched(n_slots=2, pool_pages=1)
    big = GenRequest("big", [1] * 4, 4)  # worst case 8 tokens, 2 pages
    sched2.add_waiting(big)
    assert sched2.admit() == []
    time.sleep(0.02)
    sched2.admit()
    # queue transit BEFORE the first classification charges slot_wait
    # (microseconds); the real wait after it is all page_wait
    assert big.page_wait_s > 0.015 and big.slot_wait_s < 0.001


# -- chaos acceptance: KV starvation -> kv_thrash -----------------------------
def _starved_stage_docs(monkeypatch, starve: bool):
    """Run real admissions through the real serving.kv seam (chaos
    starving the first page grants when ``starve``); returns the
    per-request closed stage dicts."""
    from horovod_tpu import chaos
    from horovod_tpu.serving.generate.scheduler import GenRequest
    if starve:
        plan = {"faults": [{"seam": "serving.kv", "kind": "starve",
                            "start": 0, "stop": 3}]}
        monkeypatch.setenv("HVD_TPU_FAULT_PLAN", json.dumps(plan))
        chaos.install(rank=0)
    sched, _pool = _sched(n_slots=2, pool_pages=4)
    reqs = [GenRequest(f"g{i}", [1] * 4, 4) for i in range(2)]
    for r in reqs:
        sched.add_waiting(r)
    deadline = time.monotonic() + 10.0
    while sched.waiting_count():
        sched.admit()
        time.sleep(0.01)
        assert time.monotonic() < deadline, "admission never unblocked"
    chaos.uninstall()
    return [r.stages() for r in reqs]


def test_chaos_kv_starvation_flags_kv_thrash(monkeypatch):
    """Acceptance pair: injected KV starvation (the serving.kv seam
    refusing page grants) piles request time into page_wait; after the
    detector's window streak the anomaly engine reports ``kv_thrash``
    naming ``page_wait`` as the dominant stage.  A clean control run of
    the same length produces ZERO serving findings."""
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.serving.metrics import LatencyWindow

    def run(starve: bool):
        monkeypatch.setenv("HVD_TPU_SERVING_STAGE_WINDOWS", "2")
        anomaly.reset()
        stage_docs = _starved_stage_docs(monkeypatch, starve)
        w = LatencyWindow(window_s=3600.0)
        findings = []
        for _ in range(2):  # the detector needs 2 consecutive windows
            for stages in stage_docs:
                w.observe(sum(stages.values()), stages=stages)
            w.maybe_roll(force=True)
            findings = [f for f in anomaly.recent_findings()
                        if f["kind"] in ("kv_thrash", "queue_growth",
                                         "ttft_drift")]
        anomaly.reset()
        return stage_docs, findings

    stage_docs, findings = run(starve=True)
    # the seam starved 3 grants -> the head piled up real page_wait
    assert all(s["page_wait"] > 0 for s in stage_docs)
    assert len(findings) == 1, findings
    assert findings[0]["kind"] == "kv_thrash"
    assert findings[0]["dominant_stage"] == "page_wait"
    assert findings[0]["stage_share"] > 0.25
    # clean control, same traffic shape: no starvation, no finding
    stage_docs, findings = run(starve=False)
    assert findings == [], findings
