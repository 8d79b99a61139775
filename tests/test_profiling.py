"""Deep-profiling subsystem tests (ISSUE 9, docs/OBSERVABILITY.md
"Deep profiling" / "Compile & memory observability" / "Re-mesh
timeline"):

* ProfileManager — step-windowed ``jax.profiler`` captures on CPU
  (non-empty bytes), size rotation, rate limiting, aborted-capture
  flush;
* recompile_storm — the detector unit battery (storm flagged with the
  offending function named; a shape-stable run stays clean) plus the
  real-jax integration;
* HBM gauges — sampling with a fake ``memory_stats`` (CPU reports
  none), min-merge across ranks, the hbm_growth slow-leak detector;
* re-mesh timeline — episode phases land as
  ``hvd_remesh_seconds{phase}``, flight spans and a history point;
* the END-TO-END ACCEPTANCE: a chaos-injected slow-step window on the
  8-device CPU mesh makes the anomaly engine fire and the
  ProfileManager autonomously write a non-empty bounded capture, with
  the ``profile_captured`` flight event and the capture path in the
  finding + autopsy summary — while a clean run of the same length
  captures nothing.
"""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.metrics.registry import Registry
from horovod_tpu.profiling import compile_watch, memory
from horovod_tpu.profiling.manager import ProfileManager


@pytest.fixture(autouse=True)
def _fresh(tmp_path, monkeypatch):
    """Every test gets its own profile dir and fresh singletons."""
    import horovod_tpu.profiling as profiling
    from horovod_tpu.diagnostics.flight_recorder import recorder
    from horovod_tpu.elastic import remesh
    from horovod_tpu.metrics import anomaly, timeseries
    monkeypatch.setenv("HVD_TPU_PROFILE_DIR", str(tmp_path / "prof"))
    profiling.reset()
    anomaly.reset()
    timeseries.reset()
    remesh.reset()
    recorder().clear()
    yield
    profiling.reset()
    anomaly.reset()
    timeseries.reset()
    remesh.reset()
    recorder().clear()


@jax.jit
def _work(x):
    return (x @ x).sum()


def _drive(mgr, steps, work=True):
    x = jnp.ones((32, 32))
    for i in range(1, steps + 1):
        mgr.on_step_begin(i)
        if work:
            _work(x).block_until_ready()
        mgr.on_step_end(i)


def _flight(kind):
    from horovod_tpu.diagnostics.flight_recorder import recorder
    return [e for e in recorder().events() if e["kind"] == kind]


# -- ProfileManager ----------------------------------------------------------

def test_capture_window_is_step_bounded_and_nonempty(tmp_path):
    mgr = ProfileManager(registry=Registry())
    info = mgr.request_capture(steps=2, reason="unit")
    assert info is not None and info["steps"] == 2
    _drive(mgr, 5)
    caps = mgr.recent_captures()
    assert len(caps) == 1, caps
    c = caps[0]
    assert c["steps"] == 2
    assert c["first_step"] == 1 and c["last_step"] == 2
    assert c["bytes"] > 0, "capture must contain real trace bytes"
    assert os.path.isdir(c["path"])
    evs = _flight("profile_captured")
    assert evs and evs[0]["path"] == c["path"]


def test_second_request_refused_while_pending_or_active():
    mgr = ProfileManager(registry=Registry())
    assert mgr.request_capture(steps=3) is not None
    assert mgr.request_capture(steps=3) is None  # pending
    mgr.on_step_begin(1)
    assert mgr.request_capture(steps=3) is None  # active
    assert mgr.dropped_requests == 2
    _drive(mgr, 3)
    # window closed: a new request is accepted again
    assert mgr.request_capture(steps=1) is not None


def test_request_during_trace_start_window_refused(monkeypatch):
    """The slot is claimed atomically with consuming the pending
    request: a request arriving while on_step_begin is still inside
    jax.profiler.start_trace must be refused, not accepted-then-lost."""
    mgr = ProfileManager(registry=Registry())
    seen = {}

    def _racing_start(path):
        # simulates an exporter/anomaly thread hitting the gap
        seen["racer"] = mgr.request_capture(steps=1, reason="racer")

    monkeypatch.setattr(mgr, "_start_trace", _racing_start)
    monkeypatch.setattr(mgr, "_stop_trace", lambda: None)
    assert mgr.request_capture(steps=1) is not None
    _drive(mgr, 2, work=False)
    assert seen["racer"] is None
    assert len(mgr.recent_captures()) == 1


def test_failed_trace_start_releases_slot(monkeypatch):
    mgr = ProfileManager(registry=Registry())

    def _broken_start(path):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(mgr, "_start_trace", _broken_start)
    assert mgr.request_capture(steps=1) is not None
    mgr.on_step_begin(1)
    mgr.on_step_end(1)
    assert mgr.status()["active"] is None
    assert mgr.recent_captures() == []
    # the slot is free again for a working capture
    monkeypatch.undo()
    assert mgr.request_capture(steps=1) is not None
    _drive(mgr, 2)
    assert len(mgr.recent_captures()) == 1


def test_finalize_racing_trace_start_cancels_cleanly(monkeypatch):
    """finalize_open_capture (autopsy/watchdog thread) landing between
    the claim and the trace start must not orphan a running trace: the
    unstarted record is dropped with nothing to flush, and the training
    thread closes the trace it just opened."""
    mgr = ProfileManager(registry=Registry())
    stopped = {"n": 0}

    def _racing_start(path):
        # the autopsy thread finalizes while start_trace is in flight
        assert mgr.finalize_open_capture("autopsy") is None

    monkeypatch.setattr(mgr, "_start_trace", _racing_start)
    monkeypatch.setattr(
        mgr, "_stop_trace",
        lambda: stopped.__setitem__("n", stopped["n"] + 1))
    assert mgr.request_capture(steps=1) is not None
    mgr.on_step_begin(1)
    mgr.on_step_end(1)
    assert stopped["n"] == 1  # the just-opened trace was closed
    assert mgr.recent_captures() == []
    assert mgr.status()["active"] is None
    # the manager still works afterwards
    monkeypatch.undo()
    assert mgr.request_capture(steps=1) is not None
    _drive(mgr, 2)
    assert len(mgr.recent_captures()) == 1


def test_failed_start_does_not_burn_anomaly_cooldown(monkeypatch):
    """The cooldown is charged when the trace STARTS: a capture that
    failed to open must leave the episode's window available."""
    mgr = ProfileManager(registry=Registry())
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "3600")

    def _broken_start(path):
        raise RuntimeError("profiler busy")

    monkeypatch.setattr(mgr, "_start_trace", _broken_start)
    assert mgr.request_capture(steps=1, rate_limited=True) is not None
    mgr.on_step_begin(1)
    mgr.on_step_end(1)
    assert mgr.recent_captures() == []
    # the failed start left the cooldown unburned: re-arm works now
    monkeypatch.undo()
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "3600")
    assert mgr.request_capture(steps=1, rate_limited=True) is not None
    _drive(mgr, 2)
    assert len(mgr.recent_captures()) == 1
    # ...and the successful start DID charge it
    assert mgr.request_capture(steps=1, rate_limited=True) is None


def test_anomaly_trigger_rate_limited(monkeypatch):
    mgr = ProfileManager(registry=Registry())
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "3600")
    assert mgr.request_capture(steps=1, rate_limited=True) is not None
    _drive(mgr, 2)
    # inside the cooldown: the anomaly path is refused...
    assert mgr.request_capture(steps=1, rate_limited=True) is None
    # ...while an explicit on-demand request still goes through
    assert mgr.request_capture(steps=1, reason="debug") is not None
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "0")
    _drive(mgr, 2)
    assert mgr.request_capture(steps=1, rate_limited=True) is not None


def test_retention_rotates_oldest_capture(tmp_path, monkeypatch):
    mgr = ProfileManager(registry=Registry())
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "0")
    mgr.request_capture(steps=1, reason="first")
    _drive(mgr, 2)
    first = mgr.recent_captures()[0]["path"]
    # budget below one capture's size: the next capture evicts the first
    monkeypatch.setenv("HVD_TPU_PROFILE_MAX_BYTES", "1")
    mgr.request_capture(steps=1, reason="second")
    _drive(mgr, 2)
    caps = mgr.recent_captures()
    assert len(caps) == 2
    second = caps[-1]["path"]
    assert not os.path.exists(first), "oldest capture must rotate out"
    assert os.path.isdir(second), "newest capture is never deleted"


def test_finalize_open_capture_flushes_partial_window():
    mgr = ProfileManager(registry=Registry())
    mgr.request_capture(steps=100, reason="will_hang")
    mgr.on_step_begin(1)
    _work(jnp.ones((16, 16))).block_until_ready()
    rec = mgr.finalize_open_capture(reason="autopsy")
    assert rec is not None and rec["aborted"] == "autopsy"
    assert rec["bytes"] > 0
    assert mgr.recent_captures()[-1]["path"] == rec["path"]
    assert mgr.finalize_open_capture() is None  # idempotent


# -- recompile storm ---------------------------------------------------------

def _fresh_engine(monkeypatch):
    from horovod_tpu.metrics import anomaly
    anomaly.reset()
    return anomaly


def test_recompile_storm_unit_battery(monkeypatch):
    """Direct detector battery: same function recompiling past warmup
    flags (function named, re-flags only after another storm's worth),
    while many distinct functions compiling once stay clean."""
    anomaly = _fresh_engine(monkeypatch)
    monkeypatch.setenv("HVD_TPU_RECOMPILE_WARMUP", "2")
    monkeypatch.setenv("HVD_TPU_RECOMPILE_STORM", "3")
    compile_watch.reset_counts()
    # shape-stable world: 50 distinct functions, one compile each
    for i in range(50):
        compile_watch._note_compiling(f"stable_fn_{i}")
    assert anomaly.recent_findings() == []
    # one function recompiles: warmup 2 + storm 3 -> flag at the 5th
    for _ in range(4):
        compile_watch._note_compiling("drifting_step")
    assert anomaly.recent_findings() == []
    compile_watch._note_compiling("drifting_step")
    findings = anomaly.recent_findings()
    assert len(findings) == 1, findings
    f = findings[0]
    assert f["kind"] == "recompile_storm"
    assert f["function"] == "drifting_step"
    assert f["compiles"] == 5
    # hysteresis: the next 2 recompiles stay quiet, the 3rd re-flags
    compile_watch._note_compiling("drifting_step")
    compile_watch._note_compiling("drifting_step")
    assert len(anomaly.recent_findings()) == 1
    compile_watch._note_compiling("drifting_step")
    assert len(anomaly.recent_findings()) == 2


def test_recompile_storm_real_jax_names_function(monkeypatch):
    anomaly = _fresh_engine(monkeypatch)
    monkeypatch.setenv("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    compile_watch.ensure_installed()
    compile_watch.reset_counts()

    @jax.jit
    def drifting_train_step(x):
        return x * 2

    for n in range(2, 10):  # shape drift: the classic silent killer
        drifting_train_step(jnp.ones(n))
    findings = anomaly.recent_findings()
    assert any(f["kind"] == "recompile_storm"
               and f["function"] == "drifting_train_step"
               for f in findings), findings
    # the flight event names it too
    evs = _flight("anomaly")
    assert any(e.get("detector") == "recompile_storm"
               and e.get("function") == "drifting_train_step"
               for e in evs), evs


def test_shape_stable_real_jax_run_is_clean(monkeypatch):
    anomaly = _fresh_engine(monkeypatch)
    compile_watch.ensure_installed()
    compile_watch.reset_counts()

    @jax.jit
    def stable_step(x):
        return x + 1

    for _ in range(30):
        stable_step(jnp.ones(8))
    assert not [f for f in anomaly.recent_findings()
                if f["kind"] == "recompile_storm"]


def test_compile_metrics_registered(monkeypatch):
    compile_watch.ensure_installed()
    compile_watch.reset_counts()

    @jax.jit
    def counted_fn(x):
        return x - 1

    counted_fn(jnp.ones(5))
    from horovod_tpu.metrics.registry import default_registry
    reg = default_registry()
    assert reg.get("hvd_compile_total").value >= 1
    assert reg.get("hvd_compile_cache_miss_total").value >= 1
    h = reg.get("hvd_compile_seconds", labels={"function": "counted_fn"})
    assert h is not None and h.count >= 1
    assert compile_watch.totals()["seconds_total"] > 0


def test_reinstall_after_uninstall_counts_each_compile_once():
    """uninstall cannot remove the jax.monitoring listener (no removal
    API) — a later ensure_installed must reuse it, not stack a second
    one that double-counts every compile."""
    import jax.monitoring
    compile_watch.ensure_installed()
    compile_watch.uninstall()
    compile_watch.ensure_installed()
    compile_watch.reset_counts()
    jax.monitoring.record_event_duration_secs(
        "/jax/core/compile/backend_compile_duration", 0.25)
    assert compile_watch.totals()["compiles"] == 1


def test_init_resets_storm_counts_per_generation(hvd):
    """Elastic re-init must drop per-function compile counts: every
    re-meshed world legitimately recompiles its jitted steps, and a
    long run would otherwise accumulate into a false recompile_storm
    (init resets anomaly baselines for exactly this reason)."""
    compile_watch.reset_counts()
    for _ in range(4):
        compile_watch._note_compiling("train_step")
    assert compile_watch.by_function()["train_step"]["compiles"] == 4
    hvd.shutdown()
    hvd.init()
    assert compile_watch.by_function().get("train_step") is None


def test_label_budget_resets_with_counts():
    """A long-lived process saturates the 32-label budget; reset_counts
    (tests, elastic re-init) must re-open it or every later function is
    attributed to 'other' forever."""
    compile_watch.reset_counts()
    for i in range(compile_watch.MAX_FUNCTION_LABELS + 5):
        compile_watch._function_label(f"saturating_fn_{i}")
    assert compile_watch._function_label("late_fn") == "other"
    compile_watch.reset_counts()
    assert compile_watch._function_label("late_fn") == "late_fn"


# -- HBM observability -------------------------------------------------------

def _fake_stats(in_use, peak, limit):
    return [{"bytes_in_use": in_use[i], "peak_bytes_in_use": peak[i],
             "bytes_limit": limit[i]} for i in range(len(in_use))]


def test_memory_gauges_from_fake_stats():
    reg = Registry()
    sampler = memory.MemorySampler(
        registry=reg,
        stats_fn=lambda: _fake_stats([100, 300], [400, 600],
                                     [1000, 900]))
    assert sampler.on_step(1) is None
    assert reg.get("hvd_hbm_bytes_in_use").value == 300   # max device
    assert reg.get("hvd_hbm_peak_bytes").value == 600     # max device
    assert reg.get("hvd_hbm_limit_bytes").value == 900    # min device
    # margin: min over devices of limit - peak = min(600, 300) = 300
    assert reg.get("hvd_hbm_oom_margin_bytes").value == 300


def test_cpu_without_stats_registers_nothing():
    reg = Registry()
    sampler = memory.MemorySampler(registry=reg, stats_fn=lambda: [])
    for i in range(3):
        assert sampler.on_step(i) is None
    assert reg.get("hvd_hbm_bytes_in_use") is None
    assert sampler._dead  # stopped asking after first contact


def test_transient_stats_failure_keeps_polling():
    """A failed first read (stats_fn -> None, the device_stats error
    signature) must not latch the sampler dead — HBM observability
    comes back when the backend recovers."""
    reg = Registry()
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] == 1:
            return None  # transient PJRT error at step 1
        return _fake_stats([100], [200], [1000])

    sampler = memory.MemorySampler(registry=reg, stats_fn=flaky)
    assert sampler.on_step(1) is None
    assert not sampler._dead
    sampler.on_step(2)
    assert reg.get("hvd_hbm_bytes_in_use").value == 100.0


def test_statless_after_transient_error_still_goes_quiet():
    """None (error) at step 1 then clean [] at step 2: no stats were
    ever seen, so the sampler still latches dead — the quiet-mode
    guarantee is 'never saw stats', not 'first sample only'."""
    reg = Registry()
    seq = iter([None, [], []])
    sampler = memory.MemorySampler(registry=reg,
                                   stats_fn=lambda: next(seq))
    sampler.on_step(1)
    assert not sampler._dead
    sampler.on_step(2)
    assert sampler._dead


def test_min_gauge_merges_min_across_ranks():
    r1, r2 = Registry(), Registry()
    r1.gauge("hvd_hbm_oom_margin_bytes", agg="min").set(500)
    r2.gauge("hvd_hbm_oom_margin_bytes", agg="min").set(200)
    merged = Registry.merge([r1.snapshot(), r2.snapshot()])
    assert merged["hvd_hbm_oom_margin_bytes"]["value"] == 200


def test_hbm_growth_detector_flags_slow_leak():
    det = memory.HbmGrowthDetector(window=5, windows=3, min_frac=0.01)
    findings = []
    b = 1000.0
    for step in range(200):
        if step % 5 == 0:
            b *= 1.05  # +5% per window: a steady leak
        f = det.observe(b)
        if f:
            findings.append(f)
    assert findings, "a steady leak must flag"
    assert findings[0]["kind"] == "hbm_growth"
    assert findings[0]["growth_ratio"] > 1.0
    assert len(findings) == 1, "one finding per episode"


def test_hbm_flat_usage_is_clean():
    det = memory.HbmGrowthDetector(window=5, windows=3, min_frac=0.01)
    import random
    rng = random.Random(3)
    for _ in range(300):  # jittery but flat
        assert det.observe(1000 * (1 + 0.02 * (rng.random() - .5))) is None


# -- /debug/profile endpoint -------------------------------------------------

def test_debug_profile_endpoint_arms_capture():
    from urllib.request import urlopen

    from horovod_tpu.metrics.exporter import MetricsExporter
    from horovod_tpu.profiling import default_manager
    exp = MetricsExporter(port=0)
    exp.start()
    try:
        body = urlopen(f"http://127.0.0.1:{exp.port}/debug/profile"
                       "?steps=2", timeout=5).read()
        doc = json.loads(body)
        assert doc["started"] is True and doc["steps"] == 2
        # second request while pending: refused, status says why
        doc2 = json.loads(urlopen(
            f"http://127.0.0.1:{exp.port}/debug/profile?steps=2",
            timeout=5).read())
        assert doc2["started"] is False
        assert doc2["status"]["pending"] is not None
        # the armed window opens and closes on the step seam
        mgr = default_manager()
        _drive(mgr, 3)
        caps = mgr.recent_captures()
        assert caps and caps[0]["path"] == doc["path"]
        assert caps[0]["bytes"] > 0
    finally:
        exp.stop()


# -- re-mesh timeline --------------------------------------------------------

def test_remesh_episode_lands_histograms_flight_and_history():
    import time as _time

    from horovod_tpu.elastic import remesh
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.registry import default_registry
    remesh.begin("internal_error", old_size=3)
    with remesh.phase("failure_detect"):
        _time.sleep(0.01)
    with remesh.phase("drain"):
        pass
    with remesh.phase("rendezvous"):
        pass
    with remesh.phase("rebuild"):
        pass
    with remesh.phase("restore"):
        pass
    remesh.mark_recovered(new_size=2, generation=7)
    assert remesh.current() is not None
    remesh.note_step_end(1)  # first completed step closes the episode
    assert remesh.current() is None
    reg = default_registry()
    for phase in ("failure_detect", "drain", "rendezvous", "rebuild",
                  "restore", "first_step"):
        h = reg.get("hvd_remesh_seconds", labels={"phase": phase})
        assert h is not None and h.count >= 1, phase
    assert reg.get("hvd_remesh_total").value >= 1
    spans = _flight("remesh_phase")
    assert {e["phase"] for e in spans} >= {"failure_detect", "drain",
                                           "restore"}
    done = _flight("remesh_complete")
    assert done and done[-1]["old_size"] == 3 \
        and done[-1]["new_size"] == 2
    # the history point renders in the CLI's remesh table
    pts = timeseries.recorder().ring.points()
    remesh_pts = [p for p in pts if "remesh" in p]
    assert remesh_pts and remesh_pts[-1]["trigger"] == "internal_error"
    from horovod_tpu.metrics.__main__ import render_remesh_table
    table = render_remesh_table(remesh_pts)
    assert "internal_error" in table and "failure_detect" in table


def test_abandoned_episode_skips_histograms_keeps_flight():
    """Partial phase times from an abandoned recovery (a retry storm)
    must not smear the regression-gateable hvd_remesh_seconds
    distribution; the evidence survives as a remesh_abandoned flight
    event."""
    import time
    from horovod_tpu.elastic import remesh
    from horovod_tpu.metrics.registry import default_registry
    reg = default_registry()

    def _counts():
        h = reg.get("hvd_remesh_seconds",
                    labels={"phase": "failure_detect"})
        c = reg.get("hvd_remesh_total")
        return (h.count if h else 0), (c.value if c else 0)

    before = _counts()
    remesh.begin("internal_error", old_size=3)
    with remesh.phase("failure_detect"):
        time.sleep(0.001)
    # a second failure before recovery: the first episode is abandoned
    remesh.begin("internal_error", old_size=3)
    assert _counts() == before
    assert _flight("remesh_abandoned")
    remesh.reset()


def test_same_world_retry_closes_spans_without_episode():
    """A transient failure that resolves into the SAME world is not a
    re-mesh episode — no histograms, no hvd_remesh_total — but the
    spans already emitted live get a remesh_retry terminal marker."""
    from horovod_tpu.elastic import remesh
    from horovod_tpu.metrics.registry import default_registry
    reg = default_registry()
    c = reg.get("hvd_remesh_total")
    before = c.value if c else 0
    remesh.begin("internal_error", old_size=3)
    with remesh.phase("drain"):
        pass
    remesh.note_same_world_retry()
    assert remesh.current() is None
    c = reg.get("hvd_remesh_total")
    assert (c.value if c else 0) == before
    retries = _flight("remesh_retry")
    assert retries and retries[-1]["trigger"] == "internal_error"


def test_remesh_noop_outside_episode():
    from horovod_tpu.elastic import remesh
    with remesh.phase("drain"):
        pass  # pass-through, nothing recorded
    remesh.note_step_end(1)
    assert not _flight("remesh_phase")


# -- CLI rendering -----------------------------------------------------------

def test_top_renders_hbm_and_compile_columns():
    from horovod_tpu.metrics.__main__ import render_top
    series = {
        "hvd_fleet_size": 2.0, "hvd_fleet_ranks_reporting": 2.0,
        "hvd_hbm_bytes_in_use": 6 * 2**30,
        "hvd_hbm_peak_bytes": 7 * 2**30,
        "hvd_hbm_limit_bytes": 16 * 2**30,
        "hvd_hbm_oom_margin_bytes": 9 * 2**30,
        "hvd_compile_total": 12.0,
        "hvd_compile_cache_miss_total": 14.0,
        'hvd_compile_seconds_sum{function="step"}': 33.5,
        "hvd_remesh_total": 2.0,
        'hvd_remesh_seconds_sum{phase="drain"}': 1.5,
    }
    out = render_top(series, "test")
    assert "hbm" in out and "6.0GiB" in out and "9.0GiB" in out
    assert "compiles" in out and "12" in out and "14 cache misses" in out
    assert "re-meshes" in out and "2 (" in out


# -- end-to-end acceptance ---------------------------------------------------

def _telemetry_loop_with_work(steps):
    """A telemetry loop doing REAL device work on the 8-device mesh so
    an auto-fired capture has something to trace."""
    from horovod_tpu.train.callbacks import TelemetryCallback
    cb = TelemetryCallback(units_per_step=32, registry=Registry())
    x = jnp.ones((8, 16, 16))
    mesh = jax.sharding.Mesh(jax.devices(), ("d",))
    y = jax.device_put(x, jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec("d")))
    step = jax.jit(lambda a: (a @ a).sum())
    for _ in range(steps):
        cb.on_step_begin()
        step(y).block_until_ready()
        cb.on_step_end()
    return cb


def test_acceptance_chaos_stall_fires_autonomous_capture(
        tmp_path, monkeypatch):
    """ISSUE 9 acceptance: chaos slow-step window -> anomaly finding ->
    ProfileManager autonomously writes a non-empty bounded capture;
    `profile_captured` flight event recorded; capture path in the
    finding and the autopsy summary."""
    from horovod_tpu import chaos
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.profiling import default_manager

    monkeypatch.setenv("HVD_TPU_PROFILE_STEPS", "3")
    plan = {"faults": [{"seam": "step", "kind": "stall",
                        "start": 30, "stop": 36, "stall_s": 0.15}]}
    monkeypatch.setenv("HVD_TPU_FAULT_PLAN", json.dumps(plan))
    chaos.install(rank=0)
    try:
        _telemetry_loop_with_work(45)
    finally:
        monkeypatch.delenv("HVD_TPU_FAULT_PLAN")
        chaos.uninstall()

    findings = anomaly.recent_findings()
    drift = [f for f in findings if f["kind"] == "step_time_drift"]
    assert drift, findings
    caps = default_manager().recent_captures()
    assert len(caps) == 1, caps
    c = caps[0]
    assert c["bytes"] > 0, "the autonomous capture must be non-empty"
    assert c["steps"] == 3
    assert c["reason"] == "anomaly:step_time_drift"
    assert os.path.isdir(c["path"])
    # the finding carries the capture path (same dict the engine keeps)
    assert drift[0].get("profile") == c["path"], drift
    evs = _flight("profile_captured")
    assert evs and evs[0]["path"] == c["path"]

    # the autopsy summary ships both the anomaly and the capture path
    from horovod_tpu.diagnostics.autopsy import write_autopsy
    bundle = write_autopsy(str(tmp_path / "bundle"), reason="test",
                           fetch_peers=False)
    summaries = [f for f in os.listdir(bundle)
                 if f.startswith("summary_rank")]
    with open(os.path.join(bundle, summaries[0])) as f:
        summary = json.load(f)
    assert any(a["kind"] == "step_time_drift"
               for a in summary["anomalies"]), summary
    assert any(p["path"] == c["path"]
               for p in summary["profiles"]), summary


def test_acceptance_clean_run_captures_nothing(tmp_path):
    from horovod_tpu.metrics import anomaly
    from horovod_tpu.profiling import default_manager, profile_dir
    _telemetry_loop_with_work(45)
    assert anomaly.recent_findings() == []
    assert default_manager().recent_captures() == []
    assert not os.path.isdir(profile_dir()) or \
        os.listdir(profile_dir()) == []
