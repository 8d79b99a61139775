"""Goodput ledger tests (docs/OBSERVABILITY.md "Goodput ledger",
ISSUE 16): closed-books wall-clock attribution — every second between
window open and close lands in exactly one category and the categories
sum back to wall time within tolerance — plus the roofline MFU
decomposition, the ``goodput_regression`` detector wiring, the CLI
views, the fleet merge, and the end-to-end acceptance: a run on the
8-device CPU mesh paying a real compile, a checkpoint save, an elastic
re-mesh and a chaos stall closes its books with each event in its
category, the stall is flagged as ``goodput_regression`` naming
``input_wait`` and arms an autonomous profile capture; an identical
clean run reports no goodput finding."""

import argparse
import json
import os
import time

import pytest

from horovod_tpu.metrics import goodput
from horovod_tpu.metrics.goodput import CATEGORIES, GoodputLedger
from horovod_tpu.metrics.registry import Registry, default_registry


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Drop every singleton the ledger feeds or reads so each test
    re-reads its knobs; unit findings must not arm real device traces
    (the e2e test below opts back in explicitly)."""
    import horovod_tpu.profiling as profiling
    from horovod_tpu.elastic import remesh
    from horovod_tpu.metrics import anomaly, timeseries
    monkeypatch.setenv("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    # a stale per-step exposed-comm gauge from another test file would
    # silently siphon in-step time out of `compute` in every window
    g = default_registry().get("hvd_overlap_exposed_comm_seconds")
    if g is not None:
        g.set(0.0)
    for mod in (goodput, anomaly, timeseries, profiling, remesh):
        mod.reset()
    yield
    g = default_registry().get("hvd_overlap_exposed_comm_seconds")
    if g is not None:
        g.set(0.0)
    for mod in (goodput, anomaly, timeseries, profiling, remesh):
        mod.reset()


def _run_steps(led, n, step_s=0.01, gap_s=0.0):
    for _ in range(n):
        led.note_step_begin()
        time.sleep(step_s)
        led.note_step_end(step_s)
        if gap_s:
            time.sleep(gap_s)


# -- the ledger: closed books by construction -------------------------------

def test_books_close_and_every_category_lands():
    led = GoodputLedger(window_steps=4, tolerance=0.05)
    led.note_step_begin()
    time.sleep(0.01)
    led.note_step_end(0.01)
    # out-of-step events between envelopes: a checkpoint stall and a
    # completed re-mesh recovery claim their slice of the gap
    time.sleep(0.012)
    led.note_checkpoint_stall(0.004)
    led.note_remesh(0.003)
    _run_steps(led, 3, step_s=0.01, gap_s=0.004)
    assert led.windows_closed == 1
    rec = led.last_window()
    assert rec["steps"] == 4
    # the closed-books invariant: categories sum to wall time exactly
    # (sequential clamping), the residual is float noise only
    assert sum(rec["seconds"].values()) == pytest.approx(
        rec["wall_s"], abs=1e-6)
    assert rec["closed"], rec
    assert set(rec["seconds"]) == set(CATEGORIES)
    s = rec["seconds"]
    assert s["compute"] == pytest.approx(0.04, rel=0.4)
    assert s["checkpoint_stall"] == pytest.approx(0.004, abs=0.002)
    assert s["remesh_recovery"] == pytest.approx(0.003, abs=0.002)
    assert s["input_wait"] > 0  # the un-attributed slice of the gaps
    assert all(v >= 0 for v in s.values()), s
    snap = led.snapshot()
    assert snap["windows"] == 1 and snap["steps"] == 4
    assert snap["books_violations"] == 0 and snap["closed"]
    assert 0 < snap["fraction"] < 1


def test_overclaimed_events_are_clamped_never_negative():
    """Absurd claimed costs (dt longer than the wall itself, hours of
    checkpoint stall) must clamp — books still close, nothing negative,
    nothing double-counted."""
    led = GoodputLedger(window_steps=1, tolerance=0.05)
    led.note_step_begin()
    time.sleep(0.005)
    led.note_checkpoint_stall(999.0)
    led.note_remesh(999.0)
    led.note_step_end(999.0)  # claimed in-step time >> wall
    rec = led.last_window()
    assert rec is not None
    s = rec["seconds"]
    assert all(v >= 0 for v in s.values()), s
    assert sum(s.values()) == pytest.approx(rec["wall_s"], abs=1e-6)
    # in-step claimed the whole wall, so the out-of-step claims got 0
    assert s["checkpoint_stall"] == 0.0 and s["remesh_recovery"] == 0.0


def test_exposed_comm_and_guard_skip_claims():
    reg = default_registry()
    g = reg.get("hvd_overlap_exposed_comm_seconds") or reg.gauge(
        "hvd_overlap_exposed_comm_seconds",
        help="per-step exposed collective seconds")
    c = reg.get("hvd_guard_skipped_steps_total") or reg.counter(
        "hvd_guard_skipped_steps_total", help="guard-zeroed updates")
    led = GoodputLedger(window_steps=3, tolerance=0.1)
    # step 1: 4ms of the 10ms step was exposed collective time
    g.set(0.004)
    led.note_step_begin()
    time.sleep(0.01)
    led.note_step_end(0.01)
    g.set(0.0)
    # step 2: the guard zeroed this update — the whole step was wasted
    led.note_step_begin()
    time.sleep(0.01)
    c.inc()
    led.note_step_end(0.01)
    # step 3: clean
    led.note_step_begin()
    time.sleep(0.01)
    led.note_step_end(0.01)
    s = led.last_window()["seconds"]
    assert s["exposed_comm"] == pytest.approx(0.004, abs=1e-4)
    assert s["guard_skipped"] == pytest.approx(0.01, abs=1e-4)
    assert s["compute"] == pytest.approx(0.016, abs=0.002)


def test_dominating_is_the_largest_non_compute_category():
    rec = {"seconds": {"compute": 50.0, "exposed_comm": 3.0,
                       "input_wait": 7.0, "idle_other": 1.0}}
    assert GoodputLedger.dominating(rec) == "input_wait"
    assert GoodputLedger.dominating({"seconds": {}}) is None


def test_window_cadence_flush_and_reopen(monkeypatch):
    monkeypatch.setenv("HVD_TPU_GOODPUT_WINDOW", "2")
    goodput.reset()
    for _ in range(5):
        goodput.note_step_begin()
        time.sleep(0.002)
        goodput.note_step_end(0.002)
    led = goodput.ledger(create=False)
    assert led is not None and led.windows_closed == 2
    # the 5th step sits in an open window; flush_open folds it in
    snap = goodput.snapshot()
    assert snap["windows"] == 2 and snap["steps"] == 4
    snap = goodput.snapshot(flush_open=True)
    assert snap["windows"] == 3 and snap["steps"] == 5
    fs = goodput.fleet_summary()
    assert fs is not None and 0 <= fs["fraction"] <= 1
    assert "dominating" in fs and fs["wall_s"] > 0


def test_module_seams_are_inert_until_a_step_lands():
    assert goodput.snapshot() is None
    assert goodput.flush() is None
    assert goodput.fleet_summary() is None
    # out-of-band events before any step must not conjure a ledger
    goodput.note_checkpoint_stall(1.0)
    goodput.note_remesh(1.0)
    assert goodput.ledger(create=False) is None


def test_disabled_knob_keeps_the_plane_dark(monkeypatch):
    monkeypatch.setenv("HVD_TPU_GOODPUT", "0")
    goodput.note_step_begin()
    goodput.note_step_end(0.01)
    assert goodput.ledger(create=False) is None
    assert goodput.snapshot() is None


def test_emit_writes_counters_gauge_and_timeseries(monkeypatch, tmp_path):
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_TPU_GOODPUT_WINDOW", "2")
    from horovod_tpu.metrics import timeseries
    timeseries.reset()
    goodput.reset()
    reg = default_registry()
    c0 = reg.get("hvd_goodput_seconds_total", labels={"category": "compute"})
    before = c0.value if c0 is not None else 0.0
    for _ in range(2):
        goodput.note_step_begin()
        time.sleep(0.004)
        goodput.note_step_end(0.004)
    c = reg.get("hvd_goodput_seconds_total", labels={"category": "compute"})
    assert c is not None and c.value > before
    frac = reg.get("hvd_goodput_fraction")
    assert frac is not None and 0 < frac.value <= 1
    pts = [p for p in timeseries.read_series(str(tmp_path))
           if isinstance(p.get("goodput"), dict)]
    assert pts and pts[-1]["goodput_steps"] == 2
    assert pts[-1]["goodput_closed"] is True
    timeseries.reset()


def test_autopsy_summary_embeds_flushed_ledger(monkeypatch, tmp_path):
    """The autopsy bundle ships the final ledger account with the open
    window flushed (docs/OBSERVABILITY.md "Goodput ledger") — the
    in-process leg of the 2-proc hang-autopsy demo, whose stall_worker
    asserts the same contract."""
    from horovod_tpu.diagnostics import autopsy
    monkeypatch.setenv("HVD_TPU_GOODPUT_WINDOW", "50")
    goodput.reset()
    # 3 steps land; window 50 never closes on its own — the autopsy
    # flush must fold the open window in
    for _ in range(3):
        goodput.note_step_begin()
        time.sleep(0.004)
        goodput.note_step_end(0.004)
    bundle = autopsy.write_autopsy(str(tmp_path / "bundle"),
                                   reason="test", fetch_peers=False)
    summaries = [p for p in os.listdir(bundle)
                 if p.startswith("summary_rank")]
    assert summaries, bundle
    doc = json.load(open(f"{bundle}/{summaries[0]}"))
    gp = doc["goodput"]
    assert gp is not None and gp["windows"] >= 1 and gp["steps"] == 3
    assert gp["closed"] and not gp["books_violations"], gp
    assert abs(sum(gp["seconds"].values()) - gp["wall_s"]) <= \
        gp["tolerance"] * gp["wall_s"] + 0.01, gp
    # no ledger at all -> the summary says None, never a crash
    goodput.reset()
    bundle2 = autopsy.write_autopsy(str(tmp_path / "bundle2"),
                                    reason="test", fetch_peers=False)
    s2 = [p for p in os.listdir(bundle2)
          if p.startswith("summary_rank")]
    assert json.load(open(f"{bundle2}/{s2[0]}"))["goodput"] is None


# -- goodput_regression detector --------------------------------------------

def _tuned_engine(monkeypatch, consecutive=2):
    from horovod_tpu.metrics.anomaly import AnomalyEngine
    monkeypatch.setenv("HVD_TPU_ANOMALY_WARMUP", "3")
    monkeypatch.setenv("HVD_TPU_ANOMALY_CONSECUTIVE", str(consecutive))
    monkeypatch.setenv("HVD_TPU_ANOMALY_K", "3")
    monkeypatch.setenv("HVD_TPU_ANOMALY_MIN_RATIO", "1.15")
    return AnomalyEngine(registry=Registry())


def test_goodput_regression_fires_and_names_the_category(monkeypatch):
    eng = _tuned_engine(monkeypatch)
    for _ in range(10):
        assert eng.observe_goodput(0.9, dominating="idle_other") == []
    # a sustained productive-fraction collapse: consecutive=2, so the
    # first bad window is a streak, the second flags
    assert eng.observe_goodput(0.4, dominating="input_wait") == []
    out = eng.observe_goodput(0.4, dominating="input_wait")
    assert len(out) == 1
    f = out[0]
    assert f["kind"] == "goodput_regression"
    assert f["category"] == "input_wait"
    assert f["value"] == pytest.approx(0.4)
    # hysteresis: the episode already flagged — no refire while low
    assert eng.observe_goodput(0.35, dominating="input_wait") == []
    # recovery re-arms: a NEW collapse is a new episode
    for _ in range(3):
        assert eng.observe_goodput(0.9) == []
    assert eng.observe_goodput(0.4, dominating="checkpoint_stall") == []
    out = eng.observe_goodput(0.4, dominating="checkpoint_stall")
    assert len(out) == 1 and out[0]["category"] == "checkpoint_stall"


def test_goodput_detector_ignores_healthy_jitter(monkeypatch):
    import random
    eng = _tuned_engine(monkeypatch)
    rng = random.Random(16)
    for _ in range(200):
        assert eng.observe_goodput(0.88 + rng.uniform(-0.03, 0.03)) == []


def test_default_knobs_catch_a_real_regression_after_compile_ramp():
    """DEFAULT thresholds must catch an 83% sustained goodput drop even
    when the first window was skewed by compile (a real out-of-repo
    drive missed this before EwmaMad's bias-corrected warmup: the slow
    EWMA lagged the compile->steady ramp and the MAD learned that lag
    as noise, inflating k*dev past the whole [0,1] range)."""
    from horovod_tpu.metrics.anomaly import AnomalyEngine
    eng = AnomalyEngine(registry=Registry())  # default env knobs
    windows = ([0.62] + [0.99] * 10          # compile ramp + steady
               + [0.15, 0.15, 0.15]          # sustained regression
               + [0.99, 0.99])               # recovery
    finds = []
    for v in windows:
        finds += eng.observe_goodput(v, dominating="input_wait")
    assert len(finds) == 1, finds
    assert finds[0]["kind"] == "goodput_regression"
    assert finds[0]["category"] == "input_wait"


# -- CLI views ---------------------------------------------------------------

def test_render_top_goodput_line():
    from horovod_tpu.metrics.__main__ import render_top
    series = {
        'hvd_goodput_seconds_total{category="compute"}': 80.0,
        'hvd_goodput_seconds_total{category="input_wait"}': 15.0,
        'hvd_goodput_seconds_total{category="compile"}': 5.0,
        "hvd_fleet_goodput_min": 0.6,
        "hvd_fleet_goodput_worst_rank": 2.0,
    }
    out = render_top(series, "test")
    line = next(ln for ln in out.splitlines() if ln.startswith("GOODPUT"))
    assert "80.0% productive" in line
    # loss categories sorted largest first
    assert line.index("input_wait") < line.index("compile")
    assert "worst rank 2 @ 60.0%" in line
    # no goodput series -> no GOODPUT line (don't render zeros)
    assert "GOODPUT" not in render_top({"hvd_steps_total": 3.0}, "test")


def _history_args(tmp_path, **kw):
    defaults = dict(dir=str(tmp_path), rank=None, last=0, json=False,
                    goodput=True, serving=False, remesh=False,
                    actions=False)
    defaults.update(kw)
    return argparse.Namespace(**defaults)


def test_history_goodput_table_and_json(monkeypatch, tmp_path, capsys):
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.__main__ import cmd_history
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    timeseries.reset()
    for frac, closed in ((0.91, True), (0.42, False)):
        timeseries.record_point({
            "goodput": {"compute": frac, "input_wait": 1 - frac},
            "goodput_wall_s": 1.0, "goodput_fraction": frac,
            "goodput_steps": 5, "goodput_closed": closed})
        timeseries.record_point({"step": 1, "step_time_s": 0.01})
    timeseries.reset()  # flush the writer
    assert cmd_history(_history_args(tmp_path)) == 0
    out = capsys.readouterr().out
    assert "2 goodput window(s)" in out
    assert "91.0%" in out and "42.0%" in out
    assert "ok" in out and "OPEN!" in out  # the unclosed window shouts
    assert cmd_history(_history_args(tmp_path, json=True)) == 0
    lines = [json.loads(ln) for ln in
             capsys.readouterr().out.strip().splitlines()]
    assert len(lines) == 2 and all("goodput" in p for p in lines)
    # the step view must NOT show goodput points
    assert cmd_history(_history_args(tmp_path, goodput=False)) == 0
    assert "goodput" not in capsys.readouterr().out
    # empty store: loud failure, nonzero rc
    empty = tmp_path / "empty"
    empty.mkdir()
    assert cmd_history(_history_args(empty)) == 1
    assert "no goodput windows" in capsys.readouterr().err


# -- fleet merge -------------------------------------------------------------

def test_fleet_merges_per_rank_goodput_and_names_worst(monkeypatch):
    from horovod_tpu.metrics.fleet import FleetAggregator
    regs = {r: Registry() for r in range(3)}
    aggs = {r: FleetAggregator(rank=r, size=3, base_port=9090,
                               registry=regs[r], push_interval=60.0)
            for r in range(3)}
    # the ledger is process-global; impersonate each rank's summary
    # around its push so the merged view carries real diversity
    fracs = {0: 0.9, 1: 0.55, 2: 0.8}
    root = aggs[0]
    for r in (1, 2):
        monkeypatch.setattr(
            goodput, "fleet_summary",
            lambda r=r: {"fraction": fracs[r], "dominating": "input_wait",
                         "wall_s": 10.0})
        assert root.ingest(aggs[r].subtree_doc())
    monkeypatch.setattr(
        goodput, "fleet_summary",
        lambda: {"fraction": fracs[0], "dominating": "idle_other",
                 "wall_s": 10.0})
    snap = root.fleet_snapshot()["snapshot"]
    for r, f in fracs.items():
        key = f'hvd_fleet_rank_goodput_fraction{{rank="{r}"}}'
        assert snap[key]["value"] == pytest.approx(f), sorted(snap)
    assert snap["hvd_fleet_goodput_min"]["value"] == pytest.approx(0.55)
    assert snap["hvd_fleet_goodput_worst_rank"]["value"] == 1
    # view-only: synthesized gauges must not leak into the local
    # registry (they would ride the next upstream push)
    assert "hvd_fleet_goodput_min" not in regs[0].snapshot()


def test_fleet_merge_survives_ranks_without_a_ledger(monkeypatch):
    from horovod_tpu.metrics.fleet import FleetAggregator
    regs = {r: Registry() for r in range(2)}
    aggs = {r: FleetAggregator(rank=r, size=2, base_port=9090,
                               registry=regs[r], push_interval=60.0)
            for r in range(2)}
    monkeypatch.setattr(goodput, "fleet_summary", lambda: None)
    assert aggs[0].ingest(aggs[1].subtree_doc())
    snap = aggs[0].fleet_snapshot()["snapshot"]
    assert "hvd_fleet_goodput_min" not in snap


# -- end-to-end acceptance (8-device CPU mesh) -------------------------------

def _e2e_env(monkeypatch, tmp_path, profile_on):
    monkeypatch.setenv("HVD_TPU_GOODPUT_WINDOW", "5")
    monkeypatch.setenv("HVD_TPU_GOODPUT_TOLERANCE", "0.05")
    monkeypatch.setenv("HVD_TPU_ANOMALY_ALPHA", "0.5")
    monkeypatch.setenv("HVD_TPU_ANOMALY_WARMUP", "2")
    monkeypatch.setenv("HVD_TPU_ANOMALY_CONSECUTIVE", "1")
    monkeypatch.setenv("HVD_TPU_ANOMALY_K", "3")
    monkeypatch.setenv("HVD_TPU_ANOMALY_MIN_RATIO", "1.15")
    monkeypatch.setenv("HVD_TPU_PROFILE_ON_ANOMALY",
                       "1" if profile_on else "0")
    monkeypatch.setenv("HVD_TPU_PROFILE_COOLDOWN_S", "0")
    monkeypatch.setenv("HVD_TPU_PROFILE_STEPS", "2")
    monkeypatch.setenv("HVD_TPU_PROFILE_DIR", str(tmp_path / "profiles"))


class _TestClock:
    """Stands in for the ``time`` module where the ledger, the StepTimer,
    the re-mesh episode and the chaos stall read it: ``perf_counter``
    moves only when one of them (or the loop) sleeps, so what a window
    holds is arithmetic and not the scheduling of a shared CPU."""

    def __init__(self):
        self._now = 0.0

    def perf_counter(self):
        return self._now

    def sleep(self, seconds):
        self._now += seconds

    def __getattr__(self, name):
        return getattr(time, name)


# what the test books for the loop's two pieces of real work
_COMPILE_S = 0.05
_CKPT_S = 0.01


def _e2e_loop(monkeypatch, ckpt, stall_steps=()):
    """The acceptance loop: 6 ledger windows of 5 steps driven through
    the real StepTimer seam — window 1 pays a REAL jit compile, window
    4 a waited checkpoint save, window 5 a completed re-mesh episode,
    and ``stall_steps`` get an inter-step chaos stall (the input
    pipeline going away BETWEEN envelopes, not inside one — in-step
    time is the step's own claim).  The clean run differs only in the
    stall.  Time is a ``_TestClock``: a step is 20 ms of it, and the
    compile and the save, which really happen and reach the ledger
    through their real seams, cost ``_COMPILE_S`` and ``_CKPT_S``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu import chaos
    from horovod_tpu.elastic import remesh
    from horovod_tpu.profiling import compile_watch
    from horovod_tpu.train import callbacks

    clock = _TestClock()
    for mod in (goodput, callbacks, remesh, chaos):
        monkeypatch.setattr(mod, "time", clock)
    compile_watch.ensure_installed()
    # the compile watcher's total, on the test's clock: _COMPILE_S once
    # the watcher has seen the loop's compile
    watched = goodput._compile_seconds_total
    before = watched()
    monkeypatch.setattr(goodput, "_compile_seconds_total",
                        lambda: _COMPILE_S * (watched() > before))
    # the store's own call after its waited save, on the test's clock
    note_stall = goodput.note_checkpoint_stall

    def booked_stall(_real_seconds):
        clock.sleep(_CKPT_S)
        note_stall(_CKPT_S)
    monkeypatch.setattr(goodput, "note_checkpoint_stall", booked_stall)

    timer = callbacks.StepTimer(registry=Registry())
    fn = jax.jit(lambda x: jnp.tanh(x) * 2.0 + x)
    x = np.arange(17.0, dtype=np.float32)  # odd shape: forces a compile
    # 33 steps: 6 full 5-step windows + 3 trailing healthy steps so a
    # capture armed at the LAST window close still gets steps to trace
    for i in range(33):
        if i in stall_steps:
            # the chaos `step` seam fired OUTSIDE the envelope: the
            # stall is wall time no step claimed -> input_wait
            chaos.step_tick(i)
        if i == 17:
            ckpt.save(1, {"w": np.zeros(64, np.float32)}, wait=True)
        if i == 22:
            remesh.begin("test", old_size=8, generation=0)
            with remesh.phase("rebuild"):
                clock.sleep(0.012)
            remesh.mark_recovered(new_size=8, generation=0)
        timer.start_step()
        if i == 0:
            fn(x).block_until_ready()  # the first step pays the compile
            clock.sleep(_COMPILE_S)
        clock.sleep(0.02)
        timer.end_step(32)
    return timer


def test_goodput_e2e_regression_flagged_and_profiled(
        monkeypatch, tmp_path):
    import horovod_tpu.profiling as profiling
    from horovod_tpu import chaos
    from horovod_tpu.checkpoint.store import ShardedCheckpointer
    from horovod_tpu.metrics import anomaly

    _e2e_env(monkeypatch, tmp_path, profile_on=True)
    plan = {"faults": [{"seam": "step", "kind": "stall",
                        "start": 25, "stop": 28, "stall_s": 0.08}]}
    monkeypatch.setenv("HVD_TPU_FAULT_PLAN", json.dumps(plan))
    anomaly.reset()
    profiling.reset()
    goodput.reset()
    chaos.install(rank=0)
    try:
        _e2e_loop(monkeypatch,
                  ShardedCheckpointer(str(tmp_path / "ckpt"), rank=0,
                                      world_size=1),
                  stall_steps=(25, 26, 27))
    finally:
        chaos.uninstall()

    # books close over the WHOLE run, compile/checkpoint/re-mesh each
    # landed in its category
    snap = goodput.snapshot(flush_open=True)
    assert snap is not None and snap["windows"] >= 6, snap
    assert snap["closed"] and snap["books_violations"] == 0, snap
    assert abs(snap["residual_s"]) <= \
        snap["tolerance"] * snap["wall_s"] + 1e-3, snap
    s = snap["seconds"]
    assert s["compute"] > 0.3, s
    assert s["compile"] > 0, s
    assert s["checkpoint_stall"] > 0, s
    assert s["remesh_recovery"] > 0.01, s
    assert s["input_wait"] > 0.15, s  # the three 80 ms stalls

    # the stall window was flagged as a goodput regression naming the
    # category that ate the time, and armed an autonomous capture
    findings = [f for f in anomaly.recent_findings()
                if f["kind"] == "goodput_regression"]
    assert findings, anomaly.recent_findings()
    f = findings[-1]
    assert f["category"] == "input_wait", f
    assert "profile" in f, f  # the planned trace path, stamped early
    caps = profiling.recent_captures()
    assert caps, "the armed capture never ran"
    trig = caps[-1]["trigger"]
    assert trig["kind"] == "goodput_regression"
    assert trig["category"] == "input_wait"


def test_goodput_e2e_clean_run_reports_nothing(monkeypatch, tmp_path):
    import horovod_tpu.profiling as profiling
    from horovod_tpu.checkpoint.store import ShardedCheckpointer
    from horovod_tpu.metrics import anomaly

    _e2e_env(monkeypatch, tmp_path, profile_on=False)
    anomaly.reset()
    profiling.reset()
    goodput.reset()
    _e2e_loop(monkeypatch,
              ShardedCheckpointer(str(tmp_path / "ckpt"), rank=0,
                                  world_size=1),
              stall_steps=())
    snap = goodput.snapshot(flush_open=True)
    assert snap is not None and snap["closed"], snap
    assert snap["books_violations"] == 0
    assert not [f for f in anomaly.recent_findings()
                if f["kind"] == "goodput_regression"], \
        anomaly.recent_findings()
