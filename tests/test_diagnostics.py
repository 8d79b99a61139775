"""Unit tests for the diagnostics subsystem: flight-recorder ring
semantics, span determinism, shard merging under skewed clocks, watchdog
arming/triggering, stall metrics, and the log-span join."""

import json
import logging
import os
import sys
import threading
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.diagnostics.flight_recorder import FlightRecorder  # noqa: E402
from horovod_tpu.diagnostics import spans  # noqa: E402
from horovod_tpu.diagnostics.merge import (load_shard,  # noqa: E402
                                           merge_shards)
from horovod_tpu.diagnostics.watchdog import Watchdog  # noqa: E402


# -- flight recorder ---------------------------------------------------------

def test_flight_recorder_bounded_drop_oldest():
    fr = FlightRecorder(capacity=8)
    for i in range(20):
        fr.record("ev", i=i)
    assert len(fr) == 8
    assert fr.dropped == 12
    events = fr.events()
    assert [e["i"] for e in events] == list(range(12, 20))  # oldest gone
    doc = fr.dump()
    assert doc["capacity"] == 8
    assert doc["dropped"] == 12
    assert doc["recorded"] == 8


def test_flight_recorder_thread_safe():
    fr = FlightRecorder(capacity=128)
    n_threads, per_thread = 8, 500

    def pump(t):
        for i in range(per_thread):
            fr.record("t", thread=t, i=i)

    threads = [threading.Thread(target=pump, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(fr) == 128
    assert fr.dropped == n_threads * per_thread - 128
    # seq is strictly increasing in the retained tail
    seqs = [e["seq"] for e in fr.events()]
    assert seqs == sorted(seqs)
    assert seqs[-1] == n_threads * per_thread


def test_flight_recorder_dump_to(tmp_path):
    fr = FlightRecorder(capacity=4)
    fr.record("x", a=1)
    path = str(tmp_path / "flight.json")
    fr.dump_to(path)
    doc = json.load(open(path))
    assert doc["events"][0]["kind"] == "x"


def test_record_event_never_raises():
    from horovod_tpu.diagnostics.flight_recorder import record_event
    record_event("ok", weird=object())  # non-serializable is fine in-ring


# -- spans -------------------------------------------------------------------

def test_span_ids_deterministic_per_name():
    spans.reset()
    assert spans.next_span("grads") == "grads#1"
    assert spans.next_span("grads") == "grads#2"
    assert spans.next_span("other") == "other#1"
    spans.reset()
    assert spans.next_span("grads") == "grads#1"  # what a peer computes


def test_active_span_is_thread_local():
    spans.reset()
    seen = {}
    with spans.active_span("a#1"):
        assert spans.current_span() == "a#1"

        def other():
            seen["other"] = spans.current_span()

        t = threading.Thread(target=other)
        t.start()
        t.join()
    assert seen["other"] is None
    assert spans.current_span() is None


def test_log_lines_carry_active_span(capsys):
    from horovod_tpu.common.logging import get_logger, reset_logger
    reset_logger()
    logger = get_logger()
    logger.setLevel(logging.WARNING)
    with spans.active_span("grads#7"):
        logger.warning("inside")
    logger.warning("outside")
    err = capsys.readouterr().err
    inside = [ln for ln in err.splitlines() if "inside" in ln][0]
    outside = [ln for ln in err.splitlines() if "outside" in ln][0]
    assert "[span grads#7]" in inside
    assert "[span" not in outside
    reset_logger()


# -- shard merging -----------------------------------------------------------

def _shard(path, rank, epoch_s, offset_s, events):
    """Write a synthetic host shard: meta anchored at shard ts=0."""
    doc = [{"ph": "i", "name": "SHARD_META", "pid": rank, "tid": "meta",
            "ts": 0.0, "s": "g",
            "args": {"epoch_us": epoch_s * 1e6, "rank": rank,
                     "source": "host",
                     "wall_offset_us": offset_s * 1e6}}]
    doc.extend(events)
    with open(path, "w") as f:
        json.dump(doc, f)
    return str(path)


def test_merge_aligns_skewed_clocks(tmp_path):
    # rank 1's wall clock runs 100s AHEAD of rank 0's; both ranks saw
    # the same collective at the same TRUE time (1s after their shard
    # start, shards started simultaneously in coordinator time)
    ev0 = [{"ph": "B", "name": "ALLREDUCE", "cat": "collective",
            "tid": "grads", "ts": 1e6, "args": {"span": "grads#1"}}]
    ev1 = [{"ph": "B", "name": "ALLREDUCE", "cat": "collective",
            "tid": "grads", "ts": 1e6, "args": {"span": "grads#1"}}]
    p0 = _shard(tmp_path / "t.rank0.json", 0, 1000.0, 0.0, ev0)
    p1 = _shard(tmp_path / "t.rank1.json", 1, 1100.0, 100.0, ev1)
    doc = merge_shards([p0, p1])
    bs = [e for e in doc["traceEvents"] if e.get("ph") == "B"]
    assert len(bs) == 2
    # aligned: identical coordinator-time timestamps, distinct tracks
    assert abs(bs[0]["ts"] - bs[1]["ts"]) < 1.0, bs
    assert {b["pid"] for b in bs} == {0, 1}
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert names == {"rank 0", "rank 1"}


def test_merge_without_offset_shows_skew(tmp_path):
    # control: zero recorded offset leaves the 100s skew visible
    ev = [{"ph": "B", "name": "A", "cat": "c", "tid": "x", "ts": 0.0}]
    p0 = _shard(tmp_path / "t.rank0.json", 0, 1000.0, 0.0, list(ev))
    p1 = _shard(tmp_path / "t.rank1.json", 1, 1100.0, 0.0, list(ev))
    doc = merge_shards([p0, p1])
    bs = sorted((e for e in doc["traceEvents"] if e.get("ph") == "B"),
                key=lambda e: e["pid"])
    assert abs(bs[1]["ts"] - bs[0]["ts"]) > 99e6


def test_merge_skips_unreadable_shard(tmp_path):
    """A rank that died with an empty/garbled shard must not cost the
    other ranks' evidence."""
    ev = [{"ph": "B", "name": "A", "cat": "c", "tid": "x", "ts": 0.0}]
    good = _shard(tmp_path / "t.rank0.json", 0, 10.0, 0.0, ev)
    bad = tmp_path / "t.rank1.json"
    bad.write_text("")  # crash right after open
    doc = merge_shards([good, str(bad)])
    assert any(e.get("ph") == "B" for e in doc["traceEvents"])


def test_merge_repairs_truncated_shard(tmp_path):
    # a crash-cut shard: unterminated array, partial trailing object
    path = tmp_path / "t.rank0.json"
    path.write_text('[\n{"ph": "B", "name": "A", "cat": "c", "tid": "x",'
                    ' "ts": 5.0},\n{"ph": "E", "na')
    events = load_shard(str(path))
    assert len(events) == 1
    assert events[0]["name"] == "A"


def test_merge_directory_and_cli(tmp_path):
    ev = [{"ph": "B", "name": "A", "cat": "c", "tid": "x", "ts": 0.0}]
    _shard(tmp_path / "timeline.rank0.json", 0, 10.0, 0.0, list(ev))
    _shard(tmp_path / "timeline.rank1.json", 1, 10.0, 0.0, list(ev))
    from horovod_tpu.diagnostics.__main__ import main
    out = str(tmp_path / "cli_merged.json")
    assert main(["merge", "--dir", str(tmp_path), "-o", out]) == 0
    doc = json.load(open(out))
    assert len({e["pid"] for e in doc["traceEvents"]}) >= 2


def test_timeline_shard_roundtrip(tmp_path):
    """A real Timeline shard (any rank) merges with correlated spans."""
    from horovod_tpu.common.timeline import Timeline
    paths = []
    for rank in (0, 1):
        tl = Timeline(rank)
        path = str(tmp_path / f"timeline.rank{rank}.json")
        tl.start_shard(path, wall_offset_s=0.0)
        assert tl.enabled
        tl.collective_begin("grads", "allreduce", "grads#1")
        tl.collective_end("grads", "grads#1")
        tl.stop()
        paths.append(path)
    doc = merge_shards(paths, str(tmp_path / "merged.json"))
    spans_seen = {}
    for ev in doc["traceEvents"]:
        span = (ev.get("args") or {}).get("span")
        if ev.get("ph") == "B" and span:
            spans_seen.setdefault(span, set()).add(ev["pid"])
    assert spans_seen.get("grads#1") == {0, 1}


# -- watchdog ----------------------------------------------------------------

def test_watchdog_no_false_positive_during_healthy_loop():
    fired = []
    wd = Watchdog(timeout_s=0.6, on_trigger=fired.append,
                  check_interval_s=0.05).start()
    try:
        t_end = time.monotonic() + 1.8
        step = 0
        while time.monotonic() < t_end:
            time.sleep(0.1)
            step += 1
            wd.notify_progress(step)
        assert fired == []
        assert wd.trigger_count == 0
    finally:
        wd.stop()


def test_watchdog_triggers_once_on_stall():
    fired = []
    wd = Watchdog(timeout_s=0.3, on_trigger=fired.append,
                  check_interval_s=0.05).start()
    try:
        time.sleep(1.2)  # several timeout periods with zero progress
        assert wd.trigger_count == 1, fired  # one bundle per stall
        assert "no step progress" in fired[0]
    finally:
        wd.stop()


def test_watchdog_disarmed_by_zero_timeout():
    wd = Watchdog(timeout_s=0)
    wd.start()
    assert not wd.armed


def test_watchdog_env_default(monkeypatch):
    monkeypatch.delenv("HVD_TPU_WATCHDOG_SECONDS", raising=False)
    monkeypatch.delenv("HOROVOD_WATCHDOG_SECONDS", raising=False)
    assert Watchdog().timeout_s == 600.0
    monkeypatch.setenv("HVD_TPU_WATCHDOG_SECONDS", "42.5")
    assert Watchdog().timeout_s == 42.5


def test_write_autopsy_degrades_without_init(tmp_path):
    """Uninitialized process: stacks + flight + summary still land."""
    from horovod_tpu.diagnostics.autopsy import write_autopsy
    from horovod_tpu.diagnostics.flight_recorder import record_event
    record_event("unit_test_marker")
    bundle = write_autopsy(str(tmp_path / "bundle"), reason="unit test",
                           fetch_peers=False)
    files = os.listdir(bundle)
    assert any(f.startswith("stacks_rank") for f in files), files
    assert any(f.startswith("flight_rank") for f in files), files
    flight = json.load(open(os.path.join(
        bundle, [f for f in files if f.startswith("flight_rank")][0])))
    assert any(e["kind"] == "unit_test_marker" for e in flight["events"])
    summary = json.load(open(os.path.join(
        bundle, [f for f in files if f.startswith("summary_rank")][0])))
    assert summary["reason"] == "unit test"


def test_telemetry_callback_arms_watchdog(monkeypatch):
    from horovod_tpu.common.basics import _state
    from horovod_tpu.diagnostics import watchdog as wd_mod
    monkeypatch.setenv("HVD_TPU_WATCHDOG_SECONDS", "120")
    # arming requires an initialized world (uninitialized processes must
    # never leak a 600s daemon into a long test run — see below)
    monkeypatch.setattr(_state, "initialized", True)
    wd_mod.reset()
    try:
        from horovod_tpu.train.callbacks import TelemetryCallback
        cb = TelemetryCallback()
        assert cb.watchdog is not None and cb.watchdog.armed
        before = cb.watchdog._last_progress
        cb.on_step_begin()
        cb.on_step_end()
        assert cb.watchdog._last_progress >= before
    finally:
        wd_mod.reset()


def test_telemetry_callback_does_not_arm_uninitialized():
    """Without hvd.init there is no world to autopsy: the callback must
    NOT leave an armed watchdog behind (zero autopsies across the
    healthy unit suite)."""
    import horovod_tpu as hvd
    from horovod_tpu.diagnostics import watchdog as wd_mod
    if hvd.is_initialized():
        pytest.skip("another test left hvd initialized")
    wd_mod.reset()
    from horovod_tpu.train.callbacks import TelemetryCallback
    cb = TelemetryCallback()
    assert cb.watchdog is None
    assert wd_mod._WATCHDOG is None


def test_telemetry_on_train_end_stands_watchdog_down(monkeypatch):
    """After training, a long eval/export with no steps is legitimate:
    on_train_end suspends the watchdog instead of letting it fire."""
    from horovod_tpu.common.basics import _state
    from horovod_tpu.diagnostics import watchdog as wd_mod
    monkeypatch.setenv("HVD_TPU_WATCHDOG_SECONDS", "120")
    monkeypatch.setattr(_state, "initialized", True)
    wd_mod.reset()
    try:
        from horovod_tpu.train.callbacks import TelemetryCallback
        cb = TelemetryCallback()
        assert cb.watchdog.armed
        cb.on_train_end()
        assert not cb.watchdog.armed
    finally:
        wd_mod.reset()


def test_watchdog_suspend_resume_cycle():
    """hvd.shutdown suspends (remembers armed), hvd.init resumes — an
    elastic re-mesh must not silently disarm hang detection."""
    from horovod_tpu.diagnostics import watchdog as wd_mod
    wd_mod.reset()
    try:
        os.environ["HVD_TPU_WATCHDOG_SECONDS"] = "120"
        wd = wd_mod.ensure_watchdog()
        assert wd is not None and wd.armed
        wd_mod.suspend()
        assert not wd.armed
        wd_mod.resume()
        assert wd.armed
        wd_mod.notify_progress(7)  # still wired to the same instance
        assert wd._last_step == 7
    finally:
        os.environ.pop("HVD_TPU_WATCHDOG_SECONDS", None)
        wd_mod.reset()


# -- stall metrics mapping ---------------------------------------------------

def test_engine_collector_surfaces_stall_metrics():
    from horovod_tpu.metrics.engine import EngineCollector
    from horovod_tpu.metrics.registry import Registry
    reg = Registry()
    counters = {"cycles": 10, "stall_warnings": 0, "stalled_tensors": 0}
    col = EngineCollector(lambda: counters, registry=reg)
    col.collect()
    snap = reg.snapshot()
    assert snap["hvd_stall_warnings_total"]["value"] == 0
    counters.update(stall_warnings=3, stalled_tensors=2)
    col.collect()
    snap = reg.snapshot()
    assert snap["hvd_stall_warnings_total"]["value"] == 3
    assert snap["hvd_stalled_tensors"]["value"] == 2
    # counter semantics: a re-collect with the same totals adds nothing
    col.collect()
    assert reg.snapshot()["hvd_stall_warnings_total"]["value"] == 3
    # an elastic re-mesh resets the C++ counters: the new engine's
    # warnings must still land (delta < 0 ⇒ whole new total is new)
    counters.update(stall_warnings=2)
    col.collect()
    assert reg.snapshot()["hvd_stall_warnings_total"]["value"] == 5


# -- engine state API (single-process degradations) --------------------------

def test_engine_state_requires_init():
    import horovod_tpu as hvd
    from horovod_tpu.common.basics import NotInitializedError
    if hvd.is_initialized():
        pytest.skip("another test left hvd initialized")
    with pytest.raises(NotInitializedError):
        hvd.engine_state()


def test_suspects_from_engine_orders_by_wait():
    from horovod_tpu.diagnostics.autopsy import suspects_from_engine
    engine = {"engine_state": {"domains": [{"id": 0, "pending": [
        {"name": "a", "waited_s": 1.0, "ready_ranks": [0],
         "missing_ranks": [1]},
        {"name": "b", "waited_s": 9.0, "ready_ranks": [0, 2],
         "missing_ranks": [3]},
    ]}]}}
    sus = suspects_from_engine(engine)
    assert [s["tensor"] for s in sus] == ["b", "a"]
    assert sus[0]["missing_ranks"] == [3]
