"""Unit tests for the step time-series history layer
(docs/OBSERVABILITY.md "Step time-series history"): ring bounds, JSONL
persistence + rotation + torn-tail tolerance, the sampling stride,
and the ``python -m horovod_tpu.metrics`` CLI (history table +
one-shot top frame)."""

import json
import os
import subprocess
import sys

import pytest

from horovod_tpu.metrics.timeseries import (SeriesWriter,
                                            StepSeriesRecorder,
                                            TimeSeriesRing, read_series)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- ring -------------------------------------------------------------------

def test_ring_bounded_drop_oldest():
    ring = TimeSeriesRing(capacity=3)
    for i in range(5):
        ring.append({"step": i})
    assert [p["step"] for p in ring.points()] == [2, 3, 4]
    assert [p["step"] for p in ring.points(last_n=2)] == [3, 4]
    assert len(ring) == 3


# -- JSONL writer / reader --------------------------------------------------

def test_writer_roundtrip_and_rank_tagging(tmp_path):
    d = str(tmp_path)
    for rank in (0, 1):
        w = SeriesWriter(d, rank=rank)
        for i in range(3):
            assert w.write({"ts": rank * 100 + i, "step": i})
        w.close()
    mine = read_series(d, rank=1)
    assert [p["step"] for p in mine] == [0, 1, 2]
    assert all(p["rank"] == 1 for p in mine)
    everyone = read_series(d)
    assert len(everyone) == 6
    assert [p["ts"] for p in everyone] == sorted(
        p["ts"] for p in everyone)  # time-sorted across ranks


def test_writer_rotation_keeps_one_generation(tmp_path):
    w = SeriesWriter(str(tmp_path), rank=0, max_bytes=200)
    for i in range(50):
        w.write({"step": i, "pad": "x" * 20})
    w.close()
    assert os.path.exists(w.path)
    assert os.path.exists(w.path + ".1")
    assert os.path.getsize(w.path) <= 200 + 64  # bounded, not unbounded
    points = read_series(str(tmp_path), rank=0)
    # rotated generation read first: order preserved, newest point last
    assert points[-1]["step"] == 49
    assert [p["step"] for p in points] == sorted(
        p["step"] for p in points)


def test_reader_skips_torn_tail_line(tmp_path):
    path = tmp_path / "obs_rank0.jsonl"
    path.write_text(json.dumps({"step": 1}) + "\n"
                    + json.dumps({"step": 2}) + "\n"
                    + '{"step": 3, "trunc')  # crash mid-append
    points = read_series(str(tmp_path), rank=0)
    assert [p["step"] for p in points] == [1, 2]


def test_recorder_sampling_stride_and_env_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    monkeypatch.setenv("HVD_TPU_OBS_SAMPLE_EVERY", "2")
    rec = StepSeriesRecorder(rank=3)
    for i in range(6):
        rec.record_step(i + 1, 0.01 * (i + 1), units=32)
    rec.close()
    assert len(rec.ring) == 3  # steps 1, 3, 5 sampled
    points = read_series(str(tmp_path), rank=3)
    assert [p["step"] for p in points] == [1, 3, 5]
    assert points[0]["units_per_s"] == pytest.approx(3200, rel=0.01)


def test_step_timer_feeds_the_series(monkeypatch, tmp_path):
    from horovod_tpu.metrics import timeseries
    from horovod_tpu.metrics.registry import Registry
    from horovod_tpu.train.callbacks import StepTimer
    monkeypatch.setenv("HVD_TPU_OBS_DIR", str(tmp_path))
    monkeypatch.delenv("HVD_TPU_OBS_SAMPLE_EVERY", raising=False)
    timeseries.reset()
    try:
        timer = StepTimer(unit="images", registry=Registry())
        for _ in range(2):
            with timer.step(units=8):
                pass
        # the ring is shared: end_step's other seams (the goodput
        # ledger's window close, a re-mesh episode's end) append points
        # of their own, with no "step"; which step of the process closes
        # a goodput window depends on the tests that ran before
        points = [p for p in timeseries.recorder().ring.points()
                  if "step" in p]
        assert [p["step"] for p in points[-2:]] == [1, 2]
        assert read_series(str(tmp_path))  # persisted too
    finally:
        timeseries.reset()


# -- CLI --------------------------------------------------------------------

def _cli(*args):
    return subprocess.run(
        [sys.executable, "-m", "horovod_tpu.metrics", *args],
        capture_output=True, text=True, cwd=REPO,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=120)


def test_cli_history_table_and_json(tmp_path):
    w = SeriesWriter(str(tmp_path), rank=0)
    for i in range(4):
        w.write({"ts": 1700000000 + i, "step": i + 1,
                 "step_time_s": 0.25, "units_per_s": 128.0})
    w.close()
    out = _cli("history", "--dir", str(tmp_path), "--last", "3")
    assert out.returncode == 0, out.stderr
    assert "step_time_s" in out.stdout and "0.25" in out.stdout
    assert "3 point(s)" in out.stdout
    js = _cli("history", "--dir", str(tmp_path), "--json")
    assert js.returncode == 0
    assert len(js.stdout.strip().splitlines()) == 4
    empty = _cli("history", "--dir", str(tmp_path / "nope"))
    assert empty.returncode == 1


def test_cli_history_remesh_honors_json_and_last(tmp_path):
    """--remesh composes with --json (JSONL out, not the table) and
    --last (episode slicing) like the step view does."""
    w = SeriesWriter(str(tmp_path), rank=0)
    for i in range(3):
        w.write({"ts": 1700000000 + i, "trigger": f"t{i}",
                 "remesh": {"drain": 0.1}, "remesh_total_s": 0.5,
                 "complete": True})
    w.write({"ts": 1700000009, "step": 1, "step_time_s": 0.2})
    w.close()
    js = _cli("history", "--dir", str(tmp_path), "--remesh", "--json")
    assert js.returncode == 0, js.stderr
    lines = [json.loads(l) for l in js.stdout.strip().splitlines()]
    assert len(lines) == 3 and all("remesh" in p for p in lines)
    last = _cli("history", "--dir", str(tmp_path), "--remesh",
                "--last", "1")
    assert last.returncode == 0
    assert "t2" in last.stdout and "t0" not in last.stdout


def test_cli_top_renders_fleet_frame():
    """One-shot frame against a live exporter serving a fleet view."""
    from horovod_tpu.metrics.exporter import MetricsExporter
    from horovod_tpu.metrics.fleet import FleetAggregator
    from horovod_tpu.metrics.registry import Registry
    reg = Registry()
    reg.counter("hvd_steps_total").inc(12)
    reg.histogram("hvd_step_time_seconds").observe(0.02)
    exp = MetricsExporter(registry=reg, port=0)
    exp.fleet = FleetAggregator(rank=0, size=1, base_port=9090,
                                registry=reg, push_interval=60.0)
    exp.start()
    try:
        out = _cli("top", "--url", f"http://127.0.0.1:{exp.port}",
                   "--once")
        assert out.returncode == 0, out.stderr
        assert "ranks reporting : 1/1" in out.stdout
        assert "steps total     : 12" in out.stdout
    finally:
        exp.stop()


def test_cli_top_render_is_pure():
    from horovod_tpu.metrics.__main__ import parse_prometheus, render_top
    series = parse_prometheus(
        "hvd_fleet_size 4\nhvd_fleet_ranks_reporting 3\n"
        "hvd_fleet_straggler_rank 2\n"
        'hvd_fleet_rank_step_time_seconds{rank="2"} 0.5\n'
        'hvd_anomaly_total{kind="step_time_drift"} 2\n'
        "# a comment\nbogus line\n")
    frame = render_top(series, "test")
    assert "3/4" in frame and "RANKS MISSING" in frame
    assert "straggler rank  : 2" in frame
    assert "step_time_drift×2" in frame
    assert "rank    2" in frame  # per-rank bar chart row

