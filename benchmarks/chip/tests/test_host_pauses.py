"""``readers/host_pauses.py`` on a window and a ring written by hand: a
90 ms collection inside one step's dispatch is read as a pause and as a
collection, and standard error names it; the same pause with no record is
named by nothing; a ring shifted by a step fails the reader's own check
and the program's value is left out; a program without the host log gives
None and no raise. A gap that a collection covers is named after it by
``scope_reduce``. One rehearsal of an accepted cell prints the three
metrics."""

import json
import os
import re
import subprocess
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))

from readers import host_pauses                     # noqa: E402
from horovod_tpu.profiling import host_log          # noqa: E402

NAMES = {"host.pause_max_ms": "pause_max_ms", "host.stall_pct": "stall_pct",
         "host.gc_pause_ms": "gc_pause_ms"}
STEPS, TRACED, AHEAD = 80, 10, 2
INPUT_S, DISPATCH_S, STEP_S = 0.002, 0.012, 0.125
SOURCE_S, PLACE_S = 0.0004, 0.0014
PAUSED, PAUSE_S = 40, 0.090
SETUP_GC_S = 1.75


def _window(pause_s=PAUSE_S, ahead=AHEAD):
    """A device-bound window as ``run_steps`` records it: the host runs
    ``ahead`` steps in front, so a pause shorter than that reaches no
    completion time; one longer does."""
    dispatch = [DISPATCH_S] * STEPS
    dispatch[PAUSED] += pause_s
    # host time each step's bench.input begins at; the device finishes a
    # step STEP_S after the later of its dispatch and the step before it
    begins, done, t, ready = [], [], 0.0, 0.0
    for i in range(STEPS):
        if i > ahead:                       # bench.wait for step i-ahead-1
            t = max(t, done[i - ahead - 1])
        begins.append(t)
        t += INPUT_S + dispatch[i]
        ready = max(ready, t) + STEP_S
        done.append(ready)
    closing = done[-1]
    done_at = done[:STEPS - ahead] + [closing] * ahead
    ctx = {"spans_seconds": {"bench.input": [INPUT_S] * STEPS,
                             "bench.dispatch": dispatch,
                             "bench.wait": [STEP_S] * (STEPS - ahead + 1)},
           "step_done_at_s": done_at, "trace_steps": TRACED}
    return ctx, begins, closing


def _fill_ring(begins, closing, opened=1000.0, collection=True, shift=0):
    """The ring as the program leaves it: set-up's batches and collections,
    a source and a place record inside every window step's bench.input, the
    collection inside the paused step's dispatch, the traced steps'
    batches and the one ``compiled_step`` takes."""
    host_log.clear()
    host_log.record(host_pauses.GC, opened - 30.0, SETUP_GC_S,
                    {"generation": 2, "collected": 7})

    def batch(at, slow=0.0):
        host_log.record(host_pauses.SOURCE, at + 2e-6, SOURCE_S + slow)
        host_log.record(host_pauses.PLACE, at + 4e-6 + SOURCE_S + slow,
                        PLACE_S)
    for k in range(12):                              # warm-up
        batch(opened - 2.0 + k * STEP_S)
    begins = begins[shift:] + [closing + 0.5 + k * STEP_S
                               for k in range(shift)]
    for i, at in enumerate(begins):
        batch(opened + at)
        if collection and i + shift == PAUSED:
            host_log.record(host_pauses.GC, opened + at + INPUT_S + 0.001,
                            PAUSE_S - 0.0005,
                            {"generation": 2, "collected": 11})
    for k in range(TRACED + 1 - shift):
        batch(opened + closing + 1.0 + k * STEP_S)
    # a collection after the window: not the window's, not set-up's
    host_log.record(host_pauses.GC, opened + closing + 3.0, 0.25,
                    {"generation": 2, "collected": 3})


def _read(ctx):
    return {name: host_pauses.read({"reader": "host_pauses", "value": value},
                                   ctx)
            for name, value in NAMES.items()}


def _said(err):
    """The numbers of the reader's line on standard error."""
    found = re.search(
        r"step (\d+): ([\d.]+) ms over the median, ([\d.]+) ms of it named "
        r"(\[.*?\]); all steps' excesses ([\d.]+) ms; collections in the "
        r"window by generation (\[[\d, ]+\]), before it (\d+) in ([\d.]+) s",
        err)
    assert found, err
    step, over, named, who, total, by_generation, n, seconds = found.groups()
    return {"step": int(step), "over_ms": float(over),
            "named_ms": float(named), "who": who, "sum_ms": float(total),
            "by_generation": json.loads(by_generation),
            "before": (int(n), float(seconds))}


@pytest.fixture(autouse=True)
def _empty_ring():
    host_log.clear()
    yield
    host_log.clear()


def test_a_collection_inside_a_steps_dispatch_is_read_and_named(capsys):
    ctx, begins, closing = _window()
    _fill_ring(begins, closing)
    got = _read(ctx)
    assert got["host.pause_max_ms"] == pytest.approx(90.0, abs=1e-6)
    assert got["host.gc_pause_ms"] == pytest.approx(89.5, abs=1e-6)
    # two steps of 125 ms in flight hide a 90 ms pause from the device
    assert got["host.stall_pct"] == pytest.approx(0.0, abs=1e-9)
    err = capsys.readouterr().err
    assert err.count("readers/host_pauses.py") == 1     # one pass, one line
    said = _said(err)
    assert said["step"] == PAUSED and "hvd.host.gc" in said["who"]
    assert said["over_ms"] == pytest.approx(90.0, abs=1e-3)
    assert said["named_ms"] == pytest.approx(89.5, abs=1e-3)
    assert said["sum_ms"] == pytest.approx(90.0, abs=1e-3)
    # the window's one collection, and set-up's; not the one after it
    assert said["by_generation"] == [0, 0, 1]
    assert said["before"] == (1, pytest.approx(SETUP_GC_S, abs=1e-3))


def test_the_same_pause_with_no_record_is_named_by_nothing(capsys):
    ctx, begins, closing = _window()
    _fill_ring(begins, closing, collection=False)
    got = _read(ctx)
    assert got["host.pause_max_ms"] == pytest.approx(90.0, abs=1e-6)
    assert got["host.gc_pause_ms"] == 0.0
    said = _said(capsys.readouterr().err)
    assert said["step"] == PAUSED and said["who"] == "[]"
    assert said["named_ms"] == 0.0 and said["by_generation"] == [0, 0, 0]


def test_a_slow_source_is_the_steps_own_and_named(capsys):
    """The step's own source span beyond its median names a pause too."""
    ctx, begins, closing = _window(pause_s=0.0)
    ctx["spans_seconds"]["bench.input"][PAUSED] += 0.030
    # (the later steps' host times move by less than the wait hides)
    host_log.clear()
    _fill_ring(begins, closing, collection=False)
    records = host_log.records()
    host_log.clear()
    n = 0
    for r in records:
        if r[0] == host_pauses.SOURCE and r[1] >= 1000.0:
            if n == PAUSED:
                r = (r[0], r[1], r[2] + 0.030, r[3])
            n += 1
        host_log.record(*r)
    got = _read(ctx)
    assert got["host.pause_max_ms"] == pytest.approx(30.0, abs=1e-6)
    said = _said(capsys.readouterr().err)
    assert said["step"] == PAUSED and "hvd.input.source" in said["who"]
    assert said["named_ms"] == pytest.approx(30.0, abs=1e-3)


def test_a_pause_longer_than_the_steps_in_flight_reaches_the_device():
    ctx, begins, closing = _window(pause_s=1.2)
    _fill_ring(begins, closing, collection=False)
    got = _read(ctx)
    assert got["host.pause_max_ms"] == pytest.approx(1200.0, abs=1e-6)
    # the device ran dry for the pause less the two steps it had in hand
    lost = 1.2 - AHEAD * STEP_S + INPUT_S + DISPATCH_S
    assert got["host.stall_pct"] == pytest.approx(
        100.0 * lost / closing, rel=0.05)


@pytest.mark.parametrize("shift", [1, -1])
def test_a_shifted_ring_fails_the_self_check(capsys, shift):
    ctx, begins, closing = _window()
    if shift > 0:
        _fill_ring(begins, closing, shift=1)
    else:       # one batch more after the window than the harness takes
        _fill_ring(begins, closing)
        host_log.record(host_pauses.SOURCE, 1000.0 + closing + 9.0, SOURCE_S)
        host_log.record(host_pauses.PLACE, 1000.0 + closing + 9.1, PLACE_S)
    got = _read(ctx)
    assert got["host.gc_pause_ms"] is None
    # the harness's own clock is read all the same
    assert got["host.pause_max_ms"] == pytest.approx(90.0, abs=1e-6)
    assert got["host.stall_pct"] is not None
    err = capsys.readouterr().err
    assert "left out" in err and ("step" in err or "batch" in err)


def test_a_step_whose_spans_do_not_fit_its_input_says_which(capsys):
    ctx, begins, closing = _window()
    _fill_ring(begins, closing)
    ctx["spans_seconds"]["bench.input"][17] = 0.0005
    assert host_pauses.read({"value": "gc_pause_ms"}, ctx) is None
    assert "step 17: hvd.input.source + hvd.input.place" in \
        capsys.readouterr().err


def test_a_program_without_the_host_log_gives_none(monkeypatch):
    """The parent's: ``annotate`` wrote nothing and there is no module."""
    ctx, _begins, _closing = _window()
    monkeypatch.setitem(sys.modules, "horovod_tpu.profiling.host_log", None)
    import horovod_tpu.profiling as profiling
    monkeypatch.delattr(profiling, "host_log")
    got = _read(ctx)
    assert got["host.pause_max_ms"] == pytest.approx(90.0, abs=1e-6)
    assert got["host.gc_pause_ms"] is None
    # and a ring too short for the window (an empty one) is said, not raised
    monkeypatch.undo()
    assert host_pauses.read({"value": "gc_pause_ms"}, dict(ctx)) is None


def test_nothing_recorded_nothing_read():
    for value in host_pauses.VALUES:
        assert host_pauses.read({"value": value}, {}) is None
    with pytest.raises(ValueError, match="not 'p99'"):
        host_pauses.read({"value": "p99"}, {})
    # a window too short for a median of gaps
    assert host_pauses.read({"value": "stall_pct"},
                            {"step_done_at_s": [0.1, 0.2, 0.2]}) is None


def test_an_idle_gap_a_collection_covers_is_named_after_it():
    import scope_reduce as sr
    from trace_reduce import DeviceTrace, Event, Trace
    ops = [("fusion.1", 0, 100), ("fusion.1", 300, 100),
           ("fusion.1", 420, 100)]
    host = [("bench.wait", 0, 98), ("bench.dispatch", 100, 200),
            ("hvd.host.gc", 105, 190), ("bench.wait", 401, 18)]
    trace = Trace(
        {0: DeviceTrace([Event(n, float(s), float(d), "fusion")
                         for n, s, d in ops], [])},
        [Event(n, float(s), float(d)) for n, s, d in host])
    assert sr.idle_gaps(trace, 2) == [
        ["bench.dispatch/hvd.host.gc", 200e-9], ["bench.wait", 20e-9]]


def test_a_rehearsal_prints_the_three_and_places_the_ring(tmp_path):
    run = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload",
         "gpt-1.3b-widths.s2048", "--seed", "2147483653", "--seconds", "2",
         "--trace", "1", "--rehearse", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=600, cwd=ROOT)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    got = {k: v["value"] for k, v in result["metrics"].items()}
    for name in NAMES:
        assert "rehearsal." + name in got, (name, run.stderr[-2000:])
        assert got["rehearsal." + name] >= 0.0
    assert "is not placed in the window" not in run.stderr
    said = _said(run.stderr)
    assert said["named_ms"] <= said["over_ms"] <= said["sum_ms"] + 1e-3
    assert got["rehearsal.host.pause_max_ms"] == pytest.approx(
        said["over_ms"], abs=1e-3)
