"""``readers/setup_spans.py`` on a ring written by hand as a run's set-up
leaves it: the reference check's program and the timed step, each a
top-level trace with phase and kernel spans inside, a nested record of a
jitted call site, a lowering and a compile, then the lowering the harness
asks for after the window. The eight ``setup.*`` metric files of PR 68 read
through ``run.read_layer_metric`` give the first records' values, a ring
without a record gives None for what reads it, and a program without the
spans (the parent commit) gives None and no raise."""

import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import run as harness                                   # noqa: E402
from readers import setup_spans                         # noqa: E402
from horovod_tpu.profiling import host_log              # noqa: E402

METRICS = {"setup.trace_lower_cover_s": 21.5, "setup.kernel_traces": 6,
           "setup.step_trace_s": 8.0, "setup.step_lower_s": 3.0,
           "setup.step_trace_model_s": 3.0, "setup.step_trace_kernels_s": 2.0,
           "setup.program_import_s": 1.25, "setup.init_s": 0.5}
HLO = "HloModule jit_step, is_scheduled=true\n\nENTRY main {}\n"


def _ctx():
    return {"hlo_text": HLO,
            "counters": {"setup_trace_lower_cover_seconds": 21.5,
                         "setup_kernel_traces": 6}}


def _read(name, ctx):
    spec = harness.read_json(CHIP, "layer_metrics", name + ".json")
    return harness.read_layer_metric(spec["read"], ctx)


def _fill_ring(spans=True, drop=()):
    """Set-up on a clock that starts at 100 s, less the records named in
    ``drop`` (a span's name, or ``<function>.<event>``). The step's trace is
    [130, 138]: phases cover [130, 133.5] and [137, 137.5], a forward
    kernel's call site [131, 132] lies inside the first, the backward
    kernel's [135, 136] inside none (autodiff's transposition runs in no
    scope of the program's)."""
    host_log.clear()

    def span(name, start, seconds):
        if name not in drop:
            host_log.record(name, start, seconds)

    def compile_(event, function, start, seconds, **more):
        if f"{function}.{event}" not in drop:
            host_log.record(setup_spans.COMPILE, start, seconds,
                            {"event": event, "function": function, **more})
    span(setup_spans.IMPORT, 100.0, 1.25)
    span(setup_spans.INIT + "/backend", 101.3, 0.2)
    span(setup_spans.INIT, 101.25, 0.5)
    if spans:
        span(setup_spans.TRACE + "/kernel/hvd_flash_attention", 111.0, 1.0)
        span(setup_spans.TRACE + "/hvd.layers", 110.0, 4.0)
    compile_("trace", "fn", 109.0, 7.0)             # the reference check's
    compile_("lower", "fn", 116.0, 2.0)
    compile_("backend_compile", "fn", 118.0, 9.0)
    if spans:
        for name, start, seconds in (
                ("/hvd.embed", 130.0, 0.5),
                ("/kernel/hvd_flash_attention", 131.0, 1.0),
                ("/hvd.attention.core", 130.9, 1.2),
                ("/hvd.layers", 130.5, 3.0),
                ("/kernel/hvd_flash_bwd", 135.0, 1.0),
                ("/hvd.optimizer", 137.0, 0.5)):
            span(setup_spans.TRACE + name, start, seconds)
        # a jitted call site of the program's, traced inside the step's
        # (under the step's own name here: a nested record is never read)
        host_log.record(setup_spans.COMPILE, 134.9, 1.2, {
            "event": "trace", "function": "step", "nested": True})
    compile_("trace", "step", 130.0, 8.0)
    compile_("lower", "step", 138.0, 3.0)
    compile_("cache_read", "step", 141.1, 3.5)
    compile_("backend_compile", "step", 141.0, 4.0)
    # after the window: the harness lowers the step again, a re-mesh inits
    compile_("trace", "step", 200.0, 7.5)
    compile_("lower", "step", 207.5, 2.5)
    host_log.record(setup_spans.INIT, 300.0, 0.25)


@pytest.fixture(autouse=True)
def _leave_the_ring_empty():
    yield
    host_log.clear()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_set_ups_ring_gives_each_metric_its_first_record(name):
    _fill_ring()
    assert _read(name, _ctx()) == pytest.approx(METRICS[name])


def test_the_parts_of_the_steps_trace_lie_inside_it():
    _fill_ring()
    value = {n: _read(n, _ctx()) for n in METRICS}
    assert value["setup.step_trace_model_s"] \
        + value["setup.step_trace_kernels_s"] <= value["setup.step_trace_s"]
    # the phases' cover is 4.0 s; the forward kernel's second is the
    # kernel's and not the model's
    assert value["setup.step_trace_model_s"] == pytest.approx(4.0 - 1.0)


@pytest.mark.parametrize("drop,gone", [
    (("step.trace",), ("setup.step_trace_s", "setup.step_trace_model_s",
                       "setup.step_trace_kernels_s")),
    (("step.lower",), ("setup.step_lower_s",)),
    ((setup_spans.IMPORT,), ("setup.program_import_s",)),
    ((setup_spans.INIT,), ())])
def test_a_missing_record_is_none_for_what_reads_it_alone(drop, gone):
    _fill_ring(drop=drop)
    for name in METRICS:
        value = _read(name, _ctx())
        assert (value is None) == (name in gone), name
    if drop == (setup_spans.INIT,):     # the re-mesh's is the first then
        assert _read("setup.init_s", _ctx()) == 0.25


def test_a_program_without_the_spans_reads_its_trace_and_nothing_inside():
    """The parent commit: ``hvd.host.compile`` records and no span of a
    phase, a kernel, the import or an init."""
    _fill_ring(spans=False, drop=(setup_spans.IMPORT, setup_spans.INIT,
                                  setup_spans.INIT + "/backend"))
    host_log._RING.pop()                # (the re-mesh's init)
    ctx = {"hlo_text": HLO, "counters": {}}
    assert {n: _read(n, ctx) for n in METRICS} == {
        **dict.fromkeys(METRICS), "setup.step_trace_s": 8.0,
        "setup.step_lower_s": 3.0}


def test_a_step_that_bound_no_kernel_reads_zero_and_no_step_reads_none():
    _fill_ring()
    kept = [r for r in host_log.records() if "/kernel/" not in r[0]]
    host_log.clear()
    for r in kept:
        host_log.record(*r)
    assert _read("setup.step_trace_kernels_s", _ctx()) == 0.0
    assert _read("setup.step_trace_model_s", _ctx()) == pytest.approx(4.0)
    other = dict(_ctx(), hlo_text="HloModule jit_another_step\n")
    assert _read("setup.step_trace_s", other) is None
    assert _read("setup.step_trace_s", dict(_ctx(), hlo_text=None)) is None
    assert _read("setup.init_s", {}) is None        # no set-up recorded


def test_a_full_ring_vouches_for_no_first_record(monkeypatch):
    _fill_ring()
    monkeypatch.setattr(host_log, "RING_RECORDS", len(host_log.records()))
    assert {n: _read(n, _ctx()) for n in METRICS} == {
        **dict.fromkeys(METRICS), "setup.trace_lower_cover_s": 21.5,
        "setup.kernel_traces": 6}      # (the counters are not the ring's)


def test_the_line_on_standard_error_counts_the_ring(capsys):
    _fill_ring()
    assert _read("setup.step_trace_s", _ctx()) == 8.0
    said = capsys.readouterr().err
    assert f"the ring holds {len(host_log.records())} records" in said
    assert "by_function()'s three largest" in said
    with pytest.raises(ValueError, match="setup_spans reads one of"):
        setup_spans.read({"value": "nothing"}, _ctx())
