"""The host log (horovod_tpu/profiling/host_log.py): ``annotate`` leaves a
record with no profiler session open, the ring is bounded, a garbage
collection is an ``hvd.host.gc`` span with its generation,
``hvd.init()`` / ``hvd.shutdown()`` install and remove one callback, a
compile's events are ``hvd.host.compile`` records that add up to
``compile_watch.totals()``, a trace inside another counts once in the cover
and is its function's in ``by_function()``, a scope and a kernel call site
leave ``hvd.host.trace`` spans while a function is traced and none after,
the package's import and ``hvd.init()`` leave theirs, the input path leaves
one pair of records a batch, and nothing a callback meets can break the ring
or the collector."""

import gc
import glob
import os
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import horovod_tpu as hvd
from horovod_tpu import profiling
from horovod_tpu.profiling import compile_watch, host_log, scopes

CHIP = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "chip")


@pytest.fixture(autouse=True)
def _fresh_log():
    host_log.uninstall_gc_callback()
    host_log.clear()
    yield
    host_log.uninstall_gc_callback()
    host_log.clear()


@pytest.fixture
def gc_callback():
    host_log.install_gc_callback()
    yield
    host_log.uninstall_gc_callback()


def _named(name, since=float("-inf")):
    return [r for r in host_log.records() if r[0] == name and r[1] >= since]


# -- the door: annotate -------------------------------------------------------

def test_annotate_records_with_no_profiler_session_open():
    before = time.perf_counter()
    with profiling.annotate(scopes.INPUT_PLACE):
        time.sleep(0.01)
    after = time.perf_counter()
    (name, start, duration, meta), = host_log.records()
    assert name == scopes.INPUT_PLACE and meta is None
    assert before <= start <= start + duration <= after
    assert 0.01 <= duration < after - before + 1e-9


def test_annotate_records_and_lets_an_exception_through():
    with pytest.raises(KeyError):
        with profiling.annotate(scopes.INPUT_SOURCE):
            raise KeyError("decode failed")
    assert [r[0] for r in host_log.records()] == [scopes.INPUT_SOURCE]


def test_the_ring_is_bounded_and_keeps_the_newest():
    for i in range(host_log.RING_RECORDS + 10):
        host_log.record(scopes.INPUT_PLACE, float(i), 0.0)
    got = host_log.records()
    assert len(got) == host_log.RING_RECORDS
    assert got[0][1] == 10.0 and got[-1][1] == host_log.RING_RECORDS + 9.0
    # a constant, not a setting: no environment variable names it
    assert not [k for k in os.environ if "RING" in k and "HVD" in k]


def test_records_reads_the_ring_in_order_and_hands_out_a_copy():
    for start in (1.0, 2.0, 3.0):
        host_log.record(scopes.INPUT_SOURCE, start, 0.5, {"n": start})
    got = host_log.records()
    assert [r[1] for r in got] == [1.0, 2.0, 3.0]
    assert got[0] == (scopes.INPUT_SOURCE, 1.0, 0.5, {"n": 1.0})
    got.clear()                      # the reader's list, not the ring
    assert len(host_log.records()) == 3


def test_nested_spans_record_inner_first_and_contained():
    with profiling.annotate(scopes.INPUT_SOURCE):
        with profiling.annotate(scopes.INPUT_PLACE):
            pass
    (inner, i0, i_s, _m), (outer, o0, o_s, _m2) = host_log.records()
    assert (inner, outer) == (scopes.INPUT_PLACE, scopes.INPUT_SOURCE)
    assert o0 <= i0 and i0 + i_s <= o0 + o_s
    # the door holds no state a span could leak: a fresh object a call
    assert profiling.annotate(outer) is not profiling.annotate(outer)


# -- garbage collections ------------------------------------------------------

@pytest.mark.parametrize("generation", [0, 1, 2])
def test_a_forced_collection_is_one_span_with_its_generation(
        gc_callback, generation):
    gc.collect()                # so that the forced one has little to do
    host_log.clear()
    gc.disable()                # none of the interpreter's own in between
    try:
        before = time.perf_counter()
        gc.collect(generation)
        after = time.perf_counter()
    finally:
        gc.enable()
    (name, start, duration, meta), = _named(scopes.HOST_GC)
    assert name == scopes.HOST_GC and meta["generation"] == generation
    assert isinstance(meta["collected"], int)
    assert before <= start <= start + duration <= after


def test_collections_are_recorded_only_while_installed():
    gc.collect(2)
    assert _named(scopes.HOST_GC) == []


def test_init_installs_one_callback_and_shutdown_removes_it():
    def installed():
        return sum(c is host_log._on_gc for c in gc.callbacks)
    hvd.shutdown()
    assert installed() == 0
    hvd.init()
    hvd.init()                       # a second init is a no-op
    assert installed() == 1
    host_log.install_gc_callback()   # and so is a second install
    assert installed() == 1
    hvd.shutdown()
    assert installed() == 0
    hvd.init()
    try:
        assert installed() == 1
        gc.collect(2)
        assert _named(scopes.HOST_GC)[-1][3]["generation"] == 2
    finally:
        hvd.shutdown()
    assert installed() == 0


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnraisableExceptionWarning")
def test_the_ring_survives_a_callback_that_raises(gc_callback, monkeypatch,
                                                  capsys):
    """Somebody else's ``gc.callbacks`` entry raising (the interpreter
    reports it and goes on), and our own annotation failing: the collection
    is still recorded, and the collector never sees a raise."""
    def broken(phase, info):
        raise RuntimeError("somebody else's callback")
    gc.callbacks.insert(0, broken)
    try:
        gc.collect(2)
    finally:
        gc.callbacks.remove(broken)
    capsys.readouterr()              # the interpreter's "Exception ignored"
    assert len(_named(scopes.HOST_GC)) >= 1

    def no_annotation(*_a, **_k):
        raise RuntimeError("no profiler")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", no_annotation)
    n = len(_named(scopes.HOST_GC))
    gc.collect(2)
    assert len(_named(scopes.HOST_GC)) > n
    assert _named(scopes.HOST_GC)[-1][3]["generation"] == 2
    monkeypatch.undo()
    with profiling.annotate(scopes.INPUT_PLACE):
        pass
    assert host_log.records()[-1][0] == scopes.INPUT_PLACE

    # a "stop" with no "start" (installed mid-collection) records nothing
    host_log.clear()
    host_log._on_gc("stop", {"generation": 2, "collected": 0})
    assert host_log.records() == []
    # and info of a shape nobody promised does not raise
    host_log._on_gc("start", {})
    host_log._on_gc("stop", {})
    assert _named(scopes.HOST_GC)[-1][3]["generation"] == -1


def test_a_collection_inside_a_traced_step_lies_on_the_traces_host_plane(
        gc_callback, tmp_path):
    """With a profiler session open the span is a ``TraceAnnotation`` on
    the host plane, inside the span that was open around it: what
    ``scope_reduce.idle_gaps`` names a gap by."""
    if CHIP not in sys.path:
        sys.path.insert(0, CHIP)
    import trace_reduce
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            jnp.ones(8).block_until_ready()
            gc.collect(2)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = trace_reduce.load(found[0], None, ("bench.", "hvd.")).host_spans
    outer, = [s for s in spans if s.name == "bench.dispatch"]
    inside = [s for s in spans if s.name == scopes.HOST_GC
              and outer.start <= s.start and s.end <= outer.end]
    assert inside, [s.name for s in spans]
    # the ring has the same collection, on the host clock
    forced = [r for r in _named(scopes.HOST_GC) if r[3]["generation"] == 2]
    assert forced and max(s.dur for s in inside) / 1e9 == pytest.approx(
        forced[-1][2], rel=0.5, abs=2e-3)


# -- compiles -----------------------------------------------------------------

KIND_OF_TOTAL = {"trace": "trace_seconds", "lower": "lower_seconds",
                 "backend_compile": "seconds_total",
                 "cache_read": "cache_read_seconds"}


def test_a_first_call_leaves_compile_records_that_add_up_to_the_totals():
    compile_watch.ensure_installed()
    before = compile_watch.totals()
    since = time.perf_counter()

    @jax.jit
    def logged_once(x):
        return jnp.tanh(x) * 3 + x.sum()

    logged_once(jnp.ones(11)).block_until_ready()
    after = compile_watch.totals()
    got = _named(scopes.HOST_COMPILE, since)
    assert {r[3]["event"] for r in got} >= {"trace", "lower",
                                            "backend_compile"}
    for kind, key in KIND_OF_TOTAL.items():
        if kind == "trace":     # jnp.tanh's own trace, inside logged_once's,
            continue            # is seconds of the total and no record
        assert sum(r[2] for r in got if r[3]["event"] == kind) == \
            pytest.approx(after[key] - before[key], abs=1e-9), kind
    traced = sum(r[2] for r in got if r[3]["event"] == "trace")
    assert 0 < traced < after["trace_seconds"] - before["trace_seconds"]
    assert traced + sum(r[2] for r in got if r[3]["event"] == "lower") == \
        pytest.approx(after["trace_lower_cover_seconds"]
                      - before["trace_lower_cover_seconds"], abs=1e-4)
    # each lies where it happened: it began after the call did, it ended
    # before the call returned
    now = time.perf_counter()
    assert all(since - 1e-3 <= r[1] and r[1] + r[2] <= now + 1e-3
               for r in got)
    assert any(r[3]["function"] and "logged_once" in r[3]["function"]
               for r in got)
    # a second call compiles nothing and logs nothing
    since = time.perf_counter()
    logged_once(jnp.ones(11)).block_until_ready()
    assert _named(scopes.HOST_COMPILE, since) == []
    assert compile_watch.totals() == after


def test_each_of_jaxs_timed_compile_events_is_a_record_and_no_other():
    import jax.monitoring
    compile_watch.ensure_installed()
    since = time.perf_counter()
    for event, seconds in (
            ("/jax/core/compile/jaxpr_trace_duration", 0.5),
            ("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25),
            ("/jax/core/compile/backend_compile_duration", 2.0),
            ("/jax/compilation_cache/cache_retrieval_time_sec", 1.5),
            ("/jax/compilation_cache/compile_time_saved_sec", 9.0)):
        jax.monitoring.record_event_duration_secs(event, seconds,
                                                  fun_name="f")
    got = [r for r in host_log.records() if r[0] == scopes.HOST_COMPILE
           and r[1] >= since - 3.0]
    assert [(r[3]["event"], r[2], r[3]["function"]) for r in got] == [
        ("trace", 0.5, "f"), ("lower", 0.25, "f"),
        ("backend_compile", 2.0, "f"), ("cache_read", 1.5, "f")]
    # start = the event's end less its duration
    assert all(r[1] + r[2] == pytest.approx(since, abs=0.05) for r in got)
    compile_watch.reset_counts()


# -- set-up's seconds: once, by function, inside a trace ------------------------

def _slow_pair(seconds=0.05):
    """An outer jitted function over an inner one, Python time in both."""
    @jax.jit
    def inner_of_the_pair(x):
        time.sleep(seconds)
        return jnp.sin(x)

    @jax.jit
    def outer_of_the_pair(x):
        time.sleep(seconds)
        return inner_of_the_pair(x) + 1.0
    return outer_of_the_pair


def test_a_nested_trace_counts_once_in_the_cover_and_twice_in_the_sum():
    compile_watch.ensure_installed()
    outer = _slow_pair()
    x = jnp.ones(3)                    # (made before: its own little programs)
    compile_watch.reset_counts()
    since = time.perf_counter()
    outer.lower(x)
    wall = time.perf_counter() - since
    totals, table = compile_watch.totals(), compile_watch.by_function()
    # the inner trace's 0.05 s: in its own event and in the outer's
    assert totals["trace_seconds"] >= 0.15
    assert 0.1 <= totals["trace_lower_cover_seconds"] <= wall
    assert totals["trace_lower_cover_seconds"] < \
        totals["trace_seconds"] + totals["lower_seconds"] - 0.04
    inner, outer_ = table["inner_of_the_pair"], table["outer_of_the_pair"]
    assert (inner["traces"], inner["nested_traces"]) == (0, 1)
    assert inner["nested_trace_seconds"] >= 0.05 > inner["trace_seconds"]
    assert (outer_["traces"], outer_["nested_traces"]) == (1, 0)
    assert outer_["trace_seconds"] >= 0.1
    # every function's top-level seconds add up to the cover
    assert sum(e["trace_seconds"] + e["lower_seconds"]
               for e in table.values()) == pytest.approx(
        totals["trace_lower_cover_seconds"], abs=1e-3)
    # and the nested ones (jnp.sin's, the inner function's) are no records
    got = _named(scopes.HOST_COMPILE, since)
    assert [(r[3]["event"], r[3]["function"]) for r in got] == [
        ("trace", "outer_of_the_pair"), ("lower", "outer_of_the_pair")]
    assert not any(r[3].get("nested") for r in got)


def test_by_function_joins_a_functions_trace_lowering_compile_and_read():
    import jax.monitoring
    compile_watch.ensure_installed()
    compile_watch.reset_counts()
    for event, seconds, name in (
            ("/jax/core/compile/jaxpr_trace_duration", 0.5, "f"),
            ("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25,
             "jit(f)"),
            ("/jax/compilation_cache/cache_retrieval_time_sec", 1.5, None),
            ("/jax/core/compile/backend_compile_duration", 2.0, "jit(f)")):
        kw = {} if name is None else {"fun_name": name}
        jax.monitoring.record_event_duration_secs(event, seconds, **kw)
    compile_watch._note_compiling("f")
    assert compile_watch.by_function() == {"f": {
        "compiles": 1, "traces": 1, "trace_seconds": 0.5,
        "nested_traces": 0, "nested_trace_seconds": 0.0,
        "lowers": 1, "lower_seconds": 0.25,
        "backend_compiles": 1, "backend_compile_seconds": 2.0,
        "cache_reads": 1, "cache_read_seconds": 1.5}}
    # one name in every record, the read given to the compile around it
    assert [(r[3]["event"], r[3]["function"])
            for r in _named(scopes.HOST_COMPILE)] == [
        ("trace", "f"), ("lower", "f"), ("cache_read", "f"),
        ("backend_compile", "f")]
    compile_watch.reset_counts()
    assert compile_watch.by_function() == {}


def test_by_function_keeps_the_functions_with_the_most_seconds():
    import jax.monitoring
    compile_watch.ensure_installed()
    compile_watch.reset_counts()

    def traced(name, seconds):
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", seconds, fun_name=name)
        time.sleep(seconds * 1.5)      # (apart: no interval in another)
    traced("the_step", 0.004)
    for i in range(compile_watch.MAX_FUNCTION_LABELS + 3):
        traced(f"little_{i}", 0.0005)
    table = compile_watch.by_function()
    assert len(table) == compile_watch.MAX_FUNCTION_LABELS + 1
    assert table["the_step"]["trace_seconds"] == 0.004
    assert table[compile_watch.OTHER]["traces"] == 3 + 1   # of 32 + 3 + 1
    assert sum(e["trace_seconds"] for e in table.values()) == \
        pytest.approx(compile_watch.totals()["trace_lower_cover_seconds"])
    compile_watch.reset_counts()


def test_a_scope_leaves_a_span_when_its_function_is_traced_and_only_then():
    def spans():
        return [r for r in host_log.records()
                if r[0].startswith(scopes.HOST_TRACE)]

    @jax.jit
    def scoped_once(x):
        with scopes.scope(scopes.MLP):
            time.sleep(0.01)
            return x * 2.0
    text = scoped_once.lower(jnp.ones(4)).as_text(debug_info=True)
    assert scopes.MLP in text          # the named scope, as ever
    (name, _t0, duration, meta), = spans()
    assert name == scopes.HOST_TRACE + "/" + scopes.MLP and meta is None
    assert duration >= 0.01
    scoped_once(jnp.ones(4)).block_until_ready()    # compiles: no new trace
    scoped_once(jnp.ones(4)).block_until_ready()    # the cached executable
    assert len(spans()) == 1


def test_a_kernel_call_site_is_a_span_and_a_count_and_names_its_caller():
    from horovod_tpu.ops.pallas_xent import fused_softmax_xent
    compile_watch.ensure_installed()
    labels = jnp.zeros((128,), jnp.int32)
    logits = jnp.ones((128, 256), jnp.float32)

    @jax.jit
    def a_jitted_call_site(lg):
        return fused_softmax_xent(lg, labels, interpret=True)

    compile_watch.reset_counts()
    host_log.clear()
    jax.jit(lambda lg: a_jitted_call_site(lg) * 2.0).trace(logits)
    kernel = scopes.HOST_TRACE + "/kernel/hvd_fused_xent"
    (name, t0, duration, _m), = [r for r in host_log.records()
                                 if r[0].startswith(scopes.HOST_TRACE)]
    assert name == kernel
    totals = compile_watch.totals()
    assert totals["kernel_traces"] == 1
    assert totals["kernel_trace_seconds"] >= duration > 0
    # the nested trace a span of the program's ended in is a record; the
    # jax.numpy traces around the kernel are not
    nested = [r for r in _named(scopes.HOST_COMPILE) if r[3].get("nested")]
    assert [r[3]["function"] for r in nested] == ["a_jitted_call_site"]
    assert nested[0][1] <= t0 and t0 + duration <= nested[0][1] + nested[0][2]
    assert compile_watch.by_function()["a_jitted_call_site"][
        "nested_traces"] == 1


def test_the_packages_import_is_one_record_and_an_init_one_span():
    import importlib
    before = time.perf_counter()
    importlib.reload(hvd)              # the file again, first line to last
    (name, start, duration, meta), = _named(scopes.HOST_IMPORT)
    assert before <= start <= start + duration <= time.perf_counter()
    assert duration > 0 and meta is None
    hvd.shutdown()
    host_log.clear()
    try:
        hvd.init()
        (_n, t0, seconds, _m), = _named(scopes.HOST_INIT)
        (_n, b0, backend_s, _m), = _named(scopes.HOST_INIT + "/backend")
        assert t0 <= b0 and b0 + backend_s <= t0 + seconds
        hvd.init()                     # initialised already: nothing to time
        assert len(_named(scopes.HOST_INIT)) == 1
    finally:
        hvd.shutdown()


# -- the input path: the program's own step clock ------------------------------

def test_device_prefetch_leaves_a_pair_a_batch_and_its_buffers():
    from horovod_tpu.data.data_loader import device_prefetch
    batches = device_prefetch(
        ({"x": np.full((2,), i)} for i in range(100)), buffer_size=2)
    taken = [next(batches) for _ in range(5)]
    assert [int(b["x"][0]) for b in taken] == [0, 1, 2, 3, 4]
    def spans():     # (reading a batch back compiles: those are records too)
        return [r for r in host_log.records()
                if r[0] in (scopes.INPUT_SOURCE, scopes.INPUT_PLACE)]
    # one source and one place a batch handed out, and the two in flight
    assert [r[0] for r in spans()] == [scopes.INPUT_SOURCE,
                                       scopes.INPUT_PLACE] * (5 + 2)
    starts = [r[1] for r in spans()]
    assert starts == sorted(starts)
    next(batches)
    assert len(spans()) == 2 * (6 + 2)


def test_one_ring_and_four_writers():
    """No second recorder: the ring is appended to in host_log.py alone
    (spans, collections), and host_log.record has two callers,
    compile_watch's listener and the package's import."""
    package = os.path.dirname(os.path.abspath(profiling.__file__))
    root = os.path.dirname(package)
    deques, record_calls = [], []
    for folder, _dirs, files in os.walk(root):
        for f in files:
            if not f.endswith(".py"):
                continue
            path = os.path.join(folder, f)
            with open(path) as fh:
                text = fh.read()
            rel = os.path.relpath(path, root)
            if "RING_RECORDS" in text:
                deques.append(rel)
            if "host_log.record(" in text:
                record_calls.append(rel)
    assert deques == [os.path.join("profiling", "host_log.py")]
    assert sorted(record_calls) == [
        "__init__.py", os.path.join("profiling", "compile_watch.py")]
