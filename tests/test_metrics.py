"""Unit tests for the metrics & telemetry subsystem (docs/OBSERVABILITY.md):
registry semantics (counter/gauge/histogram, snapshot merge), Prometheus
text rendering, the per-worker HTTP exporter round-trip, the engine-counter
derived view, and the train-loop StepTimer. Pure-host — the multi-process
live-scrape and straggler-attribution paths are covered by
test_core_multiprocess.py."""

import json
import math
import threading
import urllib.request

import pytest

from horovod_tpu.metrics.engine import EngineCollector, derived_ratios
from horovod_tpu.metrics.exporter import MetricsExporter
from horovod_tpu.metrics.registry import (DEFAULT_BUCKETS, Registry,
                                          render_prometheus)


# -- registry ---------------------------------------------------------------

def test_counter_semantics():
    reg = Registry()
    c = reg.counter("requests", help="total requests")
    c.inc()
    c.inc(4.5)
    assert c.value == 5.5
    with pytest.raises(ValueError):
        c.inc(-1)
    assert reg.counter("requests") is c  # get-or-create returns same obj


def test_gauge_semantics():
    reg = Registry()
    g = reg.gauge("queue_depth")
    g.set(7)
    g.inc(3)
    assert g.value == 10.0
    with pytest.raises(ValueError):
        reg.gauge("bad", agg="median")


def test_type_conflict_raises():
    reg = Registry()
    reg.counter("x")
    with pytest.raises(ValueError):
        reg.gauge("x")


def test_option_conflict_raises_omitted_matches():
    reg = Registry()
    g = reg.gauge("thr", agg="sum")
    assert reg.gauge("thr") is g  # omitted agg = don't-care re-get
    with pytest.raises(ValueError):
        reg.gauge("thr", agg="last")
    h = reg.histogram("lat")
    assert reg.histogram("lat") is h
    with pytest.raises(ValueError):
        reg.histogram("lat", buckets=[1.0, 2.0])


def test_labels_key_canonical_order():
    reg = Registry()
    a = reg.counter("c", labels={"b": "2", "a": "1"})
    b = reg.counter("c", labels={"a": "1", "b": "2"})
    assert a is b
    assert 'c{a="1",b="2"}' in reg.snapshot()


def test_histogram_buckets_and_moments():
    reg = Registry()
    h = reg.histogram("lat", buckets=[0.1, 1.0, 10.0])
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v)
    s = h.snapshot()
    assert s["counts"] == [1, 2, 1, 1]  # last slot = +Inf overflow
    assert s["count"] == 5
    assert abs(s["sum"] - 56.05) < 1e-9


def test_histogram_default_buckets_log_scale():
    assert DEFAULT_BUCKETS[0] == pytest.approx(1e-3)
    ratios = {DEFAULT_BUCKETS[i + 1] / DEFAULT_BUCKETS[i]
              for i in range(len(DEFAULT_BUCKETS) - 1)}
    assert ratios == {2.0}


def test_histogram_boundary_value_lands_in_le_bucket():
    """A value exactly on a bound counts toward that bound's bucket
    (Prometheus le = less-or-equal semantics)."""
    reg = Registry()
    h = reg.histogram("b", buckets=[1.0, 2.0])
    h.observe(1.0)
    assert h.snapshot()["counts"] == [1, 0, 0]


def test_histogram_rejects_bad_buckets():
    reg = Registry()
    with pytest.raises(ValueError):
        reg.histogram("h", buckets=[0.0, 1.0])
    with pytest.raises(ValueError):
        reg.histogram("h2", buckets=[1.0, math.inf])


def test_snapshot_merge_counters_histograms_add():
    def snap(n):
        reg = Registry()
        reg.counter("steps").inc(n)
        h = reg.histogram("t", buckets=[1.0, 2.0])
        h.observe(0.5 * n)
        return reg.snapshot()

    merged = Registry.merge([snap(1), snap(2), snap(4)])
    assert merged["steps"]["value"] == 7
    assert merged["t"]["count"] == 3
    assert merged["t"]["counts"] == [2, 1, 0]  # 0.5, 1.0 <= 1.0 < 2.0


def test_snapshot_merge_gauge_aggs():
    def snap(v):
        reg = Registry()
        reg.gauge("thr", agg="sum").set(v)
        reg.gauge("mfu", agg="mean").set(v / 10.0)
        reg.gauge("peak", agg="max").set(v)
        reg.gauge("last").set(v)
        return reg.snapshot()

    merged = Registry.merge([snap(1.0), snap(2.0), snap(3.0)])
    assert merged["thr"]["value"] == 6.0
    assert merged["mfu"]["value"] == pytest.approx(0.2)
    assert merged["peak"]["value"] == 3.0
    assert merged["last"]["value"] == 3.0


def test_snapshot_merge_mismatches_raise():
    ra, rb = Registry(), Registry()
    ra.counter("m")
    rb.gauge("m")
    with pytest.raises(ValueError):
        Registry.merge([ra.snapshot(), rb.snapshot()])
    rc, rd = Registry(), Registry()
    rc.histogram("h", buckets=[1.0])
    rd.histogram("h", buckets=[2.0])
    with pytest.raises(ValueError):
        Registry.merge([rc.snapshot(), rd.snapshot()])


def test_concurrent_increments_are_lossless():
    reg = Registry()
    c = reg.counter("n")
    threads = [threading.Thread(target=lambda: [c.inc() for _ in range(1000)])
               for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000


# -- prometheus rendering ---------------------------------------------------

def _parse_prometheus(text):
    """Minimal text-format v0.0.4 parser: {series_key: value}."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        key, val = line.rsplit(" ", 1)
        out[key] = float(val)
    return out


def test_render_prometheus_counter_gauge():
    reg = Registry()
    reg.counter("hvd_steps_total", help="steps").inc(3)
    reg.gauge("hvd_mfu").set(0.42)
    text = render_prometheus(reg.snapshot())
    assert "# HELP hvd_steps_total steps" in text
    assert "# TYPE hvd_steps_total counter" in text
    assert "# TYPE hvd_mfu gauge" in text
    series = _parse_prometheus(text)
    assert series["hvd_steps_total"] == 3
    assert series["hvd_mfu"] == 0.42


def test_render_prometheus_histogram_cumulative():
    reg = Registry()
    h = reg.histogram("hvd_step_time_seconds", buckets=[0.1, 1.0])
    for v in (0.05, 0.5, 5.0):
        h.observe(v)
    series = _parse_prometheus(render_prometheus(reg.snapshot()))
    assert series['hvd_step_time_seconds_bucket{le="0.1"}'] == 1
    assert series['hvd_step_time_seconds_bucket{le="1"}'] == 2
    assert series['hvd_step_time_seconds_bucket{le="+Inf"}'] == 3
    assert series["hvd_step_time_seconds_count"] == 3
    assert series["hvd_step_time_seconds_sum"] == pytest.approx(5.55)


def test_render_prometheus_labeled_histogram_and_escaping():
    reg = Registry()
    reg.histogram("h", labels={"rank": "0"}, buckets=[1.0]).observe(0.5)
    reg.gauge("g", labels={"path": 'a"b\nc'}).set(1)
    text = render_prometheus(reg.snapshot())
    assert 'h_bucket{rank="0",le="1"}' in text
    assert 'path="a\\"b\\nc"' in text


# -- engine derived view ----------------------------------------------------

def test_derived_ratios():
    c = {"cache_hits": 30, "cache_misses": 10, "responses_executed": 20,
         "fused_units": 5, "tensors_fused": 40}
    d = derived_ratios(c)
    assert d["cache_hit_rate"] == pytest.approx(0.75)
    assert d["fusion_ratio"] == pytest.approx(0.25)
    assert d["tensors_per_fused_unit"] == pytest.approx(8.0)
    assert derived_ratios({}) == {}  # no division by zero on empty engine


def test_engine_collector_mirrors_counters_and_rates():
    reg = Registry()
    counters = {"cache_hits": 8, "cache_misses": 2, "bytes_allreduced": 0}
    collector = EngineCollector(lambda: dict(counters), registry=reg)
    collector.collect()
    snap = reg.snapshot()
    assert snap["hvd_engine_cache_hits"]["value"] == 8
    assert snap["hvd_engine_cache_hit_rate"]["value"] == pytest.approx(0.8)
    # second scrape computes a bytes/s rate from the delta
    collector._prev_t -= 2.0  # pretend the first scrape was 2s ago
    counters["bytes_allreduced"] = 1 << 20
    collector.collect()
    rate = reg.snapshot()["hvd_engine_bytes_allreduced_per_second"]["value"]
    assert 0 < rate <= (1 << 20)


def test_engine_collector_autotune_decision_gauges():
    """The C++ autotuner's live decisions (ISSUE 8 satellite): counter
    keys with the autotune_ prefix surface as first-class
    hvd_autotune_* gauges — what the tuner PICKED — instead of being
    mirrored as cumulative hvd_engine_* counters."""
    reg = Registry()
    counters = {"cycles": 5,
                "autotune_fusion_bytes": 32 * 1024 * 1024,
                "autotune_cycle_ms": 2.5,
                "autotune_hierarchical": 1,
                "autotune_cache_enabled": 0}
    EngineCollector(lambda: dict(counters), registry=reg).collect()
    snap = reg.snapshot()
    assert snap["hvd_autotune_fusion_bytes"]["value"] == 32 * 1024 * 1024
    assert snap["hvd_autotune_cycle_ms"]["value"] == pytest.approx(2.5)
    assert snap["hvd_autotune_hierarchical"]["value"] == 1
    assert snap["hvd_autotune_cache_enabled"]["value"] == 0
    assert "hvd_engine_autotune_fusion_bytes" not in snap
    assert snap["hvd_engine_cycles"]["value"] == 5


def test_engine_collector_straggler_gauges():
    reg = Registry()
    report = {"tensors_timed": 2, "total_wait_seconds": 3.5,
              "ranks": {"1": {"wait_seconds": 3.0, "held_count": 2}}}
    EngineCollector(lambda: {}, registry=reg,
                    stragglers_fn=lambda: report).collect()
    snap = reg.snapshot()
    assert snap['hvd_straggler_wait_seconds{rank="1"}']["value"] == 3.0
    assert snap['hvd_straggler_held_count{rank="1"}']["value"] == 2


def test_engine_collector_survives_failing_source():
    reg = Registry()
    def boom():
        raise RuntimeError("engine gone")
    EngineCollector(boom, registry=reg).collect()  # must not raise
    assert reg.snapshot() == {}


# -- exporter round-trip ----------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as r:
        return r.status, r.headers.get("Content-Type"), r.read().decode()


def test_exporter_scrape_roundtrip():
    reg = Registry()
    reg.counter("hvd_steps_total", help="steps").inc(2)
    reg.histogram("hvd_step_time_seconds", buckets=[0.1, 1.0]).observe(0.5)
    exp = MetricsExporter(registry=reg, port=0)
    exp.start()
    try:
        status, ctype, body = _get(exp.port, "/metrics")
        assert status == 200 and "0.0.4" in ctype
        series = _parse_prometheus(body)
        assert series["hvd_steps_total"] == 2
        assert series['hvd_step_time_seconds_bucket{le="+Inf"}'] == 1
        status, ctype, body = _get(exp.port, "/healthz")
        assert status == 200 and json.loads(body) == {"status": "ok"}
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(exp.port, "/nope")
        assert e.value.code == 404
    finally:
        exp.stop()


def test_exporter_collectors_run_per_scrape_and_failures_skipped():
    reg = Registry()
    calls = []

    def refresh():
        calls.append(1)
        reg.gauge("live").set(len(calls))

    def broken():
        raise RuntimeError("collector bug")

    exp = MetricsExporter(registry=reg, port=0,
                          collectors=[refresh, broken])
    exp.start()
    try:
        _get(exp.port, "/metrics")
        _, _, body = _get(exp.port, "/metrics")
        assert _parse_prometheus(body)["live"] == 2  # ran once per scrape
    finally:
        exp.stop()


def test_exporter_unhealthy_health_fn_returns_503():
    exp = MetricsExporter(registry=Registry(), port=0,
                          health_fn=lambda: {"status": "shutdown"})
    exp.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(exp.port, "/healthz")
        assert e.value.code == 503
    finally:
        exp.stop()


def test_exporter_stop_without_start_returns():
    exp = MetricsExporter(registry=Registry(), port=0)
    t = threading.Thread(target=exp.stop, daemon=True)
    t.start()
    t.join(timeout=5)
    assert not t.is_alive()  # shutdown() must not wait on serve_forever()


# -- step timer -------------------------------------------------------------

def test_step_timer_records_histogram_and_throughput():
    from horovod_tpu.train.callbacks import StepTimer
    reg = Registry()
    timer = StepTimer(unit="images", registry=reg)
    with timer.step(units=32):
        pass
    timer.start_step()
    dt = timer.end_step(units=32)
    assert dt is not None and dt >= 0
    snap = reg.snapshot()
    assert snap["hvd_steps_total"]["value"] == 2
    assert snap["hvd_images_total"]["value"] == 64
    assert snap["hvd_step_time_seconds"]["count"] == 2
    assert snap["hvd_images_per_second"]["value"] > 0


def test_step_timer_failed_step_not_recorded():
    from horovod_tpu.train.callbacks import StepTimer
    reg = Registry()
    timer = StepTimer(registry=reg)
    with pytest.raises(RuntimeError):
        with timer.step(units=8):
            raise RuntimeError("oom")
    assert reg.snapshot()["hvd_steps_total"]["value"] == 0
    assert timer.end_step() is None  # the aborted step left no open timer


def test_step_timer_mfu_unknown_peak_stays_none():
    from horovod_tpu.train.callbacks import StepTimer
    reg = Registry()
    timer = StepTimer(flops_per_step=1e12, registry=reg)
    timer._peak = None  # device peak unknown (e.g. CPU host)
    timer.start_step()
    timer.end_step(units=1)
    assert timer.last_mfu is None  # never report the gauge's 0.0 default
    timer._peak = 2e12
    timer.start_step()
    timer.end_step(units=1)
    assert timer.last_mfu is not None and timer.last_mfu > 0
    assert reg.snapshot()["hvd_mfu"]["value"] == pytest.approx(
        timer.last_mfu)


def test_telemetry_callback_hooks():
    from horovod_tpu.train.callbacks import TelemetryCallback
    reg = Registry()
    cb = TelemetryCallback(units_per_step=16, unit="tokens", registry=reg)
    for _ in range(3):
        cb.on_step_begin()
        cb.on_step_end()
    snap = reg.snapshot()
    assert snap["hvd_steps_total"]["value"] == 3
    assert snap["hvd_tokens_total"]["value"] == 48
    assert cb.on_epoch_end({"loss": 1.0}) == {"loss": 1.0}


# -- registry get/drop_prefix (fleet + re-mesh hygiene) ---------------------

def test_registry_get_never_creates():
    reg = Registry()
    assert reg.get("absent") is None
    assert "absent" not in reg.snapshot()
    c = reg.counter("present", labels={"a": "1"})
    assert reg.get("present", labels={"a": "1"}) is c
    assert reg.get("present") is None  # label set is part of identity


def test_registry_drop_prefix():
    reg = Registry()
    reg.gauge("hvd_engine_cycles").set(1)
    reg.gauge("hvd_engine_cache_hits").set(2)
    reg.counter("hvd_stall_warnings_total").inc(3)
    assert reg.drop_prefix("hvd_engine_") == 2
    snap = reg.snapshot()
    assert "hvd_engine_cycles" not in snap
    # cumulative counters under other prefixes survive the re-mesh
    assert snap["hvd_stall_warnings_total"]["value"] == 3


# -- /healthz liveness (ISSUE 7 satellite) ----------------------------------

def test_watchdog_liveness_doc():
    from horovod_tpu.diagnostics import watchdog as wd
    wd.reset()
    try:
        live = wd.liveness()
        assert live["last_step"] is None
        assert live["last_step_age_s"] is None  # still compiling != stalled
        wd.notify_progress(7)
        live = wd.liveness()
        assert live["last_step"] == 7
        assert 0 <= live["last_step_age_s"] < 5
    finally:
        wd.reset()


def _health_doc_like_worker(state_initialized, age_s, timeout_s,
                            last_step):
    """The exporter's health rule, distilled: stalled only when steps
    HAVE flowed and then stopped past the watchdog threshold."""
    status = "ok" if state_initialized else "shutdown"
    if status == "ok" and timeout_s and timeout_s > 0 \
            and age_s is not None and age_s > timeout_s:
        status = "stalled"
    return status


def test_healthz_statuses():
    assert _health_doc_like_worker(True, None, 600, None) == "ok"
    assert _health_doc_like_worker(True, 10, 600, 5) == "ok"
    assert _health_doc_like_worker(True, 700, 600, 5) == "stalled"
    assert _health_doc_like_worker(True, 700, 0, 5) == "ok"  # disarmed
    assert _health_doc_like_worker(False, 1, 600, 5) == "shutdown"


def test_healthz_liveness_served_end_to_end(monkeypatch):
    """A live exporter built the way hvd.init builds it (same health
    closure semantics): reports last-step age, flips to 503 once the
    age crosses the threshold."""
    from horovod_tpu.diagnostics import watchdog as wd

    class _State:
        initialized = True
        rank, size, hostname = 0, 1, "test-host"
        backend = None

    state = _State()
    wd.reset()

    def health():
        doc = {"status": "ok" if state.initialized else "shutdown",
               "rank": state.rank, "size": state.size}
        live = wd.liveness()
        doc["last_step"] = live["last_step"]
        doc["last_step_age_s"] = live["last_step_age_s"]
        doc["watchdog"] = {"armed": live["armed"],
                           "timeout_s": live["timeout_s"]}
        age = live["last_step_age_s"]
        if doc["status"] == "ok" and live["timeout_s"] > 0 \
                and age is not None and age > live["timeout_s"]:
            doc["status"] = "stalled"
        return doc

    exp = MetricsExporter(registry=Registry(), port=0, health_fn=health)
    exp.start()
    try:
        # no steps yet: ok (compiling is not a stall)
        status, _, body = _get(exp.port, "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["last_step"] is None

        wd.notify_progress(41)
        status, _, body = _get(exp.port, "/healthz")
        doc = json.loads(body)
        assert status == 200 and doc["last_step"] == 41
        assert doc["last_step_age_s"] < 5
        assert doc["watchdog"]["timeout_s"] == 600.0

        # age the last step past the threshold: 503 + "stalled"
        monkeypatch.setenv("HVD_TPU_WATCHDOG_SECONDS", "0.01")
        import time
        time.sleep(0.05)
        with pytest.raises(urllib.error.HTTPError) as e:
            _get(exp.port, "/healthz")
        assert e.value.code == 503
        assert json.loads(e.value.read())["status"] == "stalled"

        # disarmed watchdog (0) never reports stalled
        monkeypatch.setenv("HVD_TPU_WATCHDOG_SECONDS", "0")
        status, _, body = _get(exp.port, "/healthz")
        assert status == 200
    finally:
        exp.stop()
        wd.reset()


@pytest.mark.parametrize("kind,peak", [
    ("TPU v5 lite", 197e12), ("TPU v5e", 197e12), ("TPU v4", 275e12)])
def test_peak_flops_known_tpu_kinds(kind, peak):
    from horovod_tpu.metrics.mfu import peak_flops
    assert peak_flops(kind) == peak


def test_peak_flops_unknown_kind_raises():
    """A device that is not in the table is an error, not a default."""
    from horovod_tpu.metrics.mfu import peak_flops
    with pytest.raises(LookupError, match="TPU v99"):
        peak_flops("TPU v99")


def test_device_peak_flops_is_none_only_off_tpu(monkeypatch):
    import jax
    from horovod_tpu.metrics import mfu

    assert mfu.device_peak_flops() is None  # the CPU test mesh

    class _Dev:
        platform = "tpu"
        device_kind = "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(LookupError):
        mfu.device_peak_flops()
    _Dev.device_kind = "TPU v5 lite"
    assert mfu.device_peak_flops() == 197e12
