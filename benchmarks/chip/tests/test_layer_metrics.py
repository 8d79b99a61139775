"""The door between ``BENCHMARK.json``'s ``per_layer`` entries, the files
of ``layer_metrics/`` and ``run.read_layer_metric``: every entry has its
file, file and entry agree, every ``read`` is a kind the harness
dispatches (an empty ``ctx`` gives None, never a raise), an unknown kind
raises, a reader the harness lacks is found as ``readers/<name>.py``, and
the three kinds PR 34 wired in read the recorded fixture through
``run.read_layer_metric`` as ``scope_reduce`` reads it alone."""

import os
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
HERE = os.path.join(CHIP, "tests")

import run as harness       # noqa: E402  (conftest.py puts CHIP on the path)

BENCH = harness.read_json(ROOT, "BENCHMARK.json")
ENTRIES = {m["name"]: m for m in BENCH["per_layer"]}
FILES = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(CHIP, "layer_metrics")))
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


def _spec(name):
    return harness.read_json(CHIP, "layer_metrics", name + ".json")


def test_every_entry_has_its_file_and_every_file_its_entry():
    assert sorted(ENTRIES) == FILES
    assert not os.path.exists(os.path.join(CHIP, "phase_metrics"))
    assert len(BENCH["per_layer"]) == len(ENTRIES) <= 128


@pytest.mark.parametrize("name", FILES)
def test_file_and_entry_agree(name):
    spec, entry = _spec(name), ENTRIES[name]
    assert set(entry) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for key in ("unit", "better", "source", "layer", "moves"):
        assert spec[key] == entry[key], key
    # (the list of cells stands in the entry alone: a cell joins it with
    # no edit to the file)
    assert "workloads" not in spec
    assert spec["source"] in SOURCES and spec["better"] in ("lower", "higher")
    assert spec["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(entry.get("workloads", ())) <= cells
    assert "workloads" not in entry or entry["workloads"]
    if name.endswith("_roofline"):
        assert spec["unit"] == "%" and "roofline" in spec["read"]


@pytest.mark.parametrize("name", FILES)
def test_every_read_is_a_kind_the_harness_dispatches(name):
    """Nothing recorded, nothing read: None, and no raise."""
    assert harness.read_layer_metric(_spec(name)["read"], {}) is None


def test_every_cell_reports_setup_a_rate_and_a_layer():
    for cell in (w["name"] for w in BENCH["workloads"]):
        layers = {m["name"] for m in harness.metrics_of(
            BENCH, "per_layer", cell)}
        # the laps, the kinds and the program's spans are every cell's
        assert {"setup.import_init_s", "setup.weights_s",
                "setup.reference_s", "setup.first_step_s", "setup.warmup_s",
                "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "step.mixed_ms",
                "step.unscoped_ms", "input.place_ms"} <= layers, cell
    # (since PR 63 a cell that reports its expert layer reports the
    # layer's four parts too; the shared expert is some of those cells')
    lists = {m["name"]: m.get("workloads") for m in BENCH["per_layer"]}
    for part in ("router", "dispatch", "experts", "combine"):
        assert lists[f"step.moe_{part}_ms"] == lists["step.moe_ms"], part
    assert set(lists["step.moe_shared_ms"]) < set(lists["step.moe_ms"])


def test_the_accepted_entries_stand_first_and_as_they_were():
    """The standing list since PR 74's merge: the names PR 63 and the PRs
    after it kept, in the order they had (the nineteen that
    ``retired_pr74.json`` names taken out, nothing moved), then the six
    shares PR 74 made, then the compiled step's two readings of memory
    (PR 75, when ``hbm_compiled_gb`` left ``end_to_end``). A later PR's
    entries stand after them."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names[:5] == ["host.input_ms", "host.dispatch_ms",
                         "setup.compile_s", "setup.compiles_in_window",
                         "step.device_ms"]
    assert names[7:13] == [
        "flash_attention_roofline", "device.idle_pct", "moe.experts_ms",
        "moe_gmm_roofline", "flash_attention_bwd_ms",
        "flash_attention_bwd_roofline"]
    assert names[13:24] == [
        "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "step.mixed_ms",
        "step.unscoped_ms", "step.attention_core_ms", "step.head_ms",
        "input.source_ms", "input.place_ms", "setup.cache_read_s",
        "setup.cache_misses"]
    assert names[64:73] == [
        "step.moe_shared_ms", "flash_attention_fwd_ms", "head_xent_ms",
        "ssm_scan_kernels_ms", "step.attention_recompute_ms",
        "step.ssm_recompute_ms", "head_xent_roofline", "ssm_scan_roofline",
        "flash_attention_adj_ms"]
    assert names[102:108] == [
        "sparse_fwd_roofline", "sparse_mean_roofline", "sparse_bwd_roofline",
        "index_bwd_roofline", "delta_conv_roofline",
        "delta_conv_bwd_roofline"]
    assert names[108:110] == ["hbm.compiled_gb", "hbm.temporaries_gb"]
    assert len(names) == len(set(names))
    retired = harness.read_json(HERE, "retired_pr74.json")
    assert len(retired) == 19 and not set(retired) & set(names)


def test_an_unknown_kind_without_a_reader_raises():
    with pytest.raises(harness.BenchFailure, match="unknown metric source"):
        harness.read_layer_metric({"nothing_known": 1}, {})
    with pytest.raises(harness.BenchFailure, match="no reader 'absent'"):
        harness.read_layer_metric({"reader": "absent"}, {})


def test_a_reader_the_harness_lacks_is_a_file(tmp_path, monkeypatch):
    """``{"reader": "<name>"}`` goes to ``readers/<name>.py:read(read,
    ctx)``, found on the path as a roofline function's file is; it sees
    every duration of the window, not only the medians."""
    readers = tmp_path / "readers"
    readers.mkdir()
    (readers / "throwaway_max.py").write_text(
        "def read(read, ctx):\n"
        "    values = ctx.get('spans_seconds', {}).get(read['span'])\n"
        "    return 1e3 * max(values) if values else None\n")
    (readers / "throwaway_broken.py").write_text("x = 1\n")
    monkeypatch.syspath_prepend(str(tmp_path))
    for module in [m for m in sys.modules if m.split(".")[0] == "readers"]:
        monkeypatch.delitem(sys.modules, module)
    read = {"reader": "throwaway_max", "span": "bench.wait"}
    ctx = {"spans_seconds": {"bench.wait": [0.001, 0.25, 0.002]}}
    assert harness.read_layer_metric(read, ctx) == 250.0
    assert harness.read_layer_metric(read, {}) is None
    with pytest.raises(harness.BenchFailure, match="throwaway_broken"):
        harness.read_layer_metric({"reader": "throwaway_broken"}, {})


def test_the_counters_a_traced_run_offers():
    totals = {"compiles": 7, "seconds_total": 3.5, "cache_misses": 9,
              "trace_seconds": 1.25, "lower_seconds": 0.5,
              "cache_read_seconds": 2.0, "persistent_cache_hits": 6,
              "persistent_cache_misses": 1, "a_later_total": 4.0,
              "not_a_number": "x", "a_flag": True}
    split = {"import_init_s": 5.0, "weights_s": 1.0, "reference_s": 9.0,
             "optimizer_and_first_step_s": 6.0, "warmup_s": 2.0}
    got = harness.setup_counters(totals, split, 0)
    # today's two, the two the phase files name, every total, the laps
    # (the sum of trace_seconds and lower_seconds went with
    # setup.trace_lower_s, PR 74: the totals it was made of stay)
    assert got["compiles_in_window"] == 0 and got["setup_compile_s"] == 3.5
    assert "setup_trace_lower_s" not in got
    assert (got["setup_trace_seconds"], got["setup_lower_seconds"]) == (
        1.25, 0.5)
    assert got["setup_cache_read_s"] == 2.0
    assert got["setup_persistent_cache_misses"] == 1
    assert got["setup_a_later_total"] == 4.0 and got["setup_compiles"] == 7
    assert "setup_not_a_number" not in got and "setup_a_flag" not in got
    assert {k: v for k, v in got.items() if k.startswith("setup_split_")} \
        == {f"setup_split_{k}": v for k, v in split.items()}
    for name in ("setup.import_init_s", "setup.weights_s",
                 "setup.reference_s", "setup.first_step_s", "setup.warmup_s",
                 "setup.cache_read_s",
                 "setup.cache_misses", "setup.compile_s",
                 "setup.compiles_in_window"):
        assert harness.read_layer_metric(
            _spec(name)["read"], {"counters": got}) is not None, name
    # a count stays the whole number it was; only a read that says "per"
    # divides (the compiled step's bytes as GB)
    assert isinstance(harness.read_layer_metric(
        {"counter": "setup_compiles"}, {"counters": got}), int)
    assert harness.read_layer_metric(
        {"counter": "setup_compiles", "per": 2}, {"counters": got}) == 3.5
    # a program without the split leaves those metrics out
    old = harness.setup_counters({"compiles": 1, "seconds_total": 1.0},
                                 split, 0)
    for name in ("setup.cache_read_s", "setup.cache_misses"):
        assert harness.read_layer_metric(
            _spec(name)["read"], {"counters": old}) is None, name


@pytest.fixture(scope="module")
def recorded():
    """The flagship's step recorded on a v5e in PR 24, as a traced run's
    ``ctx`` holds it."""
    import scope_reduce
    import trace_reduce
    with open(os.path.join(HERE, "fixture_scopes.hlo.txt")) as f:
        text = f.read()
    return {"trace": trace_reduce.load(
                os.path.join(HERE, "fixture_scopes.xplane.pb"), None,
                ("bench.", "hvd.")),
            "hlo_text": text, "scopes": scope_reduce.parse_hlo(text),
            "trace_steps": 3}


@pytest.mark.parametrize("name, want", [
    ("step.fwd_ms", 115187.0), ("step.bwd_ms", 134282.0),
    ("step.opt_ms", 21648.0), ("step.mixed_ms", 6767.0),
    ("step.unscoped_ms", 82487.0)])
def test_the_kinds_through_the_harness_on_the_recorded_step(
        recorded, name, want):
    got = harness.read_layer_metric(_spec(name)["read"], dict(recorded))
    assert got == pytest.approx(want / 3 * 1e-6, rel=1e-12)


@pytest.mark.parametrize("name", [
    "step.attention_core_ms", "step.head_ms", "input.source_ms",
    "input.place_ms", "step.moe_ms", "step.loop_ms",
    "step.attention_window_ms"])
def test_phases_and_spans_through_the_harness_on_the_recorded_step(
        recorded, name):
    import scope_reduce
    read = _spec(name)["read"]
    got = harness.read_layer_metric(read, dict(recorded))
    assert got == scope_reduce.read_metric(read, dict(recorded))
    # the recorded program is the flagship without experts, loop or
    # layer kinds: those phases are not there and the metric is left out
    there = name in ("step.attention_core_ms", "step.head_ms",
                     "input.source_ms", "input.place_ms")
    assert (got is not None and got > 0) if there else got is None
    # without the text the device metrics are left out, the spans stay
    bare = {k: v for k, v in recorded.items()
            if k not in ("hlo_text", "scopes")}
    got = harness.read_layer_metric(read, bare)
    assert (got is not None) == name.startswith("input.")


def test_the_report_a_traced_runs_record_keeps(recorded):
    import scope_reduce
    doc = scope_reduce.report(recorded["trace"], recorded["scopes"], 3)
    ident = doc["identity"]
    per_step = lambda ns: ns / 3 / 1e6                          # noqa: E731
    assert ident["kinds_ms"] == {
        "fwd": per_step(115187.0), "bwd": per_step(134282.0),
        "opt": per_step(21648.0), "mixed": per_step(6767.0),
        "unscoped": per_step(82487.0)}
    assert ident["step.device_ms_mean"] == per_step(362527.0)
    assert ident["collective.exposed_ms"] == 0.0
    assert ident["container_only_ms"] == pytest.approx(
        per_step(362527.0 - 360371.0))
    assert ident["kinds_overlap_ms"] == pytest.approx(0.0, abs=1e-12)
    assert ident["kinds_plus_exposed_over_device"] == pytest.approx(
        360371.0 / 362527.0)
    assert doc["unmatched_instructions"] == 0
    assert sum(doc["direction_sets_ms"].values()) == pytest.approx(
        sum(ident["kinds_ms"].values()))
    assert [m[0] for m in doc["mixed"]] == ["fusion (f32[512,256],..)",
                                            "fusion (f32[256],..)"]
    assert doc["unscoped"] and len(doc["unscoped"]) <= 20
    # what each phase's cover is made of: the kernels lead their phases
    assert sorted(doc["phase_top"]) == [
        "hvd.attention", "hvd.attention.core", "hvd.embed", "hvd.head",
        "hvd.layers", "hvd.mlp", "hvd.optimizer"]
    assert all(0 < len(v) <= 5 for v in doc["phase_top"].values())
    assert any(name.startswith("hvd_flash_attention")
               for name, _s in doc["phase_top"]["hvd.attention.core"])
