"""The harness end to end at tiny sizes on the CPU: a cell, a
configuration, per-layer metrics of every ``read`` kind and a reader of
its own (``readers/<name>.py``) added by new files and new entries alone,
and the reference check failing when the two sides compute different
things. A CPU trace has a host plane and no device plane: the program's
host span and the counters are read, a device metric is left out."""

import json
import math
import os
import shutil
import subprocess
import sys

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _write(doc, *parts):
    with open(os.path.join(*parts), "w") as f:
        json.dump(doc, f)


def test_a_cell_a_config_and_a_metric_are_added_by_files_alone(tmp_path):
    root = str(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "tests", "__pycache__", "tools"))

    # a configuration: its file of sizes (here BERT-Large's, one layer
    # less at the tiny size), served by the adapter that is there
    config = _read(CHIP, "configs", "bert-large.json")
    config["tiny"] = {**config["tiny"], "num_hidden_layers": 1}
    _write(config, chip, "configs", "throwaway.json")
    # a traffic mix: a data file of parameters
    job = _read(CHIP, "workloads", "train.s128.b64.json")
    job["tiny"] = {**job["tiny"], "seq_len": 8, "batch_per_chip": 2}
    _write(job, chip, "workloads", "train.throwaway.json")
    # a per-layer metric over a reader that is there
    _write({"layer": "host loop", "unit": "ms", "better": "lower",
            "source": "host_clock", "moves": "tokens_per_s_per_chip",
            "read": {"span": "bench.wait", "reduce": "median_ms"}},
           chip, "layer_metrics", "host.wait_ms.json")
    # ... over the program's own names: a scope, a host span, a counter
    # the harness never named (any total of compile_watch, any lap) ...
    step = {"layer": "train step", "unit": "ms", "better": "lower",
            "moves": "tokens_per_s_per_chip"}
    _write({**step, "source": "device_trace", "read": {
        "trace_scope": {"phase": "hvd.mlp"}, "per_step": True,
        "scale": 1e-6}}, chip, "layer_metrics", "step.throwaway_mlp_ms.json")
    _write({**step, "layer": "host loop", "source": "program_span", "read": {
        "host_span": "hvd.input.place", "reduce": "median_ms"}},
        chip, "layer_metrics", "input.throwaway_place_ms.json")
    _write({**step, "layer": "job set-up", "unit": "count",
            "source": "program_counter", "moves": "setup_s",
            "read": {"counter": "setup_persistent_cache_hits"}},
           chip, "layer_metrics", "setup.throwaway_hits.json")
    # ... and over a reader the harness lacks, a file found by its name,
    # which sees every duration of the window and not only the median
    os.makedirs(os.path.join(chip, "readers"), exist_ok=True)
    with open(os.path.join(chip, "readers", "throwaway_max.py"), "w") as f:
        f.write("def read(read, ctx):\n"
                "    assert len(ctx['step_done_at_s']) > 0\n"
                "    values = ctx['spans_seconds'].get(read['span'])\n"
                "    return 1e3 * max(values) if values else None\n")
    _write({**step, "layer": "host loop", "source": "host_clock", "read": {
        "reader": "throwaway_max", "span": "bench.wait"}},
        chip, "layer_metrics", "host.throwaway_wait_max_ms.json")
    added = ("host.wait_ms", "step.throwaway_mlp_ms",
             "input.throwaway_place_ms", "setup.throwaway_hits",
             "host.throwaway_wait_max_ms")
    bench = _read(ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": "throwaway", "source": config["source"],
        "file": "benchmarks/chip/configs/throwaway.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.s8", "config": "throwaway",
        "traffic": "train.throwaway", "chips": 1, "why": "test"})
    for name in added:
        spec = _read(chip, "layer_metrics", name + ".json")
        bench["per_layer"].append({
            "name": name, **{k: spec[k] for k in (
                "unit", "better", "source", "layer", "moves")},
            "workloads": ["throwaway.s8"]})
    _write(bench, root, "BENCHMARK.json")

    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    for trace, expect in ((1, "rehearsal.host.wait_ms"),
                          (0, "rehearsal.tokens_per_s_per_chip")):
        run = subprocess.run(
            [sys.executable, os.path.join(chip, "run.py"), "--workload",
             "throwaway.s8", "--seed", "3", "--seconds", "1", "--trace",
             str(trace), "--rehearse", "--out", os.path.join(root, "out")],
            env=env, capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        lines = run.stdout.strip().splitlines()
        result = json.loads(lines[-1])
        assert list(result) == ["correct", "attempted", "failed", "metrics",
                                "device", "compared"]
        # each number compared beside its limit, last in the line and
        # last on standard error
        assert all(math.isfinite(v) and v <= limit
                   for v, limit in result["compared"].values())
        assert {"loss_rel", "nonfinite_losses", "compiles_in_window"} <= set(
            result["compared"])
        tail = run.stderr.strip().splitlines()[-len(result["compared"]):]
        assert [line.split()[1] for line in tail] == list(result["compared"])
        assert result["correct"] is True and result["failed"] == 0
        assert result["attempted"] > 0
        assert expect in result["metrics"]
        if trace:
            got = {k[len("rehearsal."):]: v["value"]
                   for k, v in result["metrics"].items()}
            # the file-found reader, the program's span and counters
            assert got["host.throwaway_wait_max_ms"] >= got["host.wait_ms"]
            assert got["input.throwaway_place_ms"] == got["input.place_ms"] \
                > 0
            assert got["input.source_ms"] > 0
            assert got["setup.throwaway_hits"] == 0     # no cache here
            assert got["setup.cache_misses"] == 0
            assert got["setup.trace_lower_cover_s"] > 0
            laps = sum(got[f"setup.{lap}_s"] for lap in (
                "import_init", "weights", "reference", "first_step",
                "warmup"))
            setup_s = next(json.loads(line)["setup_s"] for line in lines
                           if '"event": "setup"' in line)
            assert 0 <= setup_s - laps < 0.1
            # no device plane on the CPU: the device metrics are left out
            assert not [k for k in got if k.startswith("step.")]
            record = _read(root, "out", "throwaway.s8.seed3.trace1.json")
            assert record["phases"]["program_has_scopes"] is True
            assert record["phases"]["hlo_text_bytes"] > 0
            # the compiled step's memory: every cell's, a later one's too,
            # the record's bytes to the digit; no limit is held off the chip
            nbytes = record["compiled_step_bytes"]
            assert got["hbm.compiled_gb"] == nbytes["total"] / 1e9
            assert got["hbm.temporaries_gb"] == nbytes["temp"] / 1e9 > 0
            assert record["hbm_fit"] is None
        else:
            assert set(result["metrics"]) == {
                "rehearsal.tokens_per_s_per_chip", "rehearsal.setup_s"}
        # nothing of a rehearsal stands under a metric's own name
        assert all(k.startswith("rehearsal.") for k in result["metrics"])
        # every earlier line names the device it ran on
        for line in lines[:-1]:
            doc = json.loads(line)
            assert {"platform", "kind", "count"} <= set(doc)

    # without --rehearse there is no CPU fallback: non-zero, no result
    run = subprocess.run(
        [sys.executable, os.path.join(chip, "run.py"), "--workload",
         "throwaway.s8", "--seed", "3", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert run.stdout.strip() == ""


class _Memory:
    """A ``memory_analysis()`` written by hand: arguments donated in place
    but for a scalar's worth of outputs, as a train step's are."""

    def __init__(self, argument: int, temp: int):
        self.argument_size_in_bytes = argument
        self.output_size_in_bytes = argument
        self.alias_size_in_bytes = argument - 512
        self.temp_size_in_bytes = temp


def test_the_fit_is_held_to_the_limit_less_the_margin():
    """Memory is a fit: a compiled step inside the margin under the chip's
    limit gives no result and the failure names both numbers; one byte
    outside the margin passes, whatever a parent took."""
    import pytest
    import run as harness
    peaks = _read(CHIP, "peaks.json")["TPU v5 lite"]
    limit, margin = (peaks["hbm_compile_limit_bytes"],
                     peaks["hbm_fit_margin_bytes"])
    argument = 8_000_000_000

    def step(total):
        return harness.step_bytes(_Memory(argument, total - argument - 512))

    edge = limit - margin
    assert step(edge)["total"] == edge
    assert harness.hold_fit(step(edge), peaks) == {
        "limit_bytes": limit, "margin_bytes": margin,
        "headroom_bytes": margin}
    for total in (edge + 1, limit, limit + margin):
        with pytest.raises(harness.BenchFailure) as failure:
            harness.hold_fit(step(total), peaks)
        said = str(failure.value)
        assert f"{total / 1e9:.3f} GB" in said, said
        assert f"{limit / 1e9:.3f}" in said and f"{margin / 1e9:.3f} GB" in said
    # the margin's own terms (PERF.md section 2): at least twice the largest
    # move the heap's packing alone has made (0.16 GB), and no cell the
    # benchmark has stands inside it (the fullest: 15.059 GB)
    assert margin >= 2 * 0.16e9 and 15_058_583_552 + margin < limit
    # the limit is the compiler's "15.75G" as the device states it: GiB (16
    # less the runtime's 258 MiB), not 15.75 GB
    assert limit == 16 * 2**30 - 258 * 2**20 - 512


def test_memory_is_no_end_to_end_metric_and_every_cell_reads_it():
    """``end_to_end`` holds no reading of memory (a bound against a parent
    refused every trade of memory for time that fits the chip); the two
    ``hbm.*`` readings are every cell's: no ``workloads`` list, so a cell a
    later PR adds reports them too."""
    import run as harness
    bench = _read(ROOT, "BENCHMARK.json")
    assert [m["name"] for m in bench["end_to_end"]] == [
        "tokens_per_s_per_chip", "mfu_pct", "setup_s"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    counters = {"step_bytes_total": 10_892_703_744,
                "step_bytes_temp": 6_869_846_016}
    for name, want in (("hbm.compiled_gb", 10.892703744),
                       ("hbm.temporaries_gb", 6.869846016)):
        assert "workloads" not in entries[name]
        assert entries[name]["source"] == "program_counter"
        assert entries[name]["moves"] == "tokens_per_s_per_chip"
        spec = _read(CHIP, "layer_metrics", name + ".json")
        assert harness.read_layer_metric(
            spec["read"], {"counters": counters}) == want
        for cell in (w["name"] for w in bench["workloads"]):
            assert entries[name] in harness.metrics_of(
                bench, "per_layer", cell)


def _tiny_cell(config_name, traffic, adapter_name):
    import importlib
    import horovod_tpu as hvd
    import jax
    config = _read(CHIP, "configs", config_name + ".json")
    job = _read(CHIP, "workloads", traffic + ".json")
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    adapter = importlib.import_module(f"adapters.{adapter_name}")
    reference = importlib.import_module(f"reference.{adapter_name}")
    mesh = hvd.build_mesh(devices=jax.devices()[:1], dp=-1)
    return adapter, reference, adapter.Cell(config, job, mesh, 0), config, job


def test_bert_reference_check_fails_when_a_term_is_dropped(monkeypatch):
    import run as harness
    adapter, reference, cell, config, job = _tiny_cell(
        "bert-large", "train.s128.b64", "bert")
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert check["ok"], check
    # the reference without the next-sentence loss: 0.69 of ~7
    monkeypatch.setattr(reference, "nsp_loss", lambda *a: 0.0)
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert not check["ok"] and check["loss_rel"] > 0.05, check


def test_flagship_reference_check_fails_without_rotary(monkeypatch):
    import run as harness
    adapter, reference, cell, config, job = _tiny_cell(
        "gpt-1.3b-widths", "train.s2048.b2", "flagship")
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert check["ok"], check
    # a reference that leaves the positions out: the loss barely moves at
    # random weights, the query projection's gradient does
    monkeypatch.setattr(reference, "_rope", lambda x: x)
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert not check["ok"], check
    assert check["grad_rel_l2"]["first_query"] > 0.2, check


def test_flagship_device_init_fills_init_params_tree():
    """The adapter draws on the device what transformer.init_params draws
    on the host: the same tree, shapes and scales."""
    import jax
    import numpy as np
    from horovod_tpu.models.transformer import init_params
    _a, _r, cell, _c, _j = _tiny_cell(
        "gpt-1.3b-widths", "train.s2048.b2", "flagship")
    host = init_params(np.random.RandomState(0), cell.cfg, 1)
    ours = jax.device_get(cell.params)
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(ours)
    for (path, h), o in zip(jax.tree_util.tree_leaves_with_path(host),
                            jax.tree_util.tree_leaves(ours)):
        assert h.shape == o.shape and h.dtype == o.dtype, path
        assert abs(float(o.mean()) - float(h.mean())) < 0.02, path
        assert abs(float(o.std()) / max(float(h.std()), 1e-9) - 1) < 0.05 \
            or float(h.std()) == 0.0, path
