"""tools/phase_report.py end to end at tiny sizes on the CPU, on a
throw-away cell added by files and entries alone (as test_harness.py adds
one), with one throw-away metric of each new ``read`` kind and of the new
counters. A CPU trace has a host plane and no device plane: the host span
and the counter are read, the device metric is left out without a raise."""

import json
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


def test_a_phase_metric_of_each_read_kind_is_added_by_a_file_alone(tmp_path):
    root = str(tmp_path)
    chip = os.path.join(root, "benchmarks", "chip")
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    config = _read(CHIP, "configs", "gpt-1.3b-widths.json")
    config["tiny"] = {**config["tiny"], "n_layer": 1}
    _write(config, chip, "configs", "throwaway.json")
    job = _read(CHIP, "workloads", "train.s2048.b2.json")
    job["tiny"] = {**job["tiny"], "seq_len": 16, "trace_steps": 2}
    _write(job, chip, "workloads", "train.throwaway.json")
    bench = _read(ROOT, "BENCHMARK.json")
    bench["configs"].append({
        "name": "throwaway", "source": config["source"],
        "file": "benchmarks/chip/configs/throwaway.json",
        "reduced": ["n_layer"], "why": "test"})
    bench["workloads"].append({
        "name": "throwaway.s16", "config": "throwaway",
        "traffic": "train.throwaway", "chips": 1, "why": "test"})
    _write(bench, root, "BENCHMARK.json")

    common = {"layer": "train step", "unit": "ms", "better": "lower",
              "moves": "tokens_per_s_per_chip",
              "workloads": ["throwaway.s16"]}
    metrics = os.path.join(chip, "phase_metrics")
    _write({**common, "source": "device_trace", "read": {
        "trace_scope": {"phase": "hvd.mlp"}, "per_step": True,
        "scale": 1e-6}}, metrics, "step.throwaway_mlp_ms.json")
    _write({**common, "source": "program_span", "read": {
        "host_span": "hvd.input.place", "reduce": "median_ms"}},
        metrics, "input.throwaway_place_ms.json")
    _write({**common, "source": "program_counter", "read": {
        "counter": "setup_trace_lower_s"}},
        metrics, "setup.throwaway_trace_lower_s.json")
    # a metric of another cell is not read here
    _write({**common, "source": "program_counter", "workloads": ["other"],
            "read": {"counter": "setup_compile_s"}},
           metrics, "setup.elsewhere_s.json")

    run = subprocess.run(
        [sys.executable, os.path.join(chip, "tools", "phase_report.py"),
         "--workload", "throwaway.s16", "--seed", "3", "--seconds", "0.5",
         "--rehearse", "--out", os.path.join(root, "out")],
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert run.returncode == 0, run.stderr[-2000:]
    result = json.loads(run.stdout.strip().splitlines()[-1])
    assert result["device"]["platform"] == "cpu" and result["rehearsal"]
    assert result["reference_ok"] is True
    got = result["metrics"]
    # the program's span and counters are read ...
    assert got["input.throwaway_place_ms"] > 0
    assert got["input.place_ms"] == got["input.throwaway_place_ms"]
    assert got["input.source_ms"] > 0
    assert got["setup.throwaway_trace_lower_s"] > 0
    assert got["setup.cache_misses"] == 0 and got["setup.cache_read_s"] == 0
    # ... a device metric without a device plane is left out, as is a
    # metric of another cell
    assert not [k for k in got if k.startswith("step.")]
    assert "setup.elsewhere_s" not in got
    # the compiled step's text carries the program's scopes all the same
    assert result["program_has_scopes"] is True
    assert result["tracing"]["hlo_text_bytes"] > 0
    assert result["tracing"]["traced_step_s"] > 0
    assert _read(root, "out", "throwaway.s16.seed3.phases.json") == result


def test_the_phase_metric_files_are_the_twelve_and_well_formed():
    names = sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(CHIP, "phase_metrics")))
    assert names == sorted([
        "step.fwd_ms", "step.bwd_ms", "step.opt_ms", "step.mixed_ms",
        "step.unscoped_ms", "step.attention_core_ms", "step.head_ms",
        "input.source_ms", "input.place_ms", "setup.trace_lower_s",
        "setup.cache_read_s", "setup.cache_misses"])
    bench = _read(ROOT, "BENCHMARK.json")
    layers = {m["layer"] for m in bench["per_layer"]}
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    declared = {m["name"] for m in bench["per_layer"]}
    for name in names:
        spec = _read(CHIP, "phase_metrics", name + ".json")
        assert spec["layer"] in layers, name       # a layer PERF.md names
        assert spec["moves"] in end_to_end, name
        assert spec["better"] == "lower" and spec["what"], name
        assert spec["source"] in ("device_trace", "program_span",
                                  "program_counter"), name
        assert len(spec["read"]) >= 1, name
        # not the driver's yet: SCOPES.md says what a benchmark PR adds
        assert name not in declared
