"""Bench harness contract tests (no TPU): the single-JSON-line artifact
contract under failure, model selection, and failure-identity naming.
The success path is covered on hardware by ci/check_bench.py."""

import io
import contextlib
import json
import os
import subprocess
import sys

import pytest

import bench

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_failure_json_parses_and_exits_nonzero(monkeypatch):
    """When no measurement landed: ONE parseable JSON line with the right
    metric name, value null, the error set — and a NON-ZERO exit. No
    older number is dug up to pad the line (no ``last_measured``)."""
    monkeypatch.setattr(
        bench, "_run_attempt",
        lambda deadline_s=None: (None, None,
                                 "child rc=1: backend 'tpu' down"))
    monkeypatch.setattr(bench, "BACKOFF_S", 0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), pytest.raises(SystemExit) as ex:
        bench.main()
    assert ex.value.code not in (0, None)
    lines = [l for l in buf.getvalue().strip().splitlines() if l.strip()]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc["metric"] == "resnet50_images_per_sec_per_chip"
    assert doc["value"] is None and doc["error"]
    assert doc["attempts"] == bench.MAX_ATTEMPTS
    assert "last_measured" not in doc


def test_parent_never_imports_jax():
    """A chip belongs to one process: the parent (main / _run_attempt /
    _failure_identity) must leave JAX to the measuring child. Runs the
    whole failure path in a fresh interpreter and looks at sys.modules."""
    code = (
        "import os, sys, bench\n"
        "bench.BACKOFF_S = 0\n"
        "bench.MAX_ATTEMPTS = 1\n"
        "import subprocess\n"
        "class P:  # a child that dies at once, printing nothing\n"
        "    returncode = 1\n"
        "    def __init__(self, *a, **k): self.stdout = open(os.devnull)\n"
        "    def wait(self, timeout=None): return 1\n"
        "subprocess.Popen = P\n"
        "try:\n"
        "    bench.main()\n"
        "except SystemExit as e:\n"
        "    assert e.code == 1, e.code\n"
        "else:\n"
        "    raise AssertionError('no exit')\n"
        "assert 'jax' not in sys.modules, 'parent imported jax'\n")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=60, cwd=REPO)
    assert r.returncode == 0, r.stderr[-1500:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["value"] is None and "child rc=1" in doc["error"]


def test_config_error_fails_fast(monkeypatch):
    """A deterministic config error (unknown model) must not retry and
    must not mint a real benchmark's metric name."""
    monkeypatch.setenv("HVD_BENCH_MODEL", "resent50")  # typo
    calls = []

    def counting(deadline_s=None):
        calls.append(1)
        return (None, None, "config error (no retry): child rc=2: unknown")
    monkeypatch.setattr(bench, "_run_attempt", counting)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf), pytest.raises(SystemExit) as ex:
        bench.main()
    assert ex.value.code not in (0, None)
    assert len(calls) == 1  # no retries
    doc = json.loads(buf.getvalue().strip())
    assert doc["metric"] == "unknown_model_resent50"
    assert doc["unit"] == "n/a" and doc["value"] is None


def test_unknown_model_child_exits_rc2():
    env = dict(os.environ)
    env.update({"HVD_BENCH_MODEL": "nope", "JAX_PLATFORMS": "cpu"})
    r = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(bench.__file__),
                                      "bench.py"), "--child"],
        env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode == 2
    assert "unknown HVD_BENCH_MODEL" in r.stderr


@pytest.mark.slow  # ~26s gpt-child compile; tier-1 budget (single
#                    tier runs the whole file unfiltered)
def test_gpt_child_runs_on_cpu_mesh():
    """The gpt bench child is wired end-to-end: tiny shapes on the
    8-device CPU mesh must produce the one-JSON-line contract."""
    env = dict(os.environ)
    env.update({
        "HVD_BENCH_MODEL": "gpt", "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_BENCH_GPT_DMODEL": "64", "HVD_BENCH_GPT_HEADS": "4",
        "HVD_BENCH_GPT_LAYERS": "2", "HVD_BENCH_GPT_DFF": "128",
        "HVD_BENCH_BATCH": "2", "HVD_BENCH_SEQ": "64",
        # the contract under test is the artifact schema, not timing
        # precision: a short final window keeps this inside the tier-1
        # budget (~2s/step on the 1-core CPU mesh)
        "HVD_BENCH_ITERS": "3",
    })
    r = subprocess.run(
        [sys.executable, "-c",
         "import bench\n"
         "bench._child()\n"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert r.returncode == 0, r.stderr[-1500:]
    lines = []
    for l in r.stdout.strip().splitlines():  # tolerate stray banner lines
        try:
            parsed = json.loads(l)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "metric" in parsed:
            lines.append(parsed)
    # the warmup window emits TWO provisional lines before the final one
    # (first post-compile step immediately, refined after full warmup),
    # so a deadline kill anywhere past compile still carries a value
    assert len(lines) == 3, r.stdout
    assert all(l["provisional"] is True and l["value"] > 0
               for l in lines[:2])
    doc = lines[-1]
    assert "provisional" not in doc
    assert doc["metric"] == "gpt_tokens_per_sec_per_chip"
    assert doc["value"] > 0
    # a CPU run is a test aid and says so: never read as a chip number
    assert doc["platform"] == "cpu" and doc["mfu"] is None
    assert doc["n_chips"] == 8
    assert doc["compile_s"] > 0
    # ISSUE 9: hook-measured compile time (counts EVERY backend
    # compile, not just the first-step wall clock) + HBM peak (None on
    # CPU: the backend reports no memory_stats)
    assert doc["compile_seconds"] > 0
    assert "hbm_peak_bytes" in doc and doc["hbm_peak_bytes"] is None


def test_child_exits_cleanly_before_deadline(tmp_path):
    """With the attempt deadline imminent, the child must emit the
    provisional line and exit 0 WITHOUT running the final window — a
    child the parent has to kill loses what it had not yet printed. The
    same child also proves
    the ISSUE-6 side channel: the provisional result doc must be
    mirrored into HVD_BENCH_PHASE_FILE (the parent's salvage source
    when a SIGKILL loses the stdout pipe)."""
    phase_file = str(tmp_path / "phases.json")
    env = dict(os.environ)
    env.update({
        "HVD_BENCH_MODEL": "gpt", "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        "HVD_BENCH_GPT_DMODEL": "64", "HVD_BENCH_GPT_HEADS": "4",
        "HVD_BENCH_GPT_LAYERS": "2", "HVD_BENCH_GPT_DFF": "128",
        "HVD_BENCH_BATCH": "2", "HVD_BENCH_SEQ": "64",
        "HVD_BENCH_PHASE_FILE": phase_file,
        "HVD_BENCH_CHILD_DEADLINE": "1",  # long past: skip final window
    })
    r = subprocess.run(
        [sys.executable, "-c",
         "import bench\n"
         "bench._child()\n"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=os.path.dirname(os.path.abspath(bench.__file__)))
    assert r.returncode == 0, r.stderr[-1500:]
    lines = [json.loads(l) for l in r.stdout.strip().splitlines()
             if l.strip().startswith("{")]
    # provisionals only (first-step + refined), no final window
    assert len(lines) == 2, r.stdout
    assert all(l["provisional"] is True and l["value"] > 0 for l in lines)
    assert "exiting cleanly" in r.stderr
    # the phase-file side channel carries the provisional (salvage source)
    with open(phase_file) as f:
        doc = json.load(f)
    prov = doc["provisional_result"]
    assert prov and prov["provisional"] is True and prov["value"] > 0
    assert "warmup" in doc["phases"]


def test_provisional_salvaged_when_final_window_never_lands(monkeypatch):
    """If every attempt times out but a warmup-window provisional line was
    streamed out, main() must print that REAL measured number (with the
    failure context in "note") instead of a value:null artifact."""
    prov = json.dumps({
        "metric": "resnet50_images_per_sec_per_chip", "value": 2500.0,
        "unit": "img/s/chip", "vs_baseline": 24.1, "mfu": 0.31,
        "provisional": True})
    monkeypatch.setattr(
        bench, "_run_attempt",
        lambda deadline_s=None: (None, prov, "attempt exceeded 900s "
                                 "deadline"))
    monkeypatch.setattr(bench, "BACKOFF_S", 0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.main()
    lines = [l for l in buf.getvalue().strip().splitlines() if l.strip()]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc["value"] == 2500.0
    assert doc["provisional"] is True
    assert "deadline" in doc["note"]


def test_provisional_salvaged_from_phase_file(monkeypatch, tmp_path):
    """A SIGKILLed child can lose its stdout lines entirely; the
    provisional mirrored into the HVD_BENCH_PHASE_FILE side channel must
    still be salvaged by main() instead of shipping value:null."""
    prov = {"metric": "resnet50_images_per_sec_per_chip", "value": 2400.0,
            "unit": "img/s/chip", "vs_baseline": 23.2, "mfu": 0.30,
            "provisional": True}
    phase_doc = {"phases": {"compile": 100.0}, "in_progress": "measure",
                 "provisional_result": prov}

    def fake_attempt(deadline_s=None):
        monkeypatch.setattr(bench, "_LAST_PHASES", phase_doc)
        return None, None, "attempt exceeded 900s deadline"

    monkeypatch.setattr(bench, "_run_attempt", fake_attempt)
    monkeypatch.setattr(bench, "BACKOFF_S", 0)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        bench.main()
    lines = [l for l in buf.getvalue().strip().splitlines() if l.strip()]
    assert len(lines) == 1
    doc = json.loads(lines[0])
    assert doc["value"] == 2400.0
    assert doc["provisional"] is True
    assert "deadline" in doc["note"]
    assert doc["phases"] == {"compile": 100.0}




def test_scaling_gate_extract_and_regression(tmp_path):
    """ci/check_bench.py --scaling: curve extraction from raw output and
    from MULTICHIP artifacts, and the tolerance-band regression check."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import (check_scaling_regression,
                                    extract_scaling_curve, scaling_main)
    finally:
        sys.path.remove(REPO)
    curve = {"scaling_curve": [
        {"world": 1, "samples_per_sec": 10.0, "samples_per_sec_int8": 8.0},
        {"world": 2, "samples_per_sec": 18.0,
         "samples_per_sec_int8": 15.0}]}
    tail = ("[dryrun] OK: 2 layouts on 8 devices\n"
            "[scaling] world=1 plain=10.0/s int8=8.0/s\n"
            "[scaling] " + json.dumps(curve) + "\n")
    # raw text and MULTICHIP-artifact forms both extract
    assert extract_scaling_curve(tail) == curve
    new_path = tmp_path / "MULTICHIP_new.json"
    new_path.write_text(json.dumps({"n_devices": 8, "tail": tail}))

    # within band: passes
    base_ok = {"scaling_curve": [
        {"world": 1, "samples_per_sec": 11.0,
         "samples_per_sec_int8": 9.0}]}
    assert check_scaling_regression(curve, base_ok, 0.25) == []
    # collapse beyond band: fails and names the series
    base_bad = {"scaling_curve": [
        {"world": 2, "samples_per_sec": 40.0,
         "samples_per_sec_int8": 15.0}]}
    bad = check_scaling_regression(curve, base_bad, 0.25)
    assert bad == [(2, "samples_per_sec", 18.0, 40.0)]

    # CLI: regression -> rc 1; within band -> rc 0; no baseline curve
    # (old artifact) -> rc 0 with a note; new without curve -> rc 1
    base_path = tmp_path / "MULTICHIP_base.json"
    base_path.write_text(json.dumps(
        {"tail": "[scaling] " + json.dumps(base_bad)}))
    argv = ["--scaling", str(new_path), "--baseline", str(base_path)]
    assert scaling_main(argv) == 1
    assert scaling_main(argv + ["--tolerance", "0.9"]) == 0
    old_style = tmp_path / "MULTICHIP_old.json"
    old_style.write_text(json.dumps({"tail": "[dryrun] OK\n"}))
    assert scaling_main(["--scaling", str(new_path), "--baseline",
                         str(old_style)]) == 0
    assert scaling_main(["--scaling", str(old_style), "--baseline",
                         str(base_path)]) == 1

    # a baseline world the new run COULD have measured but didn't is a
    # regression (evidence erased), and a truncated curve fails loudly
    short = dict(curve)
    short["n_devices"] = 8
    base_full = {"scaling_curve": curve["scaling_curve"] + [
        {"world": 8, "samples_per_sec": 60.0,
         "samples_per_sec_int8": 40.0}]}
    missing = check_scaling_regression(short, base_full, 0.25)
    assert (8, "missing", None, 60.0) in missing
    trunc_path = tmp_path / "MULTICHIP_trunc.json"
    trunc_path.write_text(json.dumps({"tail": "[scaling] " + json.dumps(
        dict(curve, truncated=True))}))
    full_base_path = tmp_path / "MULTICHIP_fullbase.json"
    full_base_path.write_text(json.dumps(
        {"tail": "[scaling] " + json.dumps(base_full)}))
    assert scaling_main(["--scaling", str(trunc_path), "--baseline",
                         str(base_path), "--tolerance", "0.9"]) == 1


def test_compile_budget_gate(tmp_path):
    """ci/check_bench.py --compile-budget (ISSUE 9): hook-measured
    compile_seconds gated against the baseline with a tolerance band;
    wall-clock compile_s is the fallback for pre-contract artifacts."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import (check_compile_budget,
                                    compile_budget_main,
                                    doc_compile_seconds)
    finally:
        sys.path.remove(REPO)
    new = {"metric": "resnet50", "value": 100.0, "compile_seconds": 30.0,
           "compile_s": 99.0}
    assert doc_compile_seconds(new) == (30.0, "hooks")  # hooks beat wall
    old = {"metric": "resnet50", "value": 90.0, "compile_s": 25.0}
    assert doc_compile_seconds(old) == (25.0, "wall")
    # within band / beyond band / no baseline / broken contract
    assert check_compile_budget(new, old, tolerance=0.5) is None
    assert "regression" in check_compile_budget(
        {"value": 1.0, "compile_seconds": 60.0}, old, tolerance=0.5)
    assert check_compile_budget(new, None, tolerance=0.5) is None
    assert "contract" in check_compile_budget(
        {"value": 1.0}, old, tolerance=0.5)
    # a failure doc (value null) has no compile to judge
    assert check_compile_budget(
        {"value": None, "error": "x"}, old, tolerance=0.5) is None

    # CLI roundtrip incl. the BENCH_r* "parsed" wrapper form
    new_path = tmp_path / "new.json"
    new_path.write_text(json.dumps(new))
    base_path = tmp_path / "BENCH_base.json"
    base_path.write_text(json.dumps({"n": 1, "parsed": old}))
    rc = compile_budget_main(["--compile-budget", str(new_path),
                              "--baseline", str(base_path)])
    assert rc == 0
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(
        {"value": 1.0, "compile_seconds": 60.0}))
    rc = compile_budget_main(["--compile-budget", str(bad_path),
                              "--baseline", str(base_path)])
    assert rc == 1
    rc = compile_budget_main(["--compile-budget", str(new_path),
                              "--baseline", str(base_path),
                              "--tolerance", "0.1"])
    assert rc == 1
    # a failure doc (value null, no compile time) against a real
    # baseline passes without crashing on the success-path print
    fail_path = tmp_path / "failed.json"
    fail_path.write_text(json.dumps({"value": None, "error": "boom"}))
    rc = compile_budget_main(["--compile-budget", str(fail_path),
                              "--baseline", str(base_path)])
    assert rc == 0


def test_tuned_vs_default_gate(tmp_path):
    """ci/check_bench.py --tuned TUNED --default DEFAULT (ISSUE 8):
    the autotuned run must not lose to the static default beyond the
    band — including the missing-world evidence rule — and degraded
    inputs (no curve on either side) fail rather than pass silently."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import tuned_main
    finally:
        sys.path.remove(REPO)

    def artifact(path, curve, n_devices=8):
        doc = {"n_devices": n_devices,
               "tail": "[scaling] " + json.dumps(curve)}
        path.write_text(json.dumps(doc))
        return str(path)

    default = {"scaling_curve": [
        {"world": 1, "samples_per_sec": 10.0,
         "samples_per_sec_int8": 8.0},
        {"world": 8, "samples_per_sec": 60.0,
         "samples_per_sec_int8": 45.0}]}
    default_path = artifact(tmp_path / "default.json", default)

    # tuned at least as good everywhere: passes
    good = {"scaling_curve": [
        {"world": 1, "samples_per_sec": 11.0,
         "samples_per_sec_int8": 8.5},
        {"world": 8, "samples_per_sec": 66.0,
         "samples_per_sec_int8": 50.0}]}
    good_path = artifact(tmp_path / "tuned_good.json", good)
    assert tuned_main(["--tuned", good_path,
                       "--default", default_path]) == 0

    # tuned loses a world beyond the band: fails
    bad = {"scaling_curve": [
        {"world": 1, "samples_per_sec": 11.0,
         "samples_per_sec_int8": 8.5},
        {"world": 8, "samples_per_sec": 30.0,
         "samples_per_sec_int8": 50.0}]}
    bad_path = artifact(tmp_path / "tuned_bad.json", bad)
    assert tuned_main(["--tuned", bad_path,
                       "--default", default_path]) == 1
    # ... but a wide-enough band accepts it
    assert tuned_main(["--tuned", bad_path, "--default", default_path,
                       "--tolerance", "0.6"]) == 0

    # a world the default measured but the tuned run erased: fails
    short = {"n_devices": 8, "scaling_curve": good["scaling_curve"][:1]}
    short_path = artifact(tmp_path / "tuned_short.json", short)
    assert tuned_main(["--tuned", short_path,
                       "--default", default_path]) == 1

    # degraded inputs fail loudly instead of passing by default
    empty_path = tmp_path / "empty.json"
    empty_path.write_text(json.dumps({"tail": "[dryrun] OK\n"}))
    assert tuned_main(["--tuned", str(empty_path),
                       "--default", default_path]) == 1
    assert tuned_main(["--tuned", good_path,
                       "--default", str(empty_path)]) == 1
    assert tuned_main(["--tuned", good_path]) == 2  # --default missing


def test_failure_identity_names():
    for model, metric, unit in [
            ("resnet50", "resnet50_images_per_sec_per_chip", "img/s/chip"),
            ("resnet50_bare", "resnet50_bare_images_per_sec_per_chip",
             "img/s/chip"),
            ("resnet101", "resnet101_images_per_sec_per_chip", "img/s/chip"),
            ("vgg16", "vgg16_images_per_sec_per_chip", "img/s/chip"),
            ("inception3", "inception3_images_per_sec_per_chip",
             "img/s/chip"),
            ("bert", "bert_large_seqs_per_sec_per_chip", "seq/s/chip"),
            ("bert_large", "bert_large_seqs_per_sec_per_chip",
             "seq/s/chip"),
            ("gpt", "gpt_tokens_per_sec_per_chip", "tokens/s/chip"),
            ("transformer", "gpt_tokens_per_sec_per_chip",
             "tokens/s/chip")]:
        os.environ["HVD_BENCH_MODEL"] = model
        try:
            assert bench._failure_identity() == (metric, unit)
        finally:
            del os.environ["HVD_BENCH_MODEL"]


def test_pipeline_plan_gate(tmp_path):
    """ci/check_bench.py --pipeline (ISSUE 11): the parallel_plan /
    bubble_fraction pair must be coherent with the analytic tick-count
    model; a doc without a plan passes with nothing to judge."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import check_pipeline_plan, pipeline_main
    finally:
        sys.path.remove(REPO)
    good = {"metric": "gpt_tokens_per_sec_per_chip", "value": 1.0,
            "n_chips": 8,
            "parallel_plan": {"dp": 4, "pp": 2, "schedule": "gpipe",
                              "n_microbatches": 4, "virtual_stages": 1},
            "bubble_fraction": 0.2}    # 2(M+S-1)=10 vs 2M=8 -> 0.2
    assert check_pipeline_plan(good) is None
    assert check_pipeline_plan({"value": 1.0}) is None  # pp=1 run
    wrong_bubble = dict(good, bubble_fraction=0.4286)
    assert "disagrees" in check_pipeline_plan(wrong_bubble)
    bad_tile = dict(good, n_chips=6)
    assert "does not tile" in check_pipeline_plan(bad_tile)
    missing = dict(good)
    del missing["bubble_fraction"]
    assert "without bubble_fraction" in check_pipeline_plan(missing)
    # measured bubble (ISSUE 12 satellite): range-checked when present,
    # drift vs analytic is printed, never gated
    measured = dict(good, bubble_measured=0.31)
    assert check_pipeline_plan(measured) is None
    assert "outside" in check_pipeline_plan(
        dict(good, bubble_measured=1.2))
    assert "not a number" in check_pipeline_plan(
        dict(good, bubble_measured="fast"))
    # the CLI form
    path = tmp_path / "doc.json"
    path.write_text(json.dumps(good))
    assert pipeline_main(["--pipeline", str(path)]) == 0
    path.write_text(json.dumps(measured))
    assert pipeline_main(["--pipeline", str(path)]) == 0
    path.write_text(json.dumps(wrong_bubble))
    assert pipeline_main(["--pipeline", str(path)]) == 1


def _gp_section(fractions, closed=True, violations=0, wall=100.0):
    """A synthetic ledger snapshot shaped like goodput.snapshot()."""
    secs = {c: round(f * wall, 4) for c, f in fractions.items()}
    return {"windows": 2, "steps": 100, "wall_s": wall,
            "seconds": secs, "fractions": fractions,
            "fraction": fractions.get("compute", 0.0),
            "residual_s": 0.0, "closed": closed,
            "books_violations": violations, "tolerance": 0.01}


def test_goodput_gate(tmp_path):
    """ci/check_bench.py --goodput (ISSUE 16): real-valued artifacts
    must carry a CLOSED ledger, and the exposed_comm/compile shares are
    gated against the baseline — both directions (pass + synthesized
    regression)."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import check_goodput, goodput_main
    finally:
        sys.path.remove(REPO)
    good = {"metric": "m", "value": 10.0,
            "goodput": _gp_section({"compute": 0.8, "exposed_comm": 0.1,
                                    "compile": 0.05, "idle_other": 0.05}),
            "mfu_attribution": {"mfu": 0.3, "dominating": "exposed_comm",
                                "kernel_inefficiency": 0.5}}
    base = {"metric": "m", "value": 11.0,
            "goodput": _gp_section({"compute": 0.88, "exposed_comm": 0.05,
                                    "compile": 0.05, "idle_other": 0.02})}
    # within band: passes
    assert check_goodput(good, base, tolerance=0.1) == []
    # synthesized regression: exposed_comm share triples past the band
    bad = {"metric": "m", "value": 6.0,
           "goodput": _gp_section({"compute": 0.6, "exposed_comm": 0.3,
                                   "compile": 0.05, "idle_other": 0.05})}
    problems = check_goodput(bad, base, tolerance=0.1)
    assert len(problems) == 1 and "exposed_comm" in problems[0] \
        and "REGRESSION" in problems[0], problems
    # ... a wide-enough band accepts it
    assert check_goodput(bad, base, tolerance=0.5) == []
    # real value without the ledger: the recording contract broke
    problems = check_goodput({"value": 1.0}, base, tolerance=0.1)
    assert problems and "contract" in problems[0]
    # a failure doc (value null) has nothing to account
    assert check_goodput({"value": None, "error": "x"}, base, 0.1) == []
    # books that did not close fail even with no baseline
    open_books = {"value": 1.0,
                  "goodput": _gp_section({"compute": 0.7,
                                          "idle_other": 0.1},
                                         closed=False, violations=1)}
    problems = check_goodput(open_books, None, tolerance=0.1)
    assert problems and "did NOT close" in problems[0]

    # CLI both ways, incl. the BENCH_r* "parsed" wrapper form
    new_path = tmp_path / "new.json"
    new_path.write_text(json.dumps(good))
    base_path = tmp_path / "BENCH_base.json"
    base_path.write_text(json.dumps({"n": 1, "parsed": base}))
    assert goodput_main(["--goodput", str(new_path),
                         "--baseline", str(base_path)]) == 0
    bad_path = tmp_path / "bad.json"
    bad_path.write_text(json.dumps(bad))
    assert goodput_main(["--goodput", str(bad_path),
                         "--baseline", str(base_path)]) == 1
    assert goodput_main(["--goodput", str(bad_path),
                         "--baseline", str(base_path),
                         "--tolerance", "0.5"]) == 0
    # a pre-contract baseline is judged standalone, not crashed on
    old_path = tmp_path / "old.json"
    old_path.write_text(json.dumps({"value": 5.0}))
    assert goodput_main(["--goodput", str(new_path),
                         "--baseline", str(old_path)]) == 0


def test_baseline_discovery_skips_null_artifacts_loudly(tmp_path,
                                                        monkeypatch,
                                                        capsys):
    """Baseline auto-discovery (--goodput / --compile-budget): a
    null-valued BENCH_r* round is skipped with an explicit message —
    never silently — and the gate compares against the newest REAL
    artifact behind it."""
    sys.path.insert(0, REPO)
    try:
        import ci.check_bench as cb
    finally:
        sys.path.remove(REPO)
    monkeypatch.setattr(cb, "REPO", str(tmp_path))
    # newest round failed (value null); the round before it is real
    (tmp_path / "BENCH_r9.json").write_text(json.dumps(
        {"parsed": {"value": None, "error": "backend down", "mfu": None}}))
    (tmp_path / "BENCH_r8.json").write_text("{not json")
    real = {"value": 10.0, "compile_seconds": 5.0,
            "goodput": _gp_section({"compute": 0.9, "idle_other": 0.1})}
    (tmp_path / "BENCH_r7.json").write_text(json.dumps({"parsed": real}))
    path, doc = cb.discover_baseline(
        "BENCH_r*.json", str(tmp_path / "new.json"),
        lambda d: cb.doc_goodput(d) is not None, what="goodput section")
    assert path.endswith("BENCH_r7.json") and doc["value"] == 10.0
    out = capsys.readouterr().out
    assert "BENCH_r9.json" in out and "null-valued" in out, out
    assert "BENCH_r8.json" in out, out
    # nothing real at all -> (None, None), every skip still reported
    (tmp_path / "BENCH_r7.json").unlink()
    path, doc = cb.discover_baseline(
        "BENCH_r*.json", str(tmp_path / "new.json"),
        lambda d: cb.doc_goodput(d) is not None, what="goodput section")
    assert path is None and doc is None
    assert "null-valued" in capsys.readouterr().out
    # the compile-budget gate's auto-discovery goes through the same
    # loud helper: its messages surface there too
    (tmp_path / "BENCH_r7.json").write_text(json.dumps({"parsed": real}))
    new_path = tmp_path / "candidate.json"
    new_path.write_text(json.dumps({"value": 9.0, "compile_seconds": 6.0}))
    assert cb.compile_budget_main(
        ["--compile-budget", str(new_path)]) == 0
    out = capsys.readouterr().out
    assert "null-valued" in out and "BENCH_r7.json" in out, out


def test_pipeline_plan_gate_never_raises_on_corrupt_docs():
    """Corrupt artifacts must FAIL the gate with a message, not kill it
    with a traceback (review hardening)."""
    sys.path.insert(0, REPO)
    try:
        from ci.check_bench import check_pipeline_plan
    finally:
        sys.path.remove(REPO)
    base = {"n_chips": 8,
            "parallel_plan": {"dp": 4, "pp": 2, "schedule": "gpipe",
                              "n_microbatches": 4, "virtual_stages": 1},
            "bubble_fraction": 0.2}
    for mutate in (
            lambda d: d["parallel_plan"].update(schedule="xyz"),
            lambda d: d["parallel_plan"].update(n_microbatches="many"),
            lambda d: d["parallel_plan"].update(
                schedule="interleaved", n_microbatches=10**9),
            lambda d: d.update(bubble_fraction="0.2x"),
            lambda d: d.update(parallel_plan=["dp", 4]),
            lambda d: d["parallel_plan"].update(pp=0),
    ):
        doc = json.loads(json.dumps(base))
        mutate(doc)
        problem = check_pipeline_plan(doc)
        assert isinstance(problem, str) and problem, doc
