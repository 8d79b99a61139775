#!/usr/bin/env python
"""Bench artifact contract check: bench.py must print exactly one line of
parseable JSON with the headline metric keys, succeeding (value numeric,
exit 0) when a measurement landed and otherwise failing with a diagnostic
(value null, error set, non-zero exit).

``--scaling NEW [--baseline OLD] [--tolerance T]`` is the scaling-curve
regression gate (ISSUE 6): NEW/OLD are MULTICHIP_* artifacts (or raw
dryrun output) whose ``[scaling] {json}`` line carries samples/s vs
world size with and without int8 compression; the gate fails when any
world's throughput (either series) regresses more than T (default 0.25
— CPU-mesh numbers are noisy; the band catches collapses, not jitter)
below the baseline. A baseline without a curve (older rounds) passes
with a note; a NEW artifact without a curve fails — the standing
artifact is the point.

``--tuned TUNED --default DEFAULT [--tolerance T]`` is the
autotune-never-regresses gate (ISSUE 8): TUNED is a scaling artifact
measured with the mesh autotuner on, DEFAULT the same sweep with the
static hand-set config. The gate fails when the tuned plan loses to
the default beyond T at any world (same missing-world evidence rule as
the scaling gate: a world the default measured but the tuned run
didn't is itself a failure) — autotune converging to something WORSE
than the baseline candidate means the search scored garbage, exactly
what must not ship silently.

``--compile-budget NEW [--baseline OLD] [--tolerance T]`` is the
compile-time regression gate (ISSUE 9): the bench doc records
``compile_seconds`` — MEASURED backend-compile time from the compile
hooks (docs/OBSERVABILITY.md "Compile & memory observability"), not
the old wall-clock phase that also timed the first step's run — and
the gate fails when NEW's compile time exceeds the baseline's by more
than T (default 0.5: compile time on shared hosts is noisy; the band
catches a graph-growth or cache-bust regression, not jitter).  A
baseline artifact predating the contract passes with a note (NEW
becomes the baseline); a NEW artifact with a real measured value but
no compile time fails — the recording contract broke.

``--pipeline ARTIFACT`` is the parallelism-plan contract gate
(ISSUE 11): a bench doc produced with ``HVD_BENCH_PP`` > 1 must record
the locked parallelism plan (``parallel_plan``: dp/pp/schedule/
n_microbatches/virtual_stages) and an analytic ``bubble_fraction`` that
MATCHES the schedule's tick-count model
(``horovod_tpu.parallel.pipeline.bubble_fraction``) — a plan/bubble
pair that disagrees means the child measured one layout while
reporting another. ``dp * pp`` must equal ``n_chips``. A doc without
a plan (pp=1 run) passes with a note.  When the doc also carries a
MEASURED bubble (``bubble_measured``, from the pp=1 compute-only
attribution baseline — ISSUE 12) it is range-checked and printed next
to the analytic value with their drift, so analytic-vs-measured
divergence is visible per round without being a gate (remat recompute
and collective latency legitimately live in the gap).

``--serving NEW [--baseline OLD] [--tolerance T]`` is the serving
latency gate (ISSUE 14): NEW/OLD are ``BENCH_SERVE`` artifacts from
``benchmarks/serving_bench.py`` (raw JSON or captured output).  The
gate fails when p99 regresses more than T (default 0.5) over the
baseline's, and — baseline or not — when the artifact is not CLEAN:
``shed_fraction > 0`` (a latency number bought by refusing load is not
a measurement of the same system), failed requests, or a violated
zero-drop audit (unanswered / double-answered ids) all fail.  The
request ledger (ISSUE 19) adds two standalone rules: the artifact must
carry the per-stage decomposition with its books CLOSED
(``stage_unattributed_frac`` under 10% — a p99 whose decomposition no
longer explains it is not actionable), and the reported p99 is
replayed through the shared quantile over the artifact's own
``latency_sample``.

``--serving-gen NEW [--baseline OLD] [--tolerance T]`` is the
generative-throughput gate (ISSUE 17): NEW/OLD are ``BENCH_SERVE_GEN``
artifacts from ``benchmarks/serving_bench.py --generate`` (raw JSON or
captured output).  Baseline or not, the artifact must be CLEAN: zero
failed requests (a tokens/s number that dropped streams is not a
measurement), ``decode_compiles == 1`` (slot churn re-triggering XLA
compilation is the one failure mode the static-slot design exists to
prevent — a second compile IS the regression), and ``speedup > 1``
(continuous batching must beat the request-level gang baseline it
ships next to, measured on the same warm engine with identical
tracing/callback overhead).  With a baseline, ``tokens_per_s`` must
not regress more than T (default 0.5 — CPU decode windows are noisy).
Baselines auto-discover from committed ``BENCH_SERVE_GEN*.json``;
failure artifacts are skipped LOUDLY, same semantics as ``--goodput``.

``--goodput NEW [--baseline OLD] [--tolerance T]`` is the goodput
regression gate (ISSUE 16): the bench doc records ``goodput`` — the
closed-books wall-clock ledger (docs/OBSERVABILITY.md "Goodput
ledger") — and ``mfu_attribution`` (the roofline decomposition of
1-MFU into category shares).  The gate fails when (a) NEW carries a
real measured value but no goodput section (recording contract broke),
(b) NEW's books did not close (the categories failed to sum to wall
time within the ledger's tolerance — the accounting itself is broken),
or (c) the ``exposed_comm`` or ``compile`` share grew more than T
(default 0.1, ABSOLUTE share points — CPU windows are noisy) over the
baseline's.  Baselines auto-discover from committed ``BENCH_r*.json``;
null-valued failure artifacts are skipped LOUDLY (a silent skip reads
as "compared against the last round" when it wasn't).

``--trajectory ARTIFACT [--tolerance T]`` is the within-window drift
gate (ISSUE 7): the bench doc now records ``step_time_series`` — every
iteration of the timing window — so a run whose *mean* looks fine but
whose steps were degrading (thermal creep, a neighbor ramping up, a
leak) fails instead of shipping a number that was only true at the
start of the window.  The gate compares the mean of the window's last
third against its first third; drift beyond T (default 0.5 — window
noise on shared CPUs is large) fails.  The main contract check applies
the same gate automatically when the doc carries a real (non-null)
measured value and enough points."""

import glob
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def extract_scaling_curve(text: str):
    """Last ``[scaling] {json}`` line of a dryrun's output, or None.
    Accepts either raw text or a MULTICHIP artifact's ``tail`` field."""
    doc = None
    for line in text.splitlines():
        line = line.strip()
        if not line.startswith("[scaling] "):
            continue
        try:
            parsed = json.loads(line[len("[scaling] "):])
        except ValueError:
            continue  # progress lines ([scaling] world=...) are not JSON
        if isinstance(parsed, dict) and "scaling_curve" in parsed:
            doc = parsed
    return doc


def _load_curve(path: str):
    with open(path) as f:
        text = f.read()
    try:  # MULTICHIP artifact: the dryrun output lives in "tail"
        artifact = json.loads(text)
        if isinstance(artifact, dict) and "tail" in artifact:
            text = artifact["tail"]
    except ValueError:
        pass  # raw dryrun output
    return extract_scaling_curve(text)


def check_scaling_regression(new: dict, baseline: dict,
                             tolerance: float) -> list:
    """Regressions beyond the band: [(world, series, new, base), ...].
    A baseline world the new curve failed to measure (but could have —
    it fits the new run's device count) is itself a regression: a
    slowdown that eats the measurement budget must not erase the
    evidence and pass (``None`` marks the missing measurement)."""
    base_by_world = {row["world"]: row
                     for row in baseline.get("scaling_curve", [])}
    new_worlds = {row["world"] for row in new.get("scaling_curve", [])}
    bad = []
    for row in new.get("scaling_curve", []):
        base = base_by_world.get(row["world"])
        if base is None:
            continue
        for series in ("samples_per_sec", "samples_per_sec_int8"):
            n, b = row.get(series), base.get(series)
            if n is not None and b and n < b * (1.0 - tolerance):
                bad.append((row["world"], series, n, b))
    new_capacity = new.get("n_devices") or max(new_worlds, default=0)
    for world, base in sorted(base_by_world.items()):
        if world <= new_capacity and world not in new_worlds:
            bad.append((world, "missing", None,
                        base.get("samples_per_sec")))
    return bad


TRAJECTORY_MIN_POINTS = 6


def check_trajectory(series, tolerance: float = 0.5):
    """Within-window drift check over a ``step_time_series`` list.

    Returns None when healthy, else a human-readable failure string.
    Fewer than TRAJECTORY_MIN_POINTS points (contract tests shrink
    HVD_BENCH_ITERS) or non-numeric content is not gated — but a
    *malformed* series (non-list) is always an error: the recording
    contract broke."""
    if not isinstance(series, list):
        return f"step_time_series is not a list: {series!r}"
    vals = [v for v in series if isinstance(v, (int, float)) and v >= 0]
    if len(vals) != len(series):
        return f"step_time_series carries non-numeric entries: {series!r}"
    if len(vals) < TRAJECTORY_MIN_POINTS:
        return None  # too short to judge drift (smoke/contract runs)
    third = max(1, len(vals) // 3)
    head = sum(vals[:third]) / third
    tail = sum(vals[-third:]) / third
    if head > 0 and tail > head * (1.0 + tolerance):
        return (f"trajectory drift: last third of the window averaged "
                f"{tail:.6f}s/step vs {head:.6f}s at the start "
                f"(> {tolerance:.0%} slower over {len(vals)} steps)")
    return None


def _load_bench_doc(path: str):
    """The bench result doc from a raw doc JSON, or from an artifact
    that wraps it (doc under ``parsed`` or ``result``)."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict):
        for key in ("parsed", "result"):
            if isinstance(doc.get(key), dict):
                return doc[key]
    return doc if isinstance(doc, dict) else None


def discover_baseline(pattern, exclude, want, what):
    """Newest committed artifact matching ``pattern`` whose doc
    satisfies ``want(doc)``.  Every rejected candidate is reported
    LOUDLY with the reason — a gate that silently skipped a null-valued
    round reads as "compared against the last artifact" when it
    actually reached further back (or found nothing).  ``what`` names
    the gated section for the messages."""
    for path in sorted(glob.glob(os.path.join(REPO, pattern)),
                       reverse=True):
        if os.path.abspath(path) == os.path.abspath(exclude):
            continue
        name = os.path.basename(path)
        try:
            doc = _load_bench_doc(path)
        except (OSError, ValueError) as e:
            print(f"baseline discovery: skipping {name} "
                  f"(unreadable: {e})")
            continue
        if not doc:
            print(f"baseline discovery: skipping {name} "
                  "(no parseable bench doc)")
            continue
        if doc.get("value") is None:
            print(f"baseline discovery: skipping {name} "
                  "(null-valued failure artifact — no measurement to "
                  "compare against)")
            continue
        if not want(doc):
            print(f"baseline discovery: skipping {name} "
                  f"(no {what} recorded)")
            continue
        return path, doc
    return None, None


def doc_compile_seconds(doc):
    """Measured compile seconds with wall-clock fallback for artifacts
    predating the compile-hook contract."""
    if not isinstance(doc, dict):
        return None, None
    v = doc.get("compile_seconds")
    if isinstance(v, (int, float)):
        return float(v), "hooks"
    v = doc.get("compile_s")
    if isinstance(v, (int, float)):
        return float(v), "wall"
    return None, None


def check_compile_budget(new: dict, baseline, tolerance: float):
    """None when within budget, else a failure string."""
    n, n_src = doc_compile_seconds(new)
    if n is None:
        if new.get("value") is None:
            return None  # a failure doc has no compile to judge
        return ("new artifact carries a measured value but no "
                "compile_seconds/compile_s — the recording contract "
                "broke")
    b, b_src = doc_compile_seconds(baseline) if baseline else (None, None)
    if b is None:
        return None  # no baseline: NEW becomes it
    if b > 0 and n > b * (1.0 + tolerance):
        return (f"compile-time regression: {n:.1f}s ({n_src}) vs "
                f"baseline {b:.1f}s ({b_src}) — more than "
                f"{tolerance:.0%} over budget")
    return None


def compile_budget_main(argv) -> int:
    new_path = argv[argv.index("--compile-budget") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.5
    new = _load_bench_doc(new_path)
    if not new:
        print(f"no bench doc in {new_path}")
        return 1
    baseline = None
    base_path = None
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        baseline = _load_bench_doc(base_path)
    else:
        # newest committed BENCH_r*.json carrying a compile time
        base_path, baseline = discover_baseline(
            "BENCH_r*.json", new_path,
            lambda d: doc_compile_seconds(d)[0] is not None,
            what="compile time")
    problem = check_compile_budget(new, baseline, tolerance)
    if problem:
        print(f"compile-budget gate FAILED for {new_path}: {problem}")
        return 1
    n, src = doc_compile_seconds(new)
    if n is None:
        # a failure doc (value null) passes the gate with nothing to
        # format — don't let the success print crash on None
        print(f"compile-budget gate: {new_path} is a failure artifact "
              "with no compile time; nothing to judge")
    elif baseline is None or doc_compile_seconds(baseline)[0] is None:
        print(f"compile-budget gate: no baseline compile time "
              f"({base_path}); accepting "
              f"{'%.1fs' % n if n is not None else 'n/a'} as the new "
              "baseline")
    else:
        b, bsrc = doc_compile_seconds(baseline)
        print(f"compile-budget gate OK vs {base_path} "
              f"(tolerance {tolerance:.0%}): {n:.1f}s ({src}) vs "
              f"{b:.1f}s ({bsrc})")
    return 0


# the shares the goodput gate holds against the baseline: the two
# costs an engineering change most plausibly regresses silently (an
# overlap-schedule break shows up as exposed_comm; a graph-growth or
# cache-bust regression as compile)
GOODPUT_GATED_CATEGORIES = ("exposed_comm", "compile")


def doc_goodput(doc):
    """The goodput ledger section of a bench doc, or None."""
    if not isinstance(doc, dict):
        return None
    gp = doc.get("goodput")
    return gp if isinstance(gp, dict) else None


def check_goodput(new: dict, baseline, tolerance: float) -> list:
    """Problems with an artifact's goodput books: list of failure
    strings (empty = gate passes).

    Three rules (ISSUE 16): (1) a real-valued artifact must CARRY the
    ledger — a measured number whose wall-clock account is missing is a
    recording-contract break; (2) the books must CLOSE — categories
    summing to wall time within the ledger's own tolerance is the whole
    point, and an artifact that failed its double-entry check is
    evidence of broken accounting, not a perf number; (3) the
    ``exposed_comm`` and ``compile`` shares must not grow more than
    ``tolerance`` ABSOLUTE share points over the baseline's."""
    gp = doc_goodput(new)
    if gp is None:
        if new.get("value") is None:
            return []  # a failure doc has no window to account
        return ["new artifact carries a measured value but no goodput "
                "section — the recording contract broke"]
    problems = []
    if not gp.get("closed", False) or gp.get("books_violations"):
        problems.append(
            f"goodput books did NOT close: residual {gp.get('residual_s')}s "
            f"over {gp.get('wall_s')}s wall "
            f"({gp.get('books_violations', 0)} violating window(s), "
            f"ledger tolerance {gp.get('tolerance')}) — the accounting "
            "is broken, not just slow")
    fr = gp.get("fractions") or {}
    base_gp = doc_goodput(baseline) if baseline else None
    if base_gp:
        base_fr = base_gp.get("fractions") or {}
        for cat in GOODPUT_GATED_CATEGORIES:
            n, b = fr.get(cat), base_fr.get(cat)
            if isinstance(n, (int, float)) and isinstance(b, (int, float)) \
                    and n > b + tolerance:
                problems.append(
                    f"{cat} share REGRESSION: {n:.1%} of wall time vs "
                    f"baseline {b:.1%} (> {tolerance:.0%} absolute "
                    "growth)")
    return problems


def goodput_main(argv) -> int:
    new_path = argv[argv.index("--goodput") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.1
    new = _load_bench_doc(new_path)
    if not new:
        print(f"no bench doc in {new_path}")
        return 1
    baseline = None
    base_path = None
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        baseline = _load_bench_doc(base_path)
        if baseline and doc_goodput(baseline) is None:
            print(f"baseline {base_path} predates the goodput contract; "
                  "judging the new artifact standalone")
    else:
        base_path, baseline = discover_baseline(
            "BENCH_r*.json", new_path,
            lambda d: doc_goodput(d) is not None,
            what="goodput section")
    problems = check_goodput(new, baseline, tolerance)
    if problems:
        for p in problems:
            print(f"goodput gate FAILED for {new_path}: {p}")
        return 1
    gp = doc_goodput(new)
    if gp is None:
        print(f"goodput gate: {new_path} is a failure artifact with no "
              "window to account; nothing to judge")
        return 0
    att = new.get("mfu_attribution") or {}
    note = f" vs {base_path}" if baseline and doc_goodput(baseline) \
        else " (no baseline: standalone books check only)"
    mfu = att.get("mfu")
    print(f"goodput gate OK{note} (tolerance {tolerance:.0%}): "
          f"productive={gp.get('fraction')} over {gp.get('wall_s')}s / "
          f"{gp.get('windows')} window(s), "
          f"dominating_loss={att.get('dominating')}, "
          f"mfu={'n/a' if mfu is None else mfu}, "
          f"kernel_inefficiency="
          f"{'n/a' if att.get('kernel_inefficiency') is None else att['kernel_inefficiency']}")
    return 0


def check_pipeline_plan(doc: dict):
    """None when the parallel_plan/bubble_fraction pair is coherent,
    else a failure string — NEVER an exception: a corrupt artifact must
    fail the gate with a message, not kill it with a traceback. Docs
    without a plan are not judged here."""
    plan = doc.get("parallel_plan")
    if plan is None:
        return None
    if not isinstance(plan, dict):
        return f"parallel_plan is not an object: {plan!r}"
    for key in ("dp", "pp", "schedule", "n_microbatches"):
        if key not in plan:
            return f"parallel_plan missing key {key!r}: {plan}"
    try:
        dp, pp = int(plan["dp"]), int(plan["pp"])
        n_micro = int(plan["n_microbatches"])
        v = int(plan.get("virtual_stages", 1))
        bubble = float(doc["bubble_fraction"]) \
            if doc.get("bubble_fraction") is not None else None
    except (TypeError, ValueError) as e:
        return f"parallel_plan carries non-numeric fields ({e}): {plan}"
    schedule = str(plan["schedule"])
    if schedule not in ("gpipe", "1f1b", "interleaved"):
        return f"unknown schedule {schedule!r} in parallel_plan"
    if not (1 <= dp and 1 <= pp and 1 <= v):
        return f"non-positive plan dimensions: {plan}"
    if not (1 <= n_micro <= 65536):
        # also bounds the pure-Python interleaved table build below — a
        # corrupt huge M must not hang the gate for minutes
        return f"implausible n_microbatches {n_micro} in parallel_plan"
    if bubble is None:
        return "parallel_plan recorded without bubble_fraction"
    if not (0.0 <= bubble < 1.0):
        return f"bubble_fraction {bubble} outside [0, 1)"
    n_chips = doc.get("n_chips")
    if n_chips and dp * pp != int(n_chips):
        return (f"plan dp*pp = {dp}*{pp} does not tile "
                f"n_chips={n_chips}")
    sys.path.insert(0, REPO)
    try:
        from horovod_tpu.parallel.pipeline import bubble_fraction
        expect = bubble_fraction(schedule, pp, n_micro, v)
    except Exception as e:
        return f"analytic bubble model rejected {plan}: {e}"
    finally:
        sys.path.remove(REPO)
    if abs(bubble - expect) > 5e-4:
        return (f"recorded bubble_fraction {bubble} disagrees with the "
                f"analytic value {expect:.4f} for {plan} — the child "
                "measured one layout while reporting another")
    measured = doc.get("bubble_measured")
    if measured is not None:
        # the MEASURED bubble (compute-only attribution) is judged for
        # plausibility only — drift vs the analytic value is expected
        # (remat recompute, collective latency) and PRINTED, not gated
        try:
            measured = float(measured)
        except (TypeError, ValueError):
            return f"bubble_measured is not a number: {measured!r}"
        if not (0.0 <= measured < 1.0):
            return f"bubble_measured {measured} outside [0, 1)"
    return None


def pipeline_main(argv) -> int:
    path = argv[argv.index("--pipeline") + 1]
    doc = _load_bench_doc(path)
    if not doc:
        print(f"no bench doc in {path}")
        return 1
    problem = check_pipeline_plan(doc)
    if problem:
        print(f"pipeline gate FAILED for {path}: {problem}")
        return 1
    plan = doc.get("parallel_plan")
    if plan is None:
        print(f"pipeline gate: {path} carries no parallel_plan "
              "(pp=1 run); nothing to judge")
    else:
        measured = doc.get("bubble_measured")
        analytic = doc["bubble_fraction"]
        # analytic AND measured, plus their drift, every round: the
        # analytic value is the tick model, the measured one is what
        # the devices actually did (remat + comm land in the gap)
        if measured is not None:
            detail = (f" bubble_analytic={analytic} "
                      f"bubble_measured={measured} "
                      f"drift={round(float(measured) - float(analytic), 4)}")
        else:
            detail = (f" bubble_analytic={analytic} "
                      "bubble_measured=n/a (no compute-only baseline "
                      "in this artifact)")
        print(f"pipeline gate OK for {path}: dp{plan['dp']} x "
              f"pp{plan['pp']} {plan['schedule']} "
              f"m{plan['n_microbatches']} v{plan.get('virtual_stages', 1)}"
              + detail)
    return 0


def trajectory_main(argv) -> int:
    path = argv[argv.index("--trajectory") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.5
    with open(path) as f:
        doc = json.load(f)
    series = doc.get("step_time_series")
    if series is None:
        print(f"no step_time_series in {path}: the artifact predates the "
              "trajectory contract (or the child died before the timing "
              "window)")
        return 1
    problem = check_trajectory(series, tolerance)
    if problem:
        print(f"trajectory gate FAILED for {path}: {problem}")
        return 1
    print(f"trajectory gate OK for {path} ({len(series)} steps, "
          f"tolerance {tolerance:.0%})")
    return 0


def tuned_main(argv) -> int:
    """``--tuned TUNED --default DEFAULT``: the tuned run must not lose
    to the static default. The comparison IS the scaling-regression
    check with the default as baseline — a tuned curve below the
    default's band, or a world the tuned run failed to measure, fails."""
    tuned_path = argv[argv.index("--tuned") + 1]
    if "--default" not in argv:
        print("--tuned requires --default DEFAULT_ARTIFACT (the "
              "static-config run to hold the tuned run against)")
        return 2
    default_path = argv[argv.index("--default") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.25
    tuned = _load_curve(tuned_path)
    default = _load_curve(default_path)
    if not tuned or not tuned.get("scaling_curve"):
        print(f"no scaling curve in tuned artifact {tuned_path}")
        return 1
    if not default or not default.get("scaling_curve"):
        print(f"no scaling curve in default artifact {default_path}; "
              "cannot judge the tuned run — measure the static config "
              "first")
        return 1
    bad = check_scaling_regression(tuned, default, tolerance)
    if bad:
        for world, series, n, b in bad:
            if n is None:
                print(f"tuned-vs-default FAILED world={world}: default "
                      f"measured {b:.2f}/s but the tuned run has no "
                      "measurement")
            else:
                print(f"tuned-vs-default FAILED world={world} {series}: "
                      f"tuned {n:.2f}/s vs default {b:.2f}/s "
                      f"(> {tolerance:.0%} below — autotune regressed a "
                      "previously good config)")
        return 1
    print(f"tuned-vs-default OK (tolerance {tolerance:.0%}): "
          + "; ".join(f"w{r['world']}={r['samples_per_sec']}/s"
                      for r in tuned["scaling_curve"]))
    return 0


def _default_baseline(exclude: str):
    """Newest committed MULTICHIP_r*.json that carries a curve."""
    for path in sorted(glob.glob(os.path.join(REPO, "MULTICHIP_r*.json")),
                       reverse=True):
        if os.path.abspath(path) == os.path.abspath(exclude):
            continue
        curve = _load_curve(path)
        if curve:
            return path, curve
    return None, None


def scaling_main(argv) -> int:
    new_path = argv[argv.index("--scaling") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.25
    new = _load_curve(new_path)
    if not new or not new.get("scaling_curve"):
        print(f"no scaling curve in {new_path}: the dryrun must emit the "
              "[scaling] line (HVD_DRYRUN_SCALING=0 set, or the child "
              "died before the scaling phase?)")
        return 1
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        base = _load_curve(base_path)
    else:
        base_path, base = _default_baseline(new_path)
    if not base:
        print(f"scaling gate: no baseline curve available ({base_path}); "
              f"accepting {len(new['scaling_curve'])}-point curve as the "
              "new baseline")
        return 0
    bad = check_scaling_regression(new, base, tolerance)
    if new.get("truncated"):
        # a budget-truncated curve means the measurement itself slowed
        # down — exactly the condition a perf gate must not wave through
        print("scaling gate: NEW curve is truncated (the dryrun's "
              "scaling budget ran out) — investigate the slowdown")
        return 1
    if bad:
        for world, series, n, b in bad:
            if n is None:
                print(f"scaling REGRESSION world={world}: present in "
                      f"baseline ({b:.2f}/s) but NOT measured this run")
            else:
                print(f"scaling REGRESSION world={world} {series}: "
                      f"{n:.2f}/s vs baseline {b:.2f}/s "
                      f"(> {tolerance:.0%} below)")
        return 1
    print(f"scaling gate OK vs {base_path} "
          f"(tolerance {tolerance:.0%}): "
          + "; ".join(f"w{r['world']}={r['samples_per_sec']}/s"
                      for r in new["scaling_curve"]))
    return 0


def _load_serving_doc(path: str):
    """A serving artifact: raw JSON, or the last ``BENCH_SERVE {json}``
    line of captured bench output."""
    with open(path) as f:
        text = f.read()
    doc = None
    try:
        parsed = json.loads(text)
        if isinstance(parsed, dict) and parsed.get("bench") == "serving":
            doc = parsed
    except ValueError:
        pass
    if doc is None:
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("BENCH_SERVE "):
                try:
                    parsed = json.loads(line[len("BENCH_SERVE "):])
                except ValueError:
                    continue
                if isinstance(parsed, dict):
                    doc = parsed
    return doc


#: the books-close bar (ISSUE 19): the stage decomposition must explain
#: at least 90% of the latency it rides with — past this, the ledger is
#: no longer measuring where the time went
SERVING_UNATTRIBUTED_MAX = 0.10


def check_serving(new: dict, baseline, tolerance: float):
    """Problems with a serving artifact: list of failure strings.

    Rules (ISSUE 14 + the request ledger, ISSUE 19): (1) a "clean"
    latency number that SHED requests is not clean — load-shedding
    trades completeness for latency, so a p99 bought that way must not
    pass as a measurement of the same system; same for failed/
    unanswered/double-answered requests (the zero-drop audit rides the
    artifact).  (2) p99 must not regress more than ``tolerance`` over
    the baseline's.  (3) the BOOKS must CLOSE: a measured artifact
    carries the per-stage decomposition
    (``stage_seconds``/``stage_unattributed_frac``) and its
    unattributed residual stays under
    :data:`SERVING_UNATTRIBUTED_MAX` — a p99 whose decomposition no
    longer explains it is a number nobody can act on.  (4) when the
    artifact ships its ``latency_sample``, the reported p99 is REPLAYED
    through the shared quantile implementation
    (:func:`horovod_tpu.serving.ledger.quantile`) — the gate checks the
    math, not just the number (wide band: the sample is strided)."""
    problems = []
    if not new.get("requests"):
        problems.append("no requests measured (empty window)")
    if new.get("shed_fraction"):
        problems.append(
            f"shed_fraction={new['shed_fraction']} > 0: the latency "
            "number was bought by shedding load — not a clean number "
            "(lower the client count or raise the admission budget)")
    if new.get("failed"):
        problems.append(f"{new['failed']} request(s) FAILED during the "
                        "measurement window")
    if new.get("unanswered") or new.get("answered_twice"):
        problems.append(
            f"zero-drop audit violated: unanswered="
            f"{new.get('unanswered')} answered_twice="
            f"{new.get('answered_twice')}")
    stages = new.get("stage_seconds")
    unattr = new.get("stage_unattributed_frac")
    if not isinstance(stages, dict) or not stages:
        if new.get("requests"):
            problems.append(
                "no stage_seconds breakdown: the request ledger's "
                "books are missing — the recording contract broke "
                "(rerun with a current benchmarks/serving_bench.py)")
    elif not isinstance(unattr, (int, float)):
        problems.append(
            "stage_seconds present but stage_unattributed_frac is "
            "missing — the books-close evidence did not ride the "
            "artifact")
    elif unattr >= SERVING_UNATTRIBUTED_MAX:
        problems.append(
            f"request-ledger books did NOT close: "
            f"{unattr:.1%} of attributed wall-clock is unattributed "
            f"(>= {SERVING_UNATTRIBUTED_MAX:.0%}) — the stage "
            f"decomposition no longer explains the p99 it ships with "
            f"(dominant stage: {new.get('dominant_stage')})")
    sample = new.get("latency_sample")
    if isinstance(sample, list) and len(sample) >= 10 \
            and new.get("p99_s"):
        sys.path.insert(0, REPO)
        try:
            from horovod_tpu.serving.ledger import quantile
            replay = quantile(sorted(float(v) for v in sample), 0.99)
        except Exception as e:
            replay = None
            problems.append(f"latency_sample replay failed: {e!r}")
        finally:
            sys.path.remove(REPO)
        if replay is not None:
            # the band is generous (strided sample + absolute floor):
            # this catches a percentile implementation drifting, not
            # sampling noise
            band = max(new["p99_s"] * 0.5, 0.002)
            if abs(replay - new["p99_s"]) > band:
                problems.append(
                    f"p99 replay mismatch: artifact says "
                    f"{new['p99_s']:.6f}s but the shared quantile over "
                    f"its own latency_sample says {replay:.6f}s — the "
                    "percentile math diverged")
    if baseline and baseline.get("p99_s") and new.get("p99_s"):
        base_p99, new_p99 = baseline["p99_s"], new["p99_s"]
        if new_p99 > base_p99 * (1.0 + tolerance):
            problems.append(
                f"p99 REGRESSION: {new_p99:.6f}s vs baseline "
                f"{base_p99:.6f}s (> {tolerance:.0%} above)")
    return problems


def serving_main(argv) -> int:
    new_path = argv[argv.index("--serving") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.5
    new = _load_serving_doc(new_path)
    if not new:
        print(f"no serving artifact in {new_path}: run "
              "benchmarks/serving_bench.py --out first")
        return 1
    baseline = None
    base_path = None
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        baseline = _load_serving_doc(base_path)
        if not baseline:
            print(f"baseline {base_path} carries no serving artifact; "
                  "judging the new run standalone")
    problems = check_serving(new, baseline, tolerance)
    if problems:
        for p in problems:
            print(f"serving gate FAILED for {new_path}: {p}")
        return 1
    note = f" vs {base_path}" if baseline else \
        " (no baseline: standalone checks only)"
    print(f"serving gate OK{note}: qps={new.get('qps')} "
          f"p50={new.get('p50_s')}s p99={new.get('p99_s')}s "
          f"shed_fraction={new.get('shed_fraction')} "
          f"dominant_stage={new.get('dominant_stage')} "
          f"unattributed={new.get('stage_unattributed_frac')} over "
          f"{new.get('requests')} requests")
    return 0


def _load_serving_gen_doc(path: str):
    """A generate-bench artifact: raw JSON, or the last
    ``BENCH_SERVE_GEN {json}`` line of captured bench output.  The
    space-suffixed prefix keeps ``BENCH_SERVE `` lines (request-level
    serving artifacts) from matching."""
    with open(path) as f:
        text = f.read()
    doc = None
    try:
        parsed = json.loads(text)
        if isinstance(parsed, dict) and \
                parsed.get("bench") == "serving_generate":
            doc = parsed
    except ValueError:
        pass
    if doc is None:
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("BENCH_SERVE_GEN "):
                try:
                    parsed = json.loads(line[len("BENCH_SERVE_GEN "):])
                except ValueError:
                    continue
                if isinstance(parsed, dict):
                    doc = parsed
    return doc


def check_serving_gen(new: dict, baseline, tolerance: float):
    """Problems with a generate-bench artifact: list of failure strings.

    Three standalone rules (ISSUE 17) plus a baseline rule: (1) zero
    failed requests — a tokens/s bought by dropping streams is not a
    measurement of the same system; (2) ``decode_compiles`` must be
    EXACTLY 1 — the static-slot engine's whole contract is that slot
    churn never changes the compiled shape, so a second compile is the
    regression this gate exists to catch (and 0 means the compile
    counter broke — also not a pass); (3) ``speedup > 1`` — the
    continuous engine must beat the request-level gang baseline
    measured alongside it on the same warm engine; (4) with a
    baseline, ``tokens_per_s`` must not fall more than ``tolerance``
    below the baseline's."""
    problems = []
    if not new.get("requests"):
        problems.append("no requests measured (empty window)")
    if new.get("failed"):
        problems.append(
            f"{new['failed']} request(s) FAILED (finish_reason != "
            "'length') during the measurement window")
    compiles = new.get("decode_compiles")
    if compiles != 1:
        problems.append(
            f"decode_compiles={compiles}, expected exactly 1: the "
            "static-slot contract is one compile regardless of churn "
            "(0 means the compile counter itself broke)")
    speedup = new.get("speedup")
    if not isinstance(speedup, (int, float)) or speedup <= 1.0:
        problems.append(
            f"speedup={speedup}: continuous batching must beat the "
            "request-level gang baseline measured on the same engine")
    if baseline and baseline.get("tokens_per_s") \
            and new.get("tokens_per_s"):
        base_tps, new_tps = baseline["tokens_per_s"], new["tokens_per_s"]
        if new_tps < base_tps * (1.0 - tolerance):
            problems.append(
                f"tokens/s REGRESSION: {new_tps:.2f} vs baseline "
                f"{base_tps:.2f} (> {tolerance:.0%} below)")
    return problems


def serving_gen_main(argv) -> int:
    new_path = argv[argv.index("--serving-gen") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.5
    new = _load_serving_gen_doc(new_path)
    if not new:
        print(f"no generate artifact in {new_path}: run "
              "benchmarks/serving_bench.py --generate first")
        return 1
    baseline = None
    base_path = None
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        baseline = _load_serving_gen_doc(base_path)
        if not baseline:
            print(f"baseline {base_path} carries no generate artifact; "
                  "judging the new run standalone")
    else:
        # Gen docs carry no "value" key, so discover_baseline (which
        # requires one) cannot be reused — mirror its loud-skip
        # semantics over the gen artifact pattern instead.
        for path in sorted(
                glob.glob(os.path.join(REPO, "BENCH_SERVE_GEN*.json")),
                reverse=True):
            if os.path.abspath(path) == os.path.abspath(new_path):
                continue
            name = os.path.basename(path)
            try:
                doc = _load_serving_gen_doc(path)
            except (OSError, ValueError) as e:
                print(f"baseline discovery: skipping {name} "
                      f"(unreadable: {e})")
                continue
            if not doc:
                print(f"baseline discovery: skipping {name} "
                      "(no parseable generate artifact)")
                continue
            if not doc.get("tokens_per_s"):
                print(f"baseline discovery: skipping {name} "
                      "(null tokens/s — a failure artifact has no "
                      "measurement to compare against)")
                continue
            base_path, baseline = path, doc
            break
    problems = check_serving_gen(new, baseline, tolerance)
    if problems:
        for p in problems:
            print(f"serving-gen gate FAILED for {new_path}: {p}")
        return 1
    note = f" vs {base_path}" if baseline else \
        " (no baseline: standalone checks only)"
    print(f"serving-gen gate OK{note}: "
          f"tokens_per_s={new.get('tokens_per_s')} "
          f"speedup={new.get('speedup')}x "
          f"ttft_p99={new.get('ttft_p99_s')}s "
          f"itl_p99={new.get('itl_p99_s')}s "
          f"occupancy={new.get('slot_occupancy_mean')} "
          f"compiles={new.get('decode_compiles')} over "
          f"{new.get('requests')} requests")
    return 0


def _load_rollout_doc(path: str):
    """A rollout-bench artifact: raw JSON, or the last
    ``BENCH_ROLLOUT {json}`` line of captured bench output."""
    with open(path) as f:
        text = f.read()
    doc = None
    try:
        parsed = json.loads(text)
        if isinstance(parsed, dict) and parsed.get("bench") == "rollout":
            doc = parsed
    except ValueError:
        pass
    if doc is None:
        for line in text.splitlines():
            line = line.strip()
            if line.startswith("BENCH_ROLLOUT "):
                try:
                    parsed = json.loads(line[len("BENCH_ROLLOUT "):])
                except ValueError:
                    continue
                if isinstance(parsed, dict):
                    doc = parsed
    return doc


def check_rollout(new: dict, baseline, tolerance: float):
    """Problems with a rollout-bench artifact: list of failure strings.

    Standalone rules (ISSUE 18): (1) traffic was actually served
    during the rollout; (2) the zero-drop assertion — zero failed,
    zero unanswered, zero answered-twice across BOTH governed
    transitions (pin → rollback repin, pin → promote): a rollout that
    dropped a request is not 'governed'; (3) both transition latencies
    were measured (a null promote_s/rollback_s is a failure artifact,
    not a pass).  Baseline rule: neither latency may regress more than
    ``tolerance`` above the baseline's."""
    problems = []
    if not new.get("requests"):
        problems.append("no requests measured during the rollout")
    for key in ("failed", "unanswered", "answered_twice"):
        if new.get(key):
            problems.append(
                f"{key}={new[key]}: the rollout dropped/duplicated "
                "requests — the zero-drop assertion failed")
    for key in ("promote_s", "rollback_s"):
        v = new.get(key)
        if not isinstance(v, (int, float)) or v <= 0:
            problems.append(
                f"{key}={v}: transition latency was not measured "
                "(a failure artifact has no measurement)")
        elif baseline and isinstance(baseline.get(key), (int, float)) \
                and baseline[key] > 0 \
                and v > baseline[key] * (1.0 + tolerance):
            problems.append(
                f"{key} REGRESSION: {v:.3f}s vs baseline "
                f"{baseline[key]:.3f}s (> {tolerance:.0%} above)")
    return problems


def rollout_main(argv) -> int:
    new_path = argv[argv.index("--rollout") + 1]
    tolerance = float(argv[argv.index("--tolerance") + 1]) \
        if "--tolerance" in argv else 0.5
    new = _load_rollout_doc(new_path)
    if not new:
        print(f"no rollout artifact in {new_path}: run "
              "benchmarks/rollout_bench.py first")
        return 1
    baseline = None
    base_path = None
    if "--baseline" in argv:
        base_path = argv[argv.index("--baseline") + 1]
        baseline = _load_rollout_doc(base_path)
        if not baseline:
            print(f"baseline {base_path} carries no rollout artifact; "
                  "judging the new run standalone")
    else:
        # same loud-skip discovery convention as the serving-gen gate:
        # a skipped baseline must SAY why, and a failure artifact
        # (null latency) is never silently compared against
        for path in sorted(
                glob.glob(os.path.join(REPO, "BENCH_ROLLOUT*.json")),
                reverse=True):
            if os.path.abspath(path) == os.path.abspath(new_path):
                continue
            name = os.path.basename(path)
            try:
                doc = _load_rollout_doc(path)
            except (OSError, ValueError) as e:
                print(f"baseline discovery: skipping {name} "
                      f"(unreadable: {e})")
                continue
            if not doc:
                print(f"baseline discovery: skipping {name} "
                      "(no parseable rollout artifact)")
                continue
            if not doc.get("promote_s") or not doc.get("rollback_s"):
                print(f"baseline discovery: skipping {name} "
                      "(null transition latency — a failure artifact "
                      "has no measurement to compare against)")
                continue
            base_path, baseline = path, doc
            break
    problems = check_rollout(new, baseline, tolerance)
    if problems:
        for p in problems:
            print(f"rollout gate FAILED for {new_path}: {p}")
        return 1
    note = f" vs {base_path}" if baseline else \
        " (no baseline: standalone checks only)"
    print(f"rollout gate OK{note}: promote_s={new.get('promote_s')} "
          f"rollback_s={new.get('rollback_s')} zero-drop over "
          f"{new.get('requests')} requests")
    return 0


def main() -> int:
    # budget = bench.py's own hard total wall-clock cap
    # (HVD_BENCH_TOTAL_BUDGET_S, default 1200 s) plus slack: bench must
    # always get to print its failure JSON rather than be killed mid-loop
    budget = float(os.environ.get("HVD_BENCH_TOTAL_BUDGET_S", "1200"))
    try:
        out = subprocess.run(
            [sys.executable, os.path.join(REPO, "bench.py")],
            capture_output=True, text=True, cwd=REPO, timeout=budget + 120)
    except subprocess.TimeoutExpired as e:
        print("bench.py exceeded even the worst-case budget — the "
              "attempt loop itself is wedged (contract violation):\n"
              f"stderr tail: {(e.stderr or '')[-500:]}")
        return 1
    lines = [ln for ln in out.stdout.strip().splitlines() if ln.strip()]
    if len(lines) != 1:
        print(f"expected 1 stdout line, got {len(lines)}:\n{out.stdout}")
        return 1
    doc = json.loads(lines[0])
    for key in ("metric", "value", "unit", "vs_baseline", "mfu", "phases"):
        if key not in doc:
            print(f"missing key {key!r} in {doc}")
            return 1
    if doc["value"] is None and "error" not in doc:
        print(f"null value without diagnostic error: {doc}")
        return 1
    if (doc["value"] is None) != (out.returncode != 0):
        print(f"bench.py exit code {out.returncode} disagrees with "
              f"value={doc['value']!r}: a null exits non-zero, a "
              "measurement exits 0")
        return 1
    # per-phase timing contract: a run that got as far as touching devices
    # must say WHERE the wall clock went — either completed phases
    # (device_init, setup, compile, warmup, measure: cumulative seconds) or
    # at minimum the phase in flight at kill time. A child that died
    # BEFORE its first phase boundary (import crash, unwritable tmpdir)
    # legitimately has neither — there the diagnostic is doc["error"],
    # already required above.
    phases = doc["phases"]
    if not isinstance(phases, dict):
        print(f"'phases' is not a dict: {doc}")
        return 1
    if not any(isinstance(v, (int, float)) for v in phases.values()) \
            and not doc.get("phase_in_progress") \
            and not doc.get("error"):
        print(f"no per-phase timings and no phase_in_progress: {doc}")
        return 1
    known = {"device_init", "setup", "compile", "warmup", "measure"}
    bogus = set(phases) - known
    if bogus:
        print(f"unknown phase names {sorted(bogus)} in {doc}")
        return 1
    # trajectory contract: a doc with a REAL measured value must carry
    # a healthy within-window series (provisional/salvaged docs — the
    # deadline-kill path — legitimately have none).  The automatic gate
    # uses a wide band (default 1.0 = only 2x+ in-window collapses;
    # HVD_BENCH_TRAJECTORY_TOL overrides) — shared-CPU smoke windows
    # are noisy; the strict default lives in the explicit --trajectory
    # mode used for regression analysis
    if doc["value"] is not None and not doc.get("provisional"):
        series = doc.get("step_time_series")
        if series is not None:
            tol = float(os.environ.get("HVD_BENCH_TRAJECTORY_TOL", "1.0"))
            problem = check_trajectory(series, tolerance=tol)
            if problem:
                print(f"bench {problem}")
                return 1
    # parallelism-plan contract (ISSUE 11): a doc that names a plan must
    # name it coherently (automatic form of the --pipeline gate)
    problem = check_pipeline_plan(doc)
    if problem:
        print(f"bench {problem}")
        return 1
    # integrity contract (ISSUE 13): a run whose numeric guardrail
    # skipped steps did LESS optimizer work per measured "step" — its
    # throughput number is not comparable to a clean run and must not
    # pass as one (the skips themselves point at a data-plane problem
    # on the bench host)
    if doc["value"] is not None and doc.get("guard_skipped_steps"):
        print(f"bench run skipped {doc['guard_skipped_steps']} step(s) "
              f"under the numeric guardrail — not a clean perf number: "
              f"{doc}")
        return 1
    if doc["value"] is not None and doc.get("tracing_enabled") \
            and os.environ.get("HVD_BENCH_ALLOW_TRACING", "") != "1":
        print("bench run measured with causal tracing ENABLED "
              "(HVD_TPU_TRACE) — the standing perf number must not "
              "silently pay the tracing overhead; rerun with tracing "
              f"off or set HVD_BENCH_ALLOW_TRACING=1: {doc}")
        return 1
    print(f"bench contract OK: {doc}")
    return 0


if __name__ == "__main__":
    if "--compile-budget" in sys.argv:
        sys.exit(compile_budget_main(sys.argv))
    if "--tuned" in sys.argv:
        sys.exit(tuned_main(sys.argv))
    if "--scaling" in sys.argv:
        sys.exit(scaling_main(sys.argv))
    if "--goodput" in sys.argv:
        sys.exit(goodput_main(sys.argv))
    if "--trajectory" in sys.argv:
        sys.exit(trajectory_main(sys.argv))
    if "--pipeline" in sys.argv:
        sys.exit(pipeline_main(sys.argv))
    if "--rollout" in sys.argv:
        sys.exit(rollout_main(sys.argv))
    if "--serving-gen" in sys.argv:
        sys.exit(serving_gen_main(sys.argv))
    if "--serving" in sys.argv:
        sys.exit(serving_main(sys.argv))
    sys.exit(main())
