"""The metrics <-> docs lint (ci/check_metrics_docs.py, ISSUE 7
satellite): the real tree must be in sync with docs/OBSERVABILITY.md,
and the matcher semantics that keep the lint honest are pinned here."""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _lint():
    sys.path.insert(0, os.path.join(REPO, "ci"))
    try:
        import check_metrics_docs
        return check_metrics_docs
    finally:
        sys.path.pop(0)


def test_tree_and_docs_in_sync():
    """THE gate: every registered metric documented, no stale docs."""
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "ci",
                                      "check_metrics_docs.py")],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "lint OK" in out.stdout


def test_extraction_finds_known_registrations():
    lint = _lint()
    code = lint.code_metrics()
    # plain literal, f-string pattern, multi-line call, fleet g() helper
    assert "hvd_steps_total" in code
    assert "hvd_*_total" in code            # f"hvd_{metric_unit}_total"
    assert "hvd_anomaly_total" in code      # multi-line .counter(
    assert "hvd_fleet_straggler_rank" in code   # fleet's g(...)
    assert "hvd_engine_*" in code
    # registration sites are reported for the failure message
    assert any("callbacks.py" in s for s in code["hvd_steps_total"])


def test_generic_doc_pattern_does_not_blanket_document():
    lint = _lint()
    # hvd_engine_* documents any engine mirror...
    assert lint._doc_covers_code("hvd_engine_cache_hits", "hvd_engine_*")
    # ...but the fully generic per-unit convention must not swallow
    # arbitrary counters (the lint would never fire again)
    assert not lint._doc_covers_code("hvd_anomaly_total", "hvd_*_total")
    assert lint._doc_covers_code("hvd_*_total", "hvd_*_total")


def test_histogram_subseries_not_stale():
    lint = _lint()
    undocumented, stale, _code = lint.check()
    assert undocumented == []
    assert stale == []
    # docs show hvd_step_time_seconds_bucket{...} in examples; the
    # suffix-stripping keeps that from reading as a stale mention —
    # verified implicitly by stale == [] while the docs contain it
    docs = lint.doc_metrics()
    assert any(d.startswith("hvd_step_time_seconds_bucket")
               for d in docs)


def test_suite_time_reads_a_junit_file_s_cases():
    """ci/suite_time.py (ISSUE 57 satellite; ROADMAP C11's table): a file's
    case-seconds and cases, the longest case first, the sum, and the floor
    ``--dist loadfile`` puts on the wall clock: the larger of sum / n and
    the largest file."""
    sys.path.insert(0, os.path.join(REPO, "ci"))
    try:
        import suite_time
    finally:
        sys.path.pop(0)
    junit = ('<testsuites><testsuite tests="3">'
             '<testcase classname="tests.test_a" name="one" time="7.5" />'
             '<testcase classname="tests.test_a" name="two[x-1]" time="2.5" />'
             '<testcase classname="tests.test_b" name="three" time="4" />'
             '</testsuite></testsuites>')
    assert suite_time.read(junit) == [
        ("test_a.py", "one", 7.5), ("test_a.py", "two[x-1]", 2.5),
        ("test_b.py", "three", 4.0)]
    lines = suite_time.report(junit, 2).splitlines()
    assert lines[1].split() == ["10.0", "2", "test_a.py"]
    assert lines[2].split() == ["4.0", "1", "test_b.py"]
    assert lines[5].split() == ["7.5", "test_a.py::one"]
    assert lines[-1] == ("sum 14.0 case-seconds over 3 cases; floor at 2 "
                         "loadfile workers 10.0 s (sum / n 7.0, largest file "
                         "10.0)")
    assert suite_time.report(junit, 1).endswith(
        "14.0 s (sum / n 14.0, largest file 10.0)")


def test_perf_md_can_be_opened_whole():
    """``PERF.md`` stays under the 256 000 bytes at which a session's file
    reader refuses a file whole, no line over 8 000 (ROADMAP C8 e: it had
    grown to 559 175 bytes with lines of 21 KB, and a record nobody can
    open is a record nobody checks against the ledger). When Findings
    outgrows its room, fold the oldest entries; ``CHANGES.md`` keeps them."""
    with open(os.path.join(REPO, "PERF.md"), "rb") as f:
        text = f.read()
    assert len(text) < 256_000
    assert max(len(line) for line in text.split(b"\n")) <= 8_000
