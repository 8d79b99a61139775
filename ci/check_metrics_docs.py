#!/usr/bin/env python
"""Metrics <-> docs lint (ISSUE 7 satellite): every metric name the tree
registers must appear in ``docs/OBSERVABILITY.md``, and every metric the
docs name must still exist in the tree.

Extraction is static: a registration is a string literal passed as the
first argument of a ``.counter(`` / ``.gauge(`` / ``.histogram(`` call
(the registry API), of the fleet renderer's ``g(`` helper
(``metrics/fleet.py`` synthesizes its breakdown gauges directly into the
snapshot), or of an exception-proofing ``_metric(`` wrapper
(``runner/kv_relay.py``).  F-string placeholders (``f"hvd_{unit}_total"``) become
wildcards, matched against the docs' ``hvd_<unit>_total`` convention
(``<...>`` also becomes a wildcard); histograms implicitly export
``_bucket``/``_sum``/``_count`` sub-series, so those suffixes are
stripped before matching a docs mention back to code.

Exit 0 = in sync. Exit 1 prints each missing/stale name. Run from CI
(``tests/test_metrics_docs.py`` wraps it) or by hand:

    python ci/check_metrics_docs.py [--list]
"""

from __future__ import annotations

import fnmatch
import os
import re
import sys
from typing import Dict, List, Set, Tuple

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(REPO, "docs", "OBSERVABILITY.md")

# where registrations live (tests register throwaway names on purpose)
SCAN_ROOTS = ("horovod_tpu",)
SCAN_FILES = ("__graft_entry__.py",)

_REG_CALL = re.compile(
    r'(?:\.(?:counter|gauge|histogram)|\bg|\b_metric)\('
    r'\s*(f?)"(hvd_[^"]+)"', re.S)
# docs mention: hvd_name, hvd_<unit>_name, hvd_engine_* ... optionally
# followed by a {label=...} part (stripped)
_DOC_NAME = re.compile(r"\bhvd_[A-Za-z0-9_<>*]*[A-Za-z0-9_>*]")

# C API symbols, file/dir names etc. that look like metrics but are not
# registry instruments; docs name them in other contexts
_NOT_METRICS = {"hvd_engine_state_json", "hvd_stragglers_json",
                "hvd_timeline_mark", "hvd_timeline_enabled",
                "hvd_counters_json", "hvd_shutdown_force",
                "hvd_mfu_registered",
                "hvd_autopsy",        # the autopsy bundle directory
                "hvd_profile",        # the trace-capture retention dir
                "hvd_flight_rank*"}   # crash flight-dump filenames
_HIST_SUFFIXES = ("_bucket", "_sum", "_count")


def _norm_code(name: str, is_fstring: bool) -> str:
    if is_fstring:
        name = re.sub(r"\{[^}]*\}", "*", name)
    return name


def _norm_doc(tok: str) -> str:
    return re.sub(r"<[^>]*>", "*", tok)


def code_metrics() -> Dict[str, List[str]]:
    """{normalized metric pattern: [file:line, ...]} from the tree."""
    out: Dict[str, List[str]] = {}
    paths = [os.path.join(REPO, f) for f in SCAN_FILES]
    for root in SCAN_ROOTS:
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            paths.extend(os.path.join(dirpath, f) for f in files
                         if f.endswith(".py"))
    for path in paths:
        try:
            with open(path) as f:
                text = f.read()
        except OSError:
            continue
        for m in _REG_CALL.finditer(text):
            name = _norm_code(m.group(2), bool(m.group(1)))
            line = text[:m.start()].count("\n") + 1
            rel = os.path.relpath(path, REPO)
            out.setdefault(name, []).append(f"{rel}:{line}")
    return out


def doc_metrics() -> Set[str]:
    with open(DOC) as f:
        text = f.read()
    return {_norm_doc(tok) for tok in _DOC_NAME.findall(text)}


def _pattern_match(a: str, b: str) -> bool:
    """Either side may carry ``*`` wildcards."""
    return a == b or fnmatch.fnmatchcase(a, b) or fnmatch.fnmatchcase(b, a)


def _doc_covers_code(name: str, d: str) -> bool:
    """Does doc mention ``d`` document code metric ``name``?  A doc
    wildcard must carry a meaningful literal prefix (``hvd_engine_*``
    yes, the fully generic ``hvd_*_total`` from the per-unit naming
    convention no) — otherwise one generic mention would 'document'
    every future counter and the lint would never fire again."""
    if name == d:
        return True
    if "*" in d:
        prefix = d.split("*", 1)[0]
        return len(prefix) > len("hvd_") and \
            fnmatch.fnmatchcase(name, d)
    return False


def check() -> Tuple[List[str], List[str], Dict[str, List[str]]]:
    """Returns (undocumented code metrics, stale doc metrics, all code
    metrics with their registration sites)."""
    code = code_metrics()
    docs = doc_metrics()
    undocumented = [
        name for name in sorted(code)
        if not any(_doc_covers_code(name, d) for d in docs)]

    def in_code(doc_name: str) -> bool:
        candidates = [doc_name]
        for suf in _HIST_SUFFIXES:  # histogram sub-series in examples
            if doc_name.endswith(suf):
                candidates.append(doc_name[:-len(suf)])
        return any(_pattern_match(c, k) for c in candidates for k in code)

    stale = [d for d in sorted(docs)
             if d not in _NOT_METRICS and not in_code(d)]
    return undocumented, stale, code


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    undocumented, stale, code = check()
    if "--list" in argv:
        for name, sites in sorted(code.items()):
            print(f"{name}  ({sites[0]})")
        return 0
    rc = 0
    for name in undocumented:
        print(f"UNDOCUMENTED metric {name!r} (registered at "
              f"{', '.join(code[name][:3])}) — add it to "
              "docs/OBSERVABILITY.md")
        rc = 1
    for name in stale:
        print(f"STALE docs mention {name!r} — docs/OBSERVABILITY.md names "
              "a metric nothing in the tree registers")
        rc = 1
    if rc == 0:
        print(f"metrics docs lint OK: {len(code)} registered metric "
              f"name(s), all documented; no stale docs mentions")
    return rc


if __name__ == "__main__":
    sys.exit(main())
