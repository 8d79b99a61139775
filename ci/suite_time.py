#!/usr/bin/env python
"""Where a pytest run's time went, from its junit file (ISSUE 57 satellite;
ROADMAP C11's table): case-seconds and cases a file, the twenty longest
cases, the sum, and the floor ``--dist loadfile`` puts on the wall clock
at ``n`` workers — a file is one worker's indivisible load, so the run
takes at least the larger of sum / n and the largest file. That floor is of
this run's seconds, not of the suite: the driver's six workers keep the
machine's eight cores 95 to 100 % busy from the first case to the last
files (PR 72, ``/proc/stat`` every 5 s over four whole runs), so a case's
seconds stretch with what runs beside it, the sum moves with the order and
the hour (7995 to 9624 case-seconds for one tree in one evening), and what
bounds the wall clock is the work in CPU-seconds over the cores.

    python ci/suite_time.py /tmp/_t1.xml [n_workers=6]
"""

from __future__ import annotations

import sys
import xml.etree.ElementTree as ET
from collections import defaultdict


def read(junit_xml: str):
    """(file, case, seconds) of every test case in a junit document."""
    return [(c.get("classname", "").rsplit(".", 1)[-1] + ".py",
             c.get("name", ""), float(c.get("time") or 0))
            for c in ET.fromstring(junit_xml).iter("testcase")]


def report(junit_xml: str, n_workers: int = 6) -> str:
    cases = read(junit_xml)
    files = defaultdict(lambda: [0.0, 0])
    for file, _, seconds in cases:
        files[file][0] += seconds
        files[file][1] += 1
    total = sum(s for s, _ in files.values())
    largest = max((s for s, _ in files.values()), default=0.0)
    lines = ["case-seconds  cases  file"]
    lines += [f"{s:12.1f}  {n:5d}  {f}" for f, (s, n) in
              sorted(files.items(), key=lambda kv: -kv[1][0])]
    lines += ["", "the twenty longest cases"]
    lines += [f"{s:12.1f}  {f}::{name}" for f, name, s in
              sorted(cases, key=lambda c: -c[2])[:20]]
    lines += ["", f"sum {total:.1f} case-seconds over {len(cases)} cases; "
              f"floor at {n_workers} loadfile workers "
              f"{max(total / n_workers, largest):.1f} s "
              f"(sum / n {total / n_workers:.1f}, largest file {largest:.1f})"]
    return "\n".join(lines)


if __name__ == "__main__":
    with open(sys.argv[1], encoding="utf-8") as f:
        print(report(f.read(), int(sys.argv[2]) if len(sys.argv) > 2 else 6))
