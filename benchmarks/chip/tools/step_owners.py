"""The traced step's device time by owner and reason, whole, from one
``run.py --trace 1``.

    python3 benchmarks/chip/tools/step_owners.py --workload <cell> \
        --seed <n> --seconds <s>

The metrics that read the attribution are ``run.py``'s
(``layer_metrics/step.recompute_ms`` and the others whose ``read`` names the
reader ``step_owners``), and the reader prints the rows over 0.25 ms on
standard error. This runs the cell once, in this process, and prints as its
last line everything ``readers/step_owners.py:table`` knows: every owner x
reason row, the reasons' totals beside the compute events' summed durations
(the partition), ``scope_reduce``'s kind of each instruction against the
reason it got, the ``unowned`` instructions by name, the time of
instructions that hold recomputed names but were decided otherwise, and
what the attribution cost beside the record's ``phases`` (``parse_s``,
``reduce_s``, of which the attribution is a part). The same document is
written beside the run's record as ``<cell>.seed<n>.owners.json``.
``--rehearse`` walks the tiny sizes on the CPU, where a trace has no device
plane and there is nothing to attribute.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

import run as harness       # noqa: E402  (set-up is counted from here)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--out", default=os.path.join(harness.ROOT,
                                                  "chiprun_out", "bench"))
    args = ap.parse_args(argv)
    code = harness.main(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds",
         str(args.seconds), "--trace", "1", "--out", args.out]
        + ["--rehearse"] * args.rehearse)
    if code:
        return code
    from readers import step_owners
    path = harness.record_path(args.out, args.workload, args.seed, 1)
    record = harness.read_json(path)
    found = step_owners.LAST
    doc = {"cell": args.workload, "seed": args.seed,
           "record": os.path.relpath(path, harness.ROOT),
           "metrics": record["result"]["metrics"],
           "phases": {k: record["phases"].get(k) for k in (
               "hlo_text_bytes", "hlo_text_s", "parse_s", "reduce_s",
               "report_s", "unmatched_instructions", "identity")},
           "owners": step_owners.table(found) if found is not None else None}
    with open(path[:-len(".trace1.json")] + ".owners.json", "w") as f:
        json.dump(doc, f, indent=1)
    print(json.dumps(doc), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchFailure as e:
        print(f"benchmarks/chip/tools/step_owners.py: {e}", file=sys.stderr)
        sys.exit(2)
