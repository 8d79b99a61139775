#!/usr/bin/env python
"""CI matrix runner (reference analog: the Buildkite pipeline scripts
driving docker-compose test services). Usage:

    python ci/run.py               # every tier
    python ci/run.py --tier single parallel
    python ci/run.py --list
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

import yaml

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_matrix() -> dict:
    with open(os.path.join(REPO, "ci", "matrix.yaml")) as f:
        return yaml.safe_load(f)["tiers"]


def run_tier(name: str, spec: dict) -> bool:
    print(f"=== tier {name}: {spec['description'].strip()}", flush=True)
    timeout = spec.get("timeout_minutes", 30) * 60
    if "setup" in spec:
        rc = subprocess.run(spec["setup"], shell=True, cwd=REPO).returncode
        if rc != 0:
            print(f"--- tier {name}: SETUP FAILED rc={rc}", flush=True)
            return False
    cmd = [sys.executable, "-m", "pytest", "-q", *spec["paths"]]
    t0 = time.time()
    try:
        rc = subprocess.run(cmd, cwd=REPO, timeout=timeout).returncode
    except subprocess.TimeoutExpired:
        print(f"--- tier {name}: TIMEOUT after {timeout}s", flush=True)
        return False
    print(f"--- tier {name}: {'OK' if rc == 0 else f'FAILED rc={rc}'} "
          f"({time.time() - t0:.0f}s)", flush=True)
    return rc == 0


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--tier", nargs="*", default=None)
    p.add_argument("--list", action="store_true")
    args = p.parse_args()
    matrix = load_matrix()
    if args.list:
        for name, spec in matrix.items():
            print(f"{name}: {spec['description'].strip()}")
        return 0
    names = args.tier or list(matrix)
    failed = [n for n in names if not run_tier(n, matrix[n])]
    if failed:
        print(f"FAILED tiers: {failed}", flush=True)
        return 1
    print("all tiers OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
