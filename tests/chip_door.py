"""How tier-1 reads the benchmark it does not collect (a plain module, as
``arch.py`` is; pytest collects nothing here).

``BENCHMARK.json`` and ``benchmarks/chip/layer_metrics/`` are data, and the
door between program and benchmark is held in their terms: a cell's metrics
are the ``per_layer`` entries whose ``workloads`` hold the cell, whatever
they are called, however many there are and wherever they stand; a roofline
function is the one a metric's file names, found as the harness finds it.
So a ``benchmark`` PR may rename, merge and list without an edit to
``tests/``, and a (metric, cell) pair whose ``read`` finds nothing in the
program still fails here.

The benchmark's own test modules come by import (``benchmarks_own``), a
whole module at a time (``take``): a case a later PR adds there is tier-1's.
What this file leans on by name, and a ``benchmark`` PR therefore keeps:
``run.read_json``, ``run.roofline_function`` and
``tests/test_metric_lists.py:_gate(kernel, sizes, monkeypatch)``.
"""

import functools
import importlib.util
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmarks", "chip")
for _path in (REPO, CHIP):              # as benchmarks/chip/tests/conftest.py
    if _path not in sys.path:
        sys.path.insert(0, _path)

import run as harness                                     # noqa: E402


@functools.lru_cache(maxsize=None)
def benchmarks_own(module):
    """``benchmarks/chip/tests/<module>.py``, once a process."""
    spec = importlib.util.spec_from_file_location(
        "chip_" + module, os.path.join(CHIP, "tests", module + ".py"))
    loaded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loaded)
    return loaded


def take(module, into, fixtures=(), but=()):
    """Every ``test_*`` of the benchmark's ``module``, and the ``fixtures``
    they ask for, into the namespace ``into`` of a tier-1 file (one file a
    module: its load is one worker's, and two modules' fixtures of one name
    do not meet); ``but`` the cases that are the benchmark's alone."""
    own = benchmarks_own(module)
    for name in dir(own):
        if (name.startswith("test_") or name in fixtures) \
                and name not in but:
            into[name] = getattr(own, name)


def reads(cell) -> dict:
    """``{name: read}`` of every ``per_layer`` entry whose ``workloads``
    hold ``cell``, the ``read`` from the metric's file."""
    bench = harness.read_json(REPO, "BENCHMARK.json")
    assert cell in [w["name"] for w in bench["workloads"]], cell
    return {m["name"]: harness.read_json(
                CHIP, "layer_metrics", m["name"] + ".json")["read"]
            for m in bench["per_layer"] if cell in m.get("workloads", ())}


def roofline(cell, kernel):
    """The roofline function of the one metric that lists ``cell`` and
    reads ``kernel`` against a roofline, by ``run.roofline_function``."""
    named = {name: read["roofline"] for name, read in reads(cell).items()
             if read.get("trace_ops") == kernel and "roofline" in read}
    assert len(named) == 1, (
        f"{cell}: one metric should read {kernel} against a roofline; "
        f"{sorted(named) or 'none'} do")
    return harness.roofline_function(*named.values())


def readable(cell, sizes):
    """Every ``read`` of ``reads(cell)`` held against the program: a phase
    (a cover's, an owner's) is one ``profiling.scopes`` gives a device
    instruction; a kernel is one the program's own gate takes at ``sizes``
    (the benchmark's ``_gate``: the backend answered as ``rehearse.py``
    answers it, for this check only); a roofline function is found as the
    harness finds it and counts positive work at ``sizes``. Returns what a
    case pins its mechanism by: the phases the cell's metrics read, and the
    kernels they read against a roofline."""
    from horovod_tpu.profiling import scopes
    gate = benchmarks_own("test_metric_lists")._gate
    phases, rooflined = set(), set()
    for name, read in reads(cell).items():
        phase = read.get("trace_scope", read).get("phase")
        if phase is not None:
            assert phase in scopes.DEVICE_PHASES, (name, phase)
            phases.add(phase)
        kernel = read.get("trace_ops")
        if not (kernel and re.fullmatch(r"hvd_\w+", kernel)):
            continue
        with pytest.MonkeyPatch.context() as patch:
            assert gate(kernel, sizes, patch), (
                f"{name}: the program's gate refuses {kernel} at {cell}'s "
                f"shapes")
        if "roofline" in read:
            need = harness.roofline_function(read["roofline"])(sizes)
            assert need["flops"] > 0 and need["bytes"] > 0, name
            rooflined.add(kernel)
    return phases, rooflined
