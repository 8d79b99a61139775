"""A metric lists a cell only where the cell's program gives its ``read``
something to read, and one ``read`` has one name.

A metric's list of cells stands in ``BENCHMARK.json`` alone (its file in
``layer_metrics/`` holds the ``read`` and the words, and no copy of the
list), so a cell joins an accepted metric by its name in that entry's
``workloads``, in the PR that brings the cell, with no edit to a file.

One case a (metric, cell) pair of ``BENCHMARK.json``, as far as a CPU can
tell:

* a ``trace_scope`` phase, or a ``step_owners`` phase: the phase is in the
  lowered text of the cell's ``tiny`` step (lowered once a cell, never
  compiled);
* a roofline share: its function takes ``adapter.shapes(config, job)`` at
  the published sizes and returns positive ``flops`` and ``bytes``;
* a kernel (a sum over it, or a share of its roofline): the program's own
  gate for that kernel (``flash_eligible``, ``flash_backward``'s head of
  whole lane tiles, ``gmm_path``, ``xent_path``, ``ssm_scan_path``) takes
  the kernel at the published shapes, the backend answered as
  ``rehearse.py`` answers it;
* a collective: the cell has more than one chip.

Beside them the rule of README.md, "One reading, one metric", as a CPU can
hold it, whatever the metrics and cells are: one case a file that no other
file has its ``read``; no roofline function only calls another's (a copy of
a count under a second name); one case a metric that lists several cells
that the list is in the benchmark's order and that what differs in each
cell is said, in the file's ``what`` or in the cell's own configuration
(``"reads": {"<metric>": "<words>"}``: where a cell that joins later says
it); and no name that a merge retired (``retired_*.json``: old name, and
the name that took its ``read``) stands again, since the ledger's history
of it has ended.
"""

import ast
import functools
import glob
import importlib
import os
import re

import pytest

import run as harness       # (conftest.py puts CHIP on the path)

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
BENCH = harness.read_json(ROOT, "BENCHMARK.json")
SPECS = {m["name"]: harness.read_json(CHIP, "layer_metrics",
                                      m["name"] + ".json")
         for m in BENCH["per_layer"]}
PAIRS = [(m["name"], cell) for m in BENCH["per_layer"]
         for cell in m.get("workloads", ())]
COLLECTIVE = "all-reduce"
CELLS = [w["name"] for w in BENCH["workloads"]]
SHARED = {m["name"]: m["workloads"] for m in BENCH["per_layer"]
          if len(m.get("workloads", ())) > 1}


@functools.lru_cache(maxsize=None)
def _cell(name: str, tiny: bool):
    _bench, entry, config, job = harness.load_cell(name, tiny)
    return entry, config, job, importlib.import_module(
        f"adapters.{config['adapter']}")


@functools.lru_cache(maxsize=None)
def _lowered(name: str) -> str:
    """The cell's ``tiny`` step as StableHLO with its ``op_name`` paths."""
    import jax
    import horovod_tpu as hvd
    entry, config, job, adapter = _cell(name, True)
    mesh = hvd.build_mesh(devices=jax.devices()[:entry["chips"]],
                          **job["mesh"])
    step, shapes = adapter.abstract_step(config, job, mesh,
                                         harness.make_optimizer(job))
    return step.lower(*shapes).as_text(debug_info=True)


def _gate(kernel: str, sizes: dict, monkeypatch) -> bool:
    """Whether the program's own gate takes ``kernel`` at ``sizes``."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops import pallas_attention, pallas_ssm, pallas_xent
    from horovod_tpu.parallel import moe
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rows = sizes["batch"] * sizes["seq"]
    if kernel in ("hvd_flash_attention", "hvd_flash_bwd"):
        return pallas_attention.flash_eligible(
            sizes["seq"], sizes["seq"], sizes["head_dim"])
    if kernel == pallas_attention.ADJ_NAME:
        # (``flash_backward``: a head of 64 goes heads first and keeps the
        # row sums ``jax.numpy``)
        return pallas_attention.flash_eligible(
            sizes["seq"], sizes["seq"], sizes["head_dim"]) \
            and sizes["head_dim"] % pallas_attention.MIN_BLOCK == 0
    if kernel == "hvd_fused_xent":
        return pallas_xent.xent_path(rows, sizes["vocab"],
                                     jnp.bfloat16)[0] == "kernel"
    if kernel == moe.GMM_NAME:
        # (the assignments' rows: the held ones are a prefix of them)
        return moe.gmm_path(
            rows * sizes["experts_per_token"], sizes["d_model"],
            sizes.get("d_expert", sizes["d_ff"])).startswith("pallas")
    if kernel == pallas_ssm.FWD_NAME:
        return pallas_ssm.ssm_scan_path(
            sizes["seq"], sizes["ssm_heads"], sizes["ssm_head_dim"],
            sizes["ssm_groups"], sizes["ssm_state"],
            sizes["ssm_chunk"]).startswith("kernels")
    raise AssertionError(f"no gate known for the kernel {kernel!r}")


def _something_to_read(read: dict, cell: str, monkeypatch) -> None:
    entry, config, job, adapter = _cell(cell, False)
    phase = read.get("trace_scope", read).get("phase")
    if phase is not None:
        # a whole path component, its jvp(..) / transpose(..) peeled off
        assert re.search(r'[/("]' + re.escape(phase) + r'[/)"]',
                         _lowered(cell)), f"{cell}'s tiny step has no {phase}"
        return
    assert "trace_ops" in read, read
    if COLLECTIVE in read["trace_ops"]:
        assert entry["chips"] > 1
        return
    sizes = adapter.shapes(config, job)
    if "roofline" in read:
        need = harness.roofline_function(read["roofline"])(sizes)
        assert need["flops"] > 0 and need["bytes"] > 0
    assert _gate(read["trace_ops"], sizes, monkeypatch), (
        f"the program's gate refuses {read['trace_ops']} at {cell}'s shapes")


@pytest.mark.parametrize("name, cell", PAIRS)
def test_the_cell_gives_the_metric_something_to_read(name, cell,
                                                     monkeypatch):
    _something_to_read(SPECS[name]["read"], cell, monkeypatch)


def _only_calls() -> dict:
    """The roofline functions whose body is ``return other(shapes)`` with
    ``shapes`` unchanged (a copy of a name, not a count of its own), each
    with the function it calls."""
    bare = {}
    for path in sorted(glob.glob(os.path.join(CHIP, "roofline*.py"))):
        with open(path) as f:
            tree = ast.parse(f.read(), path)
        for node in tree.body:
            if not isinstance(node, ast.FunctionDef):
                continue
            body = [s for s in node.body
                    if not (isinstance(s, ast.Expr)
                            and isinstance(s.value, ast.Constant))]
            if (len(body) == 1 and isinstance(body[0], ast.Return)
                    and isinstance(body[0].value, ast.Call)
                    and [ast.dump(a) for a in body[0].value.args]
                    == [ast.dump(ast.Name("shapes", ast.Load()))]
                    and not body[0].value.keywords):
                bare[node.name] = body[0].value.func.id
    return bare


@pytest.mark.parametrize("name", sorted(SPECS))
def test_one_read_has_one_name(name):
    """A second file with this file's ``read`` is a copy of a number: the
    cell joins this metric's list in ``BENCHMARK.json`` instead."""
    read = SPECS[name]["read"]
    assert [n for n, spec in SPECS.items() if spec["read"] == read] == [name]


def test_no_roofline_function_only_calls_another():
    assert not _only_calls()


@pytest.mark.parametrize("name", sorted(SHARED))
def test_a_shared_metric_says_what_each_cell_reads(name):
    listed = SHARED[name]
    assert listed == sorted(listed, key=CELLS.index)
    configs = {c["name"]: c["file"] for c in BENCH["configs"]}
    unsaid = [w["name"] for w in BENCH["workloads"]
              if w["name"] in listed
              and w["name"] not in SPECS[name]["what"]
              and name not in harness.read_json(
                  ROOT, configs[w["config"]]).get("reads", {})]
    assert not unsaid, (
        f"neither {name}'s what nor the configuration's reads says what "
        f"{unsaid} read")


def test_no_retired_name_stands_again():
    for path in sorted(glob.glob(os.path.join(CHIP, "tests",
                                              "retired_*.json"))):
        retired = harness.read_json(path)
        assert not set(retired) & set(SPECS), path
