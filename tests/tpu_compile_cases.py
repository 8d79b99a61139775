"""What the files that compile for a described TPU v5e share (a plain module;
pytest collects nothing here): the topology, the compile cache's switch, a
benchmark cell's step at its real sizes, and the readers of a compiled
program's text."""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.parallel import moe

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmarks", "chip")


def described_v5e():
    """A described (not attached) v5e 2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e!r}")


@contextlib.contextmanager
def compile_cache_off():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip (the next one warns), so the
    cache is off around these."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def sum32(*xs):
    return sum(x.astype(jnp.float32).sum() for x in xs)


def cell_step(cell, topo):
    """(jitted step, its abstract arguments, the adapter's shapes) of a
    cell of BENCHMARK.json at its real sizes, on one described chip: what
    ``benchmarks/chip/rehearse.py compile`` builds."""
    import importlib
    import sys
    for path in (REPO, CHIP):
        if path not in sys.path:
            sys.path.insert(0, path)
    import horovod_tpu as hvd
    import run as harness
    _bench, entry, config, job = harness.load_cell(cell, tiny=False)
    mesh = hvd.build_mesh(devices=topo.devices[:entry["chips"]],
                          **job["mesh"])
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    step, args = adapter.abstract_step(config, job, mesh,
                                       harness.make_optimizer(job))
    return step, args, adapter.shapes(config, job), harness.step_bytes


def arrays_in_memory(text):
    """The lines of a compiled program's text outside its fused
    computations: each is an instruction whose result is an array in
    memory. Inside a fusion's body the same shapes are values the fusion
    holds a tile of at a time."""
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    lines, inside = [], False
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            inside = head.group(1) in fused
        elif not inside:
            lines.append(line)
    return "\n".join(lines)


def computations_of(text):
    """{name: its instruction lines} of a compiled program's text."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            lines = found.setdefault(head.group(1), [])
        elif lines is not None and " = " in line:
            lines.append(line)
    return found


def row_array_writers(text, n_rows):
    """(where, what, the line) of every instruction of a compiled program,
    outside its fused computations, whose result (a tuple's first element)
    is a whole ``[n_rows, ..]`` array in memory and that writes it: where
    is "loop" (a ``while``'s body), "branch" (a ``conditional``'s) or
    "outside"; what is the opcode, for a custom call its target's name or,
    of a Pallas call, the kernel's. Not counted: what hands an array on
    (tuples and their elements, bitcasts, parameters, barriers, the
    containers themselves) and XLA's own moves of a buffer between HBM and
    on-chip memory (``copy-start`` / ``copy-done``: the parent's step has
    them too)."""
    computations = computations_of(text)
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    loops = set(re.findall(r"body=%([\w.\-]+)", text))
    branches = {name for found in re.findall(
        r"branch_computations=\{([^}]*)\}", text)
        for name in re.findall(r"%([\w.\-]+)", found)}
    hands_on = {"get-tuple-element", "tuple", "bitcast", "parameter", "while",
                "conditional", "opt-barrier", "copy-start", "copy-done"}
    whole = re.compile(
        r"\s*(?:ROOT )?%%[\w.\-]+ = (?:\(\w+\[%d,\d+\].*?\)|\w+\[%d,\d+\]\S*) "
        r"([\w\-]+)\(" % (n_rows, n_rows))
    found = []
    for name, lines in computations.items():
        if name in fused:
            continue
        where = ("loop" if name in loops else
                 "branch" if name in branches else "outside")
        for line in lines:
            made = whole.match(line)
            if not made or made.group(1) in hands_on:
                continue
            what = made.group(1)
            if what == "custom-call":
                what = re.search(r'custom_call_target="(\w+)"', line).group(1)
                if what == "tpu_custom_call":
                    what = moe.GMM_NAME if moe.GMM_NAME in line else line
            elif what == "fusion":
                body = "\n".join(computations[re.search(
                    r"calls=%([\w.\-]+)", line).group(1)])
                what = ("gather" if " gather(" in body else
                        "dynamic-update-slice"
                        if " dynamic-update-slice(" in body else line)
            found.append((where, what, line))
    return found


def loops_that_write_rows_in_place(text, n_rows):
    """ISSUE 44: outside a loop's body and a conditional's branch nothing
    writes a whole array of the sorted rows but the grouped-matmul kernels
    and the dispatch's gather out of the tokens (the hidden rows' buffer is
    allocated, not written); so no ``add`` of the rows' two cotangents, no
    activation and no ``reduce-precision`` over all the rows is left. The
    loops that write into such arrays in place (returned: how many) are the
    activation, its backward pass and, for gated experts, the cotangents'
    sum, a layer."""
    writers = row_array_writers(text, n_rows)
    outside = {what for where, what, _line in writers if where == "outside"}
    assert outside <= {moe.GMM_NAME, "gather", "AllocateBuffer"}, outside
    in_loops = [what for where, what, _line in writers if where == "loop"]
    assert set(in_loops) == {"dynamic-update-slice"}, set(in_loops)
    return len(in_loops)


def assert_no_scatter_into_the_table(text, vocab, width):
    scattered = re.search(
        rf"^.* = \w+\[{vocab},{width}\]\S* scatter\(.*$", text, re.M)
    assert not scattered, \
        "the table's gradient is scattered:\n" + scattered.group(0)
    assert f"bf16[{vocab},{width}]" not in text, \
        "a bf16 copy of the table: the lookup casts it whole"
