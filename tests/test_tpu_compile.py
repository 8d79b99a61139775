"""A benchmark cell's whole train step compiled for a described TPU v5e at
its real sizes (tests/test_tpu_compile_kernels.py's way, and its docstring's
caveats): short of the chip these are the only tests that hold a step's
``hbm_compiled_gb`` and that the kernels engage at a cell's shapes. A step
is a compile of one to two minutes on several threads, so each stands in a
file of its own (tests/test_tpu_compile_banded.py, _looped.py, _short_conv
.py, _ssm.py): under ``--dist loadfile`` a file is one worker's whole load,
and files named ``test_tpu_compile*`` are handed out last, where a compile's
threads use the cores the finished workers leave idle (ROADMAP C11). Here,
under the name the records cite, the share cell
smallthinker-21b-a3b.s8192's step, compiled once and read by four tests."""

import re

import jax
import pytest

from horovod_tpu.parallel import moe
from tpu_compile_cases import (arrays_in_memory,
                               assert_no_scatter_into_the_table, cell_step,
                               compile_cache_off, computations_of,
                               described_v5e,
                               loops_that_write_rows_in_place)


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


@pytest.fixture(scope="module")
def mixed_step(topo):
    """The cell smallthinker-21b-a3b.s8192's step compiled once for a
    described v5e (``jax.default_backend`` answering "tpu", the compile
    cache off), for the cases that read the compiled program: (compiled,
    the adapter's shapes, step_bytes)."""
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = cell_step(
            "smallthinker-21b-a3b.s8192", topo)
        compiled = step.lower(*args).compile()
    return compiled, shapes, step_bytes


def test_mixed_step_compiles_for_v5e_on_the_kernels(mixed_step):
    """The cell smallthinker-21b-a3b.s8192's step, one period of a full and
    three window layers at 8192 tokens with 28 / 4 grouped heads and 16 of
    64 experts held: every layer's attention is the two flash kernels (a
    call site a layer of the unrolled period, forward and backward; no
    score-shaped array in the program), the experts are ``hvd_moe_gmm``,
    the head ``hvd_fused_xent``; both layer kinds' scopes are in the
    program; the step fits with the room ISSUE 32 asks for. The expert
    layer's rows have no top-6 axis (``[8192, 6, 2560]`` is a copy padded
    to the tile's 8 or 16 sublanes) and none is an array in float32
    (ISSUE 36)."""
    compiled, shapes, step_bytes = mixed_step
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    layers = shapes["layers"]
    assert sum("hvd_flash_attention" in c for c in calls) == layers
    assert sum("hvd_flash_bwd" in c for c in calls) == layers
    assert sum(moe.GMM_NAME in c for c in calls) == 9 * layers
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    s = shapes["seq"]
    assert f",{s},{s}]" not in text, "a score-shaped array"
    k, m = shapes["experts_per_token"], shapes["d_model"]
    assert f"[{s},{k},{m}]" not in text, "the rows with a top-k axis"
    assert f"f32[{s * k},{m}]" not in arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.MIXED_PHASES:
        assert name + "/" in names, name
    assert 4.0e9 < step_bytes(compiled.memory_analysis())["total"] < 15.0e9


def test_mixed_step_moves_only_the_rows_it_holds(mixed_step):
    """ISSUE 37, on the same compiled step: of the expert layer's
    ``T * k`` = 49 152 sorted rows a quarter are an expert's here. What a
    row gather costs on the chip is set by its source (PERF.md section 6,
    PR 37), so: a gather out of the whole ``bf16[49152,2560]`` rows stands
    only in a conditional, beside a branch that gathers out of a prefix of
    them which XLA has copied on the chip (``S(1)``), two such
    conditionals a layer (the combine's forward, the dispatch's backward;
    the parent has those eight gathers unconditional); the other gathers
    read the ``[8192, 2560]`` tokens. No ``select`` writes a whole rows
    array (the parent zeroes the kernels' outputs behind the groups under
    a ``pred[49152]`` mask, five selects a layer), and the combine's
    backward pass is a loop over the held rows' chunks that writes into the
    rows in place, one a layer (the other loop a layer that writes into
    ``[49152, 2560]`` gathers nothing: ISSUE 44's sum, below)."""
    compiled, shapes, _step_bytes = mixed_step
    text = compiled.as_text()
    layers, width = shapes["layers"], shapes["d_model"]
    tokens = shapes["seq"]
    rows = tokens * shapes["experts_per_token"]
    whole = re.compile(r"bf16\[(%d|%d,%d),%d\]" % (
        rows, shapes["experts_per_token"], tokens, width))
    computations = computations_of(text)
    fused = set(re.findall(r"fusion\(.*calls=%([\w.\-]+)", text))
    loop_bodies = set(re.findall(r"body=%([\w.\-]+)", text))
    branches = [re.findall(r"%([\w.\-]+)", found) for found in re.findall(
        r"conditional\(.*branch_computations=\{([^}]*)\}", text)]

    def fusions(name):
        """(result type, first operand's name, body text) of every fusion
        that stands in computation ``name``."""
        for line in computations[name]:
            found = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) fusion\((%[\w.\-]+)"
                             r".*calls=%([\w.\-]+)", line)
            if found and found.group(3) in computations:
                yield (found.group(1), found.group(2),
                       "\n".join(computations[found.group(3)]))

    def writes_whole_rows(name, op):
        return any(whole.match(result) and f" {op}(" in body
                   for result, _operand, body in fusions(name))

    def gather_sources(name):
        """(source rows, whether the source lies on the chip) of every row
        gather (a fusion with a gather in its body) in computation
        ``name``."""
        found = []
        for _result, operand, body in fusions(name):
            source = re.search(r"= bf16\[(\d+),%d\]\S* parameter\(0\)" % width,
                               body)
            if " gather(" in body and source:
                made = re.compile(r"\s*(?:ROOT )?%s = (\S+) "
                                  % re.escape(operand))
                types = [m.group(1) for m in map(made.match,
                                                 computations[name]) if m]
                found.append((int(source.group(1)),
                              bool(types) and "S(1)" in types[0]))
        return found

    for name in computations:
        if name in fused or any(name in pair for pair in branches):
            continue
        assert rows not in [s for s, _ in gather_sources(name)], \
            (name, "a gather out of all the rows")
        assert not writes_whole_rows(name, "select"), name
    by_prefix = 0
    for pair in branches:
        sources = sorted(s for name in pair for s in gather_sources(name))
        if sources and sources[-1][0] == rows:
            prefix, on_chip = sources[0]
            assert prefix * width * 2 <= moe.GATHER_SOURCE_BYTES \
                < rows * width * 2
            assert on_chip, "the prefix is gathered from where it lies"
            by_prefix += 1
    assert by_prefix == 2 * layers
    # (and, since ISSUE 44, the loop that sums the rows' two cotangents)
    in_place = [name for name in loop_bodies
                if writes_whole_rows(name, "dynamic-update-slice")]
    assert len(in_place) == 2 * layers, in_place
    assert sorted([s for s, _ in gather_sources(name)] for name in in_place) \
        == [[]] * layers + [[tokens]] * layers


def test_mixed_step_runs_the_experts_row_wise_passes_over_held_rows(
        mixed_step):
    """On the compiled step of smallthinker-21b-a3b.s8192 (16 of 64 gated
    experts held): see :func:`loops_that_write_rows_in_place`. A
    layer's loops that write in place: the activation, its backward pass
    (one fusion that writes both cotangents and the hidden rows), the sum
    of the rows' cotangents, and the combine's backward pass (ISSUE 37)."""
    compiled, shapes, _step_bytes = mixed_step
    rows = shapes["seq"] * shapes["experts_per_token"]
    assert loops_that_write_rows_in_place(compiled.as_text(), rows) \
        == 4 * shapes["layers"]


def test_mixed_step_scatters_nothing_into_the_embedding_table(mixed_step):
    """The same, in the share cell's real step."""
    compiled, shapes, _step_bytes = mixed_step
    assert_no_scatter_into_the_table(compiled.as_text(), shapes["vocab"],
                                      shapes["d_model"])
