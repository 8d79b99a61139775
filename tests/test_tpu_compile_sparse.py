"""The cell keye-vl-2.0-30b-a3b.s16384's whole step compiled for a described
TPU v5e (tests/test_tpu_compile_kernels.py's way)."""

import re

import jax
import pytest

from horovod_tpu.parallel import moe
from tpu_compile_cases import cell_step, compile_cache_off, described_v5e


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


def _instructions(text):
    """(the computation's name, the line) of every instruction of a compiled
    module's text."""
    where = None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\{\s*$", line)
        if head:
            where = head.group(1)
        elif where and " = " in line:
            yield where, line


def _result(line):
    """The result's shape of an instruction's line."""
    found = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) [\w\-]+\(", line)
    return found.group(1) if found else ""


def test_sparse_step_compiles_for_v5e_under_the_chip_s_memory(topo):
    """The cell's step: four blocks of grouped-query attention (32 / 4 heads
    of 128) under a learned top-2048 index over 16 384 keys and 16 of 128
    experts held, every block checkpointed. The index and the selection are
    XLA code a block of 128 query rows at a time; the core is the three
    sparse kernels (Mosaic takes them at the cell's shapes): in a band's map
    ``hvd_sparse_fwd`` once (eight bands: the layers are one scan, and the
    backward pass runs it never: the layer's checkpoint keeps its outputs)
    and ``hvd_sparse_mean`` twice (forward, and in each block's checkpoint
    for the KL's gradient), ``hvd_sparse_bwd`` once a layer beside
    ``hvd_flash_adj``, and no other flash kernel; the index score pass's
    backward is ``hvd_index_bwd`` once a band's backward body in two shapes
    (half the sequence's k tiles or all), and no instruction outside a
    fusion has a block's ``[128, 16, keys]`` products for its result, in
    float32 or bfloat16 (the parent wrote them a band's backward body, 134
    MB a block at 16 384 keys); no float array with two
    sequence-long dimensions, the selection's own bytes alone; no block's dk
    / dv added into a band's float32 keys; the experts are ``hvd_moe_gmm``
    (twelve calls a layer: the checkpointed forward's three run twice) and
    the head ``hvd_fused_xent``; the index's five scopes are in the program;
    the bytes are under the compiler's 15.75 GB and round the measured
    figure."""
    from horovod_tpu.ops import pallas_attention as pa
    from horovod_tpu.ops import pallas_sparse_attention as ps
    from horovod_tpu.ops import sparse_attention as sa
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = cell_step(
            "keye-vl-2.0-30b-a3b.s16384", topo)
        compiled = step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert (shapes["layers"], shapes["index_topk"], shapes["seq"]) == (
        4, 2048, 16384)
    s = shapes["seq"]
    bands = sa.blocks(s)[1]
    assert bands == 8

    def count(name):     # (an operand may carry another call's name)
        return sum(bool(re.match(r"\s*(?:ROOT )?%%%s[\w.]* = " % name, c))
                   for c in calls)
    assert count(ps.FWD_NAME) == bands
    assert count(ps.MEAN_NAME) == 2 * bands
    assert count(ps.BWD_NAME) == 1 and count(pa.ADJ_NAME) == 1
    assert count("hvd_flash") == 1                     # (the adj's)
    index_calls = [c for c in calls if re.match(
        r"\s*(?:ROOT )?%%%s[\w.]* = " % ps.INDEX_BWD_NAME, c)]
    assert len(index_calls) == bands
    assert len({re.search(r"operand_layout_constraints=\{[^}]*\}", c).group()
                for c in index_calls}) <= 2
    products = re.compile(r"\b(?:f32|bf16)\[%d,%d,\d{4,}\]" % (
        ps.ROWS, shapes["index_heads"]))
    written = [line.split(" = ")[0].strip() + " in " + where
               for where, line in _instructions(text)
               if "fused_computation" not in where
               and products.search(_result(line))]
    assert not written, written
    # the layers are one scan: its forward body's three calls, and in the
    # backward body the checkpointed block's three again and the six behind
    assert count(moe.GMM_NAME) == 12
    assert count("hvd_fused_xent") == 1
    square = set(re.findall(r"(\w+)\[(?:\d+,)*%d,%d\]" % (s, s), text))
    assert square <= {"pred", "s8", "u8"}, square
    # the kept selection is the kernels' mask, a bit a (query, key)
    assert re.search(r"s8\[(?:\d+,)*%d,%d\]" % (ps.PIECE, ps.ROWS), text)
    assert not re.search(r"bitcast_add_fusion[.\d]* = f32\[\d+,%d,%d\]" % (
        shapes["kv_heads"], shapes["head_dim"]), text)
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.INDEX_PHASES:
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 14.0e9 < total < 14.4e9, total      # PERF.md section 6, PR 65
