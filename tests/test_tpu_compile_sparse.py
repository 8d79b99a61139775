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


def test_sparse_step_compiles_for_v5e_under_the_chip_s_memory(topo):
    """The cell's step: four blocks of grouped-query attention (32 / 4 heads
    of 128) under a learned top-2048 index over 16 384 keys and 16 of 128
    experts held, every block checkpointed. The index, the selection and the
    core are XLA code a block of 128 query rows at a time: no flash kernel in
    the program and no array with two sequence-long dimensions beside the
    selection's own bytes; the experts are ``hvd_moe_gmm`` (twelve calls a
    layer: the checkpointed forward's three run twice) and the head
    ``hvd_fused_xent``; the index's five scopes are in the program; the
    bytes are what the configuration's ``deployment`` says, under the
    compiler's 15.75 GB."""
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
    assert not any("hvd_flash" in c for c in calls)
    # the layers are one scan: its forward body's three calls, and in the
    # backward body the checkpointed block's three again and the six behind
    assert sum(moe.GMM_NAME in c for c in calls) == 12
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    s = shapes["seq"]
    square = set(re.findall(r"(\w+)\[(?:\d+,)*%d,%d\]" % (s, s), text))
    assert square <= {"pred", "s8", "u8"}, square   # the kept selection
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.INDEX_PHASES:
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 14.3e9 < total < 14.7e9, total      # PERF.md section 6, PR 64
