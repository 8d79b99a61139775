"""The cell kimi-linear-48b-a3b.s8192's whole step compiled for a described
TPU v5e (tests/test_tpu_compile_kernels.py's way)."""

import re

import jax
import pytest

from horovod_tpu.parallel import moe
from tpu_compile_cases import cell_step, compile_cache_off, described_v5e


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


def test_delta_step_compiles_for_v5e_with_no_array_of_two_sequence_lengths(
        topo):
    """The cell kimi-linear-48b-a3b.s8192's step: a leading delta + dense
    layer and one period of three delta blocks and one latent block (keys of
    192, values of 128, nothing rotated), each with 8 of 256 experts held,
    one sequence of 8192, the delta and latent blocks checkpointed. The
    latent block is the flash kernels at 32 heads of the padded 256 (two
    forward calls: the block runs again in the backward pass), the experts
    are ``hvd_moe_gmm`` at 2304 <-> 1024, the head ``hvd_fused_xent``; the
    delta rule's scan is XLA code under its six scopes, a ``while`` over the
    128 chunks in it, and no array of the program has two dimensions a
    sequence long; the bytes are under the compiler's 15.75 GB."""
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = cell_step(
            "kimi-linear-48b-a3b.s8192", topo)
        compiled = step.lower(*args).compile()
        assert moe.gmm_path(8192 * 8, 2304, 1024).startswith("pallas")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert (shapes["layers"], shapes["delta_layers"],
            shapes["attention_layers"], shapes["routed_layers"]) == (
                5, 4, 1, 4)
    assert sum("hvd_flash_attention" in c for c in calls) == 2
    assert sum("hvd_flash_bwd" in c for c in calls) == 1
    assert sum("hvd_flash_adj" in c for c in calls) >= 1
    assert sum(moe.GMM_NAME in c for c in calls) == 9 * 4
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    s = shapes["seq"]
    assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),%d,%d\]" % (s, s),
                         text), "a score-shaped array"
    assert not re.search(r"\[(?:\d+,)*%d,(?:\d+,)+%d[\],]" % (s, s), text), \
        "an array with two sequence-long dimensions"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.DELTA_PHASES:
        assert name + "/" in names, name
    assert re.search(scopes.DELTA_SCAN + r"/[^\"]*while", names)
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 13.9e9 < total < 14.6e9, total      # PERF.md section 6, PR 66
