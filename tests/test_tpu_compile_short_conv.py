"""The cell lfm2-24b-a2b.s8192's whole step compiled for a described TPU
v5e (tests/test_tpu_compile_kernels.py's way)."""

import re

import jax
import pytest

from horovod_tpu.parallel import moe
from tpu_compile_cases import (arrays_in_memory, cell_step,
                               compile_cache_off, described_v5e)


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


def test_short_conv_step_compiles_for_v5e_on_the_kernels(topo):
    """The cell lfm2-24b-a2b.s8192's step: a leading conv + dense layer and
    one period of an attention block (32 / 8 heads of 64, a norm a head) and
    three conv blocks, each with 8 of 64 experts held, two sequences of
    8192, no block checkpointed. The attention block is the two flash
    kernels at 32 / 8 x 64 (no score-shaped array in the program), the
    experts are ``hvd_moe_gmm`` at 2048 <-> 1536 on the tiles ``gmm_path``
    picks, the head ``hvd_fused_xent`` on the tied table; the mixer's three
    scopes are in the program; the bytes are what ``assumed.recomputation``
    says, under the compiler's 15.75 GB."""
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = cell_step("lfm2-24b-a2b.s8192",
                                                    topo)
        compiled = step.lower(*args).compile()
        # the latent cell's widths at twice its rows a group: its tiles
        assert moe._gmm_tile(65536, 2048, 1536, 2) == moe.GmmTiles(
            (256, 2048, 768), (256, 1536, 1024), (128, 1024, 1536))
        assert moe._gmm_tile(65536, 1536, 2048, 2) == moe.GmmTiles(
            (256, 1536, 1024), (256, 2048, 768), (128, 1536, 1024))
        assert moe.gmm_path(65536, 2048, 1536).startswith(
            f"pallas {moe.GMM_NAME} weights read as stored, [E, 2048, 1536] "
            "row-major")
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    routed, blocks = shapes["routed_layers"], len(shapes["layer_windows"])
    assert (shapes["layers"], routed, blocks, shapes["conv_layers"]) == (
        5, 4, 1, 4)
    assert sum("hvd_flash_attention" in c for c in calls) == blocks
    assert sum("hvd_flash_bwd" in c for c in calls) == blocks
    assert sum(moe.GMM_NAME in c for c in calls) == 9 * routed
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    # a head of 64 goes heads first: two sequences' 32 query heads on 8
    flash = next(c for c in calls if "hvd_flash_attention" in c)
    assert "bf16[64,8192,64]" in flash and "bf16[16,8192,64]" in flash
    s = shapes["seq"]
    assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),%d,%d\]" % (s, s),
                         text), "a score-shaped array"
    k, m = shapes["experts_per_token"], shapes["d_model"]
    assert f"f32[{2 * s * k},{m}]" not in arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.SHORT_CONV_PHASES:
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 13.8e9 < total < 14.1e9, total      # PERF.md section 6, PR 55
