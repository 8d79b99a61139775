"""The cell laguna-xs.2.s8192's whole step compiled for a described TPU v5e
(tests/test_tpu_compile_kernels.py's way): the one test short of the chip
that holds its ``hbm_compiled_gb`` (14.9 to 15.4 GB of the chip's 16)."""

import re

import jax
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel import moe
from tpu_compile_cases import (arrays_in_memory, cell_step,
                               compile_cache_off, described_v5e)


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


def test_banded_step_compiles_for_v5e_on_the_kernels(topo):
    """The cell laguna-xs.2.s8192's step: a leading full layer and one
    period of three window-512 layers at 64 query heads and a full layer at
    48, on 8 key/value heads, 32 of 256 experts held, no block checkpointed.
    Every layer's attention is the two flash kernels at its own head count
    (a call site a layer; no score-shaped array in the program), the experts
    are ``hvd_moe_gmm``, the head ``hvd_fused_xent``; the gate's scope and
    both layer kinds' are in the program; the bytes are what
    ``assumed.recomputation`` says, under the compiler's 15.75 GB."""
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = cell_step("laguna-xs.2.s8192",
                                                    topo)
        compiled = step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    layers, routed = shapes["layers"], shapes["routed_layers"]
    assert (layers, routed) == (5, 4)
    assert shapes["attention_forward_calls"] == layers   # nothing run twice
    assert sum("hvd_flash_attention" in c for c in calls) == layers
    assert sum("hvd_flash_bwd" in c for c in calls) == layers
    # the kernel that makes its adj rows (the name is an operand's too)
    assert sum(pa.ADJ_NAME in c.split(" = ")[0] for c in calls) == layers
    assert sum(moe.GMM_NAME in c for c in calls) == 9 * routed
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    # the two attention shapes, each at its own query heads
    for heads, n in ((64, 3), (48, 2)):
        assert sum(f"bf16[1,8192,{heads * 128}]" in c for c in calls
                   if "hvd_flash_attention" in c) == n, heads
    s = shapes["seq"]
    # (q of 64 heads of 128 is itself [1, 8192, 8192]: a score array has a
    # dimension of heads in front of its two of positions)
    assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),%d,%d\]" % (s, s),
                         text), "a score-shaped array"
    k, m = shapes["experts_per_token"], shapes["d_model"]
    assert f"f32[{s * k},{m}]" not in arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.MIXED_PHASES + scopes.GATED_PHASES + (
            scopes.MOE_SHARED,):
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    # PERF.md section 6: 15.574 GB from PR 53 until PR 62 took the row sums
    # (and the copies of do and o XLA fed them) out of XLA's hands
    assert 14.9e9 < total < 15.4e9, total
