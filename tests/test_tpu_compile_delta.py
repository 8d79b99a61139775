"""The cell kimi-linear-48b-a3b.s8192's whole step compiled for a described
TPU v5e (tests/test_tpu_compile_kernels.py's way)."""

import re

import jax
import pytest

from horovod_tpu.ops import pallas_delta
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
    delta rule's scan is its two kernels under ``hvd.delta.scan`` (PR 67):
    8 calls of ``hvd_delta_scan`` (four blocks, each forward run twice) and
    4 of ``hvd_delta_scan_bwd``, no ``while`` over the 128 chunks there and
    no float32 array of a chunk's and head's pairs, inverse, ``W``, ``U`` or
    decays (``[128, 32, 64, 128]``, ``[.., 64, 64]``) but the kernels' own:
    the states the chunks start from, ``[1, 128, 32, 128, 128]`` a
    differentiated forward call; the six ``hvd.delta*`` scopes are still
    there, no array of the program has two dimensions a sequence long, and
    the bytes are under the compiler's 15.75 GB. A head of the mixer is 128
    lanes of ``[1, 8192, 4096]`` and never an axis (PR 71): no instruction
    under the ``hvd.delta*`` scopes and no float32 array anywhere has
    ``[.., 32, 128]`` (``[1024, 8, 32, 128]`` was the relayout's own shape;
    the latent block's 32 heads are bfloat16 arrays of its own scopes), and
    no ``copy`` or ``reshape`` moves a ``[1, 8192, 4096]`` array."""
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
    s = shapes["seq"]
    from horovod_tpu.profiling import scopes
    assert sum("hvd_flash_attention" in c for c in calls) == 2
    assert sum("hvd_flash_bwd" in c for c in calls) == 1
    assert sum("hvd_flash_adj" in c for c in calls) >= 1
    assert sum(moe.GMM_NAME in c for c in calls) == 9 * 4
    assert sum("hvd_fused_xent" in c for c in calls) == 1
    scans = [c for c in calls if scopes.DELTA_SCAN + "/" in c]
    backward = [c for c in scans if pallas_delta.BWD_NAME in c]
    assert len(backward) == 4 and len(scans) == 8 + 4
    assert all(pallas_delta.FWD_NAME in c for c in scans)
    n, h = s // shapes["delta_chunk"], shapes["delta_heads"]
    d, c = shapes["delta_head_dim"], shapes["delta_chunk"]
    assert (n, h, d, c) == (128, 32, 128, 64)
    assert not re.search(r"f32\[(?:\d+,)*%d,%d,%d,(?:%d|%d)\]" % (n, h, c, d, c),
                         text), "a chunk's and head's float32 intermediate"
    assert not re.search(r"f32\[(?:\d+,)*%d,%d\][^ ]* " % (c, c), text), \
        "a chunk's float32 pairs or inverse"
    states = re.findall(r"f32\[1,%d,%d,%d,%d\]" % (n, h, d, d), text)
    assert states, "the states the chunks start from"
    assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),%d,%d\]" % (s, s),
                         text), "a score-shaped array"
    assert not re.search(r"\[(?:\d+,)*%d,(?:\d+,)+%d[\],]" % (s, s), text), \
        "an array with two sequence-long dimensions"
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.DELTA_PHASES:
        assert name + "/" in names, name
    assert not re.search(scopes.DELTA_SCAN + r"/[^\"]*while", names)
    head_axis = r"\[(?:\d+,)+%d,%d\]" % (h, d)
    assert not re.search(head_axis, "\n".join(
        line for line in names.splitlines() if scopes.DELTA in line)), \
        "a head axis under the mixer's scopes"
    assert not re.search("f32" + head_axis, text), "a float32 head axis"
    assert not re.search(r"= \w+\[(?:1,)?%d,%d\]\S* (?:copy|reshape)\("
                         % (s, h * d), text), "a relayout of [S, H D]"
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 12.4e9 < total < 13.13e9, total      # PERF.md section 6, PR 71


def test_the_kernels_for_a_decay_a_head_lower_for_v5e(topo):
    """``hvd_delta_scan`` / ``hvd_delta_scan_bwd`` in their bodies for a
    decay a head at the cell qwen3-next-80b-a3b.s8192's shape (one sequence
    of 8192, 32 value heads on 16 key heads of 128, chunks of 64), compiled
    by Mosaic for a described v5e: q and k stay ``[1, 8192, 16 x 128]`` (no
    array of them at the 32 value heads), the log decay and its cotangent
    ``[1, 8, 8192, 4]`` float32 in the kernels' layout (four value heads a step) (no ``[8192, 32 x
    128]`` float32 broadcast), dq and dk written once at the key heads."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    rep = NamedSharding(Mesh(np.array(topo.devices[:1]), ("dp",)), P())
    S, H, Hk, D, C = 8192, 32, 16, 128, 64

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=rep)
    args = (shape((1, S, Hk, D), jnp.bfloat16),
            shape((1, S, Hk, D), jnp.bfloat16),
            shape((1, S, H, D), jnp.bfloat16),
            shape((1, S, H), jnp.float32), shape((1, S, H), jnp.float32))

    def gradients(*ops):
        return jax.grad(lambda *x: jnp.sum(
            pallas_delta.delta_scan(*x, C)[0]), argnums=(0, 1, 2, 3, 4))(*ops)
    with compile_cache_off():
        compiled = jax.jit(gradients).lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2
    forward = next(c for c in calls if pallas_delta.BWD_NAME not in c)
    backward = next(c for c in calls if pallas_delta.BWD_NAME in c)
    assert pallas_delta.FWD_NAME in forward
    assert f"f32[1,{S // C},{H},{D},{D}]" in forward        # the states
    assert backward.count(f"bf16[1,{S},{Hk * D}]") >= 2     # dq, dk
    tile = pallas_delta.HEAD_TILE_A_HEAD
    assert f"f32[1,{H // tile},{S},{tile}]" in backward     # dg, dbeta
    assert not re.search(r"bf16\[1,%d,%d,%d\]" % (S, H, D) + r"[^ ]* (?:"
                         r"broadcast|concatenate)", text), "q or k repeated"
