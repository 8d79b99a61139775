"""The Mamba-2 cells compiled for a described TPU v5e (tests/
test_tpu_compile_kernels.py's way): granite-4.0-h-micro.s4096's whole step,
and a checkpointed Mamba block of nemotron-3-nano-30b-a3b.s8192 compiled
once and read by two tests."""

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.ops import pallas_xent as px
from horovod_tpu.models import mamba, transformer
from tpu_compile_cases import (arrays_in_memory, cell_step,
                               compile_cache_off, described_v5e, sum32)


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


@pytest.fixture(scope="module")
def v5e(topo):
    """Sharding on one device of it."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    with compile_cache_off():
        yield


def _results_in_memory(text):
    """(line, result type) of every array in memory that is no kernel
    call's own output."""
    for line in arrays_in_memory(text).splitlines():
        if "custom-call" in line or "get-tuple-element" in line:
            continue
        yield line, (line.split(" = ")[1].split("(")[0]
                     if " = " in line else "")


def test_dense_hybrid_step_compiles_for_v5e_on_the_kernels(
        topo, no_compile_cache, monkeypatch):
    """The cell granite-4.0-h-micro.s4096's step (nine Mamba-2 blocks of ONE
    group at chunk 256, one attention block at 32 / 8 heads of 64, ten
    SwiGLU FFNs, the tied sliced head): the scan's kernels at 16 heads a
    grid step (what ``ssm_head_tile`` picks under the default scoped VMEM,
    which the compiler accepts with nothing asked of it: PR 61), both flash
    kernels and the head's kernel are in the program, no array of attention
    scores (``[heads.., 4096, 4096]``) is in memory and none of the chunks'
    states but the forward kernel's own output, which the backward kernel
    reads with no copy between, and the step fits with the room ISSUE 49
    asks for (the scan's float32 output ``y`` IS ``[1, 4096, 4096]``: 4096
    positions of 64 x 64 channels)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, shapes, step_bytes = cell_step("granite-4.0-h-micro.s4096",
                                                topo)
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    assert (s, h, shapes["kv_heads"], d) == (4096, 32, 8, 64)
    assert pa.attention_path(s, s, h, d, True, False) == "flash"
    assert px.xent_path(b * s, shapes["vocab"], jnp.bfloat16)[0] == "kernel"
    assert pallas_ssm.ssm_eligible(s, 64, 64, 1, 128, 256)
    tile = pallas_ssm.ssm_head_tile(64, 64, 1, 128, 256)
    assert tile == 16
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in (pallas_ssm.FWD_NAME, pallas_ssm.BWD_NAME,
                   "hvd_flash_attention", "hvd_flash_bwd", "hvd_fused_xent"):
        assert any(kernel in c for c in calls), kernel
    # the attention block is not checkpointed: one forward call, one backward
    assert sum("hvd_flash_bwd" in c for c in calls) == 1
    assert sum("hvd_fused_xent" in c for c in calls) == shapes["head_calls"]
    scores = re.findall(r"(?:f32|bf16)\[(?:\d+,)*(?:8,4|32),4096,4096\]", text)
    assert not scores, sorted(set(scores))
    # the states 16 chunks start from, a head tile's 1024 channels a block
    states = f"f32[{b},{s // 256},{64 // tile},128,{tile * 64}]"
    forward = [c for c in calls if pallas_ssm.FWD_NAME + "/" in c]
    backward = [c for c in calls if pallas_ssm.BWD_NAME + "/" in c]
    assert forward and backward
    # what the compiler took of the scoped VMEM for each call, against the
    # estimate the tile was chosen by and the default limit
    used = [int(n) for c in forward + backward for n in re.findall(
        r'"used_scoped_memory_configs":\[\{[^}]*"size":"(\d+)"', c)]
    estimate = pallas_ssm.ssm_vmem_bytes(256, tile * 64, 128, 2)
    assert len(used) == len(forward + backward)
    assert max(used) <= estimate <= pallas_ssm.VMEM_BUDGET == 16 * 2 ** 20, (
        used, estimate)
    assert any(states in c.split(" custom-call(")[0] for c in forward)
    assert all(states in c.split(" custom-call(")[1] for c in backward)
    for line, result in _results_in_memory(text):
        assert states not in result, line
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 13.6e9 < total < 14.9e9, total


# -- the Mamba-2 scan on its kernels (ISSUE 40) -------------------------------

_BLOCK = dict(S=8192, H=64, P=64, G=8, N=128, Q=128, M=2688)


@pytest.fixture(scope="module")
def mamba_block_text(v5e):
    """A checkpointed Mamba block of the cell nemotron-3-nano-30b-a3b.s8192,
    forward and backward, compiled once for a described v5e: its text."""
    import numpy as np
    S, H, P, G, N, Q, M = _BLOCK.values()
    with compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = transformer.TransformerConfig(
            d_model=M, n_heads=32, n_layers=1, layer_pattern=(("mamba",),),
            ssm_heads=H, ssm_head_dim=P, ssm_state=N, ssm_groups=G,
            ssm_chunk=Q, dtype=jnp.bfloat16)
        assert pallas_ssm.FWD_NAME in mamba.ssm_path(cfg, S)
        leaves = jax.eval_shape(lambda: jax.tree_util.tree_map(
            lambda v: jnp.asarray(v[0, 0]), transformer.init_params(
                np.random.RandomState(0), cfg, 1)["layers"]["mamba"]))
        params = jax.tree_util.tree_map(
            lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=v5e),
            leaves)
        h = jax.ShapeDtypeStruct((1, S, M), jnp.bfloat16, sharding=v5e)

        def loss(p, h):
            block = jax.checkpoint(
                lambda p, h: mamba._mamba_block(p, h, cfg))
            return sum32(jnp.square(block(p, h)))
        return jax.jit(jax.grad(loss, (0, 1))).lower(params, h).compile(
            ).as_text()


def test_mamba_block_keeps_a_chunk_s_inside_on_the_chip(mamba_block_text):
    """The forward kernel twice (the block runs again in the backward pass)
    and the backward kernel once, under ``hvd.ssm.scan``; of what the
    ``jax.numpy`` form keeps in memory only the states the chunks start
    from are left, an output of the forward kernel (under differentiation
    it writes them both times; the first copy is read by nothing) that the
    backward kernel reads with no copy between: no ``[.., 128, 128]``
    float32 array (scores, decays, weights) and no other array of 64
    chunks' states."""
    import numpy as np
    from horovod_tpu.profiling import scopes
    S, H, P, G, N, Q, _M = _BLOCK.values()
    text = mamba_block_text
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    backward = [c for c in calls if pallas_ssm.BWD_NAME + "/" in c]
    forward = [c for c in calls if pallas_ssm.FWD_NAME + "/" in c]
    assert (len(forward), len(backward)) == (2, 1), calls
    assert all(scopes.SSM_SCAN + "/" in c for c in forward + backward)
    states = f"f32[1,{S // Q},{G},{N},{H // G * P}]"
    assert all(states in c.split(" custom-call(")[0] for c in forward)
    assert states in backward[0].split(" custom-call(")[1]
    for line, result in _results_in_memory(text):
        assert states not in result, line
        for dims in re.findall(r"f32\[([\d,]+)\]", result):
            dims = [int(d) for d in dims.split(",")]
            assert dims[-2:] != [Q, Q], line
            assert not (np.prod(dims) >= S // Q * H * P * N
                        and N in dims[-2:] and S not in dims), line


def test_mamba_block_s_norm_leaves_its_groups_where_they_lie(
        mamba_block_text):
    """The gate and the grouped norm (ISSUE 47) keep ``[8192, 4096]``
    row-major as the scan's kernel writes it: no array in memory has the
    groups on an axis of their own (the factors broadcast as ``f32[8192, 8,
    512]``, 134 MB each, and the gated product copied to the groups-major
    ``f32[1024, 8, 8, 512]`` were three each a block), and a group's eight
    factors a row are made by products with a 0/1 matrix."""
    S, H, P, G, _N, _Q, _M = _BLOCK.values()
    in_memory = arrays_in_memory(mamba_block_text)
    for dims in (f"[{S},{G},{H * P // G}]", f"[1,{S},{G},{H * P // G}]",
                 f"[{S // 8},8,{G},{H * P // G}]"):
        assert "f32" + dims not in in_memory, dims
    factors = [line for line in in_memory.splitlines()
               if re.search(rf" = f32\[{S},{G}\]\S* fusion\(", line)]
    assert factors and all("hvd.ssm.norm/" in line for line in factors), \
        factors
