"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths — no chip needed: the TPU compiler is installed and compiles
for a topology that is described, not attached. Interpret mode cannot see
what this sees (the flash kernel's log-sum-exp block spec passed every
interpret test and was refused by the TPU lowering).

Nothing runs here, so these say nothing about results or times; the
parity tests are the interpret-mode files and ``chip_smoke.py``.

Also here: the one compile-cache rule (``utils/compile_cache``) and the
contract that ``chip_smoke.py`` fails without a chip.
"""

import contextlib
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops import pallas_quantize as pq
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.ops import pallas_xent as px
from horovod_tpu.models import mamba, transformer
from horovod_tpu.parallel import moe

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def topo():
    """A described (not attached) v5e 2x2."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e!r}")


@pytest.fixture(scope="module")
def v5e(topo):
    """Sharding on one device of it."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _compile_cache_off():
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


@pytest.fixture
def no_compile_cache():
    with _compile_cache_off():
        yield


def _sum32(*xs):
    return sum(x.astype(jnp.float32).sum() for x in xs)


# attention at the smoke's flagship shape: B8 S2048 H8 D128 bf16
_QKV = [((8, 2048, 8, 128), jnp.bfloat16)] * 3
# the benchmark's cell gpt-1.3b-widths.s2048: B2 S2048 H16 D128 bf16. A tile
# that does not fit VMEM or a block spec the lowering refuses fails here
_QKV_CELL = [((2, 2048, 16, 128), jnp.bfloat16)] * 3
# the 4096-token cells (ouro-2.6b.s4096; olmoe-1b-7b.s4096 at batch 2): the
# backward holds a head's float32 dq of 4096 rows in VMEM
_QKV_S4096 = [((1, 4096, 16, 128), jnp.bfloat16)] * 3
# the cell smallthinker-21b-a3b.s8192: 28 query heads on 4 key/value
# heads, 8192 positions (a head's float32 dq of 8192 rows stays in VMEM)
_QKV_GROUPED = [((1, 8192, 28, 128), jnp.bfloat16)] \
    + [((1, 8192, 4, 128), jnp.bfloat16)] * 2
# the cell glm-4.7-flash.s8192: 20 / 20 heads of 256 (latent attention's
# keys and values come up for every head), 8192 positions: the forward's
# 1024 x 1024 tile reads exactly its VMEM budget, the backward's resident
# form leaves room for 512-tiles
_QKV_LATENT = [((1, 8192, 20, 256), jnp.bfloat16)] * 3
# the cell laguna-xs.2.s8192: window layers of 64 query heads and full
# layers of 48 on the same 8 key/value heads of 128 (groups of 8 and of 6)
_QKV_BANDED_WINDOW = [((1, 8192, 64, 128), jnp.bfloat16)] \
    + [((1, 8192, 8, 128), jnp.bfloat16)] * 2
_QKV_BANDED_FULL = [((1, 8192, 48, 128), jnp.bfloat16)] \
    + [((1, 8192, 8, 128), jnp.bfloat16)] * 2
# the cell lfm2-24b-a2b.s8192's attention block: 32 query heads on 8
# key/value heads of 64 over 8192 keys, two sequences
_QKV_SHORT_CONV = [((2, 8192, 32, 64), jnp.bfloat16)] \
    + [((2, 8192, 8, 64), jnp.bfloat16)] * 2
_FLASH = ("hvd_flash_attention", "hvd_flash_bwd")
# the attention core of the cells bert-large.s128 and bert-large.s512: q, k,
# v and the [B, S] key mask. Sixteen heads of 64 are eight 128-lane columns
_BERT_S128 = [((64, 128, 16, 64), jnp.bfloat16)] * 3 + [((64, 128), jnp.bool_)]
_BERT_S512 = [((8, 512, 16, 64), jnp.bfloat16)] * 3 + [((8, 512), jnp.bool_)]
# LM loss rows x a real tokenizer's vocab (whole rows a block: a vocabulary
# of any size, nothing padded)
_XENT = [((16384, 32000), jnp.bfloat16), ((16384,), jnp.int32)]
# the expert layer of the cell olmoe-1b-7b.s4096: 8192 tokens x top-8 rows,
# 64 experts of 2048 <-> 1024, bf16 rows and float32 parameters
_GMM_UP = [((65536, 2048), jnp.bfloat16), ((64, 2048, 1024), jnp.float32),
           ((64,), jnp.int32)]
_GMM_DOWN = [((65536, 1024), jnp.bfloat16), ((64, 1024, 2048), jnp.float32),
             ((64,), jnp.int32)]
# a held share's expert layer in smallthinker-21b-a3b.s8192: 8192 tokens x
# top-6 gathered rows, 16 held experts of 2560 <-> 768. The widths share no
# tile but 256: each call's own tile holds an expert's whole matrix, and
# what Mosaic allocates beside the blocks is this compile's to say
_GMM_SHARE = [((49152, 2560), jnp.bfloat16), ((16, 2560, 768), jnp.float32),
              ((16,), jnp.int32)]
_GMM_HYBRID = [((49152, 2688), jnp.bfloat16), ((8, 2688, 1856), jnp.float32),
               ((8,), jnp.int32)]
_GMM_HYBRID_DOWN = [((49152, 1856), jnp.bfloat16),
                    ((8, 1856, 2688), jnp.float32), ((8,), jnp.int32)]
# a held share's expert layer in glm-4.7-flash.s8192: 8192 tokens x top-4
# gathered rows, 8 held experts of 2048 <-> 1536
_GMM_LATENT = [((32768, 2048), jnp.bfloat16), ((8, 2048, 1536), jnp.float32),
               ((8,), jnp.int32)]
_GMM_LATENT_DOWN = [((32768, 1536), jnp.bfloat16),
                    ((8, 1536, 2048), jnp.float32), ((8,), jnp.int32)]
_GMM_SHARE_DOWN = [((49152, 768), jnp.bfloat16),
                   ((16, 768, 2560), jnp.float32), ((16,), jnp.int32)]
# a held share's expert layer in laguna-xs.2.s8192: 8192 tokens x top-8
# gathered rows, 32 held experts of 2048 <-> 512, ~256 rows a group
_GMM_BANDED = [((65536, 2048), jnp.bfloat16), ((32, 2048, 512), jnp.float32),
               ((32,), jnp.int32)]
_GMM_BANDED_DOWN = [((65536, 512), jnp.bfloat16),
                    ((32, 512, 2048), jnp.float32), ((32,), jnp.int32)]
# a held share's expert layer in lfm2-24b-a2b.s8192: 16 384 tokens x top-4
# gathered rows, 8 held experts of 2048 <-> 1536, ~1024 rows a group
_GMM_SHORT_CONV = [((65536, 2048), jnp.bfloat16),
                   ((8, 2048, 1536), jnp.float32), ((8,), jnp.int32)]
_GMM_SHORT_CONV_DOWN = [((65536, 1536), jnp.bfloat16),
                        ((8, 1536, 2048), jnp.float32), ((8,), jnp.int32)]
# the Mamba-2 scan of the cell nemotron-3-nano-30b-a3b.s8192: x, dt, a, b,
# c at 8192 positions, 64 heads of 64 in 8 groups, state 128, chunk 128
_SSM_CELL = [((1, 8192, 64, 64), jnp.bfloat16), ((1, 8192, 64), jnp.float32),
             ((64,), jnp.float32)] + [((1, 8192, 8, 128), jnp.bfloat16)] * 2
# the scan of the cell granite-4.0-h-micro.s4096: 4096 positions, 64 heads
# of 64 in ONE group, state 128, chunk 256 (the group's heads in head tiles)
_SSM_DENSE = [((1, 4096, 64, 64), jnp.bfloat16), ((1, 4096, 64), jnp.float32),
              ((64,), jnp.float32)] + [((1, 4096, 1, 128), jnp.bfloat16)] * 2
# and its attention block: 32 query heads of 64 on 8 key/value heads
_QKV_NARROW = [((1, 4096, 32, 64), jnp.bfloat16)] \
    + [((1, 4096, 8, 64), jnp.bfloat16)] * 2
_BLOCKS = ((8192, 256), jnp.float32)
_CODES = [((8192, 256), jnp.int8), ((8192, 1), jnp.float32)]

CASES = {
    "flash_fwd": (
        lambda q, k, v: pa.flash_attention_tpu(q, k, v, True),
        _QKV, "hvd_flash_attention"),
    "flash_fwd_grad": (_flash_grad := jax.grad(lambda q, k, v: _sum32(
        pa.flash_attention_tpu(q, k, v, True)), (0, 1, 2)), _QKV, _FLASH),
    "flash_fwd_cell": (
        lambda q, k, v: pa.flash_attention_tpu(q, k, v, True),
        _QKV_CELL, "hvd_flash_attention"),
    "flash_fwd_grad_cell": (_flash_grad, _QKV_CELL, _FLASH),
    "flash_fwd_grad_s4096": (_flash_grad, _QKV_S4096, _FLASH),
    # a window layer and a full layer of the grouped cell: the band's
    # clamps in both index maps, the k/v block index by group, the
    # backward's pieces on the diagonal and on the band's edge
    "flash_fwd_grad_window_grouped": (
        jax.grad(lambda q, k, v: _sum32(pa.flash_attention_tpu(
            q, k, v, True, window=4096)), (0, 1, 2)), _QKV_GROUPED, _FLASH),
    "flash_fwd_grad_full_grouped": (_flash_grad, _QKV_GROUPED, _FLASH),
    "flash_fwd_grad_latent": (_flash_grad, _QKV_LATENT, _FLASH),
    # a window of half the tile at a group of 8 (every live tile whole under
    # its mask, both index maps clamped to two tiles a row of tiles), and a
    # group of 6 through the k/v block index
    "flash_fwd_grad_window_narrower_than_the_tile": (
        jax.grad(lambda q, k, v: _sum32(pa.flash_attention_tpu(
            q, k, v, True, window=512)), (0, 1, 2)), _QKV_BANDED_WINDOW,
        _FLASH),
    "flash_fwd_grad_group_of_six": (_flash_grad, _QKV_BANDED_FULL, _FLASH),
    # a head of 64 in groups of 4 at the default scale, two sequences
    "flash_fwd_grad_short_conv_cell": (_flash_grad, _QKV_SHORT_CONV, _FLASH),
    # a window that is no multiple of the tile: whole masked tiles
    "flash_fwd_grad_window_unaligned": (
        jax.grad(lambda q, k, v: _sum32(pa.flash_attention_tpu(
            q, k, v, True, window=1536)), (0, 1, 2)), _QKV_S4096, _FLASH),
    # the ring-attention step: non-causal, lse differentiated too
    "flash_lse_noncausal_grad": (
        jax.grad(lambda q, k, v: _sum32(*pa.flash_attention_with_lse(
            q, k, v, causal=False)), (0, 1, 2)),
        _QKV, _FLASH),
    "block_fwd_s128": (pa.block_attention, _BERT_S128, pa.FWD_NAME),
    "block_grad_s128": (_block_grad := jax.grad(
        lambda q, k, v, m: _sum32(pa.block_attention(q, k, v, m)),
        (0, 1, 2)), _BERT_S128, pa.BWD_NAME),
    "block_fwd_s512": (pa.block_attention, _BERT_S512, pa.FWD_NAME),
    "block_grad_s512": (_block_grad, _BERT_S512, pa.BWD_NAME),
    # one head of 128 a column, no mask given
    "block_grad_d128": (
        jax.grad(lambda q, k, v: _sum32(pa.block_attention(q, k, v)),
                 (0, 1, 2)),
        [((8, 384, 8, 128), jnp.bfloat16)] * 3, pa.BWD_NAME),
    "xent_fwd": (px.fused_softmax_xent, _XENT, "hvd_fused_xent"),
    "xent_grad": (
        jax.grad(lambda l, y: px.fused_softmax_xent(l, y).sum()),
        _XENT, "hvd_fused_xent"),
    "moe_gmm_up": (moe.grouped_matmul, _GMM_UP, moe.GMM_NAME),
    "moe_gmm_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_DOWN, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_share_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHARE, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_share_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHARE_DOWN, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_latent_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_LATENT, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_latent_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_LATENT_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_banded_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_BANDED, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_banded_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_BANDED_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_short_conv_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHORT_CONV, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_short_conv_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHORT_CONV_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    # an expert width no 128-multiple divides (1856 = 2^6 * 29): a block
    # spans it whole, as the contraction and as the output's columns
    "moe_gmm_hybrid_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_HYBRID, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_hybrid_down": (moe.grouped_matmul, _GMM_HYBRID_DOWN,
                            moe.GMM_NAME),
    "moe_gmm_hybrid_down_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_HYBRID_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    # an ep shard's share at the four-chip smoke's MoE: float32, 128 wide
    "moe_gmm_smoke_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)),
        [((256, 128), jnp.float32), ((2, 128, 128), jnp.float32),
         ((2,), jnp.int32)], "transpose_jvp_" + moe.GMM_NAME),
    # widths the kernels' blocks do not fit (64 lanes): XLA's ragged dot
    "moe_gmm_narrow_grad": (
        jax.grad(lambda x, w, g: _sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)),
        [((256, 128), jnp.float32), ((4, 128, 64), jnp.float32),
         ((4,), jnp.int32)], "ragged-dot"),
    # a block spec the lowering refuses (dt and the sums a head's column
    # and a head's row) or a working set past VMEM fails here
    "ssm_scan_cell": (
        lambda x, dt, a, b, c: mamba.ssm_chunked(x, dt, a, b, c, 128),
        _SSM_CELL, pallas_ssm.FWD_NAME),
    "ssm_scan_grad_cell": (
        jax.grad(lambda x, dt, a, b, c: _sum32(mamba.ssm_chunked(
            x, dt, a, b, c, 128)), (0, 1, 2, 3, 4)),
        _SSM_CELL, (pallas_ssm.FWD_NAME, pallas_ssm.BWD_NAME)),
    # ONE group of 64 heads at chunk 256: [256, 4096] blocks do not fit the
    # scoped VMEM, a head tile of them does (pallas_ssm.ssm_head_tile)
    "ssm_scan_grad_one_group": (
        jax.grad(lambda x, dt, a, b, c: _sum32(mamba.ssm_chunked(
            x, dt, a, b, c, 256)), (0, 1, 2, 3, 4)),
        _SSM_DENSE, (pallas_ssm.FWD_NAME, pallas_ssm.BWD_NAME)),
    # a head of 64, heads first: a (1, tile, 64) block of [B*H, S, 64]
    "flash_fwd_grad_head_of_64_grouped": (
        jax.grad(lambda q, k, v: _sum32(pa.flash_attention_tpu(
            q, k, v, True, 1 / 64)), (0, 1, 2)), _QKV_NARROW, _FLASH),
    "quantize": (pq.block_quantize, [_BLOCKS], "hvd_block_quantize"),
    "quantize_ef": (pq.block_quantize_ef, [_BLOCKS],
                    "hvd_block_quantize_ef"),
    "dequantize": (pq.block_dequantize, _CODES, "hvd_block_dequantize"),
    "fused_sgd_apply": (
        lambda c, s, m: pq.fused_sgd_apply(c, s, m, 0.1, 0.9),
        _CODES + [_BLOCKS], "hvd_fused_sgd_apply"),
    "fused_adam_apply": (
        lambda c, s, m, v: pq.fused_adam_apply(
            c, s, m, v, 1e-3, 0.9, 0.999, 1e-8, 0.1, 0.001),
        _CODES + [_BLOCKS, _BLOCKS], "hvd_fused_adam_apply"),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(case, v5e, no_compile_cache, monkeypatch):
    fn, shapes, kernels = CASES[case]
    # the dispatchers ask the default backend, which is the CPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    for kernel in ([kernels] if isinstance(kernels, str) else kernels):
        assert any(kernel in line for line in calls), (kernel, calls)


def test_unaligned_expert_weights_are_read_and_updated_where_they_lie(
        v5e, no_compile_cache, monkeypatch):
    """The routed way up of the cell nemotron-3-nano-30b-a3b.s8192 (ISSUE
    41): 1856 columns are no multiple of 128 lanes and 2688 rows are, so the
    chip keeps ``f32[8,2688,1856]`` with the rows minor, ``{1,2,0}`` (if that
    assertion fails a new compiler changed the rule, not the program: look
    at ``moe._stored_transposed`` again). The three kernels read the
    weights, and write their gradient, that way round, ``[8, 1856, 2688]``
    row-major, so that forward, both gradients and an update of the donated
    weights and a moment move neither: no ``copy`` and no ``transpose`` of
    the weights' shape (handed ``[E, K, F]`` itself the calls cost a copy in
    and a copy out of each). The aligned shapes (``_GMM_*`` above) keep
    today's order."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for shapes in (_GMM_HYBRID, _GMM_UP, _GMM_DOWN, _GMM_SHARE,
                   _GMM_SHARE_DOWN, _GMM_HYBRID_DOWN):
        (rows, _), (w, _), _ = shapes
        assert moe._gmm_tile(rows[0], *w[1:], 2).transposed \
            == (shapes is _GMM_HYBRID)
    _, k, f = _GMM_HYBRID[1][0]

    def update(rows, w, sizes, m):
        def loss(w, rows):
            y = moe.grouped_matmul(rows, w, sizes)
            return _sum32(y), y
        (_, y), (d_w, d_rows) = jax.value_and_grad(loss, (0, 1),
                                                   has_aux=True)(w, rows)
        m = 0.9 * m + 0.1 * d_w
        return w - 1e-3 * m, m, d_rows, y
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e)
            for s, d in _GMM_HYBRID + [_GMM_HYBRID[1]]]
    text = jax.jit(update, donate_argnums=(1, 3)).lower(*args).compile(
        ).as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum(moe.GMM_NAME in c for c in calls) == 3, calls
    entry = text[text.index("\nENTRY "):]
    stored = re.findall(r" = f32\[8,%d,%d\]\{([\d,]+)\S* parameter\("
                        % (k, f), entry)
    assert stored == ["1,2,0"] * 2, stored
    moved = re.findall(r"^.* = \w+\[8,(?:%d,%d|%d,%d)\]\S* (?:copy|transpose)"
                       r"\(.*$" % (k, f, f, k), text, re.M)
    assert not moved, moved


def test_flash_gradient_leaves_no_score_array_and_no_float32_operand(
        v5e, no_compile_cache, monkeypatch):
    """The gradient at the GPT cell's shape, compiled for the v5e: the
    scores live in the backward kernel's VMEM. The XLA backward this
    replaced held ``f32[32, 2048 - r0, 128]`` score blocks, one set a
    128-column k block, and the ``p`` / ``ds`` operands of its matmuls in
    float32; nothing of that shape is left, nothing float32 is as large
    as q, and beside the two kernels the program only moves q, k, v, o
    and do and sums do * o."""
    import re
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    (shape, dtype), = set(_QKV_CELL)
    B, S, H, D = shape
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)] * 3
    text = jax.jit(_flash_grad).lower(*args).compile().as_text()
    # what the program materialises: the results of the entry's
    # instructions (a fusion's body holds values, not arrays)
    entry = text.split("ENTRY ", 1)[1]
    big = B * S * H * D
    for m in re.finditer(r"\bf32\[([0-9,]+)\]", entry):
        dims = [int(d) for d in m.group(1).split(",")]
        elements = 1
        for d in dims:
            elements *= d
        assert elements < big, m.group(0)
        assert not (len(dims) == 3 and dims[0] == B * H and dims[2] == 128
                    and dims[1] > 1), m.group(0)
    assert "convolution" not in text and " dot(" not in text


def test_block_attention_stays_on_its_shard_of_a_mesh(topo,
                                                      no_compile_cache):
    """Under GSPMD, batch over dp and heads over tp: each device's kernels
    take its own rows and columns (a bare pallas_call would have q, k and
    v gathered onto every device)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    x = jax.ShapeDtypeStruct((128, 128, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(
                                 mesh, P("dp", None, "tp", None)))
    m = jax.ShapeDtypeStruct((128, 128), jnp.bool_,
                             sharding=NamedSharding(mesh, P("dp", None)))
    grad = jax.grad(lambda q, k, v, m: _sum32(
        pa.block_attention(q, k, v, m)), (0, 1, 2))
    text = jax.jit(grad).lower(x, x, x, m).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("bf16[64,128,512]" in c for c in calls)
    assert "all-gather" not in text and "all-reduce" not in text
    # the benchmark's reference check on four chips: replicated arrays
    # that do not split; every device runs the whole call
    rep = NamedSharding(Mesh(np.array(topo.devices).reshape(4, 1),
                             ("dp", "tp")), P())
    x = jax.ShapeDtypeStruct((2, 128, 16, 64), jnp.bfloat16, sharding=rep)
    m = jax.ShapeDtypeStruct((2, 128), jnp.bool_, sharding=rep)
    text = jax.jit(grad).lower(x, x, x, m).compile().as_text()
    assert text.count("bf16[2,128,1024]") and pa.BWD_NAME in text


# the LM head of the two flagship cells: activations, the float32 table as
# the parameters hold it (GPT's tied embedding [V, M], transposed; OLMoE's
# lm_head [M, V]) and the labels
HEADS = {
    "gpt-1.3b-widths.s2048": (4096, 2048, 50257, True),
    "olmoe-1b-7b.s4096": (8192, 2048, 50304, False),
}


@pytest.mark.parametrize("cell", sorted(HEADS))
def test_head_touches_the_logits_once(cell, v5e, no_compile_cache,
                                      monkeypatch):
    """The head as ``forward_loss_spmd`` writes it (the table cast to
    bf16, logits matmul, loss, both gradients) compiled for the v5e at a
    cell's shape: between the logits matmul and the two backward matmuls
    stands the kernel alone. No pad, no elementwise sweep over an
    ``[N, V]`` array (the parent had ``pad`` and a ``kLoop``
    ``multiply_convert_fusion``, 2 to 4 ms a step), and the temporaries
    are one ``[N, V]`` bf16 array and ``[N, M]`` ones."""
    n, m, v, tied = HEADS[cell]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, table, labels):
        head = table.astype(jnp.bfloat16)
        return px.head_softmax_xent(x, head.T if tied else head,
                                    labels).mean()

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in (((n, m), jnp.bfloat16),
                                 ((v, m) if tied else (m, v), jnp.float32),
                                 ((n,), jnp.int32))]
    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(*args).compile()
    entry = compiled.as_text().split("ENTRY ", 1)[1].splitlines()[1:]
    # name -> (result type + opcode, operands + attributes) of the entry's
    # instructions; a view of an array (an element of the kernel's result
    # tuple, a bitcast) is the array it views
    parts = {}
    for line in entry:
        name, eq, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if eq:
            result, _, operands = rest.partition("(%")
            parts[name] = result, "%" + operands
    views = ("get-tuple-element", "bitcast")
    wide = {name for name, (result, _) in parts.items()
            if f"[{n},{v}]" in result}
    touch = {name for name, (result, operands) in parts.items()
             if (name in wide or any(w + "," in operands or w + ")" in operands
                                     for w in wide))
             and not result.endswith(views)}
    kernels = {name for name in touch if "hvd_fused_xent" in name}
    assert len(kernels) == 1 and "custom-call" in parts[min(kernels)][0]
    # what else writes or reads an [N, V] array: the logits matmul and the
    # two backward matmuls (XLA:TPU's convolution fusions are kOutput)
    matmuls = touch - kernels
    assert len(matmuls) == 3, sorted(touch)
    for name in matmuls:
        result, operands = parts[name]
        assert result.endswith(" fusion") and "kind=kOutput" in operands, \
            (name, result)
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= n * v * 2 + 4 * n * m * 4


# -- whole train steps of the benchmark's flagship cells ---------------------

# -- the forward flash kernel where its operands lie (ISSUE 50) ---------------

#: a checkpointed attention block, forward and backward, at a cell's real
#: widths: (TransformerConfig fields, the block's stack, positions).
#: glm-4.7-flash.s8192's latent block (20 heads of 256: ``flash_vmem_bytes``
#: of its 1024 x 1024 tile is 8.6 MiB of ``VMEM_BUDGET``'s 16) and
#: ouro-2.6b.s4096's plain one (16 heads of 128)
_ATTENTION_BLOCKS = {
    "latent block, 20 heads of 256": (dict(
        d_model=2048, n_heads=20, head_width=256, q_latent=768,
        kv_latent=512, rope_width=64, layer_pattern=(("latent",),)),
        "latent", 8192),
    "plain block, 16 heads of 128": (dict(d_model=2048, n_heads=16),
                                     None, 4096),
}


@pytest.mark.parametrize("block", sorted(_ATTENTION_BLOCKS))
def test_attention_block_hands_the_forward_kernel_its_operands_in_place(
        block, v5e, no_compile_cache, monkeypatch):
    """``hvd_flash_attention`` reads q, k, v and writes o as ``[1, S, H *
    D]`` in both of a checkpointed block's calls, under the default scoped
    VMEM (no limit is asked for), and the program holds no heads-first
    copy of any of them: no ``[H, S, D]`` array (the parent's ``copy`` and
    ``transpose`` between ``[1, 8192, 20, 256]`` and ``[20, 8192, 256]``),
    so whatever lies beside the call moves ``[1, S, ..]`` arrays only."""
    import numpy as np
    fields, stack, S = _ATTENTION_BLOCKS[block]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = transformer.TransformerConfig(
        n_layers=1, dtype=jnp.bfloat16, max_seq=S, vocab_size=1024, **fields)
    H, D = cfg.n_heads, cfg.head_dim
    layers = jax.eval_shape(lambda: transformer.init_params(
        np.random.RandomState(0), cfg, 1))["layers"]
    params = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape[2:], v.dtype, sharding=v5e),
        layers[stack] if stack else layers)
    h = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.bfloat16, sharding=v5e)
    positions = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=v5e)
    apply = (transformer._BLOCK_KINDS[stack].apply if stack else
             lambda p, x, pos, cfg, kind: (
                 transformer._attention_block(p, x, pos, cfg), None))
    kind = (stack,) if stack else transformer._PLAIN_LAYER

    def loss(p, h, positions):
        run = jax.checkpoint(lambda p, h: apply(p, h, positions, cfg,
                                                kind)[0])
        return _sum32(jnp.square(run(p, h)))
    assert pa.flash_vmem_bytes(*pa.flash_blocks(S, S, D, jnp.bfloat16), D,
                               2) <= pa.VMEM_BUDGET
    text = jax.jit(jax.grad(loss, (0, 1))).lower(
        params, h, positions).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "hvd_flash_attention" in line.split(" = ")[0]]
    assert len(calls) == 2, calls          # the block's, and its recomputation
    where = f"bf16[1,{S},{H * D}]"
    for call in calls:
        result, operands = call.split(" custom-call(")
        operands = operands.split("), custom_call_target")[0]
        assert result.count(where) == 1 and f"f32[{H},1,{S}]" in result, call
        assert operands.count(",") == 2, call
    defined = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ", text, re.M))
    for call in calls:
        for operand in re.findall(r"%[\w.\-]+", call.split(
                " custom-call(")[1].split(")")[0]):
            assert defined[operand].startswith(where), (operand, call)
    heads_first = re.findall(rf"\w+\[(?:1,)?{H},{S},{D}\]", text)
    assert not heads_first, sorted(set(heads_first))


_CHIP = os.path.join(_REPO, "benchmarks", "chip")


def _cell_step(cell, topo):
    """(jitted step, its abstract arguments, the adapter's shapes) of a
    cell of BENCHMARK.json at its real sizes, on one described chip: what
    ``benchmarks/chip/rehearse.py compile`` builds."""
    import importlib
    import sys
    for path in (_REPO, _CHIP):
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


def test_looped_step_compiles_for_v5e_with_both_kernels(
        topo, no_compile_cache, monkeypatch):
    """The cell ouro-2.6b.s4096's step, 6 layers looped 4 times at 4096
    tokens: both kernels engage, the calls a step are what the adapter's
    ``shapes()`` tells the roofline functions (the forward flash kernel
    once a layer pass in the forward scan and once more in the backward
    scan, which recomputes the checkpointed pass; the head's kernel once a
    loop step), the loop's scopes are in the program, and the step fits
    with the room the issue asks for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, shapes, step_bytes = _cell_step("ouro-2.6b.s4096", topo)
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    assert pa.attention_path(s, s, h, d, True, False) == "flash"
    assert px.xent_path(b * s, shapes["vocab"], jnp.bfloat16)[0] == "kernel"
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    passes = shapes["layers"] * shapes["loops"]
    # a call site in a scan's body runs once a layer pass
    assert sum("hvd_flash_attention" in c for c in calls) * passes \
        == shapes["attention_forward_calls"]
    assert sum("hvd_fused_xent" in c for c in calls) == shapes["head_calls"]
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    assert scopes.LOOP + "/" in names and scopes.LOOP_GATE + "/" in names
    assert step_bytes(compiled.memory_analysis())["total"] < 15.0e9


def test_dense_hybrid_step_compiles_for_v5e_on_the_kernels(
        topo, no_compile_cache, monkeypatch):
    """The cell granite-4.0-h-micro.s4096's step (nine Mamba-2 blocks of ONE
    group at chunk 256, one attention block at 32 / 8 heads of 64, ten
    SwiGLU FFNs, the tied sliced head): the scan's kernels, both flash
    kernels and the head's kernel are in the program, no array of attention
    scores (``[heads.., 4096, 4096]``) or of a head tile's whole states is
    in memory, and the step fits with the room ISSUE 49 asks for (the
    scan's float32 output ``y`` IS ``[1, 4096, 4096]``: 4096 positions of
    64 x 64 channels)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, shapes, step_bytes = _cell_step("granite-4.0-h-micro.s4096",
                                                topo)
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    assert (s, h, shapes["kv_heads"], d) == (4096, 32, 8, 64)
    assert pa.attention_path(s, s, h, d, True, False) == "flash"
    assert px.xent_path(b * s, shapes["vocab"], jnp.bfloat16)[0] == "kernel"
    assert pallas_ssm.ssm_eligible(s, 64, 64, 1, 128, 256)
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
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 13.6e9 < total < 14.9e9, total


def _arrays_in_memory(text):
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


@pytest.fixture(scope="module")
def mixed_step(topo):
    """The cell smallthinker-21b-a3b.s8192's step compiled once for a
    described v5e (``jax.default_backend`` answering "tpu", the compile
    cache off), for the cases that read the compiled program: (compiled,
    the adapter's shapes, step_bytes)."""
    with _compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = _cell_step(
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
    assert f"f32[{s * k},{m}]" not in _arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.MIXED_PHASES:
        assert name + "/" in names, name
    assert 4.0e9 < step_bytes(compiled.memory_analysis())["total"] < 15.0e9


def test_banded_step_compiles_for_v5e_on_the_kernels(topo):
    """The cell laguna-xs.2.s8192's step: a leading full layer and one
    period of three window-512 layers at 64 query heads and a full layer at
    48, on 8 key/value heads, 32 of 256 experts held, no block checkpointed.
    Every layer's attention is the two flash kernels at its own head count
    (a call site a layer; no score-shaped array in the program), the experts
    are ``hvd_moe_gmm``, the head ``hvd_fused_xent``; the gate's scope and
    both layer kinds' are in the program; the bytes are what
    ``assumed.recomputation`` says, under the compiler's 15.75 GB."""
    with _compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = _cell_step("laguna-xs.2.s8192",
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
    assert f"f32[{s * k},{m}]" not in _arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.MIXED_PHASES + scopes.GATED_PHASES + (
            scopes.MOE_SHARED,):
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 15.4e9 < total < 15.75e9, total      # PERF.md section 6, PR 53


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
    with _compile_cache_off(), pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "default_backend", lambda: "tpu")
        step, args, shapes, step_bytes = _cell_step("lfm2-24b-a2b.s8192",
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
    assert f"f32[{2 * s * k},{m}]" not in _arrays_in_memory(text), \
        "the rows in float32"
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    for name in scopes.SHORT_CONV_PHASES:
        assert name + "/" in names, name
    total = step_bytes(compiled.memory_analysis())["total"]
    assert 13.8e9 < total < 14.1e9, total      # PERF.md section 6, PR 55


def _computations(text):
    """{name: its instruction lines} of a compiled program's text."""
    found, lines = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(.*\{$", line)
        if head:
            lines = found.setdefault(head.group(1), [])
        elif lines is not None and " = " in line:
            lines.append(line)
    return found


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
    computations = _computations(text)
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


def _row_array_writers(text, n_rows):
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
    computations = _computations(text)
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


def _loops_that_write_rows_in_place(text, n_rows):
    """ISSUE 44: outside a loop's body and a conditional's branch nothing
    writes a whole array of the sorted rows but the grouped-matmul kernels
    and the dispatch's gather out of the tokens (the hidden rows' buffer is
    allocated, not written); so no ``add`` of the rows' two cotangents, no
    activation and no ``reduce-precision`` over all the rows is left. The
    loops that write into such arrays in place (returned: how many) are the
    activation, its backward pass and, for gated experts, the cotangents'
    sum, a layer."""
    writers = _row_array_writers(text, n_rows)
    outside = {what for where, what, _line in writers if where == "outside"}
    assert outside <= {moe.GMM_NAME, "gather", "AllocateBuffer"}, outside
    in_loops = [what for where, what, _line in writers if where == "loop"]
    assert set(in_loops) == {"dynamic-update-slice"}, set(in_loops)
    return len(in_loops)


def test_mixed_step_runs_the_experts_row_wise_passes_over_held_rows(
        mixed_step):
    """On the compiled step of smallthinker-21b-a3b.s8192 (16 of 64 gated
    experts held): see :func:`_loops_that_write_rows_in_place`. A
    layer's loops that write in place: the activation, its backward pass
    (one fusion that writes both cotangents and the hidden rows), the sum
    of the rows' cotangents, and the combine's backward pass (ISSUE 37)."""
    compiled, shapes, _step_bytes = mixed_step
    rows = shapes["seq"] * shapes["experts_per_token"]
    assert _loops_that_write_rows_in_place(compiled.as_text(), rows) \
        == 4 * shapes["layers"]


def test_a_latent_share_s_expert_layer_runs_row_wise_passes_over_held_rows(
        v5e, no_compile_cache, monkeypatch):
    """One expert layer of glm-4.7-flash.s8192 between its dispatch and its
    combine (``_GMM_LATENT``'s shapes: 32 768 sorted rows of 8192 tokens,
    8 of 64 gated silu experts of 2048 <-> 1536 held), forward and backward
    under the layer's checkpoint policy, as ``moe_layer_spmd`` runs
    ``expert_fn``: the same. The parent's program has here a fusion with
    five ``bf16[32768,1536]`` outputs, one with two and ``add_any
    bf16[32768,2048]``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    ((rows, width), _), (weights, _), _ = _GMM_LATENT
    k, tokens = 4, rows // 4

    def layer(x, we1, we3, we2, order, inverse, sizes, g):
        held = moe.rows_held(sizes, 64)

        def gathered(x, we1, we3, we2):
            sorted_rows = moe._dispatch(x, order, inverse, held, k)
            return moe.expert_ffn(sorted_rows, we1, we3, we2, sizes, held,
                                  jax.nn.silu)
        out, vjp = jax.vjp(jax.checkpoint(
            gathered, policy=moe._all_but_gathers), x, we1, we3, we2)
        return out, vjp(g)
    up, down = weights, (weights[0], weights[2], weights[1])
    args = [jax.ShapeDtypeStruct(s, d, sharding=v5e) for s, d in [
        ((tokens, width), jnp.bfloat16), (up, jnp.float32),
        (up, jnp.float32), (down, jnp.float32), ((rows,), jnp.int32),
        ((rows,), jnp.int32), ((weights[0],), jnp.int32),
        ((rows, width), jnp.bfloat16)]]
    text = jax.jit(layer).lower(*args).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert sum(moe.GMM_NAME in c for c in calls) == 9
    assert _loops_that_write_rows_in_place(text, rows) == 3


@pytest.mark.parametrize("cell", ["gpt-1.3b-widths.s2048",
                                  "olmoe-1b-7b.s4096"])
def test_the_block_s_new_fields_leave_the_flagship_cells_alone(
        cell, topo, no_compile_cache, monkeypatch):
    """``n_loops``, ``post_norm``, ``ffn_gated`` at their defaults and
    ``remat=None`` on the single scan: the step the cell lowers is, to the
    letter, the one with every new field spelled out and no checkpoint, so
    it compiles to the same program and the same bytes."""
    import dataclasses
    from horovod_tpu.models import transformer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, _shapes, _bytes = _cell_step(cell, topo)
    lowered = step.lower(*args).as_text()
    real = transformer.TransformerConfig
    monkeypatch.setattr(
        transformer, "TransformerConfig",
        lambda **kw: dataclasses.replace(
            real(**kw), n_loops=1, post_norm=False, ffn_gated=False,
            remat=False))
    spelled_out, args, _shapes, _bytes = _cell_step(cell, topo)
    assert spelled_out.lower(*args).as_text() == lowered


# -- the Mamba-2 scan on its kernels (ISSUE 40) -------------------------------

_BLOCK = dict(S=8192, H=64, P=64, G=8, N=128, Q=128, M=2688)


@pytest.fixture(scope="module")
def mamba_block_text(v5e):
    """A checkpointed Mamba block of the cell nemotron-3-nano-30b-a3b.s8192,
    forward and backward, compiled once for a described v5e: its text."""
    import numpy as np
    S, H, P, G, N, Q, M = _BLOCK.values()
    with _compile_cache_off(), pytest.MonkeyPatch.context() as patch:
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
            return _sum32(jnp.square(block(p, h)))
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
    for line in _arrays_in_memory(text).splitlines():
        result = line.split(" = ")[1].split("(")[0] if " = " in line else ""
        if "custom-call" in line or "get-tuple-element" in line:
            continue
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
    in_memory = _arrays_in_memory(mamba_block_text)
    for dims in (f"[{S},{G},{H * P // G}]", f"[1,{S},{G},{H * P // G}]",
                 f"[{S // 8},8,{G},{H * P // G}]"):
        assert "f32" + dims not in in_memory, dims
    factors = [line for line in in_memory.splitlines()
               if re.search(rf" = f32\[{S},{G}\]\S* fusion\(", line)]
    assert factors and all("hvd.ssm.norm/" in line for line in factors), \
        factors


# -- the embedding's gradient (ISSUE 38) --------------------------------------

def _assert_no_scatter_into_the_table(text, vocab, width):
    scattered = re.search(
        rf"^.* = \w+\[{vocab},{width}\]\S* scatter\(.*$", text, re.M)
    assert not scattered, \
        "the table's gradient is scattered:\n" + scattered.group(0)
    assert f"bf16[{vocab},{width}]" not in text, \
        "a bf16 copy of the table: the lookup casts it whole"


def test_untied_embedding_gradient_scatters_nothing_into_the_table(
        v5e, no_compile_cache):
    """The lookup and its gradient at the share cell's table (37 984 rows
    of 2560, 8192 tokens), a head of its own: no ``scatter`` has the table
    for its result (on a v5e that scatter of 8192 rows is 15 ms at this
    width, bf16 or float32; the float32 sums of the sorted ids' runs,
    gathered, are under 3: PERF.md §6, PR 38) and no bf16 copy of the
    table exists. The sums are added in float32. Cast the table before the lookup again, or drop the
    hand-written gradient, and this fails."""
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                _embed_lookup)
    from horovod_tpu.profiling import scopes
    vocab, width, tokens = 37984, 2560, 8192
    cfg = TransformerConfig(vocab_size=vocab, d_model=width, n_heads=20,
                            n_layers=1, d_ff=width, max_seq=tokens,
                            dtype=jnp.bfloat16, tie_embeddings=False)

    def gradient(table, ids, cotangent):
        with jax.named_scope(scopes.EMBED):
            rows, back = jax.vjp(lambda e: _embed_lookup(e, ids, cfg), table)
        return rows, back(cotangent)[0]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    text = jax.jit(gradient).lower(
        spec((vocab, width), jnp.float32), spec((1, tokens), jnp.int32),
        spec((1, tokens, width), jnp.bfloat16)).compile().as_text()
    _assert_no_scatter_into_the_table(text, vocab, width)
    sums = re.findall(r"= (\w+)\[\d+,\d+\]\S* scatter\(", text)
    assert sums and set(sums) <= {"f32", "s32"}, sums


def test_mixed_step_scatters_nothing_into_the_embedding_table(mixed_step):
    """The same, in the share cell's real step."""
    compiled, shapes, _step_bytes = mixed_step
    _assert_no_scatter_into_the_table(compiled.as_text(), shapes["vocab"],
                                      shapes["d_model"])


@pytest.fixture
def cache_dir_updates(monkeypatch):
    """Record, without applying, what compile_cache.enable() would set."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    return updates


def test_compile_cache_env_set_sets_no_dir_in_code(cache_dir_updates,
                                                   monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache.enable() is None
    assert cache_dir_updates == []


def test_compile_cache_env_unset_is_checkout_local(cache_dir_updates,
                                                   monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert cache_dir_updates == [("jax_compilation_cache_dir", want)]


def test_chip_smoke_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=_REPO)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "tpu" in proc.stderr.lower(), proc.stderr
