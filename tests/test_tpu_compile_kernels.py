"""The main path's Pallas kernels, compiled for a described TPU v5e at
real widths — no chip needed: the TPU compiler is installed and compiles
for a topology that is described, not attached. Interpret mode cannot see
what this sees (the flash kernel's log-sum-exp block spec passed every
interpret test and was refused by the TPU lowering).

Nothing runs here, so these say nothing about results or times; the
parity tests are the interpret-mode files and ``chip_smoke.py``. The
benchmark cells' whole steps compiled the same way: tests/test_tpu_compile.py
(the share cell's) and tests/test_tpu_compile_<cell>.py, a file a step so
that none is a worker's whole load (ROADMAP C11).

What the compiler makes around the kernels of a block at real widths:
tests/test_tpu_compile_blocks.py.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops import pallas_delta
from horovod_tpu.ops import pallas_quantize as pq
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.ops import pallas_xent as px
from horovod_tpu.models import delta, mamba
from horovod_tpu.parallel import moe
from tpu_compile_cases import (compile_cache_off, described_v5e,
                               loops_that_write_rows_in_place, sum32)


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
# 1024 x 1024 tile reads exactly its VMEM budget, and so does the backward's
# beside a head's resident dq (30.9 of BWD_VMEM_BUDGET's 32 MiB by the least
# limit that compiles; 512-tiles until PR 58 counted what the compiler does)
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
# the delta rule's scan: q, k, v bf16, g float32 [B, S, H, D], beta [B, S, H]
_DELTA_CELL = [((1, 512, 32, 128), jnp.bfloat16)] * 3 \
    + [((1, 512, 32, 128), jnp.float32), ((1, 512, 32), jnp.float32)]
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
    "flash_fwd_grad": (_flash_grad := jax.grad(lambda q, k, v: sum32(
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
        jax.grad(lambda q, k, v: sum32(pa.flash_attention_tpu(
            q, k, v, True, window=4096)), (0, 1, 2)), _QKV_GROUPED, _FLASH),
    "flash_fwd_grad_full_grouped": (_flash_grad, _QKV_GROUPED, _FLASH),
    "flash_fwd_grad_latent": (_flash_grad, _QKV_LATENT, _FLASH),
    # a window of half the tile at a group of 8 (every live tile whole under
    # its mask, both index maps clamped to two tiles a row of tiles), and a
    # group of 6 through the k/v block index
    "flash_fwd_grad_window_narrower_than_the_tile": (
        jax.grad(lambda q, k, v: sum32(pa.flash_attention_tpu(
            q, k, v, True, window=512)), (0, 1, 2)), _QKV_BANDED_WINDOW,
        _FLASH),
    "flash_fwd_grad_group_of_six": (_flash_grad, _QKV_BANDED_FULL, _FLASH),
    # a head of 64 in groups of 4 at the default scale, two sequences
    "flash_fwd_grad_short_conv_cell": (_flash_grad, _QKV_SHORT_CONV, _FLASH),
    # a window that is no multiple of the tile: whole masked tiles
    "flash_fwd_grad_window_unaligned": (
        jax.grad(lambda q, k, v: sum32(pa.flash_attention_tpu(
            q, k, v, True, window=1536)), (0, 1, 2)), _QKV_S4096, _FLASH),
    # the ring-attention step: non-causal, lse differentiated too
    "flash_lse_noncausal_grad": (
        jax.grad(lambda q, k, v: sum32(*pa.flash_attention_with_lse(
            q, k, v, causal=False)), (0, 1, 2)),
        _QKV, _FLASH),
    "block_fwd_s128": (pa.block_attention, _BERT_S128, pa.FWD_NAME),
    "block_grad_s128": (_block_grad := jax.grad(
        lambda q, k, v, m: sum32(pa.block_attention(q, k, v, m)),
        (0, 1, 2)), _BERT_S128, pa.BWD_NAME),
    "block_fwd_s512": (pa.block_attention, _BERT_S512, pa.FWD_NAME),
    "block_grad_s512": (_block_grad, _BERT_S512, pa.BWD_NAME),
    # one head of 128 a column, no mask given
    "block_grad_d128": (
        jax.grad(lambda q, k, v: sum32(pa.block_attention(q, k, v)),
                 (0, 1, 2)),
        [((8, 384, 8, 128), jnp.bfloat16)] * 3, pa.BWD_NAME),
    "xent_fwd": (px.fused_softmax_xent, _XENT, "hvd_fused_xent"),
    "xent_grad": (
        jax.grad(lambda l, y: px.fused_softmax_xent(l, y).sum()),
        _XENT, "hvd_fused_xent"),
    "moe_gmm_up": (moe.grouped_matmul, _GMM_UP, moe.GMM_NAME),
    "moe_gmm_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_DOWN, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_share_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHARE, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_share_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHARE_DOWN, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_latent_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_LATENT, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_latent_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_LATENT_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_banded_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_BANDED, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_banded_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_BANDED_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_short_conv_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHORT_CONV, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_short_conv_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_SHORT_CONV_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    # an expert width no 128-multiple divides (1856 = 2^6 * 29): a block
    # spans it whole, as the contraction and as the output's columns
    "moe_gmm_hybrid_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_HYBRID, "transpose_jvp_" + moe.GMM_NAME),
    "moe_gmm_hybrid_down": (moe.grouped_matmul, _GMM_HYBRID_DOWN,
                            moe.GMM_NAME),
    "moe_gmm_hybrid_down_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)), _GMM_HYBRID_DOWN,
        "transpose_jvp_" + moe.GMM_NAME),
    # an ep shard's share at the four-chip smoke's MoE: float32, 128 wide
    "moe_gmm_smoke_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)),
        [((256, 128), jnp.float32), ((2, 128, 128), jnp.float32),
         ((2,), jnp.int32)], "transpose_jvp_" + moe.GMM_NAME),
    # widths the kernels' blocks do not fit (64 lanes): XLA's ragged dot
    "moe_gmm_narrow_grad": (
        jax.grad(lambda x, w, g: sum32(moe.grouped_matmul(x, w, g)),
                 (0, 1)),
        [((256, 128), jnp.float32), ((4, 128, 64), jnp.float32),
         ((4,), jnp.int32)], "ragged-dot"),
    # a block spec the lowering refuses (dt and the sums a head's column
    # and a head's row) or a working set past VMEM fails here
    "ssm_scan_cell": (
        lambda x, dt, a, b, c: mamba.ssm_chunked(x, dt, a, b, c, 128),
        _SSM_CELL, pallas_ssm.FWD_NAME),
    "ssm_scan_grad_cell": (
        jax.grad(lambda x, dt, a, b, c: sum32(mamba.ssm_chunked(
            x, dt, a, b, c, 128)), (0, 1, 2, 3, 4)),
        _SSM_CELL, (pallas_ssm.FWD_NAME, pallas_ssm.BWD_NAME)),
    # ONE group of 64 heads at chunk 256: [256, 4096] blocks do not fit the
    # scoped VMEM, a head tile of them does (pallas_ssm.ssm_head_tile)
    "ssm_scan_grad_one_group": (
        jax.grad(lambda x, dt, a, b, c: sum32(mamba.ssm_chunked(
            x, dt, a, b, c, 256)), (0, 1, 2, 3, 4)),
        _SSM_DENSE, (pallas_ssm.FWD_NAME, pallas_ssm.BWD_NAME)),
    # the cell kimi-linear-48b-a3b.s8192's scan at a sixteenth of its
    # length: blocks of 64 rows, [64, 64] float32 matmuls at HIGHEST, a
    # column of beta a head, the states' and the last sums' block specs
    "delta_scan_cell": (
        lambda *v: delta.delta_chunked(*v, 64), _DELTA_CELL,
        pallas_delta.FWD_NAME),
    "delta_scan_grad_cell": (
        jax.grad(lambda *v: sum32(delta.delta_chunked(*v, 64)[0]),
                 (0, 1, 2, 3, 4)),
        _DELTA_CELL, (pallas_delta.FWD_NAME, pallas_delta.BWD_NAME)),
    # a head of 64, heads first: a (1, tile, 64) block of [B*H, S, 64]
    "flash_fwd_grad_head_of_64_grouped": (
        jax.grad(lambda q, k, v: sum32(pa.flash_attention_tpu(
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
            return sum32(y), y
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
    assert loops_that_write_rows_in_place(text, rows) == 3
