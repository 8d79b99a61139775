"""The Pallas flash-attention forward kernel against the XLA oracle
(interpret mode on the CPU mesh; the chip's compile of it is
tests/test_tpu_compile_kernels.py's), the rule that picks its tile, and the
form it runs a tile in: the state along the lanes, pieces of
``PIECE_ROWS`` k rows. The backward kernel: test_pallas_attention_backward
.py; both under a mask's band: test_pallas_attention_banded.py and
test_pallas_attention_windows.py."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import (attend, flash_attention_tpu,
                                              flash_blocks)
from horovod_tpu.parallel.ring_attention import _plain_attention
from pallas_attention_cases import LENGTHS, assert_forward, qkv


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_oracle(causal):
    assert_forward(*qkv(), causal)


# (Sq, Sk, H) -> the tile the rule picks at float32, head_dim 128: one tile
# (of two pieces, of eight), several tiles of one size, a q and a k tile of
# different sizes, and the narrow tile on one axis only
_RULE_SHAPES = {
    (256, 256, 2): (256, 256),
    (1024, 1024, 1): (1024, 1024),
    (384, 384, 1): (128, 128),
    (128, 256, 2): (128, 256),
    (512, 1536, 1): (512, 512),
}


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", sorted(_RULE_SHAPES))
def test_flash_kernel_matches_oracle_at_the_rules_tiles(shape, causal):
    Sq, Sk, H = shape
    assert flash_blocks(Sq, Sk, 128, jnp.float32) == _RULE_SHAPES[shape]
    assert_forward(*qkv(B=1, S=Sq, Sk=Sk, H=H, seed=3), causal)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256),
                                    (512, 256), (256, 512)])
def test_flash_kernel_tile_overrides(blocks, causal):
    """Every tile gives the oracle's result: q tiles wider and narrower
    than k tiles put the diagonal through tiles in every way (crossed,
    wholly below, wholly above and never fetched)."""
    assert_forward(*qkv(B=1, S=512, H=1, seed=4), causal,
                    block_q=blocks[0], block_k=blocks[1])


def test_flash_kernel_tile_does_not_change_float32_bits_much():
    """The tile changes the order of the online-softmax updates only."""
    q, k, v = qkv(B=1, S=512, H=1, seed=5)
    a = flash_attention_tpu(q, k, v, True, interpret=True,
                            block_q=128, block_k=128)
    b = flash_attention_tpu(q, k, v, True, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [256, 1024])
def test_flash_kernel_bf16_inputs_match_float32_oracle(S, causal):
    """bf16 q, k, v are multiplied as bf16 (float32 accumulation, float32
    softmax statistics); against the float32 oracle on the same values
    the result holds chip_smoke.py's tolerance."""
    q, k, v = qkv(B=1, S=S, H=1, seed=6, dtype=jnp.bfloat16)
    assert_forward(q, k, v, causal, rtol=2e-2, atol=2e-2)


def test_flash_lse_is_float32_for_bf16_inputs():
    q, k, v = qkv(B=1, S=256, H=1, dtype=jnp.bfloat16)
    o, lse = pa.flash_attention_with_lse(q, k, v, True, interpret=True)
    assert o.dtype == jnp.bfloat16 and lse.dtype == jnp.float32
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / 128 ** 0.5
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -jnp.inf)
    want = jax.nn.logsumexp(s, -1).reshape(1, 256)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_attend_fallback_on_cpu():
    # CPU backend → must take the XLA fallback (no pallas compile) and agree
    q, k, v = qkv(S=16, D=8)
    out = attend(q, k, v, causal=True)
    ref = _plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def test_flash_kernel_rect():
    # Sq != Sk (cross-block boundary conditions)
    assert_forward(*qkv(B=1, S=128, Sk=256, seed=1), False)



# -- the tile rule ----------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [128, 256, 512])
def test_flash_blocks_divide_fit_and_never_go_under_128(D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    for Sq in LENGTHS:
        for Sk in LENGTHS:
            bq, bk = flash_blocks(Sq, Sk, D, dtype)
            assert bq in pa.TILES and bk in pa.TILES
            assert Sq % bq == 0 and Sk % bk == 0
            assert min(bq, bk) >= pa.MIN_BLOCK
            fits = pa.flash_vmem_bytes(bq, bk, D, itemsize) <= pa.VMEM_BUDGET
            assert fits or (bq, bk) == (pa.MIN_BLOCK, pa.MIN_BLOCK)


def test_flash_blocks_at_the_benchmarks_shape():
    # gpt-1.3b-widths.s2048: B2 S2048 H16 D128 bf16 -> 32 x 2 x 2 steps a
    # call where 128 x 128 took 32 x 16 x 16 (PERF.md, PR 25's sweep)
    assert flash_blocks(2048, 2048, 128, jnp.bfloat16) == (1024, 1024)
    assert pa.flash_grid(2, 16, 2048, 2048, 1024, 1024) == (32, 2, 2)


def test_flash_blocks_short_and_rectangular_shapes_keep_their_tiles():
    # a ring step with 256 local positions, the rectangular test, and a
    # length only 128 divides
    assert flash_blocks(256, 256, 128, jnp.bfloat16) == (256, 256)
    assert flash_blocks(128, 256, 128, jnp.float32) == (128, 256)
    assert flash_blocks(384, 640, 128, jnp.bfloat16) == (128, 128)


def test_flash_blocks_shrink_the_q_tile_first_under_the_vmem_budget():
    # the score tile is in pieces of PIECE_ROWS k rows, so what fills
    # the budget is the operands' blocks: float32 tiles of 1024 x 1024 fit
    # at a head of 128 and of 256, not of 512; the k tile stays wide
    assert pa.flash_vmem_bytes(1024, 1024, 128, 4) <= pa.VMEM_BUDGET
    assert flash_blocks(2048, 2048, 128, jnp.float32) == (1024, 1024)
    assert pa.flash_vmem_bytes(1024, 1024, 512, 4) > pa.VMEM_BUDGET
    assert flash_blocks(2048, 2048, 512, jnp.float32) == (512, 1024)
    assert pa.flash_vmem_bytes(512, 1024, 512, 4) <= pa.VMEM_BUDGET
    # the q tile first, down to MIN_BLOCK, then the k tile
    assert flash_blocks(4096, 4096, 1024, jnp.bfloat16) == (256, 1024)
    assert flash_blocks(4096, 4096, 1024, jnp.float32) == (128, 512)


def test_flash_vmem_bytes_counts_the_pieces_not_the_score_tile():
    """Two pieces of ``PIECE_ROWS`` k rows are in flight, whatever the k
    tile: doubling it adds its k and v blocks alone; the state is rows."""
    def count(bq, bk, D=128, itemsize=2):
        return pa.flash_vmem_bytes(bq, bk, D, itemsize)
    assert pa.PIECE_ROWS == pa.MIN_BLOCK == pa.piece_rows(1024)
    assert count(1024, 1024) - count(1024, 512) == 2 * 2 * 512 * 128 * 2
    io = 2 * 4 * 1024 * 128 * 2 + 2 * 8 * 1024 * 4
    pieces = 2 * 128 * 1024 * (4 + 4 + 2)
    scratch = 2 * 1024 * 128 * 4 + 2 * 8 * 1024 * 4
    assert count(1024, 1024) == io + pieces + scratch == 5898240
    # glm-4.7-flash.s8192's head of 256 at the cells' tile
    assert count(1024, 1024, 256) == 9043968 <= pa.VMEM_BUDGET


@pytest.mark.parametrize("Sq,Sk", [(100, 128), (128, 192), (64, 64)])
def test_flash_blocks_refuses_what_128_does_not_divide(Sq, Sk):
    assert not pa.flash_eligible(Sq, Sk, 128)
    with pytest.raises(ValueError):
        flash_blocks(Sq, Sk, 128, jnp.float32)


def test_flash_eligible_is_the_contract_of_128():
    assert pa.flash_eligible(128, 256, 128)
    assert pa.flash_eligible(384, 384, 256)
    # a head of 64 runs heads first (PR 49); no other width under a tile
    assert pa.flash_eligible(256, 256, 64) and pa.NARROW_HEAD == 64
    assert not pa.flash_eligible(256, 256, 32)
    assert not pa.flash_eligible(256, 256, 96)
    assert not pa.flash_eligible(256, 256, 192)
    assert not pa.flash_eligible(200, 256, 64)


#: every cell's causal core: (S, head_dim) -> the forward's tile and the
#: backward's (block_q, block_k, rows), bfloat16. A change to a rule that
#: moves one of these moves a cell's kernel
_CELL_TILES = {
    "gpt-1.3b-widths.s2048": ((2048, 128), (1024, 1024), (1024, 1024, 2048)),
    "olmoe-1b-7b.s4096": ((4096, 128), (1024, 1024), (1024, 1024, 4096)),
    "ouro-2.6b.s4096": ((4096, 128), (1024, 1024), (1024, 1024, 4096)),
    "smallthinker-21b-a3b.s8192": ((8192, 128), (1024, 1024),
                                   (1024, 1024, 8192)),
    "nemotron-3-nano-30b-a3b.s8192": ((8192, 128), (1024, 1024),
                                      (1024, 1024, 8192)),
    "glm-4.7-flash.s8192": ((8192, 256), (1024, 1024), (1024, 1024, 8192)),
    "granite-4.0-h-micro.s4096": ((4096, 64), (1024, 1024),
                                  (1024, 1024, 4096)),
    "laguna-xs.2.s8192": ((8192, 128), (1024, 1024), (1024, 1024, 8192)),
}


@pytest.mark.parametrize("cell", sorted(_CELL_TILES))
def test_flash_tiles_at_every_cell_s_shape(cell):
    (S, D), fwd, bwd = _CELL_TILES[cell]
    assert pa.flash_eligible(S, S, D)
    assert flash_blocks(S, S, D, jnp.bfloat16) == fwd
    blocks = pa.flash_bwd_blocks(S, S, D, jnp.bfloat16)
    assert tuple(blocks) == bwd and blocks.rows == S     # dq resident
    assert pa.flash_bwd_vmem_bytes(*blocks, D, 2) <= pa.BWD_VMEM_BUDGET


def test_the_forward_s_state_lies_along_the_lanes():
    """The kernel's scratch: the accumulator ``[D, block_q]`` and ``m``,
    ``l`` as ``[1, block_q]`` rows; no ``[block_q, 1]`` column is left."""
    q = jax.ShapeDtypeStruct((1, 512, 2, 128), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: pa._flash_fwd_impl(
        q, k, v, True, 0.1, 256, 256, False))(q, q, q)
    call = next(e for e in jaxpr.eqns if str(e.primitive) == "pallas_call")
    kernel = call.params["jaxpr"]
    scratch = [tuple(x.aval.shape) for x in kernel.invars[-3:]]
    assert scratch == [(128, 256), (1, 256), (1, 256)], scratch
    assert "hvd_flash_attention" in str(jaxpr)


@pytest.mark.parametrize("shape", [(1, 512, 4, 4, 128), (2, 256, 4, 2, 128),
                                   (1, 256, 2, 2, 256)])
def test_flash_forward_transposes_nothing_at_a_head_of_whole_lane_tiles(
        shape):
    """``_flash_fwd_impl`` at ``D % 128 == 0``: q, k, v go into the call
    and o comes out of it by reshapes alone, ``[B, S, heads * D]``."""
    B, S, H, Hkv, D = shape
    q = jax.ShapeDtypeStruct((B, S, H, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, Hkv, D), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: pa._flash_fwd_impl(
        q, k, v, True, 0.1, 256, 256, False))(q, kv, kv)
    outer = [str(e.primitive) for e in jaxpr.eqns]
    assert "transpose" not in outer and "pallas_call" in outer, outer
    call = next(e for e in jaxpr.eqns if str(e.primitive) == "pallas_call")
    assert [tuple(x.aval.shape) for x in call.invars] == [
        (B, S, H * D), (B, S, Hkv * D), (B, S, Hkv * D)]
    assert [tuple(x.aval.shape) for x in call.outvars] == [
        (B, S, H * D), (B * H, 1, S)]


def test_flash_forward_goes_heads_first_at_a_head_of_64():
    q = jax.ShapeDtypeStruct((1, 256, 8, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(lambda q, k, v: pa._flash_fwd_impl(
        q, k, v, True, 0.1, 256, 256, False))(q, kv, kv)
    call = next(e for e in jaxpr.eqns if str(e.primitive) == "pallas_call")
    assert [tuple(x.aval.shape) for x in call.invars] == [
        (8, 256, 64), (2, 256, 64), (2, 256, 64)]
    assert sum(str(e.primitive) == "transpose" for e in jaxpr.eqns) == 4


def _live(r, c, crossed):
    """Whether score (q row r, k row c) of a square tile whose corners lie
    on the diagonal (or on a window's lower ``"edge"``) is live."""
    return c > r if crossed == "edge" else c <= r


@pytest.mark.parametrize("crossed", ["diagonal", "edge"])
@pytest.mark.parametrize("tile", [128, 256, 512, 1024])
def test_tile_pieces_cover_every_live_score_once_and_no_dead_block(tile,
                                                                   crossed):
    """The pieces both kernels run of a diagonal tile (the backward's are
    the forward's: ``bwd_tile_pieces``) are ``PIECE_ROWS`` k rows against
    the q rows from the piece's first k row on: 36 of a 1024 x 1024 tile's
    64 blocks of 128 x 128, the ones that hold a live score, in eight
    pieces (the parent's two bands of 512 q rows ran 48); of an edge tile
    the mirror image. Every live score lies in exactly one piece, and
    every block a piece holds has a live score."""
    pieces = pa.tile_pieces(tile, tile, crossed)
    assert pa.bwd_tile_pieces(tile, tile, True, crossed) == pieces
    rows = pa.piece_rows(tile)
    assert rows == 128 == pa.PIECE_ROWS
    n = tile // rows
    assert len(pieces) == n
    assert pa.tile_piece_blocks(tile, tile, crossed) == (
        n * (n + 1) // 2, n * n)
    if tile == 1024:
        assert pa.tile_piece_blocks(tile, tile, crossed) == (36, 64)
    covered = np.zeros((tile, tile), int)       # [q row, k row]
    for k0, n_k, q0, q1 in pieces:
        assert n_k == rows and k0 % rows == q0 % rows == q1 % rows == 0
        covered[q0:q1, k0:k0 + n_k] += 1
    r, c = np.mgrid[:tile, :tile]
    live = _live(r, c, crossed)
    assert covered.max() == 1 and (covered[live] == 1).all()
    blocks = covered.reshape(n, rows, n, rows).max((1, 3)) > 0
    assert (blocks == live.reshape(n, rows, n, rows).any((1, 3))).all()
    # the edge's pieces are the diagonal's, mirrored in both axes
    other = "edge" if crossed == "diagonal" else "diagonal"
    mirror = sorted((tile - k0 - n_k, n_k, tile - q1, tile - q0)
                    for k0, n_k, q0, q1 in pa.tile_pieces(tile, tile, other))
    assert sorted(pieces) == mirror


@pytest.mark.parametrize("tile", [(128, 128), (512, 512), (1024, 1024),
                                  (512, 1024), (1024, 256)])
def test_tile_pieces_of_a_tile_no_line_crosses_corner_to_corner(tile):
    """A tile inside the band, or one the mask cuts anywhere else (a tile
    that is not square, a window that is no multiple of it): every piece
    spans all the q rows, the k rows once each. The backward runs those
    pieces of a tile the mask cuts and a tile inside the band whole."""
    bq, bk = tile
    pieces = pa.tile_pieces(bq, bk)
    assert pa.bwd_tile_pieces(bq, bk, True) == pieces
    assert pa.bwd_tile_pieces(bq, bk, False) == [(0, bk, 0, bq)]
    assert pieces == [(k0, 128, 0, bq) for k0 in range(0, bk, 128)]
    assert pa.tile_piece_blocks(bq, bk) == (bq * bk // 128 ** 2,) * 2


@pytest.mark.parametrize("tile", [256, 512, 1024])
def test_an_edge_piece_ends_on_a_q_row_with_no_live_key(tile):
    """The ``NEG_INF`` / ``alpha = 0`` case: the last q row of an edge
    tile's piece sees none of the piece's k rows (``c > r`` fails for all
    of them), every other row does; on the diagonal every row of a piece
    sees a key. The row's next piece, or its next tile's first, holds a live
    key for it, so the ``exp(0)`` it gathered is wiped (the parity cases
    under a window of whole tiles run it)."""
    r, c = np.mgrid[:tile, :tile]
    for crossed, dead in (("edge", 1), ("diagonal", 0)):
        live = _live(r, c, crossed)
        for k0, n_k, q0, q1 in pa.tile_pieces(tile, tile, crossed):
            seen = live[q0:q1, k0:k0 + n_k].any(1)
            assert (~seen).sum() == dead
            if dead:
                assert not seen[-1] and q1 == k0 + n_k
                later = live[q1 - 1, k0 + n_k:]
                assert later.all() and (later.size or k0 + n_k == tile)


@pytest.mark.parametrize("tiles", [
    (1024, 1024, None, True), (1024, 1024, 4096, True),
    (256, 256, 256, True), (1024, 1024, 1536, False),
    (512, 1024, None, False), (1024, 512, 2048, False)])
def test_tiles_run_in_bands_where_the_mask_s_lines_cross_them_corner_to_corner(
        tiles):
    block_q, block_k, window, banded = tiles
    assert pa.banded_tiles(block_q, block_k, window) is banded

