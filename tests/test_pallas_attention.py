"""Pallas flash-attention kernel vs the XLA oracle (interpret mode on the
CPU mesh; the real-TPU path is exercised by bench/examples)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import (attend, flash_attention_tpu,
                                              flash_blocks)
from horovod_tpu.parallel.ring_attention import _plain_attention


def _qkv(B=2, S=256, H=2, D=128, seed=0, Sk=None, dtype=jnp.float32):
    rng = np.random.RandomState(seed)
    mk = lambda s: (jnp.asarray(rng.randn(B, s, H, D), jnp.float32)
                    * 0.3).astype(dtype)
    return mk(S), mk(Sk or S), mk(Sk or S)


def _assert_forward(q, k, v, causal, rtol=1e-5, atol=1e-5, **blocks):
    out = flash_attention_tpu(q, k, v, causal=causal, interpret=True,
                              **blocks)
    ref = _plain_attention(*(x.astype(jnp.float32) for x in (q, k, v)),
                           causal=causal)
    assert out.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), rtol=rtol, atol=atol)


def _assert_grads(q, k, v, causal, cotangent, **blocks):
    """The custom-VJP backward (blockwise recompute from lse) must agree
    with autodiff through the XLA oracle — the kernel is used in training
    forwards, so its gradient is load-bearing."""
    def loss_flash(q, k, v):
        return cotangent(flash_attention_tpu(q, k, v, causal=causal,
                                             interpret=True, **blocks))

    def loss_ref(q, k, v):
        return cotangent(_plain_attention(q, k, v, causal=causal))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=2e-4, atol=2e-4, err_msg=name)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_matches_oracle(causal):
    _assert_forward(*_qkv(), causal)


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
    _assert_forward(*_qkv(B=1, S=Sq, Sk=Sk, H=H, seed=3), causal)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("blocks", [(128, 128), (256, 128), (128, 256),
                                    (512, 256), (256, 512)])
def test_flash_kernel_tile_overrides(blocks, causal):
    """Every tile gives the oracle's result: q tiles wider and narrower
    than k tiles put the diagonal through tiles in every way (crossed,
    wholly below, wholly above and never fetched)."""
    _assert_forward(*_qkv(B=1, S=512, H=1, seed=4), causal,
                    block_q=blocks[0], block_k=blocks[1])


def test_flash_kernel_tile_does_not_change_float32_bits_much():
    """The tile changes the order of the online-softmax updates only."""
    q, k, v = _qkv(B=1, S=512, H=1, seed=5)
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
    q, k, v = _qkv(B=1, S=S, H=1, seed=6, dtype=jnp.bfloat16)
    _assert_forward(q, k, v, causal, rtol=2e-2, atol=2e-2)


def test_flash_lse_is_float32_for_bf16_inputs():
    q, k, v = _qkv(B=1, S=256, H=1, dtype=jnp.bfloat16)
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
    q, k, v = _qkv(S=16, D=8)
    out = attend(q, k, v, causal=True)
    ref = _plain_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


def _cos_cotangent(o):
    return jnp.sum(o * jnp.cos(o))   # non-trivial cotangent


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_grads_match_oracle(causal):
    _assert_grads(*_qkv(B=1, S=256, H=2, D=128), causal, _cos_cotangent)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1024, 1024, 1), (384, 384, 1),
                                   (128, 256, 2)])
def test_flash_kernel_grads_match_oracle_at_the_rules_tiles(shape, causal):
    """The forward's tile (512 x 1024, 128 x 128, 128 x 256) and the
    backward's (``flash_bwd_blocks``: 1024 x 1024 in four pieces on the
    diagonal, 128 x 128, 128 x 256) are two rules."""
    Sq, Sk, H = shape
    _assert_grads(*_qkv(B=1, S=Sq, Sk=Sk, H=H, seed=7), causal,
                  _cos_cotangent)


def _backward(q, k, v, causal, cotangent, blocks=None, lse_weight=None):
    """(dq, dk, dv) from ``flash_backward`` on the forward kernel's own
    residuals, and the oracle's by autodiff; with ``lse_weight`` the
    loss also reads the log-sum-exp (ring attention's merge does)."""
    scale = q.shape[-1] ** -0.5
    o, lse = pa.flash_attention_with_lse(q, k, v, causal, interpret=True)

    def loss(o, lse):
        extra = 0.0 if lse_weight is None else jnp.sum(lse * lse_weight)
        return cotangent(o) + extra
    do, dlse = jax.grad(loss, (0, 1))(o.astype(jnp.float32), lse)
    got = pa.flash_backward(q, k, v, o, lse, do.astype(q.dtype), dlse,
                            causal, scale, blocks=blocks, interpret=True)

    def oracle(q, k, v):
        B, Sq, H, _D = q.shape
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
        if causal:
            s = jnp.where(jnp.tril(jnp.ones(s.shape[-2:], bool)), s,
                          -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        return loss(o, jax.nn.logsumexp(s, -1).reshape(B * H, Sq))
    want = jax.grad(oracle, (0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v)))
    return got, want


def _assert_backward(got, want, tol=2e-4):
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == got[0].dtype
        np.testing.assert_allclose(np.asarray(g.astype(jnp.float32)),
                                   np.asarray(w), rtol=tol, atol=tol,
                                   err_msg=name)


# (Sq, Sk, block_q, block_k, rows): q tiles wider and narrower than k
# tiles, square tiles of several diagonal pieces, dq resident and in q
# ranges of two tiles and of one, Sq != Sk both ways
_BWD_TILES = [(512, 512, 128, 128, 512), (512, 512, 256, 128, 512),
              (512, 512, 128, 256, 512), (512, 512, 512, 512, 512),
              (1024, 1024, 512, 512, 1024), (512, 512, 128, 256, 256),
              (512, 512, 128, 128, 128), (768, 512, 256, 256, 768),
              (256, 512, 128, 128, 256)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tile", _BWD_TILES)
def test_flash_backward_tile_overrides(tile, causal):
    """Every tile and every q range gives the oracle's gradients: the
    diagonal crosses tiles in every way, tiles above it are skipped, a
    square tile on it runs in pieces, partial dk / dv of ranges add up."""
    Sq, Sk, bq, bk, rows = tile
    got, want = _backward(*_qkv(B=1, S=Sq, Sk=Sk, H=2, seed=9), causal,
                          _cos_cotangent, pa.BwdBlocks(bq, bk, rows))
    _assert_backward(got, want)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_takes_the_lse_cotangent(causal):
    q, k, v = _qkv(B=1, S=256, H=2, seed=10)
    weight = jnp.asarray(np.random.RandomState(11).randn(2, 256),
                         jnp.float32)
    got, want = _backward(q, k, v, causal, _cos_cotangent,
                          lse_weight=weight)
    _assert_backward(got, want)
    # and it matters: without it dq differs
    plain, _ = _backward(q, k, v, causal, _cos_cotangent)
    assert float(jnp.max(jnp.abs(plain[0] - got[0]))) > 1e-3


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [256, 1024])
def test_flash_backward_bf16_inputs_match_float32_oracle(S, causal):
    """bf16 operands multiply as bf16 with float32 accumulation, p and ds
    are cast for their matmuls: against the float32 oracle on the same
    values the gradients hold chip_smoke.py's tolerance."""
    q, k, v = _qkv(B=1, S=S, H=1, seed=12, dtype=jnp.bfloat16)
    got, want = _backward(q, k, v, causal, lambda o: jnp.sum(o ** 2))
    assert got[0].dtype == jnp.bfloat16
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        err = jnp.max(jnp.abs(g.astype(jnp.float32) - w)) / jnp.max(
            jnp.abs(w))
        assert float(err) <= 2e-2, (name, float(err))


def test_flash_grads_rect():
    """Sq != Sk backward (cross-attention shape)."""
    _assert_grads(*_qkv(B=1, S=128, Sk=256, seed=2), False,
                  lambda o: jnp.sum(o ** 2))


def test_flash_kernel_rect():
    # Sq != Sk (cross-block boundary conditions)
    _assert_forward(*_qkv(B=1, S=128, Sk=256, seed=1), False)


# -- the tile rule ----------------------------------------------------------

_LENGTHS = [128, 256, 384, 512, 640, 1024, 1536, 2048, 4096, 8192]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [128, 256, 512])
def test_flash_blocks_divide_fit_and_never_go_under_128(D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    for Sq in _LENGTHS:
        for Sk in _LENGTHS:
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
    # the score tile is in pieces of FWD_PIECE_ROWS k rows, so what fills
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
    """Two pieces of ``FWD_PIECE_ROWS`` k rows are in flight, whatever the k
    tile: doubling it adds its k and v blocks alone; the state is rows."""
    def count(bq, bk, D=128, itemsize=2):
        return pa.flash_vmem_bytes(bq, bk, D, itemsize)
    assert pa.FWD_PIECE_ROWS == pa.MIN_BLOCK == pa.fwd_piece_rows(1024)
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


# -- the backward's tile rule -------------------------------------------------

# what the three causal cells and a ring step of chip_smoke.py call it
# with (head_dim 128, bf16): (Sq, Sk) -> (block_q, block_k, rows)
_BWD_RULE = {
    (2048, 2048): (1024, 1024, 2048),     # gpt-1.3b-widths.s2048
    (4096, 4096): (1024, 1024, 4096),     # olmoe-1b-7b.s4096, ouro-2.6b.s4096
    (512, 512): (512, 512, 512),          # ring attention, sp=4 of 2048
    (128, 256): (128, 256, 128),
    (384, 640): (128, 128, 384),
}


@pytest.mark.parametrize("shape", sorted(_BWD_RULE))
def test_flash_bwd_blocks_at_the_shapes_that_run(shape):
    blocks = pa.flash_bwd_blocks(*shape, 128, jnp.bfloat16)
    assert blocks == _BWD_RULE[shape]
    assert blocks.rows == shape[0]        # dq resident: one range
    assert pa.flash_bwd_vmem_bytes(*blocks, 128, 2) <= pa.BWD_VMEM_BUDGET


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [128, 256, 512])
def test_flash_bwd_blocks_divide_and_fit(D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    for Sq in _LENGTHS + [16384, 65536]:
        for Sk in _LENGTHS:
            bq, bk, rows = pa.flash_bwd_blocks(Sq, Sk, D, dtype)
            assert bq in pa.TILES and bk in pa.TILES
            assert Sq % rows == 0 and rows % bq == 0 and Sk % bk == 0
            assert pa.flash_bwd_vmem_bytes(bq, bk, rows, D, itemsize) \
                <= pa.BWD_VMEM_BUDGET


def test_flash_bwd_blocks_keep_dq_resident_while_it_fits():
    """A head's float32 dq and its output block are 8 bytes a row and
    lane at bf16: resident to 16 384 rows at head_dim 128 beside the
    smallest tiles; beyond that the q rows go in ranges."""
    for S in (4096, 8192, 16384):
        assert pa.flash_bwd_blocks(S, S, 128, jnp.bfloat16).rows == S
    long = pa.flash_bwd_blocks(65536, 65536, 128, jnp.bfloat16)
    assert long.rows < 65536 and 65536 % long.rows == 0
    assert pa.flash_bwd_grid(1, 2, 65536, 65536, long)[1] \
        == 65536 // long.rows
    # a length whose only divisors are 1 and itself goes tile by tile
    prime = pa.flash_bwd_blocks(128 * 251, 128 * 251, 128, jnp.bfloat16)
    assert prime == (128, 128, 128)
    # float32 and a wider head hold fewer rows
    assert pa.flash_bwd_blocks(16384, 16384, 256, jnp.float32).rows < 16384
    assert pa.flash_bwd_grid(2, 16, 2048, 2048, pa.BwdBlocks(
        1024, 1024, 2048)) == (32, 1, 2, 2)


@pytest.mark.parametrize("tile", [(2048, 2048, 1024, 1024, 3, 4),
                                  (2048, 2048, 512, 512, 10, 16),
                                  (4096, 4096, 1024, 1024, 10, 16),
                                  (512, 1024, 256, 128, 6, 16),
                                  (1024, 512, 128, 256, 14, 16)])
def test_flash_bwd_causal_tiles_above_the_diagonal_are_not_visited(tile):
    """The q tile a grid step fetches is clamped to the k tile's first
    live one, and a q tile's dq is written at its last live k tile: by
    that arithmetic the live tiles are those the mask leaves anything
    of (S 2048: 3 of 4 at 1024 x 1024, 10 of 16 at 512 x 512)."""
    Sq, Sk, bq, bk, live, steps = tile
    nq, nk = Sq // bq, Sk // bk
    assert nq * nk == steps
    seen = 0
    for kj in range(nk):
        first = min(pa._first_live_q_tile(kj, bq, bk), nq)
        for qi in range(nq):
            any_live = qi * bq + bq - 1 >= kj * bk
            assert any_live == (qi >= first)
            if any_live:
                assert kj <= min(pa._last_live_k_tile(qi, bq, bk), nk - 1)
            seen += any_live
    assert seen == live
    for qi in range(nq):     # the write comes at a live tile, the last
        last = min(pa._last_live_k_tile(qi, bq, bk), nk - 1)
        assert qi >= pa._first_live_q_tile(last, bq, bk)
        assert last == nk - 1 or qi < pa._first_live_q_tile(last + 1, bq,
                                                            bk)


@pytest.mark.parametrize("Sq,Sk", [(100, 128), (128, 192)])
def test_flash_bwd_blocks_refuses_what_128_does_not_divide(Sq, Sk):
    with pytest.raises(ValueError):
        pa.flash_bwd_blocks(Sq, Sk, 128, jnp.float32)


def test_flash_grads_rect_causal():
    """Sq != Sk under the causal mask, both ways (top-left alignment: a
    k tile beyond the last q row gets zeros)."""
    _assert_grads(*_qkv(B=1, S=256, Sk=128, seed=13), True,
                  lambda o: jnp.sum(o ** 2))
    _assert_grads(*_qkv(B=1, S=128, Sk=384, seed=14), True,
                  lambda o: jnp.sum(o ** 2))


# -- a head of 64: heads first, grouped, a scale of its own (PR 49) -----------

def _narrow(B=1, S=256, H=8, Hkv=2, D=64, seed=11, dtype=jnp.float32):
    rng = np.random.RandomState(seed)

    def mk(heads):
        return (jnp.asarray(rng.randn(B, S, heads, D), jnp.float32)
                * 0.8).astype(dtype)
    return mk(H), mk(Hkv), mk(Hkv)


#: (S, H, Hkv, block_q, block_k, rows of a q range): 4 query heads a k/v
#: head as granite-4.0-h-micro's 32 / 8, tiles that meet the diagonal corner
#: to corner and that do not, dq resident and in two q ranges
_NARROW_TILES = [(256, 8, 2, 128, 128, 256), (512, 4, 1, 256, 256, 512),
                 (512, 8, 2, 128, 256, 512), (512, 4, 4, 256, 128, 256),
                 (256, 2, 2, 256, 256, 256)]
SCALE = 1 / 64


@pytest.mark.parametrize("tile", _NARROW_TILES)
def test_flash_pair_at_a_head_of_64_is_the_banded_form(tile):
    """Forward, dq, dk and dv at heads of 64, grouped, causal, without
    positions, scores times 1/64 (not 1/sqrt(64)) against
    ``_banded_attention``: the kernels heads first, a k/v head's gradient
    the sum over its group's query heads and the q ranges."""
    S, H, Hkv, bq, bk, rows = tile
    q, k, v = _narrow(S=S, H=H, Hkv=Hkv)
    o, lse = pa.flash_attention_with_lse(q, k, v, True, SCALE, bq, bk,
                                         interpret=True)
    want = pa._banded_attention(q, k, v, None, SCALE)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    assert lse.shape == (H, S) and lse.dtype == jnp.float32
    w = jnp.cos(jnp.arange(o.size, dtype=jnp.float32).reshape(o.shape))
    got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse), True,
                            SCALE, pa.BwdBlocks(bq, bk, rows),
                            interpret=True)
    ref = jax.grad(lambda q, k, v: jnp.sum(
        pa._banded_attention(q, k, v, None, SCALE) * w), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, ref, ("dq", "dk", "dv")):
        assert g.shape == r.shape and g.dtype == r.dtype, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    # 1/sqrt(D) is another function
    other = pa.flash_attention_tpu(q, k, v, True, None, bq, bk,
                                   interpret=True)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


def test_flash_at_a_head_of_64_is_differentiable_through_the_custom_vjp():
    """``attend``'s way in: ``flash_attention_tpu`` with the rule's tiles,
    bfloat16 operands, against the float32 banded form."""
    q, k, v = _narrow(S=512, dtype=jnp.bfloat16)
    w = jnp.sin(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))

    def loss(f):
        return lambda q, k, v: jnp.sum(f(q, k, v).astype(jnp.float32) * w)
    got = jax.grad(loss(lambda q, k, v: flash_attention_tpu(
        q, k, v, True, SCALE, interpret=True)), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: pa._banded_attention(
        q, k, v, None, SCALE)), (0, 1, 2))(
            *(x.astype(jnp.float32) for x in (q, k, v)))
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16, name
        err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
                    / jnp.max(jnp.abs(r)))
        assert err < 2e-2, (name, err)


def test_a_window_at_a_head_of_64():
    q, k, v = _narrow(S=512, H=4, Hkv=2)
    got = flash_attention_tpu(q, k, v, True, SCALE, 128, 128, interpret=True,
                              window=256)
    want = pa._banded_attention(q, k, v, 256, SCALE)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_attend_takes_the_kernels_at_a_head_of_64_on_a_tpu(monkeypatch):
    assert pa.attention_path(4096, 4096, 32, 64, True, False) == "xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.attention_path(4096, 4096, 32, 64, True, False) == "flash"
    # BERT's core (a key mask, non-causal) stays on the block kernels
    assert pa.attention_path(512, 512, 16, 64, False, True) == "block"
    assert pa.attention_path(128, 128, 16, 64, False, True) == "xla"
    q = jax.ShapeDtypeStruct((1, 256, 8, 64), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 64), jnp.bfloat16)
    text = str(jax.make_jaxpr(jax.grad(lambda q, k, v: jnp.sum(pa.attend(
        q, k, v, causal=True, scale=SCALE).astype(jnp.float32)), (0, 1, 2))
    )(q, kv, kv))
    assert "hvd_flash_attention" in text and "hvd_flash_bwd" in text
    # heads first: the kernels' operands are [B * heads, S, 64]
    assert "bf16[8,256,64]" in text and "bf16[2,256,64]" in text


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
    "glm-4.7-flash.s8192": ((8192, 256), (1024, 1024), (512, 512, 8192)),
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


# -- the forward in the backward's form (PR 50, PR 56): operands in place as
# [B, S, heads * D], scores transposed and the softmax state along the lanes,
# every tile in pieces of FWD_PIECE_ROWS k rows ------------------------------

def _heads(B, S, H, Hkv, D, seed=50):
    rng = np.random.RandomState(seed)

    def mk(heads):
        return jnp.asarray(rng.randn(B, S, heads, D) * 0.5, jnp.float32)
    return mk(H), mk(Hkv), mk(Hkv)


def _banded_lse(q, k, window, scale):
    """A row's log-partition over its live keys, ``[B * H, S]`` float32."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    s = jnp.einsum("bqhgd,bkhd->bhgqk",
                   q.reshape(B, S, Hkv, H // Hkv, D), k) * scale
    t, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    live = j <= t
    if window is not None:
        live = jnp.logical_and(live, j > t - window)
    return jax.nn.logsumexp(jnp.where(live, s, -jnp.inf), axis=-1).reshape(
        B * H, S)


#: name -> (B, S, H, Hkv, D, block_q, block_k, window): square tiles on the
#: diagonal, on a window's edge (the window a multiple of the tile) and
#: whole where the band's edge cuts a tile anywhere else or the tile is not
#: square; grouped heads as the share cell's 28 / 4; a head of two lane
#: tiles; a head of 64 (heads first). Pieces of 128 k rows: a tile of 256
#: has two, one of 512 four and the cells' 1024 x 1024 eight
_BANDED = {
    "causal": (1, 512, 2, 2, 128, 256, 256, None),
    "causal, four bands a tile": (1, 512, 1, 1, 128, 512, 512, None),
    "two batch rows of three heads": (2, 256, 3, 3, 128, 256, 256, None),
    "window a multiple of the tile": (1, 768, 2, 2, 128, 256, 256, 256),
    "window of two tiles": (1, 1024, 1, 1, 128, 256, 256, 512),
    "window no multiple of the tile": (1, 768, 2, 2, 128, 256, 256, 320),
    "grouped heads 28 / 4": (1, 256, 28, 4, 128, 256, 256, None),
    "grouped heads under a window": (1, 512, 4, 2, 128, 256, 256, 256),
    "a head of 256": (1, 512, 2, 2, 256, 256, 256, None),
    "a head of 64, heads first": (1, 512, 8, 2, 64, 256, 256, None),
    "a head of 64 under a window": (1, 512, 4, 2, 64, 256, 256, 256),
    "q tile wider than k tile": (1, 512, 2, 2, 128, 256, 128, None),
    "k tile wider than q tile": (1, 512, 2, 1, 128, 128, 256, None),
    "k tile wider, a window": (1, 512, 2, 2, 128, 128, 256, 256),
    "the cells' tile, causal": (1, 2048, 1, 1, 128, 1024, 1024, None),
    "the cells' tile, a window": (1, 3072, 2, 1, 128, 1024, 1024, 1024),
    # Laguna's window layers and full layers: a window of half the tile, so
    # every tile the band touches runs whole under its mask, and groups of 8
    # and of 6 (no power of two) through the index maps
    "window 512 under the cells' tile, a group of 8":
        (1, 2048, 8, 1, 128, 1024, 1024, 512),
    "window 512 under the cells' tile, a group of 6":
        (1, 2048, 6, 1, 128, 1024, 1024, 512),
    "a group of 6, causal, two k/v heads": (1, 512, 12, 2, 128, 256, 256,
                                            None),
    "window 512 in tiles of 512, a group of 8":
        (1, 1536, 8, 1, 128, 512, 512, 512),
    # SmallThinker's window: an edge tile and four whole ones a row of tiles
    # under it, in eight pieces each; and with a q tile of half the k tile,
    # where every crossed tile's pieces span all its q rows
    "window 4096 at the cells' tile, a group of 2":
        (1, 5120, 2, 1, 128, 1024, 1024, 4096),
    "window 4096, a q tile of half the k tile":
        (1, 5120, 1, 1, 128, 512, 1024, 4096),
}


@pytest.mark.parametrize("case", sorted(_BANDED))
def test_flash_forward_in_place_and_banded_is_the_banded_form(case):
    """o, lse and the three gradients (of a loss that reads o and lse) of
    the kernels against ``_plain_attention`` / ``_banded_attention`` in
    float32, autodiff through it for the gradients."""
    B, S, H, Hkv, D, bq, bk, window = _BANDED[case]
    assert len(pa.tile_pieces(bq, bk)) == bk // pa.FWD_PIECE_ROWS
    q, k, v = _heads(B, S, H, Hkv, D)
    scale = 1.0 / D ** 0.5
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    u = jnp.sin(jnp.arange(B * H * S, dtype=jnp.float32).reshape(B * H, S))

    def flash(q, k, v):
        return pa.flash_attention_with_lse(q, k, v, True, None, bq, bk,
                                           interpret=True, window=window)

    def reference(q, k, v):
        o = (_plain_attention(q, k, v, True) if window is None and H == Hkv
             else pa._banded_attention(q, k, v, window))
        return o, _banded_lse(q, k, window, scale)

    def loss(f):
        def total(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * u)
        return total

    (o, lse), (o_ref, lse_ref) = flash(q, k, v), reference(q, k, v)
    assert o.shape == q.shape and lse.shape == (B * H, S)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


@pytest.mark.parametrize("D", [64, 128, 256])
def test_flash_without_a_mask_at_every_head_width(D):
    """Non-causal (a ring step's off-diagonal call): every tile runs all its
    pieces unmasked; o, lse and, through the ``custom_vjp``, dq, dk, dv of a
    loss that reads both, at groups of 2, two q tiles of 256 against a k
    tile of four pieces."""
    B, S, H, Hkv = 1, 512, 4, 2
    q, k, v = _heads(B, S, H, Hkv, D, seed=56)
    scale = 1.0 / D ** 0.5
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    u = jnp.sin(jnp.arange(B * H * S, dtype=jnp.float32).reshape(B * H, S))

    def flash(q, k, v):
        return pa.flash_attention_with_lse(q, k, v, False, None, 256, 512,
                                           interpret=True)

    def reference(q, k, v):
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(B, S, Hkv, H // Hkv, D), k) * scale
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v)
        return o.reshape(q.shape), jax.nn.logsumexp(s, -1).reshape(B * H, S)

    def loss(f):
        def total(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * u)
        return total

    for got, want in zip(flash(q, k, v), reference(q, k, v)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q, k, v)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


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
    """The pieces of a diagonal tile are ``FWD_PIECE_ROWS`` k rows against
    the q rows from the piece's first k row on: 36 of a 1024 x 1024 tile's
    64 blocks of 128 x 128, the ones that hold a live score, in eight
    pieces (the parent's two bands of 512 q rows ran 48); of an edge tile
    the mirror image. Every live score lies in exactly one piece, and
    every block a piece holds has a live score."""
    pieces = pa.tile_pieces(tile, tile, crossed)
    rows = pa.fwd_piece_rows(tile)
    assert rows == 128 == pa.FWD_PIECE_ROWS
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
    spans all the q rows, the k rows once each."""
    bq, bk = tile
    pieces = pa.tile_pieces(bq, bk)
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


# -- a window narrower than the tile (Laguna: 512 under 1024 x 1024) ----------

def test_a_window_of_half_a_tile_at_the_cell_s_shape():
    """8192 x 8192 in 1024 x 1024 tiles under a window of 512: 15 of the 36
    causal tiles are live, eight on the diagonal and seven that the band's
    lower edge crosses; none is wholly inside the band, 512 divides no tile,
    so every one runs whole under its mask (2 x 1024^2 scores a q tile for
    the 1024 x 512 + a triangle that are live); the index maps stay inside
    them. In 512 x 512 tiles the band is corner to corner again."""
    bq = bk = 1024
    n, window = 8, 512
    assert not pa.banded_tiles(bq, bk, window)
    assert pa.banded_tiles(512, 512, window)
    live = whole = 0
    for qi in range(n):
        lo = int(pa._first_band_k_tile(qi, bq, bk, window))
        hi = int(pa._last_live_k_tile(qi, bq, bk))
        assert hi == qi and lo == max(qi - 1, 0)
        for kj in range(n):
            crossed, clean = (bool(x) for x in pa._band_tiles(
                qi * bq, kj * bk, bq, bk, window))
            assert (crossed or clean) == (lo <= kj <= hi), (qi, kj)
            live += crossed
            whole += clean
            if crossed:
                assert int(pa._first_live_q_tile(kj, bq, bk)) <= qi \
                    <= int(pa._last_band_q_tile(kj, bq, bk, window))
    assert (live, whole) == (15, 0)
    # (the band's edge crosses the diagonal tiles too: all fifteen)
    assert pa.band_tile_counts(8192, bq, bk, window) == (36, 15, 15)
    assert pa.band_tile_counts(8192, 512, 512, window) == (136, 31, 15)
    # scores computed against scores live, a head: 15.7 M for 4.1 M
    computed = 15 * bq * bk
    alive = window * (window + 1) // 2 + (8192 - window) * window
    assert round(computed / alive, 2) == 3.87


@pytest.mark.parametrize("group", [6, 8])
def test_the_backward_under_a_window_of_half_a_tile(group):
    """``flash_backward`` at the cell's tile, a window of 512 and Laguna's
    groups, on the forward's own (o, lse): dk and dv are the group's sum."""
    B, S, D, window = 1, 2048, 128, 512
    q, k, v = _heads(B, S, group, 1, D, seed=53)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, window) * w), (0, 1, 2))(q, k, v)
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None, 1024, 1024,
                                             True, window)
        got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse),
                                True, D ** -0.5,
                                pa.BwdBlocks(1024, 1024, S), True, window)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert g.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


# -- a windowed call takes its tile and its grid from the window (PR 54) ------

#: window -> (forward tile, backward (block_q, block_k, rows)) at 8192
#: positions of heads of 128, bfloat16: no tile wider than the window (or
#: than MIN_BLOCK), SmallThinker's 4096 and no window as they were
_WINDOW_TILES = {
    None: ((1024, 1024), (1024, 1024, 8192)),
    8192: ((1024, 1024), (1024, 1024, 8192)),
    4096: ((1024, 1024), (1024, 1024, 8192)),
    1024: ((1024, 1024), (1024, 1024, 8192)),
    1000: ((512, 512), (512, 512, 8192)),
    512: ((512, 512), (512, 512, 8192)),
    320: ((256, 256), (256, 256, 8192)),
    128: ((128, 128), (128, 128, 8192)),
    100: ((128, 128), (128, 128, 8192)),
    1: ((128, 128), (128, 128, 8192)),
}


@pytest.mark.parametrize("window", sorted(_WINDOW_TILES, key=str))
def test_the_tile_rules_see_the_window(window):
    fwd, bwd = _WINDOW_TILES[window]
    assert flash_blocks(8192, 8192, 128, jnp.bfloat16, window) == fwd
    assert tuple(pa.flash_bwd_blocks(8192, 8192, 128, jnp.bfloat16,
                                     window)) == bwd
    # a trailing argument: a call without one means what it meant
    assert flash_blocks(8192, 8192, 128, jnp.bfloat16) == (1024, 1024)
    assert pa.banded_tiles(*fwd, window) == (
        window is None or window % fwd[0] == 0)


def test_the_tile_rule_under_a_window_at_lengths_its_tile_must_divide():
    # 512 does not divide 1280: the largest that does and is no wider
    assert flash_blocks(1280, 1280, 128, jnp.bfloat16, 640) == (256, 256)
    assert flash_blocks(1536, 1536, 128, jnp.bfloat16, 4096) == (512, 512)
    # the budgets still hold: a head of 256 in float32 under a window
    bq, bk = flash_blocks(4096, 4096, 256, jnp.float32, 1024)
    assert pa.flash_vmem_bytes(bq, bk, 256, 4) <= pa.VMEM_BUDGET
    assert max(bq, bk) <= 1024


#: the two cells that pass a window: (S, window, group) -> the tile both
#: kernels take, (grid steps, tiles run) a head forward and backward
_WINDOW_CELLS = {
    "laguna-xs.2.s8192": ((8192, 512), 512, (32, 31)),
    "smallthinker-21b-a3b.s8192": ((8192, 4096), 1024, (40, 30)),
}


def _live_tiles(S, bq, bk, window):
    """{(q tile, k tile)} with a live score, by the mask's definition."""
    return {(r0 // bq, c0 // bk)
            for r0 in range(0, S, bq) for c0 in range(0, S, bk)
            if c0 <= r0 + bq - 1 and c0 + bk - 1 > r0 - window}


@pytest.mark.parametrize("cell", sorted(_WINDOW_CELLS))
def test_a_windowed_cell_s_grids_walk_the_band_alone(cell):
    (S, window), tile, (steps, live) = _WINDOW_CELLS[cell]
    H, D = 4, 128
    assert flash_blocks(S, S, D, jnp.bfloat16, window) == (tile, tile)
    blocks = pa.flash_bwd_blocks(S, S, D, jnp.bfloat16, window)
    assert tuple(blocks) == (tile, tile, S)              # dq resident
    fwd = pa.flash_grid(1, H, S, S, tile, tile, window)
    bwd = pa.flash_bwd_grid(1, H, S, S, blocks, window)
    assert fwd == (H, S // tile, steps // (S // tile))
    assert bwd == (H, 1, S // tile, steps // (S // tile))
    assert pa.band_tile_counts(S, tile, tile, window)[1] == live
    # without a window the grids are the sequence's, as they were
    assert pa.flash_grid(1, H, S, S, tile, tile) == (H, S // tile,
                                                     S // tile)
    assert pa.flash_bwd_grid(1, H, S, S, blocks) == (H, 1, S // tile,
                                                     S // tile)


#: (S, block_q, block_k, rows of a q range, window): the two cells', tiles
#: that are not square, a window no multiple of the tile, one wider than
#: the sequence, one of a single key, q rows in ranges
_BAND_WALKS = [(8192, 512, 512, 8192, 512), (8192, 1024, 1024, 8192, 4096),
               (8192, 1024, 1024, 8192, 512), (2048, 256, 256, 2048, 320),
               (2048, 512, 256, 2048, 512), (2048, 256, 512, 2048, 384),
               (1024, 256, 256, 1024, 4096), (1024, 128, 128, 1024, 1),
               (2048, 256, 256, 1024, 512), (2048, 128, 256, 512, 700),
               (1280, 256, 256, 1280, 640)]


@pytest.mark.parametrize("walk", _BAND_WALKS)
def test_the_band_s_index_maps_visit_every_live_tile_exactly_once(walk):
    """The forward's k axis and the backward's q axis under a window, step
    by step in plain integers: the steps that stand for a tile inside the
    band are the tiles with a live score, each once; a step past the band
    stays on the band's last tile (nothing to fetch)."""
    S, bq, bk, rows, window = walk
    want = _live_tiles(S, bq, bk, window)
    assert len(want) == pa.band_tile_counts(S, bq, bk, window)[1]
    _, nq, steps = pa.flash_grid(1, 1, S, S, bq, bk, window)
    assert steps <= S // bk
    seen = []
    for qi in range(nq):
        for step in range(steps):
            kj, last = pa._band_k_tile(qi, step, bq, bk, window)
            assert isinstance(kj, int) and last < S // bk
            if kj <= last:
                seen.append((qi, kj))
    assert sorted(seen) == sorted(want)
    _, ranges, nk, steps = pa.flash_bwd_grid(
        1, 1, S, S, pa.BwdBlocks(bq, bk, rows), window)
    tiles = rows // bq
    assert steps <= tiles and ranges == S // rows
    seen = []
    for r in range(ranges):
        for kj in range(nk):
            for step in range(steps):
                qi, last = pa._band_q_tile(kj, step, r * tiles, tiles, bq,
                                           bk, window)
                assert last < (r + 1) * tiles
                if qi <= last:
                    assert qi >= r * tiles
                    seen.append((qi, kj))
    assert sorted(seen) == sorted(want)


#: name -> (B, S, H, Hkv, D, window, dtype, the rule's forward tile, the
#: forward's k steps a q tile, the backward's q steps a k tile): o, lse, dq,
#: dk and dv through the rule's own tile and grid (no override)
_RULE_BANDED = {
    "window 512, a group of 8":
        (1, 2048, 8, 1, 128, 512, jnp.float32, (512, 512), 2, 2),
    "window 512, a group of 6":
        (1, 1536, 6, 1, 128, 512, jnp.float32, (512, 512), 2, 2),
    "window 256": (1, 1024, 2, 1, 128, 256, jnp.float32, (256, 256), 2, 2),
    "window 320, no multiple of 128":
        (1, 768, 2, 2, 128, 320, jnp.float32, (256, 256), 3, 3),
    "a window wider than the sequence":
        (1, 512, 2, 1, 128, 1024, jnp.float32, (512, 512), 1, 1),
    # SmallThinker's group and tile: 2 x 1024 < S, so the last q tile's band
    # starts past the first k tile
    "a window of one tile, a group of 7":
        (1, 3072, 7, 1, 128, 1024, jnp.bfloat16, (1024, 1024), 2, 2),
    # (float32 at a head of 512 halves the q tile)
    "a window of one k tile under a q tile of half":
        (1, 3072, 2, 1, 512, 1024, jnp.float32, (512, 1024), 2, 5),
    # three of five q tiles have a band the sequence's start cuts
    "two tiles and a half, the first q tiles cut":
        (1, 1280, 2, 2, 128, 640, jnp.float32, (256, 256), 4, 4),
    "a window under the smallest tile, two batch rows":
        (2, 512, 2, 2, 128, 100, jnp.float32, (128, 128), 2, 2),
    "a head of 64 under a window of 256":
        (1, 1024, 4, 2, 64, 256, jnp.float32, (256, 256), 2, 2),
}


@pytest.mark.parametrize("case", sorted(_RULE_BANDED))
def test_a_windowed_call_by_the_rule_s_tile_and_grid_is_the_banded_form(
        case):
    """o, lse and the three gradients (of a loss that reads o and lse) of a
    windowed call as ``attend`` makes it, tile and grid by the rules,
    against ``_banded_attention`` / ``_banded_lse`` in float32 and autodiff
    through them."""
    B, S, H, Hkv, D, window, dtype, tile, k_steps, q_steps = \
        _RULE_BANDED[case]
    assert flash_blocks(S, S, D, dtype, window) == tile
    assert pa.flash_grid(B, H, S, S, *tile, window) == (
        B * H, S // tile[0], k_steps)
    blocks = pa.flash_bwd_blocks(S, S, D, dtype, window)
    assert blocks.rows == S
    assert pa.flash_bwd_grid(B, H, S, S, blocks, window) == (
        B * H, 1, S // blocks.block_k, q_steps)
    q, k, v = (x.astype(dtype) for x in _heads(B, S, H, Hkv, D, seed=54))
    scale = 1.0 / D ** 0.5
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    u = jnp.sin(jnp.arange(B * H * S, dtype=jnp.float32).reshape(B * H, S))

    def flash(q, k, v):
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None,
                                             interpret=True, window=window)
        return o.astype(jnp.float32), lse

    def reference(q, k, v):
        return (pa._banded_attention(q, k, v, window),
                _banded_lse(q, k, window, scale))

    def loss(f):
        def total(q, k, v):
            o, lse = f(q, k, v)
            return jnp.sum(o * w) + jnp.sum(lse * u)
        return total

    exact = dtype == jnp.float32
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    (o, lse), (o_ref, lse_ref) = flash(q, k, v), reference(q32, k32, v32)
    assert o.shape == q.shape and lse.shape == (B * H, S)
    assert lse.dtype == jnp.float32
    # bfloat16 operands: p, ds and the cotangent are rounded to 8 bits for
    # their matmuls, the reference multiplies the same values in float32
    tol = dict(rtol=2e-5, atol=2e-5) if exact else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref), **tol)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(reference), (0, 1, 2))(q32, k32, v32)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        assert g.shape == r.shape and g.dtype == dtype, name
        if exact:
            np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                       rtol=2e-4, atol=2e-4, err_msg=name)
        else:
            err = float(jnp.max(jnp.abs(g.astype(jnp.float32) - r))
                        / jnp.max(jnp.abs(r)))
            assert err < 4e-2, (name, err)


@pytest.mark.parametrize("ranges", [(1024, 256, 256, 512, 256),
                                    (1024, 128, 256, 256, 320),
                                    (1024, 256, 128, 512, 2000)])
def test_the_backward_s_band_walk_with_the_q_rows_in_ranges(ranges):
    """A window where dq is not resident: a range's steps start at the
    band's first q tile inside the range and a range the band leaves runs
    nothing; dk and dv are the ranges' sum."""
    S, bq, bk, rows, window = ranges
    D = 128
    q, k, v = _heads(1, S, 2, 1, D, seed=55)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    want = jax.grad(lambda *a: jnp.sum(
        pa._banded_attention(*a, window) * w), (0, 1, 2))(q, k, v)
    o, lse = pa.flash_attention_with_lse(q, k, v, True, None, interpret=True,
                                         window=window)
    blocks = pa.BwdBlocks(bq, bk, rows)
    assert pa.flash_bwd_grid(1, 2, S, S, blocks, window)[1] == S // rows > 1
    got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse), True,
                            D ** -0.5, blocks, True, window)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_chip_smoke_s_attention_path_prints_the_band_s_grid_and_tiles():
    """``chip_smoke.py``'s line for a windowed call, from the functions the
    kernels call: the rule's tile, the grid steps a call takes and, a head,
    the steps taken and the tiles run."""
    import chip_smoke
    laguna = chip_smoke._flash_call((1, 8192, 64, 128), 8, 512)
    assert laguna.startswith(
        "pallas hvd_flash_attention 512x512, 2048 steps, operands in place "
        "[1, 8192, 8192], scores [k, q] with m, l [1, 512] and acc [128, "
        "512] along the lanes, a tile in 4 pieces of 128 k rows, on the "
        "diagonal 10 of 16 blocks, on the band's edge 10 of 16 blocks; "
        "hvd_flash_bwd 512x512, dq resident, 2048 steps, "), laguna
    assert laguna.endswith(
        "; window 512: forward 32 steps and 31 of 136 causal tiles a head, "
        "15 on the edge, backward 32 steps and 31 of 136 causal tiles a "
        "head, 15 on the edge; kv heads 8, group 8"), laguna
    share = chip_smoke._flash_call((1, 8192, 28, 128), 4, 4096)
    assert "hvd_flash_attention 1024x1024, 1120 steps" in share
    assert "hvd_flash_bwd 1024x1024, dq resident, 1120 steps" in share
    assert share.endswith(
        "; window 4096: forward 40 steps and 30 of 36 causal tiles a head, "
        "4 on the edge, backward 40 steps and 30 of 36 causal tiles a head, "
        "4 on the edge; kv heads 4, group 7"), share
    full = chip_smoke._flash_call((1, 8192, 48, 128), 8)
    assert "hvd_flash_attention 1024x1024, 3072 steps" in full
    assert ("acc [128, 1024] along the lanes, a tile in 8 pieces of 128 k "
            "rows, on the diagonal 36 of 64 blocks; hvd_flash_bwd") in full
    narrow = chip_smoke._flash_call((1, 4096, 32, 64), 8)
    assert "operands heads first [32, 4096, 64], scores [k, q] with m, l " \
        "[1, 1024] and acc [64, 1024] along the lanes" in narrow
    assert "window" not in full and full.endswith("kv heads 8, group 6")
