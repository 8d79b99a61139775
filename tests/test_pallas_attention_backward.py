"""The flash-attention backward kernel ``hvd_flash_bwd`` against autodiff
through the XLA oracle (interpret mode): a tile that a mask's line crosses
in pieces of ``PIECE_ROWS`` k rows whose two operand-only matmuls are
written ``BWD_AHEAD`` pieces ahead, a clean tile whole (PR 58; the written
order itself is held in the kernel's jaxpr). The rule that picks its tile
and q ranges, and the pair of kernels at a head of 64 (heads first,
grouped)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import flash_attention_tpu
from pallas_attention_cases import (LENGTHS, assert_backward, assert_grads,
                                    backward, cos_cotangent, qkv)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_kernel_grads_match_oracle(causal):
    assert_grads(*qkv(B=1, S=256, H=2, D=128), causal, cos_cotangent)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("shape", [(1024, 1024, 1), (384, 384, 1),
                                   (128, 256, 2)])
def test_flash_kernel_grads_match_oracle_at_the_rules_tiles(shape, causal):
    """The forward's tile (512 x 1024, 128 x 128, 128 x 256) and the
    backward's (``flash_bwd_blocks``: 1024 x 1024 in four pieces on the
    diagonal, 128 x 128, 128 x 256) are two rules."""
    Sq, Sk, H = shape
    assert_grads(*qkv(B=1, S=Sq, Sk=Sk, H=H, seed=7), causal,
                  cos_cotangent)


# (Sq, Sk, block_q, block_k, rows): q tiles wider and narrower than k
# tiles (a crossed tile's pieces span all its q rows under the mask), square
# tiles of several diagonal pieces beside clean tiles, dq resident and in q
# ranges of two tiles and of one, Sq != Sk both ways; a k tile of four
# pieces against q tiles of one (every piece adds to a q tile's whole dqT)
_BWD_TILES = [(512, 512, 128, 128, 512), (512, 512, 256, 128, 512),
              (512, 512, 128, 256, 512), (512, 512, 512, 512, 512),
              (1024, 1024, 512, 512, 1024), (512, 512, 128, 256, 256),
              (512, 512, 128, 128, 128), (768, 512, 256, 256, 768),
              (256, 512, 128, 128, 256), (512, 512, 128, 512, 256),
              (768, 1024, 256, 512, 768)]


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("tile", _BWD_TILES)
def test_flash_backward_tile_overrides(tile, causal):
    """Every tile and every q range gives the oracle's gradients: the
    diagonal crosses tiles in every way, tiles above it are skipped, a
    square tile on it runs in pieces, partial dk / dv of ranges add up."""
    Sq, Sk, bq, bk, rows = tile
    assert len(pa.bwd_tile_pieces(bq, bk, True)) == bk // pa.PIECE_ROWS
    got, want = backward(*qkv(B=1, S=Sq, Sk=Sk, H=2, seed=9), causal,
                          cos_cotangent, pa.BwdBlocks(bq, bk, rows))
    assert_backward(got, want)


@pytest.mark.parametrize("D", [64, 256])
def test_flash_backward_in_pieces_at_a_head_of(D):
    """A head of half a lane tile (heads first) and of two, grouped 4 on
    2, causal in two 256 x 256 tiles a side: a diagonal tile in two pieces
    and a clean one, the transposed dq ``[D, 256]`` a q tile."""
    rng = np.random.RandomState(15)

    def mk(heads):
        return jnp.asarray(rng.randn(1, 512, heads, D) * 0.5, jnp.float32)
    q, k, v, w = mk(4), mk(2), mk(2), mk(4)
    o, lse = pa.flash_attention_with_lse(q, k, v, True, None, 256, 256,
                                         interpret=True)
    got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse), True,
                            D ** -0.5, pa.BwdBlocks(256, 256, 512),
                            interpret=True)
    want = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        pa._banded_attention(q, k, v, None) * w), (0, 1, 2)))(q, k, v)
    assert_backward(got, want)


# -- the order a tile is written in (PR 58) -----------------------------------

def _tile_bodies(jaxpr):
    """The jaxprs, this one or a ``cond`` branch inside it, that hold an
    ``exp``: a tile's body, each of its pieces one ``exp``."""
    found = []
    if any(str(e.primitive) == "exp" for e in jaxpr.eqns):
        found.append(jaxpr)
    for e in jaxpr.eqns:
        for branch in e.params.get("branches", ()):
            found += _tile_bodies(branch.jaxpr)
    return found


#: name -> (Sq, Sk, block_q, block_k, causal, window) and the pieces of each
#: tile body the kernel holds, in the order written: the crossed tiles'
#: (diagonal, then the band's edge), then the clean tile's one
_WRITTEN = {
    "causal": (1024, 1024, 512, 512, True, None, [4, 1]),
    "a window of whole tiles": (1024, 1024, 256, 256, True, 256, [2, 2, 1]),
    "no mask": (256, 512, 256, 512, False, None, [1]),
    "a crossed tile that is not square": (512, 512, 128, 256, True, None,
                                          [2, 1]),
    "the cells' tile": (2048, 2048, 1024, 1024, True, None, [8, 1]),
}


@pytest.mark.parametrize("case", sorted(_WRITTEN))
def test_a_piece_s_operand_only_matmuls_are_written_before_the_last_exp(case):
    """What the gain rests on (PERF.md, PR 56, PR 58): Mosaic's scheduler
    keeps the order a kernel is written in, so in the body of a crossed
    tile in the kernel's jaxpr the two ``dot_general``s that need operands
    alone (``sT``, ``dpT``) of the ``BWD_AHEAD`` pieces after piece ``n``
    come before piece ``n``'s ``exp``, beside its own two and the three a
    piece (dv, dk, dq) of the pieces before it. Written in order the count
    before the ``exp`` would be ``2 (n + 1) + 3 n``. A clean tile is one
    piece (:func:`bwd_tile_pieces`)."""
    Sq, Sk, bq, bk, causal, window, pieces = _WRITTEN[case]
    D, H = 128, 2
    x = jax.ShapeDtypeStruct((1, Sq, H * D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, Sk, H * D), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((H, 1, Sq), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda *a: pa._flash_bwd_local.__wrapped__(
        *a, H=H, causal=causal, scale=0.1, blocks=pa.BwdBlocks(bq, bk, Sq),
        interpret=False, window=window))(x, kv, kv, x, row, row)
    call = next(e for e in jaxpr.eqns if "pallas_call" in str(e.primitive))
    found = _tile_bodies(call.params["jaxpr"])
    assert pa.BWD_AHEAD >= 1
    assert len(pa.bwd_tile_pieces(bq, bk, False)) == 1
    assert len(pa.bwd_tile_pieces(bq, bk, True)) == bk // pa.PIECE_ROWS
    assert pa.bwd_tile_pieces(bq, bk, True, "edge") == pa.tile_pieces(
        bq, bk, "edge")
    written = []
    for body in found:
        names = [str(e.primitive) for e in body.eqns]
        exps = [n for n, name in enumerate(names) if name == "exp"]
        written.append(len(exps))
        assert names.count("dot_general") == 5 * len(exps)
        for n, at in enumerate(exps):
            ahead = min(n + 1 + pa.BWD_AHEAD, len(exps))
            assert names[:at].count("dot_general") == 2 * ahead + 3 * n, (
                case, n)
    assert written == pieces


@pytest.mark.parametrize("causal", [True, False])
def test_flash_backward_takes_the_lse_cotangent(causal):
    q, k, v = qkv(B=1, S=256, H=2, seed=10)
    weight = jnp.asarray(np.random.RandomState(11).randn(2, 256),
                         jnp.float32)
    got, want = backward(q, k, v, causal, cos_cotangent,
                          lse_weight=weight)
    assert_backward(got, want)
    # and it matters: without it dq differs
    plain, _ = backward(q, k, v, causal, cos_cotangent)
    assert float(jnp.max(jnp.abs(plain[0] - got[0]))) > 1e-3


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("S", [256, 1024])
def test_flash_backward_bf16_inputs_match_float32_oracle(S, causal):
    """bf16 operands multiply as bf16 with float32 accumulation, p and ds
    are cast for their matmuls: against the float32 oracle on the same
    values the gradients hold chip_smoke.py's tolerance."""
    q, k, v = qkv(B=1, S=S, H=1, seed=12, dtype=jnp.bfloat16)
    got, want = backward(q, k, v, causal, lambda o: jnp.sum(o ** 2))
    assert got[0].dtype == jnp.bfloat16
    for g, w, name in zip(got, want, ("dq", "dk", "dv")):
        err = jnp.max(jnp.abs(g.astype(jnp.float32) - w)) / jnp.max(
            jnp.abs(w))
        assert float(err) <= 2e-2, (name, float(err))


def test_flash_grads_rect():
    """Sq != Sk backward (cross-attention shape)."""
    assert_grads(*qkv(B=1, S=128, Sk=256, seed=2), False,
                  lambda o: jnp.sum(o ** 2))


# -- the backward's tile rule -------------------------------------------------

# what the nine causal cells and a ring step of chip_smoke.py call it with
# (bf16): (Sq, Sk, head_dim, window) -> (block_q, block_k, rows)
_BWD_RULE = {
    (2048, 2048, 128, None): (1024, 1024, 2048),  # gpt-1.3b-widths.s2048
    (4096, 4096, 128, None): (1024, 1024, 4096),  # olmoe-1b-7b, ouro-2.6b
    (4096, 4096, 64, None): (1024, 1024, 4096),   # granite-4.0-h-micro
    # nemotron-3-nano-30b-a3b, and the full layers of smallthinker-21b-a3b
    # and laguna-xs.2
    (8192, 8192, 128, None): (1024, 1024, 8192),
    (8192, 8192, 128, 4096): (1024, 1024, 8192),  # smallthinker's windows
    (8192, 8192, 128, 512): (512, 512, 8192),     # laguna-xs.2's windows
    # glm-4.7-flash: 512 x 512 while the score tile was counted five times
    # (PR 58: 11.07 ms a call alone at 512 x 512, 9.64 at 1024 x 1024)
    (8192, 8192, 256, None): (1024, 1024, 8192),
    (8192, 8192, 64, None): (1024, 1024, 8192),   # lfm2-24b-a2b
    (512, 512, 128, None): (512, 512, 512),       # ring attention, sp=4
    (128, 256, 128, None): (128, 256, 128),
    (384, 640, 128, None): (128, 128, 384),
}


@pytest.mark.parametrize("shape", sorted(_BWD_RULE, key=str))
def test_flash_bwd_blocks_at_the_shapes_that_run(shape):
    Sq, Sk, D, window = shape
    blocks = pa.flash_bwd_blocks(Sq, Sk, D, jnp.bfloat16, window)
    assert blocks == _BWD_RULE[shape]
    assert blocks.rows == Sq              # dq resident: one range
    assert pa.flash_bwd_vmem_bytes(*blocks, D, 2) <= pa.BWD_VMEM_BUDGET


def test_flash_bwd_vmem_bytes_counts_a_clean_tile_s_scores_once():
    """The blocks the pipeline double-buffers, the float32 accumulators
    (dqT a q tile of the range) and one clean tile's scores at 7 bytes each
    in bfloat16, 10 in float32: what the compiler takes, rounded up (the
    parent counted 18 and 24). The latent cell's 1024 x 1024 at a head of
    256 fits by that count and not by the parent's."""
    def count(bq, bk, rows, D=128, itemsize=2):
        return pa.flash_bwd_vmem_bytes(bq, bk, rows, D, itemsize)
    io = 2 * 4 * 1024 * 128 * 2 + 2 * 2 * 8 * 1024 * 4
    out = 2 * (2 * 1024 + 8192) * 128 * 2
    scratch = (2 * 1024 + 8192) * 128 * 4
    assert count(1024, 1024, 8192) == io + out + scratch + 7 * 1024 * 1024
    assert count(512, 512, 512, itemsize=4) - count(256, 512, 512,
                                                    itemsize=4) \
        == 2 * 2 * 256 * 128 * 4 + 2 * 2 * 8 * 256 * 4 + 10 * 256 * 512
    assert count(1024, 1024, 8192, D=256) <= pa.BWD_VMEM_BUDGET \
        < count(1024, 1024, 8192, D=256) + 11 * 1024 * 1024


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("D", [128, 256, 512])
def test_flash_bwd_blocks_divide_and_fit(D, dtype):
    itemsize = jnp.dtype(dtype).itemsize
    for Sq in LENGTHS + [16384, 65536]:
        for Sk in LENGTHS:
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
    prime = pa.flash_bwd_blocks(128 * 263, 128 * 263, 128, jnp.bfloat16)
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
    assert_grads(*qkv(B=1, S=256, Sk=128, seed=13), True,
                  lambda o: jnp.sum(o ** 2))
    assert_grads(*qkv(B=1, S=128, Sk=384, seed=14), True,
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
    ref = jax.jit(jax.grad(lambda q, k, v: jnp.sum(
        pa._banded_attention(q, k, v, None, SCALE) * w), (0, 1, 2)))(q, k, v)
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
    got = jax.jit(jax.grad(loss(lambda q, k, v: flash_attention_tpu(
        q, k, v, True, SCALE, interpret=True)), (0, 1, 2)))(q, k, v)
    want = jax.jit(jax.grad(loss(lambda q, k, v: pa._banded_attention(
        q, k, v, None, SCALE)), (0, 1, 2)))(
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



# -- adj = sum_d do * o - dlse is a kernel's, ``hvd_flash_adj``, where a head
# is whole lane tiles (ISSUE 62) --------------------------------------------

def _parent_adj(do, o, dlse, *, H, interpret):
    """The parent's ``jax.numpy`` form in ``_flash_adj_local``'s place:
    float32 copies of do and o, their product summed over a head, the
    heads moved in front of the positions."""
    B, Sq, M = do.shape
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    delta = jnp.sum(prod.reshape(B, Sq, H, M // H), axis=-1)
    return delta.transpose(0, 2, 1).reshape(B * H, 1, Sq) - dlse


#: name -> (S, H, Hkv, D, window, the backward's blocks (None: the rule's,
#: and ``jax.grad`` through the custom VJP too), dtype, a live dlse)
_ADJ_CASES = {
    "a head of 64 in groups of 4, heads first": (256, 8, 2, 64, None, None,
                                                 jnp.bfloat16, True),
    "three heads of 64, heads first": (128, 3, 3, 64, None, None,
                                       jnp.float32, False),
    "a head of 128, a group of 1": (256, 2, 2, 128, None, None, jnp.float32,
                                    True),
    "a head of 256": (256, 2, 2, 256, None, None, jnp.bfloat16, False),
    "a group of 7": (128, 7, 1, 128, None, None, jnp.float32, True),
    "a group of 16": (128, 16, 1, 128, None, None, jnp.bfloat16, False),
    "a window narrower than a tile": (512, 2, 1, 128, 192,
                                      pa.BwdBlocks(256, 256, 512),
                                      jnp.float32, True),
    "a window wider than a tile": (512, 2, 2, 128, 320,
                                   pa.BwdBlocks(128, 128, 512), jnp.float32,
                                   False),
    "two q ranges": (512, 2, 2, 128, None, pa.BwdBlocks(128, 128, 256),
                     jnp.float32, True),
    "two q ranges under a window, heads of 64": (
        512, 4, 2, 64, 256, pa.BwdBlocks(128, 128, 256), jnp.float32, True),
    "more lanes than a block holds: a head a step": (
        128, 17, 17, 256, None, None, jnp.float32, True),
}


@pytest.mark.parametrize("case", sorted(_ADJ_CASES))
def test_adj_is_the_kernel_s_and_the_gradients_are_the_parent_s(case,
                                                                 monkeypatch):
    """``hvd_flash_adj``'s rows against ``sum(do32 * o32, -1) - dlse`` to
    1e-6 of the largest, and dq, dk, dv of ``flash_backward`` (and of
    ``jax.grad`` through ``flash_attention_with_lse`` / ``_tpu`` where the
    tile is the rule's) against the same call with the parent's
    ``jax.numpy`` sums in the kernel's place, to the backward cases'
    tolerance. A head of 64 goes heads first and keeps those sums: its
    call holds no ``hvd_flash_adj``."""
    S, H, Hkv, D, window, blocks, dtype, live_dlse = _ADJ_CASES[case]
    rng = np.random.RandomState(62)

    def mk(heads, scale=0.5):
        return (jnp.asarray(rng.randn(1, S, heads, D), jnp.float32)
                * scale).astype(dtype)
    q, k, v, do = mk(H), mk(Hkv), mk(Hkv), mk(H, 1.0)
    o, lse = pa.flash_attention_with_lse(q, k, v, True, interpret=True,
                                         window=window)
    dlse = jnp.asarray(rng.randn(H, S) * live_dlse, jnp.float32)

    def backward():
        return pa.flash_backward(q, k, v, o, lse, do, dlse, True, D ** -0.5,
                                 blocks, interpret=True, window=window)
    in_place = D % pa.MIN_BLOCK == 0
    assert (pa.ADJ_NAME in str(jax.make_jaxpr(backward)())) == in_place
    if in_place:
        rows = pa._flash_adj_local(
            do.reshape(1, S, H * D), o.reshape(1, S, H * D),
            dlse.reshape(H, 1, S), H=H, interpret=True)
        want = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                       -1).transpose(0, 2, 1).reshape(H, 1, S) \
            - dlse.reshape(H, 1, S)
        assert rows.shape == want.shape and rows.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(rows - want))) \
            <= 1e-6 * float(jnp.max(jnp.abs(want)))

    def grads():
        if blocks is not None:
            return ()
        if live_dlse:
            def loss(q, k, v):
                o, lse = pa.flash_attention_with_lse(
                    q, k, v, True, interpret=True, window=window)
                return jnp.sum(o.astype(jnp.float32) * do) \
                    + jnp.sum(lse * dlse)
        else:
            def loss(q, k, v):
                return jnp.sum(flash_attention_tpu(
                    q, k, v, True, interpret=True,
                    window=window).astype(jnp.float32) * do)
        return jax.grad(loss, (0, 1, 2))(q, k, v)
    got = backward() + grads()
    monkeypatch.setattr(pa, "_flash_adj_local", _parent_adj)
    reference = backward() + grads()
    assert len(got) == (3 if blocks else 6)
    assert_backward(got[:3], reference[:3])
    assert_backward(got[3:], reference[3:])
    # and the two ways in agree: the custom VJP calls ``flash_backward``
    for g, r in zip(got[3:], got[:3]):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


#: q of the seven cells that run ``hvd_flash_adj`` (``flash_backward`` at
#: heads of whole lane tiles) -> (rows, heads) a step
_ADJ_BLOCKS = {
    (8192, 20, 256): (128, 20), (8192, 28, 128): (256, 28),
    (8192, 64, 128): (128, 64), (8192, 48, 128): (128, 48),
    (4096, 16, 128): (512, 16), (2048, 16, 128): (512, 16),
    (8192, 32, 128): (256, 32),
}


@pytest.mark.parametrize("shape", sorted(_ADJ_BLOCKS))
def test_flash_adj_blocks_are_whole_rows_of_the_cells_arrays(shape):
    """A step of ``hvd_flash_adj`` reads whole rows of ``[B, S, H * D]``
    (every head: one contiguous read) at the cells' shapes, as many as
    ``ADJ_BLOCK_BYTES`` hold; wider rows go a divisor of the heads a
    step."""
    S, H, D = shape
    rows, heads = pa.flash_adj_blocks(S, H, D, jnp.bfloat16)
    assert (rows, heads) == _ADJ_BLOCKS[shape]
    assert rows * heads * D * 2 <= pa.ADJ_BLOCK_BYTES
    rows32, heads32 = pa.flash_adj_blocks(S, H, D, jnp.float32)
    assert H % heads32 == 0
    assert rows32 * heads32 * D * 4 <= pa.ADJ_BLOCK_BYTES
