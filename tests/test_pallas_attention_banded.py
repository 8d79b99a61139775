"""Both flash kernels in the backward's form (PR 50, PR 56) under the mask's
band, tile by override: operands in place as ``[B, S, heads * D]``, scores
transposed and the softmax state along the lanes, every tile in pieces of
``PIECE_ROWS`` k rows; against ``_banded_attention`` in float32
(interpret mode). A windowed call by the rule's own tile and grid:
test_pallas_attention_windows.py.

A case is its traces and compiles (about 7 s that no shape moves), so each
side is one program (``one_trace``) and a case runs at the least tile and
sequence that cross the boundary its line names: what a tile of 1024 has
that one of 256 lacks is eight pieces for two, and two cases keep the
cells' tile for that."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel.ring_attention import _plain_attention
from pallas_attention_cases import banded_lse, heads, one_trace, weights

#: name -> (B, S, H, Hkv, D, block_q, block_k, window), and the boundary the
#: case crosses. Pieces of 128 k rows: a tile of 256 has two, one of 512
#: four and the cells' 1024 x 1024 eight
_BANDED = {
    # square tiles on the diagonal, in two pieces and in four
    "causal": (1, 512, 2, 2, 128, 256, 256, None),
    "causal, four bands a tile": (1, 512, 1, 1, 128, 512, 512, None),
    # the batch and the head in the grid's first axis
    "two batch rows of three heads": (2, 256, 3, 3, 128, 256, 256, None),
    # the band's lower edge corner to corner through a tile (edge pieces),
    # a dead tile before the band, and a tile wholly inside it
    "window a multiple of the tile": (1, 768, 2, 2, 128, 256, 256, 256),
    "window of two tiles": (1, 1024, 1, 1, 128, 256, 256, 512),
    # the edge cuts a tile anywhere else: the tile runs whole under its mask
    "window no multiple of the tile": (1, 768, 2, 2, 128, 256, 256, 320),
    # the share cell's group of 7 through the index maps
    "grouped heads 28 / 4": (1, 256, 28, 4, 128, 256, 256, None),
    "grouped heads under a window": (1, 512, 4, 2, 128, 256, 256, 256),
    # a head of two lane tiles; a head of 64 (heads first)
    "a head of 256": (1, 512, 2, 2, 256, 256, 256, None),
    "a head of 64, heads first": (1, 512, 8, 2, 64, 256, 256, None),
    "a head of 64 under a window": (1, 512, 4, 2, 64, 256, 256, 256),
    # tiles that are not square: no line crosses them corner to corner
    "q tile wider than k tile": (1, 512, 2, 2, 128, 256, 128, None),
    "k tile wider than q tile": (1, 512, 2, 1, 128, 128, 256, None),
    "k tile wider, a window": (1, 512, 2, 2, 128, 128, 256, 256),
    # the cells' tile: eight pieces on the diagonal, and on a window's edge
    # with a dead tile before the last q tile's band
    "the cells' tile, causal": (1, 2048, 1, 1, 128, 1024, 1024, None),
    "the cells' tile, a window": (1, 3072, 2, 1, 128, 1024, 1024, 1024),
    # Laguna's window and full layers: a window of half the tile, so every
    # tile the band touches runs whole under its mask (two q tiles: three
    # live, one dead), and groups of 8 and of 6 (no power of two) through
    # the index maps
    "a window of half the tile, a group of 8":
        (1, 512, 8, 1, 128, 256, 256, 128),
    "a window of half the tile, a group of 6":
        (1, 512, 6, 1, 128, 256, 256, 128),
    "a group of 6, causal, two k/v heads": (1, 512, 12, 2, 128, 256, 256,
                                            None),
    "a window of one tile, a group of 8": (1, 768, 8, 1, 128, 256, 256, 256),
    # SmallThinker's window of four tiles under five: an edge tile and four
    # whole ones in the last row of tiles; and with a q tile of half the k
    # tile, where every crossed tile's pieces span all its q rows
    "a window of four tiles under five, a group of 2":
        (1, 1280, 2, 1, 128, 256, 256, 1024),
    "a window of four k tiles, a q tile of half the k tile":
        (1, 1280, 1, 1, 128, 128, 256, 1024),
}


@pytest.mark.parametrize("case", sorted(_BANDED))
def test_flash_forward_in_place_and_banded_is_the_banded_form(case):
    """o, lse and the three gradients (of a loss that reads o and lse) of
    the kernels against ``_plain_attention`` / ``_banded_attention`` in
    float32, autodiff through it for the gradients."""
    B, S, H, Hkv, D, bq, bk, window = _BANDED[case]
    assert len(pa.tile_pieces(bq, bk)) == bk // pa.PIECE_ROWS
    q, k, v = heads(B, S, H, Hkv, D)
    scale = 1.0 / D ** 0.5
    w, u = weights(q, B * H)

    def flash(q, k, v):
        return pa.flash_attention_with_lse(q, k, v, True, None, bq, bk,
                                           interpret=True, window=window)

    def reference(q, k, v):
        o = (_plain_attention(q, k, v, True) if window is None and H == Hkv
             else pa._banded_attention(q, k, v, window))
        return o, banded_lse(q, k, window, scale)

    (o, lse), got = one_trace(flash, q, k, v, w, u)
    (o_ref, lse_ref), want = one_trace(reference, q, k, v, w, u)
    assert o.shape == q.shape and lse.shape == (B * H, S)
    assert lse.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref),
                               rtol=2e-5, atol=2e-5)
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
    q, k, v = heads(B, S, H, Hkv, D, seed=56)
    scale = 1.0 / D ** 0.5
    w, u = weights(q, B * H)

    def flash(q, k, v):
        return pa.flash_attention_with_lse(q, k, v, False, None, 256, 512,
                                           interpret=True)

    def reference(q, k, v):
        s = jnp.einsum("bqhgd,bkhd->bhgqk",
                       q.reshape(B, S, Hkv, H // Hkv, D), k) * scale
        o = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(s, -1), v)
        return o.reshape(q.shape), jax.nn.logsumexp(s, -1).reshape(B * H, S)

    outs, got = one_trace(flash, q, k, v, w, u)
    refs, want = one_trace(reference, q, k, v, w, u)
    for a, b in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)
    for g, r, name in zip(got, want, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
