"""The flash kernels under a window: a band and grouped heads by overridden
tiles, a window narrower than the tile, and a windowed call as ``attend``
makes it, its tile and its grid from the window by the rules (PR 54), against
``_banded_attention`` in float32 (interpret mode); the band's index maps
step by step in plain integers."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops.pallas_attention import flash_blocks
from horovod_tpu.parallel.ring_attention import _plain_attention
from pallas_attention_cases import banded_lse, heads, one_trace, weights


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


# -- a band and grouped heads, tile by override (PR 32: SmallThinker's) -------

def _grouped(S, H, Hkv, D=128, B=1, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, w = (jax.random.normal(k, (B, S, H, D), jnp.float32)
            for k in (ks[0], ks[3]))
    k, v = (jax.random.normal(kk, (B, S, Hkv, D), jnp.float32)
            for kk in ks[1:3])
    return q, k, v, w


@pytest.mark.parametrize("window, tile", [
    (64, 128),      # smaller than a tile
    (192, 128),     # not a multiple of a tile
    (128, 128),     # a tile
    (256, 256),     # a tile, the backward's pieces on the diagonal and edge
    (256, 128),     # two tiles
    (None, 128),    # grouped heads alone
])
def test_flash_kernels_take_a_band_and_grouped_heads(window, tile):
    """Forward and backward kernels against the XLA path, 4 query heads on
    2 key/value heads: the band's edge inside a tile, across tiles and on
    a tile's corner; dk and dv are a group's sum."""
    q, k, v, w = _grouped(512, 4, 2)
    with jax.default_matmul_precision("highest"):
        want = pa._banded_attention(q, k, v, window)
        want_grads = jax.jit(jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, window) * w), (0, 1, 2)))(q, k, v)
        o, lse = pa.flash_attention_with_lse(
            q, k, v, True, None, tile, tile, True, window)
        got_grads = pa.flash_backward(
            q, k, v, o, lse, w, jnp.zeros_like(lse), True, 128 ** -0.5,
            pa.BwdBlocks(tile, tile, 512), True, window)
    assert rel(o, want) < 1e-5
    for name, g, r in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert g.shape == r.shape and rel(g, r) < 1e-5, name


def test_flash_backward_in_q_ranges_with_a_band_and_a_group():
    """The q rows in two ranges and a tile that is not square: each range
    clamps its own q tiles to the band."""
    q, k, v, w = _grouped(512, 2, 1, seed=1)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, 192) * w), (0, 1, 2)))(q, k, v)
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None, 256, 128,
                                             True, 192)
        got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse),
                                True, 128 ** -0.5,
                                pa.BwdBlocks(128, 256, 256), True, 192)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert rel(g, r) < 1e-5, name


def test_attend_s_xla_path_is_the_same_function():
    """Off the TPU ``attend`` takes the XLA form: a window that covers the
    sequence and no group is plain causal attention."""
    q, k, v, _w = _grouped(128, 4, 4, D=16)
    np.testing.assert_allclose(pa.attend(q, k, v, window=128),
                               _plain_attention(q, k, v), rtol=1e-5,
                               atol=1e-6)
    assert rel(pa.attend(q, k, v, window=32), _plain_attention(q, k, v)) \
        > 1e-2
    with pytest.raises(ValueError, match="window"):
        pa.attend(q, k, v, causal=False, window=32)
    with pytest.raises(ValueError, match="k/v heads"):
        pa.attend(q, k[:, :, :3], v[:, :, :3])


def test_the_band_s_live_tiles_at_the_cell_s_shape():
    """8192 x 8192, 1024 x 1024 tiles, a window of 4096: 30 of the 36
    causal tiles run, four of them on the band's edge; the index maps
    stay inside them."""
    bq = bk = 1024
    n, window = 8, 4096
    live = edge = 0
    for qi in range(n):
        lo = int(pa._first_band_k_tile(qi, bq, bk, window))
        hi = int(pa._last_live_k_tile(qi, bq, bk))
        for kj in range(n):
            crossed, whole = (bool(x) for x in pa._band_tiles(
                qi * bq, kj * bk, bq, bk, window))
            assert (crossed or whole) == (lo <= kj <= hi), (qi, kj)
            live += crossed or whole
            edge += crossed and kj != qi
            if crossed or whole:
                assert int(pa._first_live_q_tile(kj, bq, bk)) <= qi \
                    <= int(pa._last_band_q_tile(kj, bq, bk, window))
    assert (live, edge) == (30, 4)
    assert pa.band_tile_counts(8192, bq, bk, window) == (36, 30, 4)
    assert pa.band_tile_counts(8192, bq, bk, None) == (36, 36, 0)


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
    """``flash_backward`` under a window of half its tile (the cell's 512
    under 1024 x 1024, here 128 under 256 x 256: two q tiles, three live and
    one dead, none inside the band, so every tile runs whole under its
    mask) and Laguna's groups, on the forward's own (o, lse): dk and dv are
    the group's sum."""
    B, S, D, window, tile = 1, 512, 128, 128, 256
    assert not pa.banded_tiles(tile, tile, window)
    q, k, v = heads(B, S, group, 1, D, seed=53)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, window) * w), (0, 1, 2)))(q, k, v)
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None, tile, tile,
                                             True, window)
        got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse),
                                True, D ** -0.5,
                                pa.BwdBlocks(tile, tile, S), True, window)
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
#: dk and dv through the rule's own tile and grid (no override). The tile is
#: the rule's, so a case's S is the least that gives the rule that tile and
#: the walk its steps; the heads are the least that make the named group
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
    # SmallThinker's tile in bfloat16: 2 x 1024 < S, so the last q tile's
    # band starts past the first k tile (its group of 7 through the index
    # maps: test_pallas_attention_banded.py's "grouped heads 28 / 4")
    "a window of one tile, bfloat16":
        (1, 3072, 2, 1, 128, 1024, jnp.bfloat16, (1024, 1024), 2, 2),
    # (float32 at a head of 512 halves the q tile; the backward's is 256 x
    # 512, six q tiles a k tile's band)
    "a window of one k tile under a q tile of half":
        (1, 3072, 2, 1, 512, 1024, jnp.float32, (512, 1024), 2, 6),
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
    against ``_banded_attention`` / ``banded_lse`` in float32 and autodiff
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
    q, k, v = (x.astype(dtype) for x in heads(B, S, H, Hkv, D, seed=54))
    scale = 1.0 / D ** 0.5
    w, u = weights(q, B * H)

    def flash(q, k, v):
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None,
                                             interpret=True, window=window)
        return o.astype(jnp.float32), lse

    def reference(q, k, v):
        return (pa._banded_attention(q, k, v, window),
                banded_lse(q, k, window, scale))

    exact = dtype == jnp.float32
    (o, lse), got = one_trace(flash, q, k, v, w, u)
    (o_ref, lse_ref), want = one_trace(
        reference, *(x.astype(jnp.float32) for x in (q, k, v)), w, u)
    assert o.shape == q.shape and lse.shape == (B * H, S)
    assert lse.dtype == jnp.float32
    # bfloat16 operands: p, ds and the cotangent are rounded to 8 bits for
    # their matmuls, the reference multiplies the same values in float32
    tol = dict(rtol=2e-5, atol=2e-5) if exact else dict(rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), **tol)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(lse_ref), **tol)
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
    q, k, v = heads(1, S, 2, 1, D, seed=55)
    w = jnp.cos(jnp.arange(q.size, dtype=jnp.float32).reshape(q.shape))
    want = jax.jit(jax.grad(lambda *a: jnp.sum(
        pa._banded_attention(*a, window) * w), (0, 1, 2)))(q, k, v)
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
    kernels call: the rule's tile, the grid steps a call takes, both
    kernels' pieces (and how many the backward writes ahead) and, a head,
    the steps taken and the tiles run."""
    import chip_smoke
    laguna = chip_smoke._flash_call((1, 8192, 64, 128), 8, 512)
    assert laguna.startswith(
        "pallas hvd_flash_attention 512x512, 2048 steps, operands in place "
        "[1, 8192, 8192], scores [k, q] with m, l [1, 512] and acc [128, "
        "512] along the lanes, a tile in 4 pieces of 128 k rows, on the "
        "diagonal 10 of 16 blocks, on the band's edge 10 of 16 blocks; "
        "hvd_flash_bwd 512x512, dq resident, 2048 steps, a crossed tile in 4 "
        "pieces of 128 k rows, on the diagonal 10 of 16 blocks, on the "
        "band's edge 10 of 16 blocks, sT and dpT written "
        f"{pa.BWD_AHEAD} ahead, a clean tile in 1, dqT [128, 512] a q tile, "
        "VMEM estimate "), laguna
    assert laguna.endswith(
        "; window 512: forward 32 steps and 31 of 136 causal tiles a head, "
        "15 on the edge, backward 32 steps and 31 of 136 causal tiles a "
        "head, 15 on the edge; kv heads 8, group 8"), laguna
    share = chip_smoke._flash_call((1, 8192, 28, 128), 4, 4096)
    assert "hvd_flash_attention 1024x1024, 1120 steps" in share
    assert ("hvd_flash_bwd 1024x1024, dq resident, 1120 steps, a crossed tile "
            "in 8 pieces of 128 k rows, on the diagonal 36 of 64 blocks, on "
            "the band's edge 36 of 64 blocks, sT and dpT written") in share
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
