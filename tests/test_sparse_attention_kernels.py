"""The sparse core's Pallas kernels (``ops/pallas_sparse_attention.py``:
``hvd_sparse_fwd``, ``hvd_sparse_mean``, ``hvd_sparse_bwd`` and the index
score pass's backward ``hvd_index_bwd``) against the XLA form of
``ops/sparse_attention.py``, interpret mode on the CPU: the outputs, the
indexer's loss, the counted and the packed selection, and the six gradients
through ``indexed_attention``; the rows' log-sum-exp, the heads' mean
attention and the index's three gradients at the kernels' own door. Each side
of a case is one jitted program, run once a process; the shapes are the least
that cross what the case names (a block is 128 positions, a piece 128
keys)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_sparse_attention as ps
from horovod_tpu.ops import sparse_attention as sa

SCALE = 128 ** -0.5


def _recent(qi, ki, w):
    """Index inputs whose scores grow with the key's position: a query
    selects its ``topk`` LAST causal keys, so from the second block on a
    row's first k tile holds no selected key."""
    pos = jnp.arange(ki.shape[1], dtype=jnp.float32)[None, :, None]
    return (jnp.ones_like(qi), (pos * jnp.ones_like(ki, jnp.float32)
                                ).astype(ki.dtype), jnp.ones_like(w))


def _tied(qi, ki, w):
    """Every index score +0.0: ties, broken to the lower index."""
    return jnp.zeros_like(qi), ki, w


# (B, S, H, Hkv, topk, block_k, dtype, what is done to the index's inputs)
CASES = {
    "a group of 8, rows short of topk, 4 blocks x 2 tiles x 2 pieces":
        (1, 512, 8, 1, 48, 256, jnp.float32, None),
    "batch 2, groups of 2, bfloat16":
        (2, 256, 4, 2, 32, 128, jnp.bfloat16, None),
    "first key tiles hold no selected key":
        (1, 384, 2, 1, 16, 128, jnp.float32, _recent),
    "a row selects one key": (1, 256, 2, 1, 1, 128, jnp.float32, None),
    "ties, groups of 1": (1, 256, 2, 2, 24, 256, jnp.float32, _tied),
    # index heads two a lane tile: the index's gradient is hvd_index_bwd's
    "16 index heads of 64, bands shorter than their calls' three tiles":
        (1, 384, 2, 1, 48, 128, jnp.float32, None),
}
#: (index heads, their width) of a case: two of 8 (an odd shape, autodiff of
#: the XLA expression) unless named
INDEX_HEADS = {
    "16 index heads of 64, bands shorter than their calls' three tiles":
        (16, 64),
}
RESULTS = ("o", "index_loss", "selected_keys", "selection")
GRADIENTS = ("dq", "dk", "dv", "d index query", "d index key",
             "d index weight")


def _inputs(B, S, H, Hkv, dtype, index, Hi=2, Di=8, D=128):
    keys = jax.random.split(jax.random.PRNGKey(65), 6)
    q = jax.random.normal(keys[0], (B, S, H, D), dtype)
    k, v = (jax.random.normal(kk, (B, S, Hkv, D), dtype) for kk in keys[1:3])
    qi = jax.random.normal(keys[3], (B, S, Hi, Di), dtype)
    ki = jax.random.normal(keys[4], (B, S, Di), dtype)
    w = jax.random.normal(keys[5], (B, S, Hi), jnp.float32)
    if index is not None:
        qi, ki, w = index(qi, ki, w)
    return q, k, v, qi, ki, w


@functools.lru_cache(maxsize=None)
def _both(case):
    """{form: (results, gradients)} of ``indexed_attention`` under a seeded
    cotangent of o plus three times the indexer's loss."""
    B, S, H, Hkv, topk, block_k, dtype, index = CASES[case]
    args = _inputs(B, S, H, Hkv, dtype, index, *INDEX_HEADS.get(case, (2, 8)))
    u = jax.random.normal(jax.random.PRNGKey(66), args[0].shape, jnp.float32)

    def side(kernels):
        def total(*args):
            out = sa.indexed_attention(*args, topk, SCALE, kernels=kernels)
            return jnp.sum(out[0].astype(jnp.float32) * u) + 3.0 * out[1], out
        (_, out), grads = jax.jit(jax.value_and_grad(
            total, argnums=tuple(range(6)), has_aux=True))(*args)
        return ([np.asarray(x, np.float64) for x in out],
                [np.asarray(g, np.float64) for g in grads])
    return {"xla": side(None), "kernels": side(ps.Kernels(block_k, True))}


def _close(got, want, dtype):
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=tol * max(np.abs(want).max(), 1.0))


@pytest.mark.parametrize("what", RESULTS)
@pytest.mark.parametrize("case", CASES)
def test_the_kernels_form_gives_the_xla_form_s_results(case, what):
    both, n = _both(case), RESULTS.index(what)
    got, want = both["kernels"][0][n], both["xla"][0][n]
    if what in ("selected_keys", "selection"):      # the same code made both
        np.testing.assert_array_equal(got, want)
    else:
        _close(got, want, CASES[case][6])


@pytest.mark.parametrize("what", GRADIENTS)
@pytest.mark.parametrize("case", CASES)
def test_the_kernels_form_gives_the_xla_form_s_gradients(case, what):
    both, n = _both(case), GRADIENTS.index(what)
    got, want = both["kernels"][1][n], both["xla"][1][n]
    # (a softmax over one key has no gradient; relu'(0) = 0 under the ties)
    assert np.abs(want).max() > 0 or case in (
        "a row selects one key", "ties, groups of 1"), what
    _close(got, want, CASES[case][6])


def test_the_cases_select_what_they_name():
    """Rows short of ``topk`` attend every causal key; the ``_recent`` case's
    later rows select nothing in their first tile; ``topk`` 1 selects one."""
    def chosen(case):
        bits = _both(case)["xla"][0][3].astype(np.uint8)
        return np.unpackbits(bits, axis=-1).astype(bool)[0]
    short = chosen(next(iter(CASES)))
    assert short[:48].sum(1).tolist() == list(range(1, 49))
    assert (short[48:].sum(1) == 48).all()
    recent = chosen("first key tiles hold no selected key")
    assert not recent[256:, :128].any() and (recent[256:].sum(1) == 16).all()
    assert (chosen("a row selects one key").sum(1) == 1).all()
    tied = chosen("ties, groups of 1")
    assert tied[100, :24].all() and tied[100].sum() == 24


# -- at the kernels' own door: a hand-made selection ----------------------------

@functools.lru_cache(maxsize=None)
def _door(group):
    """(kernels' (o, lse, p), the jax.numpy (o, lse, p)) of the third block
    of 384 positions under a selection whose rows see nothing in the first
    two k tiles but row 0, which sees key 5 alone; row 1 sees one key of the
    last tile; the rest a seeded half of the block's own causal keys."""
    S, Hkv, D, t0, bk = 384, 2, 128, 256, 128
    H = Hkv * group
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    q = jax.random.normal(keys[0], (ps.ROWS, H, D), jnp.float32)
    k, v = (jax.random.normal(kk, (S, Hkv, D), jnp.float32)
            for kk in keys[1:3])
    t = t0 + np.arange(ps.ROWS)
    chosen = np.array(jax.random.bernoulli(keys[3], 0.5, (ps.ROWS, S)))
    chosen &= (np.arange(S)[None] <= t[:, None]) & (np.arange(S)[None] >= t0)
    chosen[np.arange(ps.ROWS), t] = True            # (no row is empty)
    chosen[0] = np.arange(S) == 5
    chosen[1] = np.arange(S) == t0 + 1
    mask = ps.pack_selection(jnp.asarray(chosen), bk)
    np.testing.assert_array_equal(ps.unpack_selection(mask, bk), chosen)
    kern = ps.Kernels(bk, True)

    @jax.jit
    def kernels(q, k, v, mask):
        q, k, v = (x.reshape(x.shape[0], -1) for x in (q, k, v))
        door = dict(scale=SCALE, head_dim=D, kern=kern)
        o, lse = ps.sparse_forward(q, k, v, mask, jnp.int32(t0), **door)
        return o.reshape(ps.ROWS, H, D), lse, ps.heads_mean(
            q, k, lse, mask, jnp.int32(t0), **door)
    s = jnp.einsum("rhgd,khd->hgrk", q.reshape(ps.ROWS, Hkv, group, D),
                   k) * SCALE
    s = jnp.where(chosen, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("hgrk,khd->rhgd", p, v).reshape(ps.ROWS, H, D)
    want = (o, lse.reshape(H, 1, ps.ROWS), p.sum((0, 1)) / H)
    return ([np.asarray(x) for x in kernels(q, k, v, mask)],
            [np.asarray(x) for x in want])


@pytest.mark.parametrize("what", ["o", "lse", "p"])
@pytest.mark.parametrize("group", [1, 8])
def test_rows_with_no_selected_key_in_their_first_tiles(group, what):
    """The running max starts above a masked score: a row's masked first
    tiles add nothing to its sum (``exp(-1e30 - (-1e30))`` would be 1)."""
    got, want = _door(group)
    n = ["o", "lse", "p"].index(what)
    np.testing.assert_allclose(got[n], want[n], rtol=2e-5, atol=2e-6)
    if what == "p":         # row 0 puts all its weight on key 5
        assert got[n][0, 5] == pytest.approx(1.0, abs=1e-6)
        assert got[n][0].sum() == pytest.approx(1.0, abs=1e-6)


# (index heads, keys of the band, keys of the call, k tile, the block's first
# position, dtype, whether row 5's products are all <= 0)
INDEX_DOOR = {
    "16 heads, a band of 256 keys in a call of 512, a row of score +0.0":
        (16, 256, 512, 128, 128, jnp.float32, True),
    "4 heads, the diagonal tile the band's last":
        (4, 384, 384, 128, 256, jnp.float32, False),
    "16 heads, the diagonal in the first of two tiles of two pieces, "
    "bfloat16": (16, 512, 512, 256, 128, jnp.bfloat16, False),
}
INDEX_GRADIENTS = ("d index query", "d index weight", "d index key")


@functools.lru_cache(maxsize=None)
def _index_door(case):
    """(``hvd_index_bwd``'s (dqi, dw, dki), autodiff's of the XLA expression)
    of ``ct * KL(target || softmax_chosen(index_scores))`` for one block
    under a seeded selection (half its causal keys and its own) and a seeded
    target on it."""
    Hi, keys, span, bk, t0, dtype, dead = INDEX_DOOR[case]
    Di, ct = 64, 1.7
    rng = jax.random.split(jax.random.PRNGKey(69), 5)
    qi = jax.random.normal(rng[0], (ps.ROWS, Hi, Di), dtype)
    w = jax.random.normal(rng[1], (ps.ROWS, Hi), jnp.float32)
    ki = jax.random.normal(rng[2], (keys, Di), jnp.float32)
    if dead:
        qi, ki = qi.at[5].set(-jnp.abs(qi[5])), jnp.abs(ki)
    t = t0 + np.arange(ps.ROWS)
    chosen = np.array(jax.random.bernoulli(rng[3], 0.5, (ps.ROWS, keys)))
    chosen &= np.arange(keys)[None] <= t[:, None]
    chosen[np.arange(ps.ROWS), t] = True
    chosen = jnp.asarray(chosen)
    target = jax.nn.softmax(jnp.where(
        chosen, jax.random.normal(rng[4], chosen.shape), -jnp.inf), axis=-1)
    if dead:
        assert float(sa.index_scores(qi, w, ki)[5].max()) == 0.0

    def loss(qi, w, ki):
        return ct * sa._index_loss(target, sa.index_scores(qi, w, ki), chosen)

    @jax.jit
    def kernel(qi, w, ki):
        mask = ps.pack_selection(chosen, bk)
        mask = jnp.pad(mask, ((0, span // bk - mask.shape[0]), (0, 0), (0, 0)))
        return ps.index_backward(
            qi, w, ps.place_keys(ki.astype(dtype), Di, span),
            jnp.pad(target, ((0, 0), (0, span - keys))), mask,
            ps.index_rows(target, sa.index_scores(qi, w, ki), chosen),
            jnp.float32(ct), jnp.int32(t0), keys, ps.Kernels(bk, True))
    return ([np.asarray(x, np.float64) for x in kernel(qi, w, ki)],
            [np.asarray(x, np.float64)
             for x in jax.jit(jax.grad(loss, (0, 1, 2)))(qi, w, ki)])


@pytest.mark.parametrize("what", INDEX_GRADIENTS)
@pytest.mark.parametrize("case", INDEX_DOOR)
def test_the_index_kernel_gives_autodiff_s_gradients(case, what):
    """The products made again a tile at a time, ``dI`` formed from them,
    the keys past the band and the tiles past the diagonal untouched (their
    dki exactly 0), ``relu'(0) = 0`` on a row whose products are all <= 0."""
    got, want = _index_door(case)
    n = INDEX_GRADIENTS.index(what)
    assert got[n].shape == want[n].shape and np.abs(want[n]).max() > 0
    _close(got[n], want[n], INDEX_DOOR[case][5])
    if what == "d index key":
        _, _, _, bk, t0, _, _ = INDEX_DOOR[case]
        past = ((t0 + ps.ROWS - 1) // bk + 1) * bk
        assert not got[n][past:].any() and not want[n][past:].any()
    elif INDEX_DOOR[case][6]:       # row 5: no head is live
        assert not got[n][5].any() and not want[n][5].any()


def test_the_index_kernel_runs_in_the_backward_pass_alone():
    """The kernels' form makes the index scores with ``index_scores`` itself
    (the selection reads the parent's bits) and calls ``hvd_index_bwd`` once
    a band in the gradient, never in the forward pass; an index shape the
    kernel does not take (two heads of 8) keeps autodiff."""
    kern = ps.Kernels(128, True)

    def text(Hi, Di, grad):
        args = _inputs(1, 256, 2, 1, jnp.float32, None, Hi, Di)

        def loss(*args):
            return sa.indexed_attention(*args, 32, SCALE, kernels=kern)[1]
        return str(jax.make_jaxpr(jax.grad(loss, (3, 4, 5)) if grad else loss)(
            *args))
    assert sa.blocks(256) == (128, 2)
    assert text(16, 64, True).count("name=" + ps.INDEX_BWD_NAME) == 2
    assert ps.INDEX_BWD_NAME not in text(16, 64, False)
    assert ps.INDEX_BWD_NAME not in text(2, 8, True)
    assert ps.index_kernel_shapes(128, 16, 64)
    for odd in ((64, 16, 64), (128, 2, 8), (128, 3, 64), (128, 16, 48),
                (128, 4, 256)):
        assert not ps.index_kernel_shapes(*odd), odd


def test_the_mask_is_a_bit_a_key_at_a_tile_of_1024():
    chosen = np.asarray(jax.random.bernoulli(jax.random.PRNGKey(3), 0.3,
                                             (ps.ROWS, 2048 + 512)))
    mask = ps.pack_selection(jnp.asarray(chosen), 1024)
    assert mask.shape == (3, ps.PIECE, ps.ROWS) and mask.dtype == jnp.int8
    back = np.asarray(ps.unpack_selection(mask, 1024))
    np.testing.assert_array_equal(back[:, :2560], chosen)
    assert not back[:, 2560:].any()
    # piece n of tile j is bit n of the tile's bytes, keys on the sublanes
    words = np.asarray(mask).astype(np.int32)
    np.testing.assert_array_equal(
        (words[1] & (1 << 3)) != 0, chosen[:, 1024 + 384:1024 + 512].T)


def test_the_path_is_read_from_the_backend_and_the_shape(monkeypatch):
    assert sa.sparse_path(16384, 32, 4, 128) == "xla"        # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sa.sparse_path(16384, 32, 4, 128) == "pallas"
    assert ps.key_tile(16384) == 1024 and ps.key_tile(384) == 128
    assert ps.sparse_bwd_rows(16384, 1024, 8, 128, jnp.bfloat16) == 2048
    # a band's calls cover half the sequence's k tiles or all: two shapes
    assert [sa._call_tiles(2048 * band, 16384, 1024)
            for band in range(1, 9)] == [8] * 4 + [16] * 4
    assert sa._call_tiles(128, 384, 128) == 3
    for odd in ((16384 + 64, 32, 4, 128), (16384, 32, 4, 64),
                (16384, 32, 5, 128)):
        assert sa.sparse_path(*odd) == "xla", odd
