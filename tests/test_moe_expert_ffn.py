"""The experts' feed-forward over the rows a device holds (ISSUE 44):
``moe.expert_ffn`` against the plain expression ``down(activation(rows @
we1) [* rows @ we3])`` in the same dtype, forward and every gradient, for the three activations the configurations name, at the
held counts a layer meets (every row and no device scalar, a share that
ends inside a chunk, none, every row under a device scalar), on XLA's
``ragged_dot`` and on the megablox kernels in interpret mode; the rows
from ``held`` on hold NaN and none reaches a result. And what a jaxpr can
hold of the form: no loop and no ``custom_vjp`` where every row is held;
where a share is, loops, no sum over all the rows and only the kernels'
outputs kept.

On the CPU only values are checked; which arrays the TPU's compiler then
makes is ``tests/test_tpu_compile.py``'s.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe
from moe_cases import all_jaxprs

#: 256 sorted rows in chunks of 64, four experts held; widths of one
#: 128-lane tile, so that the megablox kernels apply in interpret mode
_ROWS, _WIDTH, _EXPERTS, _CHUNK = 256, 128, 4, 64

ACTIVATIONS = {
    "gated silu": (True, jax.nn.silu),
    "gated relu": (True, jax.nn.relu),
    "ungated relu2": (False, lambda h: jnp.square(jax.nn.relu(h))),
}
#: name -> (group sizes, whether ``held`` is a device scalar)
HELD = {
    "None": ((70, 0, 121, 65), False),
    "a share": ((30, 0, 45, 25), True),      # 100: ends inside chunk 2
    "no row": ((0, 0, 0, 0), True),
    "every row": ((70, 0, 121, 65), True),
}


def _case(gated, sizes, dtype, seed=0):
    rng = np.random.RandomState(seed)
    held = sum(sizes)

    def weights(k, f):
        return jnp.asarray(rng.randn(_EXPERTS, k, f) / np.sqrt(k),
                           jnp.float32)
    params = {"we1": weights(_WIDTH, _WIDTH), "we2": weights(_WIDTH, _WIDTH)}
    if gated:
        params["we3"] = weights(_WIDTH, _WIDTH)
    rows = jnp.asarray(rng.randn(_ROWS, _WIDTH), dtype)
    ct = rng.randn(_ROWS, _WIDTH).astype(np.float32)
    ct[held:] = 0.0         # no one reads the way down's rows from there on
    return rows, params, jnp.asarray(sizes, jnp.int32), jnp.asarray(ct)


def _plain(rows, params, sizes, activation):
    """The expression the layer is, differentiated by JAX, in the rows'
    dtype on XLA's ``ragged_dot``."""
    h = activation(moe.grouped_matmul(rows, params["we1"], sizes))
    if "we3" in params:
        h = h * moe.grouped_matmul(rows, params["we3"], sizes)
    return moe.grouped_matmul(h, params["we2"], sizes)


@functools.lru_cache(maxsize=None)
def _both(kind, dtype, path, scalar):
    """(the layer's value and gradients, the plain expression's) as ``(rows,
    params, group_sizes, ct) ->``, each traced and compiled once under this
    file's ``ROW_CHUNK``. Whether ``held`` is None or a device scalar is
    static in the trace, so a program a ``(kind, dtype, path, scalar)``; the
    group sizes, and with them the scalar's value, are arguments."""
    gated, activation = ACTIVATIONS[kind]
    interpret = path == "kernels"

    def layer(rows, params, group_sizes, ct):
        n_held = jnp.sum(group_sizes)
        out = moe.expert_ffn(rows, params["we1"], params.get("we3"),
                             params["we2"], group_sizes,
                             n_held if scalar else None,
                             activation, interpret=interpret)
        # (a select, not a product: 0 * NaN is NaN)
        live = (jnp.arange(_ROWS) < n_held)[:, None]
        return jnp.sum(jnp.where(live, out.astype(jnp.float32) * ct, 0)), out

    def plain(rows, params, group_sizes, ct):
        out = _plain(rows, params, group_sizes, activation)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    wide = jax.ShapeDtypeStruct((_EXPERTS, _WIDTH, _WIDTH), jnp.float32)
    shapes = (jax.ShapeDtypeStruct((_ROWS, _WIDTH), dtype),
              {name: wide for name in (("we1", "we2", "we3") if gated
                                       else ("we1", "we2"))},
              jax.ShapeDtypeStruct((_EXPERTS,), jnp.int32),
              jax.ShapeDtypeStruct((_ROWS, _WIDTH), jnp.float32))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "ROW_CHUNK", _CHUNK)
        assert moe._row_chunk(_ROWS) == _CHUNK
        return tuple(jax.jit(jax.value_and_grad(f, (0, 1), has_aux=True)
                             ).lower(*shapes).compile()
                     for f in (layer, plain))


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("kind", sorted(ACTIVATIONS))
def test_the_way_up_over_the_held_rows_is_the_plain_expression(
        kind, held, dtype, path):
    gated, _activation = ACTIVATIONS[kind]
    sizes, scalar = HELD[held]
    n_held = sum(sizes)
    rows, params, group_sizes, ct = _case(gated, sizes, dtype)
    layer, plain = _both(kind, dtype, path, scalar)
    # what lies in the rows from ``held`` on reaches nothing
    planted = rows.at[n_held:].set(jnp.nan) if scalar else rows
    (_, out), (d_rows, d_params) = layer(planted, params, group_sizes, ct)
    (_, want_out), (want_rows, want_params) = plain(rows, params,
                                                    group_sizes, ct)
    assert out.dtype == d_rows.dtype == dtype
    # float32 at 1e-6; bfloat16 within tests/test_smallthinker.py's 2e-2
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    pairs = [("out", out[:n_held], want_out[:n_held]),
             ("d_rows", d_rows[:n_held], want_rows[:n_held])]
    pairs += [("d_" + name, d_params[name], want_params[name])
              for name in sorted(params)]
    for name, got, want in pairs:
        got, want = (np.asarray(a, np.float64) for a in (got, want))
        assert got.shape == want.shape and np.all(np.isfinite(got)), name
        assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want), \
            (name, np.linalg.norm(got - want), np.linalg.norm(want))


@pytest.mark.parametrize("kind", sorted(ACTIVATIONS))
def test_where_every_row_is_held_the_way_up_is_the_expression_it_was(kind):
    """``held`` None: ``expert_ffn`` traces, to the letter, what
    ``_moe_ffn``'s ``expert_fn`` was before it (one pass, no loop, no
    ``custom_vjp`` of its own), which is what keeps the cell
    olmoe-1b-7b.s4096's program the parent's (``ci/cell_jaxpr.py``)."""
    gated, activation = ACTIVATIONS[kind]
    rows, params, group_sizes, _ct = _case(gated, HELD["None"][0],
                                           jnp.bfloat16)

    def before(rows, params):
        h = moe.grouped_matmul(rows, params["we1"], group_sizes)
        if gated:
            h = activation(h) * moe.grouped_matmul(rows, params["we3"],
                                                   group_sizes)
        else:
            h = activation(h)
        return moe.grouped_matmul(h, params["we2"], group_sizes)

    def now(rows, params):
        return moe.expert_ffn(rows, params["we1"], params.get("we3"),
                              params["we2"], group_sizes, None, activation)

    def grad_text(f):
        return str(jax.make_jaxpr(jax.grad(
            lambda r, p: jnp.sum(f(r, p).astype(jnp.float32)), (0, 1)))(
                rows, params))
    assert grad_text(now) == grad_text(before)
    assert "while" not in grad_text(now)


@pytest.mark.parametrize("kind", sorted(ACTIVATIONS))
def test_a_share_s_way_up_sums_nothing_over_all_the_rows(kind, monkeypatch):
    """Under a device scalar the layer's gradient has one loop forward
    (the activation), one backward (the activation again, for the way
    down's weight gradient, and its derivative) and, for gated experts,
    one more (the sum of the rows' two cotangents, which autodiff takes as
    an ``add_any`` over all ``[N, M]``), and keeps the rows and the
    kernels' outputs, no other ``[N, F]`` array."""
    monkeypatch.setattr(moe, "ROW_CHUNK", _CHUNK)   # a chunk is not [N, M]
    gated, activation = ACTIVATIONS[kind]
    rows, params, group_sizes, _ct = _case(gated, HELD["a share"][0],
                                           jnp.bfloat16)

    def layer(rows, params):
        return jnp.sum(moe.expert_ffn(
            rows, params["we1"], params.get("we3"), params["we2"],
            group_sizes, jnp.sum(group_sizes), activation
        ).astype(jnp.float32))

    eqns = [eqn for jaxpr in all_jaxprs(
        jax.make_jaxpr(jax.grad(layer, (0, 1)))(rows, params).jaxpr)
        for eqn in jaxpr.eqns]
    assert sum(eqn.primitive.name == "while" for eqn in eqns) \
        == (3 if gated else 2)
    assert (_ROWS, _WIDTH) not in [
        eqn.outvars[0].aval.shape for eqn in eqns
        if eqn.primitive.name == "add_any"], "a sum over all the rows"
    # what the forward keeps for the backward: the residuals of the vjp
    _out, vjp = jax.vjp(layer, rows, params)
    kept = [leaf.shape for leaf in jax.tree_util.tree_leaves(vjp)
            if getattr(leaf, "shape", ()) == (_ROWS, _WIDTH)
            and leaf.dtype == jnp.bfloat16]
    # rows, h1 (and h3)
    assert len(kept) == (3 if gated else 2), kept


def test_rows_held_is_none_where_the_groups_are_all_the_experts():
    sizes = jnp.asarray([3, 0, 5, 1], jnp.int32)
    assert moe.rows_held(sizes, 4) is None
    assert int(moe.rows_held(sizes, 16)) == 9


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


# (PR 41, the hybrid cell's experts; these stood in tests/test_nemotron_h.py)
# -- the grouped matmul at a width no 128-multiple divides ------------------------

def test_gmm_takes_a_width_no_lane_tile_divides_as_one_block(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = moe.gmm_path(49152, 2688, 1856)
    assert path.startswith(
        "pallas hvd_moe_gmm weights read as stored, [E, 1856, 2688] (1856 "
        "columns are no multiple of 128 lanes and 2688 rows are, so the "
        "chip keeps the rows minor: transpose_rhs forward, the weight "
        "gradient written [E, 1856, 2688]): forward 256x896x1856; input "
        "gradient 256x1856x896, a group's weights resident; weight gradient "
        "256x1856x384"), path
    down = moe.gmm_path(49152, 1856, 2688)
    assert down.startswith(
        "pallas hvd_moe_gmm weights read as stored, [E, 1856, 2688] "
        "row-major (transpose_rhs in the input gradient): forward "
        "256x1856x896, a group's weights resident; input gradient "
        "256x896x1856; weight gradient 256x1856x384"), down
    assert moe._lane_tiles(1856) == [1856] and moe._lane_tiles(64) == []
    assert moe._lane_tiles(2688) == [2688, 896, 384, 128]
    # the way up's calls are the way down's, each other's: one stored shape
    assert moe._gmm_tile(49152, 2688, 1856, 2) == moe.GmmTiles(
        (256, 896, 1856), (256, 1856, 896), (256, 1856, 384), True)
    assert moe._gmm_tile(49152, 1856, 2688, 2) == moe.GmmTiles(
        (256, 1856, 896), (256, 896, 1856), (256, 1856, 384), False)
    # the OLMoE and SmallThinker shapes keep the tiles PR 33 gave them
    assert moe._gmm_tile(65536, 2048, 1024, 2) == moe.GmmTiles(
        (256, 2048, 1024), (256, 1024, 2048), (256, 1024, 1024))
    assert moe._gmm_tile(49152, 2560, 768, 2) == moe.GmmTiles(
        (256, 2560, 768), (256, 768, 2560), (256, 1280, 768))
    assert moe._gmm_tile(49152, 768, 2560, 2) == moe.GmmTiles(
        (256, 768, 2560), (256, 2560, 768), (256, 768, 1280))


@pytest.mark.parametrize("k, f, transposed", [
    (128, 192, True), (256, 192, True), (128, 256, False),
    (192, 128, False), (192, 192, False)])
def test_the_kernels_at_such_a_width_are_the_ragged_dot(k, f, transposed):
    """Interpret mode: 192 columns (1.5 lane tiles) as one block, forward
    and both gradients, groups that start inside a row tile and end before
    the rows do. Where the columns are no multiple of 128 lanes and the
    rows are, the calls read the weights ``[E, f, k]`` and return their
    gradient swapped back (ISSUE 41); elsewhere in the order they had."""
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(256, k), jnp.float32)
    w = jnp.asarray(rng.randn(3, k, f) / 8, jnp.float32)
    sizes = jnp.asarray([100, 0, 92], jnp.int32)
    assert moe._gmm_tile(256, k, f, 4).transposed == transposed
    weight = jnp.asarray(rng.randn(256, f), jnp.float32)
    inside = (jnp.arange(256) < 192)[:, None]

    def run(interpret):
        def loss(r, w):
            y = moe.grouped_matmul(r, w, sizes, interpret=interpret)
            return jnp.sum(jnp.where(inside, y, 0) * weight)
        return jax.jit(jax.value_and_grad(loss, (0, 1)))(rows, w)
    with jax.default_matmul_precision("highest"):
        (got, (d_rows, d_w)), (want, (r_rows, r_w)) = run(True), run(False)
    assert d_w.shape == w.shape and d_w.dtype == w.dtype
    assert _rel(got, want) < 1e-5
    assert _rel(jnp.where(inside, d_rows, 0), r_rows) < 1e-5
    assert _rel(d_w, r_w) < 1e-5
