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

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe
from test_moe_combine import _all_jaxprs

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


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("held", sorted(HELD))
@pytest.mark.parametrize("kind", sorted(ACTIVATIONS))
def test_the_way_up_over_the_held_rows_is_the_plain_expression(
        kind, held, dtype, path, monkeypatch):
    monkeypatch.setattr(moe, "ROW_CHUNK", _CHUNK)
    assert moe._row_chunk(_ROWS) == _CHUNK
    gated, activation = ACTIVATIONS[kind]
    sizes, scalar = HELD[held]
    n_held = sum(sizes)
    rows, params, group_sizes, ct = _case(gated, sizes, dtype)
    interpret = path == "kernels"

    def layer(rows, params):
        out = moe.expert_ffn(rows, params["we1"], params.get("we3"),
                             params["we2"], group_sizes,
                             jnp.int32(n_held) if scalar else None,
                             activation, interpret=interpret)
        # (a select, not a product: 0 * NaN is NaN)
        live = (jnp.arange(_ROWS) < n_held)[:, None]
        return jnp.sum(jnp.where(live, out.astype(jnp.float32) * ct, 0)), out

    def plain(rows, params):
        out = _plain(rows, params, group_sizes, activation)
        return jnp.sum(out.astype(jnp.float32) * ct), out

    # what lies in the rows from ``held`` on reaches nothing
    planted = rows.at[n_held:].set(jnp.nan) if scalar else rows
    (_, out), (d_rows, d_params) = jax.jit(jax.value_and_grad(
        layer, (0, 1), has_aux=True))(planted, params)
    (_, want_out), (want_rows, want_params) = jax.value_and_grad(
        plain, (0, 1), has_aux=True)(rows, params)
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

    eqns = [eqn for jaxpr in _all_jaxprs(
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
