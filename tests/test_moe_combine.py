"""The expert layer's row movement back to the tokens (ISSUE 36):
``moe._combine`` and ``moe._dispatch``'s backward against the formulas they
replace, ``rows[inverse].reshape(T, k, M)`` summed over its middle axis in
float32 and autodiff through that; and that no value of the layer's
gradient program has a top-k axis beside the model width, nor a float32
array of the rows' size outside one elementwise pass. And the layer that
reads only the rows it holds (ISSUE 37) against the dense weighted sum
over the held experts, at held counts around a chunk's and the gathers'
prefix's edge.

On the CPU only values are checked; which arrays the TPU's compiler then
makes is ``tests/test_tpu_compile.py``'s (the share cell's real step).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.parallel import moe
from moe_cases import all_jaxprs

T, M = 48, 40


def _case(k, dtype, seed=0):
    """Sorted rows ``[T * k, M]`` whose last quarter is a dead zero tail
    (the rows behind a held share's groups: ``held`` is the rest), a random
    permutation and its inverse, float32 weights, a cotangent in
    ``dtype``."""
    rng = np.random.RandomState(seed)
    n = T * k
    rows = rng.randn(n, M).astype(np.float32)
    rows[_held(k):] = 0.0
    order = rng.permutation(n).astype(np.int32)
    weights = rng.rand(T, k).astype(np.float32)
    g = rng.randn(T, M).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(weights),
            jnp.asarray(order), jnp.asarray(np.argsort(order), jnp.int32),
            jnp.asarray(g, dtype))


def _held(k):
    return T * k - T * k // 4


def _replaced_combine(rows, weights, inverse):
    k = weights.shape[1]
    by_token = rows[inverse].reshape(-1, k, rows.shape[1])
    return jnp.sum(by_token.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(rows.dtype)


def _close(got, want, tol):
    got, want = (np.asarray(a, np.float64) for a in (got, want))
    assert np.linalg.norm(got - want) <= tol * np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [2, 6, 8])
def test_combine_is_the_weighted_sum_it_replaces(k, dtype):
    rows, weights, order, inverse, g = _case(k, dtype)
    want, want_vjp = jax.vjp(
        lambda r, w: _replaced_combine(r, w, inverse), rows, weights)
    got, got_vjp = jax.vjp(
        lambda r, w: moe._combine(r, w, order, inverse, jnp.int32(_held(k)),
                                  None, rows.dtype), rows, weights)
    assert got.dtype == rows.dtype and got.shape == (T, M)
    # float32: the k additions' order is the compiler's; bfloat16: that
    # last float32 bit can move the one rounding of the output
    _close(got, want, 1e-5 if dtype == jnp.float32 else 2 ** -8)
    (d_rows, d_weights), (want_rows, want_weights) = got_vjp(g), want_vjp(g)
    assert d_rows.dtype == rows.dtype and d_weights.dtype == weights.dtype
    # behind the held rows no cotangent is computed, and none is read
    d_rows, want_rows = d_rows[:_held(k)], want_rows[:_held(k)]
    if dtype == jnp.bfloat16:   # one product, one rounding: no order in it
        np.testing.assert_array_equal(np.asarray(d_rows, np.float32),
                                      np.asarray(want_rows, np.float32))
    else:
        _close(d_rows, want_rows, 1e-5)
    _close(d_weights, want_weights, 1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("k", [2, 6, 8])
def test_dispatch_backward_is_the_sum_it_replaces(k, dtype):
    _rows, _weights, order, inverse, _g = _case(k, dtype)
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(T, M), dtype)
    g = jnp.asarray(rng.randn(T * k, M), dtype)
    held = _held(k)
    rows, vjp = jax.vjp(
        lambda x: moe._dispatch(x, order, inverse, jnp.int32(held), k), x)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(x[order // k], np.float32))
    g = g.at[held:].set(0)      # what lies there is not read
    want = g[inverse].reshape(T, k, M).astype(jnp.float32).sum(axis=1)
    (got,) = vjp(g.at[held:].set(jnp.nan))
    assert got.dtype == x.dtype
    _close(got, want.astype(dtype), 1e-5 if dtype == jnp.float32 else 2 ** -8)
    # and the sum is the gather's transpose
    (scattered,) = jax.vjp(lambda x: x[order // k], x.astype(jnp.float32)
                           )[1](g.astype(jnp.float32))
    _close(got, scattered, 1e-5 if dtype == jnp.float32 else 2 ** -7)


#: what one loop fusion holds: casts, products, a smaller operand broadcast
#: into them, and the sum that ends them
_MAKES = {"convert_element_type", "mul", "broadcast_in_dim"}
_READS = {"convert_element_type", "mul", "reduce_sum"}


@pytest.mark.parametrize("k", [2, 6])
def test_the_layer_s_gradient_has_no_top_k_axis_beside_the_width(k):
    """``jax.grad`` through ``moe_layer_spmd`` on bfloat16 tokens: no value
    of any dtype is shaped ``[..., k, M]`` (with k under the sublane tile
    that is a padded copy on a TPU), and a float32 value with the rows'
    ``T * k * M`` elements exists only inside an elementwise pass: made by
    a cast, a product or a broadcast, read by casts, products and sums;
    never gathered, reshaped, kept for the backward or handed across a
    ``custom_vjp`` (ISSUE 36 asked for no such value at all, but its own
    backward's ``f32(g[order // k])`` is one: this is what of it a jaxpr
    can hold, and the compiled share cell holds the rest)."""
    n_experts = 8
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(T, M), jnp.bfloat16)
    router = jnp.asarray(rng.randn(M, n_experts), jnp.float32)
    scale = jnp.asarray(rng.rand(n_experts, M), jnp.bfloat16)

    def expert_fn(scale, rows, group_sizes):
        del group_sizes
        return rows * scale[0]

    def loss(x, router, scale):
        y, _metrics = moe.moe_layer_spmd(x, router, expert_fn, scale,
                                         axis_name=None, k=k)
        return jnp.sum(y.astype(jnp.float32))

    top = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(x, router, scale).jaxpr
    big, seen = T * k * M, 0

    def rows_sized(var):    # a Literal has an aval too, and is no array
        aval = var.aval
        return (aval.dtype == jnp.float32 and getattr(aval, "size", 0) >= big)

    for jaxpr in all_jaxprs(top):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            for var in eqn.outvars:
                assert var.aval.shape[-2:] != (k, M), (name, var.aval)
                if rows_sized(var):
                    assert name in _MAKES, (name, var.aval)
                    seen += 1
            for var in eqn.invars:
                assert not rows_sized(var) or name in _READS, (name, var.aval)
        for var in (*jaxpr.invars, *jaxpr.outvars):
            assert not rows_sized(var), ("crosses a call", var.aval)
    assert seen, "the backward's one pass is in float32"


# -- the layer bounded by the rows it holds (ISSUE 37) -----------------------

#: 64 tokens x top-2 over 8 experts of which this device holds the first
#: four (``share=(0, 2)``): 128 sorted rows in chunks of 32, the first 48
#: of them a gather's fast source; widths of one 128-lane tile, so that the
#: megablox kernels apply in interpret mode
_TOKENS, _K, _EXPERTS, _WIDTH, _CHUNK, _PREFIX = 64, 2, 8, 128, 32, 48
_ROWS = _TOKENS * _K


def _forced_logits(held, rng):
    """Router logits under which exactly ``held`` of the ``_ROWS``
    assignments, spread over the tokens, go to the held experts 0..3 (a
    token's two choices differ), the rest to 4..7."""
    on_held = np.zeros(_ROWS, bool)
    on_held[rng.permutation(_ROWS)[:held]] = True
    logits = rng.randn(_TOKENS, _EXPERTS).astype(np.float32)
    half = _EXPERTS // 2
    for t in range(_TOKENS):
        for j in range(_K):
            # choice j takes from its own pair of a half's four experts
            e = 2 * j + rng.randint(2) + (0 if on_held[t * _K + j] else half)
            logits[t, e] += 20.0 + rng.rand()
    return jnp.asarray(logits)


def _dense_share(x, logits, params, dtype):
    """The layer's result with no rows moved: every held expert on every
    token, weighted by what the router gave that expert there."""
    _probs, weights, experts = moe.route(logits, _K, False)
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(_EXPERTS // 2):
        w_e = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=1)
        h = jnp.tanh(x @ params["w1"][e].astype(dtype))
        y = y + w_e[:, None] * (h @ params["w2"][e].astype(dtype)
                                ).astype(jnp.float32)
    return y.astype(dtype)


@functools.lru_cache(maxsize=None)
def _both(dtype, path):
    """(the layer's value and gradients, the dense sum's) as ``(x, logits,
    params, ct) ->``, each traced and compiled once a ``(dtype, path)`` under
    this section's ``ROW_CHUNK`` and ``GATHER_SOURCE_BYTES``: ``held``
    changes the forced logits' values, no shape."""
    router = jnp.zeros((_WIDTH, _EXPERTS), jnp.float32)    # logits are given
    interpret = path == "kernels"

    def expert_fn(p, rows, group_sizes):
        h = jnp.tanh(moe.grouped_matmul(rows, p["w1"], group_sizes,
                                        interpret=interpret))
        return moe.grouped_matmul(h, p["w2"], group_sizes,
                                  interpret=interpret)

    def layer(x, logits, params, ct):
        y, metrics = moe.moe_layer_spmd(
            x, router, expert_fn, params, axis_name=None, k=_K,
            logits=logits, share=(0, 2))
        return jnp.sum(y.astype(jnp.float32) * ct), (y, metrics)

    def dense(x, logits, params, ct):
        y = _dense_share(x, logits, params, dtype)
        return jnp.sum(y.astype(jnp.float32) * ct), y

    f32 = jnp.float32
    weights = jax.ShapeDtypeStruct((_EXPERTS // 2, _WIDTH, _WIDTH), f32)
    shapes = (jax.ShapeDtypeStruct((_TOKENS, _WIDTH), dtype),
              jax.ShapeDtypeStruct((_TOKENS, _EXPERTS), f32),
              {"w1": weights, "w2": weights},
              jax.ShapeDtypeStruct((_TOKENS, _WIDTH), f32))
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "ROW_CHUNK", _CHUNK)
        patch.setattr(moe, "GATHER_SOURCE_BYTES",
                      _PREFIX * _WIDTH * jnp.dtype(dtype).itemsize)
        assert moe._row_chunk(_ROWS) == _CHUNK
        return tuple(jax.jit(jax.value_and_grad(f, (0, 1, 2), has_aux=True)
                             ).lower(*shapes).compile()
                     for f in (layer, dense))


@pytest.mark.parametrize("path", ["xla", "kernels"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("held", [0, 1, _CHUNK - 1, _CHUNK, _CHUNK + 1,
                                  _PREFIX, _PREFIX + 1, _ROWS])
def test_the_layer_over_the_rows_it_holds_is_the_dense_weighted_sum(
        held, dtype, path):
    """``moe_layer_spmd`` of a share against the dense sum over its
    experts: forward, and the gradients of the tokens, of the router's
    logits (through the k weights) and of the expert parameters; routing
    forced so that no assignment, one, a chunk of them less one, a chunk,
    a chunk and one, as many as the token-major gathers' fast source holds,
    one more, and all of them are held; nothing dropped. ``kernels`` runs
    the megablox kernels in interpret mode, where a row that no kernel
    wrote reads NaN: none reaches a result."""
    rng = np.random.RandomState(held)
    logits = _forced_logits(held, rng)
    x = jnp.asarray(rng.randn(_TOKENS, _WIDTH), dtype)
    params = {
        "w1": jnp.asarray(rng.randn(_EXPERTS // 2, _WIDTH, _WIDTH)
                          / np.sqrt(_WIDTH), jnp.float32),
        "w2": jnp.asarray(rng.randn(_EXPERTS // 2, _WIDTH, _WIDTH)
                          / np.sqrt(_WIDTH), jnp.float32)}
    ct = jnp.asarray(rng.randn(_TOKENS, _WIDTH), jnp.float32)
    layer, dense = _both(dtype, path)
    (_, (y, metrics)), got = layer(x, logits, params, ct)
    assert float(metrics.held_rows) == held and float(metrics.dropped) == 0
    (_, want_y), want = dense(x, logits, params, ct)
    tol = 2e-5 if dtype == jnp.float32 else 2 ** -6
    names = ["y", "d_x", "d_logits", "d_w1", "d_w2"]
    pairs = zip(names, [y, got[0], got[1], got[2]["w1"], got[2]["w2"]],
                [want_y, want[0], want[1], want[2]["w1"], want[2]["w2"]])
    for name, g, w in pairs:
        g, w = (np.asarray(a, np.float64) for a in (g, w))
        assert np.all(np.isfinite(g)), name
        assert np.linalg.norm(g - w) <= tol * max(np.linalg.norm(w), 1e-30), \
            (name, np.linalg.norm(g - w), np.linalg.norm(w))
