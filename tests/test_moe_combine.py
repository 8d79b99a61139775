"""The expert layer's row movement back to the tokens (ISSUE 36):
``moe._combine`` and ``moe._dispatch``'s backward against the formulas they
replace, ``rows[inverse].reshape(T, k, M)`` summed over its middle axis in
float32 and autodiff through that; and that no value of the layer's
gradient program has a top-k axis beside the model width, nor a float32
array of the rows' size outside one elementwise pass.

On the CPU only values are checked; which arrays the TPU's compiler then
makes is ``tests/test_tpu_compile.py``'s (the share cell's real step).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.extend import core as jex_core

from horovod_tpu.parallel import moe

T, M = 48, 40


def _case(k, dtype, seed=0):
    """Sorted rows ``[T * k, M]`` whose last quarter is a dead zero tail
    (the rows behind a held share's groups), a random permutation and its
    inverse, float32 weights, a cotangent in ``dtype``."""
    rng = np.random.RandomState(seed)
    n = T * k
    rows = rng.randn(n, M).astype(np.float32)
    rows[n - n // 4:] = 0.0
    order = rng.permutation(n).astype(np.int32)
    weights = rng.rand(T, k).astype(np.float32)
    g = rng.randn(T, M).astype(np.float32)
    return (jnp.asarray(rows, dtype), jnp.asarray(weights),
            jnp.asarray(order), jnp.asarray(np.argsort(order), jnp.int32),
            jnp.asarray(g, dtype))


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
        lambda r, w: moe._combine(r, w, order, inverse, None, rows.dtype),
        rows, weights)
    assert got.dtype == rows.dtype and got.shape == (T, M)
    # float32: the k additions' order is the compiler's; bfloat16: that
    # last float32 bit can move the one rounding of the output
    _close(got, want, 1e-5 if dtype == jnp.float32 else 2 ** -8)
    (d_rows, d_weights), (want_rows, want_weights) = got_vjp(g), want_vjp(g)
    assert d_rows.dtype == rows.dtype and d_weights.dtype == weights.dtype
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
    rows, vjp = jax.vjp(lambda x: moe._dispatch(x, order, inverse, k), x)
    np.testing.assert_array_equal(np.asarray(rows, np.float32),
                                  np.asarray(x[order // k], np.float32))
    want = g[inverse].reshape(T, k, M).astype(jnp.float32).sum(axis=1)
    (got,) = vjp(g)
    assert got.dtype == x.dtype
    _close(got, want.astype(dtype), 1e-5 if dtype == jnp.float32 else 2 ** -8)
    # and the sum is the gather's transpose
    (scattered,) = jax.vjp(lambda x: x[order // k], x.astype(jnp.float32)
                           )[1](g.astype(jnp.float32))
    _close(got, scattered, 1e-5 if dtype == jnp.float32 else 2 ** -7)


def _sub_jaxprs(value):
    if isinstance(value, jex_core.ClosedJaxpr):
        yield value.jaxpr
    elif isinstance(value, jex_core.Jaxpr):
        yield value
    elif isinstance(value, (tuple, list)):
        for item in value:
            yield from _sub_jaxprs(item)


def _all_jaxprs(jaxpr):
    yield jaxpr
    for eqn in jaxpr.eqns:
        for value in eqn.params.values():
            for sub in _sub_jaxprs(value):
                yield from _all_jaxprs(sub)


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

    for jaxpr in _all_jaxprs(top):
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
