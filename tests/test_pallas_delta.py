"""The gated delta rule's Pallas kernels (``ops/pallas_delta.py``:
``hvd_delta_scan``, ``hvd_delta_scan_bwd``) in interpret mode on the CPU, in
float32, against the ``jax.numpy`` form ``models/delta.py:
_delta_chunked_numpy`` and against the recurrence one position at a time
(the benchmark's plain reference, ``reference/kimi_linear.py:delta_rule``),
o and all five cotangents, at heads of one lane tile (the kernels' least)
and shapes that cross a chunk boundary, have two sub-blocks or four a chunk
(one or two levels of the inverse's merge), two heads a grid step, a batch of
two and values wider than keys.

TOL is ``tests/test_pallas_ssm.py``'s: both sides are float32 and differ in
the order of their sums (1e-7 to 1e-5 here); what TOL must not let through
(a decay in bfloat16, a state that is not carried, the decay applied after
the correction, a missing causal mask) reads 4 times it and more, forward
and backward.
"""

import functools
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.models import delta
from horovod_tpu.ops import pallas_delta as pd

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from reference import kimi_linear as reference        # noqa: E402
from reference import qwen3_next as head_reference    # noqa: E402

TOL = 1e-4
#: (B, S, H, D, Dv, chunk, heads a grid step)
SHAPES = {
    "two chunks of four sub-blocks": (1, 128, 1, 128, 128, 64, 1),
    "batch 2, two heads a step, two sub-blocks": (2, 64, 2, 128, 128, 32, 2),
    "values wider than keys, one sub-block": (1, 32, 1, 128, 256, 16, 1),
}
NAMES = ("q", "k", "v", "g", "beta")


def _operands(shape, seed=0, dtype=jnp.float32, rate=0.3):
    B, S, H, D, Dv = shape[:5]
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(B, S, H, D)) * D ** -0.5
    k = unit(rng.randn(B, S, H, D))
    v = rng.randn(B, S, H, Dv)
    g = -rate * np.exp(rng.randn(B, S, H, D))
    beta = 1 / (1 + np.exp(-rng.randn(B, S, H)))
    return (jnp.asarray(q, dtype), jnp.asarray(k, dtype),
            jnp.asarray(v, dtype), jnp.asarray(g, jnp.float32),
            jnp.asarray(beta, jnp.float32))


def _weight(shape, seed=1):
    B, S, H, _, Dv = shape[:5]
    return jnp.asarray(np.random.RandomState(seed).randn(B, S, H, Dv),
                       jnp.float32)


# A form: shape -> (operands -> (o, what the form gives beside o)).

def _kernels(shape):
    """Beside o: every chunk's last ``Gamma``, the kernels' small output."""
    chunk, head_tile = shape[-2:]
    return lambda *v: pd.delta_scan(*v, chunk, delta.SUB, True, head_tile)


def _numpy_form(shape):
    """Beside o: the least of the chunks' last ``Gamma``."""
    return lambda *v: delta._delta_chunked_numpy(*v, shape[-2])


def _recurrence(shape):
    return lambda *v: (reference.delta_rule(
        *(x.astype(jnp.float32) for x in v)), None)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


def _program(form, shape):
    """``(weight, *ops)`` -> o, the five cotangents under
    ``sum(form(shape)(*ops) weight)`` and what the form gives beside o, as
    one jitted program."""
    f = form(shape)

    def run(weight, *v):
        o, pull, beside = jax.vjp(f, *v, has_aux=True)
        return (o,) + pull(weight) + (beside,)
    return jax.jit(run)


#: a form at a shape is traced and compiled once a file (``jax.jit`` keeps a
#: program an operand dtype); operands and weights differ a case
_sound_program = functools.lru_cache(maxsize=None)(_program)


def _both(form, shape, weight, ops):
    return _sound_program(form, shape)(weight, *ops)


def _both_under_a_patch(form, shape, weight, ops):
    """Traced now, from the pieces as they are patched now, and kept by
    nobody: no sound program serves a patched case, nor the reverse."""
    return _program(form, shape)(weight, *ops)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_the_kernels_are_the_numpy_form_and_the_recurrence(shape):
    """o, and the cotangents of q, k, v, g and beta."""
    ops, weight = _operands(shape), _weight(shape)
    got = _both(_kernels, shape, weight, ops)
    want = _both(_numpy_form, shape, weight, ops)
    stepwise = _both(_recurrence, shape, weight, ops)
    assert got[0].dtype == jnp.float32 and got[0].shape == weight.shape
    for name, g, w, r in zip(("o",) + NAMES, got, want, stepwise):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rel(g, w) < TOL, name
        assert _rel(g, r) < TOL, name


def test_one_chunk_is_the_whole_sequence_and_the_state_crosses_chunks():
    shape = (1, 64, 1, 128, 128, 64, 1)
    ops, weight = _operands(shape, seed=2), _weight(shape)
    whole = _both(_kernels, shape, weight, ops)
    halves = _both(_kernels, shape[:5] + (32, 1), weight, ops)
    for name, g, w in zip(("o",) + NAMES, halves, whole):
        assert _rel(g, w) < TOL, name
    a_half = jax.jit(_kernels(shape[:5] + (32, 1)))     # traced once for both
    alone, _ = a_half(*(x[:, 32:] for x in ops))
    assert _rel(alone, whole[0][:, 32:]) > 1e-2
    first, _ = a_half(*(x[:, :32] for x in ops))
    assert _rel(first, whole[0][:, :32]) < TOL


def test_the_chunks_last_sums_are_the_numpy_form_s():
    """``delta_min_log_decay``: the most negative ``Gamma_C`` of any chunk,
    head and channel, from the kernel's small extra output."""
    shape = SHAPES["batch 2, two heads a step, two sub-blocks"]
    ops = _operands(shape, rate=1.5)
    _, got = delta.delta_chunked(*ops, shape[5], interpret=True)
    want = _both(_numpy_form, shape, _weight(shape), ops)[-1]
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(got) < -10


def test_a_fast_decay_stays_finite_and_equal():
    """``g = -3.5`` a position: ``Gamma_C`` is -224 a chunk of 64 (the cell's
    ``delta_min_log_decay`` is -223 to -250) and ``exp(-Gamma_j)`` alone
    would overflow float32; in the kernels as in ``_pairs`` every exponent
    is <= 0."""
    shape = SHAPES["two chunks of four sub-blocks"]
    q, k, v, g, beta = _operands(shape)
    ops, weight = (q, k, v, jnp.full_like(g, -3.5), beta), _weight(shape)
    got = _both(_kernels, shape, weight, ops)
    want = _both(_recurrence, shape, weight, ops)
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got[:6])
    for name, g_, w in zip(("o",) + NAMES, got, want):
        assert _rel(g_, w) < TOL, name
    _, low = delta.delta_chunked(*ops, 64, interpret=True)
    assert float(low) == -224.0


# -- what TOL must not let through -------------------------------------------

def _bf16_decay(log_decay):
    return jnp.exp(log_decay.astype(jnp.bfloat16)).astype(jnp.float32)


def _not_carried(state, whole, own):
    return jnp.zeros_like(own)


def _decay_after_the_correction(state, whole, own):
    return (state + own) * whole


def _no_mask(later, earlier):
    return jnp.ones(jnp.broadcast_shapes(jnp.shape(later),
                                         jnp.shape(earlier)), bool)


def _decays_capped(log_decay):
    """Without the mask a decay's exponent is positive above the diagonal;
    capped so that the wrong sum stays finite."""
    return jnp.exp(jnp.minimum(log_decay, 3.0))


@pytest.mark.parametrize("what, patches", [
    ("the decays made in bfloat16", {"_decay": _bf16_decay}),
    ("a state that is not carried", {"_carry": _not_carried}),
    ("the decay applied after the correction",
     {"_carry": _decay_after_the_correction}),
    ("no causal mask inside a sub-block", {"_reaches": _no_mask,
                                           "_decay": _decays_capped}),
])
def test_a_wrong_piece_fails_on_the_kernels(monkeypatch, what, patches):
    """The forward and, beside it, the gradients (the backward kernel makes
    its decays, mask and carried cotangent from the same three pieces). The
    sound kernels on the same operands are inside TOL."""
    assert max(_errors(_both)) < TOL
    for name, wrong in patches.items():
        monkeypatch.setattr(pd, name, wrong)
    forward, backward = _errors(_both_under_a_patch)
    assert forward > 4 * TOL, (what, forward)
    assert backward > 4 * TOL, (what, backward)


_WRONG_AT = (1, 64, 1, 128, 128, 32, 1)


def _errors(both):
    """(o's, the worst cotangent's) distance from the recurrence's."""
    weight, ops = _weight(_WRONG_AT), _operands(_WRONG_AT, rate=1.0)
    want = _both(_recurrence, _WRONG_AT, weight, ops)
    got = both(_kernels, _WRONG_AT, weight, ops)
    return (_rel(got[0], want[0]),
            max(_rel(g, w) for g, w in zip(got[1:6], want[1:6])))


def test_bfloat16_operands_keep_float32_sums_decays_and_state():
    """The cell's dtypes: o float32, every cotangent in its operand's dtype,
    and both within bfloat16's rounding of the ``jax.numpy`` form at the
    same dtypes (which rounds the same operands in another order)."""
    shape = SHAPES["batch 2, two heads a step, two sub-blocks"]
    ops, weight = _operands(shape, dtype=jnp.bfloat16), _weight(shape)
    got = _both(_kernels, shape, weight, ops)
    want = _both(_numpy_form, shape, weight, ops)
    assert got[0].dtype == jnp.float32
    assert _rel(got[0], want[0]) < 1e-2
    for name, g, w, op in zip(NAMES, got[1:], want[1:], ops):
        assert g.dtype == op.dtype, name
        assert _rel(g, w) < 2e-2, name


# -- a decay a head, keys shared by the value heads (PR 70) -------------------

#: (B, S, H, key heads, D, Dv, chunk, value heads a grid step)
HEAD_SHAPES = {
    "two value heads on one key head a step, four sub-blocks":
        (1, 128, 2, 1, 128, 128, 64, 2),
    "batch 2, a key head a value head, two sub-blocks":
        (2, 64, 2, 2, 128, 128, 32, 2),
    "four value heads on one key head, two a step":
        (1, 64, 4, 1, 128, 128, 32, 2),
    "values wider than keys, one value head a step":
        (1, 32, 2, 1, 128, 256, 16, 1),
    "two key heads' four value heads a step (the cell's tile)":
        (1, 64, 4, 2, 128, 128, 32, None),
}


def _head_operands(shape, seed=0, dtype=jnp.float32, rate=0.3):
    B, S, H, Hk, D, Dv = shape[:6]
    q, k, v, g, beta = _operands((B, S, H, D, Dv), seed, dtype, rate)
    return q[:, :, :Hk], k[:, :, :Hk], v, g[..., 0], beta


def _head_recurrence(shape):
    return lambda *v: (head_reference.delta_rule(
        *(x.astype(jnp.float32) for x in v)), None)


@pytest.mark.parametrize("shape", HEAD_SHAPES.values(),
                         ids=HEAD_SHAPES.keys())
def test_the_kernels_for_a_decay_a_head_are_the_numpy_form_and_the_recurrence(
        shape):
    """o, and the cotangents of q, k (at the key heads: a key head's value
    heads' parts summed in the kernel, or outside it where a step holds a
    part of a group), v, g ``[B, S, H]`` and beta."""
    ops = _head_operands(shape)
    weight = _weight(shape[:3] + shape[4:6])
    got = _both(_kernels, shape, weight, ops)
    want = _both(_numpy_form, shape, weight, ops)
    stepwise = _both(_head_recurrence, shape, weight, ops)
    for name, g, w, r, op in zip(("o",) + NAMES, got, want, stepwise,
                                 (weight,) + ops):
        assert g.dtype == w.dtype and g.shape == w.shape == op.shape, name
        assert _rel(g, w) < TOL, name
        assert _rel(g, r) < TOL, name
    last = got[-1]
    assert last.shape == (shape[0], shape[1] // shape[6], 1, shape[2])
    assert float(jnp.min(last)) == pytest.approx(float(want[-1]), rel=1e-6)


@pytest.mark.parametrize("what, patches", [
    ("the decays made in bfloat16", {"_decay": _bf16_decay}),
    ("the decay applied after the correction",
     {"_carry": _decay_after_the_correction}),
    ("no causal mask on the pairs' factor", {"_reaches": _no_mask,
                                             "_decay": _decays_capped}),
])
def test_a_wrong_piece_fails_on_the_kernels_for_a_decay_a_head(
        monkeypatch, what, patches):
    """The three pieces both forms' bodies are made of, here under the
    bodies for a decay a head, at a fast decay; the sound kernels on the
    same operands are inside TOL."""
    shape = (1, 64, 2, 1, 128, 128, 32, 2)
    ops = _head_operands(shape, rate=1.0)
    weight = _weight(shape[:3] + shape[4:6])
    want = _both(_head_recurrence, shape, weight, ops)

    def errors(both):
        got = both(_kernels, shape, weight, ops)
        return (_rel(got[0], want[0]),
                max(_rel(g, w) for g, w in zip(got[1:6], want[1:6])))
    assert max(errors(_both)) < TOL
    for name, wrong in patches.items():
        monkeypatch.setattr(pd, name, wrong)
    forward, backward = errors(_both_under_a_patch)
    assert forward > 4 * TOL, (what, forward)
    assert backward > 4 * TOL, (what, backward)


def test_a_decay_a_channel_refuses_shared_keys():
    q, k, v, g, beta = _operands((1, 32, 2, 128, 128))
    with pytest.raises(ValueError, match="key heads"):
        pd.delta_scan(q[:, :, :1], k[:, :, :1], v, g, beta, 16,
                      delta.SUB, True, 1)


# -- which form runs -----------------------------------------------------------

CELL = (8192, 32, 128, 128, 64)           # S, H, D, Dv, chunk


def _calls_a_kernel(S, H, D, Dv, chunk):
    q = jax.ShapeDtypeStruct((1, S, H, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, S, H, Dv), jnp.bfloat16)
    g = jax.ShapeDtypeStruct((1, S, H, D), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, S, H), jnp.float32)
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *x: delta.delta_chunked(*x, chunk))(q, q, v, g, beta))


def test_the_kernels_run_on_a_tpu_at_the_cell_s_shape(monkeypatch):
    assert pd.delta_scan_path(*CELL) == "xla"       # the CPU
    assert not _calls_a_kernel(256, 2, 128, 128, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pd.delta_scan_path(*CELL) == "kernels"
    said = pd.describe(*CELL)
    assert pd.FWD_NAME in said and pd.BWD_NAME in said
    assert "128 chunks" in said and "sub-blocks of 16 rows" in said
    assert "heads on the lanes" in said
    assert pd.delta_vmem_bytes(64, 128, 128, pd.delta_head_tile(
        32, 128, 128, 64), 2) <= pd.VMEM_BUDGET
    assert _calls_a_kernel(256, 2, 128, 128, 64)


@pytest.mark.parametrize("what, shape", [
    ("the tiny sizes: heads of 16, chunks of 8", (64, 2, 16, 16, 8)),
    ("heads of 64", (8192, 32, 64, 64, 64)),
    ("values of 96", (8192, 32, 128, 96, 64)),
    ("a chunk of 8 rows", (8192, 32, 128, 128, 8)),
    ("a chunk of one and a half sub-blocks", (8192, 32, 128, 128, 24)),
    ("a sequence of half a chunk more", (8192 + 32, 32, 128, 128, 64)),
])
def test_the_numpy_form_runs_where_the_kernels_do_not_fit(monkeypatch, what,
                                                          shape):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pd.delta_scan_path(*shape) == "xla", what
    if shape[0] % shape[4] == 0:
        assert not _calls_a_kernel(*shape), what
