"""A gated delta-rule mixer with a decay a channel three layers in four and
latent attention without positions the fourth, one dense layer leading a
stack of expert layers of which one chip holds a share (Kimi-Linear: ISSUE
66), in float32 at the benchmark configuration's ``tiny`` sizes (64 positions
in eight chunks of 8, so that seven chunks start from a carried state; 2 delta
heads of 16 with 4 taps; 4 latent heads of 12 + 4 with values 8; 16 experts
top-2 of which a share holds 2), against the plain reference
``benchmarks/chip/reference/kimi_linear.py``, which computes the delta rule as
the recurrence over positions, on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums (1e-7
to 1e-5); 1e-4 is far below what a decay in the wrong place, a scalar in
place of the vector, a dropped factor or a state that is not carried does
(``test_a_wrong_term_fails``, ``test_a_wrong_reading_of_the_recurrence_fails``).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, rel as _rel
from horovod_tpu.models import delta, latent
from horovod_tpu.models import transformer as t
from horovod_tpu.parallel import build_mesh
from horovod_tpu.parallel.ring_attention import _plain_attention

ARCH = arch.get("kimi_linear")
adapter, reference = ARCH.adapter, ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    if what != "grad:layers.experts.router_bias":   # a buffer: no gradient
        assert np.linalg.norm(np.asarray(want[what])) > 0, what
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_the_decay_beside_the_experts():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows",
                        "delta_min_log_decay"}
    assert float(aux["dropped"]) == 0.0 and float(aux["aux_loss"]) == 0.0
    # the most negative sum of a chunk's log decays, over the leading block
    # too (no expert layer beside it): the seeded weights decay
    assert np.isfinite(float(aux["delta_min_log_decay"]))
    assert float(aux["delta_min_log_decay"]) < -1.0


def test_the_adapter_draws_init_params_tree_on_the_device():
    """The same tree, shapes and dtypes; every leaf of a sample worth a
    spread within a quarter of ``init_params``' (the decay's rate and bias
    in its ranges), the table at ``assumed.embedding_std``."""
    host = t.init_params(np.random.RandomState(0), CFG, 1)
    ours = jax.device_get(jax.jit(ARCH.init_function())(
        jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(ours)
    for (path, h), o in zip(jax.tree_util.tree_leaves_with_path(host),
                            jax.tree_util.tree_leaves(ours)):
        assert h.shape == o.shape and h.dtype == o.dtype, path
        if h.size >= 256 and float(h.std()) > 0 and path[0].key != "embed":
            assert abs(float(o.std()) / float(h.std()) - 1) < 0.25, path
    for part in (ours["lead"]["delta"], ours["layers"]["delta"]):
        assert np.all((np.exp(part["a_log"]) >= 1)
                      & (np.exp(part["a_log"]) <= 16))
        dt = np.log1p(np.exp(part["dt_bias"]))          # softplus
        assert np.all((dt > 0.9e-3) & (dt < 1.1e-1))
    assert float(ours["embed"].std()) == pytest.approx(
        ARCH.CONFIG["assumed"]["embedding_std"], rel=0.05)


# -- the chunked scan against the recurrence ------------------------------------

def _scan_inputs(seed=0, b=2, s=64, h=2, d=16, rate=0.3):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(b, s, h, d)) * d ** -0.5
    k = unit(rng.randn(b, s, h, d))
    v = rng.randn(b, s, h, d)
    g = -rate * np.exp(rng.randn(b, s, h, d))
    beta = 1 / (1 + np.exp(-rng.randn(b, s, h)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _both(inputs, chunk, sub):
    """(the chunked scan's output and gradients, the recurrence's), the
    loss a fixed random projection of the outputs."""
    w = jnp.asarray(np.random.RandomState(9).randn(*inputs[2].shape),
                    jnp.float32)

    def chunked(*x):
        return jnp.sum(delta.delta_chunked(*x, chunk, sub)[0] * w)

    def recurrence(*x):
        return jnp.sum(reference.delta_rule(*x) * w)
    with jax.default_matmul_precision("highest"):
        return tuple(
            (jax.jit(lambda *x, f=f: f(*x))(*inputs),
             jax.jit(jax.grad(f, argnums=(0, 1, 2, 3, 4)))(*inputs))
            for f in (chunked, recurrence))


@pytest.mark.parametrize("chunk, sub", [(1, 16), (8, 16), (64, 16), (16, 4),
                                        (32, 4)])
def test_the_chunked_scan_is_the_recurrence(chunk, sub):
    """At chunks of 1, 8 and the whole sequence; one sub-block, four (two
    levels of the merge) and eight (three)."""
    (got, got_grads), (want, want_grads) = _both(_scan_inputs(), chunk, sub)
    assert _rel(got, want) < 1e-5
    for g, w, name in zip(got_grads, want_grads, "q k v g beta".split()):
        assert _rel(g, w) < TOL, name


def test_a_fast_decay_stays_finite_and_equal():
    """``g = -3`` a position: ``exp(-Gamma_j)`` alone is ``e^192`` at the end
    of a chunk of 64 and overflows float32; the pairs are formed so that
    every exponent is <= 0."""
    q, k, v, g, beta = _scan_inputs()
    g = jnp.full_like(g, -3.0)
    for chunk, sub in ((64, 16), (32, 4), (8, 16)):
        (got, got_grads), (want, want_grads) = _both((q, k, v, g, beta),
                                                     chunk, sub)
        assert np.isfinite(float(got))
        assert all(bool(jnp.all(jnp.isfinite(x))) for x in got_grads)
        assert _rel(got, want) < 1e-5
        for a, w in zip(got_grads, want_grads):
            assert _rel(a, w) < TOL
    o, low = delta.delta_chunked(q, k, v, g, beta, 64)
    assert bool(jnp.all(jnp.isfinite(o))) and float(low) == -192.0


def test_the_inverse_is_forward_substitution_s():
    rng = np.random.RandomState(3)
    a = np.tril(0.3 * rng.randn(5, 32, 32), -1).astype(np.float32)
    want = np.linalg.inv(np.eye(32) + a.astype(np.float64))
    for sub in (32, 16, 8, 4):
        got = delta.unit_lower_inverse(jnp.asarray(a), sub)
        assert _rel(got, want) < 1e-5, sub
    # and its hand-written backward is the inverse's
    w = jnp.asarray(rng.randn(5, 32, 32), jnp.float32)
    got = jax.grad(lambda x: jnp.sum(delta.unit_lower_inverse(x, 8) * w))(
        jnp.asarray(a))
    want = jax.grad(lambda x: jnp.sum(jnp.linalg.inv(
        jnp.eye(32) + jnp.tril(x, -1)) * w))(jnp.asarray(a))
    assert _rel(got, want) < 1e-4


def test_a_chunk_that_does_not_divide_the_sequence_is_refused():
    with pytest.raises(ValueError, match="delta_chunk"):
        delta.delta_chunked(*_scan_inputs(s=24), 16)


# -- a head is a run of lanes ---------------------------------------------------

def _flat_pieces(form):
    """(the block's flat pieces, the same with a head's channels on an axis
    of their own, float32 operands) of a block of 4 value heads of 16, in
    the form with a decay a head on 2 key heads: each a function of arrays
    ``[B, S, heads 16]`` as the projections write them."""
    B, S, H, D = 2, 24, 4, 16
    Hk = 2 if form == "head" else H
    keys, eps = Hk * D, 1e-5
    activation = jax.nn.silu if form == "head" else jax.nn.sigmoid
    rng = np.random.RandomState(5)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    def axis_l2norm(x, heads):
        y = x.reshape(B, S, heads, D)
        y = y * jax.lax.rsqrt(jnp.sum(jnp.square(y), -1, keepdims=True)
                              + delta.L2_EPS)
        return y.reshape(x.shape)

    def cut(qkv):       # q | k of one array, as the form with a decay a head
        return qkv[..., :keys], qkv[..., keys:2 * keys]

    def axis_norm_gate(o, gate, weight):
        o = o.reshape(B, S, H, D)
        y = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                               + eps)
             * weight * activation(gate).reshape(B, S, H, D))
        return y.reshape(B, S, H * D)

    def axis_decay(rate, a_log, dt_bias):
        if form == "head":
            return -(jnp.exp(a_log) * jax.nn.softplus(rate + dt_bias))
        return -(jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (rate + dt_bias).reshape(B, S, H, D))).reshape(rate.shape)
    per = 1 if form == "head" else D
    return {
        "l2norm": (
            lambda qkv: tuple(delta._l2norm(x, Hk) for x in cut(qkv)),
            lambda qkv: tuple(axis_l2norm(x, Hk) for x in cut(qkv)),
            (3.0 * draw(B, S, 2 * keys + H * D),)),
        "norm_gate": (
            lambda o, gate, weight: delta._head_norm(o, weight, H, eps)
            * activation(gate),
            axis_norm_gate,
            (3.0 * draw(B, S, H * D), draw(B, S, H * D),
             1.0 + 0.1 * draw(D))),
        "decay": (
            delta._log_decay, axis_decay,
            (draw(B, S, H * per), draw(H), draw(H * per))),
    }


@pytest.mark.parametrize("piece", ["l2norm", "norm_gate", "decay"])
@pytest.mark.parametrize("form", ["channel", "head"])
def test_a_head_on_the_lanes_is_the_head_on_an_axis(form, piece):
    """The two L2 norms, the output norm times its gate and the log decay,
    every array ``[B, S, heads D]`` with a head's sums products with a 0/1
    matrix, against the head-axis form: values and every operand's gradient,
    float32 (heads of 16: the products are right at any width, the cells'
    128 is only where the lanes' tiles fall; the block with a decay a head
    takes its q and k through the head-axis L2 norm, models/delta.py says
    why, so the flat one on fewer key heads is held here and by Kimi's
    block)."""
    flat, by_axis, operands = _flat_pieces(form)[piece]

    def both(f):
        out, pull = jax.vjp(f, *operands)
        rng = np.random.RandomState(6)
        ct = jax.tree_util.tree_map(
            lambda o: jnp.asarray(rng.randn(*o.shape), jnp.float32), out)
        return jax.tree_util.tree_leaves((out, pull(ct)))
    got, want = both(flat), both(by_axis)
    assert len(got) == len(want) > len(operands)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == jnp.float32
        assert np.linalg.norm(np.asarray(w)) > 0
        assert _rel(g, w) < 1e-6


# -- the padded core ------------------------------------------------------------

def test_the_padded_core_is_the_unpadded_one():
    """Keys of 12 + 4 and values of 8 padded with zero channels to one lane
    tile: scores and outputs are the unpadded XLA core's, gradients too."""
    rng = np.random.RandomState(1)
    q, k = (jnp.asarray(rng.randn(2, 32, 4, 16), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
    w = jnp.asarray(rng.randn(2, 32, 4, 8), jnp.float32)
    scale = 16 ** -0.5
    got = latent.padded_core(q, k, v, scale)
    want = _plain_attention(q, k, v, True, scale)
    assert got.shape == want.shape == (2, 32, 4, 8)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    grads = [jax.grad(lambda *x, f=f: jnp.sum(f(*x) * w), argnums=(0, 1, 2))(
        q, k, v) for f in (lambda *x: latent.padded_core(*x, scale),
                           lambda *x: _plain_attention(*x, True, scale))]
    for a, b in zip(*grads):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


# -- each wrong term fails ------------------------------------------------------

#: the leading delta block with its dense FFN and the latent block with its
#: experts: every wrong term below is in one of them
_CUT = {"num_hidden_layers": 2, "linear_attn_config": {
    **ARCH.CONFIG["linear_attn_config"], "kda_layers": [1],
    "full_attn_layers": [2]}}
SMALL = ARCH.cut(_CUT)


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.sound < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for k, v in SMALL.kept()[2].items()
               if k.startswith("grad:") and "router_bias" not in k)


def test_the_leading_block_s_decay_is_reported():
    """``delta_min_log_decay`` is over every delta block, a leading one
    too, which stacks no expert layer's terms beside it (the cut stack's
    only delta block leads): a faster rate there lowers it."""
    params, batch, _want = SMALL.kept()
    low = jax.jit(lambda p: t.forward_loss_spmd(
        p, batch["tokens"], batch["targets"], SMALL.CFG)[1][
            "delta_min_log_decay"])
    lead = params["lead"]["delta"]
    fast = {**params, "lead": {**params["lead"], "delta": {
        **lead, "a_log": lead["a_log"] + 3.0}}}
    assert float(low(fast)) < 2 * float(low(params)) < -2.0


def _scan_with(**changed):
    """``delta_chunked`` with an input changed before the scan."""
    real = delta.delta_chunked

    def scan(q, k, v, g, beta, chunk, sub=delta.SUB):
        x = {"q": q, "k": k, "v": v, "g": g, "beta": beta}
        x.update({name: change(x[name]) for name, change in changed.items()})
        return real(x["q"], x["k"], x["v"], x["g"], x["beta"], chunk, sub)
    return scan


FAULTS = {
    "a scalar decay a head in place of the vector":
        {"patch": lambda: (delta, "delta_chunked", _scan_with(
            g=lambda g: jnp.broadcast_to(
                jnp.mean(g, axis=-1, keepdims=True), g.shape)))},
    "beta dropped":
        {"patch": lambda: (delta, "delta_chunked", _scan_with(
            beta=jnp.ones_like))},
    "the L2 norm dropped":
        {"patch": lambda: (delta, "_l2norm",
                           lambda x, heads: x.astype(jnp.float32))},
    "silu off the convolution":
        {"patch": lambda: (delta, "_short_conv", lambda x, taps:
                           delta._causal_conv(x, taps, None))},
    "the state not carried across chunks":
        {"patch": lambda: (delta, "_carry", jnp.zeros_like)},
    "the query's scale dropped":
        {"patch": lambda: (delta, "delta_chunked", _scan_with(
            q=lambda q: q * CFG.delta_head_dim ** 0.5))},
    "the latent key rotated": {"cfg": {"latent_rope": True}},
    "the score scale of the position-free part (12^-1/2)":
        {"cfg": {"attention_scale": 12 ** -0.5}},
    "the score scale of the padded head (128^-1/2)":
        {"cfg": {"attention_scale": 128 ** -0.5}},
}


@pytest.mark.parametrize("what", sorted(FAULTS))
def test_a_wrong_term_fails(monkeypatch, what):
    """What TOL must not let through: each moves the loss or a leaf's
    gradient far beyond it."""
    SMALL.kept()        # the reference's side, before anything is patched
    change = FAULTS[what]
    if "patch" in change:
        monkeypatch.setattr(*change["patch"]())
    cfg = dataclasses.replace(SMALL.CFG, **change.get("cfg", {}))
    err = SMALL.error(what, cfg)
    assert err > 20 * TOL, (what, err)


def _decay_after_the_correction(state, inputs):
    q, k, v, g, beta = inputs
    predicted = jnp.einsum("bhd,bhdv->bhv", k, state)
    state = jnp.exp(g)[..., None] * (state + jnp.einsum(
        "bhd,bhv->bhdv", beta[..., None] * k, v - predicted))
    return state, jnp.einsum("bhd,bhdv->bhv", q, state)


def _added_not_corrected(state, inputs):
    """A Mamba-2 head's update with the vector decay: the outer product
    added, nothing taken away."""
    q, k, v, g, beta = inputs
    state = jnp.exp(g)[..., None] * state + jnp.einsum(
        "bhd,bhv->bhdv", beta[..., None] * k, v)
    return state, jnp.einsum("bhd,bhdv->bhv", q, state)


_SOUND_STEP = reference.delta_step


def _output_before_the_update(state, inputs):
    return (_SOUND_STEP(state, inputs)[0],
            jnp.einsum("bhd,bhdv->bhv", inputs[0], state))


WRONG_RECURRENCES = {
    "the decay applied after the correction": _decay_after_the_correction,
    "the outer product added, nothing taken away": _added_not_corrected,
    "the output read before the state's update": _output_before_the_update,
}


@pytest.mark.parametrize("what", sorted(WRONG_RECURRENCES))
def test_a_wrong_reading_of_the_recurrence_fails(monkeypatch, what):
    """A reading the config cannot say, in the plain reference's one step: the
    sound reference, which the program is inside TOL of, is far from it."""
    params, batch, _want = SMALL.kept()
    monkeypatch.setattr(reference, "delta_step", WRONG_RECURRENCES[what])
    got = SMALL.want(params, batch)
    err = SMALL.error(what, got=got)
    assert err > 20 * TOL, (what, err)


def test_values_as_wide_as_keys_are_another_tree():
    """``value_width`` unset reads ``wkvb`` and ``wo`` at the keys' width:
    other shapes, so the configuration's tree does not run under it."""
    wide = dataclasses.replace(SMALL.CFG, value_width=None)
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), wide))
    ours = SMALL.kept()[0]["layers"]["latent"]
    assert shapes["layers"]["latent"]["wkvb"].shape[-1] == 4 * (12 + 16)
    assert ours["wkvb"].shape[-1] == 4 * (12 + 8)
    assert shapes["layers"]["latent"]["wo"].shape[-2] == 4 * 16
    assert ours["wo"].shape[-2] == 4 * 8
    with pytest.raises(Exception):
        SMALL.error("values read at the keys' width", wide)


def test_a_query_latent_is_another_tree_and_glm_s_leaves_stand():
    with_latent = dataclasses.replace(SMALL.CFG, q_latent=24)
    names = [leaf.name for leaf in latent._leaves(with_latent)]
    assert names == ["ln1", "wqa", "q_latent_norm", "wqb", "wkva",
                     "kv_latent_norm", "wkvb", "wo"]
    assert [leaf.name for leaf in latent._leaves(SMALL.CFG)] == [
        "ln1", "wq", "wkva", "kv_latent_norm", "wkvb", "wo"]


def test_a_stack_of_delta_and_dense_blocks_alone_trains():
    """No expert layer anywhere: the step's terms are the zero auxiliary
    loss and the mixer's own."""
    cfg = t.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=4, d_ff=64,
        max_seq=32, layer_pattern=(("delta",), ("dense",)), delta_heads=2,
        delta_head_dim=8, delta_chunk=8, ffn_gated=True, dtype=jnp.float32)
    params = jax.tree_util.tree_map(
        jnp.asarray, t.init_params(np.random.RandomState(0), cfg))
    tokens = jnp.asarray(np.random.RandomState(1).randint(0, 64, (2, 16)))
    (loss, aux), grads = jax.jit(jax.value_and_grad(
        lambda p: t.forward_loss_spmd(p, tokens, tokens, cfg),
        has_aux=True))(params)
    assert set(aux) == {"aux_loss", "delta_min_log_decay"}
    assert np.isfinite(float(loss)) and float(aux["aux_loss"]) == 0.0
    assert float(aux["delta_min_log_decay"]) < 0
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree_util.tree_leaves(grads))
    assert float(jnp.linalg.norm(grads["layers"]["delta"]["wf_down"])) > 0


# -- what is refused, by name ---------------------------------------------------

def test_paths_that_do_not_implement_the_mixer_refuse_it_by_name():
    for axes in ({"sp": 2}, {"tp": 2}):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        with pytest.raises(NotImplementedError,
                           match="delta|lead_pattern|latent"):
            t.param_shardings(CFG, mesh)
    alone = t.TransformerConfig(
        layer_pattern=(("delta",), ("dense",)), delta_heads=2,
        delta_head_dim=16, n_layers=4, d_model=64, n_heads=4)
    for axes in ({"sp": 2}, {"tp": 2}, {"pp": 2}):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        with pytest.raises(NotImplementedError, match=r'\("delta",\)'):
            t.param_shardings(alone, mesh)
    with pytest.raises(NotImplementedError, match="n_loops"):
        dataclasses.replace(alone, n_loops=2)
    with pytest.raises(ValueError, match="delta_heads"):
        dataclasses.replace(alone, delta_heads=0)
    nope = t.TransformerConfig(
        layer_pattern=(("latent",), ("dense",)), kv_latent=16, rope_width=4,
        latent_rope=False, value_width=8, n_layers=4, d_model=64, n_heads=4)
    for axes in ({"sp": 2}, {"tp": 2}, {"pp": 2}):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        with pytest.raises(NotImplementedError, match=r'\("latent",\)'):
            t.param_shardings(nope, mesh)
    with pytest.raises(ValueError, match="rope_width"):
        dataclasses.replace(nope, rope_width=0)


# -- the cells the benchmark has keep their program -------------------------------

#: every accepted configuration's tiny program at the parent commit
#: (4e909a0), as tests/test_keye_vl2.py records them (its nine, and its own
#: configuration's since): sha256 (16 hex digits) of the text of
#: ``jax.make_jaxpr`` of its loss's gradient, addresses struck out, and of
#: its tree's shapes. A JAX upgrade that prints a jaxpr differently moves the
#: first of each pair and not the second: record them again from the commit
#: before the upgrade.
PARENT_PROGRAMS = {
    "gpt-1.3b-widths": ("8d09444310935e8c", "f756b4a151f15d25"),
    "olmoe-1b-7b": ("8a85a574931f0773", "33ae69a8ed6dc084"),
    "ouro-2.6b": ("198f8569c959b0e7", "c1b56a957a2d3cfc"),
    "smallthinker-21b-a3b": ("25a381f3cf477ad8", "aa7813b0c9b8afe3"),
    "nemotron-3-nano-30b-a3b": ("6b9d91906fbdd3c6", "b5efb155c4415a28"),
    "glm-4.7-flash": ("4b48cf80e570394e", "df3eff602e9b7fc4"),
    "granite-4.0-h-micro": ("e450fe130a391ab5", "a91a56ea9b269743"),
    "laguna-xs.2": ("bbf4b9c942caf34e", "17eefd1c9df9f2a1"),
    "lfm2-24b-a2b": ("5c2c60f6948d2ef1", "bb85add417c43d56"),
    "keye-vl-2.0-30b-a3b": ("4c76e28950e8d448", "2d71ac9c8cb44b7e"),
}


def _digest(text: str) -> str:
    import hashlib
    import re
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def test_the_new_configuration_is_the_only_one_without_a_parent():
    """(Of the configurations there were at PR 66: a later one's file holds
    this file's configuration to its own parent, tests/test_qwen3_next.py.)"""
    assert set(PARENT_PROGRAMS) | {"kimi-linear-48b-a3b"} <= set(
        arch.configs())


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_configuration_without_a_delta_block_keeps_its_tree_and_jaxpr(name):
    """To the letter: a config that names no delta block, no unrotated latent
    key and no value width takes no new branch and has no new leaf (GLM's
    with the changed ``models/latent.py``)."""
    model, config, job = arch.configs()[name]
    cfg = model(config, job)
    assert cfg.delta_heads == 0 and cfg.latent_rope \
        and cfg.value_width is None
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    tree = _digest(str(jax.tree_util.tree_map(lambda a: a.shape, shapes)))
    assert (_digest(arch.grad_jaxpr(cfg)), tree) == PARENT_PROGRAMS[name]


# -- the benchmark's own count of the algorithm's work ----------------------------

import chip_door                                          # noqa: E402

chip_door.take("test_kimi_linear", globals())
