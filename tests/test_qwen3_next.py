"""A gated delta rule with one decay a head on keys shared by two value heads
three layers in four, grouped-query softmax attention gated a channel the
fourth, zero-centred norm weights, and after every mixer an expert layer with
a gated shared expert of which one chip holds a share (Qwen3-Next: ISSUE 70),
in float32 at the benchmark configuration's ``tiny`` sizes (64 positions in
eight chunks of 8, so that seven chunks start from a carried state; 2 key
heads read by 4 value heads of 16 with 4 taps; 4 / 2 attention heads of 16
with 4 rotated channels; 16 experts top-2 of which a share holds 2), against
the plain reference ``benchmarks/chip/reference/qwen3_next.py``, which
computes the delta rule as the recurrence over positions, on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums (1e-7
to 1e-5); 1e-4 is far below what a decay in the wrong place, a gate of the
wrong form, a norm weight that is not zero-centred, a rotation over the whole
head or keys read by the wrong value heads does (``tests/test_qwen3_next_faults.py``, a file of its own so that a second
worker compiles the faulty programs).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, rel as _rel
from horovod_tpu.models import delta
from horovod_tpu.models import transformer as t
from horovod_tpu.parallel import build_mesh

ARCH = arch.get("qwen3_next")
adapter, reference = ARCH.adapter, ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0, what
    assert _rel(got[what], want[what]) < TOL, what


def test_the_lower_precision_control_is_bfloat16_to_the_loss():
    """What the cell's loss bound is held against on the chip
    (``tools/qwen3_next_precision.py``): the reference on bfloat16
    parameters computes in bfloat16 through the last block, the head and
    the softmax statistics (a float32 rotary table once lifted everything
    after the attention block, and the control read like the reference),
    and its loss is then outside the bound."""
    params, batch, want = ARCH.kept()
    low = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params)
    loss, grads = reference.loss_and_grads(low, LEAVES, batch, SIZES)
    assert {x.dtype for x in [loss, *grads.values()]} == {
        jnp.dtype(jnp.bfloat16)}
    assert _rel(loss.astype(jnp.float32), want["loss"]) \
        > 3 * reference.TOLERANCE["loss_rel"]


def test_every_leaf_is_compared():
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), CFG))
    assert len(LEAVES) == len(jax.tree_util.tree_leaves(shapes)) == 27
    assert set(shapes["layers"]) == {"delta", "attention_gated_channel",
                                     "experts"}
    assert shapes["layers"]["delta"]["w_in"].shape == (1, 3, 64, 2 * 32
                                                       + 2 * 64)
    assert shapes["layers"]["delta"]["conv"].shape == (1, 3, 4, 2 * 32 + 64)
    assert shapes["layers"]["attention_gated_channel"]["wq"].shape == (
        1, 1, 64, 2 * 64)
    assert shapes["layers"]["experts"]["ws_gate"].shape == (1, 4, 64, 1)


def test_the_step_reports_the_decay_beside_the_experts():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows",
                        "delta_min_log_decay"}
    assert float(aux["dropped"]) == 0.0 and float(aux["aux_loss"]) == 0.0
    assert np.isfinite(float(aux["delta_min_log_decay"]))
    assert float(aux["delta_min_log_decay"]) < -1.0


def test_the_norm_weights_are_drawn_zero_centred():
    """Zeros wherever the scale is ``1 + w``, ones for the delta block's
    output norm, whose scale is ``w``."""
    tree = t.init_params(np.random.RandomState(0), CFG)
    layers = tree["layers"]
    for leaf in (tree["ln_f"], layers["delta"]["ln1"],
                 layers["experts"]["ln2"],
                 *(layers["attention_gated_channel"][k]
                   for k in ("ln1", "q_norm", "k_norm"))):
        assert np.all(leaf == 0)
    assert np.all(layers["delta"]["norm"] == 1)


def test_the_adapter_draws_init_params_tree_on_the_device():
    """The same tree, shapes and dtypes; every leaf of a sample worth a
    spread within a quarter of ``init_params``' (the decay's rate and bias
    in their ranges), the zero-centred norm weights zeros on both sides,
    the table at ``assumed.embedding_std``."""
    host = t.init_params(np.random.RandomState(0), CFG, 1)
    ours = jax.device_get(jax.jit(ARCH.init_function())(
        jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(ours)
    for (path, h), o in zip(jax.tree_util.tree_leaves_with_path(host),
                            jax.tree_util.tree_leaves(ours)):
        assert h.shape == o.shape and h.dtype == o.dtype, path
        if float(h.std()) == 0:
            np.testing.assert_array_equal(h, o, str(path))
        elif h.size >= 256 and path[0].key != "embed":
            assert abs(float(o.std()) / float(h.std()) - 1) < 0.25, path
    part = ours["layers"]["delta"]
    assert np.all((np.exp(part["a_log"]) >= 1) & (np.exp(part["a_log"]) <= 16))
    dt = np.log1p(np.exp(part["dt_bias"]))          # softplus
    assert np.all((dt > 0.9e-3) & (dt < 1.1e-1))
    assert float(ours["embed"].std()) == pytest.approx(
        ARCH.CONFIG["assumed"]["embedding_std"], rel=0.05)


# -- the chunked scan against the recurrence ------------------------------------

def _scan_inputs(seed=0, b=2, s=64, h=4, hk=2, d=16, rate=0.3):
    rng = np.random.RandomState(seed)

    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(b, s, hk, d)) * d ** -0.5
    k = unit(rng.randn(b, s, hk, d))
    v = rng.randn(b, s, h, d)
    g = -rate * np.exp(rng.randn(b, s, h))
    beta = 1 / (1 + np.exp(-rng.randn(b, s, h)))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


def _both(inputs, chunk, sub=delta.SUB):
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


@pytest.mark.parametrize("chunk, sub, key_heads", [
    (8, 16, 2), (64, 16, 2), (64, 16, 4), (32, 4, 1), (1, 16, 2)])
def test_the_chunked_scan_with_a_decay_a_head_is_the_recurrence(
        chunk, sub, key_heads):
    """At chunks of 1, 8 and the whole sequence; keys a value head, a key
    head for two and one for all four."""
    inputs = _scan_inputs(hk=key_heads)
    (got, got_grads), (want, want_grads) = _both(inputs, chunk, sub)
    assert _rel(got, want) < TOL        # (a sum of signed terms)
    for g, w, name, x in zip(got_grads, want_grads, "q k v g beta".split(),
                             inputs):
        assert g.shape == x.shape, name
        assert _rel(g, w) < TOL, name


def test_a_fast_decay_a_head_stays_finite_and_equal():
    """``g = -3`` a position: ``exp(-Gamma_j)`` alone is ``e^192`` at the end
    of a chunk of 64 and overflows float32; the pairs' factor has no
    positive exponent."""
    q, k, v, g, beta = _scan_inputs()
    g = jnp.full_like(g, -3.0)
    (got, got_grads), (want, want_grads) = _both((q, k, v, g, beta), 64)
    assert np.isfinite(float(got))
    assert all(bool(jnp.all(jnp.isfinite(x))) for x in got_grads)
    assert _rel(got, want) < 1e-5
    for a, w in zip(got_grads, want_grads):
        assert _rel(a, w) < TOL
    o, low = delta.delta_chunked(q, k, v, g, beta, 64)
    assert bool(jnp.all(jnp.isfinite(o))) and float(low) == -192.0


def test_key_heads_that_do_not_divide_the_value_heads_are_refused():
    q, k, v, g, beta = _scan_inputs(h=4, hk=3)
    with pytest.raises(ValueError, match="key heads"):
        delta.delta_chunked(q, k, v, g, beta, 8)
    # and a decay a channel has a key head a value head
    q, k, v, g, beta = _scan_inputs()
    wide = jnp.broadcast_to(g[..., None], g.shape + (16,))
    with pytest.raises(ValueError, match="key heads"):
        delta.delta_chunked(q, k, v, wide, beta, 8)


# -- what is refused, by name ---------------------------------------------------

def test_paths_that_do_not_implement_the_form_refuse_it_by_name():
    alone = t.TransformerConfig(
        layer_pattern=(("delta",), ("dense",)), delta_heads=4,
        delta_key_heads=2, delta_decay="head", delta_head_dim=16,
        n_layers=4, d_model=64, n_heads=4)
    for cfg, meshes in ((CFG, ({"sp": 2}, {"tp": 2})),
                        (alone, ({"sp": 2}, {"tp": 2}, {"pp": 2}))):
        for axes in meshes:
            mesh = build_mesh(devices=jax.devices()[:2], **axes)
            with pytest.raises(NotImplementedError, match=r'\("delta",\)'):
                t.param_shardings(cfg, mesh)
    with pytest.raises(ValueError, match="delta_key_heads"):
        dataclasses.replace(alone, delta_key_heads=3)
    with pytest.raises(ValueError, match="delta_key_heads"):
        dataclasses.replace(alone, delta_decay="channel")
    with pytest.raises(ValueError, match="delta_decay"):
        dataclasses.replace(alone, delta_decay="row")
    for field in ({"post_norm": True}, {"qk_norm": True}, {"n_loops": 2}):
        with pytest.raises(NotImplementedError, match="zero_centred_norms"):
            t.TransformerConfig(zero_centred_norms=True, **field)
    with pytest.raises(NotImplementedError,
                       match="zero_centred_norms.*mamba"):
        t.TransformerConfig(
            zero_centred_norms=True, layer_pattern=(("mamba",), ("dense",)),
            ssm_heads=2, n_layers=2)
    with pytest.raises(ValueError, match="channel"):
        t.TransformerConfig(layer_pattern=(
            ("attention", None, True, None, "row"), ("dense",)), n_layers=2)
    with pytest.raises(ValueError, match="qk_norm"):
        t.TransformerConfig(qk_norm=True, layer_pattern=(
            ("attention", None, True, None, "channel"), ("dense",)),
            n_layers=2)


# -- the cells the benchmark has keep their program -------------------------------

#: every accepted configuration's tiny program at the parent commit
#: (e452a37), as tests/test_kimi_linear.py records them (its ten, and its own
#: configuration's since): sha256 (16 hex digits) of the text of
#: ``jax.make_jaxpr`` of its loss's gradient, addresses struck out, and of
#: its tree's shapes. A JAX upgrade that prints a jaxpr differently moves the
#: first of each pair and not the second: record them again from the commit
#: before the upgrade.
PARENT_PROGRAMS = {
    "glm-4.7-flash": ("4b48cf80e570394e", "df3eff602e9b7fc4"),
    "gpt-1.3b-widths": ("8d09444310935e8c", "f756b4a151f15d25"),
    "granite-4.0-h-micro": ("e450fe130a391ab5", "a91a56ea9b269743"),
    "keye-vl-2.0-30b-a3b": ("4c76e28950e8d448", "2d71ac9c8cb44b7e"),
    # the one program PR 71 meant to change (the delta block keeps its heads
    # on the lanes; "caee61d05be8b7da" before), the tree as it was
    "kimi-linear-48b-a3b": ("5c52dba4f9460937", "7be7a7599ccf84b6"),
    "laguna-xs.2": ("bbf4b9c942caf34e", "17eefd1c9df9f2a1"),
    "lfm2-24b-a2b": ("5c2c60f6948d2ef1", "bb85add417c43d56"),
    "nemotron-3-nano-30b-a3b": ("6b9d91906fbdd3c6", "b5efb155c4415a28"),
    "olmoe-1b-7b": ("8a85a574931f0773", "33ae69a8ed6dc084"),
    "ouro-2.6b": ("198f8569c959b0e7", "c1b56a957a2d3cfc"),
    "smallthinker-21b-a3b": ("25a381f3cf477ad8", "aa7813b0c9b8afe3"),
}


def _digest(text: str) -> str:
    import hashlib
    import re
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def test_the_new_configuration_is_the_only_one_without_a_parent():
    assert sorted(arch.configs()) == sorted(
        [*PARENT_PROGRAMS, "qwen3-next-80b-a3b"])


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_an_accepted_configuration_keeps_its_tree_and_jaxpr(name):
    """To the letter: a config that names no decay a head, no shared keys,
    no gate a channel, no gated shared expert and no zero-centred norm takes no
    new branch and has no new leaf (Kimi's with the changed
    ``models/delta.py``, every config's with the changed ``rmsnorm``)."""
    model, config, job = arch.configs()[name]
    cfg = model(config, job)
    assert cfg.delta_decay == "channel" and cfg.delta_key_heads is None \
        and not cfg.zero_centred_norms and not cfg.moe_shared_gate
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    tree = _digest(str(jax.tree_util.tree_map(lambda a: a.shape, shapes)))
    assert (_digest(arch.grad_jaxpr(cfg)), tree) == PARENT_PROGRAMS[name]


# -- the benchmark's own count of the algorithm's work ----------------------------

import chip_door                                          # noqa: E402

chip_door.take("test_qwen3_next", globals())
