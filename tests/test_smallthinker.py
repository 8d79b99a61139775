"""Window and full (NoPE) layers in one stack, grouped-query heads at a head
width of their own, a router that reads the block's input, ReLU-gated
experts and one chip's share of the experts (ISSUE 32), in float32 at the
benchmark configuration's ``tiny`` sizes (two periods of [full, window,
window, window], window 32 of 64 positions, 8 query / 2 key-value heads of
16, 8 experts top-2 of which a share holds 2), against the plain reference
``benchmarks/chip/reference/smallthinker.py`` on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums, as
in tests/test_olmoe.py: 1e-4 is a hundredth of what one bfloat16 rounding
in the router or the combine does (``test_a_wrong_term_fails``).
"""

import dataclasses
import functools
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, get_leaves, rel as _rel
from horovod_tpu.models import decode
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params

from horovod_tpu.parallel import build_mesh, moe

ARCH = arch.get("smallthinker")
adapter, reference = ARCH.adapter, ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_params, _batch, _program = ARCH.params, ARCH.batch, ARCH.program


@functools.partial(jax.jit, static_argnums=0)
def _program_logits(cfg, params, tokens):
    return arch.logits(ARCH, params, tokens, cfg)


def _reference_logits(params, tokens, sizes=SIZES):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, sizes)[0]


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("what", ["logits", "loss", "load_balance_loss"]
                         + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_the_rows_it_holds_and_drops_nothing():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    assert float(aux["dropped"]) == 0.0
    # 8 layers x 128 tokens x top-2 assignments, of which 2 of 8 experts
    # are held: a quarter, give or take the router's preferences
    every = CFG.n_layers * 128 * CFG.moe_top_k
    assert 0.1 * every < float(aux["held_rows"]) < 0.4 * every
    # a model that holds every expert reports what it did before
    whole = dataclasses.replace(CFG, expert_share=(0, 1))
    _loss, aux1, _grads = _program(whole, _params(whole), _batch())
    assert "held_rows" not in aux1 and float(aux1["dropped"]) == 0.0


@pytest.mark.parametrize("index", [1, 3])
def test_another_share_of_the_experts_matches_the_reference(index):
    """Share ``index`` of 4: the reference is told the same first expert
    (on the stack of one full and one window layer: the share is the expert
    layer's, whatever the depth)."""
    cfg = dataclasses.replace(SMALL.CFG, expert_share=(index, 4))
    sizes = {**SMALL.SIZES,
             "first_expert": index * SMALL.SIZES["held_experts"]}
    params, batch = SMALL.params(cfg, seed=1), SMALL.batch(seed=1)
    loss, _aux, grads = SMALL.program(cfg, params, batch)
    leaves = {k: SMALL.LEAVES[k] for k in (
        "last_router", "last_experts_down", "window_key")}
    want = SMALL.want(params, batch, sizes, leaves)
    assert _rel(loss, want["loss"]) < TOL
    for k, v in get_leaves(grads, leaves).items():
        assert np.linalg.norm(np.asarray(want[f"grad:{k}"])) > 0, k
        assert _rel(v, want[f"grad:{k}"]) < TOL, k


# -- the share cut: one expert layer -----------------------------------------

def _expert_layer(seed=0, tokens=96):
    """One expert layer's operands at the tiny widths: tokens, the router's
    logits (of something else than the tokens), all 8 experts' weights."""
    rng = np.random.RandomState(seed)
    m, f, e = CFG.d_model, CFG.d_ff, CFG.n_experts
    x = jnp.asarray(rng.randn(tokens, m), jnp.float32)
    router = jnp.asarray(rng.randn(m, e) * 0.5, jnp.float32)
    logits = jnp.asarray(rng.randn(tokens, m), jnp.float32) @ router
    experts = {"we1": jnp.asarray(rng.randn(e, m, f) / 8, jnp.float32),
               "we3": jnp.asarray(rng.randn(e, m, f) / 8, jnp.float32),
               "we2": jnp.asarray(rng.randn(e, f, m) / 8, jnp.float32)}
    return x, router, logits, experts


def _expert_fn(ep, rows, group_sizes):
    h = jax.nn.relu(moe.grouped_matmul(rows, ep["we1"], group_sizes)) \
        * moe.grouped_matmul(rows, ep["we3"], group_sizes)
    return moe.grouped_matmul(h, ep["we2"], group_sizes)


def _share_of(experts, index, of=4):
    held = CFG.n_experts // of
    return {k: v[index * held:(index + 1) * held]
            for k, v in experts.items()}


def _uncut_layer(x, logits, experts):
    """The whole layer by the reference: every expert on every token."""
    sizes = {**SIZES, "first_expert": 0, "held_experts": CFG.n_experts}
    with jax.default_matmul_precision("highest"):
        _probs, _choice, combine = reference.route(logits, sizes)
        return reference.experts(experts, x, combine)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: what every share computes, summed,
    is what the uncut reference gives for the whole layer; and between
    them they hold every assignment once."""
    x, router, logits, experts = _expert_layer()
    parts, held_rows = [], []

    @functools.partial(jax.jit, static_argnums=1)
    def layer(held, share):
        return moe.moe_layer_spmd(
            x, router, _expert_fn, held, axis_name=None, k=CFG.moe_top_k,
            renormalize=True, logits=logits, share=share)
    for i in range(4):
        y, m = layer(_share_of(experts, i), (i, 4))
        assert float(m.dropped) == 0.0
        parts.append(y)
        held_rows.append(float(m.held_rows))
    want = _uncut_layer(x, logits, experts)
    assert _rel(sum(parts), want) < TOL
    assert sum(held_rows) == x.shape[0] * CFG.moe_top_k
    assert all(h > 0 for h in held_rows)
    # no share is the whole: leaving three out is not a rounding error
    assert _rel(parts[0], want) > 0.3
    # and the layer that holds every expert is the uncut layer
    y, m = layer(experts, (0, 1))
    assert _rel(y, want) < TOL and float(m.held_rows) == 2 * x.shape[0]


def test_live_ep4_gives_the_uncut_layer_and_shard_i_is_share_i():
    """On four devices the live ``ep`` axis gives the uncut result, and
    the gradient that reaches shard i's experts is the one the one-chip
    share i computes: the share is the ep path without its exchange."""
    x, router, logits, experts = _expert_layer(seed=1)
    weight = jnp.asarray(np.random.RandomState(2).randn(*x.shape),
                         jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:4], ep=4)

    # the router's logits come from ``x @ router`` here on both sides
    def over_ep(ep_params):
        y, _m = moe.moe_layer(x, router, _expert_fn, ep_params, mesh,
                              k=CFG.moe_top_k, renormalize=True,
                              token_axes=("ep",))
        return jnp.sum(y * weight), y
    (_s, y), grads = jax.jit(jax.value_and_grad(over_ep, has_aux=True))(
        experts)
    assert _rel(y, _uncut_layer(x, x @ router, experts)) < TOL

    for i in range(4):
        def alone(share_params):
            y_i, _m = moe.moe_layer_spmd(
                x, router, _expert_fn, share_params, axis_name=None,
                k=CFG.moe_top_k, renormalize=True, share=(i, 4))
            return jnp.sum(y_i * weight)
        want = jax.jit(jax.grad(alone))(_share_of(experts, i))
        for name, g in _share_of(grads, i).items():
            assert _rel(g, want[name]) < TOL, (i, name)


def test_a_share_on_a_live_ep_axis_is_refused_by_name():
    x, router, logits, experts = _expert_layer()
    mesh = build_mesh(devices=jax.devices()[:2], ep=2)
    from horovod_tpu._compat import shard_map
    from jax.sharding import PartitionSpec as P

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("ep"), P()),
                       out_specs=P("ep"), check_vma=False)
    def run(xl, ep_params):
        return moe.moe_layer_spmd(xl, router, _expert_fn, ep_params, "ep",
                                  k=2, share=(1, 4))[0]
    with pytest.raises(ValueError, match=r"share=\(1, 4\).*'ep'"):
        run(x, _share_of(experts, 0))
    with pytest.raises(ValueError, match="expert_share"):
        t.param_shardings(CFG, mesh)
    with pytest.raises(ValueError, match="hold"):
        moe.moe_layer_spmd(x, router, _expert_fn, experts, axis_name=None,
                           k=2, share=(0, 4))


# -- the layer kinds ------------------------------------------------------------

def _causal(cfg):
    """``cfg`` with every window taken out of its pattern."""
    return dataclasses.replace(cfg, layer_pattern=tuple(
        (None, rope) for _window, rope in cfg.layer_pattern))


def test_a_sequence_inside_the_window_is_causal_a_longer_one_is_not():
    params = _params()
    tokens = _batch()["tokens"]
    short = tokens[:, :32]          # 32 positions: every key is inside
    np.testing.assert_allclose(
        _program_logits(CFG, params, short),
        _program_logits(_causal(CFG), params, short), rtol=1e-5, atol=1e-6)
    banded = _program_logits(CFG, params, tokens)
    assert _rel(banded, _program_logits(_causal(CFG), params, tokens)) > 1e-2
    assert _rel(banded, _reference_logits(params, tokens)) < TOL
    # the first 32 positions of the long sequence are the short one's
    np.testing.assert_allclose(banded[:, :32],
                               _program_logits(CFG, params, short),
                               rtol=1e-5, atol=1e-6)


def test_the_full_layers_ignore_positions():
    """Layer 0 (full, NoPE) gives the same output at any positions; a
    window layer with rope does not (rope is relative: the positions are
    stretched, not shifted)."""
    params = _params()
    p0, p1 = ({k: v[0, i] for k, v in params["layers"].items()}
              for i in (0, 1))
    x = jnp.asarray(np.random.RandomState(3).randn(2, 64, CFG.d_model),
                    jnp.float32)
    here, there = jnp.arange(64), 3 * jnp.arange(64) + 7
    full, window = CFG.layer_pattern[0], CFG.layer_pattern[1]
    np.testing.assert_array_equal(
        t._attention_block(p0, x, here, CFG, full),
        t._attention_block(p0, x, there, CFG, full))
    assert _rel(t._attention_block(p1, x, there, CFG, window),
                t._attention_block(p1, x, here, CFG, window)) > 1e-3


def test_the_router_reads_the_block_s_input():
    """Layer 0's choices depend on the embedding alone: attention's
    weights perturbed, they stay; a router on the normed tokens (the
    default) moves with them."""
    params, tokens = _params(), _batch()["tokens"]
    other = jax.tree_util.tree_map(lambda a: a, params)
    other["layers"] = {**params["layers"],
                       "wo": params["layers"]["wo"] * 3.0 + 0.05}

    @functools.partial(jax.jit, static_argnums=0)
    def choices(cfg, p):
        return t.router_choices(p, tokens, cfg)

    def layer0(cfg, p):
        return np.asarray(choices(cfg, p))[0]
    np.testing.assert_array_equal(layer0(CFG, params), layer0(CFG, other))
    after = dataclasses.replace(CFG, moe_router_input="tokens")
    assert (layer0(after, params) != layer0(after, other)).mean() > 0.05
    # and the reference chooses what the program chooses
    theirs = jax.jit(lambda p, b: reference.losses(p, b, SIZES)[4])(
        params, _batch())
    ours = choices(CFG, params)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))


def _bf16_softmax(logits, k, renormalize):
    probs = jax.nn.softmax(logits.astype(jnp.bfloat16), axis=-1
                           ).astype(jnp.float32)
    weights, experts = jax.lax.top_k(probs, k)
    return probs, weights / jnp.sum(weights, -1, keepdims=True), experts


def _bf16_products(rows, by):
    return rows.astype(jnp.bfloat16) * by.astype(jnp.bfloat16)


#: the full layer without positions and one window layer: each wrong term
#: below is in one of the two
SMALL = ARCH.cut({"num_hidden_layers": 2}, adapter._leaf_paths([None, 32]))


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.CFG.layer_pattern == CFG.layer_pattern[:2]
    assert SMALL.sound < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for v in SMALL.kept()[2].values())


@pytest.mark.parametrize("what, change", [
    ("silu in place of relu", {"cfg": {"moe_activation": "silu"}}),
    ("the router on the normed tokens",
     {"cfg": {"moe_router_input": "tokens"}}),
    ("rope on the full layers", {"cfg": {"layer_pattern": (
        (None, True), (32, True))}}),
    ("top-k weights not renormalised", {"cfg": {"moe_renormalize": False}}),
    ("router softmax in bfloat16", {"patch": (moe, "route", _bf16_softmax)}),
    ("combine in bfloat16", {"patch": (moe, "_products", _bf16_products)}),
])
def test_a_wrong_term_fails(monkeypatch, what, change):
    """What TOL must not let through: each moves the last router's
    gradient far beyond it."""
    if "patch" in change:
        monkeypatch.setattr(*change["patch"])
    cfg = dataclasses.replace(SMALL.CFG, **change.get("cfg", {}))
    err = SMALL.error(what, cfg, only=("grad:last_router",))
    assert err > 20 * TOL, (what, err)


# -- what is refused, by name ---------------------------------------------------

def test_paths_that_do_not_implement_a_field_refuse_it_by_name():
    mesh_sp = build_mesh(devices=jax.devices()[:2], sp=2)
    params, batch = _params(), _batch()
    with pytest.raises(NotImplementedError, match="layer_pattern.*sp"):
        _program(dataclasses.replace(CFG, expert_share=(0, 1)),
                 _params(dataclasses.replace(CFG, expert_share=(0, 1))),
                 batch, {"sp": 2})
    del mesh_sp
    # grouped heads alone on sp
    grouped = dataclasses.replace(_causal(CFG), expert_share=(0, 1),
                                  layer_pattern=((None, True),))
    with pytest.raises(NotImplementedError, match="n_kv_heads"):
        _program(grouped, _params(grouped), batch, {"sp": 2})
    # a pipeline stage that is not whole periods: 8 layers, 4 stages of 2
    with pytest.raises(ValueError, match="layer_pattern.*whole periods"):
        t.param_shardings(CFG, build_mesh(devices=jax.devices()[:4], pp=4))
    t.param_shardings(dataclasses.replace(CFG, expert_share=(0, 1)),
                      build_mesh(devices=jax.devices()[:2], pp=2))
    # tp that does not divide the k/v heads
    with pytest.raises(ValueError, match="n_kv_heads"):
        t.param_shardings(CFG, build_mesh(devices=jax.devices()[:4], tp=4))
    # a pattern that does not divide the layers; unknown words
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(CFG, n_layers=6)
    with pytest.raises(ValueError, match="moe_router_input"):
        dataclasses.replace(CFG, moe_router_input="attention")
    with pytest.raises(ValueError, match="moe_activation"):
        dataclasses.replace(CFG, moe_activation="swish")
    with pytest.raises(ValueError, match="n_kv_heads"):
        dataclasses.replace(CFG, n_kv_heads=3)
    # (the decode paths' refusals: tests/test_architectures.py)
    assert decode.kv_cache_spec(t.TransformerConfig())[0] == 4


def test_a_pipeline_of_whole_periods_runs_the_pattern():
    """Two stages of one period each: the stage's scan goes over the
    pattern as the single scan does (forward; the loss is finite and close
    to one device's, microbatches aside)."""
    cfg = dataclasses.replace(CFG, expert_share=(0, 1), n_microbatches=2)
    batch = _batch(n_seqs=4)
    loss1, _aux, _g = _program(cfg, _params(cfg), batch)
    mesh = build_mesh(devices=jax.devices()[:2], pp=2)
    p = shard_params(t.init_params(np.random.RandomState(0), cfg,
                                   n_stages=2), cfg, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    loss, _aux, _grads = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=2e-2)


# -- the cells the benchmark has keep their program -------------------------------

@pytest.mark.parametrize("name", [
    "gpt-1.3b-widths", "olmoe-1b-7b", "ouro-2.6b"])
def test_the_new_fields_leave_the_other_configurations_jaxpr_alone(
        monkeypatch, name):
    """tests/test_tpu_compile.py's way, at the tiny sizes: the gradient
    function a configuration traces is, to the letter, the one with every
    new field spelled out (the head width as the quotient, as many k/v
    heads as heads, a pattern of one plain layer, the router on the
    tokens, silu, every expert held)."""
    model, config, job = arch.configs()[name]
    other = sys.modules[model.__module__]
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = other.host_batch(config, job, 0, 0, 1)

    def jaxpr():
        cfg = other._model_config(config, job)
        params = jax.eval_shape(
            lambda: t.init_params(np.random.RandomState(0), cfg, 1))
        return cfg, str(jax.make_jaxpr(t.make_grad_fn(cfg, mesh))(
            params, batch["tokens"], batch["targets"]))
    cfg, plain = jaxpr()
    real = t.TransformerConfig
    monkeypatch.setattr(t, "TransformerConfig", lambda **kw: (
        lambda c: dataclasses.replace(
            c, head_width=c.d_model // c.n_heads, n_kv_heads=c.n_heads,
            layer_pattern=((None, True),), moe_router_input="tokens",
            moe_activation="silu", expert_share=(0, 1)))(real(**kw)))
    spelled, spelled_out = jaxpr()
    assert spelled != cfg and spelled.head_width is not None
    assert spelled_out == plain
