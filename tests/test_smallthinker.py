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
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.models import _kinds, decode
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel import build_mesh, moe

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from adapters import smallthinker as adapter          # noqa: E402
from reference import smallthinker as reference       # noqa: E402
from trees import get_leaves                           # noqa: E402

TOL = 1e-4


def _tiny(name, traffic):
    with open(os.path.join(_CHIP, "configs", name + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(_CHIP, "workloads", traffic + ".json")) as f:
        job = json.load(f)
    return {**config, **config["tiny"]}, {**job, **job["tiny"]}


CONFIG, JOB = _tiny("smallthinker-21b-a3b", "train.s8192.b1")
SIZES = adapter.shapes(CONFIG, JOB)
CFG = adapter._model_config(CONFIG, JOB)
LEAVES = {
    **adapter._leaf_paths(SIZES["layer_windows"]),
    "full_key": (("layers", "wk"), (0, 4)),
    "window_query": (("layers", "wq"), (0, 5)),
    "first_router": (("layers", "router"), (0, 0)),
    "expert_gate": (("layers", "we1"), (0, 7, 1)),
    "expert_up": (("layers", "we3"), (0, 7, 1)),
    "wo": (("layers", "wo"), (0, 2)),
    "embed": (("embed",), None),
}


def _params(cfg=CFG, seed=0):
    return jax.tree_util.tree_map(
        jnp.asarray, t.init_params(np.random.RandomState(seed), cfg, 1))


def _batch(n_seqs=2, seed=0, config=CONFIG, job=JOB):
    return jax.tree_util.tree_map(
        jnp.asarray, adapter.host_batch(config, job, seed, 0, n_seqs))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    # (experts no token chose have a gradient of zeros on both sides)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


def _program(cfg, params, batch, mesh_axes=None):
    axes = mesh_axes or {"dp": 1}
    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(devices=jax.devices()[:n], **axes)
    p = shard_params(params, cfg, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    loss, aux, grads = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
    return loss + aux["aux_loss"], aux, grads


def _program_logits(cfg, params, tokens):
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, _aux = t._run_layers(params["layers"], x,
                            jnp.arange(tokens.shape[1]), cfg)
    return _kinds.rmsnorm(x, params["ln_f"], cfg.norm_eps) @ params["lm_head"]


def _reference_logits(params, tokens, sizes=SIZES):
    with jax.default_matmul_precision("highest"):
        return reference.forward(params, tokens, sizes)[0]


def test_the_tiny_preset_is_the_one_the_issue_asks_for():
    assert CFG.dtype == jnp.float32 and CFG.n_layers == 8
    assert CFG.layer_pattern == ((None, False), (32, True), (32, True),
                                 (32, True))
    assert (CFG.n_heads, CFG.kv_heads, CFG.head_dim) == (8, 2, 16)
    assert CFG.n_heads * CFG.head_dim != CFG.d_model
    assert (CFG.n_experts, CFG.moe_top_k, CFG.held_experts,
            CFG.expert_share) == (8, 2, 2, (0, 4))
    assert JOB["seq_len"] == 64 > 32


# -- the program against the reference ---------------------------------------

@pytest.fixture(scope="module")
def both_sides():
    params, batch = _params(), _batch()
    loss, aux, grads = _program(CFG, params, batch)
    got = {"loss": loss, "load_balance_loss": aux["load_balance_loss"],
           "logits": _program_logits(CFG, params, batch["tokens"]),
           **{f"grad:{k}": v for k, v in get_leaves(grads, LEAVES).items()}}
    with jax.default_matmul_precision("highest"):
        total, _xent, balance, _z, _c = reference.losses(params, batch,
                                                         SIZES)
    _loss, want_grads = reference.loss_and_grads(params, LEAVES, batch, SIZES)
    want = {"loss": total, "load_balance_loss": balance,
            "logits": _reference_logits(params, batch["tokens"]),
            **{f"grad:{k}": v for k, v in want_grads.items()}}
    return got, want, aux


@pytest.mark.parametrize("what", ["logits", "loss", "load_balance_loss"]
                         + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(both_sides, what):
    got, want, _aux = both_sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_the_rows_it_holds_and_drops_nothing(both_sides):
    _got, _want, aux = both_sides
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
    """Share ``index`` of 4: the reference is told the same first expert."""
    cfg = dataclasses.replace(CFG, expert_share=(index, 4))
    sizes = {**SIZES, "first_expert": index * SIZES["held_experts"]}
    params, batch = _params(cfg, seed=1), _batch(seed=1)
    loss, _aux, grads = _program(cfg, params, batch)
    leaves = {k: LEAVES[k] for k in ("last_router", "last_experts_down",
                                     "window_key")}
    want_loss, want = reference.loss_and_grads(params, leaves, batch, sizes)
    assert _rel(loss, want_loss) < TOL
    for k, v in get_leaves(grads, leaves).items():
        assert _rel(v, want[k]) < TOL, k


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
    for i in range(4):
        y, m = moe.moe_layer_spmd(
            x, router, _expert_fn, _share_of(experts, i), axis_name=None,
            k=CFG.moe_top_k, renormalize=True, logits=logits, share=(i, 4))
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
    y, m = moe.moe_layer_spmd(x, router, _expert_fn, experts, axis_name=None,
                              k=CFG.moe_top_k, renormalize=True,
                              logits=logits)
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
    (_s, y), grads = jax.value_and_grad(over_ep, has_aux=True)(experts)
    assert _rel(y, _uncut_layer(x, x @ router, experts)) < TOL

    for i in range(4):
        def alone(share_params):
            y_i, _m = moe.moe_layer_spmd(
                x, router, _expert_fn, share_params, axis_name=None,
                k=CFG.moe_top_k, renormalize=True, share=(i, 4))
            return jnp.sum(y_i * weight)
        want = jax.grad(alone)(_share_of(experts, i))
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

    def layer0(cfg, p):
        return np.asarray(t.router_choices(p, tokens, cfg))[0]
    np.testing.assert_array_equal(layer0(CFG, params), layer0(CFG, other))
    after = dataclasses.replace(CFG, moe_router_input="tokens")
    assert (layer0(after, params) != layer0(after, other)).mean() > 0.05
    # and the reference chooses what the program chooses
    theirs = reference.losses(params, _batch(), SIZES)[4]
    ours = t.router_choices(params, tokens, CFG)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))


def _bf16_softmax(logits, k, renormalize):
    probs = jax.nn.softmax(logits.astype(jnp.bfloat16), axis=-1
                           ).astype(jnp.float32)
    weights, experts = jax.lax.top_k(probs, k)
    return probs, weights / jnp.sum(weights, -1, keepdims=True), experts


def _bf16_products(rows, by):
    return rows.astype(jnp.bfloat16) * by.astype(jnp.bfloat16)


@pytest.mark.parametrize("what, change", [
    ("silu in place of relu", {"cfg": {"moe_activation": "silu"}}),
    ("the router on the normed tokens",
     {"cfg": {"moe_router_input": "tokens"}}),
    ("rope on the full layers", {"cfg": {"layer_pattern": (
        (None, True), (32, True), (32, True), (32, True))}}),
    ("top-k weights not renormalised", {"cfg": {"moe_renormalize": False}}),
    ("router softmax in bfloat16", {"patch": (moe, "route", _bf16_softmax)}),
    ("combine in bfloat16", {"patch": (moe, "_products", _bf16_products)}),
])
def test_a_wrong_term_fails(monkeypatch, what, change):
    """What TOL must not let through: each moves the last router's
    gradient far beyond it."""
    params, batch = _params(), _batch()
    leaf = {"last_router": LEAVES["last_router"]}
    _want_loss, want = reference.loss_and_grads(params, leaf, batch, SIZES)
    if "patch" in change:
        monkeypatch.setattr(*change["patch"])
    cfg = dataclasses.replace(CFG, **change.get("cfg", {}))
    _loss, _aux, grads = _program(cfg, params, batch)
    err = _rel(get_leaves(grads, leaf)["last_router"], want["last_router"])
    assert err > 20 * TOL, (what, err)


# -- the flash kernels with a band and grouped heads (interpret mode) ----------

def _qkv(S, H, Hkv, D=128, B=1, seed=0):
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
    q, k, v, w = _qkv(512, 4, 2)
    with jax.default_matmul_precision("highest"):
        want = pa._banded_attention(q, k, v, window)
        want_grads = jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, window) * w), (0, 1, 2))(q, k, v)
        o, lse = pa.flash_attention_with_lse(
            q, k, v, True, None, tile, tile, True, window)
        got_grads = pa.flash_backward(
            q, k, v, o, lse, w, jnp.zeros_like(lse), True, 128 ** -0.5,
            pa.BwdBlocks(tile, tile, 512), True, window)
    assert _rel(o, want) < 1e-5
    for name, g, r in zip(("dq", "dk", "dv"), got_grads, want_grads):
        assert g.shape == r.shape and _rel(g, r) < 1e-5, name


def test_flash_backward_in_q_ranges_with_a_band_and_a_group():
    """The q rows in two ranges and a tile that is not square: each range
    clamps its own q tiles to the band."""
    q, k, v, w = _qkv(512, 2, 1, seed=1)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda *a: jnp.sum(
            pa._banded_attention(*a, 192) * w), (0, 1, 2))(q, k, v)
        o, lse = pa.flash_attention_with_lse(q, k, v, True, None, 256, 128,
                                             True, 192)
        got = pa.flash_backward(q, k, v, o, lse, w, jnp.zeros_like(lse),
                                True, 128 ** -0.5,
                                pa.BwdBlocks(128, 256, 256), True, 192)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        assert _rel(g, r) < 1e-5, name


def test_attend_s_xla_path_is_the_same_function():
    """Off the TPU ``attend`` takes the XLA form: a window that covers the
    sequence and no group is plain causal attention."""
    from horovod_tpu.parallel.ring_attention import _plain_attention
    q, k, v, _w = _qkv(128, 4, 4, D=16)
    np.testing.assert_allclose(pa.attend(q, k, v, window=128),
                               _plain_attention(q, k, v), rtol=1e-5,
                               atol=1e-6)
    assert _rel(pa.attend(q, k, v, window=32), _plain_attention(q, k, v)) \
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
    # the decode paths
    for field, cfg in [
            ("layer_pattern", t.TransformerConfig(layer_pattern=(
                (None, True), (64, True)))),
            ("n_kv_heads", t.TransformerConfig(n_kv_heads=2)),
            ("moe_router_input", t.TransformerConfig(
                moe_router_input="block_input")),
            ("expert_share", t.TransformerConfig(n_experts=8,
                                                 expert_share=(1, 4)))]:
        with pytest.raises(NotImplementedError, match=field):
            decode.kv_cache_spec(cfg)
        with pytest.raises(NotImplementedError, match=field):
            decode.decode_step_paged(params, None, None, None, None, None,
                                     None, cfg)
        with pytest.raises(NotImplementedError, match=field):
            decode.prefill_chunk_paged(params, None, None, None, None, None,
                                       None, cfg)
        with pytest.raises(NotImplementedError, match=field):
            decode.reference_greedy_decode(params, cfg, [1, 2], 1)
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

@pytest.mark.parametrize("name, traffic, module", [
    ("gpt-1.3b-widths", "train.s2048.b2", "flagship"),
    ("olmoe-1b-7b", "train.s4096.b2", "olmoe"),
    ("ouro-2.6b", "train.s4096.b1", "ouro"),
])
def test_the_new_fields_leave_the_other_configurations_jaxpr_alone(
        monkeypatch, name, traffic, module):
    """tests/test_tpu_compile.py's way, at the tiny sizes: the gradient
    function a configuration traces is, to the letter, the one with every
    new field spelled out (the head width as the quotient, as many k/v
    heads as heads, a pattern of one plain layer, the router on the
    tokens, silu, every expert held)."""
    import importlib
    other = importlib.import_module(f"adapters.{module}")
    config, job = _tiny(name, traffic)
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
