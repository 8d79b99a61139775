"""Gated short-convolution mixers beside grouped-query attention with an
RMSNorm a head, a leading dense layer, sigmoid-routed experts with no shared
one and a tied head, as chip 0 of an expert-parallel group (ISSUE 55,
LFM2-24B-A2B), in float32 at the benchmark configuration's ``tiny`` sizes
(the leading layer and two periods of [attention, conv, conv, conv]; 4 query
heads of 16 on 2 key/value heads; 3 taps under 64 positions; 16 experts
top-4 of which a share holds 2), against the plain reference
``benchmarks/chip/reference/lfm2_moe.py`` on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums (they
read 7e-7 to 2e-6): 1e-4 is far below what the least of the wrong readings
does (``test_a_wrong_reading_of_the_equations_fails``).
"""

import dataclasses
import hashlib
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
import chip_door
from arch import TOL, rel as _rel
from horovod_tpu.models import short_conv
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.parallel import build_mesh
from horovod_tpu.profiling import scopes
from reference.smallthinker import _rms_norm             # noqa: E402

ARCH = arch.get("lfm2_moe")
adapter, reference = ARCH.adapter, ARCH.reference
CONFIG, SIZES, CFG, LEAVES = ARCH.CONFIG, ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_cell, _params, _batch = ARCH.cell, ARCH.params, ARCH.batch


def test_the_cell_keeps_every_published_width():
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    assert cfg.layer_pattern == (("attention", None, True), ("experts",)) + (
        ("conv",), ("experts",)) * 3
    assert cfg.lead_pattern == (("conv",), ("dense",)) and cfg.n_layers == 8
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.dense_ff, cfg.d_ff, cfg.conv_taps) == (
                2048, 32, 8, 64, 11776, 1536, 3)
    assert (cfg.moe_top_k, cfg.n_experts, cfg.held_experts,
            cfg.moe_routed_scale, cfg.norm_eps, cfg.rope_theta,
            cfg.vocab_size, cfg.expert_share, cfg.qk_norm,
            cfg.tie_embeddings, cfg.remat) == (
                4, 64, 8, 1.0, 1e-5, 1e6, 8192, (0, 8), "head", True, None)
    assert (job["seq_len"], job["batch_per_chip"]) == (8192, 2)
    # every number of the catalog row's config, under its own key, but the
    # five the cut changes
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: config[k] for k in published} == published
    assert set(config["reduced"]) == set(config["reduced_from"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"}
    assert {k: config["reduced_from"][k] for k in (
        "num_hidden_layers", "num_dense_layers", "num_experts",
        "vocab_size")} == {"num_hidden_layers": 40, "num_dense_layers": 2,
                           "num_experts": 64, "vocab_size": 65536}
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 8192)
    # published layer 0, then one whole period: layers 2-5
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert "head_dim" not in config     # a head is hidden / heads
    sizes = adapter.shapes(config, job)
    assert (sizes["head_dim"], sizes["kv_heads"], sizes["held_experts"],
            sizes["first_expert"], sizes["d_expert"], sizes["dense_ff"],
            sizes["routed_layers"], sizes["layer_windows"]) == (
                64, 8, 8, 0, 1536, 11776, 4, [None])
    for reading in ("head", "conv_mixer", "qk_norm", "router",
                    "rope_layout"):
        assert "no network here" in config["assumed"][reading], reading
    shapes, count = arch.drawn_shapes(adapter, cfg, config), arch.count
    assert count(shapes) == 469_285_248     # the deployment's 469.3 M
    assert round(count(shapes["lead"]["conv"]) / 1e6, 2) == 16.79
    assert round(count(shapes["lead"]["dense"]) / 1e6, 2) == 72.35
    assert round(count(shapes["layers"]["attention"]) / 1e6, 2) == 10.49
    assert round(count(shapes["layers"]["experts"]) / 4e6, 2) == 75.63
    assert round(count(shapes["embed"]) / 1e6, 2) == 16.78
    # the adapter's tree is init_params' tree
    small = dataclasses.replace(cfg, vocab_size=8, d_model=16, dense_ff=8,
                                d_ff=8, head_width=8)
    arch.assert_the_adapter_s_tree_is_init_params(adapter, small, config)


def test_the_step_s_required_flops_by_hand():
    """3 x 405.8 M = 1.217 G a trained token, 19.95 TFLOP a step of 16 384
    tokens: the four conv mixers 33 %, the leading dense FFN 36, the
    attention block's projections 5 and its scores 8.5, the routers 0.3, the
    held routed experts 9, the sliced tied head 8."""
    config, job = _cell(tiny=False)
    conv = 4 * (2 * 2048 * 6144 + 2 * 2048 * 2048)
    proj = 2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512
    scores = 2 * 2 * 32 * 64 * 8193 / 2
    dense = 3 * 2 * 2048 * 11776
    routers = 4 * 2 * 2048 * 64
    routed = 4 * 4 * 8 / 64 * 3 * 2 * 2048 * 1536
    head = 2 * 2048 * 8192
    forward = conv + proj + scores + dense + routers + routed + head
    got = adapter.flops_per_token(config, job)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert forward == pytest.approx(405.8e6, rel=5e-4)
    assert 16384 * got == pytest.approx(19.95e12, rel=1e-3)
    assert adapter.tokens_per_step(job, 1) == 16384
    for part, share in ((conv, 0.33), (dense, 0.36), (proj, 0.05),
                        (scores, 0.083), (routers, 0.003), (routed, 0.093),
                        (head, 0.083)):
        assert part / forward == pytest.approx(share, abs=0.005)


def test_the_kernels_least_work_by_hand():
    """The grouped matmuls' count is the one a metric of this cell reads.
    The other three kernels' counts are taken through the dense hybrid
    cell's metrics, which list this cell too since PR 63: they take generic
    ``shapes()`` keys only and count this cell's calls right."""
    gmm = chip_door.roofline("lfm2-24b-a2b.s8192", "hvd_moe_gmm")
    fwd, bwd, xent = (
        chip_door.roofline("granite-4.0-h-micro.s4096", kernel)
        for kernel in ("hvd_flash_attention", "hvd_flash_bwd",
                       "hvd_fused_xent"))
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    rows = 2 * 8192 * 4 * 8 / 64
    assert rows == 8192 == 8 * 1024     # 1024 rows a held expert
    need = gmm(sizes)
    assert need["flops"] == 4 * 9 * 2 * rows * 2048 * 1536
    assert need["bytes"] == 4 * 9 * 2 * (rows * (2048 + 1536)
                                         + 8 * 2048 * 1536)
    one = 2 * 2 * 2 * 32 * 64 * (8192 * 8193 / 2)   # batch 2, one block
    need = fwd(sizes)
    assert need["flops"] == one
    assert need["bytes"] == 2 * 2 * 8192 * 40 * 64 * 2 + 2 * 32 * 8192 * 4
    need = bwd(sizes)
    assert need["flops"] == 2.5 * one
    assert need["bytes"] == (4 * 2 * 8192 * 40 * 64 * 2
                             + 2 * 2 * 32 * 8192 * 4)
    assert xent(sizes)["bytes"] == 2 * 16384 * 8192 * 2 + 12 * 16384


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0
    assert _rel(got[what], want[what]) < TOL, what


def test_every_leaf_but_the_expert_bias_has_a_gradient():
    _got, _want, aux, grads = ARCH.sides
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        bias = path[-1].key == "router_bias"
        assert bool(np.any(np.asarray(g) != 0)) != bias, path
    # every block's taps, not the stack's sum
    for part, blocks in (("lead", 1), ("layers", 6)):
        g = np.asarray(grads[part]["conv"]["conv_w"]).reshape(-1, 3, 32)
        assert g.shape[0] == blocks
        assert np.all(np.linalg.norm(g, axis=(1, 2)) > 0), part
    assert float(aux["dropped"]) == 0.0
    assert 0 < float(aux["held_rows"]) < 8 * 2 * 64 * 4
    assert float(aux["max_expert_load"]) >= 1.0


# -- each wrong reading of the equations fails --------------------------------
# A block of the program against the reference's block, on the block's own
# seeded leaves and input: the output and the gradient of every leaf and of
# the input. Where the config can say the reading, the program runs it against
# the sound reference; where only the equations can, the reference runs it
# against the sound program. Either way the comparison that holds the two
# together fails. Two readings are of the whole model (the head, the lead).

def _block_leaves(kind, cfg=CFG, seed=0):
    """The leaves of one block of ``kind``, norms off 1 and the expert bias
    off 0, and an input ``[2, 24, M]``."""
    rng = np.random.RandomState(seed)
    p = {}
    for leaf in t._row(kind).leaves(cfg):
        a = leaf.draw(rng, leaf.shape)
        if np.all(a == 1):
            a = 1 + 0.3 * rng.randn(*a.shape).astype(np.float32)
        if leaf.name == "router_bias":
            a = 0.1 * rng.randn(*a.shape).astype(np.float32)
        p[leaf.name] = jnp.asarray(a)
    return p, jnp.asarray(rng.randn(2, 24, cfg.d_model), jnp.float32)


def _reference_block(kind):
    """The reference's block of ``kind`` as it stands now (a test may have
    swapped one of its functions), ``(p, x) -> x'``."""
    if kind == ("conv",):
        return lambda p, x: reference.conv_block(p, x, SIZES)
    if kind == ("dense",):
        return lambda p, x: reference.dense_ffn(p, x, SIZES)
    if kind == ("experts",):
        return lambda p, x: reference.experts(p, x, SIZES)[0]
    return lambda p, x: reference.MIXERS["full_attention"](p, x, SIZES)


def _block_error(kind, cfg=CFG, leaves=None, scale=1.0) -> float:
    """The largest relative distance, over the output and the gradients of
    the leaves and the input (times ``scale``), of the program's block of
    ``kind`` under ``cfg`` (on ``leaves(p)``, where the reading changes a
    leaf's shape) from the reference's."""
    want_p, x = _block_leaves(kind)
    p = want_p if leaves is None else leaves(want_p)
    x = x * scale
    probe = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)

    def sides(block, p):
        def scalar(p, x):
            y = block(p, x)
            return jnp.sum(y * probe), y
        (_, y), grads = jax.value_and_grad(scalar, argnums=(0, 1),
                                           has_aux=True)(p, x)
        return y, grads
    got_y, (got_p, got_x) = jax.jit(lambda p: sides(
        lambda p, x: t._block(p, x, jnp.arange(x.shape[1]), cfg,
                              kind)[0], p))(p)
    with jax.default_matmul_precision("highest"):
        want_y, (want, want_x) = jax.jit(
            lambda p: sides(_reference_block(kind), p))(want_p)
    shared = [k for k in want if k in got_p
              and got_p[k].shape == want[k].shape and k != "router_bias"]
    assert len(shared) >= 3
    return max([_rel(got_y, want_y), _rel(got_x, want_x)]
               + [_rel(got_p[k], want[k]) for k in shared])


CONV, DENSE, EXPERTS = ("conv",), ("dense",), ("experts",)
ATTENTION = ("attention", None, True)


@pytest.mark.parametrize("kind", [CONV, DENSE, EXPERTS, ATTENTION],
                         ids=lambda k: k[0])
def test_the_sound_block_is_inside_the_tolerance(kind):
    assert _block_error(kind) < TOL


def _tiled(p):
    """OLMoE's norm on the same weights: a head's weight tiled over the
    projection."""
    return {**p, "q_norm": jnp.tile(p["q_norm"], CFG.n_heads),
            "k_norm": jnp.tile(p["k_norm"], CFG.kv_heads)}


#: (what, the block's kind, the config's wrong reading[, what it does to the
#: program's leaves]). An eps shows on a small residual stream (the
#: embedding's rows as ``init_params`` draws them: std 0.02)
WRONG_CONFIGS = [
    ("no renormalisation", EXPERTS, {"moe_renormalize": False}),
    ("softmax scores", EXPERTS, {"moe_router_scores": "softmax"}),
    ("top-3", EXPERTS, {"moe_top_k": 3}),
    ("a scaling factor of 2.5", EXPERTS, {"moe_routed_scale": 2.5}),
    ("another share of the experts", EXPERTS, {"expert_share": (1, 8)}),
    ("relu for silu in the experts", EXPERTS, {"moe_activation": "relu"}),
    ("eps 1e-6 in the mixer's block", CONV, {"norm_eps": 1e-6}),
    ("eps 1e-6 in the attention block", ATTENTION, {"norm_eps": 1e-6}),
    ("theta 10 000", ATTENTION, {"rope_theta": 1e4}),
    ("no QK-norm", ATTENTION, {"qk_norm": False}),
    ("QK-norm over the projection", ATTENTION, {"qk_norm": True}, _tiled),
    ("two taps", CONV, {"conv_taps": 2},
     lambda p: {**p, "conv_w": p["conv_w"][1:]}),
    ("a gelu dense FFN", DENSE, {"ffn_gated": False}),
]


@pytest.mark.parametrize("what, kind, change, leaves",
                         [(*w, None)[:4] for w in WRONG_CONFIGS],
                         ids=[w[0] for w in WRONG_CONFIGS])
def test_a_wrong_reading_of_the_config_fails(what, kind, change, leaves):
    """Each moves the block's output or a gradient far beyond TOL."""
    scale = 0.02 if what.startswith("eps") else 1.0
    if scale != 1.0:
        assert _block_error(kind, scale=scale) < TOL
    err = _block_error(kind, dataclasses.replace(CFG, **change), leaves,
                       scale)
    assert err > 5 * TOL, (what, err)


def _conv(z, taps, shift=0):
    """``c[t] = sum_j taps[j] z[t - (K - 1) + j + shift]``, zeros outside."""
    k, s = taps.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (k - 1 - shift, shift), (0, 0)))
    return sum(taps[j] * padded[:, j:j + s] for j in range(k))


def _mixer(chain, order="bcu"):
    """A reading of the mixer as ``reference.short_conv``: the thirds in
    ``order``, ``chain(B, C, u, taps)`` between the two projections."""
    def mixer(p, h):
        parts = jnp.split(h @ p["conv_in"], 3, axis=-1)
        b, c, u = (parts[order.index(name)] for name in "bcu")
        return chain(b, c, u, p["conv_w"]) @ p["conv_out"]
    return mixer


def _attention(norm_then_rotate):
    """A reading of the attention mixer as ``reference.attention``:
    ``norm_then_rotate(q or k [B, S, H, D], its weight [D], eps, theta)``
    between the projection and the core."""
    def attention(p, x, sizes):
        b, s, _ = x.shape
        heads, kv, d = sizes["heads"], sizes["kv_heads"], sizes["head_dim"]
        eps, theta = sizes["norm_eps"], sizes["rope_theta"]
        h = _rms_norm(x, p["ln1"], eps)
        q = norm_then_rotate((h @ p["wq"]).reshape(b, s, heads, d),
                             p["q_norm"], eps, theta)
        k = norm_then_rotate((h @ p["wk"]).reshape(b, s, kv, d),
                             p["k_norm"], eps, theta)
        v = (h @ p["wv"]).reshape(b, s, kv, d)
        o = reference._attend(q.reshape(b, s, kv, heads // kv, d), k, v,
                              None)
        return x + o.reshape(b, s, heads * d) @ p["wo"]
    return attention


def _route(pick, weigh):
    """A reading of the router as ``reference.route``: the choice the top-k
    of ``pick(scores, bias)``, the weights ``weigh(scores, bias)`` at the
    chosen experts over their sum."""
    def route(logits, bias, sizes, choice=None):
        scores = jax.nn.sigmoid(logits)
        _, choice = jax.lax.top_k(pick(scores, bias),
                                  sizes["experts_per_token"])
        chosen = jnp.sum(jax.nn.one_hot(choice, sizes["experts"],
                                        dtype=logits.dtype), axis=1)
        combine = chosen * weigh(scores, bias)
        return choice, combine / jnp.sum(combine, axis=-1, keepdims=True)
    return route


def _first_head_s_weight(x, g, eps, theta):
    """One weight a head, of which the tree's is the first head's: the
    other heads' (the same values) are leaves of their own."""
    g = jnp.concatenate([g[None], jnp.broadcast_to(
        jax.lax.stop_gradient(g), (x.shape[2] - 1,) + g.shape)])
    return reference._rotate(_rms_norm(x, g, eps), theta)


def _rotate_interleaved(x, theta):
    """The rotation over the pairs ``(x[2i], x[2i + 1])``."""
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    halves = jnp.concatenate([pairs[..., 0], pairs[..., 1]], -1)
    x1, x2 = jnp.split(reference._rotate(halves, theta), 2, axis=-1)
    return jnp.stack([x1, x2], -1).reshape(x.shape)


def _sound_chain(b, c, u, taps):
    return c * _conv(b * u, taps)


#: (the block's kind, the name in ``reference`` that is swapped, the sound
#: reading written again in the frames above)
SOUND = {
    "short_conv": (CONV, _mixer(_sound_chain)),
    "attention": (ATTENTION, _attention(
        lambda x, g, eps, theta: reference._rotate(_rms_norm(x, g, eps),
                                                   theta))),
    "route": (EXPERTS, _route(lambda s, b: s + b, lambda s, b: s)),
}

#: (what, the name in ``reference`` that is swapped, its wrong reading)
WRONG_EQUATIONS = [
    ("the thirds as [C | B | u]", "short_conv",
     _mixer(_sound_chain, order="cbu")),
    ("the thirds as [B | u | C]", "short_conv",
     _mixer(_sound_chain, order="buc")),
    ("a tap on t + 1", "short_conv", _mixer(
        lambda b, c, u, taps: c * _conv(b * u, taps, shift=1))),
    ("the taps reversed", "short_conv", _mixer(
        lambda b, c, u, taps: c * _conv(b * u, taps[::-1]))),
    ("silu after the taps", "short_conv", _mixer(
        lambda b, c, u, taps: c * jax.nn.silu(_conv(b * u, taps)))),
    ("silu on the in-projection", "short_conv", _mixer(
        lambda b, c, u, taps: c * _conv(b * jax.nn.silu(u), taps))),
    ("the gate C before the taps", "short_conv", _mixer(
        lambda b, c, u, taps: _conv(c * b * u, taps))),
    ("the gate B after the taps", "short_conv", _mixer(
        lambda b, c, u, taps: c * b * _conv(u, taps))),
    ("a bias of one on the taps", "short_conv", _mixer(
        lambda b, c, u, taps: c * (1 + _conv(b * u, taps)))),
    ("QK-norm after the rotation", "attention", _attention(
        lambda x, g, eps, theta: _rms_norm(
            reference._rotate(x, theta), g, eps))),
    ("a norm weight a head", "attention",
     _attention(_first_head_s_weight)),
    ("the interleaved layout", "attention", _attention(
        lambda x, g, eps, theta: _rotate_interleaved(
            _rms_norm(x, g, eps), theta))),
    ("top-4 of the unbiased scores", "route",
     _route(lambda s, b: s, lambda s, b: s)),
    ("weights from the biased scores", "route",
     _route(lambda s, b: s + b, lambda s, b: s + b)),
]


def _swapped_error(monkeypatch, name, reading) -> float:
    """The sound program's block from the reference's with ``reading`` for
    its ``name``."""
    if name == "attention":
        monkeypatch.setitem(reference.MIXERS, "full_attention", reading)
    else:
        monkeypatch.setattr(reference, name, reading)
    return _block_error(SOUND[name][0])


@pytest.mark.parametrize("name", sorted(SOUND))
def test_the_sound_reading_written_again_is_inside_it(monkeypatch, name):
    """The test's own frames of the three functions are the reference's
    where they hold the sound reading."""
    assert _swapped_error(monkeypatch, name, SOUND[name][1]) < TOL


@pytest.mark.parametrize("what, name, reading", WRONG_EQUATIONS,
                         ids=[w for w, _, _ in WRONG_EQUATIONS])
def test_a_wrong_reading_of_the_equations_fails(monkeypatch, what, name,
                                                reading):
    err = _swapped_error(monkeypatch, name, reading)
    assert err > 5 * TOL, (what, err)


#: the leading mixer with its dense FFN and the attention block with its
#: experts: what the readings of the whole model are shown on
_CUT = {"num_hidden_layers": 2, "layer_types": ["conv", "full_attention"]}
SMALL = ARCH.cut(_CUT, adapter._leaf_paths({**CONFIG, **_CUT}))


def test_the_sound_program_is_inside_the_tolerance():
    assert SMALL.error("the sound program", n_seqs=1) < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for v in SMALL.kept(n_seqs=1)[2].values())


def test_an_untied_head_fails():
    """A head of its own that starts as the table's transpose: the same
    loss, and a table whose gradient lacks the head's part."""
    params = SMALL.kept(n_seqs=1)[0]
    err = SMALL.error("an untied head",
                      dataclasses.replace(SMALL.CFG, tie_embeddings=False),
                      {**params, "lm_head": params["embed"].T}, n_seqs=1)
    assert err > 5 * TOL, err


def test_the_second_dense_layer_kept_fails():
    """Both published leading layers, the second with the first's weights
    (the named leaves: those whose place the longer lead does not move)."""
    params = SMALL.kept(n_seqs=1)[0]
    lead = jax.tree_util.tree_map(lambda a: jnp.concatenate([a, a]),
                                  params["lead"])
    err = SMALL.error(
        "the second dense layer kept", dataclasses.replace(
            SMALL.CFG, lead_pattern=SMALL.CFG.lead_pattern * 2),
        {**params, "lead": lead}, n_seqs=1)
    assert err > 5 * TOL, err


# -- the mixer alone ----------------------------------------------------------

def test_the_mixer_is_causal_and_sees_two_positions_back():
    """An input at ``t`` moves no output before ``t``, and none past
    ``t + 2``: the block has no state but its last two positions."""
    rng = np.random.RandomState(0)
    p = {leaf.name: jnp.asarray(leaf.draw(rng, leaf.shape))
         for leaf in short_conv.KIND.leaves(CFG)}
    x = jnp.asarray(rng.randn(2, 24, 32), jnp.float32)
    y = np.asarray(short_conv._conv_block(p, x, CFG))
    later = x.at[:, 9].add(1.0)
    moved = np.abs(np.asarray(short_conv._conv_block(p, later, CFG)) - y
                   ).max(axis=(0, 2))
    assert np.all(moved[:9] == 0) and np.all(moved[12:] == 0)
    assert np.all(moved[9:12] > 0)
    # the chain by hand at one position, the last tap on the position itself
    h = _rms_norm(x, p["ln1"], CFG.norm_eps)
    b, c, u = np.split(np.asarray(h @ p["conv_in"]), 3, axis=-1)
    z, w = b * u, np.asarray(p["conv_w"])
    want = c[:, 5] * (w[0] * z[:, 3] + w[1] * z[:, 4] + w[2] * z[:, 5])
    np.testing.assert_allclose(
        np.asarray(short_conv.gate_chain(h @ p["conv_in"], p["conv_w"])
                   )[:, 5], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y[:, 5] - np.asarray(x)[:, 5],
                               want @ np.asarray(p["conv_out"]),
                               rtol=1e-4, atol=1e-5)
    # before the start: zeros
    np.testing.assert_allclose(
        np.asarray(short_conv.gate_chain(h @ p["conv_in"], p["conv_w"])
                   )[:, 0], c[:, 0] * w[2] * z[:, 0], rtol=1e-5, atol=1e-6)


def test_the_chain_is_float32_on_bfloat16_thirds():
    """bf16 compute: the thirds are the projection's bf16, the chain's
    products and sums float32, rounded once before ``conv_out``."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    rng = np.random.RandomState(0)
    bcu = jnp.asarray(rng.randn(1, 8, 96), jnp.bfloat16)
    taps = jnp.asarray(rng.randn(3, 32), jnp.float32)
    assert short_conv.gate_chain(bcu, taps).dtype == jnp.float32
    p = {leaf.name: jnp.asarray(leaf.draw(rng, leaf.shape))
         for leaf in short_conv.KIND.leaves(cfg)}
    x = jnp.asarray(rng.randn(1, 8, 32), jnp.bfloat16)
    text = str(jax.make_jaxpr(lambda p, x: short_conv._conv_block(p, x, cfg))(
        p, x))
    assert text.count("dot_general") == 2
    assert "preferred_element_type=bfloat16" in text


# -- one mechanism, and the configurations that name none of it ---------------

#: every accepted configuration's tiny program at the parent commit
#: (9912b80): sha256 (16 hex digits) of the text of ``jax.make_jaxpr`` of its
#: loss's gradient, addresses struck out, and of its tree's shapes. A JAX
#: upgrade that prints a jaxpr differently moves the first of each pair and
#: not the second: record them again from the commit before the upgrade.
PARENT_PROGRAMS = {
    "gpt-1.3b-widths": ("8d09444310935e8c", "f756b4a151f15d25"),
    "olmoe-1b-7b": ("8a85a574931f0773", "33ae69a8ed6dc084"),
    "ouro-2.6b": ("198f8569c959b0e7", "c1b56a957a2d3cfc"),
    "smallthinker-21b-a3b": ("25a381f3cf477ad8", "aa7813b0c9b8afe3"),
    "nemotron-3-nano-30b-a3b": ("6b9d91906fbdd3c6", "b5efb155c4415a28"),
    "glm-4.7-flash": ("4b48cf80e570394e", "df3eff602e9b7fc4"),
    "granite-4.0-h-micro": ("e450fe130a391ab5", "a91a56ea9b269743"),
    "laguna-xs.2": ("bbf4b9c942caf34e", "17eefd1c9df9f2a1"),
}


def _configs():
    """Every benchmark configuration's tiny model config, by its adapter."""
    return {name: model(config, job)
            for name, (model, config, job) in arch.configs().items()}


def _digest(text: str) -> str:
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def test_the_new_configuration_is_the_only_one_without_a_parent():
    """(Of the configurations there were at PR 55: a later one's file holds
    this file's configuration to its own parent, tests/test_keye_vl2.py.)"""
    assert set(PARENT_PROGRAMS) | {"lfm2-24b-a2b"} <= set(_configs())


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_configuration_that_names_neither_keeps_its_tree_and_jaxpr(name):
    cfg = _configs()[name]
    assert cfg.conv_taps == 0 and cfg.qk_norm in (False, True)
    assert all(kind[0] != "conv"
               for kind in cfg.layer_pattern + cfg.lead_pattern)
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    tree = _digest(str(jax.tree_util.tree_map(lambda a: a.shape, shapes)))
    assert (_digest(arch.grad_jaxpr(cfg)), tree) == PARENT_PROGRAMS[name]


def test_qk_norm_true_is_the_norm_over_the_projection():
    """OLMoE's tree (its jaxpr: the case ``olmoe-1b-7b`` above): weights as
    wide as the projections, over tp; ``"head"``: one ``[head_dim]`` each,
    whole on every shard."""
    cfg = _configs()["olmoe-1b-7b"]
    assert cfg.qk_norm is True
    leaves = {leaf.name: leaf for leaf in t._attention_leaves(cfg)}
    assert leaves["q_norm"].shape == (cfg.n_heads * cfg.head_dim,)
    assert leaves["q_norm"].spec == leaves["k_norm"].spec == ("tp",)
    leaves = {leaf.name: leaf for leaf in t._attention_leaves(CFG)}
    assert leaves["q_norm"].shape == leaves["k_norm"].shape == (16,)
    assert leaves["q_norm"].spec == leaves["k_norm"].spec == ()
    with pytest.raises(ValueError, match="qk_norm='whole'"):
        dataclasses.replace(CFG, qk_norm="whole")


def test_the_new_kind_is_a_stack_of_its_own_under_one_scan():
    params = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), CFG))
    assert sorted(params["layers"]) == ["attention", "conv", "experts"]
    assert sorted(params["lead"]) == ["conv", "dense"]
    conv = params["layers"]["conv"]
    assert {k: v.shape for k, v in conv.items()} == {
        "ln1": (1, 6, 32), "conv_in": (1, 6, 32, 96),
        "conv_w": (1, 6, 3, 32), "conv_out": (1, 6, 32, 32)}
    assert list(t._BLOCK_KINDS["conv"].leaves(CFG))[0].name == "ln1"
    assert params["lead"]["conv"]["conv_in"].shape == (1, 32, 96)
    assert params["layers"]["attention"]["q_norm"].shape == (1, 2, 16)
    assert "lm_head" not in params
    assert t._row(("conv",)) is short_conv.KIND
    assert not short_conv.KIND.checkpointed
    text = arch.grad_jaxpr(CFG)
    assert text.count("scan[") >= 2     # forward and its transpose
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    sh = t.param_shardings(CFG, mesh)
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(params)


def test_the_scopes_nest_as_stated():
    params, batch = _params(), _batch(n_seqs=1)
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    p = shard_params(params, CFG, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    text = jax.jit(t.make_grad_fn(CFG, mesh)).lower(p, tok, tgt).as_text(
        debug_info=True)
    assert (scopes.SHORT_CONV, scopes.SHORT_CONV_PROJ,
            scopes.SHORT_CONV_GATE) == (
                "hvd.short_conv", "hvd.short_conv.proj",
                "hvd.short_conv.gate")
    for part in (scopes.SHORT_CONV_PROJ, scopes.SHORT_CONV_GATE):
        assert f"{scopes.LAYERS}/{scopes.SHORT_CONV}/{part}" in text \
            or f"{scopes.SHORT_CONV}/{part}" in text
    # the heads' norm: inside the attention block, outside its core
    assert f"{scopes.ATTENTION}/{scopes.ATTENTION_CORE}" in text
    assert f"{scopes.ATTENTION}/rsqrt" in text
    assert f"{scopes.MLP}/{scopes.MOE}/{scopes.MOE_ROUTER}" in text


def test_what_the_kind_does_not_admit_is_refused_by_name():
    with pytest.raises(ValueError, match="conv_taps=0"):
        dataclasses.replace(CFG, conv_taps=0)
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, layer_pattern=(("conv", 3),))
    for axis in ("tp", "sp", "pp"):
        mesh = build_mesh(devices=jax.devices()[:2], **{axis: 2})
        with pytest.raises(NotImplementedError,
                           match=fr"\(\"conv\",\) block.*live {axis}|"
                                 fr"lead_pattern.*live {axis}"):
            t.param_shardings(CFG, mesh)
    # with no leading blocks it is the kind's own refusal on every axis
    bare = dataclasses.replace(CFG, lead_pattern=())
    for axis in ("tp", "sp", "pp"):
        mesh = build_mesh(devices=jax.devices()[:2], **{axis: 2})
        with pytest.raises(NotImplementedError,
                           match=fr"\(\"conv\",\) block.*live {axis}"):
            t.param_shardings(bare, mesh)
    with pytest.raises(ValueError, match="n_kv_heads, qk_norm or post_norm"):
        dataclasses.replace(CFG, layer_pattern=(("latent",),), q_latent=8,
                            kv_latent=8, rope_width=8, n_kv_heads=None)


def test_the_adapter_draws_the_taps_inside_their_bound():
    ours = jax.jit(ARCH.init_function())(jax.random.PRNGKey(0))
    taps = ours["layers"]["conv"]["conv_w"]
    assert np.abs(taps).max() <= 1 / np.sqrt(3)
