"""Two attention shapes in one stack, a rotary table a kind, a head-wise gate
on the core's output, sigmoid-routed experts with a shared one, as chip 0 of
an expert-parallel group (ISSUE 53, Laguna-XS.2), in float32 at the benchmark
configuration's ``tiny`` sizes (the leading dense layer and two periods of
[window, window, window, full]; 8 / 6 query heads of 16 on 2 key/value heads:
groups of 4 and 3; a window of 8 under 64 positions; 16 experts top-4 of which
a share holds 2, a shared expert), against the plain reference
``benchmarks/chip/reference/laguna.py`` on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums (they
read 2e-7 to 7e-7): 1e-4 is far below what the least of the wrong terms does
(``test_a_wrong_term_fails``).
"""

import dataclasses
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
import chip_door
from arch import TOL, rel as _rel
from horovod_tpu.models import _kinds
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.models._kinds import Rope, Yarn
from horovod_tpu.parallel import build_mesh
from horovod_tpu.profiling import scopes

ARCH = arch.get("laguna")
adapter, reference = ARCH.adapter, ARCH.reference
CONFIG, CFG, LEAVES = ARCH.CONFIG, ARCH.CFG, ARCH.LEAVES
_cell, _params, _batch = ARCH.cell, ARCH.params, ARCH.batch
WINDOW, FULL = "attention_8_gated", "attention_6_gated"


def test_the_cell_keeps_every_published_width():
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    window, experts, *_rest, full, _ = cfg.layer_pattern
    assert cfg.layer_pattern == (window, experts) * 3 + (full, experts)
    assert cfg.lead_pattern == (full, ("dense",)) and cfg.n_layers == 8
    assert window == ("attention", 512, Rope(10000.0), 64, True)
    assert full == ("attention", None, Rope(
        500000.0, 64, Yarn(64.0, 4096, 64.0, 1.0, 1.4158883083359672)),
        48, True)
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim,
            cfg.dense_ff, cfg.d_ff, cfg.moe_shared_width) == (
                2048, 48, 8, 128, 8192, 512, 512)
    assert (cfg.moe_top_k, cfg.n_experts, cfg.held_experts,
            cfg.moe_routed_scale, cfg.norm_eps, cfg.vocab_size,
            cfg.expert_share) == (8, 256, 32, 2.5, 1e-6, 12544, (0, 8))
    assert job["seq_len"] == 8192 == 16 * window[1]
    assert set(config["reduced"]) == set(config["reduced_from"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["reduced_from"] == {
        "num_hidden_layers": 40, "num_experts": 256, "vocab_size": 100352}
    # the three lists stay whole as published; the first five are read
    assert len(config["layer_types"]) == len(config["mlp_layer_types"]) == \
        len(config["num_attention_heads_per_layer"]) == 40
    sizes = adapter.shapes(config, job)
    assert sizes["layer_heads"] == [48, 64, 64, 64, 48]
    assert sizes["layer_windows"] == [None, 512, 512, 512, None]
    assert sizes["layer_dense"] == [True, False, False, False, False]
    assert (sizes["kv_heads"], sizes["held_experts"], sizes["first_expert"],
            sizes["d_expert"], sizes["dense_ff"]) == (8, 32, 0, 512, 8192)
    for reading in ("gate", "router", "blocks", "yarn", "rope_layout"):
        assert "no network here" in config["assumed"][reading], reading
    shapes = arch.drawn_shapes(adapter, cfg, config)
    n = arch.count(shapes)
    assert 691.5e6 < n < 691.7e6, n     # the deployment's 691.6 M parameters
    full_block = sum(int(np.prod(a.shape[1:])) for a in
                     shapes["lead"]["attention_48_gated"].values())
    window_block = sum(int(np.prod(a.shape[2:])) for a in
                       shapes["layers"]["attention_64_gated"].values())
    assert round(full_block / 1e6, 2) == 29.46
    assert round(window_block / 1e6, 2) == 37.88
    # the adapter's tree is init_params' tree
    small = dataclasses.replace(cfg, vocab_size=8, d_model=16, dense_ff=8,
                                d_ff=8, moe_shared_width=8, head_width=8)
    arch.assert_the_adapter_s_tree_is_init_params(adapter, small, config)


def test_the_step_s_required_flops_by_hand():
    """3 x 801.8 M = 2.405 G a trained token, 19.70 TFLOP a step of 8192
    tokens (ISSUE 53's count): attention projections 43 %, the two full
    cores 25, the three window cores 6, the dense FFN 13, router + shared
    3.7, the held routed experts 3.1, the sliced head 6.4."""
    config, job = _cell(tiny=False)
    kv = 2 * 2 * 2048 * 1024
    proj = (2 * (2 * 2 * 2048 * 48 * 128 + kv + 2 * 2048 * 48)
            + 3 * (2 * 2 * 2048 * 64 * 128 + kv + 2 * 2048 * 64))
    full = 2 * 2 * 2 * 48 * 128 * 8193 / 2
    window = 3 * 2 * 2 * 64 * 128 * (512 * 513 / 2 + 7680 * 512) / 8192
    dense = 3 * 2 * 2048 * 8192
    beside = 4 * (2 * 2048 * 256 + 3 * 2 * 2048 * 512)
    routed = 4 * 8 * 32 / 256 * 3 * 2 * 2048 * 512
    head = 2 * 2048 * 12544
    forward = proj + full + window + dense + beside + routed + head
    got = adapter.flops_per_token(config, job)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert forward == pytest.approx(801.8e6, rel=5e-4)
    assert 8192 * got == pytest.approx(19.70e12, rel=1e-3)
    for part, share in ((proj, 0.43), (full, 0.25), (window, 0.06),
                        (dense, 0.13), (beside, 0.037), (routed, 0.031),
                        (head, 0.064)):
        assert part / forward == pytest.approx(share, abs=0.005)


def test_the_kernels_least_work_by_hand():
    gmm, fwd, bwd, xent = (
        chip_door.roofline("laguna-xs.2.s8192", kernel) for kernel in (
            "hvd_moe_gmm", "hvd_flash_attention", "hvd_flash_bwd",
            "hvd_fused_xent"))
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    live = {None: 8192 * 8193 / 2, 512: 512 * 513 / 2 + 7680 * 512}
    one = {w: 2 * 2 * 128 * s for w, s in live.items()}
    calls = sizes["attention_forward_calls"] // 5
    assert calls == 1 + bool(config["assumed"]["checkpoint_every_block"])
    need = fwd(sizes)
    assert need["flops"] == calls * (2 * 48 * one[None] + 3 * 64 * one[512])
    assert need["bytes"] == calls * (
        2 * (2 * 8192 * 56 * 128 * 2 + 48 * 8192 * 4)
        + 3 * (2 * 8192 * 72 * 128 * 2 + 64 * 8192 * 4))
    # the window cores' least work is a fifth of the attention cores'
    assert 3 * 64 * one[512] / (need["flops"] / calls) == pytest.approx(
        0.19, abs=0.01)
    need = bwd(sizes)
    assert need["flops"] == 2.5 * (2 * 48 * one[None] + 3 * 64 * one[512])
    assert need["bytes"] == (
        2 * (4 * 8192 * 56 * 128 * 2 + 2 * 48 * 8192 * 4)
        + 3 * (4 * 8192 * 72 * 128 * 2 + 2 * 64 * 8192 * 4))
    rows = 8192 * 8 * 32 / 256
    assert rows == 8192
    need = gmm(sizes)
    assert need["flops"] == 4 * 9 * 2 * rows * 2048 * 512
    assert need["bytes"] == 4 * 9 * 2 * (rows * (2048 + 512)
                                         + 32 * 2048 * 512)
    assert xent(sizes)["bytes"] == 2 * 8192 * 12544 * 2 + 12 * 8192


# -- the rotary tables --------------------------------------------------------

def test_the_yarn_table_by_hand():
    """The full layers' table at the published numbers: ``low`` 5, ``high``
    16, and three frequencies, one before the ramp, one on it, one past it,
    each computed here from the issue's formula."""
    config, _job = _cell(tiny=False)
    table = adapter._rope(config, "full_attention")
    assert table == Rope(500000.0, 64,
                         Yarn(64.0, 4096, 64.0, 1.0, 1.4158883083359672))
    corr = [64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(5e5))
            for n in (64, 1)]
    assert [round(c, 3) for c in corr] == [5.660, 15.802]
    assert _kinds.yarn_ramp(table, 64) == (5, 16)
    freqs, factor = _kinds.rope_table(table, 128)
    assert freqs.shape == (32,) and freqs.dtype == np.float32
    assert factor == 1.4158883083359672 == pytest.approx(
        0.1 * math.log(64) + 1, rel=1e-15)
    pos = [500000.0 ** (2 * i / 64) for i in range(32)]
    assert freqs[3] == np.float32(1 / pos[3])                   # kept
    assert freqs[10] == np.float32(
        (1 / (64 * pos[10])) * (5 / 11) + (1 / pos[10]) * (6 / 11))
    assert freqs[20] == np.float32(1 / (64 * pos[20]))          # divided
    assert freqs[10] == pytest.approx(0.0091506, rel=1e-4)
    # the reference computes its own, from the config's group
    want, want_factor = reference.inv_freq(
        config["rope_parameters"]["full_attention"], 128)
    np.testing.assert_allclose(freqs, want, rtol=1e-7)
    assert want_factor == factor
    # the window layers' table is the default one over the whole head
    freqs, factor = _kinds.rope_table(
        adapter._rope(config, "sliding_attention"), 128)
    assert factor == 1.0 and freqs.shape == (64,)
    np.testing.assert_allclose(
        freqs, 10000.0 ** (-np.arange(64) / 64), rtol=1e-7)
    with pytest.raises(ValueError, match="rotated channels"):
        _kinds.rope_table(Rope(1e4, 130), 128)


def test_a_table_of_the_kind_s_own_rotates_its_channels_only():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(2, 12, 3, 16), jnp.float32)
    positions = jnp.arange(12)
    # the default table as a Rope is the default rope
    np.testing.assert_allclose(
        _kinds.rope(x, positions, Rope(10000.0)),
        _kinds.rope(x, positions, 10000.0), rtol=2e-6, atol=2e-6)
    table = Rope(500000.0, 8, Yarn(64.0, 16, 64.0, 1.0, 1.25))
    y = np.asarray(_kinds.rope(x, positions, table))
    np.testing.assert_array_equal(y[..., 8:], np.asarray(x)[..., 8:])
    # position 0 turns nothing: the rotated channels times the factor
    np.testing.assert_allclose(y[:, 0, :, :8], 1.25 * np.asarray(x)[:, 0, :, :8],
                               rtol=1e-6)
    freqs, _ = _kinds.rope_table(table, 16)
    t5 = 5 * freqs.astype(np.float64)
    x1, x2 = np.asarray(x)[:, 5, :, :4], np.asarray(x)[:, 5, :, 4:8]
    np.testing.assert_allclose(
        y[:, 5, :, :4], 1.25 * (x1 * np.cos(t5) - x2 * np.sin(t5)),
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        y[:, 5, :, 4:8], 1.25 * (x1 * np.sin(t5) + x2 * np.cos(t5)),
        rtol=1e-5, atol=1e-6)


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0
    assert _rel(got[what], want[what]) < TOL, what

def test_every_gate_s_leaf_reaches_its_gradient():
    """``wg`` of all nine attention blocks, both shapes: no block's gate is
    a constant, and no leaf of the tree is left without a gradient but the
    routers' correction bias, a buffer."""
    _got, _want, aux, grads = ARCH.sides
    for part, stack, heads, blocks in (("lead", FULL, 6, 1),
                                       ("layers", FULL, 6, 2),
                                       ("layers", WINDOW, 8, 6)):
        g = np.asarray(grads[part][stack]["wg"]).reshape(-1, 32, heads)
        assert g.shape[0] == blocks
        assert np.all(np.linalg.norm(g, axis=(1, 2)) > 0), (part, stack)
    for path, g in jax.tree_util.tree_leaves_with_path(grads):
        bias = path[-1].key == "router_bias"
        assert bool(np.any(np.asarray(g) != 0)) != bias, path
    assert float(aux["dropped"]) == 0.0
    assert 0 < float(aux["held_rows"]) < 8 * 2 * 64 * 4
    assert float(aux["max_expert_load"]) >= 1.0


# -- each wrong reading of the equations fails --------------------------------

def _replace_kinds(cfg, change):
    def swap(pattern):
        return tuple(change(k) if k[0] == "attention" else k
                     for k in pattern)
    return dataclasses.replace(cfg, layer_pattern=swap(cfg.layer_pattern),
                               lead_pattern=swap(cfg.lead_pattern))


def _full_kinds(cfg, change):
    return _replace_kinds(
        cfg, lambda k: change(k) if k[1] is None else k)


def _window_kinds(cfg, change):
    return _replace_kinds(
        cfg, lambda k: change(k) if k[1] is not None else k)


def _with(kind, **fields):
    names = ("word", "window", "rope", "heads", "gated")
    return tuple(fields.get(n, v) for n, v in zip(names, kind))


def _yarn(kind, **fields):
    return _with(kind, rope=kind[2]._replace(
        yarn=kind[2].yarn._replace(**fields)))


WRONG = [
    ("the gate left out", lambda c: _replace_kinds(
        c, lambda k: _with(k, gated=False))),
    ("the window layers full", lambda c: _window_kinds(
        c, lambda k: _with(k, window=None))),
    ("the window one key wider", lambda c: _window_kinds(
        c, lambda k: _with(k, window=9))),
    ("the full layers under the window", lambda c: _full_kinds(
        c, lambda k: _with(k, window=8))),
    ("the window layers' table on the full layers", lambda c: _full_kinds(
        c, lambda k: _with(k, rope=Rope(10000.0)))),
    ("the full layers' table on the window layers", lambda c: _window_kinds(
        c, lambda k: _with(k, rope=c.lead_pattern[0][2]))),
    ("the whole head rotated on the full layers", lambda c: _full_kinds(
        c, lambda k: _with(k, rope=k[2]._replace(width=None)))),
    ("no positions on the full layers", lambda c: _full_kinds(
        c, lambda k: _with(k, rope=False))),
    ("YaRN left out", lambda c: _full_kinds(
        c, lambda k: _with(k, rope=k[2]._replace(yarn=None)))),
    ("YaRN without its factor on cos and sin", lambda c: _full_kinds(
        c, lambda k: _yarn(k, attention_factor=1.0))),
    ("YaRN's slow frequencies divided by 8", lambda c: _full_kinds(
        c, lambda k: _yarn(k, factor=8.0))),
    ("YaRN from 4096 original positions at these sizes",
     lambda c: _full_kinds(c, lambda k: _yarn(k, original=4096))),
    ("theta 10 000 under YaRN", lambda c: _full_kinds(
        c, lambda k: _with(k, rope=k[2]._replace(theta=10000.0)))),
    ("the scaling factor left out", lambda c: dataclasses.replace(
        c, moe_routed_scale=1.0)),
    ("the renormalisation left out", lambda c: dataclasses.replace(
        c, moe_renormalize=False)),
    ("softmax for sigmoid", lambda c: dataclasses.replace(
        c, moe_router_scores="softmax")),
    ("top-3", lambda c: dataclasses.replace(c, moe_top_k=3)),
    ("relu for silu in the experts", lambda c: dataclasses.replace(
        c, moe_activation="relu")),
    ("no shared expert", lambda c: dataclasses.replace(
        c, moe_shared_width=0)),
    ("another share of the experts", lambda c: dataclasses.replace(
        c, expert_share=(1, 8))),
    ("a gelu dense FFN", lambda c: dataclasses.replace(c, ffn_gated=False)),
    ("eps 1e-5", lambda c: dataclasses.replace(c, norm_eps=1e-3)),
]


#: the leading full layer with its dense FFN, and one window layer with its
#: experts: each wrong term below is in one of these four blocks (the tree
#: from seed 3: the one router sends rows to both experts held here)
_CUT = {"num_hidden_layers": 2, "num_attention_heads_per_layer": [6, 8],
        "layer_types": ["full_attention", "sliding_attention"],
        "mlp_layer_types": ["dense", "sparse"]}
SMALL = ARCH.cut(_CUT, {
    **{name: leaf for name, leaf in adapter._leaf_paths(
        {**CONFIG, **_CUT}).items() if name != "first_query"},
    "embed": (("embed",), None),
    "final_norm": (("ln_f",), None),
    "lead_gate": (("lead", FULL, "wg"), (0,)),
    "lead_key": (("lead", FULL, "wk"), (0,)),
    "lead_out": (("lead", FULL, "wo"), (0,)),
    "dense_gate": (("lead", "dense", "w1"), (0,)),
    "dense_up": (("lead", "dense", "w3"), (0,)),
    "window_query": (("layers", WINDOW, "wq"), (0, 0)),
    "window_value": (("layers", WINDOW, "wv"), (0, 0)),
    "expert_gate": (("layers", "experts", "we1"), (0, 0, 1)),
    "expert_up": (("layers", "experts", "we3"), (0, 0, 0)),
    "shared_gate": (("layers", "experts", "ws1"), (0, 0)),
    "shared_down": (("layers", "experts", "ws2"), (0, 0)),
    "experts_norm": (("layers", "experts", "ln2"), (0, 0)),
}, seed=3)


def _sound_name(stack: str) -> str:
    """The sound tree's stack that a changed config's stack takes its leaves
    from: a kind without the gate is a stack of another name."""
    return stack if stack in (WINDOW, FULL, "dense", "experts") \
        else stack + "_gated"


def _error(what, cfg):
    """``SMALL.error`` of ``cfg``'s program on the sound tree under
    ``cfg``'s names for its stacks (with no mesh a leaf ``cfg`` does not
    read is no error; a block is gated where it has a ``wg``)."""
    params, batch, _want = SMALL.kept(n_seqs=1)

    def stacks(pattern, sound):
        return {key: {name: leaf for name, leaf in
                      sound[_sound_name(key)].items()
                      if name != "wg" or key.endswith("_gated")}
                for key in dict.fromkeys(t._stack_of(k) for k in pattern)}
    tree = {**params, "lead": stacks(cfg.lead_pattern, params["lead"]),
            "layers": stacks(cfg.layer_pattern, params["layers"])}
    loss, grads = SMALL.plain(cfg, tree, batch)
    for part in ("lead", "layers"):
        grads[part] = {_sound_name(key): stack
                       for key, stack in grads[part].items()}
    got = {"loss": loss}
    for name, (path, index) in SMALL.LEAVES.items():
        if path[-1] == "wg" and "wg" not in grads[path[0]][path[1]]:
            continue
        got[f"grad:{name}"] = arch.get_leaves(grads, {name: (path, index)})[
            name]
    return SMALL.error(what, got=got, n_seqs=1)


def test_the_sound_program_is_inside_the_tolerance():
    assert _error("the sound program", SMALL.CFG) < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for v in SMALL.kept(n_seqs=1)[2].values())


@pytest.mark.parametrize("what, change", WRONG, ids=[w for w, _ in WRONG])
def test_a_wrong_term_fails(what, change):
    """Each moves the loss or a named gradient far beyond TOL."""
    err = _error(what, change(SMALL.CFG))
    assert err > 5 * TOL, (what, err)


# -- one mechanism, and the configurations that name none of it ---------------


#: the stacks of each accepted configuration's tree, as the parent commit
#: (91415db) names them: a kind that names neither heads nor gate keeps its
#: word's stack
PARENT_STACKS = {
    "gpt-1.3b-widths": None, "olmoe-1b-7b": None, "ouro-2.6b": None,
    "smallthinker-21b-a3b": None,
    "nemotron-3-nano-30b-a3b": ["attention", "experts", "mamba"],
    "glm-4.7-flash": ["experts", "latent"],
    "granite-4.0-h-micro": ["attention", "dense", "mamba"],
}


@pytest.mark.parametrize("name", sorted(PARENT_STACKS))
def test_a_configuration_that_names_neither_keeps_its_tree(name):
    model, config, job = arch.configs()[name]
    cfg = model(config, job)
    for kind in cfg.layer_pattern + cfg.lead_pattern:
        assert t._kind_fields(kind) == (None, False)
        assert t._kind_heads(cfg, kind) == cfg.n_heads
        if isinstance(kind[0], str):
            # the row of _BLOCK_KINDS itself: its leaves are the word's
            assert t._row(kind) is t._BLOCK_KINDS[kind[0]]
            assert t._stack_of(kind) == kind[0]
        else:
            assert t._stack_of(kind) is None
    layers = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))["layers"]
    if PARENT_STACKS[name] is None:
        assert "wq" in layers and "wg" not in layers
    else:
        assert sorted(layers) == PARENT_STACKS[name]
        assert all("wg" not in stack for stack in layers.values())


def test_a_kind_s_fields_at_their_defaults_trace_the_short_kind_s_program():
    """``("attention", window, rope)`` and the same kind with ``heads``
    None and ``gated`` False: one tree, one jaxpr, to the letter."""
    short = t.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_kv_heads=2, n_layers=4,
        d_ff=64, layer_pattern=(("attention", 8, True), ("dense",),
                                ("attention", None, False), ("dense",)),
        ffn_gated=True, tie_embeddings=False, dtype=jnp.float32)
    spelled = dataclasses.replace(short, layer_pattern=tuple(
        k + (None, False) if k[0] == "attention" else k
        for k in short.layer_pattern))
    assert spelled != short
    assert arch.grad_jaxpr(spelled) == arch.grad_jaxpr(short)
    gated = dataclasses.replace(short, layer_pattern=tuple(
        k + (None, True) if k[0] == "attention" else k
        for k in short.layer_pattern))
    assert arch.grad_jaxpr(gated) != arch.grad_jaxpr(short)


def test_two_attention_shapes_are_two_stacks_under_one_scan():
    params = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), CFG))
    assert sorted(params["layers"]) == [FULL, WINDOW, "experts"]
    assert sorted(params["lead"]) == [FULL, "dense"]
    window, full = params["layers"][WINDOW], params["layers"][FULL]
    assert window["wq"].shape == (1, 6, 32, 8 * 16)
    assert full["wq"].shape == (1, 2, 32, 6 * 16)
    assert window["wo"].shape == (1, 6, 8 * 16, 32)
    assert window["wg"].shape == (1, 6, 32, 8)
    assert full["wg"].shape == (1, 2, 32, 6)
    assert window["wk"].shape == full["wk"].shape[:1] + (6, 32, 2 * 16)
    assert params["lead"][FULL]["wq"].shape == (1, 32, 6 * 16)
    # one scan over the two periods, both shapes inside its body
    text = arch.grad_jaxpr(CFG)
    assert text.count("scan[") >= 2     # forward and its transpose
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    sh = t.param_shardings(CFG, mesh)
    assert jax.tree_util.tree_structure(sh) == \
        jax.tree_util.tree_structure(params)
    # tp splits a gate's heads as it splits the queries'
    two = build_mesh(devices=jax.devices()[:2], tp=2)
    sh = t.param_shardings(CFG, two)
    assert tuple(sh["layers"][WINDOW]["wg"].spec) == (None, None, None, "tp")
    assert tuple(sh["layers"][WINDOW]["wq"].spec) == (None, None, None, "tp")


def test_the_gate_s_scope_is_nested_in_the_attention_block_s():
    params, batch = _params(), _batch(n_seqs=1)
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    p = shard_params(params, CFG, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    text = jax.jit(t.make_grad_fn(CFG, mesh)).lower(p, tok, tgt).as_text(
        debug_info=True)
    assert scopes.ATTENTION_GATE == "hvd.attention.gate"
    assert f"{scopes.ATTENTION}/{scopes.ATTENTION_GATE}" in text
    for core in (scopes.ATTENTION_CORE_WINDOW, scopes.ATTENTION_CORE_FULL):
        assert f"{scopes.ATTENTION_CORE}/{core}" in text
    assert f"{scopes.MOE}/{scopes.MOE_SHARED}" in text


def test_what_the_kinds_do_not_admit_is_refused_by_name():
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, layer_pattern=(
            ("attention", None, True, 6, True, "more"), ("experts",)))
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, layer_pattern=(("experts", 6),))
    with pytest.raises(ValueError, match="does not divide the 5 heads"):
        dataclasses.replace(CFG, layer_pattern=(
            ("attention", None, True, 5, True), ("experts",)))
    mesh = build_mesh(devices=jax.devices()[:2], sp=2)
    with pytest.raises(NotImplementedError, match="lead_pattern.*live sp"):
        t.param_shardings(CFG, mesh)


