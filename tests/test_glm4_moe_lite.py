"""Latent attention (MLA), a dense layer leading a stack of expert layers, a
gated shared expert and a multi-token-prediction module on the main head, as
chip 0 of an expert-parallel group (ISSUE 43), in float32 at the benchmark
configuration's ``tiny`` sizes (layer 0 dense and two expert layers, the
prediction module a fourth; 4 heads of 12 + 4 with values of 16, latents of
24 and 16; 16 experts top-4 of which a share holds 2, a shared expert),
against the plain reference ``benchmarks/chip/reference/glm4_moe_lite.py``
on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums
(they read 1e-7 to 2e-6): 1e-4 is a fifth of what the least of the wrong
terms must do (``test_a_wrong_term_fails``).
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
import chip_door
from arch import TOL, get_leaves, rel as _rel
from horovod_tpu.models import latent
from horovod_tpu.models import transformer as t
from horovod_tpu.models._kinds import rmsnorm, rope
from horovod_tpu.ops import pallas_attention
from horovod_tpu.parallel import build_mesh

ARCH = arch.get("glm4_moe_lite")
adapter, reference = ARCH.adapter, ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_cell, _params, _batch = ARCH.cell, ARCH.params, ARCH.batch


def test_the_cell_keeps_every_published_width():
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    assert (cfg.d_model, cfg.q_latent, cfg.kv_latent, cfg.n_heads,
            cfg.head_dim, cfg.rope_width, cfg.dense_ff, cfg.d_ff,
            cfg.moe_shared_width) == (
                2048, 768, 512, 20, 256, 64, 10240, 1536, 1536)
    assert config["qk_nope_head_dim"] + cfg.rope_width == cfg.head_dim == \
        config["v_head_dim"]
    assert (cfg.moe_top_k, cfg.n_experts, cfg.held_experts,
            cfg.moe_routed_scale, cfg.rope_theta, cfg.norm_eps,
            cfg.mtp_depth) == (4, 64, 8, 1.8, 1e6, 1e-5, 1)
    assert (cfg.n_layers, cfg.lead_pattern, cfg.vocab_size,
            cfg.expert_share) == (
                8, (("latent",), ("dense",)), 19360, (0, 8))
    assert set(config["reduced"]) == set(config["reduced_from"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["reduced_from"] == {
        "num_hidden_layers": 47, "n_routed_experts": 64,
        "vocab_size": 154880}
    n = arch.count(arch.drawn_shapes(adapter, cfg, config))
    assert 706e6 < n < 707e6, n      # the deployment's 706.5 M parameters
    # the adapter's tree is init_params' tree
    small = dataclasses.replace(cfg, vocab_size=8, d_model=16, dense_ff=8,
                                d_ff=8, moe_shared_width=8, head_width=16,
                                rope_width=4, q_latent=8, kv_latent=8)
    arch.assert_the_adapter_s_tree_is_init_params(adapter, small, config)


def test_the_step_s_required_flops_by_hand():
    """29.7 TFLOP a step of 8192 tokens (ISSUE 43's count): latent attention
    63 % of it (its core 42, its projections 22), both heads 13, the expert
    layers 12, the dense FFN 10."""
    config, job = _cell(tiny=False)
    proj = 2 * (2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960
                + 5120 * 2048)
    core = 2 * 2 * 5120 * 8193 / 2
    dense = 3 * 2 * 2048 * 10240
    routed = 4 * 8 / 64 * 3 * 2 * 2048 * 1536
    experts = 2 * 2048 * 64 + 3 * 2 * 2048 * 1536 + routed
    head, w_eh = 2 * 2048 * 19360, 2 * 4096 * 2048
    forward = 6 * (proj + core) + dense + 5 * experts + w_eh + 2 * head
    got = adapter.flops_per_token(config, job)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert 8192 * got == pytest.approx(29.7e12, rel=5e-3)
    assert 6 * core / forward == pytest.approx(0.42, abs=0.01)
    assert 6 * proj / forward == pytest.approx(0.22, abs=0.01)
    assert 2 * head / forward == pytest.approx(0.13, abs=0.005)
    assert 5 * experts / forward == pytest.approx(0.12, abs=0.005)
    assert dense / forward == pytest.approx(0.10, abs=0.005)


def test_the_kernels_least_work_by_hand():
    gmm, fwd, bwd, xent = (
        chip_door.roofline("glm-4.7-flash.s8192", kernel) for kernel in (
            "hvd_moe_gmm", "hvd_flash_attention", "hvd_flash_bwd",
            "hvd_fused_xent"))
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    rows = 8192 * 4 * 8 / 64
    assert rows == 4096
    need = gmm(sizes)
    assert need["flops"] == 5 * 9 * 2 * rows * 2048 * 1536
    assert need["bytes"] == 5 * 9 * 2 * (rows * (2048 + 1536)
                                         + 8 * 2048 * 1536)
    one = 2 * 2 * 20 * 256 * 8192 * 8193 / 2
    need = fwd(sizes)
    # six blocks, each one's forward run again by its checkpointed backward
    assert need["flops"] == 12 * one
    assert need["bytes"] == 12 * (4 * 8192 * 20 * 256 * 2 + 20 * 8192 * 4)
    assert bwd(sizes)["flops"] == 6 * 2.5 * one
    need = xent(sizes)
    assert need["bytes"] == 2 * (2 * 8192 * 19360 * 2 + 12 * 8192)


def test_the_flash_tiles_at_the_cell_s_head_width():
    """What ``flash_blocks`` / ``flash_bwd_blocks`` pick at 8192 positions of
    256 channels, beside what they pick at 128 (no tile at 128 or 64 moves
    with this PR)."""
    assert pallas_attention.flash_blocks(8192, 8192, 256, jnp.bfloat16) == (
        1024, 1024)
    # (the score tile in pieces of 128 k rows since PR 56: 8.6 MiB of the 16)
    assert pallas_attention.flash_vmem_bytes(1024, 1024, 256, 2) == \
        9043968 <= pallas_attention.VMEM_BUDGET
    # (512 x 512 until PR 58 counted a clean tile's scores as the compiler
    # does: 31.1 MiB of BWD_VMEM_BUDGET's 32)
    assert pallas_attention.flash_bwd_blocks(
        8192, 8192, 256, jnp.bfloat16) == (1024, 1024, 8192)
    assert pallas_attention.flash_blocks(8192, 8192, 128, jnp.bfloat16) == (
        1024, 1024)
    assert pallas_attention.flash_bwd_blocks(
        8192, 8192, 128, jnp.bfloat16) == (1024, 1024, 8192)
    assert pallas_attention.flash_eligible(8192, 8192, 256)


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("what", ["loss", "main_loss", "mtp_loss"]
                         + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_loss_is_its_two_parts_and_the_step_reports_its_rows():
    got, _want, aux, grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows",
                        "main_loss", "mtp_loss"}
    assert float(got["loss"]) == pytest.approx(
        float(aux["main_loss"]) + 0.3 * float(aux["mtp_loss"]), rel=1e-6)
    assert float(aux["dropped"]) == 0.0 and float(aux["aux_loss"]) == 0.0
    # 3 expert layers (two of the stack, the module's) x 128 tokens x top-4,
    # of which 2 of 16 experts are held: an eighth, give or take the
    # router's preferences
    every = 3 * 128 * CFG.moe_top_k
    assert 0.04 * every < float(aux["held_rows"]) < 0.3 * every
    for experts in (grads["layers"]["experts"],
                    grads["mtp"]["layers"]["experts"]):
        assert not np.any(np.asarray(experts["router_bias"]))
    # the main stack's choices are the reference's, layer by layer
    params, batch = _params(), _batch()
    ours = t.router_choices(params, batch["tokens"], CFG)
    with jax.default_matmul_precision("highest"):
        theirs = reference.forward(params, batch["tokens"],
                                   batch["targets"], SIZES)[2]
    assert ours.shape == (2, 128, CFG.moe_top_k)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))


def test_the_rope_key_is_one_head_that_every_query_head_reads():
    """``_up`` hands ``attend`` 4 / 4 heads whose last ``rope_width`` key
    channels are the same for every head, rotated; the first are each
    head's own."""
    params = _params()
    p = jax.tree_util.tree_map(lambda a: a[0], params["lead"]["latent"])
    h = jnp.asarray(np.random.RandomState(3).randn(2, 64, CFG.d_model),
                    jnp.float32)
    positions = jnp.arange(64)
    c_q, c_kv, k_r = latent._down(p, h, CFG)
    assert (c_q.shape, c_kv.shape, k_r.shape) == (
        (2, 64, 24), (2, 64, 16), (2, 64, 4))
    q, k, v = latent._up(p, c_q, c_kv, k_r, positions, CFG)
    assert q.shape == k.shape == v.shape == (2, 64, 4, 16)
    np.testing.assert_array_equal(k[:, :, 0, 12:], k[:, :, 3, 12:])
    np.testing.assert_allclose(
        k[:, :, 0, 12:], rope(k_r[:, :, None], positions, CFG.rope_theta
                              )[:, :, 0], rtol=1e-6)
    assert _rel(k[:, :, 0, :12], k[:, :, 3, :12]) > 0.5
    # position 0 is rotated by nothing
    np.testing.assert_allclose(k[:, 0, 0, 12:], k_r[:, 0], rtol=1e-6)


# -- what TOL must not let through --------------------------------------------

#: layer 0 dense, one expert layer, the prediction module
SMALL = ARCH.cut({"num_hidden_layers": 2}, {
    "lm_head": (("lm_head",), None),
    "embed": (("embed",), None),
    "first_query_down": (("lead", "latent", "wqa"), (0,)),
    "kv_down": (("layers", "latent", "wkva"), (0, 0)),
    "kv_up": (("layers", "latent", "wkvb"), (0, 0)),
    "dense_down": (("lead", "dense", "w2"), (0,)),
    "router": (("layers", "experts", "router"), (0, 0)),
    "shared_down": (("layers", "experts", "ws2"), (0, 0)),
    "mtp_proj": (("mtp", "proj"), None),
})


def _merged(a, b):
    """``a`` with the leaves ``b`` has and ``a`` lacks."""
    if not isinstance(a, dict):
        return a
    return {**b, **{k: _merged(v, b[k]) if k in b else v
                    for k, v in a.items()}}


def _small_error(what, cfg):
    tree = None
    if cfg.lead_pattern != SMALL.CFG.lead_pattern \
            or cfg.layer_pattern != SMALL.CFG.layer_pattern:
        # the blocks the sound tree lacks, drawn for this stack
        tree = _merged(SMALL.kept()[0], SMALL.params(cfg, seed=1))
    return SMALL.error(what, cfg, tree)


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.sound < TOL


_sound_rotate, _sound_down = latent._rotate, latent._down
_sound_shared, _sound_mtp_input = t._shared_expert, t._mtp_input
_sound_mtp_loss, _sound_attend = t._mtp_loss, pallas_attention.attend


def _rope_on_the_whole_head(q, k_r, positions, cfg):
    return (rope(q, positions, cfg.rope_theta),
            rope(k_r, positions, cfg.rope_theta))


def _no_rope_on_the_shared_key(q, k_r, positions, cfg):
    return _sound_rotate(q, k_r, positions, cfg)[0], k_r


def _no_norm_on_the_query_latent(p, h, cfg):
    return _sound_down({**p, "q_latent_norm": None}, h, cfg)


def _no_norm_on_the_kv_latent(p, h, cfg):
    return _sound_down({**p, "kv_latent_norm": None}, h, cfg)


def _norm_or_not(x, g, eps=1e-6):
    return x if g is None else rmsnorm(x, g, eps)


def _the_rope_key_normed(p, h, cfg):
    c_q, c_kv, k_r = _sound_down(p, h, cfg)
    return c_q, c_kv, rmsnorm(k_r, jnp.ones(k_r.shape[-1]), cfg.norm_eps)


def _scale_of_the_position_free_part(q, k, v, causal=True, scale=None,
                                     **kw):
    nope = SMALL.CFG.head_dim - SMALL.CFG.rope_width
    return _sound_attend(q, k, v, causal=causal, scale=nope ** -0.5, **kw)


def _shared_expert_without_its_gate(p, toks, activation):
    return _sound_shared({k: v for k, v in p.items() if k != "ws3"}, toks,
                         activation)


def _target_of_the_first_head(targets):
    return targets


def _state_after_the_final_norm(params, state, targets, cfg):
    return _sound_mtp_input(
        params, rmsnorm(state, params["ln_f"], cfg.norm_eps), targets, cfg)


def _embedding_without_its_norm(params, state, targets, cfg):
    # (a norm whose weight undoes it: the table's rows as they are)
    nxt = t._embed_lookup(params["embed"], targets, cfg)
    undo = jnp.sqrt(jnp.mean(jnp.square(nxt), -1, keepdims=True)
                    + cfg.norm_eps)
    mp = params["mtp"]
    return jnp.concatenate(
        [rmsnorm(state, mp["norm_h"], cfg.norm_eps),
         rmsnorm(nxt, mp["norm_e"], cfg.norm_eps) * undo], -1) @ mp["proj"]


def _a_head_of_its_own(params, state, targets, head, positions, cfg):
    own = jnp.asarray(np.random.RandomState(9).randn(*head.shape)
                      / np.sqrt(head.shape[0]), head.dtype)
    return _sound_mtp_loss(params, state, targets, own, positions, cfg)


@pytest.mark.parametrize("what, change", [
    ("rope on the whole head, not its last channels",
     {"patch": (latent, "_rotate", _rope_on_the_whole_head)}),
    ("no rope on the shared key",
     {"patch": (latent, "_rotate", _no_rope_on_the_shared_key)}),
    ("the norm on the queries' latent left out",
     {"patch": (latent, "_down", _no_norm_on_the_query_latent),
      "also": (latent, "rmsnorm", _norm_or_not)}),
    ("the norm on the keys' and values' latent left out",
     {"patch": (latent, "_down", _no_norm_on_the_kv_latent),
      "also": (latent, "rmsnorm", _norm_or_not)}),
    ("the rope key normed",
     {"patch": (latent, "_down", _the_rope_key_normed)}),
    ("the scale of the position-free part alone",
     {"patch": (pallas_attention, "attend",
                _scale_of_the_position_free_part)}),
    ("experts in layer 0",
     {"cfg": {"lead_pattern": (("latent",), ("experts",))}}),
    ("a dense FFN after layer 0",
     {"cfg": {"layer_pattern": (("latent",), ("dense",))}}),
    ("the shared expert without its gate",
     {"patch": (t, "_shared_expert", _shared_expert_without_its_gate)}),
    ("the scaling factor left out", {"cfg": {"moe_routed_scale": 1.0}}),
    ("the renormalisation left out", {"cfg": {"moe_renormalize": False}}),
    ("softmax for sigmoid", {"cfg": {"moe_router_scores": "softmax"}}),
    ("the prediction loss left out", {"cfg": {"mtp_weight": 0.0}}),
    ("the prediction loss weighted 1", {"cfg": {"mtp_weight": 1.0}}),
    ("the prediction's target the first head's",
     {"patch": (t, "_mtp_targets", _target_of_the_first_head)}),
    ("the prediction module reading the state after the final norm",
     {"patch": (t, "_mtp_input", _state_after_the_final_norm)}),
    ("the prediction module reading the embedding without its norm",
     {"patch": (t, "_mtp_input", _embedding_without_its_norm)}),
    ("a head of its own in the prediction module",
     {"patch": (t, "_mtp_loss", _a_head_of_its_own)}),
])
def test_a_wrong_term_fails(monkeypatch, what, change):
    """Each moves the loss or a named gradient of a stack of the dense
    layer, one expert layer and the prediction module far beyond TOL."""
    for key in ("patch", "also"):
        if key in change:
            monkeypatch.setattr(*change[key])
    cfg = dataclasses.replace(SMALL.CFG, **change.get("cfg", {}))
    err = _small_error(what, cfg)
    assert err > 5 * TOL, (what, err)


# -- a table and a head read twice --------------------------------------------

def _second_lookup_cut(params, state, targets, cfg):
    return _sound_mtp_input(
        {**params, "embed": jax.lax.stop_gradient(params["embed"])}, state,
        targets, cfg)


def _second_head_cut(params, state, targets, head, positions, cfg):
    return _sound_mtp_loss(params, state, targets,
                           jax.lax.stop_gradient(head), positions, cfg)


@pytest.mark.parametrize("leaf, patch", [
    ("embed", ("_mtp_input", _second_lookup_cut)),
    ("lm_head", ("_mtp_loss", _second_head_cut))])
def test_a_gradient_is_the_sum_of_its_two_uses(monkeypatch, leaf, patch):
    """The embedding is read for the tokens and again for the next tokens,
    the head by both predictions: the program's gradient of each is the
    reference's (``jax.grad`` through both uses), and with the second use's
    cotangent cut it is far from it, while every other named gradient is
    what it was."""
    params, batch, want = SMALL.kept()
    _loss, grads = SMALL.sound_grads
    assert _rel(grads[leaf], want[f"grad:{leaf}"]) < TOL
    monkeypatch.setattr(t, *patch)
    _loss, cut = SMALL.plain(SMALL.CFG, params, batch)
    assert _rel(cut[leaf], want[f"grad:{leaf}"]) > 50 * TOL
    others = {name: spec for name, spec in SMALL.LEAVES.items()
              if name not in ("embed", "lm_head")}
    for name, got in get_leaves(cut, others).items():
        assert _rel(got, want[f"grad:{name}"]) < TOL, name


# -- what is refused, by name -------------------------------------------------

@pytest.mark.parametrize("axis", ["sp", "pp", "tp"])
def test_the_new_blocks_on_a_live_axis_are_refused_by_name(axis):
    mesh = build_mesh(devices=jax.devices()[:2], **{axis: 2})
    name = (r"lead_pattern and mtp_depth on a live " + axis
            if axis != "tp" else r"latent.*live tp")
    with pytest.raises(NotImplementedError, match=name):
        t.param_shardings(CFG, mesh)
    with pytest.raises(NotImplementedError, match=name):
        t.make_grad_fn(CFG, mesh)
    # the periodic stack alone names the latent block
    alone = dataclasses.replace(CFG, lead_pattern=(), mtp_depth=0)
    with pytest.raises(NotImplementedError, match=rf"latent.*live {axis}"):
        t.param_shardings(alone, mesh)
    # and a dense block leading a stack of expert blocks shards over tp
    rest = dataclasses.replace(
        CFG, lead_pattern=(("dense",),), mtp_depth=0, n_layers=2,
        layer_pattern=(("experts",),))
    if axis == "tp":
        spec = t.param_shardings(rest, mesh)["lead"]["dense"]["w1"].spec
        assert tuple(spec) == (None, None, "tp")


def test_a_looped_stack_with_the_new_fields_is_refused_by_name():
    for fields in ({"mtp_depth": 0}, {"lead_pattern": ()}):
        with pytest.raises(NotImplementedError,
                           match="n_loops=2 with lead_pattern or mtp_depth"):
            dataclasses.replace(CFG, n_loops=2, **fields)
    with pytest.raises(NotImplementedError, match="mtp_depth=2"):
        dataclasses.replace(CFG, mtp_depth=2)
    with pytest.raises(ValueError, match="lead_pattern.*two-sublayer"):
        t.TransformerConfig(lead_pattern=(("dense",),))
    with pytest.raises(ValueError, match="kv_latent=0"):
        dataclasses.replace(CFG, kv_latent=0)
    # (no query latent is a form of its own since PR 66: another tree)
    assert dataclasses.replace(CFG, q_latent=0).q_latent == 0
    with pytest.raises(ValueError, match="n_kv_heads, qk_norm or post_norm"):
        dataclasses.replace(CFG, qk_norm=True)


