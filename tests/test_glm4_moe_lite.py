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
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.models import decode, latent
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.models._kinds import rmsnorm, rope
from horovod_tpu.ops import pallas_attention
from horovod_tpu.parallel import build_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from adapters import glm4_moe_lite as adapter            # noqa: E402
from reference import glm4_moe_lite as reference         # noqa: E402
from trees import get_leaves                              # noqa: E402

TOL = 1e-4


def _cell(tiny: bool):
    with open(os.path.join(_CHIP, "configs", "glm-4.7-flash.json")) as f:
        config = json.load(f)
    with open(os.path.join(_CHIP, "workloads",
                           "train.s8192.b1.latent.json")) as f:
        job = json.load(f)
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


CONFIG, JOB = _cell(tiny=True)
SIZES = adapter.shapes(CONFIG, JOB)
CFG = adapter._model_config(CONFIG, JOB)
LEAVES = {
    **adapter._leaf_paths(SIZES["expert_layers"]),
    "embed": (("embed",), None),
    "final_norm": (("ln_f",), None),
    "query_norm": (("layers", "latent", "q_latent_norm"), (0, 0)),
    "kv_norm": (("layers", "latent", "kv_latent_norm"), (0, 1)),
    "query_up": (("layers", "latent", "wqb"), (0, 0)),
    "attention_out": (("lead", "latent", "wo"), (0,)),
    "dense_gate": (("lead", "dense", "w1"), (0,)),
    "dense_up": (("lead", "dense", "w3"), (0,)),
    "first_router": (("layers", "experts", "router"), (0, 0)),
    "expert_gate": (("layers", "experts", "we1"), (0, 1, 1)),
    "expert_up": (("layers", "experts", "we3"), (0, 0, 0)),
    "shared_gate": (("layers", "experts", "ws1"), (0, 1)),
    "shared_up": (("layers", "experts", "ws3"), (0, 0)),
    "mtp_state_norm": (("mtp", "norm_h"), None),
    "mtp_token_norm": (("mtp", "norm_e"), None),
    "mtp_final_norm": (("mtp", "ln_f"), None),
    "mtp_kv_down": (("mtp", "layers", "latent", "wkva"), (0,)),
    "mtp_router": (("mtp", "layers", "experts", "router"), (0,)),
    "mtp_experts_down": (("mtp", "layers", "experts", "we2"), (0,)),
}


def _params(cfg=CFG, seed=0):
    """``init_params``' tree with every norm's weight moved off 1 (a norm
    after a norm is no change while both weights are 1: what the prediction
    module reads would not show)."""
    rng = np.random.RandomState(seed + 100)
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(
            1 + 0.3 * rng.randn(*a.shape).astype(np.float32)
            if np.all(a == 1) else a),
        t.init_params(np.random.RandomState(seed), cfg, 1))


def _batch(n_seqs=2, seed=0):
    return jax.tree_util.tree_map(
        jnp.asarray, adapter.host_batch(CONFIG, JOB, seed, 0, n_seqs))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


def _program(cfg, params, batch):
    """(loss, aux, gradients) on a mesh of one device, through
    ``make_grad_fn`` as the benchmark's adapter calls it."""
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    p = shard_params(params, cfg, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    loss, aux, grads = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
    return loss + aux["aux_loss"], aux, grads


def _plain_grads(cfg, params, batch):
    """Loss and gradients with no mesh (a tree that holds a leaf ``cfg``
    does not read is no error here)."""
    def loss_fn(p):
        loss, aux = t.forward_loss_spmd(p, batch["tokens"],
                                        batch["targets"], cfg)
        return loss + aux["aux_loss"]
    return jax.jit(jax.value_and_grad(loss_fn))(params)


def test_the_tiny_preset_is_the_one_the_issue_asks_for():
    assert CFG.dtype == jnp.float32 and CFG.one_sublayer
    assert CFG.layer_pattern == (("latent",), ("experts",))
    assert CFG.lead_pattern == (("latent",), ("dense",))
    assert (CFG.n_layers, CFG.mtp_depth, CFG.mtp_weight) == (4, 1, 0.3)
    assert (CFG.n_heads, CFG.kv_heads, CFG.head_dim, CFG.rope_width,
            CFG.q_latent, CFG.kv_latent) == (4, 4, 16, 4, 24, 16)
    assert (CFG.d_ff, CFG.dense_ff, CFG.moe_shared_width) == (32, 96, 32)
    assert (CFG.n_experts, CFG.moe_top_k, CFG.held_experts,
            CFG.expert_share) == (16, 4, 2, (0, 8))
    assert (CFG.moe_router_scores, CFG.moe_activation, CFG.moe_gated,
            CFG.ffn_gated, CFG.moe_routed_scale, CFG.moe_renormalize,
            CFG.moe_balance_weight, CFG.tie_embeddings) == (
                "sigmoid", "silu", True, True, 1.8, True, 0.0, False)


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
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(adapter._init_function(cfg, config),
                       jax.random.PRNGKey(0))))
    assert 706e6 < n < 707e6, n      # the deployment's 706.5 M parameters
    # the adapter's tree is init_params' tree
    want = jax.eval_shape(lambda: t.init_params(
        np.random.RandomState(0), dataclasses.replace(cfg, vocab_size=8)))
    got = jax.eval_shape(adapter._init_function(
        dataclasses.replace(cfg, vocab_size=8), config),
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, got) == \
        jax.tree_util.tree_map(lambda a: a.shape, want)


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
    import roofline_latent_flash_attention as fwd
    import roofline_latent_flash_attention_backward as bwd
    import roofline_latent_head_xent as xent
    import roofline_latent_moe_gmm as gmm
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    rows = 8192 * 4 * 8 / 64
    assert rows == 4096
    need = gmm.latent_moe_gmm(sizes)
    assert need["flops"] == 5 * 9 * 2 * rows * 2048 * 1536
    assert need["bytes"] == 5 * 9 * 2 * (rows * (2048 + 1536)
                                         + 8 * 2048 * 1536)
    one = 2 * 2 * 20 * 256 * 8192 * 8193 / 2
    need = fwd.latent_flash_attention(sizes)
    # six blocks, each one's forward run again by its checkpointed backward
    assert need["flops"] == 12 * one
    assert need["bytes"] == 12 * (4 * 8192 * 20 * 256 * 2 + 20 * 8192 * 4)
    assert bwd.latent_flash_attention_backward(sizes)["flops"] == \
        6 * 2.5 * one
    need = xent.latent_head_xent(sizes)
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
    assert pallas_attention.flash_bwd_blocks(
        8192, 8192, 256, jnp.bfloat16) == (512, 512, 8192)
    assert pallas_attention.flash_blocks(8192, 8192, 128, jnp.bfloat16) == (
        1024, 1024)
    assert pallas_attention.flash_bwd_blocks(
        8192, 8192, 128, jnp.bfloat16) == (1024, 1024, 8192)
    assert pallas_attention.flash_eligible(8192, 8192, 256)


# -- the program against the reference ----------------------------------------

@pytest.fixture(scope="module")
def both_sides():
    params, batch = _params(), _batch()
    loss, aux, grads = _program(CFG, params, batch)
    got = {"loss": loss, "main_loss": aux["main_loss"],
           "mtp_loss": aux["mtp_loss"],
           **{f"grad:{k}": v for k, v in get_leaves(grads, LEAVES).items()}}
    want_loss, want_grads = reference.loss_and_grads(params, LEAVES, batch,
                                                     SIZES)
    with jax.default_matmul_precision("highest"):
        parts = reference.losses(params, batch, SIZES)
    want = {"loss": want_loss, "main_loss": parts[1], "mtp_loss": parts[5],
            **{f"grad:{k}": v for k, v in want_grads.items()}}
    return got, want, aux, grads


@pytest.mark.parametrize("what", ["loss", "main_loss", "mtp_loss"]
                         + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(both_sides, what):
    got, want, _aux, _grads = both_sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_loss_is_its_two_parts_and_the_step_reports_its_rows(both_sides):
    got, _want, aux, grads = both_sides
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
SMALL_CONFIG = {**CONFIG, "num_hidden_layers": 2}
SMALL = adapter._model_config(SMALL_CONFIG, JOB)
SMALL_SIZES = adapter.shapes(SMALL_CONFIG, JOB)
SMALL_LEAVES = {
    "lm_head": (("lm_head",), None),
    "embed": (("embed",), None),
    "first_query_down": (("lead", "latent", "wqa"), (0,)),
    "kv_down": (("layers", "latent", "wkva"), (0, 0)),
    "kv_up": (("layers", "latent", "wkvb"), (0, 0)),
    "dense_down": (("lead", "dense", "w2"), (0,)),
    "router": (("layers", "experts", "router"), (0, 0)),
    "shared_down": (("layers", "experts", "ws2"), (0, 0)),
    "mtp_proj": (("mtp", "proj"), None),
}


def _merged(a, b):
    """``a`` with the leaves ``b`` has and ``a`` lacks."""
    if not isinstance(a, dict):
        return a
    return {**b, **{k: _merged(v, b[k]) if k in b else v
                    for k, v in a.items()}}


@pytest.fixture(scope="module")
def small_reference():
    params, batch = _params(SMALL), _batch()
    want_loss, want = reference.loss_and_grads(params, SMALL_LEAVES, batch,
                                               SMALL_SIZES)
    return params, batch, want_loss, want


def _small_error(cfg, small_reference):
    params, batch, want_loss, want = small_reference
    if cfg.lead_pattern != SMALL.lead_pattern \
            or cfg.layer_pattern != SMALL.layer_pattern:
        # the blocks the sound tree lacks, drawn for this stack
        params = _merged(params, _params(cfg, seed=1))
    loss, grads = _plain_grads(cfg, params, batch)
    return max([_rel(loss, want_loss)] + [
        _rel(v, want[k])
        for k, v in get_leaves(grads, SMALL_LEAVES).items()])


def test_the_sound_small_stack_matches_the_reference(small_reference):
    assert _small_error(SMALL, small_reference) < TOL


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
    nope = SMALL.head_dim - SMALL.rope_width
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
def test_a_wrong_term_fails(monkeypatch, small_reference, what, change):
    """Each moves the loss or a named gradient of a stack of the dense
    layer, one expert layer and the prediction module far beyond TOL."""
    for key in ("patch", "also"):
        if key in change:
            monkeypatch.setattr(*change[key])
    cfg = dataclasses.replace(SMALL, **change.get("cfg", {}))
    err = _small_error(cfg, small_reference)
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
def test_a_gradient_is_the_sum_of_its_two_uses(monkeypatch, small_reference,
                                               leaf, patch):
    """The embedding is read for the tokens and again for the next tokens,
    the head by both predictions: the program's gradient of each is the
    reference's (``jax.grad`` through both uses), and with the second use's
    cotangent cut it is far from it, while every other named gradient is
    what it was."""
    params, batch, _want_loss, want = small_reference
    _loss, grads = _plain_grads(SMALL, params, batch)
    assert _rel(grads[leaf], want[leaf]) < TOL
    monkeypatch.setattr(t, *patch)
    _loss, cut = _plain_grads(SMALL, params, batch)
    assert _rel(cut[leaf], want[leaf]) > 50 * TOL
    others = {name: spec for name, spec in SMALL_LEAVES.items()
              if name not in ("embed", "lm_head")}
    for name, got in get_leaves(cut, others).items():
        assert _rel(got, want[name]) < TOL, name


# -- the share cut: one expert layer ------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts that the eight
    shares compute and the shared expert counted ONCE are what the uncut
    reference gives for the whole layer; between them the shares hold
    every assignment once."""
    cfg = dataclasses.replace(CFG, expert_share=(0, 1))
    rng = np.random.RandomState(0)
    m, f, fs, e = cfg.d_model, cfg.d_ff, cfg.moe_shared_width, cfg.n_experts
    h = jnp.asarray(rng.randn(1, 96, m), jnp.float32)

    def w(*shape, scale=1 / 8):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    p = {"router": w(m, e, scale=0.3), "router_bias": w(e, scale=0.1),
         "we1": w(e, m, f), "we3": w(e, m, f), "we2": w(e, f, m),
         "ws1": w(m, fs), "ws3": w(m, fs), "ws2": w(fs, m)}
    sizes = {**SIZES, "experts": e, "first_expert": 0, "held_experts": e}
    with jax.default_matmul_precision("highest"):
        want, _choice = reference.expert_layer(p, h[0], sizes)
        shared = want - reference.expert_layer(p, h[0], sizes,
                                               shared=False)[0]
    parts, held_rows = [], []
    for i in range(8):
        share = dataclasses.replace(cfg, expert_share=(i, 8))
        held = {k: v[2 * i:2 * i + 2] if k in ("we1", "we2", "we3") else v
                for k, v in p.items()}
        y, aux = t._moe_ffn(held, h, share)
        assert float(aux["dropped"]) == 0.0
        parts.append(y[0])
        held_rows.append(float(aux["held_rows"]))
    routed = [part - shared for part in parts]
    assert _rel(sum(routed) + shared, want) < TOL
    assert sum(held_rows) == 96 * cfg.moe_top_k
    # the shares' outputs summed count the shared expert eight times
    assert _rel(sum(parts), want) > 1.0
    # no share is the whole, and the layer that holds every expert is
    assert _rel(routed[0] + shared, want) > 0.3
    y, aux = t._moe_ffn(p, h, cfg)
    assert _rel(y[0], want) < TOL and "held_rows" not in aux


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
    with pytest.raises(ValueError, match="q_latent=0"):
        dataclasses.replace(CFG, q_latent=0)
    with pytest.raises(ValueError, match="n_kv_heads, qk_norm or post_norm"):
        dataclasses.replace(CFG, qk_norm=True)


def test_the_decode_paths_refuse_the_new_fields_by_name():
    params = _params()
    for field, cfg in [
            ("kv_latent", CFG), ("lead_pattern", CFG), ("mtp_depth", CFG),
            ("mtp_depth", t.TransformerConfig(mtp_depth=1))]:
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
    with pytest.raises(NotImplementedError, match="dense GPT block"):
        decode.flatten_decode_params(params)
