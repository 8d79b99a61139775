"""A stack of one-sublayer blocks: Mamba-2 mixers with a chunked scan,
sigmoid-routed ungated ReLU² experts with a shared expert, attention without
positions, as chip 0 of an expert-parallel group (ISSUE 39), in float32 at
the benchmark configuration's ``tiny`` sizes (two periods of MEMEMEM*E, 4
Mamba heads of 8 with state 16 in 2 groups, four chunks of 16 in 64
positions, 16 experts top-4 of which a share holds 2, a shared expert),
against the plain reference ``benchmarks/chip/reference/nemotron_h.py`` on
seeded weights; the reference runs the recurrence one position at a time.

TOL: both sides are float32 here and differ in the order of their sums (the
chunked form against the step-by-step one most of all; they read 1e-6 to
1e-5): 1e-4 is a fifth of what the least of the wrong terms does to a
stack of one block a kind (``test_a_wrong_term_fails``) and an eighth of
what bfloat16 decays do to the scan
(``test_the_scan_s_decays_in_bfloat16_fail``).
"""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.models import decode, mamba
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.parallel import build_mesh, moe

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from adapters import nemotron_h as adapter            # noqa: E402
from reference import nemotron_h as reference         # noqa: E402
from trees import get_leaves                           # noqa: E402

TOL = 1e-4


def _cell(tiny: bool):
    with open(os.path.join(_CHIP, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(_CHIP, "workloads",
                           "train.s8192.b1.hybrid.json")) as f:
        job = json.load(f)
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


CONFIG, JOB = _cell(tiny=True)
SIZES = adapter.shapes(CONFIG, JOB)
CFG = adapter._model_config(CONFIG, JOB)
PATTERN = CONFIG["hybrid_override_pattern"]
LEAVES = {
    **adapter._leaf_paths(PATTERN),
    "embed": (("embed",), None),
    "conv_taps": (("layers", "mamba", "ssm_conv_w"), (0, 1)),
    "conv_bias": (("layers", "mamba", "ssm_conv_b"), (0, 2)),
    "dt_bias": (("layers", "mamba", "ssm_dt_bias"), (0, 4)),
    "skip": (("layers", "mamba", "ssm_d"), (0, 5)),
    "gate_norm": (("layers", "mamba", "ssm_norm"), (0, 6)),
    "ssm_out": (("layers", "mamba", "ssm_out"), (0, 7)),
    "mamba_norm": (("layers", "mamba", "ln1"), (0, 3)),
    "query": (("layers", "attention", "wq"), (0, 1)),
    "first_router": (("layers", "experts", "router"), (0, 0)),
    "expert_up": (("layers", "experts", "we1"), (0, 5, 1)),
    "shared_up": (("layers", "experts", "ws1"), (0, 2)),
}


def _params(cfg=CFG, seed=0):
    return jax.tree_util.tree_map(
        jnp.asarray, t.init_params(np.random.RandomState(seed), cfg, 1))


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
    assert CFG.dtype == jnp.float32 and CFG.n_layers == 18
    assert CFG.layer_pattern == tuple(adapter.KINDS[c] for c in "MEMEMEM*E")
    assert CFG.one_sublayer and PATTERN == "MEMEMEM*E" * 2
    assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state, CFG.ssm_groups,
            CFG.ssm_conv, CFG.ssm_chunk) == (4, 8, 16, 2, 4, 16)
    assert CFG.ssm_inner == 32 != CONFIG["expand"] * CFG.d_model
    assert JOB["seq_len"] == 4 * CFG.ssm_chunk
    assert (CFG.n_heads, CFG.kv_heads, CFG.head_dim) == (4, 2, 16)
    assert (CFG.n_experts, CFG.moe_top_k, CFG.held_experts,
            CFG.expert_share) == (16, 4, 2, (0, 8))
    assert (CFG.moe_router_scores, CFG.moe_activation, CFG.moe_gated,
            CFG.moe_routed_scale, CFG.moe_shared_width,
            CFG.moe_renormalize, CFG.moe_balance_weight) == (
                "sigmoid", "relu2", False, 2.5, 64, True, 0.0)


def test_the_cell_keeps_every_published_width():
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    assert (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk) == (
                2688, 64, 64, 128, 8, 4, 128)
    assert (cfg.ssm_inner, cfg.ssm_conv_width) == (4096, 6144)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 2, 128)
    assert (cfg.d_ff, cfg.moe_shared_width, cfg.moe_top_k, cfg.n_experts,
            cfg.held_experts, cfg.moe_routed_scale, cfg.norm_eps) == (
                1856, 3712, 6, 128, 8, 2.5, 1e-5)
    assert cfg.layer_pattern == tuple(adapter.KINDS[c] for c in "MEMEMEM*E")
    assert (cfg.n_layers, cfg.vocab_size, cfg.expert_share) == (
        9, 16384, (0, 16))
    assert set(config["reduced"]) == set(config["reduced_from"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(adapter._init_function(cfg, config),
                       jax.random.PRNGKey(0))))
    assert 666e6 < n < 668e6, n      # the deployment's 667 M parameters


def test_the_step_s_required_flops_by_hand():
    """17.6 TFLOP a step of 8192 tokens (ISSUE 39's count), the Mamba
    blocks 45 % of it, the routed experts 4 %, the sliced head 12 %."""
    config, job = _cell(tiny=False)
    mamba = (2 * 2688 * 10304 + 2 * 4096 * 2688 + 2 * 4 * 6144
             + 2 * 8 * 128 * 64.5 + 2 * 64 * 64 * 64.5
             + 2 * 2 * 64 * 64 * 128)
    attention = (2 * 2 * 2688 * 4096 + 2 * 2 * 2688 * 256
                 + 2 * 2 * 4096 * 8193 / 2)
    routed = 6 * 8 / 128 * 2 * 2 * 2688 * 1856
    experts = 2 * 2688 * 128 + 2 * 2 * 2688 * 3712 + routed
    head = 2 * 2688 * 16384
    forward = 4 * mamba + attention + 4 * experts + head
    got = adapter.flops_per_token(config, job)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert 8192 * got == pytest.approx(17.6e12, rel=5e-3)
    assert 4 * mamba / forward == pytest.approx(0.45, abs=0.01)
    assert 4 * routed / forward == pytest.approx(0.04, abs=0.005)
    assert head / forward == pytest.approx(0.12, abs=0.005)


def test_the_kernels_least_work_by_hand():
    import roofline_hybrid_flash_attention as fwd
    import roofline_hybrid_flash_attention_backward as bwd
    import roofline_hybrid_moe_gmm as gmm
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    rows = 8192 * 6 * 8 / 128
    assert rows == 3072
    need = gmm.hybrid_moe_gmm(sizes)
    assert need["flops"] == 4 * 6 * 2 * rows * 2688 * 1856
    assert need["bytes"] == 4 * 6 * 2 * (rows * (2688 + 1856)
                                         + 8 * 2688 * 1856)
    need = fwd.hybrid_flash_attention(sizes)
    assert need["flops"] == 2 * 2 * 32 * 128 * 8192 * 8193 / 2
    assert need["bytes"] == 2 * 8192 * (32 + 2) * 128 * 2 + 32 * 8192 * 4
    assert bwd.hybrid_flash_attention_backward(sizes)["flops"] == \
        2.5 * need["flops"]


def test_the_scan_kernels_least_work_by_hand():
    """Four Mamba blocks, two forward calls and one backward call each, at
    8192 positions of 64 heads of 64, 8 groups, state 128, chunk 128."""
    import roofline_hybrid_ssm_scan as scan
    config, job = _cell(tiny=False)
    need = scan.hybrid_ssm_scan(adapter.shapes(config, job))
    scores, weighted = 2 * 8 * 128 * 64.5, 2 * 4096 * 64.5
    state = 2 * 4096 * 128
    forward = scores + weighted + 2 * state
    assert need["flops"] == 4 * 8192 * (
        2 * forward + 2 * forward + scores + state)
    x, bc, sums, y = 4096 * 2, 2 * 1024 * 2, 2 * 64 * 4, 4096 * 4
    assert need["bytes"] == 4 * 8192 * (
        2 * (x + bc + sums + y) + (x + bc + sums + y) + (x + bc + sums)
        + 2 * 4096 * 128 * 4 / 128)
    # 0.24 GB a forward call, the scan's matmuls 1/20 of a block's
    assert 0.23e9 < 8192 * (x + bc + sums + y) < 0.25e9
    assert need["bytes"] / 819e9 > need["flops"] / 197e12


# -- the program against the reference ---------------------------------------

@pytest.fixture(scope="module")
def both_sides():
    params, batch = _params(), _batch()
    loss, aux, grads = _program(CFG, params, batch)
    got = {"loss": loss,
           **{f"grad:{k}": v for k, v in get_leaves(grads, LEAVES).items()}}
    want_loss, want_grads = reference.loss_and_grads(params, LEAVES, batch,
                                                     SIZES)
    want = {"loss": want_loss,
            **{f"grad:{k}": v for k, v in want_grads.items()}}
    return got, want, aux, grads


@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(both_sides, what):
    got, want, _aux, _grads = both_sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_its_rows_and_no_gradient_reaches_the_bias(
        both_sides):
    _got, _want, aux, grads = both_sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    assert float(aux["dropped"]) == 0.0 and float(aux["aux_loss"]) == 0.0
    # 8 expert blocks x 128 tokens x top-4, of which 2 of 16 experts are
    # held: an eighth, give or take the router's preferences
    every = PATTERN.count("E") * 128 * CFG.moe_top_k
    assert 0.05 * every < float(aux["held_rows"]) < 0.25 * every
    assert not np.any(np.asarray(grads["layers"]["experts"]["router_bias"]))
    # the choices are the reference's, block by block
    params, batch = _params(), _batch()
    ours = t.router_choices(params, batch["tokens"], CFG)
    with jax.default_matmul_precision("highest"):
        theirs = reference.forward(params, batch["tokens"], SIZES)[1]
    assert ours.shape == (PATTERN.count("E"), 128, CFG.moe_top_k)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))


def test_the_correction_bias_moves_the_choice_and_no_weight():
    """A bias that favours expert 3 puts it among every token's choices;
    the weights stay the chosen scores, renormalised and scaled."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(32, 16), jnp.float32)
    bias = jnp.zeros(16).at[3].set(10.0)
    scores, weights, experts = moe.route(logits, 4, True, "sigmoid", bias,
                                         2.5)
    assert np.all(np.any(np.asarray(experts) == 3, axis=-1))
    chosen = np.take_along_axis(np.asarray(jax.nn.sigmoid(logits)),
                                np.asarray(experts), -1)
    np.testing.assert_allclose(
        weights, 2.5 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(np.sum(weights, -1), 2.5, rtol=1e-6)
    np.testing.assert_array_equal(scores, jax.nn.sigmoid(logits))
    choice, combine = reference.route(logits, bias, {
        **SIZES, "experts": 16})
    np.testing.assert_array_equal(np.sort(choice, -1), np.sort(experts, -1))
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(combine), np.asarray(experts), -1),
        weights, rtol=1e-6)
    with pytest.raises(ValueError, match="router scores"):
        moe.route(logits, 4, True, "tanh")


# -- the scan -------------------------------------------------------------------

def _scan_operands(seed=0, B=2, S=64, H=4, P=8, G=2, N=16):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(B, S, H) - 1, jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.uniform(0, 2.5, H), jnp.float32))
    b, c = (jnp.asarray(rng.randn(B, S, G, N), jnp.float32)
            for _ in range(2))
    return x, dt, a, b, c


def _stepwise(x, dt, a, b, c):
    heads, groups = x.shape[2], b.shape[2]
    return reference.recurrence(
        x, dt, a, *(jnp.repeat(v, heads // groups, axis=2) for v in (b, c)))


@pytest.mark.parametrize("chunk", [8, 16])
def test_the_chunked_form_is_the_step_by_step_form(chunk):
    """A sequence of 8 or 4 chunks: outputs and every operand's gradient;
    a second chunk size is a second place for every boundary."""
    ops = _scan_operands()
    weight = jnp.asarray(np.random.RandomState(1).randn(2, 64, 4, 8),
                         jnp.float32)
    np.testing.assert_allclose(mamba.ssm_chunked(*ops, chunk), _stepwise(*ops),
                               rtol=2e-5, atol=2e-5)
    got = jax.grad(lambda *v: jnp.sum(mamba.ssm_chunked(*v, chunk) * weight),
                   (0, 1, 2, 3, 4))(*ops)
    want = jax.grad(lambda *v: jnp.sum(_stepwise(*v) * weight),
                    (0, 1, 2, 3, 4))(*ops)
    for name, g, w in zip(("x", "dt", "a", "b", "c"), got, want):
        assert _rel(g, w) < TOL, name
    with pytest.raises(ValueError, match="ssm_chunk=24"):
        mamba.ssm_chunked(*ops, 24)


def test_the_scan_s_decays_in_bfloat16_fail(monkeypatch):
    """The nearest precision below, in the one place the decays are made:
    eight times TOL against the float32 recurrence (most of an output is
    its own position's term, whose decay is exp(0) in any precision; in a
    whole block at its initial time steps of 1e-3 to 1e-1 the same fault
    reads 4e-4 to 5e-4 on the block's gradients)."""
    ops = _scan_operands()
    want = _stepwise(*ops)
    assert _rel(mamba.ssm_chunked(*ops, 16), want) < TOL
    monkeypatch.setattr(mamba, "_ssm_decay", _bf16_decay)
    assert _rel(mamba.ssm_chunked(*ops, 16), want) > 5 * TOL


def test_one_chunk_is_the_whole_sequence_and_the_state_crosses_chunks():
    ops = _scan_operands(seed=2)
    whole = mamba.ssm_chunked(*ops, 64)
    np.testing.assert_allclose(mamba.ssm_chunked(*ops, 16), whole, rtol=2e-5,
                               atol=2e-5)
    # the first chunk needs no carried state, the later ones do
    x, dt, a, b, c = ops
    alone = mamba.ssm_chunked(x[:, 16:32], dt[:, 16:32], a, b[:, 16:32],
                          c[:, 16:32], 16)
    assert _rel(alone, whole[:, 16:32]) > 1e-2
    np.testing.assert_allclose(
        mamba.ssm_chunked(x[:, :16], dt[:, :16], a, b[:, :16], c[:, :16], 16),
        whole[:, :16], rtol=2e-5, atol=2e-5)


def test_the_convolution_is_causal_with_the_last_tap_on_the_present():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(1, 12, 3), jnp.float32)
    taps = jnp.asarray(rng.randn(4, 3), jnp.float32)
    bias = jnp.asarray(rng.randn(3), jnp.float32)
    y = np.asarray(mamba._causal_conv(x, taps, bias))
    for pos in (0, 2, 7):
        want = np.asarray(bias) + sum(
            np.asarray(taps[j]) * np.asarray(x[0, pos - 3 + j])
            for j in range(4) if pos - 3 + j >= 0)
        np.testing.assert_allclose(y[0, pos], want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(y, reference._conv(x, taps, bias), rtol=1e-5,
                               atol=1e-6)
    later = x.at[0, 8].add(1.0)
    np.testing.assert_array_equal(
        np.asarray(mamba._causal_conv(later, taps, bias))[0, :8], y[0, :8])


# -- what TOL must not let through ---------------------------------------------

def _bf16_decay(log_decay):
    return jnp.exp(log_decay.astype(jnp.bfloat16)).astype(jnp.float32)


def _no_carried_state(whole, states):
    return jnp.zeros_like(states)


def _norm_by_reshape(y, z, weight, groups, eps):
    """The gate and the grouped norm as the definition reads: a group's
    channels on an axis of their own."""
    B, S, C = y.shape
    y = (y * jax.nn.silu(z)).reshape(B, S, groups, C // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(B, S, C) * weight


@pytest.mark.parametrize("what", ["the result", "y", "z", "the weight"])
@pytest.mark.parametrize("shape", [
    (1, 24, 4096, 8), (2, 16, 384, 3), (1, 8, 64, 1), (3, 5, 40, 40),
], ids=lambda s: "x".join(map(str, s)))
def test_the_grouped_norm_needs_no_axis_for_the_groups(shape, what):
    """``_gated_norm`` keeps ``[B, S, C]`` and sums a group through a 0/1
    matrix; result and gradients are the definition's in float32 (the cell's
    8 groups of 512, a width no lane tile divides, one group, a channel a
    group)."""
    B, S, C, groups = shape
    rng = np.random.RandomState(C + groups)
    y, z, seen = (jnp.asarray(rng.randn(B, S, C), jnp.float32) * scale
                  for scale in (3.0, 2.0, 1.0))
    weight = jnp.asarray(1 + 0.1 * rng.randn(C), jnp.float32)

    def run(norm):
        out, pull = jax.vjp(
            lambda y, z, w: norm(y, z, w, groups, 1e-5), y, z, weight)
        return dict(zip(["the result", "y", "z", "the weight"],
                        (out,) + pull(seen)))
    got, want = run(mamba._gated_norm)[what], run(_norm_by_reshape)[what]
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert float(jnp.max(jnp.abs(got - want))) \
        <= 1e-5 * float(jnp.max(jnp.abs(want))), (shape, what)


def test_the_grouped_norm_takes_the_compute_dtype_s_operands():
    """bfloat16 y and z are raised before the gate: the same numbers as
    float32 copies of them."""
    rng = np.random.RandomState(0)
    y, z = (jnp.asarray(rng.randn(1, 16, 256), jnp.bfloat16) for _ in "yz")
    weight = jnp.ones((256,), jnp.bfloat16)
    got = mamba._gated_norm(y, z, weight, 2, 1e-5)
    want = mamba._gated_norm(y.astype(jnp.float32), z.astype(jnp.float32),
                             weight.astype(jnp.float32), 2, 1e-5)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _gate_after_the_norm(y, z, weight, groups, eps):
    B, S, C = y.shape
    y = y.reshape(B, S, groups, C // groups)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y.reshape(B, S, C) * weight * jax.nn.silu(z)


_shared_once = t._shared_expert


def _shared_once_a_held_expert(p, toks, activation):
    return CFG.held_experts * _shared_once(p, toks, activation)


#: one block of each kind: what a wrong term is shown on
SMALL = dataclasses.replace(CFG, n_layers=3, layer_pattern=tuple(
    adapter.KINDS[c] for c in "ME*"))
SMALL_SIZES = {**SIZES, "pattern": "ME*", "layers": 3}
SMALL_LEAVES = {
    "lm_head": (("lm_head",), None),
    "ssm_in": (("layers", "mamba", "ssm_in"), (0, 0)),
    "ssm_a_log": (("layers", "mamba", "ssm_a_log"), (0, 0)),
    "router": (("layers", "experts", "router"), (0, 0)),
}


@pytest.fixture(scope="module")
def small_reference():
    params, batch = _params(SMALL), _batch()
    want_loss, want = reference.loss_and_grads(params, SMALL_LEAVES, batch,
                                               SMALL_SIZES)
    return params, batch, want_loss, want


def _small_error(cfg, small_reference):
    params, batch, want_loss, want = small_reference
    loss, grads = _plain_grads(cfg, params, batch)
    return max([_rel(loss, want_loss)] + [
        _rel(v, want[k])
        for k, v in get_leaves(grads, SMALL_LEAVES).items()])


def test_the_sound_small_stack_matches_the_reference(small_reference):
    assert _small_error(SMALL, small_reference) < TOL


@pytest.fixture
def on_the_kernels(monkeypatch):
    """The Mamba blocks' scan on ``ops/pallas_ssm.py``'s kernels, in
    interpret mode (what a TPU runs at the cell's shapes)."""
    import functools
    monkeypatch.setattr(mamba, "ssm_chunked", functools.partial(
        mamba.ssm_chunked, interpret=True))


def test_the_sound_small_stack_matches_the_reference_on_the_kernels(
        small_reference, on_the_kernels):
    calls = str(jax.make_jaxpr(lambda p, b: t.forward_loss_spmd(
        p, b["tokens"], b["targets"], SMALL)[0])(*small_reference[:2]))
    assert "pallas_call" in calls
    assert _small_error(SMALL, small_reference) < TOL


@pytest.mark.parametrize("what, change", [
    ("a chunk boundary that drops the carried state",
     {"patch": (mamba, "_carried_states", _no_carried_state)}),
    ("silu for relu, squared", {"patch": (jax.nn, "relu", jax.nn.silu)}),
    ("gelu, the other ungated expert, for relu squared",
     {"cfg": {"moe_activation": "silu"}}),
    ("softmax for sigmoid", {"cfg": {"moe_router_scores": "softmax"}}),
    ("the scaling factor left out", {"cfg": {"moe_routed_scale": 1.0}}),
    ("the renormalisation left out", {"cfg": {"moe_renormalize": False}}),
    ("the shared expert left out", {"cfg": {"moe_shared_width": 0}}),
    ("the shared expert counted once a held expert",
     {"patch": (t, "_shared_expert", _shared_once_a_held_expert)}),
    ("rope on the attention block", {"cfg": {"layer_pattern": (
        ("mamba",), ("experts",), ("attention", None, True))}}),
    ("the gate after the norm",
     {"patch": (mamba, "_gated_norm", _gate_after_the_norm)}),
])
def test_a_wrong_term_fails(monkeypatch, small_reference, what, change):
    """Each moves the loss or a named gradient of a stack of one Mamba, one
    expert and one attention block far beyond TOL."""
    if "patch" in change:
        monkeypatch.setattr(*change["patch"])
    cfg = dataclasses.replace(SMALL, **change.get("cfg", {}))
    err = _small_error(cfg, small_reference)
    assert err > 5 * TOL, (what, err)


def _kernel_state_not_carried(state, whole, own):
    return own


@pytest.mark.parametrize("what, patch", [
    ("a chunk boundary that drops the carried state",
     ("_carry", _kernel_state_not_carried)),
    ("the decays made in bfloat16", ("_decay", _bf16_decay)),
])
def test_a_wrong_scan_fails_on_the_kernels(monkeypatch, small_reference,
                                           on_the_kernels, what, patch):
    """The same stack with the scan on the kernels: a dropped state moves
    the loss far beyond TOL, bfloat16 decays a block's gradients by four
    times TOL and more (``test_the_scan_s_decays_in_bfloat16_fail`` says
    why a whole block reads less than the scan alone)."""
    from horovod_tpu.ops import pallas_ssm
    monkeypatch.setattr(pallas_ssm, *patch)
    err = _small_error(SMALL, small_reference)
    assert err > 4 * TOL, (what, err)


# -- the share cut: one expert layer ---------------------------------------------

def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """model-configs guide, section 4: the routed parts that the sixteen
    shares compute and the shared expert counted ONCE are what the uncut
    reference gives for the whole layer; between them the shares hold
    every assignment once."""
    cfg = dataclasses.replace(CFG, n_experts=32, expert_share=(0, 1))
    rng = np.random.RandomState(0)
    m, f, fs, e = cfg.d_model, cfg.d_ff, cfg.moe_shared_width, cfg.n_experts
    h = jnp.asarray(rng.randn(1, 96, m), jnp.float32)
    p = {"router": jnp.asarray(rng.randn(m, e) * 0.3, jnp.float32),
         "router_bias": jnp.asarray(rng.randn(e) * 0.1, jnp.float32),
         "we1": jnp.asarray(rng.randn(e, m, f) / 8, jnp.float32),
         "we2": jnp.asarray(rng.randn(e, f, m) / 8, jnp.float32),
         "ws1": jnp.asarray(rng.randn(m, fs) / 8, jnp.float32),
         "ws2": jnp.asarray(rng.randn(fs, m) / 8, jnp.float32)}
    sizes = {**SIZES, "experts": e, "first_expert": 0, "held_experts": e}
    with jax.default_matmul_precision("highest"):
        want, _choice = reference.layer(p, h[0], sizes)
        shared = reference.layer(p, h[0], sizes)[0] \
            - reference.layer(p, h[0], sizes, shared=False)[0]
    parts, held_rows = [], []
    for i in range(16):
        share = dataclasses.replace(cfg, expert_share=(i, 16))
        held = {k: v[2 * i:2 * i + 2] if k in ("we1", "we2") else v
                for k, v in p.items()}
        y, aux = t._moe_ffn(held, h, share)
        assert float(aux["dropped"]) == 0.0
        parts.append(y[0])
        held_rows.append(float(aux["held_rows"]))
    routed = [part - shared for part in parts]
    assert _rel(sum(routed) + shared, want) < TOL
    assert sum(held_rows) == 96 * cfg.moe_top_k
    # the shares' outputs summed count the shared expert sixteen times
    assert _rel(sum(parts), want) > 1.0
    # no share is the whole, and the layer that holds every expert is
    assert _rel(routed[0] + shared, want) > 0.3
    y, aux = t._moe_ffn(p, h, cfg)
    assert _rel(y[0], want) < TOL and "held_rows" not in aux


# -- the grouped matmul at a width no 128-multiple divides ------------------------

def test_gmm_takes_a_width_no_lane_tile_divides_as_one_block(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    path = moe.gmm_path(49152, 2688, 1856)
    assert path.startswith(
        "pallas hvd_moe_gmm weights read as stored, [E, 1856, 2688] (1856 "
        "columns are no multiple of 128 lanes and 2688 rows are, so the "
        "chip keeps the rows minor: transpose_rhs forward, the weight "
        "gradient written [E, 1856, 2688]): forward 256x896x1856; input "
        "gradient 256x1856x896, a group's weights resident; weight gradient "
        "256x1856x384"), path
    down = moe.gmm_path(49152, 1856, 2688)
    assert down.startswith(
        "pallas hvd_moe_gmm weights read as stored, [E, 1856, 2688] "
        "row-major (transpose_rhs in the input gradient): forward "
        "256x1856x896, a group's weights resident; input gradient "
        "256x896x1856; weight gradient 256x1856x384"), down
    assert moe._lane_tiles(1856) == [1856] and moe._lane_tiles(64) == []
    assert moe._lane_tiles(2688) == [2688, 896, 384, 128]
    # the way up's calls are the way down's, each other's: one stored shape
    assert moe._gmm_tile(49152, 2688, 1856, 2) == moe.GmmTiles(
        (256, 896, 1856), (256, 1856, 896), (256, 1856, 384), True)
    assert moe._gmm_tile(49152, 1856, 2688, 2) == moe.GmmTiles(
        (256, 1856, 896), (256, 896, 1856), (256, 1856, 384), False)
    # the OLMoE and SmallThinker shapes keep the tiles PR 33 gave them
    assert moe._gmm_tile(65536, 2048, 1024, 2) == moe.GmmTiles(
        (256, 2048, 1024), (256, 1024, 2048), (256, 1024, 1024))
    assert moe._gmm_tile(49152, 2560, 768, 2) == moe.GmmTiles(
        (256, 2560, 768), (256, 768, 2560), (256, 1280, 768))
    assert moe._gmm_tile(49152, 768, 2560, 2) == moe.GmmTiles(
        (256, 768, 2560), (256, 2560, 768), (256, 768, 1280))


@pytest.mark.parametrize("k, f, transposed", [
    (128, 192, True), (256, 192, True), (128, 256, False),
    (192, 128, False), (192, 192, False)])
def test_the_kernels_at_such_a_width_are_the_ragged_dot(k, f, transposed):
    """Interpret mode: 192 columns (1.5 lane tiles) as one block, forward
    and both gradients, groups that start inside a row tile and end before
    the rows do. Where the columns are no multiple of 128 lanes and the
    rows are, the calls read the weights ``[E, f, k]`` and return their
    gradient swapped back (ISSUE 41); elsewhere in the order they had."""
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(256, k), jnp.float32)
    w = jnp.asarray(rng.randn(3, k, f) / 8, jnp.float32)
    sizes = jnp.asarray([100, 0, 92], jnp.int32)
    assert moe._gmm_tile(256, k, f, 4).transposed == transposed
    weight = jnp.asarray(rng.randn(256, f), jnp.float32)
    inside = (jnp.arange(256) < 192)[:, None]

    def run(interpret):
        def loss(r, w):
            y = moe.grouped_matmul(r, w, sizes, interpret=interpret)
            return jnp.sum(jnp.where(inside, y, 0) * weight)
        return jax.value_and_grad(loss, (0, 1))(rows, w)
    with jax.default_matmul_precision("highest"):
        (got, (d_rows, d_w)), (want, (r_rows, r_w)) = run(True), run(False)
    assert d_w.shape == w.shape and d_w.dtype == w.dtype
    assert _rel(got, want) < 1e-5
    assert _rel(jnp.where(inside, d_rows, 0), r_rows) < 1e-5
    assert _rel(d_w, r_w) < 1e-5


# -- what is refused, by name -------------------------------------------------------

@pytest.mark.parametrize("axis", ["sp", "pp", "tp"])
def test_a_mamba_block_on_a_live_axis_is_refused_by_name(axis):
    mesh = build_mesh(devices=jax.devices()[:2], **{axis: 2})
    with pytest.raises(NotImplementedError, match=rf"mamba.*live {axis}"):
        t.param_shardings(CFG, mesh)
    with pytest.raises(NotImplementedError, match="mamba"):
        t.make_grad_fn(CFG, mesh)
    # the same stack without its Mamba blocks shards as the others do
    rest = dataclasses.replace(CFG, n_layers=4, layer_pattern=(
        ("experts",), ("attention", None, False)))
    if axis != "sp":
        t.param_shardings(rest, mesh)


def test_the_decode_paths_refuse_the_new_fields_by_name():
    params = _params()
    for field, cfg in [
            ("ssm_heads", CFG),
            ("moe_router_scores", t.TransformerConfig(
                n_experts=8, moe_router_scores="sigmoid")),
            ("moe_shared_width", t.TransformerConfig(
                n_experts=8, moe_shared_width=64))]:
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


def test_a_pattern_or_a_word_the_program_does_not_know_is_refused():
    with pytest.raises(ValueError, match="one of"):
        dataclasses.replace(CFG, layer_pattern=(("mamba",), ("retention",)),
                            n_layers=2)
    with pytest.raises(ValueError, match="throughout or of none"):
        dataclasses.replace(CFG, layer_pattern=(("mamba",), (None, True)),
                            n_layers=2)
    with pytest.raises(ValueError, match="one of"):     # a kind's length
        dataclasses.replace(CFG, layer_pattern=(("attention",),),
                            n_layers=2)
    with pytest.raises(ValueError, match="n_experts=0"):
        t.TransformerConfig(layer_pattern=(("experts",),))
    with pytest.raises(ValueError, match="ssm_groups=3"):
        dataclasses.replace(CFG, ssm_groups=3)
    with pytest.raises(ValueError, match="ssm_heads=0"):
        t.TransformerConfig(layer_pattern=(("mamba",),))
    with pytest.raises(ValueError, match="moe_router_scores"):
        dataclasses.replace(CFG, moe_router_scores="tanh")
    with pytest.raises(ValueError, match="moe_activation"):
        dataclasses.replace(CFG, moe_activation="swish")
    params, batch = _params(), _batch()
    for wrong in ({"moe_gated": True}, {"moe_activation": "relu"}):
        with pytest.raises(NotImplementedError, match="moe_activation"):
            _plain_grads(dataclasses.replace(CFG, **wrong), params, batch)
    with pytest.raises(ValueError, match="ssm_chunk=48"):
        _plain_grads(dataclasses.replace(CFG, ssm_chunk=48), params, batch)


# -- what the architecture keeps for the backward pass ---------------------------------

def test_a_mamba_block_is_checkpointed_where_nothing_says_otherwise():
    """``remat=None``: the Mamba blocks under ``jax.checkpoint``, the
    expert and attention blocks not; ``remat=False`` keeps everything, and
    the gradients are the same."""
    params, batch = _params(SMALL), _batch()

    def checkpoints(cfg):
        return str(jax.make_jaxpr(jax.grad(lambda p: t.forward_loss_spmd(
            p, batch["tokens"], batch["targets"], cfg)[0]))(params)
            ).count("remat")
    kept = dataclasses.replace(SMALL, remat=False)
    assert checkpoints(SMALL) > checkpoints(kept)
    _loss, a = _plain_grads(SMALL, params, batch)
    _loss, b = _plain_grads(kept, params, batch)
    for (path, x), y in zip(jax.tree_util.tree_leaves_with_path(a),
                            jax.tree_util.tree_leaves(b)):
        assert _rel(x, y) < 1e-5, path


@pytest.mark.parametrize("pattern, barriers", [("ME*", 1), ("E*", 0)])
def test_a_stack_with_a_mamba_block_finishes_its_gradients_before_the_update(
        pattern, barriers):
    """``make_train_step`` puts one ``optimization_barrier`` over the whole
    tree of gradients between the backward pass and the optimizer where a
    block kind's row says ``gradients_first`` (the Mamba row: ISSUE 47), and
    none where no block says so."""
    import optax
    cfg = dataclasses.replace(SMALL, n_layers=len(pattern), remat=False,
                              layer_pattern=tuple(
                                  adapter.KINDS[c] for c in pattern))
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    params = shard_params(_params(cfg), cfg, mesh)
    tx = optax.adamw(1e-3)
    batch = _batch()
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    step = jax.make_jaxpr(t.make_train_step(cfg, mesh, tx))(
        params, t.init_opt_state(tx, params, mesh), tok, tgt)
    leaves = len(jax.tree_util.tree_leaves(params))

    def over_the_tree(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "optimization_barrier":
                yield len(eqn.outvars) == leaves
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from over_the_tree(inner)
    assert sum(over_the_tree(step.jaxpr)) == barriers, pattern
