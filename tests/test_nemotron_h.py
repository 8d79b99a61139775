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

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
import chip_door
from arch import TOL, rel as _rel
from horovod_tpu.models import mamba
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.parallel import build_mesh, moe

ARCH = arch.get("nemotron_h")
adapter, reference = ARCH.adapter, ARCH.reference
CONFIG, SIZES, CFG, LEAVES = ARCH.CONFIG, ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_cell, _params, _batch = ARCH.cell, ARCH.params, ARCH.batch
_plain_grads = ARCH.plain
PATTERN = CONFIG["hybrid_override_pattern"]
CELL = "nemotron-3-nano-30b-a3b.s8192"


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
    n = arch.count(arch.drawn_shapes(adapter, cfg, config))
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
    gmm, fwd, bwd = (chip_door.roofline(CELL, kernel) for kernel in (
        "hvd_moe_gmm", "hvd_flash_attention", "hvd_flash_bwd"))
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    rows = 8192 * 6 * 8 / 128
    assert rows == 3072
    need = gmm(sizes)
    assert need["flops"] == 4 * 6 * 2 * rows * 2688 * 1856
    assert need["bytes"] == 4 * 6 * 2 * (rows * (2688 + 1856)
                                         + 8 * 2688 * 1856)
    need = fwd(sizes)
    assert need["flops"] == 2 * 2 * 32 * 128 * 8192 * 8193 / 2
    assert need["bytes"] == 2 * 8192 * (32 + 2) * 128 * 2 + 32 * 8192 * 4
    assert bwd(sizes)["flops"] == 2.5 * need["flops"]


def test_the_scan_kernels_least_work_by_hand():
    """Four Mamba blocks, two forward calls and one backward call each, at
    8192 positions of 64 heads of 64, 8 groups, state 128, chunk 128."""
    config, job = _cell(tiny=False)
    need = chip_door.roofline(CELL, "hvd_ssm_scan")(
        adapter.shapes(config, job))
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

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_its_rows_and_no_gradient_reaches_the_bias():
    _got, _want, aux, grads = ARCH.sides
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
    ours = jax.jit(lambda p, tok: t.router_choices(p, tok, CFG))(
        params, batch["tokens"])
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(lambda p, tok: reference.forward(p, tok, SIZES)[1])(
            params, batch["tokens"])
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
    np.testing.assert_allclose(mamba.ssm_chunked(*ops, chunk),
                               jax.jit(_stepwise)(*ops), rtol=2e-5, atol=2e-5)
    got = jax.jit(jax.grad(
        lambda *v: jnp.sum(mamba.ssm_chunked(*v, chunk) * weight),
        (0, 1, 2, 3, 4)))(*ops)
    want = jax.jit(jax.grad(lambda *v: jnp.sum(_stepwise(*v) * weight),
                            (0, 1, 2, 3, 4)))(*ops)
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
SMALL = ARCH.cut({"num_hidden_layers": 3, "hybrid_override_pattern": "ME*"}, {
    "lm_head": (("lm_head",), None),
    "ssm_in": (("layers", "mamba", "ssm_in"), (0, 0)),
    "ssm_a_log": (("layers", "mamba", "ssm_a_log"), (0, 0)),
    "router": (("layers", "experts", "router"), (0, 0)),
})


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.CFG == dataclasses.replace(CFG, n_layers=3, layer_pattern=(
        ("mamba",), ("experts",), ("attention", None, False)))
    assert SMALL.sound < TOL


@pytest.fixture
def on_the_kernels(monkeypatch):
    """The Mamba blocks' scan on ``ops/pallas_ssm.py``'s kernels, in
    interpret mode (what a TPU runs at the cell's shapes)."""
    import functools
    monkeypatch.setattr(mamba, "ssm_chunked", functools.partial(
        mamba.ssm_chunked, interpret=True))


def test_the_sound_small_stack_matches_the_reference_on_the_kernels(
        on_the_kernels):
    calls = str(jax.make_jaxpr(lambda p, b: t.forward_loss_spmd(
        p, b["tokens"], b["targets"], SMALL.CFG)[0])(*SMALL.kept()[:2]))
    assert "pallas_call" in calls
    assert SMALL.error("the sound program on the kernels") < TOL


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
def test_a_wrong_term_fails(monkeypatch, what, change):
    """Each moves the loss or a named gradient of a stack of one Mamba, one
    expert and one attention block far beyond TOL."""
    if "patch" in change:
        monkeypatch.setattr(*change["patch"])
    cfg = dataclasses.replace(SMALL.CFG, **change.get("cfg", {}))
    err = SMALL.error(what, cfg)
    assert err > 5 * TOL, (what, err)


def _kernel_state_not_carried(state, whole, own):
    return own


@pytest.mark.parametrize("what, patch", [
    ("a chunk boundary that drops the carried state",
     ("_carry", _kernel_state_not_carried)),
    ("the decays made in bfloat16", ("_decay", _bf16_decay)),
])
def test_a_wrong_scan_fails_on_the_kernels(monkeypatch, on_the_kernels, what,
                                           patch):
    """The same stack with the scan on the kernels: a dropped state moves
    the loss far beyond TOL, bfloat16 decays a block's gradients by four
    times TOL and more (``test_the_scan_s_decays_in_bfloat16_fail`` says
    why a whole block reads less than the scan alone)."""
    from horovod_tpu.ops import pallas_ssm
    monkeypatch.setattr(pallas_ssm, *patch)
    err = SMALL.error(what + ", on the kernels")
    assert err > 4 * TOL, (what, err)


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
    small = SMALL.CFG
    params, batch = _params(small), _batch()

    def checkpoints(cfg):
        return str(jax.make_jaxpr(jax.grad(lambda p: t.forward_loss_spmd(
            p, batch["tokens"], batch["targets"], cfg)[0]))(params)
            ).count("remat")
    kept = dataclasses.replace(small, remat=False)
    assert checkpoints(small) > checkpoints(kept)
    _loss, a = _plain_grads(small, params, batch)
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
    cfg = dataclasses.replace(SMALL.CFG, n_layers=len(pattern), remat=False,
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
