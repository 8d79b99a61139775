"""OLMoE on the normal path (ISSUE 26): the flagship block with QK-norm,
SiLU-gated experts, the dropless sorted dispatch, an untied head and both
auxiliary losses, in float32 at the benchmark configuration's ``tiny``
sizes, against the plain reference ``benchmarks/chip/reference/olmoe.py``
on seeded weights; the reference itself against ``transformers``'
``OlmoeForCausalLM``.

TOL: both sides are float32 here and differ in the order of their sums (a
grouped matmul over sorted rows against every expert on every token; a
fused cross-entropy against logsumexp), measured at 1e-6 of a leaf's norm.
1e-4 leaves that room and is a hundredth of what one bfloat16 rounding in
the router, the top-k weights or the combine does (``test_a_wrong_term_
fails``), so none of them can hide in it.
"""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, rel as _rel
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.parallel import build_mesh, moe

ARCH = arch.get("olmoe")
reference = ARCH.reference
CONFIG, SIZES, CFG, LEAVES = ARCH.CONFIG, ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_params, _batch, _program = ARCH.params, ARCH.batch, ARCH.program
assert CFG.dtype == jnp.float32


@pytest.mark.parametrize("what", [
    "logits", "loss", "load_balance_loss", "router_z_loss", "grad:router",
    "grad:expert_gate", "grad:expert_up", "grad:expert_down", "grad:wq",
    "grad:lm_head"])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_its_load_and_drops_nothing():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped"}
    assert float(aux["dropped"]) == 0.0
    # 128 tokens x top-2 over 8 experts: the mean group is 32 rows
    assert 1.0 <= float(aux["max_expert_load"]) <= 4.0
    np.testing.assert_allclose(
        float(aux["aux_loss"]),
        0.01 * float(aux["load_balance_loss"])
        + 0.001 * float(aux["router_z_loss"]), rtol=1e-6)


def test_dropless_when_every_token_takes_the_same_experts():
    """A router of zeros ties every expert; top-k then takes experts 0 and 1
    for every token on both sides: two groups of all the rows, six empty."""
    params = _params()
    params["layers"]["router"] = jnp.zeros_like(params["layers"]["router"])
    batch = _batch()
    loss, aux, grads = _program(CFG, params, batch)
    assert float(aux["dropped"]) == 0.0
    assert float(aux["max_expert_load"]) == CFG.n_experts / CFG.moe_top_k
    want = ARCH.want(params, batch)
    assert _rel(loss, want["loss"]) < TOL
    assert _rel(grads["layers"]["we2"][0, 1, 0],
                reference.loss_and_grads(
                    params, {"d": (("layers", "we2"), (0, 1, 0))}, batch,
                    SIZES)[1]["d"]) < TOL
    # an expert no token chose has no gradient
    assert float(jnp.abs(grads["layers"]["we2"][0, 1, 5]).max()) == 0.0


@pytest.mark.parametrize("renormalize", [False, True])
def test_norm_topk_prob_both_ways(renormalize):
    cfg = dataclasses.replace(CFG, moe_renormalize=renormalize)
    sizes = {**SIZES, "norm_topk_prob": renormalize}
    params, batch = _params(seed=1), _batch(seed=1)
    loss, _aux, grads = _program(cfg, params, batch)
    leaves = {"router": LEAVES["router"]}
    want_loss, want = reference.loss_and_grads(params, leaves, batch, sizes)
    assert _rel(loss, want_loss) < TOL
    assert _rel(grads["layers"]["router"][0, 1], want["router"]) < TOL
    # and the two are different models
    other = reference.loss_and_grads(
        params, leaves, batch, {**sizes, "norm_topk_prob": not renormalize})
    assert _rel(grads["layers"]["router"][0, 1], other[1]["router"]) > 0.1


def _bf16_softmax(logits, k, renormalize):
    probs = jax.nn.softmax(logits.astype(jnp.bfloat16), axis=-1
                           ).astype(jnp.float32)
    weights, experts = jax.lax.top_k(probs, k)
    return probs, weights, experts


def _bf16_weights(logits, k, renormalize, route=moe.route):
    probs, weights, experts = route(logits, k, renormalize)
    return probs, weights.astype(jnp.bfloat16).astype(jnp.float32), experts


def _bf16_products(rows, by):
    return rows.astype(jnp.bfloat16) * by.astype(jnp.bfloat16)


def _drop_one(*args, ffn=moe.expert_ffn):
    return ffn(*args).at[0].set(0.0)


@pytest.mark.parametrize("what, where, wrong", [
    ("router softmax in bfloat16", (moe, "route"), _bf16_softmax),
    ("top-k weights in bfloat16", (moe, "route"), _bf16_weights),
    ("combine in bfloat16", (moe, "_products"), _bf16_products),
    ("a dropped assignment", (t, "expert_ffn"), _drop_one),
])
def test_a_wrong_term_fails(monkeypatch, what, where, wrong):
    """What TOL must not let through: each moves the router's gradient far
    beyond it (the loss of 128 random tokens hardly notices)."""
    assert ARCH.sound < TOL
    monkeypatch.setattr(*where, wrong)
    err = ARCH.error(what, only=("grad:router",))
    assert err > 20 * TOL, (what, err)


@pytest.mark.parametrize("axes", [
    {"ep": 2}, {"dp": 2, "ep": 2}, {"ep": 2, "sp": 2}, {"ep": 4}])
def test_expert_parallel_layouts_give_one_device_s_result(axes):
    """Dropless: no capacity per group, so every layout computes the same
    loss, the same auxiliary terms and (after the data shards' sum, which
    the flagship's gradient sync leaves undivided) the same gradients."""
    params, batch = _params(), _batch(n_seqs=4)
    loss1, aux1, grads1 = _program(CFG, params, batch)
    loss, aux, grads = _program(CFG, params, batch, axes)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    for k in aux1:
        np.testing.assert_allclose(float(aux[k]), float(aux1[k]), rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    shards = int(np.prod(list(axes.values())))
    for name in ("router", "we1", "we2", "we3", "wq"):
        assert _rel(np.asarray(grads["layers"][name]) / shards,
                    grads1["layers"][name]) < TOL, name


@pytest.mark.parametrize("d_ff", [128, 192])
@pytest.mark.parametrize("axes", [{"ep": 2}, {"dp": 2, "ep": 2}])
def test_expert_parallel_on_the_kernels_gives_one_device_s_result(
        monkeypatch, axes, d_ff):
    """The same with the block's three grouped matmuls on the TPU path's
    Pallas kernels (interpret mode; experts 128 wide so the kernels apply):
    a shard's groups end before its rows do, and what the kernel leaves
    unwritten there (NaN here, stale memory on a chip) must reach neither
    the loss nor a gradient. Experts 192 wide, 1.5 lane tiles: the two ways
    up ``[E, 128, 192]`` are read and differentiated the other way round, as
    a chip stores them (``moe._stored_transposed``), beside the way down
    ``[E, 192, 128]`` in today's order."""
    assert moe._gmm_tile(256, 128, d_ff, 4).transposed == (d_ff == 192)
    assert not moe._gmm_tile(256, d_ff, 128, 4).transposed
    cfg = dataclasses.replace(CFG, d_ff=d_ff)
    params, batch = _params(cfg), _batch(n_seqs=4)
    loss1, _aux1, grads1 = _program(cfg, params, batch)
    monkeypatch.setattr(t, "expert_ffn", functools.partial(
        moe.expert_ffn, interpret=True))
    loss, _aux, grads = _program(cfg, params, batch, axes)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    shards = int(np.prod(list(axes.values())))
    for name in ("router", "we1", "we2", "we3", "wq"):
        assert _rel(np.asarray(grads["layers"][name]) / shards,
                    grads1["layers"][name]) < TOL, name


def test_router_choices_are_the_step_s():
    """``router_choices`` returns what the layer's router chose: the
    reference forced to them agrees with the program as it does on its
    own choices (float32: both sides choose alike)."""
    params, batch = _params(), _batch()
    ours = jax.jit(functools.partial(t.router_choices, cfg=CFG))(
        params, batch["tokens"])
    theirs = jax.jit(lambda p, b: reference.losses(p, b, SIZES)[4])(
        params, batch)
    assert ours.shape == theirs.shape == (
        CFG.n_layers, batch["tokens"].size, CFG.moe_top_k)
    np.testing.assert_array_equal(np.sort(ours, -1), np.sort(theirs, -1))


def test_tensor_parallel_experts_and_qk_norm_forward():
    """tp shards an expert's width and the heads: the QK-norm's mean runs
    over the whole projection, the experts' partial sums meet after the
    combine. Forward only: the flagship's tp gradients are ROADMAP's."""
    params, batch = _params(), _batch(n_seqs=4)
    loss1, aux1, _ = _program(CFG, params, batch)
    loss, aux, _ = _program(CFG, params, batch, {"ep": 2, "tp": 2})
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    np.testing.assert_allclose(float(aux["load_balance_loss"]),
                               float(aux1["load_balance_loss"]), rtol=1e-5)


def test_pipeline_carries_the_weighted_auxiliary_sum():
    params1, batch = _params(), _batch(n_seqs=4)
    _loss1, aux1, _ = _program(CFG, params1, batch)
    cfg = dataclasses.replace(CFG, n_microbatches=2)
    mesh = build_mesh(devices=jax.devices()[:2], pp=2)
    params = t.init_params(np.random.RandomState(0), cfg, n_stages=2)
    p = shard_params(params, cfg, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    loss, aux, _ = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
    assert set(aux) == {"aux_loss"}
    # per microbatch, then averaged: the load-balancing term is not linear
    # in the batch, so this is close to, not equal to, the whole batch's
    np.testing.assert_allclose(float(aux["aux_loss"]),
                               float(aux1["aux_loss"]), rtol=0.1)
    assert np.isfinite(float(loss))


def test_init_params_and_shardings_hold_the_new_leaves():
    mesh = build_mesh(devices=jax.devices()[:4], ep=2, tp=2)
    params = t.init_params(np.random.RandomState(0), CFG, 1)
    sh = t.param_shardings(CFG, mesh)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(sh)
    assert set(params["layers"]) >= {"q_norm", "k_norm", "router", "we1",
                                     "we2", "we3"}
    assert params["lm_head"].shape == (CFG.d_model, CFG.vocab_size)
    assert params["layers"]["we3"].shape == (
        1, CFG.n_layers, CFG.n_experts, CFG.d_model, CFG.d_ff)
    # a default config is the GPT block: none of them
    dense = t.init_params(np.random.RandomState(0), t.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=4, n_layers=2, d_ff=64), 1)
    assert set(dense) == {"embed", "ln_f", "layers"}
    assert set(dense["layers"]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                    "w1", "w2"}


# -- the reference against the public implementation -------------------------

def _hf_model(params):
    torch = pytest.importorskip("torch")
    transformers = pytest.importorskip("transformers")
    hf_config = transformers.OlmoeConfig(**{
        k: CONFIG[k] for k in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "hidden_act", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "attention_bias", "clip_qkv",
            "num_experts", "num_experts_per_tok", "norm_topk_prob",
            "router_aux_loss_coef", "tie_word_embeddings")},
        attn_implementation="eager")
    model = transformers.OlmoeForCausalLM(hf_config).to(torch.float32).eval()

    def put(module_weight, array, transpose=True):
        a = np.asarray(array, np.float32)
        with torch.no_grad():
            module_weight.copy_(torch.from_numpy(a.T.copy() if transpose
                                                 else a.copy()))
    put(model.model.embed_tokens.weight, params["embed"], False)
    put(model.model.norm.weight, params["ln_f"], False)
    put(model.lm_head.weight, params["lm_head"])
    for i, layer in enumerate(model.model.layers):
        p = {k: v[0, i] for k, v in params["layers"].items()}
        attn, mlp = layer.self_attn, layer.mlp
        put(layer.input_layernorm.weight, p["ln1"], False)
        put(layer.post_attention_layernorm.weight, p["ln2"], False)
        put(attn.q_norm.weight, p["q_norm"], False)
        put(attn.k_norm.weight, p["k_norm"], False)
        for ours, theirs in (("wq", attn.q_proj), ("wk", attn.k_proj),
                             ("wv", attn.v_proj), ("wo", attn.o_proj),
                             ("router", mlp.gate)):
            put(theirs.weight, p[ours])
        for e, expert in enumerate(mlp.experts):
            put(expert.gate_proj.weight, p["we1"][e])
            put(expert.up_proj.weight, p["we3"][e])
            put(expert.down_proj.weight, p["we2"][e])
    return torch, model


@pytest.fixture(scope="module")
def hf_outputs():
    params, batch = _params(seed=2), _batch(seed=2)
    torch, model = _hf_model(params)
    with torch.no_grad():
        out = model(torch.from_numpy(np.asarray(batch["tokens"], np.int64)),
                    output_router_logits=True)
    from transformers.models.olmoe.modeling_olmoe import \
        load_balancing_loss_func
    per_layer = [float(load_balancing_loss_func(
        (logits,), CONFIG["num_experts"], CONFIG["num_experts_per_tok"]))
        for logits in out.router_logits]
    with jax.default_matmul_precision("highest"):
        logits, balance, _z, _c = reference.forward(
            params, batch["tokens"], SIZES)
    return (out.logits.numpy(), np.mean(per_layer), float(out.aux_loss),
            np.asarray(logits), float(balance))


def test_reference_logits_match_transformers_olmoe(hf_outputs):
    hf_logits, _lb, _concat, logits, _balance = hf_outputs
    assert _rel(logits, hf_logits) < TOL


def test_reference_load_balancing_loss_matches_transformers(hf_outputs):
    _logits, hf_per_layer, hf_concatenated, _l, balance = hf_outputs
    # the paper's (and this repo's) form: per layer, then averaged
    np.testing.assert_allclose(balance, hf_per_layer, rtol=1e-5)
    # transformers concatenates the layers before its two means: close at
    # two layers, equal at one (configs/olmoe-1b-7b.json, "assumed")
    np.testing.assert_allclose(balance, hf_concatenated, rtol=0.05)
