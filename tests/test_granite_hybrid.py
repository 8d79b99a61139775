"""Granite 4.0-H Micro (ISSUE 49): layers of two sublayers, nine Mamba-2
mixers of ONE group to one attention layer without positions, a SwiGLU FFN in
every layer, four scalar multipliers and a tied, sliced table; in float32 at
the benchmark configuration's ``tiny`` sizes (the ten layers MMMMM A MMMM, 8
Mamba heads of 16 with state 16 in one group, four chunks of 16 in 64
positions, 8 query heads of 8 on 2 key/value heads with the scores'
multiplier kept at 1/64) against the plain reference
``benchmarks/chip/reference/granite_hybrid.py`` on seeded weights; the
reference runs the recurrence one position at a time.

TOL: both sides are float32 here and differ in the order of their sums (they
read 1e-7 to 3e-6): 1e-4 is a thirtieth of what the least of the faults
below does (``test_a_fault_fails``).
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
from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.parallel import build_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from adapters import granite_hybrid as adapter        # noqa: E402
from reference import granite_hybrid as reference     # noqa: E402
from trees import get_leaves                           # noqa: E402

TOL = 1e-4
CELL = "granite-4.0-h-micro.s4096"


def _cell(tiny: bool):
    with open(os.path.join(_CHIP, "configs",
                           "granite-4.0-h-micro.json")) as f:
        config = json.load(f)
    with open(os.path.join(_CHIP, "workloads",
                           "train.s4096.b1.ssm.json")) as f:
        job = json.load(f)
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


CONFIG, JOB = _cell(tiny=True)
SIZES = adapter.shapes(CONFIG, JOB)
CFG = adapter._model_config(CONFIG, JOB)
TYPES = CONFIG["layer_types"]
MIXER, FFN, ATTENTION = ("mamba",), ("dense",), ("attention", None, False)
PERIOD = (MIXER, FFN) * 5 + (ATTENTION, FFN) + (MIXER, FFN) * 4


def _params(cfg=CFG, seed=0):
    """``init_params``' tree with the table at the configuration's scale
    (at 0.02 the logits say nothing) and the norm weights and the skip off
    their ones, so that a gradient through them is not through a 1."""
    rng = np.random.RandomState(seed)
    params = t.init_params(rng, cfg, 1)
    params["embed"] = params["embed"] * (
        CONFIG["assumed"]["embedding_std"] / 0.02)
    for stack, names in (("mamba", ("ln1", "ssm_norm", "ssm_d")),
                         ("dense", ("ln2",)), ("attention", ("ln1",))):
        for name in names:
            leaf = params["layers"][stack][name]
            params["layers"][stack][name] = (
                leaf + 0.2 * rng.randn(*leaf.shape)).astype(np.float32)
    return jax.tree_util.tree_map(jnp.asarray, params)


def _all_leaves(params) -> dict:
    """Every leaf of the tree, whole (``trees.py``'s form)."""
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {".".join(k.key for k in path): (tuple(k.key for k in path), None)
            for path, _leaf in flat}


LEAVES = _all_leaves(jax.eval_shape(lambda: _params()))


def _batch(n_seqs=2, seed=0):
    return jax.tree_util.tree_map(
        jnp.asarray, adapter.host_batch(CONFIG, JOB, seed, 0, n_seqs))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


def _program(cfg, params, batch):
    """(loss, gradients) on a mesh of one device, through ``make_grad_fn``
    as the benchmark's adapter calls it."""
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    p = shard_params(params, cfg, mesh)
    tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
    loss, aux, grads = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
    assert set(aux) == {"aux_loss"} and float(aux["aux_loss"]) == 0.0
    return loss, grads


def _reference(params, batch, sizes=SIZES, leaves=None):
    return reference.loss_and_grads(params, leaves or LEAVES, batch, sizes)


# -- the configuration ---------------------------------------------------------

def test_the_tiny_preset_is_the_one_the_issue_asks_for():
    assert CFG.dtype == jnp.float32 and CFG.n_layers == 20
    assert CFG.layer_pattern == PERIOD and CFG.one_sublayer
    assert TYPES == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert (CFG.ssm_heads, CFG.ssm_head_dim, CFG.ssm_state, CFG.ssm_groups,
            CFG.ssm_conv, CFG.ssm_chunk) == (8, 16, 16, 1, 4, 16)
    assert CFG.ssm_inner == 128 == CONFIG["mamba_expand"] * CFG.d_model
    assert JOB["seq_len"] == 4 * CFG.ssm_chunk            # chunk < S
    assert (CFG.n_heads, CFG.kv_heads, CFG.head_dim) == (8, 2, 8)
    assert CFG.n_heads // CFG.kv_heads == 4 and CFG.head_dim < 128
    assert (CFG.embed_scale, CFG.residual_scale, CFG.attention_scale,
            CFG.logits_scale) == (12.0, 0.22, 1 / 64, 1 / 8)
    assert CFG.attention_scale != CFG.head_dim ** -0.5
    assert (CFG.ffn_gated, CFG.tie_embeddings, CFG.dense_ff, CFG.remat,
            CFG.n_experts, CFG.norm_eps) == (True, True, 128, None, 0, 1e-5)
    # the Mamba blocks alone are checkpointed (the ladder's kept rung)
    assert [t._checkpointed(CFG, kind) for kind in (MIXER, FFN, ATTENTION)
            ] == [True, False, False]


def test_the_cell_keeps_every_published_width():
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    assert (cfg.d_model, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state,
            cfg.ssm_groups, cfg.ssm_conv, cfg.ssm_chunk) == (
                2048, 64, 64, 128, 1, 4, 256)
    assert (cfg.ssm_inner, cfg.ssm_conv_width) == (4096, 4352)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.dense_ff, cfg.d_ff, cfg.ffn_gated, cfg.tie_embeddings,
            cfg.norm_eps) == (8192, 8192, True, True, 1e-5)
    assert (cfg.embed_scale, cfg.residual_scale, cfg.attention_scale,
            cfg.logits_scale) == (12.0, 0.22, 0.015625, 0.125)
    assert cfg.layer_pattern == PERIOD
    assert (cfg.n_layers, cfg.vocab_size, job["seq_len"]) == (
        20, 12544, 4096)
    assert 12544 == 98 * 128 == 100352 // 8
    assert config["reduced"] == ["num_hidden_layers", "layer_types",
                                 "vocab_size"]
    assert set(config["reduced"]) == set(config["reduced_from"])
    assert config["layer_types"] == config["reduced_from"]["layer_types"][:10]
    assert (config["reduced_from"]["num_hidden_layers"],
            config["reduced_from"]["vocab_size"]) == (40, 100352)
    n = sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(
        jax.eval_shape(adapter._init_function(cfg, config),
                       jax.random.PRNGKey(0))))
    assert 772.0e6 < n < 772.3e6, n      # the deployment's 772.1 M


def test_the_configuration_holds_every_key_of_the_catalog_s():
    """Every number and word of the published ``config`` under its key,
    but the three ``reduced`` names (ISSUE 49's row of the catalog)."""
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8,
        "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_key_value_heads": 8,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True}
    config, _job = _cell(tiny=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert not set(config["tiny"]) & {"mamba_n_groups", "layer_types",
                                      "num_hidden_layers"}


def test_the_step_s_required_flops_by_hand():
    """4.76 GFLOP a token, 19.5 TFLOP a step of 4096 (ISSUE 49's count):
    the FFNs 64 %, the nine Mamba mixers 31 %, the attention mixer 2.4 %,
    the sliced head 3.3 %."""
    config, job = _cell(tiny=False)
    ffn = 3 * 2 * 2048 * 8192
    mixer = (2 * 2048 * 8512 + 2 * 4096 * 2048 + 2 * 4 * 4352
             + 2 * 128 * 128.5 + 2 * 64 * 64 * 128.5
             + 2 * 2 * 64 * 64 * 128)
    attention = (2 * 2 * 2048 * 2048 + 2 * 2 * 2048 * 512
                 + 2 * 2 * 2048 * 4097 / 2)
    head = 2 * 2048 * 12544
    forward = 10 * ffn + 9 * mixer + attention + head
    got = adapter.flops_per_token(config, job)
    assert got == pytest.approx(3 * forward, rel=1e-12)
    assert got == pytest.approx(4.76e9, rel=5e-3)
    assert 4096 * got == pytest.approx(19.5e12, rel=5e-3)
    assert 10 * ffn / forward == pytest.approx(0.64, abs=0.01)
    assert 9 * mixer / forward == pytest.approx(0.31, abs=0.01)
    assert attention / forward == pytest.approx(0.024, abs=0.002)
    assert head / forward == pytest.approx(0.033, abs=0.002)


def test_the_kernels_least_work_by_hand():
    import roofline_dense_ssm_flash_attention as fwd
    import roofline_dense_ssm_flash_attention_backward as bwd
    import roofline_dense_ssm_head_xent as xent
    import roofline_dense_ssm_scan as scan
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    once = 2 * 2 * 32 * 64 * 4096 * 4097 / 2
    need = fwd.dense_ssm_flash_attention(sizes)
    assert need["flops"] == once
    assert need["bytes"] == 2 * 4096 * (32 + 8) * 64 * 2 + 32 * 4096 * 4
    need = bwd.dense_ssm_flash_attention_backward(sizes)
    assert need["flops"] == 2.5 * once
    assert need["bytes"] == 4 * 4096 * (32 + 8) * 64 * 2 + 2 * 32 * 4096 * 4
    need = xent.dense_ssm_head_xent(sizes)
    assert need["bytes"] == 2 * 4096 * 12544 * 2 + 12 * 4096
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    # nine Mamba blocks, two forward calls and one backward call each, ONE
    # group: the scores and b and c once, whatever the head tiles
    need = scan.dense_ssm_scan(sizes)
    scores, weighted, state = (2 * 128 * 128.5, 2 * 4096 * 128.5,
                               2 * 4096 * 128)
    forward = scores + weighted + 2 * state
    assert need["flops"] == 9 * 4096 * (
        2 * forward + 2 * forward + scores + state)
    x, bc, sums, y = 4096 * 2, 2 * 128 * 2, 2 * 64 * 4, 4096 * 4
    assert need["bytes"] == 9 * 4096 * (
        2 * (x + bc + sums + y) + (x + bc + sums + y) + (x + bc + sums)
        + 2 * 4096 * 128 * 4 / 256)


def test_the_paths_at_the_cell_s_shapes_name_the_kernels(monkeypatch):
    config, job = _cell(tiny=False)
    cfg = adapter._model_config(config, job)
    assert pa.attention_path(4096, 4096, 32, 64, True, False) == "xla"
    assert mamba.ssm_path(cfg, 4096).startswith("jax.numpy (backend cpu)")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.attention_path(4096, 4096, 32, 64, True, False) == "flash"
    said = mamba.ssm_path(cfg, 4096)
    assert said.startswith("kernels hvd_ssm_scan / hvd_ssm_scan_bwd")
    assert "16 chunks" in said and "head tiles of" in said
    assert "each Mamba block checkpointed" in said


def test_the_decode_paths_refuse_the_multipliers_by_name():
    for field in ("embed_scale", "residual_scale", "attention_scale",
                  "logits_scale"):
        cfg = dataclasses.replace(t.TransformerConfig(), **{field: 0.5})
        with pytest.raises(NotImplementedError, match=field):
            decode.kv_cache_spec(cfg)


# -- the program against the reference ---------------------------------------

@pytest.fixture(scope="module")
def both_sides():
    params, batch = _params(), _batch()
    loss, grads = _program(CFG, params, batch)
    got = {"loss": loss, **get_leaves(grads, LEAVES)}
    want_loss, want_grads = _reference(params, batch)
    return got, {"loss": want_loss, **want_grads}


@pytest.mark.parametrize("what", ["loss"] + sorted(LEAVES))
def test_program_matches_the_reference(both_sides, what):
    got, want = both_sides
    assert got[what].shape == want[what].shape
    assert _rel(got[what], want[what]) < TOL, what


def test_every_leaf_s_gradient_is_compared_and_none_is_zero(both_sides):
    _got, want = both_sides
    assert len(LEAVES) == 9 + 4 + 5 + 2
    assert {name.split(".")[-1] for name in LEAVES} >= {
        "embed", "ln_f", "ssm_in", "ssm_a_log", "ssm_dt_bias", "ssm_norm",
        "ssm_d", "ssm_conv_w", "ssm_conv_b", "wq", "wk", "wv", "wo", "w1",
        "w2", "w3", "ln1", "ln2"}
    for name in LEAVES:
        assert float(jnp.linalg.norm(want[name])) > 0, name


# -- a fault fails --------------------------------------------------------------

def _norm_before_the_gate(y, z, weight, groups, eps):
    y = y.astype(jnp.float32)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True) + eps)
    return y * weight * jax.nn.silu(z.astype(jnp.float32))


_gated_norm = mamba._gated_norm


def _norm_over_groups(y, z, weight, groups, eps):
    """The hybrid cell's form: groups of channels, each its own mean (at the
    tiny width two groups of 64 where the hybrid cell has eight of 512)."""
    return _gated_norm(y, z, weight, 2, eps)


def _table_times_twelve(params):
    return {**params, "embed": params["embed"] * 12.0}


def _a_head_of_its_own(params):
    rng = np.random.RandomState(7)
    head = rng.randn(CFG.d_model, CFG.vocab_size).astype(np.float32) * 0.18
    return {**params, "lm_head": jnp.asarray(head)}


def _with_rope(cfg):
    return tuple(("attention", None, True) if kind == ATTENTION else kind
                 for kind in cfg.layer_pattern)


#: name -> (config fields changed, (module, attribute, wrong piece) patched,
#: what is done to the parameters)
FAULTS = {
    "the embedding's multiplier left out": ({"embed_scale": 1.0}, None, None),
    "the embedding's multiplier on the table, so on the head too":
        ({"embed_scale": 1.0}, None, _table_times_twelve),
    "the residual multiplier left out": ({"residual_scale": 1.0}, None,
                                         None),
    "the residual multiplier not on the Mamba mixers":
        ({}, (mamba, "scaled", lambda x, factor: x), None),
    "the residual multiplier on the whole sum":
        ({}, (t, "_ffn_block", None), None),
    "scores over sqrt(D) for the attention multiplier":
        ({"attention_scale": None}, None, None),
    "the logits' divisor left out": ({"logits_scale": 1.0}, None, None),
    "the logits multiplied where they are divided":
        ({"logits_scale": 8.0}, None, None),
    "the norm before the gate":
        ({}, (mamba, "_gated_norm", _norm_before_the_gate), None),
    "a norm over groups of channels":
        ({}, (mamba, "_gated_norm", _norm_over_groups), None),
    "an untied head": ({"tie_embeddings": False}, None, _a_head_of_its_own),
    "rope left on": ({"layer_pattern": _with_rope}, None, None),
    "two groups of B and C": ({"ssm_groups": 2}, None, "redraw"),
}


def _sum_scaled_ffn_block(p, x, cfg, logits=None, routed=None):
    """``c * (x + f(norm(x)))``: the multiplier on the whole sum."""
    plain = dataclasses.replace(cfg, residual_scale=1.0)
    y, aux = _ffn_block(p, x, plain, logits, routed)
    return y * cfg.residual_scale, aux


_ffn_block = t._ffn_block


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails(monkeypatch, fault, both_sides):
    """Each fault moves the loss or a gradient by 30 x TOL and more against
    the reference, which the sound program meets at TOL."""
    fields, patch, change = FAULTS[fault]
    fields = {k: v(CFG) if callable(v) else v for k, v in fields.items()}
    cfg = dataclasses.replace(CFG, **fields)
    if patch is not None:
        module, name, wrong = patch
        monkeypatch.setattr(module, name, wrong or _sum_scaled_ffn_block)
    params = _params()
    if change == "redraw":
        # another tree (B and C of two groups): the leaves both trees have
        # in one shape, drawn alike up to the Mamba in-projection's width
        params = _params(cfg)
    elif change is not None:
        params = change(params)
    loss, grads = _program(cfg, params, _batch())
    _got, want = both_sides
    errors = [_rel(loss, want["loss"])] + [
        _rel(g, want[name])
        for name, g in get_leaves(grads, {
            k: v for k, v in LEAVES.items()
            if k in ("ln_f", "layers.dense.w1", "layers.attention.wq")
        }).items()]
    assert max(errors) > 30 * TOL, (fault, errors)


def test_a_bfloat16_residual_stream_fails(both_sides):
    """The nearest precision below on the whole program: at these widths
    the loss hardly moves (1e-5), every gradient does."""
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    _loss, grads = _program(cfg, _params(), _batch())
    _got, want = both_sides
    for name in ("ln_f", "layers.mamba.ssm_a_log", "layers.attention.wk"):
        error = _rel(get_leaves(grads, LEAVES)[name], want[name])
        assert error > 10 * TOL, (name, error)


# -- the share: a sliced vocabulary ---------------------------------------------

def test_the_eight_slices_logits_side_by_side_are_the_uncut_model_s():
    """Chip i of the eight holds rows ``[i V/8, (i + 1) V/8)`` of the tied
    table; the ids' rows come from the slices that hold them (here all from
    slice 0, where the traffic draws them) and every chip has the same
    layers. Its logits over its slice, side by side with the others', are
    the uncut model's; and the program at the uncut table descends the
    uncut reference's loss, at slice 0 the reference's at slice 0."""
    uncut = dataclasses.replace(CFG, vocab_size=8 * CFG.vocab_size)
    params, batch = _params(uncut, seed=3), _batch(seed=3)
    v = CFG.vocab_size
    assert int(batch["tokens"].max()) < v
    sizes = {**SIZES, "vocab": 8 * v}
    with jax.default_matmul_precision("highest"):
        whole = reference.forward(params, batch["tokens"], sizes)
        slices = [reference.forward(
            {**params, "embed": params["embed"][i * v:(i + 1) * v]},
            batch["tokens"], SIZES, lookup=params["embed"][:v])
            for i in range(8)]
    assert whole.shape[-1] == 8 * v and slices[0].shape[-1] == v
    assert _rel(jnp.concatenate(slices, -1), whole) < 1e-6
    none = {"ln_f": LEAVES["ln_f"]}
    loss, _ = _program(uncut, params, batch)
    assert _rel(loss, _reference(params, batch, sizes, none)[0]) < TOL
    first = {**params, "embed": params["embed"][:v]}
    loss0, _ = _program(CFG, first, batch)
    want0 = _reference(first, batch, SIZES, none)[0]
    assert _rel(loss0, want0) < TOL
    # a smaller vocabulary is another loss, not a part of the uncut one
    assert _rel(loss0, loss) > 100 * TOL


# -- the kernels' path through the model ----------------------------------------

def test_the_attention_block_hands_the_multiplier_to_the_core(monkeypatch):
    seen = []
    real = pa.attend

    def attend(q, k, v, **kwargs):
        seen.append((q.shape, k.shape, kwargs))
        return real(q, k, v, **kwargs)
    monkeypatch.setattr(pa, "attend", attend)
    _program(CFG, _params(), _batch())
    assert seen and all(kw["scale"] == 1 / 64 and kw["window"] is None
                        and kw["causal"] for _q, _k, kw in seen)
    assert {(q[2:], k[2:]) for q, k, _kw in seen} == {((8, 8), (2, 8))}


# -- the stated float32 parts, one at a time in bfloat16 ------------------------

def _precision_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "granite_hybrid_precision",
        os.path.join(_CHIP, "tools", "granite_hybrid_precision.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("part", ["decays", "carried_state", "sums",
                                  "gate_norm", "parameters"])
def test_a_stated_float32_part_in_bfloat16_fails(monkeypatch, both_sides,
                                                 part):
    """``tools/granite_hybrid_precision.py --low <part>``'s own patch on the
    float32 program: each part moves the loss or a gradient past TOL (on the
    chip, beside bfloat16 operands, the cell's bounds see only some of them:
    PERF.md section 6, PR 49)."""
    from horovod_tpu.ops import pallas_ssm
    tool = _precision_tool()
    assert part in tool.PARTS
    for module, name in ((mamba, "_ssm_decay"), (mamba, "_carried_states"),
                         (mamba, "_chunk_sums"), (mamba, "_gated_norm"),
                         (pallas_ssm, "_decay"), (pallas_ssm, "_carry")):
        monkeypatch.setattr(module, name, getattr(module, name))  # restored
    tool.lower(part)
    params = _params()
    if part == "parameters":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    loss, grads = _program(CFG, params, _batch())
    _got, want = both_sides
    errors = [_rel(loss, want["loss"])] + [
        _rel(g, want[name]) for name, g in get_leaves(grads, {
            k: LEAVES[k] for k in ("ln_f", "layers.mamba.ssm_a_log",
                                   "layers.mamba.ssm_dt_bias")}).items()]
    assert max(errors) > 3 * TOL, (part, errors)


def test_a_multiplier_is_applied_in_float32():
    """0.22 as a bfloat16 is 0.2197: the product is made in float32 and
    rounded once."""
    from horovod_tpu.models._kinds import scaled
    x = jnp.asarray(np.random.RandomState(0).randn(4096), jnp.bfloat16)
    got = scaled(x, 0.22).astype(jnp.float32)
    exact = x.astype(jnp.float32) * 0.22
    assert got.dtype == jnp.float32 and scaled(x, 0.22).dtype == jnp.bfloat16

    def factor(y):      # the least-squares multiple of ``exact`` that y is
        return float(jnp.sum(y * exact) / jnp.sum(exact * exact))
    assert abs(factor(got) - 1) < 2e-4
    short = (x * jnp.asarray(0.22, jnp.bfloat16)).astype(jnp.float32)
    assert abs(factor(short) - 1) > 1e-3
    assert scaled(x, 1.0) is x
