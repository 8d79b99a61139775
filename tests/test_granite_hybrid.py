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
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
import chip_door
from arch import TOL, rel as _rel
from horovod_tpu.models import mamba
from horovod_tpu.models import transformer as t
from horovod_tpu.ops import pallas_attention as pa

ARCH = arch.get("granite_hybrid")
adapter, reference = ARCH.adapter, ARCH.reference
CFG, LEAVES = ARCH.CFG, ARCH.LEAVES
_cell = ARCH.cell
MIXER, FFN, ATTENTION = ("mamba",), ("dense",), ("attention", None, False)
PERIOD = (MIXER, FFN) * 5 + (ATTENTION, FFN) + (MIXER, FFN) * 4
#: one Mamba layer and the attention layer, each with its FFN: what a fault
#: is shown on
SMALL = ARCH.cut({"num_hidden_layers": 2,
                  "layer_types": ["mamba", "attention"]})


def _program(arch_, cfg, params, batch):
    """(loss, gradients) of ``arch_.program``, which has no auxiliary
    loss."""
    loss, aux, grads = arch_.program(cfg, params, batch)
    assert set(aux) == {"aux_loss"} and float(aux["aux_loss"]) == 0.0
    return loss, grads


# -- the configuration ---------------------------------------------------------

def test_the_mamba_blocks_alone_are_checkpointed():
    """(the ladder's kept rung)"""
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
    n = arch.count(arch.drawn_shapes(adapter, cfg, config))
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
    fwd, bwd, xent, scan = (
        chip_door.roofline("granite-4.0-h-micro.s4096", kernel)
        for kernel in ("hvd_flash_attention", "hvd_flash_bwd",
                       "hvd_fused_xent", "hvd_ssm_scan"))
    config, job = _cell(tiny=False)
    sizes = adapter.shapes(config, job)
    once = 2 * 2 * 32 * 64 * 4096 * 4097 / 2
    need = fwd(sizes)
    assert need["flops"] == once
    assert need["bytes"] == 2 * 4096 * (32 + 8) * 64 * 2 + 32 * 4096 * 4
    need = bwd(sizes)
    assert need["flops"] == 2.5 * once
    assert need["bytes"] == 4 * 4096 * (32 + 8) * 64 * 2 + 2 * 32 * 4096 * 4
    need = xent(sizes)
    assert need["bytes"] == 2 * 4096 * 12544 * 2 + 12 * 4096
    assert need["bytes"] / 819e9 > need["flops"] / 197e12
    # nine Mamba blocks, two forward calls and one backward call each, ONE
    # group: the scores and b and c once, whatever the head tiles
    need = scan(sizes)
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


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("what", ["loss"] + [f"grad:{k}"
                                             for k in sorted(LEAVES)])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss"} and float(aux["aux_loss"]) == 0.0
    assert got[what].shape == want[what].shape
    assert _rel(got[what], want[what]) < TOL, what


def test_every_leaf_s_gradient_is_compared_and_none_is_zero():
    _got, want, _aux, _grads = ARCH.sides
    assert len(LEAVES) == 9 + 4 + 5 + 2
    assert {name.split(".")[-1] for name in LEAVES} >= {
        "embed", "ln_f", "ssm_in", "ssm_a_log", "ssm_dt_bias", "ssm_norm",
        "ssm_d", "ssm_conv_w", "ssm_conv_b", "wq", "wk", "wv", "wo", "w1",
        "w2", "w3", "ln1", "ln2"}
    for name in LEAVES:
        assert float(jnp.linalg.norm(want[f"grad:{name}"])) > 0, name

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


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.CFG.layer_pattern == (MIXER, FFN, ATTENTION, FFN)
    assert sorted(SMALL.LEAVES) == sorted(LEAVES)
    assert SMALL.sound < TOL


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_fails(monkeypatch, fault):
    """Each fault moves the loss or a gradient of the stack of one layer a
    kind by 30 x TOL and more against the reference, which the sound program
    meets at TOL."""
    fields, patch, change = FAULTS[fault]
    fields = {k: v(SMALL.CFG) if callable(v) else v
              for k, v in fields.items()}
    cfg = dataclasses.replace(SMALL.CFG, **fields)
    if patch is not None:
        module, name, wrong = patch
        monkeypatch.setattr(module, name, wrong or _sum_scaled_ffn_block)
    params = SMALL.kept()[0]
    if change == "redraw":
        # another tree (B and C of two groups): the leaves both trees have
        # in one shape, drawn alike up to the Mamba in-projection's width
        params = SMALL.params(cfg)
    elif change is not None:
        params = change(params)
    err = SMALL.error(fault, cfg, params, only=(
        "loss", "grad:ln_f", "grad:layers.dense.w1",
        "grad:layers.attention.wq"))
    assert err > 30 * TOL, (fault, err)


def test_a_bfloat16_residual_stream_fails():
    """The nearest precision below on the whole program: at these widths
    the loss hardly moves (1e-5), every gradient does."""
    cfg = dataclasses.replace(SMALL.CFG, dtype=jnp.bfloat16)
    params, batch, _want = SMALL.kept()
    _loss, grads = SMALL.plain(cfg, params, batch)
    for name in ("ln_f", "layers.mamba.ssm_a_log", "layers.attention.wk"):
        got = arch.get_leaves(grads, {name: SMALL.LEAVES[name]})
        error = SMALL.error(f"a bfloat16 residual stream, {name}",
                            got={f"grad:{name}": got[name]})
        assert error > 10 * TOL, (name, error)

# -- the share: a sliced vocabulary ---------------------------------------------

def test_the_eight_slices_logits_side_by_side_are_the_uncut_model_s():
    """Chip i of the eight holds rows ``[i V/8, (i + 1) V/8)`` of the tied
    table; the ids' rows come from the slices that hold them (here all from
    slice 0, where the traffic draws them) and every chip has the same
    layers (here one of each kind: the cut is the table's). Its logits over
    its slice, side by side with the others', are the uncut model's; and the
    program at the uncut table descends the uncut reference's loss, at slice
    0 the reference's at slice 0."""
    cfg, sizes0 = SMALL.CFG, SMALL.SIZES
    uncut = dataclasses.replace(cfg, vocab_size=8 * cfg.vocab_size)
    params, batch = SMALL.params(uncut, seed=3), SMALL.batch(seed=3)
    v = cfg.vocab_size
    assert int(batch["tokens"].max()) < v
    sizes = {**sizes0, "vocab": 8 * v}
    one_slice = jax.jit(lambda p, tok, lookup: reference.forward(
        p, tok, sizes0, lookup=lookup))
    with jax.default_matmul_precision("highest"):
        whole = jax.jit(lambda p, tok: reference.forward(p, tok, sizes))(
            params, batch["tokens"])
        slices = [one_slice(
            {**params, "embed": params["embed"][i * v:(i + 1) * v]},
            batch["tokens"], params["embed"][:v]) for i in range(8)]
    assert whole.shape[-1] == 8 * v and slices[0].shape[-1] == v
    assert _rel(jnp.concatenate(slices, -1), whole) < 1e-6
    none = {"ln_f": LEAVES["ln_f"]}
    loss, _ = _program(SMALL, uncut, params, batch)
    assert _rel(loss, SMALL.want(params, batch, sizes, none)["loss"]) < TOL
    first = {**params, "embed": params["embed"][:v]}
    loss0, _ = _program(SMALL, cfg, first, batch)
    want0 = SMALL.want(first, batch, sizes0, none)["loss"]
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
    _program(SMALL, SMALL.CFG, *SMALL.kept()[:2])
    assert seen and all(kw["scale"] == 1 / 64 and kw["window"] is None
                        and kw["causal"] for _q, _k, kw in seen)
    assert {(q[2:], k[2:]) for q, k, _kw in seen} == {((8, 8), (2, 8))}


# -- the stated float32 parts, one at a time in bfloat16 ------------------------

def _precision_tool():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "granite_hybrid_precision",
        os.path.join(arch.CHIP, "tools", "granite_hybrid_precision.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    return tool


@pytest.mark.parametrize("part", ["decays", "carried_state", "sums",
                                  "gate_norm", "parameters"])
def test_a_stated_float32_part_in_bfloat16_fails(monkeypatch, part):
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
    params = SMALL.kept()[0]
    if part == "parameters":
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
    err = SMALL.error(f"float32 part {part} in bfloat16", tree=params, only=(
        "loss", "grad:ln_f", "grad:layers.mamba.ssm_a_log",
        "grad:layers.mamba.ssm_dt_bias"))
    assert err > 3 * TOL, (part, err)


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
