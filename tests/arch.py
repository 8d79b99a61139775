"""What supporting an architecture means in tier-1: one harness for the
tests of a benchmark configuration's model (a plain module, as the
``*_worker.py`` files are; pytest collects nothing here).

``ROWS`` holds one entry an architecture, which is what a ``model_config`` PR
adds: its adapter (``benchmarks/chip/adapters/<name>.py``, whose plain
reference is ``reference/<name>.py``), its configuration and workload files,
the named leaves beyond ``adapter._leaf_paths``, which of ``init_params``'
leaves move off their initial 1 or 0, and the rows of data that
``tests/test_architectures.py`` runs for every architecture alike.
``get(name)`` is that architecture at its ``tiny`` preset: the files read,
``SIZES``, ``CFG``, ``LEAVES``, ``params``, ``batch``, ``program`` (through
``make_grad_fn`` on a mesh, as the adapter calls it), the sound program's and
the reference's two sides computed once and kept (``sides``), and ``error``,
the one rule for a fault.

A fault costs the smallest program that holds the faulty term: ``cut`` gives
the same architecture at fewer layers (one period, or one layer of each
kind), and ``error`` there compiles the changed program alone; the sound
reference of a stack is computed once a module. A wrong reading of the
equations that the config cannot say goes into the plain reference instead
(``tests/test_lfm2_moe.py``'s ``_swapped_error``).
"""

from __future__ import annotations

import dataclasses
import functools
import importlib
import json
import os
import sys

import numpy as np
import jax
import jax.numpy as jnp

from horovod_tpu.models import _kinds
from horovod_tpu.models import transformer as t
from horovod_tpu.models import shard_batch, shard_params
from horovod_tpu.models._kinds import Rope, Yarn
from horovod_tpu.parallel import build_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(REPO, "benchmarks", "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

from trees import get_leaves                              # noqa: E402

#: both sides are float32 at the tiny sizes and differ in the order of their
#: sums (1e-7 to 1e-5); each file's docstring says what 1e-4 is far below
TOL = 1e-4


def rel(got, want) -> float:
    """The distance of ``got`` from ``want`` over ``want``'s norm (experts
    no token chose have a gradient of zeros on both sides)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


# -- which of init_params' leaves move ----------------------------------------

def _norms_off_one(bias: bool):
    """Every norm's weight off 1 (a norm after a norm is no change while
    both weights are 1) and, with ``bias``, the routers' expert bias off 0
    (so that the choice is of score + bias)."""
    def moved(arch, tree, rng, seed):
        rng = np.random.RandomState(seed + 100)

        def leaf(path, a):
            if np.all(a == 1):
                a = 1 + 0.3 * rng.randn(*a.shape).astype(np.float32)
            if bias and path[-1].key == "router_bias":
                a = 0.1 * rng.randn(*a.shape).astype(np.float32)
            return a
        return jax.tree_util.tree_map_with_path(leaf, tree)
    return moved


def _keye_moved(arch, tree, rng, seed):
    """Every norm's weight off 1 and the index key's bias off 0 (a bias of
    0 hides a wrong gradient, and a dropped one)."""
    tree = _norms_off_one(bias=False)(arch, tree, rng, seed)
    bias = tree["layers"]["k_idx_norm_bias"]
    tree["layers"]["k_idx_norm_bias"] = (
        0.3 * np.random.RandomState(seed + 200).randn(*bias.shape)
    ).astype(np.float32)
    return tree


def _kimi_moved(arch, tree, rng, seed):
    """Every norm's weight off 1, the table at a scale at which the logits
    say something, and the routers' bias off 0 under routers wide enough
    that the token and not the bias decides the choice (at init_params'
    0.02 every token of a layer picks the bias's two experts, and a cut
    stack's held experts see none)."""
    tree = _norms_off_one(bias=False)(arch, tree, rng, seed)
    tree["embed"] = tree["embed"] * (1.0 / 0.02)
    experts = tree["layers"]["experts"]
    experts["router"] = experts["router"] * 10.0
    experts["router_bias"] = (0.05 * np.random.RandomState(seed + 300).randn(
        *experts["router_bias"].shape)).astype(np.float32)
    return tree


def _qwen_moved(arch, tree, rng, seed):
    """The zero-centred norm weights off 0 and the delta blocks' output norm
    off 1 (a weight of 0 or 1 hides whether the scale is ``1 + w`` or
    ``w``), the table at a scale at which the logits say something, and the
    routers wide enough that a cut stack's held experts see tokens."""
    rng = np.random.RandomState(seed + 100)

    def leaf(path, a):
        if np.all(a == 0) or np.all(a == 1):
            a = a + 0.3 * rng.randn(*a.shape).astype(np.float32)
        return a
    tree = jax.tree_util.tree_map_with_path(leaf, tree)
    tree["embed"] = tree["embed"] * (1.0 / 0.02)
    experts = tree["layers"]["experts"]
    experts["router"] = experts["router"] * 10.0
    return tree


def _granite_moved(arch, tree, rng, seed):
    """The table at the configuration's scale (at 0.02 the logits say
    nothing) and the norm weights and the skip off their ones, so that a
    gradient through them is not through a 1."""
    tree["embed"] = tree["embed"] * (
        arch.CONFIG["assumed"]["embedding_std"] / 0.02)
    for stack, names in (("mamba", ("ln1", "ssm_norm", "ssm_d")),
                         ("dense", ("ln2",)), ("attention", ("ln1",))):
        for name in names:
            leaf = tree["layers"][stack][name]
            tree["layers"][stack][name] = (
                leaf + 0.2 * rng.randn(*leaf.shape)).astype(np.float32)
    return tree


def _ouro_moved(arch, tree, rng, seed):
    if "exit_gate_bias" in tree:      # a bias of 0 hides a wrong gradient
        tree["exit_gate_bias"] = np.full((1,), 0.3, np.float32)
    return tree


# -- what an architecture compares beside the loss and the named gradients ----

def logits(arch, params, tokens, cfg=None):
    """The program's blocks and head on one device, up to the logits (the
    stacks that are one scan of two-sublayer layers)."""
    cfg = cfg or arch.CFG
    x = params["embed"].astype(cfg.dtype)[tokens]
    x, _aux = t._run_layers(params["layers"], x,
                            jnp.arange(tokens.shape[1]), cfg)
    return _kinds.rmsnorm(x, params["ln_f"], cfg.norm_eps) @ params["lm_head"]


def _routed_got(*names):
    def got(arch, params, batch, aux):
        return {"logits": logits(arch, params, batch["tokens"]),
                **{name: aux[name] for name in names}}
    return got


def _routed_want(*names):
    """``reference.losses``' (total, xent, balance, z, choices) and
    ``reference.forward``'s logits."""
    def want(arch, params, batch, sizes):
        with jax.default_matmul_precision("highest"):
            losses, logits = jax.jit(lambda p, b: (
                arch.reference.losses(p, b, sizes),
                arch.reference.forward(p, b["tokens"], sizes)[0]))(
                    params, batch)
        at = {"load_balance_loss": 2, "router_z_loss": 3}
        return {"loss": losses[0], "logits": logits,
                **{name: losses[at[name]] for name in names}}
    return want


def _glm_got(arch, params, batch, aux):
    return {"main_loss": aux["main_loss"], "mtp_loss": aux["mtp_loss"]}


def _glm_want(arch, params, batch, sizes):
    with jax.default_matmul_precision("highest"):
        parts = jax.jit(lambda p, b: arch.reference.losses(p, b, sizes))(
            params, batch)
    return {"main_loss": parts[1], "mtp_loss": parts[5]}


def _keye_got(arch, params, batch, aux):
    return {"logits": logits(arch, params, batch["tokens"]),
            "index_loss": aux["index_loss"]}


def _keye_want(arch, params, batch, sizes):
    """``reference.losses``' (objective, xent, the indexers' summed loss,
    selections) and ``reference.forward``'s logits."""
    with jax.default_matmul_precision("highest"):
        losses, logits = jax.jit(lambda p, b: (
            arch.reference.losses(p, b, sizes)[:3],
            arch.reference.forward(p, b["tokens"], sizes)[0]))(params, batch)
    return {"logits": logits, "index_loss": losses[2]}


_OURO_REPORTED = ("step_losses", "exit_share", "gate_entropy")


def _ouro_got(arch, params, batch, aux):
    return {k: aux[k] for k in _OURO_REPORTED}


def _ouro_loss_and_grads(arch, params, leaves, batch, sizes):
    """The plain objective, no checkpoint anywhere, and its gradients (the
    scan and the checkpoints are ``reference.loss_and_grads``', the chip's
    check)."""
    def objective(p):
        return arch.reference.objective(p, batch, sizes)[0]
    with jax.default_matmul_precision("highest"):
        total, grads = jax.jit(jax.value_and_grad(objective))(params)
    return total, get_leaves(grads, leaves)


def _ouro_want(arch, params, batch, sizes):
    with jax.default_matmul_precision("highest"):
        reported = jax.jit(lambda p, b: arch.reference.objective(
            p, b, sizes)[1:])(params, batch)
    return dict(zip(_OURO_REPORTED, reported))


def _every_leaf(arch) -> dict:
    """Every leaf of the tree, whole (``trees.py``'s form)."""
    flat = jax.tree_util.tree_flatten_with_path(
        jax.eval_shape(lambda: arch.params()))[0]
    return {".".join(k.key for k in path): (tuple(k.key for k in path), None)
            for path, _leaf in flat}


# -- the rows -----------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Row:
    """One architecture's entry: what a ``model_config`` PR adds."""
    config: str                 # benchmarks/chip/configs/<config>.json
    workload: str               # benchmarks/chip/workloads/<workload>.json
    #: ``(config, sizes) ->`` what ``adapter._leaf_paths`` takes, or None
    #: where the tests name every leaf themselves
    paths: object = None
    #: the named leaves beyond ``adapter._leaf_paths``: a dict, or
    #: ``(arch) ->`` one
    leaves: object = dataclasses.field(default_factory=dict)
    #: ``(arch, init_params' tree, its rng, seed) ->`` the tree with the
    #: leaves that say nothing at their initial values moved
    moved: object = None
    #: ``(arch) ->`` what ``adapter._init_function`` takes after the config
    init_args: object = lambda arch: (arch.CONFIG,)
    #: what the two sides compare beside the loss and the named gradients
    got_more: object = None     # (arch, params, batch, aux) -> dict
    want_more: object = None    # (arch, params, batch, sizes) -> dict
    #: ``(arch, params, leaves, batch, sizes) -> (loss, named gradients)``
    #: where it is not ``reference.loss_and_grads``
    loss_and_grads: object = None
    # what tests/test_architectures.py reads (None or empty: the architecture
    # has no such case)
    #: the tiny preset the issue asked for: ``{"cfg": fields of CFG, "job":
    #: keys of JOB, "sizes": keys of SIZES, "config": keys of CONFIG}``
    tiny: dict = None
    #: the modules ``reference/<name>.py`` may import
    reference_imports: tuple = None
    #: ``(spread of a drawn leaf's std about init_params', whether the table
    #: is drawn at ``assumed.embedding_std`` instead)``
    drawn: tuple = None
    #: the share cut of one expert layer: ``(the shares, the experts of the
    #: uncut layer, the least distance of the shares' outputs summed, which
    #: count the shared expert once a share, from the uncut layer's)``
    shares: tuple = None
    #: the shape of ``router_choices`` at the tiny preset, ``(expert layers,
    #: tokens, top-k)``, where ``reference.losses`` returns its own
    choices: tuple = None
    #: what the decode paths refuse by name, ``(id, the name's pattern,
    #: config or (arch) -> config (None: the tiny CFG), the paths)``; the
    #: paths are words of ``spec paged greedy flatten``
    refused: tuple = ()


_EXPERTS, _DENSE, _CONV, _MAMBA = ("experts",), ("dense",), ("conv",), (
    "mamba",)
_DELTA = ("delta",)
_NOPE = ("attention", None, False)
_LAGUNA_WINDOW = ("attention", 8, Rope(10000.0), 8, True)
_LAGUNA_FULL = ("attention", None, Rope(
    500000.0, 8, Yarn(64.0, 16, 64.0, 1.0, 1.4158883083359672)), 6, True)
_QWEN_ATTENTION = ("attention", None, Rope(1e7, 4), None, "channel")
_SIGMOID_SHARE = {"n_experts": 16, "moe_top_k": 4, "held_experts": 2,
                  "expert_share": (0, 8), "moe_router_scores": "sigmoid",
                  "moe_renormalize": True, "moe_balance_weight": 0.0}
_PAGED = "spec paged greedy"


def _plain(**fields):
    return lambda arch: t.TransformerConfig(**fields)


#: the parent's dense GPT block at Ouro's tiny widths: what a new field is
#: switched on in, one at a time
DENSE = t.TransformerConfig(vocab_size=512, d_model=128, n_heads=4,
                            n_layers=2, d_ff=256, max_seq=64,
                            dtype=jnp.float32)


def _dense_tree(field):
    """``DENSE`` with ``field`` on, and its own tree."""
    return lambda arch: dataclasses.replace(
        DENSE, **{field: 2 if field == "n_loops" else True})


ROWS = {
    "olmoe": Row(
        config="olmoe-1b-7b", workload="train.s4096.b2",
        leaves={
            "router": (("layers", "router"), (0, 1)),
            "expert_gate": (("layers", "we1"), (0, 1, 3)),
            "expert_up": (("layers", "we3"), (0, 1, 3)),
            "expert_down": (("layers", "we2"), (0, 1, 3)),
            "wq": (("layers", "wq"), (0, 0)),
            "lm_head": (("lm_head",), None)},
        init_args=lambda arch: (),
        got_more=_routed_got("load_balance_loss", "router_z_loss"),
        want_more=_routed_want("load_balance_loss", "router_z_loss"),
        reference_imports=(), drawn=(0.1, False)),
    "ouro": Row(
        config="ouro-2.6b", workload="train.s4096.b1",
        leaves={
            "embed": (("embed",), None), "ln_f": (("ln_f",), None),
            "lm_head": (("lm_head",), None),
            "exit_gate": (("exit_gate",), None),
            "exit_gate_bias": (("exit_gate_bias",), None),
            **{name: (("layers", name), (0, layer))
               for layer, names in enumerate((
                   ("ln1", "wq", "wk", "wo", "w1", "ln2_post"),
                   ("ln1_post", "wv", "ln2", "w3", "w2")))
               for name in names}},
        moved=_ouro_moved, init_args=lambda arch: (),
        got_more=_ouro_got, want_more=_ouro_want,
        loss_and_grads=_ouro_loss_and_grads,
        reference_imports=(), drawn=(0.15, False),
        refused=tuple((field, field, _dense_tree(field), "flatten")
                      for field in ("post_norm", "ffn_gated", "n_loops"))),
    "smallthinker": Row(
        config="smallthinker-21b-a3b", workload="train.s8192.b1",
        paths=lambda config, sizes: sizes["layer_windows"],
        leaves={
            "full_key": (("layers", "wk"), (0, 4)),
            "window_query": (("layers", "wq"), (0, 5)),
            "first_router": (("layers", "router"), (0, 0)),
            "expert_gate": (("layers", "we1"), (0, 7, 1)),
            "expert_up": (("layers", "we3"), (0, 7, 1)),
            "wo": (("layers", "wo"), (0, 2)),
            "embed": (("embed",), None)},
        init_args=lambda arch: (arch.CONFIG["assumed"]["embedding_std"],),
        got_more=_routed_got("load_balance_loss"),
        want_more=_routed_want("load_balance_loss"),
        tiny={"cfg": {
            "dtype": jnp.float32, "n_layers": 8,
            "layer_pattern": ((None, False),) + ((32, True),) * 3,
            "n_heads": 8, "kv_heads": 2, "head_dim": 16, "d_model": 64,
            "n_experts": 8, "moe_top_k": 2, "held_experts": 2,
            "expert_share": (0, 4)},
            "job": {"seq_len": 64}},
        refused=(
            ("layer_pattern", "layer_pattern",
             _plain(layer_pattern=((None, True), (64, True))), _PAGED),
            ("n_kv_heads", "n_kv_heads", _plain(n_kv_heads=2), _PAGED),
            ("moe_router_input", "moe_router_input",
             _plain(moe_router_input="block_input"), _PAGED),
            ("expert_share", "expert_share",
             _plain(n_experts=8, expert_share=(1, 4)), _PAGED))),
    "nemotron_h": Row(
        config="nemotron-3-nano-30b-a3b", workload="train.s8192.b1.hybrid",
        paths=lambda config, sizes: config["hybrid_override_pattern"],
        leaves={
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
            "shared_up": (("layers", "experts", "ws1"), (0, 2))},
        tiny={"cfg": {
            "dtype": jnp.float32, "n_layers": 18, "one_sublayer": True,
            "layer_pattern": (_MAMBA, _EXPERTS) * 3 + (_MAMBA, _NOPE,
                                                      _EXPERTS),
            "ssm_heads": 4, "ssm_head_dim": 8, "ssm_state": 16,
            "ssm_groups": 2, "ssm_conv": 4, "ssm_chunk": 16,
            "ssm_inner": 32, "d_model": 64,     # (not expand x d_model)
            "n_heads": 4, "kv_heads": 2, "head_dim": 16,
            **_SIGMOID_SHARE, "moe_activation": "relu2", "moe_gated": False,
            "moe_routed_scale": 2.5, "moe_shared_width": 64},
            "config": {"hybrid_override_pattern": "MEMEMEM*E" * 2,
                       "expand": 2},
            "job": {"seq_len": 4 * 16}},        # four chunks
        refused=(
            ("ssm_heads", "ssm_heads", None, _PAGED),
            ("moe_router_scores", "moe_router_scores",
             _plain(n_experts=8, moe_router_scores="sigmoid"), _PAGED),
            ("moe_shared_width", "moe_shared_width",
             _plain(n_experts=8, moe_shared_width=64), _PAGED)),
        shares=(16, 32, 1.0)),
    "glm4_moe_lite": Row(
        config="glm-4.7-flash", workload="train.s8192.b1.latent",
        paths=lambda config, sizes: sizes["expert_layers"],
        leaves={
            "embed": (("embed",), None), "final_norm": (("ln_f",), None),
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
            "mtp_experts_down": (("mtp", "layers", "experts", "we2"),
                                 (0,))},
        moved=_norms_off_one(bias=False),
        got_more=_glm_got, want_more=_glm_want,
        tiny={"cfg": {
            "dtype": jnp.float32, "one_sublayer": True,
            "layer_pattern": (("latent",), _EXPERTS),
            "lead_pattern": (("latent",), _DENSE),
            "n_layers": 4, "mtp_depth": 1, "mtp_weight": 0.3,
            "n_heads": 4, "kv_heads": 4, "head_dim": 16, "rope_width": 4,
            "q_latent": 24, "kv_latent": 16,
            "d_ff": 32, "dense_ff": 96, "moe_shared_width": 32,
            **_SIGMOID_SHARE, "moe_activation": "silu", "moe_gated": True,
            "ffn_gated": True, "moe_routed_scale": 1.8,
            "tie_embeddings": False}},
        refused=(
            ("kv_latent", "kv_latent", None, _PAGED),
            ("lead_pattern", "lead_pattern", None, _PAGED),
            ("mtp_depth", "mtp_depth", None, _PAGED),
            ("mtp_depth alone", "mtp_depth", _plain(mtp_depth=1), _PAGED),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(8, 16, 1.0)),
    "granite_hybrid": Row(
        config="granite-4.0-h-micro", workload="train.s4096.b1.ssm",
        leaves=_every_leaf, moved=_granite_moved,
        tiny={"cfg": {
            "dtype": jnp.float32, "n_layers": 20, "one_sublayer": True,
            "layer_pattern": (_MAMBA, _DENSE) * 5 + (_NOPE, _DENSE)
            + (_MAMBA, _DENSE) * 4,
            "ssm_heads": 8, "ssm_head_dim": 16, "ssm_state": 16,
            "ssm_groups": 1, "ssm_conv": 4, "ssm_chunk": 16,
            "ssm_inner": 128, "d_model": 64,    # mamba_expand x d_model
            "n_heads": 8, "kv_heads": 2, "head_dim": 8,   # groups of 4
            "embed_scale": 12.0, "residual_scale": 0.22,
            "attention_scale": 1 / 64,          # (not head_dim ** -0.5)
            "logits_scale": 1 / 8, "ffn_gated": True,
            "tie_embeddings": True, "dense_ff": 128, "remat": None,
            "n_experts": 0, "norm_eps": 1e-5},
            "config": {"layer_types": ["mamba"] * 5 + ["attention"]
                       + ["mamba"] * 4, "mamba_expand": 2},
            "job": {"seq_len": 4 * 16}},        # chunk < S
        refused=tuple(
            (field, field, lambda arch, field=field: dataclasses.replace(
                t.TransformerConfig(), **{field: 0.5}), "spec")
            for field in ("embed_scale", "residual_scale", "attention_scale",
                          "logits_scale"))),
    "laguna": Row(
        config="laguna-xs.2", workload="train.s8192.b1.banded",
        paths=lambda config, sizes: config,
        leaves={
            "embed": (("embed",), None), "final_norm": (("ln_f",), None),
            "lead_gate": (("lead", "attention_6_gated", "wg"), (0,)),
            "lead_key": (("lead", "attention_6_gated", "wk"), (0,)),
            "lead_norm": (("lead", "attention_6_gated", "ln1"), (0,)),
            "dense_gate": (("lead", "dense", "w1"), (0,)),
            "dense_up": (("lead", "dense", "w3"), (0,)),
            "window_query": (("layers", "attention_8_gated", "wq"), (0, 4)),
            "window_value": (("layers", "attention_8_gated", "wv"), (0, 5)),
            "window_out": (("layers", "attention_8_gated", "wo"), (0, 2)),
            "second_window_gate": (("layers", "attention_8_gated", "wg"),
                                   (0, 3)),
            "full_key": (("layers", "attention_6_gated", "wk"), (0, 0)),
            "full_gate": (("layers", "attention_6_gated", "wg"), (0, 1)),
            "full_out": (("layers", "attention_6_gated", "wo"), (0, 0)),
            "first_router": (("layers", "experts", "router"), (0, 0)),
            "expert_gate": (("layers", "experts", "we1"), (0, 3, 1)),
            "expert_up": (("layers", "experts", "we3"), (0, 3, 0)),
            "shared_gate": (("layers", "experts", "ws1"), (0, 1)),
            "shared_up": (("layers", "experts", "ws3"), (0, 6)),
            "shared_down": (("layers", "experts", "ws2"), (0, 7)),
            "experts_norm": (("layers", "experts", "ln2"), (0, 2))},
        moved=_norms_off_one(bias=True),
        tiny={"cfg": {
            "dtype": jnp.float32, "one_sublayer": True,
            "layer_pattern": (_LAGUNA_WINDOW, _EXPERTS) * 3
            + (_LAGUNA_FULL, _EXPERTS),
            "lead_pattern": (_LAGUNA_FULL, _DENSE),
            "n_layers": 16,                     # two periods
            # groups of 4 and 3 on the same two key/value heads
            "n_heads": 6, "kv_heads": 2, "head_dim": 16,
            "d_ff": 16, "dense_ff": 64, "moe_shared_width": 16,
            **_SIGMOID_SHARE, "moe_activation": "silu", "moe_gated": True,
            "ffn_gated": True, "moe_routed_scale": 2.5,
            "tie_embeddings": False, "qk_norm": False},
            "job": {"seq_len": 64},             # eight windows of 8
            "sizes": {
                "layer_heads": [6, 8, 8, 8, 6, 8, 8, 8, 6],
                "layer_windows": [None, 8, 8, 8, None, 8, 8, 8, None]}},
        reference_imports=("__future__", "math", "numpy", "jax", "trees",
                           "reference"),
        drawn=(0.25, True),
        refused=(
            ("the cell", "layer_pattern", None, "spec greedy"),
            ("a gated kind alone", "layer_pattern", _plain(layer_pattern=(
                ("attention", None, True, None, True), _DENSE)),
             "spec greedy"),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(8, 16, 0.5), choices=(8, 2 * 64, 4)),
    "lfm2_moe": Row(
        config="lfm2-24b-a2b", workload="train.s8192.b2",
        paths=lambda config, sizes: config,
        leaves={
            "final_norm": (("ln_f",), None),
            "lead_norm": (("lead", "conv", "ln1"), (0,)),
            "lead_taps": (("lead", "conv", "conv_w"), (0,)),
            "lead_conv_out": (("lead", "conv", "conv_out"), (0,)),
            "dense_gate": (("lead", "dense", "w1"), (0,)),
            "dense_up": (("lead", "dense", "w3"), (0,)),
            "conv_in": (("layers", "conv", "conv_in"), (0, 2)),
            "conv_taps": (("layers", "conv", "conv_w"), (0, 0)),
            "conv_norm": (("layers", "conv", "ln1"), (0, 4)),
            "query": (("layers", "attention", "wq"), (0, 1)),
            "value": (("layers", "attention", "wv"), (0, 0)),
            "out": (("layers", "attention", "wo"), (0, 1)),
            "k_norm": (("layers", "attention", "k_norm"), (0, 0)),
            "second_q_norm": (("layers", "attention", "q_norm"), (0, 1)),
            "first_router": (("layers", "experts", "router"), (0, 0)),
            "expert_gate": (("layers", "experts", "we1"), (0, 3, 0)),
            "expert_up": (("layers", "experts", "we3"), (0, 5, 0)),
            "experts_norm": (("layers", "experts", "ln2"), (0, 2))},
        moved=_norms_off_one(bias=True),
        tiny={"cfg": {
            "dtype": jnp.float32, "one_sublayer": True,
            "layer_pattern": (("attention", None, True), _EXPERTS)
            + (_CONV, _EXPERTS) * 3,
            "lead_pattern": (_CONV, _DENSE),
            "n_layers": 16,                     # two periods of eight blocks
            "d_model": 32, "n_heads": 4, "kv_heads": 2, "head_dim": 16,
            "d_ff": 16, "dense_ff": 64, "conv_taps": 3, "vocab_size": 512,
            **_SIGMOID_SHARE, "moe_activation": "silu", "moe_gated": True,
            "ffn_gated": True, "moe_routed_scale": 1.0,
            "moe_shared_width": 0, "tie_embeddings": True,
            "qk_norm": "head", "norm_eps": 1e-5, "rope_theta": 1e6},
            "job": {"seq_len": 64, "batch_per_chip": 2},
            "sizes": {
                "layer_types": ["conv"] + ["full_attention", "conv", "conv",
                                           "conv"] * 2,
                "layer_dense": [True] + [False] * 8}},
        reference_imports=("__future__", "math", "numpy", "jax", "trees",
                           "reference"),
        drawn=(0.25, True),
        refused=(
            ("the cell", "qk_norm.*layer_pattern.*conv_taps", None,
             "spec greedy"),
            ("the mixer alone", "layer_pattern.*conv_taps",
             _plain(layer_pattern=(_CONV, _DENSE), conv_taps=3),
             "spec greedy"),
            ("the heads' norm alone", "qk_norm", _plain(qk_norm="head"),
             "spec greedy"),
            ("the mixer, in words", "short convolution",
             _plain(layer_pattern=(_CONV, _DENSE), conv_taps=3), "spec"),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(8, 16, None), choices=(8, 2 * 64, 4)),
    "keye_vl2": Row(
        config="keye-vl-2.0-30b-a3b", workload="train.s16384.b1.sparse",
        leaves=_every_leaf, moved=_keye_moved,
        init_args=lambda arch: (arch.CONFIG["assumed"]["embedding_std"],),
        got_more=_keye_got, want_more=_keye_want,
        tiny={"cfg": {
            "dtype": jnp.float32, "n_layers": 2, "d_model": 64,
            "n_heads": 8, "kv_heads": 2, "head_dim": 16, "qk_norm": "head",
            "index_topk": 16, "index_heads": 2, "index_head_dim": 8,
            "n_experts": 16, "moe_top_k": 2, "held_experts": 2,
            "expert_share": (0, 8), "moe_gated": True,
            "moe_renormalize": True, "moe_balance_weight": 0.0,
            "tie_embeddings": False, "rope_theta": 1e7, "remat": True},
            "job": {"seq_len": 64}},            # four times topk
        reference_imports=("__future__", "math", "jax", "trees"),
        drawn=(0.25, True),
        refused=(
            ("the cell", "index_topk", None, _PAGED),
            ("the index alone", "index_topk", _plain(
                index_topk=16, index_heads=2, index_head_dim=8), _PAGED),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(8, 16, None)),
    "kimi_linear": Row(
        config="kimi-linear-48b-a3b", workload="train.s8192.b1.delta",
        leaves=_every_leaf, moved=_kimi_moved,
        tiny={"cfg": {
            "dtype": jnp.float32, "one_sublayer": True,
            "layer_pattern": (_DELTA, _EXPERTS) * 2 + (("latent",), _EXPERTS)
            + (_DELTA, _EXPERTS),
            "lead_pattern": (_DELTA, _DENSE),
            "n_layers": 8,                      # layers 2-5: one period
            "d_model": 64, "delta_heads": 2, "delta_head_dim": 16,
            "delta_taps": 4, "delta_chunk": 8,
            "n_heads": 4, "kv_heads": 4, "head_dim": 16, "rope_width": 4,
            "q_latent": 0, "kv_latent": 16, "latent_rope": False,
            "value_width": 8,
            "d_ff": 32, "dense_ff": 96, "moe_shared_width": 32,
            **{**_SIGMOID_SHARE, "moe_top_k": 2},
            "moe_activation": "silu", "moe_gated": True, "ffn_gated": True,
            "moe_routed_scale": 2.446, "tie_embeddings": False,
            "norm_eps": 1e-5},
            "job": {"seq_len": 8 * 8},          # eight chunks carry a state
            "sizes": {
                "layer_mixers": ["delta"] * 3 + ["latent", "delta"],
                "head_dim": 128, "qk_head_dim": 16, "value_head_dim": 8}},
        reference_imports=("__future__", "math", "jax", "trees",
                           "reference"),
        # (no ``drawn``: a rate a head is two numbers at the tiny preset, no
        # sample to take a spread of; tests/test_kimi_linear.py holds the
        # adapter's draw leaf by leaf)
        refused=(
            ("the cell", "delta_heads", None, _PAGED),
            ("the mixer alone", "layer_pattern.*delta_heads", _plain(
                layer_pattern=(_DELTA, _DENSE), delta_heads=2,
                delta_head_dim=16), _PAGED),
            ("a latent block without rotation", "latent_rope", _plain(
                layer_pattern=(("latent",), _DENSE), kv_latent=16,
                rope_width=4, latent_rope=False), _PAGED),
            ("values narrower than keys", "value_width", _plain(
                layer_pattern=(("latent",), _DENSE), kv_latent=16,
                rope_width=4, value_width=8), _PAGED),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(8, 16, 1.0), choices=(4, 2 * 64, 2)),
    "qwen3_next": Row(
        config="qwen3-next-80b-a3b", workload="train.s8192.b1.gdn",
        leaves=_every_leaf, moved=_qwen_moved,
        tiny={"cfg": {
            "dtype": jnp.float32, "one_sublayer": True,
            "layer_pattern": (_DELTA, _EXPERTS) * 3 + (_QWEN_ATTENTION,
                                                      _EXPERTS),
            "lead_pattern": (), "n_layers": 8,  # layers 0-3: one period
            "d_model": 64, "delta_heads": 4, "delta_key_heads": 2,
            "delta_head_dim": 16, "delta_taps": 4, "delta_chunk": 8,
            "delta_decay": "head",
            "n_heads": 4, "kv_heads": 2, "head_dim": 16, "qk_norm": "head",
            "zero_centred_norms": True, "d_ff": 32, "moe_shared_width": 32,
            "moe_shared_gate": True, "n_experts": 16, "moe_top_k": 2,
            "held_experts": 2, "expert_share": (0, 8),
            "moe_router_scores": "softmax", "moe_renormalize": True,
            "moe_balance_weight": 0.0, "moe_activation": "silu",
            "moe_gated": True, "tie_embeddings": False, "norm_eps": 1e-6},
            "job": {"seq_len": 8 * 8},          # eight chunks carry a state
            "sizes": {
                "layer_mixers": ["delta"] * 3 + ["attention"],
                "rope_width": 4, "delta_decay_width": 1}},
        reference_imports=("__future__", "jax", "trees", "reference"),
        # (no ``drawn``: a rate a head is a dozen numbers at the tiny preset;
        # tests/test_qwen3_next.py holds the adapter's draw leaf by leaf)
        refused=(
            ("the cell", "moe_shared_gate.*delta_heads.*delta_decay"
             ".*delta_key_heads.*zero_centred_norms", None, _PAGED),
            ("a decay a head alone", "delta_decay", _plain(
                layer_pattern=(_DELTA, _DENSE), delta_heads=2,
                delta_head_dim=16, delta_decay="head"), _PAGED),
            ("shared keys alone", "delta_key_heads", _plain(
                layer_pattern=(_DELTA, _DENSE), delta_heads=4,
                delta_key_heads=2, delta_head_dim=16, delta_decay="head"),
             _PAGED),
            ("a gate a channel alone", "layer_pattern", _plain(
                layer_pattern=(("attention", None, True, None, "channel"),
                               _DENSE)), "spec greedy"),
            ("the shared expert's gate alone", "moe_shared_gate", _plain(
                n_experts=8, moe_shared_width=64, moe_shared_gate=True),
             _PAGED),
            ("zero-centred norms alone", "zero_centred_norms",
             _plain(zero_centred_norms=True), _PAGED),
            ("the tree", "dense GPT block", None, "flatten")),
        shares=(16, 32, 0.5), choices=(4, 2 * 64, 2)),
}


# -- an architecture at its tiny preset ---------------------------------------

class Arch:
    """``ROWS[name]`` at its ``tiny`` preset, or (``cut``) at that preset
    with fewer layers."""

    def __init__(self, name: str, changes: dict = None, leaves: dict = None,
                 seed: int = 0):
        self.name, self.row, self.seed = name, ROWS[name], seed
        self.adapter = importlib.import_module(f"adapters.{name}")
        self.reference = importlib.import_module(f"reference.{name}")
        config, self.JOB = self.cell(tiny=True)
        self.CONFIG = {**config, **(changes or {})}
        self.SIZES = self.adapter.shapes(self.CONFIG, self.JOB)
        self.CFG = self.adapter._model_config(self.CONFIG, self.JOB)
        if leaves is None:
            row = self.row
            leaves = row.leaves(self) if callable(row.leaves) else {
                **(self.adapter._leaf_paths(row.paths(self.CONFIG, self.SIZES))
                   if row.paths else {}), **row.leaves}
        self.LEAVES = leaves
        self._kept = {}

    def cell(self, tiny: bool):
        """The configuration and workload files, whole or at ``tiny``."""
        with open(os.path.join(CHIP, "configs",
                               self.row.config + ".json")) as f:
            config = json.load(f)
        with open(os.path.join(CHIP, "workloads",
                               self.row.workload + ".json")) as f:
            job = json.load(f)
        if tiny:
            config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
        return config, job

    def cut(self, changes: dict, leaves: dict = None,
            seed: int = 0) -> "Arch":
        """The same architecture at the tiny preset with ``changes`` to its
        configuration file's keys (fewer layers: what a fault is shown on),
        its gradients named by ``leaves`` (None: by the row's rule), its
        kept tree drawn from ``seed`` (one at which every named gradient is
        there to move)."""
        return Arch(self.name, changes, leaves, seed)

    def params(self, cfg=None, seed=0, n_stages=1):
        """``init_params``' tree, the row's leaves moved."""
        rng = np.random.RandomState(seed)
        tree = t.init_params(rng, cfg or self.CFG, n_stages)
        if self.row.moved is not None:
            tree = self.row.moved(self, tree, rng, seed)
        return jax.tree_util.tree_map(jnp.asarray, tree)

    def batch(self, n_seqs=2, seed=0):
        return jax.tree_util.tree_map(jnp.asarray, self.adapter.host_batch(
            self.CONFIG, self.JOB, seed, 0, n_seqs))

    def init_function(self, cfg=None):
        """The adapter's draw of the tree on the device."""
        return self.adapter._init_function(cfg or self.CFG,
                                           *self.row.init_args(self))

    def program(self, cfg, params, batch, mesh_axes=None):
        """(loss + weighted auxiliary losses, aux, gradients) through
        ``make_grad_fn`` on a mesh (one device by default), as the
        benchmark's adapter calls it."""
        axes = mesh_axes or {"dp": 1}
        n = int(np.prod(list(axes.values())))
        mesh = build_mesh(devices=jax.devices()[:n], **axes)
        p = shard_params(params, cfg, mesh)
        tok, tgt = shard_batch(batch["tokens"], batch["targets"], mesh)
        loss, aux, grads = jax.jit(t.make_grad_fn(cfg, mesh))(p, tok, tgt)
        return t._objective(loss, aux), aux, grads

    def plain(self, cfg, params, batch):
        """(loss + weighted auxiliary losses, gradients) with no mesh (a
        tree that holds a leaf ``cfg`` does not read is no error here)."""
        def loss_fn(p):
            loss, aux = t.forward_loss_spmd(p, batch["tokens"],
                                            batch["targets"], cfg)
            return t._objective(loss, aux)
        return jax.jit(jax.value_and_grad(loss_fn))(params)

    def want(self, params, batch, sizes=None, leaves=None) -> dict:
        """The reference's side: its loss, the named gradients and what the
        row compares beside them."""
        sizes = sizes or self.SIZES
        fn = self.row.loss_and_grads or (
            lambda arch, *args: self.reference.loss_and_grads(*args))
        loss, grads = fn(self, params, leaves or self.LEAVES, batch, sizes)
        more = self.row.want_more(self, params, batch, sizes) \
            if self.row.want_more else {}
        return {"loss": loss, **more,
                **{f"grad:{k}": v for k, v in grads.items()}}

    def kept(self, n_seqs=2):
        """(the seeded tree, a batch of ``n_seqs``, the reference's side on
        them), computed once."""
        if n_seqs not in self._kept:
            params, batch = self.params(seed=self.seed), self.batch(n_seqs)
            self._kept[n_seqs] = params, batch, self.want(params, batch)
        return self._kept[n_seqs]

    @functools.cached_property
    def sides(self):
        """(the sound program's side, the reference's, the step's aux, its
        whole tree of gradients) at the tiny preset, computed once."""
        params, batch, want = self.kept()
        loss, aux, grads = self.program(self.CFG, params, batch)
        more = self.row.got_more(self, params, batch, aux) \
            if self.row.got_more else {}
        got = {"loss": loss, **more, **{
            f"grad:{k}": v for k, v in get_leaves(grads, self.LEAVES).items()}}
        return got, want, aux, grads

    @functools.cached_property
    def sound_grads(self):
        """(loss, whole tree of gradients) of the sound program with no mesh
        on the kept tree, computed once."""
        params, batch, _want = self.kept()
        return self.plain(self.CFG, params, batch)

    @functools.cached_property
    def sound(self) -> float:
        """``error`` of the sound program, computed once."""
        loss, grads = self.sound_grads
        return self.error("the sound program", got={"loss": loss, **{
            f"grad:{k}": v for k, v in get_leaves(grads,
                                                  self.LEAVES).items()}})

    def error(self, what: str, cfg=None, tree=None, got=None, only=None,
              n_seqs=2) -> float:
        """The largest relative distance of the loss and the named
        gradients (``only``: of those) of ``cfg``'s program on ``tree`` (the
        tiny CFG, the kept tree) from the kept reference's, the program as
        it stands now (a test may have patched a piece of it); or of
        ``got``, a side the test computed itself. Printed beside the sound
        program's (``-s``)."""
        params, batch, want = self.kept(n_seqs)
        if got is None:
            loss, grads = self.plain(
                cfg or self.CFG, params if tree is None else tree, batch)
            got = {"loss": loss, **{f"grad:{k}": v for k, v in get_leaves(
                grads, self.LEAVES).items()}}
        err = max(rel(v, want[k]) for k, v in got.items()
                  if only is None or k in only)
        print(f"\n{self.name} ({self.SIZES['layers']} layers) {what}: "
              f"{err:.3g}")
        return err


@functools.lru_cache(maxsize=None)
def get(name: str) -> Arch:
    return Arch(name)


def drawn_shapes(adapter, cfg, config):
    """The tree the adapter draws on the device for ``cfg``, as shapes."""
    return jax.eval_shape(adapter._init_function(cfg, config),
                          jax.random.PRNGKey(0))


def count(tree) -> int:
    """The elements of a tree of arrays or of shapes."""
    return sum(int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(tree))


def assert_the_adapter_s_tree_is_init_params(adapter, cfg, config):
    want = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    assert jax.tree_util.tree_map(lambda a: a.shape, want) == \
        jax.tree_util.tree_map(lambda a: a.shape,
                               drawn_shapes(adapter, cfg, config))


# -- every configuration the benchmark has ------------------------------------

def configs() -> dict:
    """Every benchmark configuration's ``(_model_config, tiny config, tiny
    job)``, by its adapter (BERT is no ``TransformerConfig``)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    files = {c["name"]: c["file"] for c in bench["configs"]}
    seen = {}
    for cell in bench["workloads"]:
        if cell["config"] in seen:
            continue
        with open(os.path.join(REPO, files[cell["config"]])) as f:
            config = json.load(f)
        with open(os.path.join(CHIP, "workloads",
                               cell["traffic"] + ".json")) as f:
            job = json.load(f)
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
        model = getattr(importlib.import_module(
            f"adapters.{config['adapter']}"), "_model_config", None)
        if model is not None:
            seen[cell["config"]] = (model, config, job)
    return seen


def grad_jaxpr(cfg) -> str:
    """The text of the jaxpr of ``cfg``'s loss's gradient, 2 x 32 tokens."""
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)

    def loss_fn(p, tokens, targets):
        return t.forward_loss_spmd(p, tokens, targets, cfg)[0]
    return str(jax.make_jaxpr(jax.grad(loss_fn))(shapes, tok, tok))
