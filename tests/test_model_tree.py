"""The seams of ``horovod_tpu/models``: a leaf and a block kind are declared
once, and three things hold the declarations to what they replaced.

* ``init_params`` to the bit: a digest of the tree a seed gives, for the small
  configurations the architecture tests build (``test_transformer.py``,
  ``test_olmoe.py``, ``test_ouro.py``, ``test_smallthinker.py``,
  ``test_nemotron_h.py``), taken at commit f9a62e1, before the leaves were
  declarations: the order of the draws is part of the contract.
* ``param_shardings`` and ``init_params`` build the same tree, and every spec
  fits its leaf, on one device and with ``tp`` and ``ep`` live.
* the decode model (``models/decode.py``) refuses, from the configuration
  alone and at every entry point, each field that is not the plain dense GPT
  block's.

Nothing here compiles a program: the trees are drawn on the host and the
refusals come before anything is traced.
"""

import dataclasses
import hashlib
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import TransformerConfig, init_params, param_shardings
from horovod_tpu.models import decode
from horovod_tpu.parallel import build_mesh

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from adapters import (glm4_moe_lite, nemotron_h, olmoe, ouro,   # noqa: E402
                      smallthinker)


def _tiny(adapter, config: str, workload: str) -> TransformerConfig:
    """An adapter's configuration at its cell's ``tiny`` sizes, as the
    architecture's own test file builds it."""
    with open(os.path.join(_CHIP, "configs", config + ".json")) as f:
        config = json.load(f)
    with open(os.path.join(_CHIP, "workloads", workload + ".json")) as f:
        job = json.load(f)
    return adapter._model_config({**config, **config["tiny"]},
                                 {**job, **job["tiny"]})


_NEMOTRON = _tiny(nemotron_h, "nemotron-3-nano-30b-a3b",
                  "train.s8192.b1.hybrid")
CONFIGS = {
    # tests/test_transformer.py's CFG and MOE_CFG
    "gpt": TransformerConfig(vocab_size=64, d_model=32, n_heads=4, n_layers=4,
                             d_ff=64, max_seq=32, dtype=jnp.float32,
                             n_microbatches=2, remat=False),
    "gpt-moe": TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                 n_layers=4, d_ff=64, max_seq=32, n_experts=4,
                                 dtype=jnp.float32, n_microbatches=2,
                                 remat=False),
    "olmoe": _tiny(olmoe, "olmoe-1b-7b", "train.s4096.b2"),
    "ouro": _tiny(ouro, "ouro-2.6b", "train.s4096.b1"),
    "smallthinker": _tiny(smallthinker, "smallthinker-21b-a3b",
                          "train.s8192.b1"),
    "nemotron-h": _NEMOTRON,
    # tests/test_nemotron_h.py's SMALL: one block of each kind
    "nemotron-h-me*": dataclasses.replace(
        _NEMOTRON, n_layers=3,
        layer_pattern=tuple(nemotron_h.KINDS[c] for c in "ME*")),
    # tests/test_glm4_moe_lite.py's CFG: leading blocks and a prediction
    # module beside the periodic stack
    "glm": _tiny(glm4_moe_lite, "glm-4.7-flash", "train.s8192.b1.latent"),
}


def tree_digest(tree) -> str:
    """sha256 over the sorted leaf paths, their shapes, dtypes and bytes."""
    digest = hashlib.sha256()
    leaves = sorted(
        (jax.tree_util.keystr(path), np.asarray(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(tree))
    for path, leaf in leaves:
        digest.update(f"{path} {leaf.shape} {leaf.dtype}\n".encode())
        digest.update(np.ascontiguousarray(leaf).tobytes())
    return digest.hexdigest()


#: ``tree_digest(init_params(RandomState(0), cfg, stages))`` at f9a62e1
DIGESTS = {
    ("gpt", 1):
        "702f740e2709ba02770c4226a8b7b010027c43b7f91fed37d2db735f73ce11d8",
    ("gpt", 2):
        "36f77b23395824778e2741358dba76823e94d470a58293bc07ac1375c8f62fd4",
    ("gpt-moe", 1):
        "27557776cc2cb3ee0f245947912da5b9e638baf9a6c5de83981b91e6a49649c7",
    ("gpt-moe", 2):
        "f9b6672c5f08d70961661cd8c38a163469804cfc4dbcf019b781f034c4ac16d4",
    ("olmoe", 1):
        "88b76c63d004cf19f0ec2139db67420cabb501daac72baad75e4c936d1833505",
    ("olmoe", 2):
        "7ee377e1ee0b6befb28bfd2bfada322abbdae3813e9d373aca929ef4917e6dba",
    ("ouro", 1):
        "57069092a171ccf63d710af79bad10c244d1d997808d43dfa8dcd4e45925b3a4",
    ("ouro", 2):
        "7abf3784dd516fd67ef19c5f2a1afea77885237f36f64a8bb863ad9413702c98",
    ("smallthinker", 1):
        "79b74dae85da7d8da0321cecab776fb8fa4b91b6e435c19950d78e9c58e7901d",
    ("smallthinker", 2):
        "87a206b8259115068736f2c8e96445c3a7dc5187ed43b8500544b147d6622bd4",
    ("nemotron-h", 1):
        "dcb10e5d741138a238a1212fd84445c3a5b33e2ebd1a153ca7a53c301747c3ff",
    ("nemotron-h", 2):
        "30235653da28f157f1a59b5b4e97be0c05235737e98e59b8e8a97291f9dac2ee",
    ("nemotron-h-me*", 1):
        "68ee584f58ade4fe8e1baaa34ff3142d31605da355849961b96e120be428509b",
}


@pytest.mark.parametrize("name, stages", list(DIGESTS),
                         ids=[f"{n}-stages{s}" for n, s in DIGESTS])
def test_a_seed_gives_the_tree_it_gave_before_the_declarations(name, stages):
    tree = init_params(np.random.RandomState(0), CONFIGS[name], stages)
    assert tree_digest(tree) == DIGESTS[name, stages]


def _live_axes(cfg: TransformerConfig) -> dict:
    """What of tp=2 and ep=2 the configuration runs on: a tp shard holds
    whole k/v heads, a Mamba or a latent attention block has no tp, and a
    device holds its experts by its place on ep or by ``expert_share``, not
    both."""
    axes = {}
    if cfg.kv_heads % 2 == 0 and not {("mamba",), ("latent",)} & set(
            cfg.layer_pattern):
        axes["tp"] = 2
    if cfg.expert_share == (0, 1):
        axes["ep"] = 2
    return axes


_MESHES = [(name, {}) for name in CONFIGS] + [
    (name, _live_axes(cfg)) for name, cfg in CONFIGS.items()
    if _live_axes(cfg)]


@pytest.mark.parametrize(
    "name, axes", _MESHES,
    ids=[f"{n}-{'-'.join(a) or 'one-device'}" for n, a in _MESHES])
def test_the_shardings_are_the_tree_of_init_params(name, axes):
    """One declaration, two trees: the same structure, and every spec names
    live axes only, has no more entries than its leaf has dimensions, and
    splits a dimension its axis divides."""
    cfg = CONFIGS[name]
    n = int(np.prod(list(axes.values()), dtype=int))
    mesh = build_mesh(devices=jax.devices()[:n], **(axes or {"dp": 1}))
    params = init_params(np.random.RandomState(0), cfg)
    shardings = param_shardings(cfg, mesh)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(shardings)
    split = set()
    for (path, leaf), sharding in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves(shardings)):
        spec = tuple(sharding.spec)
        assert len(spec) <= leaf.ndim, (path, spec, leaf.shape)
        for size, axis in zip(leaf.shape, spec):
            if axis is not None:
                assert axes.get(axis, 1) > 1, (path, spec)
                assert size % axes[axis] == 0, (path, spec, leaf.shape)
                split.add(axis)
    # (ep splits experts only: a dense model has none to split)
    assert split == set(axes) - ({"ep"} if not cfg.n_experts else set())


#: each field that is not the plain dense GPT block's, set alone on the
#: default config
NOT_PLAIN = {
    "n_experts": {"n_experts": 8},
    "qk_norm": {"qk_norm": True},
    "tie_embeddings": {"tie_embeddings": False},
    "post_norm": {"post_norm": True},
    "ffn_gated": {"ffn_gated": True},
    "n_loops": {"n_loops": 2},
    "layer_pattern": {"layer_pattern": ((None, True), (64, True))},
    "n_kv_heads": {"n_kv_heads": 2},
    "moe_router_input": {"moe_router_input": "block_input"},
    "expert_share": {"expert_share": (0, 2)},
    "moe_router_scores": {"moe_router_scores": "sigmoid"},
    "moe_shared_width": {"moe_shared_width": 64},
    "ssm_heads": {"ssm_heads": 2},
    "kv_latent": {"kv_latent": 16},
    "lead_pattern": {"lead_pattern": (("dense",),),
                     "layer_pattern": (("attention", None, True),)},
    "mtp_depth": {"mtp_depth": 1},
    "moe_activation": {"moe_activation": "relu"},
    "delta_decay": {"delta_decay": "head"},
    "index_topk": {"index_topk": 16, "index_heads": 2, "index_head_dim": 8},
}
#: and every other field the decode paths do not read, whatever it is, at
#: another value of its default's kind (a name above where the config's own
#: validation wants a word or company): a bool, int, float or None field of
#: a later PR is refused with no line here and none in ``decode.py``; a str
#: or tuple field needs a line above, since only its config knows its words
_ANOTHER = {bool: lambda v: not v, int: lambda v: v + 2,
            float: lambda v: v + 0.5, type(None): lambda v: 2}
for _field in dataclasses.fields(TransformerConfig):
    if _field.name not in decode._ALLOWED_FIELDS + tuple(NOT_PLAIN):
        assert type(_field.default) in _ANOTHER, (
            f"{_field.name} needs a NOT_PLAIN line of its own")
        NOT_PLAIN[_field.name] = {
            _field.name: _ANOTHER[type(_field.default)](_field.default)}


@pytest.mark.parametrize("field", list(NOT_PLAIN))
def test_every_decode_entry_point_refuses_the_field_by_name(field):
    """From the configuration alone, ``kv_cache_spec`` first: the engine
    sizes its pages from it before it has seen a tree. Every field of
    ``TransformerConfig`` but the few the decode paths read."""
    cfg = TransformerConfig(**NOT_PLAIN[field])
    nothing = (None,) * 7
    for entry, args in [
            (decode.kv_cache_spec, (cfg,)),
            (decode.decode_step_paged, (*nothing, cfg)),
            (decode.prefill_chunk_paged, (*nothing, cfg)),
            (decode.reference_greedy_decode, (None, cfg, [1, 2], 1))]:
        with pytest.raises(NotImplementedError, match=field):
            entry(*args)


def test_the_default_config_is_what_the_decode_model_runs():
    cfg = TransformerConfig()
    assert decode.kv_cache_spec(cfg) == (
        cfg.n_layers, cfg.n_heads * cfg.head_dim, jnp.float32)
    small = CONFIGS["gpt"]
    flat = decode.flatten_decode_params(
        init_params(np.random.RandomState(0), small, n_stages=2))
    assert flat["layers"]["wq"].shape == (
        small.n_layers, small.d_model, small.n_heads * small.head_dim)
    # a head wider than d_model / n_heads is read, not refused
    decode.kv_cache_spec(dataclasses.replace(cfg, head_width=128))
    assert set(decode._ALLOWED_FIELDS) < {
        f.name for f in dataclasses.fields(TransformerConfig)}
    # nor is how training stores and schedules the same function
    decode.kv_cache_spec(dataclasses.replace(
        cfg, dtype=jnp.float32, param_dtype=jnp.bfloat16, n_microbatches=4,
        remat=not cfg.remat, max_seq=4096, norm_eps=1e-6, rope_theta=5e5))


@pytest.mark.parametrize("name", [n for n in CONFIGS if n != "gpt"])
def test_a_tree_that_is_not_a_plain_config_s_is_refused(name):
    """The tree comes from outside, beside its config: a mismatch is
    refused in the same class."""
    params = init_params(np.random.RandomState(0), CONFIGS[name])
    with pytest.raises(NotImplementedError, match="dense GPT block"):
        decode.flatten_decode_params(params)
