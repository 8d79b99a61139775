"""Grouped-query attention under a learned index over its keys (DeepSeek-V3.2's
indexer, its exact top-k selection and its own loss: ISSUE 64) before a layer
of routed experts of which one chip holds a share, in float32 at the
benchmark configuration's ``tiny`` sizes (64 positions under ``topk`` 16, so
three query rows of four really select; 2 index heads of 8; 8 query heads of
16 on 2 key/value heads; 16 experts top-2 of which a share holds 2), against
the plain reference ``benchmarks/chip/reference/keye_vl2.py`` on seeded
weights.

TOL: both sides are float32 here and differ in the order of their sums (1e-7
to 1e-5); 1e-4 is far below what one wrong key of a row, a leaked gradient
or a missing factor does (``test_a_wrong_term_fails``). The selection itself
is compared bit for bit.
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
from horovod_tpu.ops import sparse_attention as sa
from horovod_tpu.parallel import build_mesh

ARCH = arch.get("keye_vl2")
adapter, reference = ARCH.adapter, ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_params, _batch, _program = ARCH.params, ARCH.batch, ARCH.program
INDEXER = ("wq_idx", "wk_idx", "k_idx_norm", "k_idx_norm_bias", "w_idx")


@pytest.fixture(autouse=True)
def _several_blocks_and_bands(monkeypatch):
    """Blocks of 16 rows in 2 bands of the 64 positions: every path of the
    blocked form runs (a band's blocks, a block past its band's diagonal)."""
    monkeypatch.setattr(sa, "ROWS", 16)
    monkeypatch.setattr(sa, "BANDS", 2)


@functools.partial(jax.jit, static_argnums=0)
def _program_logits(cfg, params, tokens):
    return arch.logits(ARCH, params, tokens, cfg)


# -- the program against the reference ---------------------------------------

@pytest.mark.parametrize("what", ["logits", "loss", "index_loss"]
                         + [f"grad:{k}" for k in LEAVES])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0, what
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_the_index_beside_the_experts():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows",
                        "index_loss", "selected_keys"}
    assert float(aux["dropped"]) == 0.0 and float(aux["aux_loss"]) == 0.0
    assert float(aux["index_loss"]) > 0
    # mean over the 64 positions of min(t + 1, 16), to the digit
    assert float(aux["selected_keys"]) == adapter.mean_keys(64, 16) == 14.125


def test_the_selection_is_the_reference_s_bit_for_bit():
    """One set a query (no head axis), exactly ``topk`` keys of a row that
    has more, every causal key of one that has not; and the reference told
    the program's selection computes what it computes alone."""
    params, batch, want = ARCH.kept()
    ours = np.asarray(jax.jit(lambda p, tok: t.index_selections(
        p, tok, CFG))(params, batch["tokens"]))
    with jax.default_matmul_precision("highest"):
        theirs = jax.jit(lambda p, b: reference.losses(p, b, SIZES)[3])(
            params, batch)
    assert ours.shape == (2, 2, 64, 8)
    np.testing.assert_array_equal(ours, np.asarray(theirs))
    chosen = np.unpackbits(ours, axis=-1)
    np.testing.assert_array_equal(
        chosen.sum(-1), np.broadcast_to(np.minimum(np.arange(64) + 1, 16),
                                        (2, 2, 64)))
    assert not np.triu(chosen, 1).any()          # no key after its query
    with jax.default_matmul_precision("highest"):
        forced, _grads = reference.loss_and_grads(
            params, {"wq": LEAVES["layers.wq"]}, batch, SIZES,
            selection=jnp.asarray(ours))
    assert _rel(forced, want["loss"]) < 1e-6


def test_the_two_gradient_sets_are_disjoint():
    """The indexer's leaves learn from ``L_I`` alone and every other leaf
    from the cross-entropy alone: to the bit, not to a tolerance."""
    params, batch = _params(), _batch()

    def part(name):
        def loss_fn(p):
            loss, aux = t.forward_loss_spmd(p, batch["tokens"],
                                            batch["targets"], CFG)
            return {"xent": loss, "index": aux["index_loss"]}[name]
        return jax.jit(jax.grad(loss_fn))(params)
    xent, index = part("xent"), part("index")
    for name, g in xent["layers"].items():
        moved = float(jnp.max(jnp.abs(g))) > 0
        assert moved != (name in INDEXER), name
    for name, g in index["layers"].items():
        moved = float(jnp.max(jnp.abs(g))) > 0
        assert moved == (name in INDEXER), name
    for name in ("embed", "ln_f", "lm_head"):
        assert float(jnp.max(jnp.abs(index[name]))) == 0.0, name


# -- the selection: exact, by value, ties to the lower index -------------------

def _select_by_sort(scores, t, topk):
    """The selection by a stable sort, in NumPy."""
    out = np.zeros(scores.shape, bool)
    for r, row in enumerate(np.asarray(scores)):
        n = int(t[r]) + 1
        order = np.argsort(-row[:n], kind="stable")[:topk]
        out[r, order] = True
    return out


@pytest.mark.parametrize("case", ["random", "ties", "all equal", "signed zero",
                                  "negative"])
def test_select_is_the_stable_sort_s_top_k(case):
    rng = np.random.RandomState(5)
    rows, keys, topk = 24, 96, 16
    scores = rng.randn(rows, keys).astype(np.float32)
    if case == "ties":          # few distinct values: every row has ties
        scores = np.round(scores * 2) / 2
    elif case == "all equal":
        scores = np.full_like(scores, 0.25)
    elif case == "signed zero":
        scores = np.where(rng.rand(rows, keys) < 0.5, 0.0, -0.0
                          ).astype(np.float32)
        scores = np.where(scores == 0, 0.0, scores)     # as index_scores does
    elif case == "negative":
        scores = -np.abs(np.round(scores * 2) / 2)
    t0 = 72 - rows              # rows at positions 48 .. 71 of 96 keys
    pos = t0 + np.arange(rows)
    got = jax.jit(functools.partial(sa.select, topk=topk))(
        jnp.asarray(scores), jnp.asarray(pos, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got),
                                  _select_by_sort(scores, pos, topk))
    # and rows with no more causal keys than topk take them all
    few = jax.jit(functools.partial(sa.select, topk=topk))(
        jnp.asarray(scores), jnp.arange(rows, dtype=jnp.int32))
    np.testing.assert_array_equal(
        np.asarray(few), _select_by_sort(scores, np.arange(rows), topk))


def test_blocks_divide_the_sequence(monkeypatch):
    assert sa.blocks(64) == (16, 2)
    monkeypatch.setattr(sa, "ROWS", 128)
    monkeypatch.setattr(sa, "BANDS", 8)
    for seq in (16384, 384, 40, 64):
        rows, bands = sa.blocks(seq)
        assert seq % (rows * bands) == 0 and rows <= 128 and bands <= 8
    assert sa.blocks(16384) == (128, 8) and sa.blocks(64) == (64, 1)


# -- what the index leaves alone ------------------------------------------------

def _without_index(cfg, params):
    """The plain grouped block's config and tree: no indexer."""
    plain = dataclasses.replace(cfg, index_topk=0, index_heads=0,
                                index_head_dim=0)
    layers = {k: v for k, v in params["layers"].items() if k not in INDEXER}
    return plain, {**params, "layers": layers}


def test_rows_with_no_more_keys_than_topk_are_dense_causal_attention():
    """The first 16 positions select every causal key in every layer, and a
    position reads only positions before it: their logits are the plain
    grouped block's, the later positions' are not."""
    params, tokens = _params(), _batch()["tokens"]
    sparse = _program_logits(CFG, params, tokens)
    dense = _program_logits(*_without_index(CFG, params), tokens)
    np.testing.assert_allclose(sparse[:, :16], dense[:, :16], rtol=2e-5,
                               atol=2e-6)
    assert _rel(sparse[:, 16:], dense[:, 16:]) > 1e-2


def test_topk_beyond_the_sequence_is_the_plain_block_and_the_index_still_learns():
    cfg = dataclasses.replace(CFG, index_topk=64)
    params, batch = _params(), _batch()
    np.testing.assert_allclose(
        _program_logits(cfg, params, batch["tokens"]),
        _program_logits(*_without_index(CFG, params), batch["tokens"]),
        rtol=2e-5, atol=2e-6)
    loss, aux, grads = _program(cfg, params, batch)
    assert float(aux["selected_keys"]) == 32.5 and float(aux["index_loss"]) > 0
    for name in INDEXER:
        assert float(jnp.linalg.norm(grads["layers"][name])) > 0, name
    want = ARCH.want(params, batch, {**SIZES, "index_topk": 64})
    assert _rel(loss, want["loss"]) < TOL
    for name in INDEXER:
        assert _rel(grads["layers"][name],
                    want[f"grad:layers.{name}"]) < TOL, name


# -- each wrong term fails ------------------------------------------------------

#: one layer: every wrong term below is in it
SMALL = ARCH.cut({"num_hidden_layers": 1})


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.sound < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for k, v in SMALL.kept()[2].items() if k.startswith("grad:"))


class _Without:
    """A module with some of its names replaced."""

    def __init__(self, real, **names):
        self._real, self._names = real, names

    def __getattr__(self, name):
        return self._names[name] if name in self._names \
            else getattr(self._real, name)


def _scores_without_relu(qi, w, ki):
    s = jnp.einsum("rjd,kd->rjk", qi, ki.astype(qi.dtype),
                   preferred_element_type=jnp.float32)
    return jnp.sum(s * w[:, :, None], axis=1)


def _unscaled(real):
    scale = CFG.index_heads ** -0.5 * CFG.index_head_dim ** -0.5

    def inputs(*args):
        qi, ki, w = real(*args)
        return qi, ki, w / scale
    return inputs


def _rope_but_on_the_index(real):
    def rope(x, positions, theta=10000.0):
        return x if x.shape[-1] == CFG.index_head_dim \
            else real(x, positions, theta)
    return rope


def _every_causal_key(scores, t, topk):
    return jnp.arange(scores.shape[1])[None, :] <= t[:, None]


def _a_set_a_half_of_the_heads(real):
    """The second half of the heads under a selection of their own (the
    index queries against the other heads' weights)."""
    def block(topk, scale, total, x, consts):
        o, kl, count, bits = real(topk, scale, total, x, consts)
        q, qi, w, t0 = x
        other = real(topk, scale, total, (q, qi[:, ::-1], w, t0), consts)[0]
        half = o.shape[1] // 2
        return o.at[:, half:].set(other[:, half:]), kl, count, bits
    return block


FAULTS = {
    "topk off by one": {"cfg": {"index_topk": 15}},
    "the selection not shared across the heads":
        {"patch": lambda: (sa, "_block", _a_set_a_half_of_the_heads(
            sa._block))},
    "L_I's gradient reaches the model (no stop_gradient on the target)":
        {"patch": lambda: (sa, "lax", _Without(
            sa.lax, stop_gradient=lambda x: x))},
    "L_I's gradient reaches the model (no stop_gradient on the indexer's "
    "input)":
        {"patch": lambda: (t, "lax", _Without(
            t.lax, stop_gradient=lambda x: x))},
    "relu dropped": {"patch": lambda: (sa, "index_scores",
                                       _scores_without_relu)},
    "w unscaled": {"patch": lambda: (t, "_index_inputs",
                                     _unscaled(t._index_inputs))},
    "the key's norm dropped":
        {"patch": lambda: (t, "layernorm", lambda x, g, b, eps: x)},
    "rope off the index":
        {"patch": lambda: (t, "rope", _rope_but_on_the_index(t.rope))},
    "softmax over all causal keys":
        {"patch": lambda: (sa, "select", _every_causal_key)},
}


@pytest.mark.parametrize("what", sorted(FAULTS))
def test_a_wrong_term_fails(monkeypatch, what):
    """What TOL must not let through: each moves the loss or a leaf's
    gradient far beyond it."""
    SMALL.kept()        # the reference's side, before anything is patched
    change = FAULTS[what]
    if "patch" in change:
        monkeypatch.setattr(*change["patch"]())
    cfg = dataclasses.replace(SMALL.CFG, **change.get("cfg", {}))
    err = SMALL.error(what, cfg)
    assert err > 20 * TOL, (what, err)


# -- what is refused, by name ---------------------------------------------------

def test_paths_that_do_not_implement_the_index_refuse_it_by_name():
    for axes in ({"sp": 2}, {"tp": 2}, {"pp": 2}):
        mesh = build_mesh(devices=jax.devices()[:2], **axes)
        with pytest.raises(NotImplementedError, match="index_topk"):
            t.param_shardings(CFG, mesh)
    whole = dataclasses.replace(CFG, expert_share=(0, 1))
    with pytest.raises(NotImplementedError, match="index_topk"):
        _program(whole, _params(whole), _batch(), {"sp": 2})
    for pattern in (((32, True),),                        # a window
                    (("latent",), ("experts",)),          # the latent kind
                    (("attention", None, True), ("experts",))):
        with pytest.raises(NotImplementedError, match="index_topk"):
            dataclasses.replace(
                CFG, layer_pattern=pattern, kv_latent=16, q_latent=16,
                rope_width=4, **({"n_kv_heads": None, "qk_norm": False}
                                 if pattern[0] == ("latent",) else {}))
    with pytest.raises(NotImplementedError, match="index_topk"):
        dataclasses.replace(CFG, n_loops=2)
    with pytest.raises(ValueError, match="index_heads"):
        dataclasses.replace(CFG, index_heads=0)
    with pytest.raises(ValueError, match="whole bytes"):
        sa.indexed_attention(*(jnp.zeros((1, 12) + s) for s in (
            (2, 4), (1, 4), (1, 4), (1, 4), (4,), (1,))), 4, 1.0)


# -- the cells the benchmark has keep their program -------------------------------

#: every accepted configuration's tiny program at the parent commit
#: (0944ed2), as tests/test_lfm2_moe.py records them (its eight, and its own
#: configuration's since): sha256 (16 hex digits) of the text of
#: ``jax.make_jaxpr`` of its loss's gradient, addresses struck out, and of
#: its tree's shapes. A JAX upgrade that prints a jaxpr differently moves the
#: first of each pair and not the second: record them again from the commit
#: before the upgrade.
PARENT_PROGRAMS = {
    "gpt-1.3b-widths": ("8d09444310935e8c", "f756b4a151f15d25"),
    "olmoe-1b-7b": ("8a85a574931f0773", "33ae69a8ed6dc084"),
    "ouro-2.6b": ("198f8569c959b0e7", "c1b56a957a2d3cfc"),
    "smallthinker-21b-a3b": ("25a381f3cf477ad8", "aa7813b0c9b8afe3"),
    "nemotron-3-nano-30b-a3b": ("6b9d91906fbdd3c6", "b5efb155c4415a28"),
    "glm-4.7-flash": ("4b48cf80e570394e", "df3eff602e9b7fc4"),
    "granite-4.0-h-micro": ("e450fe130a391ab5", "a91a56ea9b269743"),
    "laguna-xs.2": ("bbf4b9c942caf34e", "17eefd1c9df9f2a1"),
    "lfm2-24b-a2b": ("5c2c60f6948d2ef1", "bb85add417c43d56"),
}


def _digest(text: str) -> str:
    import hashlib
    import re
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


def test_the_new_configuration_is_the_only_one_without_a_parent():
    """(Of the configurations there were at PR 64: a later one's file holds
    this file's configuration to its own parent, tests/test_kimi_linear.py.)"""
    assert set(PARENT_PROGRAMS) | {"keye-vl-2.0-30b-a3b"} <= set(
        arch.configs())


@pytest.mark.parametrize("name", sorted(PARENT_PROGRAMS))
def test_a_configuration_without_an_index_keeps_its_tree_and_jaxpr(name):
    """To the letter: a config that names no index takes no new branch, has
    no new leaf and stops no gradient."""
    model, config, job = arch.configs()[name]
    cfg = model(config, job)
    assert cfg.index_topk == 0
    shapes = jax.eval_shape(
        lambda: t.init_params(np.random.RandomState(0), cfg))
    tree = _digest(str(jax.tree_util.tree_map(lambda a: a.shape, shapes)))
    assert (_digest(arch.grad_jaxpr(cfg)), tree) == PARENT_PROGRAMS[name]


# -- the benchmark's own count of the algorithm's work ----------------------------

import chip_door                                          # noqa: E402

chip_door.take("test_keye_vl2", globals())
