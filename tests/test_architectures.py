"""The tests that are one test for every architecture: each runs over the
rows of ``tests/arch.py``, and an architecture's case is the data its row
holds (a row that holds none for a test has no case of it). The program
against the reference, the faults and whatever is an architecture's own stand
in its ``tests/test_<architecture>.py``.
"""

import dataclasses
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, rel
from horovod_tpu.models import decode
from horovod_tpu.models import transformer as t


def _rows(field):
    return sorted(name for name, row in arch.ROWS.items()
                  if getattr(row, field) is not None)


@pytest.mark.parametrize("name", _rows("tiny"))
def test_the_tiny_preset_is_the_one_the_issue_asks_for(name):
    a = arch.get(name)
    for field, value in a.row.tiny["cfg"].items():
        assert getattr(a.CFG, field) == value, field
    for part, held in (("config", a.CONFIG), ("job", a.JOB),
                       ("sizes", a.SIZES)):
        for key, value in a.row.tiny.get(part, {}).items():
            assert held[key] == value, (part, key)


@pytest.mark.parametrize("name", _rows("reference_imports"))
def test_the_reference_imports_nothing_of_the_program(name):
    a = arch.get(name)
    with open(a.reference.__file__) as f:
        text = f.read()
    assert "horovod_tpu" not in text.split('"""', 2)[2]
    assert '"highest"' in text
    imports = [line for line in text.splitlines()
               if line.startswith(("import ", "from "))]
    allowed = a.row.reference_imports
    assert not allowed or all(
        line.split()[1].split(".")[0] in allowed for line in imports), imports


@pytest.mark.parametrize("name", _rows("drawn"))
def test_the_adapter_draws_init_params_tree_on_the_device(name):
    a = arch.get(name)
    spread, table_apart = a.row.drawn
    host = t.init_params(np.random.RandomState(0), a.CFG, 1)
    ours = jax.device_get(jax.jit(a.init_function())(jax.random.PRNGKey(0)))
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(ours)
    for (path, h), o in zip(jax.tree_util.tree_leaves_with_path(host),
                            jax.tree_util.tree_leaves(ours)):
        assert h.shape == o.shape and h.dtype == o.dtype, path
        if float(h.std()) > 0 and not (table_apart
                                       and path[0].key == "embed"):
            assert abs(float(o.std()) / float(h.std()) - 1) < spread, path
    if table_apart:
        assert float(ours["embed"].std()) == pytest.approx(
            a.CONFIG["assumed"]["embedding_std"], rel=0.05)


@pytest.mark.parametrize("name, what", [
    (name, entry[0]) for name, row in sorted(arch.ROWS.items())
    for entry in row.refused])
def test_the_decode_paths_refuse_the_new_fields_by_name(name, what):
    """A serving path that does not implement a field of the training
    configuration says which, wherever it is entered."""
    a = arch.get(name)
    _, names, cfg, paths = next(e for e in a.row.refused if e[0] == what)
    cfg = a.CFG if cfg is None else cfg(a)
    own_tree = cfg is not a.CFG and "flatten" in paths
    params = t.init_params(np.random.RandomState(0), cfg, 1) if own_tree \
        else a.params()
    calls = {
        "spec": [lambda: decode.kv_cache_spec(cfg)],
        "paged": [lambda: decode.decode_step_paged(
            params, None, None, None, None, None, None, cfg),
            lambda: decode.prefill_chunk_paged(
                params, None, None, None, None, None, None, cfg)],
        "greedy": [lambda: decode.reference_greedy_decode(
            params, cfg, [1, 2], 1)],
        "flatten": [lambda: decode.flatten_decode_params(params)]}
    for path in paths.split():
        for call in calls[path]:
            with pytest.raises(NotImplementedError, match=names):
                call()


@pytest.mark.parametrize("name", _rows("choices"))
def test_the_routers_choices_are_the_reference_s(name):
    a = arch.get(name)
    params, batch = a.params(), a.batch()
    got = jax.jit(lambda p, tok: t.router_choices(p, tok, a.CFG))(
        params, batch["tokens"])
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, b: a.reference.losses(p, b, a.SIZES)[4])(
            params, batch)
    assert got.shape == want.shape == a.row.choices
    np.testing.assert_array_equal(np.sort(got, -1), np.sort(want, -1))


@pytest.mark.parametrize("name", _rows("shares"))
def test_the_shares_add_up_to_the_uncut_layer(name):
    """model-configs guide, section 4: the routed parts that the shares
    compute and the shared expert (where there is one) counted ONCE are what
    the uncut reference gives for the whole layer; between them the shares
    hold every assignment once."""
    a = arch.get(name)
    of, e, summed_apart = a.row.shares
    cfg = dataclasses.replace(a.CFG, n_experts=e, expert_share=(0, 1))
    rng = np.random.RandomState(0)
    m, f, fs = cfg.d_model, cfg.d_ff, cfg.moe_shared_width
    h = jnp.asarray(rng.randn(1, 96, m), jnp.float32)

    def w(*shape, scale=1 / 8):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.float32)
    routed_names = ("we1", "we3", "we2") if cfg.moe_gated else ("we1", "we2")
    p = {"router": w(m, e, scale=0.3), "router_bias": w(e, scale=0.1),
         **{k: w(e, *((f, m) if k == "we2" else (m, f)))
            for k in routed_names},
         **{k.replace("e", "s"): w(*((fs, m) if k == "we2" else (m, fs)))
            for k in routed_names if fs},
         **({"ws_gate": w(m, 1)} if cfg.moe_shared_gate else {})}
    layer_fn = getattr(a.reference, "expert_layer", None) or a.reference.layer

    @functools.partial(jax.jit, static_argnums=(2, 3, 4))
    def reference(p, h, experts, first, shared=True):
        return layer_fn(
            p, h, {**a.SIZES, "experts": e, "held_experts": experts,
                   "first_expert": first}, **({} if shared else
                                              {"shared": False}))

    def uncut(p, h, shared=True):
        return reference(p, h, e, 0, shared)
    with jax.default_matmul_precision("highest"):
        want, _choice = uncut(p, h[0])
        shared = want - uncut(p, h[0], shared=False)[0] if fs else 0.0
    layer = jax.jit(t._moe_ffn, static_argnums=2)
    parts, held_rows, held = [], [], e // of
    for i in range(of):
        share = dataclasses.replace(cfg, expert_share=(i, of))
        mine = {k: v[held * i:held * (i + 1)] if k in routed_names else v
                for k, v in p.items()}
        y, aux = layer(mine, h, share)
        assert float(aux["dropped"]) == 0.0
        parts.append(y[0])
        held_rows.append(float(aux["held_rows"]))
        # and a share is the reference's at the same share
        with jax.default_matmul_precision("highest"):
            theirs, _ = reference(mine, h[0], held, held * i)
        assert rel(y[0], theirs) < TOL
    routed = [part - shared for part in parts]
    assert rel(sum(routed) + shared, want) < TOL
    assert sum(held_rows) == 96 * cfg.moe_top_k
    if fs:  # the shares' outputs summed count the shared expert ``of`` times
        assert rel(sum(parts), want) > summed_apart
    # no share is the whole, and the layer that holds every expert is
    assert rel(routed[0] + shared, want) > 0.3
    y, aux = layer(p, h, cfg)
    assert rel(y[0], want) < TOL and "held_rows" not in aux
