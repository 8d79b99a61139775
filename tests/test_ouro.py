"""Ouro on the normal path (ISSUE 30): the flagship block with sandwich
norms and a SiLU-gated dense FFN, the stack of layers looped with the same
weights, ``ln_f`` after every loop step, the head and the exit gate on
every step's state and the exit-weighted loss, in float32 at the benchmark
configuration's ``tiny`` sizes (2 layers, 2 loop steps; one case at 3 loop
steps), against the plain reference ``benchmarks/chip/reference/ouro.py``
on seeded weights.

TOL: both sides are float32 here and differ in the order of their sums (a
scan against Python loops, a fused cross-entropy against logsumexp, the
exit distribution in log space against plain products), measured at 1e-6 of
a leaf's norm. 1e-4 leaves that room and is far under what each fault of
``test_a_wrong_term_fails`` does (tried, each fails): the exit gate or the
entropy in bfloat16, the un-normed state fed to the next loop step, a loop
step dropped, ``p_T`` taken from ``lambda_T``, the post-norms left out.
"""

import dataclasses
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL, get_leaves, rel as _rel
from horovod_tpu.models import _kinds
from horovod_tpu.models import transformer as t
from horovod_tpu.parallel import build_mesh

ARCH = arch.get("ouro")
reference = ARCH.reference
SIZES, CFG, LEAVES = ARCH.SIZES, ARCH.CFG, ARCH.LEAVES
_params, _batch, _program = ARCH.params, ARCH.batch, ARCH.program


@pytest.mark.parametrize("what", [
    "loss", "step_losses", "exit_share", "gate_entropy",
    *(f"grad:{k}" for k in LEAVES)])
def test_program_matches_the_reference(what):
    """The first case to run pays for ``ARCH.sides``: the one trace and
    compile of the tiny preset's step and of the reference, which every case
    after it reads."""
    got, want, _aux, _grads = ARCH.sides
    assert np.linalg.norm(np.asarray(want[what])) > 0
    assert _rel(got[what], want[what]) < TOL, what


def test_the_step_reports_its_exit_distribution():
    _got, _want, aux, _grads = ARCH.sides
    assert set(aux) == {"aux_loss", "step_losses", "exit_share",
                        "gate_entropy"}
    assert aux["step_losses"].shape == aux["exit_share"].shape == (
        CFG.n_loops,)
    np.testing.assert_allclose(float(aux["exit_share"].sum()), 1.0,
                               atol=1e-6)
    assert float(aux["aux_loss"]) == 0.0
    assert 0.0 < float(aux["gate_entropy"]) <= np.log(CFG.n_loops) + 1e-6


def test_three_loop_steps_match_the_reference():
    """The middle steps' ``p_t = lambda_t prod_{j<t}(1 - lambda_j)``, which
    two loop steps do not have."""
    cfg = dataclasses.replace(CFG, n_loops=3)
    sizes = {**SIZES, "loops": 3}
    params, batch = _params(cfg, seed=1), _batch(seed=1)
    loss, aux, grads = _program(cfg, params, batch)
    want = ARCH.want(params, batch, sizes)
    assert _rel(loss, want["loss"]) < TOL
    assert _rel(aux["exit_share"], want["exit_share"]) < TOL
    np.testing.assert_allclose(float(aux["exit_share"].sum()), 1.0,
                               atol=1e-6)
    for name in ("exit_gate", "exit_gate_bias", "lm_head", "wq", "w2"):
        assert _rel(get_leaves(grads, LEAVES)[name],
                    want[f"grad:{name}"]) < TOL, name


# -- what TOL must not let through --------------------------------------------

def _gate_in_bf16(monkeypatch):
    """The gate as the MXU would take it: bfloat16 operands and result."""
    def exit_gate(params, states):
        z = states.astype(jnp.bfloat16) @ params["exit_gate"][:, 0].astype(
            jnp.bfloat16) + params["exit_gate_bias"].astype(jnp.bfloat16)
        return z.astype(jnp.float32)
    monkeypatch.setattr(t, "_exit_gate", exit_gate)


def _entropy_in_bf16(monkeypatch):
    """The exit distribution, its logarithm and the entropy in bfloat16
    (``_looped_loss`` computes in the dtype of ``z``)."""
    monkeypatch.setattr(
        t, "_looped_loss", lambda z, nll, real=t._looped_loss: real(
            z.astype(jnp.bfloat16), nll))


def _unnormed_state_fed_forward(monkeypatch):
    """``ln_f`` on the head's input only: the next loop step gets the
    stack's raw output."""
    def loop_layers(lp, ln_f, x, positions, cfg):
        states, auxs = [], None
        for _ in range(cfg.n_loops):
            x, auxs = t._scan_layers(lp, x, positions, cfg)
            states.append(_kinds.rmsnorm(x, ln_f, cfg.norm_eps))
        return jnp.stack(states), t._over_layers(auxs)
    monkeypatch.setattr(t, "_loop_layers", loop_layers)


def _a_loop_step_dropped(monkeypatch):
    """The last step's state is the one before it: its pass never ran."""
    real = t._loop_layers

    def loop_layers(lp, ln_f, x, positions, cfg):
        states, auxs = real(lp, ln_f, x, positions, cfg)
        return states.at[-1].set(states[-2]), auxs
    monkeypatch.setattr(t, "_loop_layers", loop_layers)


def _last_share_from_its_own_gate(monkeypatch):
    """``p_T = lambda_T prod_{j<T}(1 - lambda_j)``: no longer sums to 1."""
    def looped_loss(z, nll):
        stay = jnp.concatenate([jnp.zeros_like(z[:1]), jnp.cumsum(
            jax.nn.log_sigmoid(-z[:-1]), axis=0)])
        log_p = stay + jax.nn.log_sigmoid(z)
        p = jnp.exp(log_p)
        entropy = -jnp.sum(p * log_p, axis=0)
        loss = jnp.mean(jnp.sum(p * nll, axis=0)
                        - t.EXIT_ENTROPY_WEIGHT * entropy)
        return loss, {"step_losses": jnp.mean(nll, axis=(1, 2)),
                      "exit_share": jnp.mean(p, axis=(1, 2)),
                      "gate_entropy": jnp.mean(entropy)}
    monkeypatch.setattr(t, "_looped_loss", looped_loss)


def _post_norms_left_out(monkeypatch):
    def without(p, x, positions, cfg, *kind, block=t._block):
        return block(p, x, positions,
                     dataclasses.replace(cfg, post_norm=False), *kind)
    monkeypatch.setattr(t, "_block", without)


@pytest.mark.parametrize("fault", [
    _gate_in_bf16, _entropy_in_bf16, _unnormed_state_fed_forward,
    _a_loop_step_dropped, _last_share_from_its_own_gate,
    _post_norms_left_out], ids=lambda f: f.__name__.strip("_"))
def test_a_wrong_term_fails(monkeypatch, fault):
    """Each moves the loss or the gate's gradient far beyond TOL."""
    assert ARCH.sound < TOL
    fault(monkeypatch)
    err = ARCH.error(fault.__name__.strip("_"),
                     only=("loss", "grad:exit_gate", "grad:wq"))
    assert err > 20 * TOL, (fault.__name__, err)


# -- defaults reproduce the parent's model; remat means something -------------

DENSE = arch.DENSE


def _parents_loss(params, tokens, targets, cfg):
    """The dense GPT block and its loss as the parent commit's
    ``forward_loss_spmd`` computed them, written out from the same
    building blocks: pre-norms only, ``gelu(x w1) w2``, one scan, one
    head."""
    positions = jnp.arange(tokens.shape[1])

    def block(x, p):
        x = t._attention_block(p, x, positions, cfg)
        h = _kinds.rmsnorm(x, p["ln2"], cfg.norm_eps)
        o = jax.nn.gelu(h @ p["w1"].astype(h.dtype)) @ p["w2"].astype(
            h.dtype)
        return x + o.astype(x.dtype), None
    x = params["embed"].astype(cfg.dtype)[tokens]
    flat = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), params["layers"])
    x, _ = jax.lax.scan(block, x, flat)
    x = _kinds.rmsnorm(x, params["ln_f"], cfg.norm_eps)
    return jnp.mean(t._head_xent(
        x, params["embed"].astype(cfg.dtype).T, targets))


def test_the_new_fields_at_their_defaults_are_the_parent_s_model():
    """``n_loops=1, post_norm=False, ffn_gated=False``: the parent's tree,
    loss and gradients, bit for bit."""
    explicit = dataclasses.replace(DENSE, n_loops=1, post_norm=False,
                                   ffn_gated=False)
    assert explicit == DENSE and DENSE.remat is None
    params, batch = _params(DENSE), _batch()
    assert set(params) == {"embed", "ln_f", "layers"}
    assert set(params["layers"]) == {"ln1", "ln2", "wq", "wk", "wv", "wo",
                                     "w1", "w2"}
    loss, aux, grads = _program(DENSE, params, batch)
    assert set(aux) == {"aux_loss"}
    want_loss, want = jax.jit(jax.value_and_grad(_parents_loss),
                              static_argnums=3)(
        params, batch["tokens"], batch["targets"], DENSE)
    assert float(loss) == float(want_loss)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=str(path))


@pytest.mark.parametrize("path, cfg", [("scan", DENSE), ("looped", CFG)])
def test_remat_changes_what_is_stored_not_what_is_computed(path, cfg):
    params, batch = _params(cfg), _batch()
    loss0, _aux, grads0 = _program(cfg, params, batch)
    for remat in (True, False):
        loss, _aux, grads = _program(
            dataclasses.replace(cfg, remat=remat), params, batch)
        np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
        for g, g0 in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(grads0)):
            assert _rel(g, g0) < 1e-5, (path, remat)


def test_remat_none_checkpoints_the_looped_stack_only():
    """What ``None`` resolves to, read from the traced step: a looped
    stack's blocks run under ``checkpoint`` (since ISSUE 52 in the stack's
    own backward pass, which pulls each period back through
    ``jax.checkpoint``), the single scan's do not (the GPT and OLMoE cells'
    programs stay as they were), and an explicit value is honoured on
    both."""
    def checkpointed(cfg):
        mesh = build_mesh(devices=jax.devices()[:1], dp=1)
        params, batch = _params(cfg), _batch()
        text = str(jax.make_jaxpr(t.make_grad_fn(cfg, mesh))(
            params, batch["tokens"], batch["targets"]))
        return "checkpoint" in text or "remat" in text
    assert checkpointed(CFG) and not checkpointed(DENSE)
    assert not checkpointed(dataclasses.replace(CFG, remat=False))
    assert checkpointed(dataclasses.replace(DENSE, remat=True))


# -- the looped stack's own backward pass (ISSUE 52) ----------------------------

def _lowered(cfg, params=None, batch=None):
    """The gradient function of ``cfg`` on one device, lowered."""
    mesh = build_mesh(devices=jax.devices()[:1], dp=1)
    params, batch = params or _params(cfg), batch or _batch()
    return jax.jit(t.make_grad_fn(cfg, mesh)).lower(
        params, batch["tokens"], batch["targets"])


@pytest.mark.parametrize("loops", [2, 3])
@pytest.mark.parametrize("axes", [
    None, {"dp": 2}, {"sp": 2}, {"dp": 2, "sp": 2}, {"tp": 2}],
    ids=lambda axes: "x".join(f"{k}{v}" for k, v in (axes or {"one": ""}
                                                      ).items()))
def test_the_accumulating_backward_is_autodiff_s(axes, loops):
    """The checkpointed looped stack's hand-written backward pass (one
    accumulator, every pass adding its slice in place) against the scans'
    transpose (``remat=False``: two stacks, a whole add a loop step): the
    same float32 additions in the same order, so every leaf's gradient to
    the bit. Where the sequence is sharded the ring's forward is run again
    by the one path and stored by the other, which XLA:CPU compiles to sums
    of another order: 8e-7 of a matrix's norm and one or two units in the
    last place of a scalar leaf (1.6e-6), the very numbers the parent's
    checkpointed path reads against ``remat=False``, so 1e-5 there."""
    cfg = dataclasses.replace(CFG, n_loops=loops)
    params, batch = _params(cfg, seed=loops), _batch(n_seqs=4, seed=loops)
    loss, aux, grads = _program(cfg, params, batch, axes)
    loss0, aux0, grads0 = _program(dataclasses.replace(cfg, remat=False),
                                   params, batch, axes)
    np.testing.assert_allclose(float(loss), float(loss0), rtol=1e-6)
    for k in aux0:
        np.testing.assert_allclose(np.asarray(aux[k]), np.asarray(aux0[k]),
                                   rtol=1e-6, atol=1e-7, err_msg=k)
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree_util.tree_leaves(grads0)):
        if "sp" in (axes or {}):
            assert _rel(g, g0) < 1e-5, path
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(g0),
                                          err_msg=str(path))


@pytest.mark.parametrize("fields", [
    dict(layer_pattern=((None, False), (8, True))),
    dict(layer_pattern=(("attention", None, True), ("dense",)), n_layers=4,
         dense_ff=64),
    dict(n_experts=4, moe_top_k=2, moe_gated=True, moe_balance_weight=0.5,
         moe_z_weight=0.1)],
    ids=["two_kinds_a_period", "a_stack_a_word", "experts"])
def test_the_accumulating_backward_takes_every_looped_stack(fields):
    """What a looped stack may be beside Ouro's: a period of several kinds
    (a period's input is stored, its blocks run again together), one-sublayer
    blocks in a stack a word (an accumulator a stack), expert layers (the
    auxiliary loss's gradient, the router's alone, goes through the
    hand-written backward; the integer counters beside it have no
    cotangent). Loss, terms and every gradient to the bit."""
    cfg = dataclasses.replace(CFG, **fields)
    params, batch = _params(cfg), _batch()
    loss, aux, grads = _program(cfg, params, batch)
    loss0, aux0, grads0 = _program(dataclasses.replace(cfg, remat=False),
                                   params, batch)
    assert (float(aux["aux_loss"]) > 0) == ("n_experts" in fields)
    assert float(loss) == float(loss0)
    for k in aux0:
        np.testing.assert_array_equal(np.asarray(aux[k]), np.asarray(aux0[k]),
                                      err_msg=k)
    for (path, g), g0 in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree_util.tree_leaves(grads0)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(g0),
                                      err_msg=str(path))


def _backward_loops(text):
    """{outer | inner: the shapes of the float32 arrays the loop carries,
    leading 1s dropped} of the looped stack's two backward ``while``s in a
    compiled step's text."""
    loops = {}
    for line in text.splitlines():
        if " while(" not in line or "transpose(jvp(hvd.layers))" not in line:
            continue
        carried, rest = line.split(" while(", 1)
        nested = "/while/body/" in rest.split('op_name="', 1)[1]
        loops["inner" if nested else "outer"] = [
            tuple(int(n) for n in dims.split(",") if n)
            for dims in re.findall(r"f32\[([\d,]*)\]", carried)]
    return {k: [tuple(int(n) for n in np.trim_zeros(np.array(s) - 1, "f") + 1)
                for s in v] for k, v in loops.items()}


@pytest.mark.parametrize("remat, whole_adds", [(None, False), (False, True)],
                         ids=["accumulating", "autodiff"])
def test_the_backward_loops_carry_one_accumulator(remat, whole_adds):
    """Read from the compiled tiny step: each of the backward's two loops
    carries the stacked float32 weights and ONE more float32 array of each
    stacked leaf's shape, the accumulator the inner loop hands back to the
    outer, and nowhere in the program is a whole stacked leaf added to
    another. The scans' transpose (``remat=False``, and the parent's
    checkpointed path) stacks a pass's gradients in the inner loop and adds
    that stack to the outer loop's own, leaf by leaf, once a loop step:
    the second case shows that the search finds those adds."""
    cfg = dataclasses.replace(CFG, remat=remat)
    params = _params(cfg)
    text = _lowered(cfg, params).compile().as_text()
    stacked = [leaf.shape[1:] for leaf in
               jax.tree_util.tree_leaves(params["layers"])]
    loops = _backward_loops(text)
    assert set(loops) == {"outer", "inner"}
    for shape in set(stacked) if remat is None else ():
        # the weights themselves, and the gradients' one set
        assert loops["inner"].count(shape) == 2 * stacked.count(shape), shape
        assert loops["outer"].count(shape) == 2 * stacked.count(shape), shape
    adds = [line for line in text.splitlines() if re.search(
        r"= f32\[(?:1,)?(%s)\]\S* add\(" % "|".join(
            ",".join(map(str, shape)) for shape in set(stacked)), line)]
    assert bool(adds) == whole_adds, adds[:3]


# -- layouts --------------------------------------------------------------------

@pytest.mark.parametrize("axes", [{"dp": 2}, {"sp": 2}, {"dp": 2, "sp": 2}])
def test_data_layouts_give_one_device_s_result(axes):
    """Nothing in the loop is per-device: the same loss, the same exit
    distribution and (after the data shards' sum, which the flagship's
    gradient sync leaves undivided) the same gradients."""
    params, batch = _params(), _batch(n_seqs=4)
    loss1, aux1, grads1 = _program(CFG, params, batch)
    loss, aux, grads = _program(CFG, params, batch, axes)
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    for k in aux1:
        np.testing.assert_allclose(np.asarray(aux[k]), np.asarray(aux1[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    shards = int(np.prod(list(axes.values())))
    for (path, g), g1 in zip(jax.tree_util.tree_leaves_with_path(grads),
                             jax.tree_util.tree_leaves(grads1)):
        assert _rel(np.asarray(g) / shards, g1) < TOL, path


def test_tensor_parallel_gives_one_device_s_result():
    """tp shards the heads, the FFN's width (``w3`` as ``w1``) and the
    head's vocabulary (the psum algebra in place of the kernel); the
    post-norms, the gate and the loop see whole activations. Loss, exit
    distribution and the sharded leaves' gradients (which the flagship
    leaves scaled by the shards, ROADMAP A17, as a replicated leaf's miss
    the tp sum)."""
    params, batch = _params(), _batch(n_seqs=4)
    loss1, aux1, grads1 = _program(CFG, params, batch)
    loss, aux, grads = _program(CFG, params, batch, {"tp": 2})
    np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-5)
    for k in aux1:
        np.testing.assert_allclose(np.asarray(aux[k]), np.asarray(aux1[k]),
                                   rtol=1e-5, atol=1e-6, err_msg=k)
    for name in ("wq", "wo", "w1", "w3", "w2"):
        assert _rel(np.asarray(grads["layers"][name]) / 2,
                    grads1["layers"][name]) < TOL, name
    assert _rel(np.asarray(grads["lm_head"]) / 2, grads1["lm_head"]) < TOL


def test_a_live_pipeline_axis_is_refused_by_name():
    cfg = dataclasses.replace(CFG, n_microbatches=2)
    params, batch = _params(cfg, n_stages=2), _batch(n_seqs=4)
    with pytest.raises(NotImplementedError, match="looped stack.*pp axis"):
        _program(cfg, params, batch, {"pp": 2})
    # the block's new parts alone ride the pipeline as any block does
    cfg = dataclasses.replace(cfg, n_loops=1)
    params = _params(cfg, n_stages=2)
    loss, _aux, _grads = _program(cfg, params, batch, {"pp": 2})
    assert np.isfinite(float(loss))


# -- the tree, the adapter, the serving paths -----------------------------------

def test_init_params_and_shardings_hold_the_new_leaves():
    mesh = build_mesh(devices=jax.devices()[:4], dp=2, tp=2)
    params = t.init_params(np.random.RandomState(0), CFG, 1)
    sh = t.param_shardings(CFG, mesh)
    assert jax.tree_util.tree_structure(params) == \
        jax.tree_util.tree_structure(sh)
    assert set(params["layers"]) == {"ln1", "ln1_post", "ln2", "ln2_post",
                                     "wq", "wk", "wv", "wo", "w1", "w3",
                                     "w2"}
    assert params["exit_gate"].shape == (CFG.d_model, 1)
    assert params["exit_gate_bias"].shape == (1,)
    assert params["layers"]["w3"].shape == (1, CFG.n_layers, CFG.d_model,
                                            CFG.d_ff)
    assert sh["layers"]["w3"].spec == sh["layers"]["w1"].spec


def test_the_plain_objective_is_python_loops_alone():
    """The objective tier-1 differentiates: the scan and the checkpoints
    are ``loss_and_grads``' (the chip's check)."""
    with open(reference.__file__) as f:
        text = f.read()
    plain = text.split("def stored_less", 1)[0].split('"""', 2)[2]
    assert "scan" not in plain and "checkpoint" not in plain
