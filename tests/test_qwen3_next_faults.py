"""What ``tests/test_qwen3_next.py``'s TOL must not let through (ISSUE 70): each
wrong term of the Qwen3-Next blocks, in the program (a field of the config, a
patched piece) or in the plain reference's one function (a reading the
config cannot say), on the smallest stack that holds it: one delta block and
the attention block, each with its experts. A file of its own: under
``--dist loadfile`` a second worker compiles these dozen faulty programs.
"""

import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import arch
from arch import TOL
from horovod_tpu.models import delta
from horovod_tpu.models import transformer as t
from horovod_tpu.models._kinds import Rope

ARCH = arch.get("qwen3_next")
reference, CFG = ARCH.reference, ARCH.CFG

#: one delta block and the attention block, each with its experts: every
#: wrong term below is in one of them
SMALL = ARCH.cut({"num_hidden_layers": 2, "full_attention_interval": 2})


def test_the_sound_small_stack_matches_the_reference():
    assert SMALL.SIZES["layer_mixers"] == ["delta", "attention"]
    assert SMALL.sound < TOL
    assert all(np.linalg.norm(np.asarray(v)) > 0
               for k, v in SMALL.kept()[2].items() if k.startswith("grad:"))


def _scan_with(**changed):
    """``delta_chunked`` with an input changed before the scan."""
    real = delta.delta_chunked

    def scan(q, k, v, g, beta, chunk, sub=delta.SUB):
        x = {"q": q, "k": k, "v": v, "g": g, "beta": beta}
        x.update({name: change(x[name]) for name, change in changed.items()})
        return real(x["q"], x["k"], x["v"], x["g"], x["beta"], chunk, sub)
    return scan


def _every_key_head_in_turn(x):
    """``[k0, k1, k0, k1]``: value head ``h`` on key head ``h % Hk``."""
    return jnp.tile(x, (1, 1, CFG.delta_heads // CFG.delta_key_heads, 1))


def _ungated(shared_expert):
    """``_shared_expert`` on the tree without ``ws_gate`` (the leaf decides,
    as ``wg`` does for an attention block)."""
    return lambda p, toks, activation: shared_expert(
        {k: v for k, v in p.items() if k != "ws_gate"}, toks, activation)


_FULL_ROPE = tuple(
    kind if kind[0] != "attention" else kind[:2] + (Rope(1e7),) + kind[3:]
    for kind in CFG.layer_pattern[-2:])

FAULTS = {
    "the norm's weight not zero-centred": {"cfg": {"zero_centred_norms": False}},
    "rope over the whole head": {"cfg": {"layer_pattern": (
        SMALL.CFG.layer_pattern[:2] + _FULL_ROPE)}},
    "the shared expert ungated":
        {"patch": lambda: (t, "_shared_expert", _ungated(t._shared_expert))},
    "the top-k weights not renormalised": {"cfg": {"moe_renormalize": False}},
    "value head h reading key head h % 2":
        {"patch": lambda: (delta, "delta_chunked", _scan_with(
            q=_every_key_head_in_turn, k=_every_key_head_in_turn))},
    "the state not carried across chunks":
        {"patch": lambda: (delta, "_carry", jnp.zeros_like)},
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


def _decay_after_the_correction(state, inputs):
    q, k, v, g, beta = inputs
    predicted = jnp.einsum("bhd,bhdv->bhv", k, state)
    state = jnp.exp(g)[..., None, None] * (state + jnp.einsum(
        "bhd,bhv->bhdv", beta[..., None] * k, v - predicted))
    return state, jnp.einsum("bhd,bhdv->bhv", q, state)


def _a_gate_a_head(gate):
    return jax.nn.sigmoid(jnp.mean(gate, axis=-1, keepdims=True))


WRONG_READINGS = {
    "the decay applied after the correction":
        ("delta_step", _decay_after_the_correction),
    "sigmoid(z) where the output norm's gate is silu(z)":
        ("_value_gate", jax.nn.sigmoid),
    "the attention's gate taken a head where it is a channel":
        ("_output_gate", _a_gate_a_head),
}


@pytest.mark.parametrize("what", sorted(WRONG_READINGS))
def test_a_wrong_reading_fails(monkeypatch, what):
    """A reading the config cannot say, in the plain reference's one
    function: the sound reference, which the program is inside TOL of, is
    far from it."""
    params, batch, _want = SMALL.kept()
    monkeypatch.setattr(reference, *WRONG_READINGS[what])
    got = SMALL.want(params, batch)
    err = SMALL.error(what, got=got)
    assert err > 20 * TOL, (what, err)


def test_silu_where_kimi_s_gate_is_a_sigmoid_is_another_block():
    """And back: the form with a decay a channel keeps its low-rank sigmoid
    gate and its three projections; neither form's tree runs the other."""
    channel = dataclasses.replace(SMALL.CFG, delta_decay="channel",
                                  delta_key_heads=None)
    names = [leaf.name for leaf in delta._leaves(channel)]
    assert "wg_down" in names and "w_in" not in names
    names = [leaf.name for leaf in delta._leaves(SMALL.CFG)]
    assert names == ["dt_bias", "a_log", "ln1", "w_in", "conv", "w_ba",
                     "norm", "wo"]
    with pytest.raises(Exception):
        SMALL.error("the other form on this tree", channel)
