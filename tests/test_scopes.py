"""The phase vocabulary (horovod_tpu/profiling/scopes.py): the names are
written in one place, the two train steps the chip benchmark runs carry
every phase forward and backward in their compiled text, a scope is
metadata only, JAX names a checkpointed block's second forward as the
vocabulary says it does and the one hand-written backward that runs
forward work again says so, the input path's host spans open once per
batch in order, and the compile watcher splits a program's way to the
device."""

import contextlib
import os
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.parallel import build_mesh
from horovod_tpu.profiling import compile_watch, scopes

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "horovod_tpu")


# -- the vocabulary ----------------------------------------------------------

def test_the_vocabulary_is_the_only_place_the_strings_are_written():
    names = scopes.DEVICE_PHASES + scopes.HOST_SPANS
    assert len(set(names)) == len(names) == 51
    assert scopes.RECOMPUTE in scopes.DEVICE_PHASES
    # JAX's own word is no phase, and is written here alone all the same
    names += (scopes.RECOMPUTED,)
    assert not scopes.RECOMPUTED.startswith("hvd.")
    assert scopes.HOST_SPANS == ("hvd.input.source", "hvd.input.place",
                                 "hvd.host.gc", "hvd.host.compile",
                                 "hvd.host.trace", "hvd.host.import",
                                 "hvd.host.init")
    assert all(n.startswith("hvd.") for n in names[:-1])
    home = os.path.join(PACKAGE, "profiling", "scopes.py")
    elsewhere = []
    for folder, _dirs, files in os.walk(PACKAGE):
        for f in files:
            path = os.path.join(folder, f)
            if f.endswith(".py") and path != home:
                with open(path) as fh:
                    text = fh.read()
                elsewhere += [(os.path.relpath(path, PACKAGE), n)
                              for n in names if n in text]
    assert not elsewhere, elsewhere


# -- the device side: phases and directions in the compiled step -------------

def _bert_step():
    from horovod_tpu.models import init_opt_state
    from horovod_tpu.models.bert import (Bert, BertConfig, init_bert,
                                         make_bert_train_step)
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    model = Bert(BertConfig(vocab_size=64, hidden_size=32, num_layers=2,
                            num_heads=4, intermediate_size=64,
                            max_position=32, dtype=jnp.float32))
    params = init_bert(model, jax.random.PRNGKey(0), seq_len=16, mesh=mesh)
    tx = optax.adamw(1e-3)
    ids = jnp.zeros((2, 16), jnp.int32)
    batch = {"input_ids": ids, "token_type_ids": ids,
             "attention_mask": jnp.ones((2, 16), bool), "mlm_labels": ids,
             "mlm_mask": jnp.ones((2, 16), jnp.float32),
             "nsp_labels": jnp.zeros((2,), jnp.int32)}
    return (make_bert_train_step(model, tx, mesh),
            (params, init_opt_state(tx, params, mesh), batch))


def _flagship_step(dp: int = 1):
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=32,
                            dtype=jnp.float32, remat=False)
    mesh = build_mesh(devices=jax.devices()[:dp], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2 * dp, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _moe_step():
    """The flagship block as OLMoE sets it: gated experts, QK-norm, an
    untied head, both auxiliary losses."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=16, max_seq=32, n_experts=4,
                            moe_top_k=2, moe_gated=True, moe_z_weight=1e-3,
                            qk_norm=True, tie_embeddings=False,
                            dtype=jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _looped_step():
    """The flagship block as Ouro sets it: sandwich norms, a gated FFN, the
    stack looped twice, a head and the exit gate on every loop step."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=64, max_seq=32, n_loops=2,
                            post_norm=True, ffn_gated=True,
                            tie_embeddings=False, dtype=jnp.float32)
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _mixed_step():
    """The flagship block with two kinds of layer (a full layer without
    positions, a window layer with rope), grouped heads, the router on the
    block's input, ReLU-gated experts of which a share is held."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=2, d_ff=16, max_seq=32, n_experts=4,
                            moe_top_k=2, moe_gated=True,
                            moe_renormalize=True, tie_embeddings=False,
                            dtype=jnp.float32, head_width=16, n_kv_heads=2,
                            layer_pattern=((None, False), (8, True)),
                            moe_router_input="block_input",
                            moe_activation="relu", expert_share=(0, 2))
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _hybrid_step():
    """A stack of one-sublayer blocks: a Mamba-2 mixer, sigmoid-routed
    ungated experts with a shared expert of which a share is held,
    attention without positions."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=3, d_ff=16, max_seq=32, n_experts=4,
                            moe_top_k=2, moe_renormalize=True,
                            moe_activation="relu2", moe_balance_weight=0.0,
                            moe_router_scores="sigmoid",
                            moe_routed_scale=2.5, moe_shared_width=32,
                            tie_embeddings=False, dtype=jnp.float32,
                            head_width=16, n_kv_heads=2, layer_pattern=(
                                ("mamba",), ("experts",),
                                ("attention", None, False)),
                            expert_share=(0, 2), ssm_heads=4,
                            ssm_head_dim=8, ssm_state=8, ssm_groups=2,
                            ssm_chunk=8)
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _gated_step():
    """Two attention shapes in one period, each a stack of its own: window
    layers of 8 query heads and full layers of 6 on 2 key/value heads, a
    rotary table a kind, a gate a head on the core's output; a dense layer
    leading sigmoid-routed experts of which a share is held."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    from horovod_tpu.models._kinds import Rope, Yarn
    full = ("attention", None, Rope(5e5, 8, Yarn(64.0, 8)), 6, True)
    window = ("attention", 8, Rope(1e4), 8, True)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=6,
                            n_layers=4, d_ff=16, dense_ff=32, max_seq=32,
                            n_experts=4, moe_top_k=2, moe_gated=True,
                            moe_renormalize=True, moe_balance_weight=0.0,
                            moe_router_scores="sigmoid",
                            moe_routed_scale=2.5, moe_shared_width=16,
                            ffn_gated=True, tie_embeddings=False,
                            dtype=jnp.float32, head_width=16, n_kv_heads=2,
                            layer_pattern=(window, ("experts",),
                                           full, ("experts",)),
                            lead_pattern=(full, ("dense",)),
                            expert_share=(0, 2))
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _short_conv_step():
    """Gated short-convolution blocks beside an attention block with a norm
    a head, a conv + dense layer leading sigmoid-routed experts of which a
    share is held, a tied head."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_layers=4, d_ff=16, dense_ff=32, max_seq=32,
                            n_experts=4, moe_top_k=2, moe_gated=True,
                            moe_renormalize=True, moe_balance_weight=0.0,
                            moe_router_scores="sigmoid", ffn_gated=True,
                            dtype=jnp.float32, head_width=16, n_kv_heads=2,
                            qk_norm="head", conv_taps=3,
                            layer_pattern=(("attention", None, True),
                                           ("experts",), ("conv",),
                                           ("experts",)),
                            lead_pattern=(("conv",), ("dense",)),
                            expert_share=(0, 2))
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _delta_step():
    """Gated delta-rule blocks beside a latent block without rotation and
    with values narrower than keys, a delta + dense layer leading
    sigmoid-routed experts of which a share is held, an untied head."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=4, d_ff=16, dense_ff=32, max_seq=32,
                            n_experts=4, moe_top_k=2, moe_gated=True,
                            moe_renormalize=True, moe_balance_weight=0.0,
                            moe_router_scores="sigmoid", ffn_gated=True,
                            dtype=jnp.float32, head_width=16, kv_latent=8,
                            rope_width=4, latent_rope=False, value_width=8,
                            delta_heads=2, delta_head_dim=8, delta_chunk=8,
                            tie_embeddings=False,
                            layer_pattern=(("delta",), ("experts",),
                                           ("latent",), ("experts",)),
                            lead_pattern=(("delta",), ("dense",)),
                            expert_share=(0, 2))
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


def _gated_delta_step():
    """Delta blocks with a decay a head on shared keys beside an attention
    block gated a channel, zero-centred norms, softmax-routed experts with
    a gated shared expert of which a share is held (Qwen3-Next's blocks)."""
    from horovod_tpu.models import (TransformerConfig, init_opt_state,
                                    init_params, make_train_step,
                                    shard_batch, shard_params)
    from horovod_tpu.models._kinds import Rope
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                            n_kv_heads=2, head_width=8, qk_norm="head",
                            zero_centred_norms=True, n_layers=4, d_ff=16,
                            max_seq=32,
                            n_experts=4, moe_top_k=2, moe_gated=True,
                            moe_renormalize=True, moe_balance_weight=0.0,
                            moe_shared_width=16, moe_shared_gate=True,
                            dtype=jnp.float32, delta_heads=4,
                            delta_key_heads=2, delta_head_dim=8,
                            delta_chunk=8, delta_decay="head",
                            tie_embeddings=False,
                            layer_pattern=(("delta",), ("experts",), (
                                "attention", None, Rope(1e7, 2), None,
                                "channel"), ("experts",)),
                            expert_share=(0, 2))
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    params = shard_params(init_params(np.random.RandomState(0), cfg, 1),
                          cfg, mesh)
    tx = optax.adamw(1e-3)
    tokens = np.zeros((2, 16), np.int32)
    t, y = shard_batch(tokens, tokens, mesh)
    return (make_train_step(cfg, mesh, tx),
            (params, init_opt_state(tx, params, mesh, cfg), t, y))


_TEXTS = {}


def _compiled_text(model: str) -> str:
    if model not in _TEXTS:
        step, args = {"bert": _bert_step, "flagship": _flagship_step,
                      "flagship.dp2": lambda: _flagship_step(2),
                      "moe": _moe_step, "looped": _looped_step,
                      "mixed": _mixed_step,
                      "hybrid": _hybrid_step,
                      "gated": _gated_step,
                      "short_conv": _short_conv_step,
                      "delta": _delta_step,
                      "gated_delta": _gated_delta_step}[model]()
        _TEXTS[model] = step.lower(*args).compile().as_text()
    return _TEXTS[model]


def _directions(text: str, phase: str) -> set:
    """The directions in which ``phase`` is a whole component of an
    instruction's op_name path, ``jvp(..)`` / ``transpose(..)`` peeled
    off: differentiation wraps the outermost component of the name stack
    (``jvp(Bert)/layer_0/hvd.mlp/..`` under flax,
    ``transpose(jvp(hvd.head))/..`` where the scope is outermost), so
    the direction is the path's and the phase a component's."""
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        parts = [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
        if phase in parts:
            found.add("bwd" if "transpose(" in path else "fwd")
    return found


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES)
@pytest.mark.parametrize("model", ["bert", "flagship"])
def test_the_step_carries_every_model_phase_in_both_directions(model, phase):
    assert _directions(_compiled_text(model), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES)
def test_the_moe_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("moe"), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.LOOP_PHASES)
def test_the_looped_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("looped"), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.MIXED_PHASES)
def test_the_mixed_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("mixed"), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.HYBRID_PHASES
                         + (scopes.ATTENTION_CORE_FULL,))
def test_the_hybrid_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("hybrid"), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.MIXED_PHASES + scopes.GATED_PHASES
                         + (scopes.MOE_SHARED,))
def test_the_gated_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("gated"), phase) == {"fwd", "bwd"}


def test_the_gate_nests_in_attention_beside_the_core():
    """hvd.attention.gate inside hvd.attention and outside
    hvd.attention.core (``step.attention_core_ms`` is the kernels' cover,
    not the gate's); a step without a gated kind has no such phase."""
    assert scopes.GATED_PHASES == ("hvd.attention.gate",)
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("gated"))

    def parts(path):
        return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
    gate = [parts(p) for p in paths if scopes.ATTENTION_GATE in parts(p)]
    assert gate and all(scopes.ATTENTION in p
                        and scopes.ATTENTION_CORE not in p for p in gate)
    assert not any(scopes.ATTENTION_GATE in _compiled_text(model)
                   for model in ("flagship", "moe", "looped", "mixed",
                                 "hybrid"))


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.SHORT_CONV_PHASES)
def test_the_short_conv_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("short_conv"), phase) == {"fwd", "bwd"}


def test_the_short_conv_s_parts_nest_in_it_and_the_heads_norm_in_attention():
    """hvd.short_conv.proj and hvd.short_conv.gate inside hvd.short_conv
    (the mixer, norm to out-projection); the QK-norm a
    head inside hvd.attention and outside hvd.attention.core
    (``step.attention_core_ms`` is the kernels' cover); a step without the
    kind has no such phase."""
    assert scopes.SHORT_CONV_PHASES == (
        "hvd.short_conv", "hvd.short_conv.proj", "hvd.short_conv.gate")
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("short_conv"))

    def parts(path):
        return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
    for part in (scopes.SHORT_CONV_PROJ, scopes.SHORT_CONV_GATE):
        inside = [parts(p) for p in paths if part in parts(p)]
        assert inside and all(
            scopes.SHORT_CONV in p
            and p.index(scopes.SHORT_CONV) < p.index(part)
            and scopes.ATTENTION not in p and scopes.MLP not in p
            for p in inside), part
        assert any(scopes.LAYERS in p for p in inside), part
    # the matmuls are the projections', the taps' pads the gate's
    assert any(p[-1].startswith("dot_general") for p in map(parts, paths)
               if scopes.SHORT_CONV_PROJ in p)
    assert not any(p[-1].startswith("dot_general") for p in map(parts, paths)
                   if scopes.SHORT_CONV_GATE in p)
    # three norms an attention block (ln1, q's, k's): none in the core
    norms = [parts(p) for p in paths if "rsqrt" in parts(p)[-1]
             and scopes.ATTENTION in parts(p)]
    assert norms and not any(scopes.ATTENTION_CORE in p for p in norms)
    assert not any(scopes.SHORT_CONV in _compiled_text(model)
                   for model in ("flagship", "moe", "looped", "mixed",
                                 "hybrid", "gated"))


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.DELTA_PHASES
                         + (scopes.ATTENTION_LATENT,
                            scopes.ATTENTION_LATENT_DOWN,
                            scopes.ATTENTION_LATENT_UP))
def test_the_delta_step_carries_every_phase_in_both_directions(phase):
    assert _directions(_compiled_text("delta"), phase) == {"fwd", "bwd"}


@pytest.mark.parametrize("phase", scopes.MODEL_PHASES + scopes.MOE_PHASES
                         + scopes.DELTA_PHASES
                         + (scopes.ATTENTION_GATE, scopes.MOE_SHARED,
                            scopes.ATTENTION_CORE_FULL))
def test_the_gated_delta_step_carries_every_phase_in_both_directions(phase):
    """The form with a decay a head keeps the mixer's six phases; the
    attention's gate a channel is under ``hvd.attention.gate``, the shared
    expert with its gate under ``hvd.moe.shared``."""
    assert _directions(_compiled_text("gated_delta"), phase) == {"fwd",
                                                                 "bwd"}


#: ``models/delta.py:_head_sums`` and ``_to_channels`` as a jaxpr names them
_HEAD_SUMS = ("...c,ch->...h", "...h,ch->...c")


def test_the_gates_of_the_gated_delta_step_are_where_their_phases_say():
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("gated_delta"))

    def parts(path):
        return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
    # the sigmoids: the attention's gate a channel, the shared expert's a
    # token, beta's in the mixer's gates; silu(z) in the mixer's norm
    # (a sigmoid is ``logistic``, or where the backend expands it its ``exp``)
    gates = [parts(p) for p in paths
             if parts(p)[-1].startswith(("logistic", "exp"))]
    assert any(scopes.ATTENTION_GATE in p for p in gates)
    assert any(scopes.MOE_SHARED in p for p in gates)
    assert any(scopes.DELTA_GATES in p for p in gates)
    assert any(scopes.DELTA_NORM in p for p in gates)
    # b | a are one projection under the gates, q | k | v | z one under proj
    dots = [parts(p) for p in paths if parts(p)[-1].startswith("dot_general")
            and scopes.DELTA in parts(p)]
    assert {next(c for c in reversed(p) if c.startswith("hvd.")) for p in
            dots} >= {scopes.DELTA_PROJ, scopes.DELTA_GATES}
    assert all(p[-2] in _HEAD_SUMS for p in dots
               if scopes.DELTA_CONV in p or scopes.DELTA_NORM in p)


def test_the_delta_mixer_s_parts_nest_in_it():
    """hvd.delta.proj, .conv, .gates, .scan and .norm inside hvd.delta (the
    mixer, norm to out-projection) and in no attention or MLP phase; the
    scan holds the chunks' loop; a step without the kind has no such
    phase."""
    assert scopes.DELTA_PHASES == (
        "hvd.delta", "hvd.delta.proj", "hvd.delta.conv", "hvd.delta.gates",
        "hvd.delta.scan", "hvd.delta.norm")
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("delta"))

    def parts(path):
        return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
    for part in scopes.DELTA_PHASES[1:]:
        inside = [parts(p) for p in paths if part in parts(p)]
        assert inside and all(
            scopes.DELTA in p and p.index(scopes.DELTA) < p.index(part)
            and scopes.ATTENTION not in p and scopes.MLP not in p
            for p in inside), part
        assert any(scopes.LAYERS in p for p in inside), part
    assert any("while" in p[-1] for p in map(parts, paths)
               if scopes.DELTA_SCAN in p)
    # no projection under the convolutions or the norm: the products there
    # are a head's sums with the 0/1 matrix and their way back
    assert all(p[-2] in _HEAD_SUMS for p in map(parts, paths)
               if p[-1].startswith("dot_general")
               and (scopes.DELTA_CONV in p or scopes.DELTA_NORM in p))
    assert not any(scopes.DELTA in _compiled_text(model)
                   for model in ("flagship", "moe", "hybrid", "short_conv"))


def test_the_mixer_s_parts_nest_in_ssm_and_the_shared_expert_in_moe():
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("hybrid"))

    def parts(path):
        return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]
    for inner in scopes.HYBRID_PHASES[2:]:
        inside = [parts(p) for p in paths if inner in parts(p)]
        assert inside and all(scopes.SSM in p for p in inside), inner
    shared = [parts(p) for p in paths if scopes.MOE_SHARED in parts(p)]
    assert shared and all(scopes.MOE in p and scopes.MLP in p
                          for p in shared)
    # a Mamba block is no attention and no MLP
    assert not any(scopes.ATTENTION in p or scopes.MLP in p
                   for p in map(parts, paths) if scopes.SSM in p)


def test_the_layer_kinds_nest_in_the_attention_core():
    """hvd.attention.core.window and hvd.attention.core.full inside
    hvd.attention.core, so ``step.attention_core_ms`` covers both; a stack
    of one kind of layer has neither; and the router of a block that
    reads its input is scoped where its logits are computed, before
    attention, outside hvd.mlp."""
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("mixed"))
    for inner in scopes.MIXED_PHASES:
        found = [p for p in paths
                 if inner in re.sub(r"[()]", "/", p).split("/")]
        assert found, inner
        for path in found:
            assert scopes.ATTENTION_CORE in re.sub(
                r"[()]", "/", path).split("/"), path
    assert not any(name in _compiled_text(model)
                   for name in scopes.MIXED_PHASES
                   for model in ("flagship", "moe", "looped"))
    router = [p for p in paths if scopes.MOE_ROUTER in p and "dot_general"
              in p]
    assert router and any(scopes.MLP not in p for p in router), router


def test_the_loop_nests_in_layers_and_its_gate_in_head():
    """hvd.loop inside hvd.layers and hvd.loop.gate inside hvd.head: the
    five kinds and the phase metrics of the other steps read a looped step
    unchanged; a step that does not loop has neither."""
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("looped"))
    for inner, outer in ((scopes.LOOP, scopes.LAYERS),
                         (scopes.LOOP_GATE, scopes.HEAD)):
        found = [p for p in paths
                 if inner in re.sub(r"[()]", "/", p).split("/")]
        assert found, inner
        for path in found:
            assert outer in re.sub(r"[()]", "/", path).split("/"), path
    assert not any(name in _compiled_text("flagship")
                   for name in scopes.LOOP_PHASES)


def test_the_expert_layer_s_phases_nest_in_moe_inside_mlp():
    """hvd.moe.* inside hvd.moe inside hvd.mlp: the five kinds and the
    phase metrics of the dense steps read an MoE step unchanged."""
    paths = re.findall(r'op_name="([^"]*)"', _compiled_text("moe"))
    inner = [p for p in paths if any(
        f"/{name}/" in p or p.endswith("/" + name)
        for name in scopes.MOE_PHASES[1:])]
    assert inner
    for path in inner:
        assert scopes.MOE in re.sub(r"[()]", "/", path).split("/"), path
    for path in paths:
        if scopes.MOE + "/" in path or scopes.MOE + ")" in path:
            assert scopes.MLP in path, path
    assert not any(name in _compiled_text("flagship")
                   for name in scopes.MOE_PHASES)


@pytest.mark.parametrize("model", ["bert", "flagship"])
def test_the_update_is_under_the_optimizer_scope(model):
    text = _compiled_text(model)
    assert _directions(text, scopes.OPTIMIZER) == {"fwd"}
    # nothing of the model proper is traced under it
    for path in re.findall(r'op_name="([^"]*)"', text):
        if f"/{scopes.OPTIMIZER}/" in path:
            assert not any(p in path for p in scopes.MODEL_PHASES), path


def test_the_flagship_gradient_psum_is_under_grad_sync():
    text = _compiled_text("flagship.dp2")
    assert _directions(text, scopes.GRAD_SYNC) == {"fwd"}
    synced = [line for line in text.splitlines()
              if " all-reduce(" in line and scopes.GRAD_SYNC in line]
    assert synced


def test_a_phase_is_not_found_by_substring():
    text = 'x = f32[] add(a, b), metadata={op_name="jit(f)/jvp(' \
           + scopes.ATTENTION_CORE + ')/add"}'
    assert _directions(text, scopes.ATTENTION_CORE) == {"fwd"}
    assert _directions(text, scopes.ATTENTION) == set()


# -- the reasons: work done again --------------------------------------------

def _parts(path: str) -> list:
    return [re.sub(r"^(?:\w+\()+|\)+$", "", c) for c in path.split("/")]


def test_jax_names_the_second_forward_of_a_checkpointed_block():
    """A scan of ``jax.checkpoint``ed blocks under ``hvd.layers``, as
    ``_scan_periods`` builds one, differentiated and compiled: the second
    forward, and nothing else, carries ``scopes.RECOMPUTED`` in its path.
    A JAX that renames the component fails here, before a reader on the
    chip finds nothing recomputed."""
    def block(x, w):
        with jax.named_scope(scopes.MLP):
            return jnp.tanh(x @ w)

    def loss(ws, x):
        with jax.named_scope(scopes.LAYERS):
            x, _ = jax.lax.scan(
                lambda x, w: (jax.checkpoint(block)(x, w), None), x, ws)
        return jnp.sum(x)
    text = jax.jit(jax.grad(loss)).lower(
        jnp.ones((3, 8, 8)), jnp.ones((4, 8))).compile().as_text()
    paths = [p for p in re.findall(r'op_name="([^"]*)"', text)
             if scopes.MLP in _parts(p)]
    assert all(scopes.LAYERS in _parts(p) for p in paths)
    first = [p for p in paths if "transpose(" not in p]
    again = [p for p in paths if scopes.RECOMPUTED in _parts(p)]
    backward = [p for p in paths if "transpose(" in p and p not in again]
    assert first and again and backward
    assert not any(scopes.RECOMPUTED in p for p in first + backward)
    assert all("transpose(" in p for p in again)
    # what is made again is the forward's tanh and matmul; the backward
    # proper is the matmul's two transposes
    assert any(p.endswith("/tanh") for p in again)
    assert any(p.endswith("/dot_general") for p in again)
    assert any(p.endswith("/dot_general") for p in backward)
    assert not any(p.endswith("/tanh") for p in backward)


def test_the_hybrid_step_s_checkpointed_blocks_are_named_so():
    """The program's own: the Mamba blocks of the hybrid stack are
    checkpointed, and their second forward is every phase of the mixer
    under ``scopes.RECOMPUTED``; the update is not."""
    # (a reduction's own little computation carries the path's tail only)
    paths = re.findall(r'op_name="(jit\([^"]*)"', _compiled_text("hybrid"))
    again = [_parts(p) for p in paths if scopes.RECOMPUTED in _parts(p)]
    for phase in (scopes.SSM_PROJ, scopes.SSM_CONV, scopes.SSM_SCAN,
                  scopes.SSM_NORM):
        assert any(phase in p for p in again), phase
    assert all(scopes.LAYERS in p for p in again)
    assert not any(scopes.OPTIMIZER in p for p in again)


def test_the_held_experts_backward_names_the_hidden_rows_made_again():
    """``_ffn_held_bwd`` makes the hidden rows again from the kept
    products: under ``scopes.RECOMPUTE`` in the lowered text, inside the
    experts' phase, in the backward pass only; the plain expression of a
    layer whose every row is held runs nothing again."""
    from horovod_tpu.parallel import moe
    rng = np.random.RandomState(0)
    rows = jnp.asarray(rng.randn(16, 8), jnp.float32)
    we1, we3 = (jnp.asarray(rng.randn(2, 8, 4), jnp.float32)
                for _ in range(2))
    we2 = jnp.asarray(rng.randn(2, 4, 8), jnp.float32)
    sizes = jnp.asarray([3, 5], jnp.int32)

    def lowered(held):
        def loss(rows, we1, we3, we2):
            with jax.named_scope(scopes.MOE_EXPERTS):
                return jnp.sum(moe.expert_ffn(rows, we1, we3, we2, sizes,
                                              held, jax.nn.silu))
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3))).lower(
            rows, we1, we3, we2).as_text(debug_info=True)
    again = [p for p in re.findall(r'"(jit\([^"]*)"', lowered(jnp.int32(8)))
             if scopes.RECOMPUTE in _parts(p)]
    assert again
    assert all(scopes.MOE_EXPERTS in _parts(p) and "transpose(" in p
               for p in again)
    assert scopes.RECOMPUTE not in lowered(None)


def test_a_scope_is_metadata_only(monkeypatch):
    """With every named_scope taken out the lowered program is the same
    text: a scope changes no jaxpr, hence no compiled code."""
    def lowered():
        step, args = _flagship_step()
        return step.lower(*args).as_text()
    with_scopes = lowered()
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    assert lowered() == with_scopes


# -- the host side: the input path's spans -----------------------------------

def _record_annotations(monkeypatch):
    from horovod_tpu.data import data_loader
    entered = []

    @contextlib.contextmanager
    def annotate(name):
        entered.append(name)
        yield
    monkeypatch.setattr(data_loader, "annotate", annotate)
    return entered


def test_device_prefetch_enters_source_then_place_once_per_batch(
        monkeypatch):
    from horovod_tpu.data.data_loader import device_prefetch
    entered = _record_annotations(monkeypatch)
    out = list(device_prefetch(
        ({"x": np.full((2,), i)} for i in range(5)), buffer_size=2))
    assert [int(b["x"][0]) for b in out] == [0, 1, 2, 3, 4]
    assert entered[:10] == [scopes.INPUT_SOURCE, scopes.INPUT_PLACE] * 5
    # then only the next() calls that find the source exhausted
    assert set(entered[10:]) == {scopes.INPUT_SOURCE}


def test_device_prefetch_surfaces_a_source_error_at_its_position(
        monkeypatch):
    from horovod_tpu.data.data_loader import device_prefetch
    entered = _record_annotations(monkeypatch)

    def source():
        yield np.zeros(2)
        yield np.ones(2)
        raise RuntimeError("decode failed")
    it = device_prefetch(source(), buffer_size=2)
    assert float(next(it)[0]) == 0.0
    assert float(next(it)[0]) == 1.0
    with pytest.raises(RuntimeError, match="decode failed"):
        next(it)
    assert entered.count(scopes.INPUT_PLACE) == 2


def test_annotate_is_a_trace_annotation():
    from horovod_tpu import profiling
    span = profiling.annotate(scopes.INPUT_PLACE)
    assert isinstance(span, jax.profiler.TraceAnnotation)
    with span:      # no profiler session: the host log alone keeps it
        pass
    assert profiling.host_log.records()[-1][0] == scopes.INPUT_PLACE
    assert not hasattr(profiling, "annotate_fn")


# -- the counters: a program's way to the device, split ----------------------

NEW_TOTALS = ("trace_seconds", "lower_seconds", "cache_read_seconds",
              "persistent_cache_hits", "persistent_cache_misses")


def test_compile_totals_split_rises_on_the_first_call_only():
    compile_watch.ensure_installed()
    compile_watch.reset_counts()
    zero = compile_watch.totals()
    assert set(NEW_TOTALS) <= set(zero)
    assert all(zero[k] == 0 for k in zero)

    @jax.jit
    def split_me(x):
        return jnp.tanh(x) * 3

    split_me(jnp.ones(7)).block_until_ready()
    first = compile_watch.totals()
    assert first["trace_seconds"] > 0 and first["lower_seconds"] > 0
    # today's keys count as before: one backend compile, one tracing-cache
    # miss, its seconds
    assert first["compiles"] >= 1 and first["cache_misses"] >= 1
    assert first["seconds_total"] > 0
    split_me(jnp.ones(7)).block_until_ready()
    assert compile_watch.totals() == first


def test_compile_totals_listen_to_each_of_jaxs_compile_events():
    import jax.monitoring
    compile_watch.ensure_installed()
    compile_watch.reset_counts()
    for event, seconds in (
            ("/jax/core/compile/jaxpr_trace_duration", 0.5),
            ("/jax/core/compile/jaxpr_to_mlir_module_duration", 0.25),
            ("/jax/core/compile/backend_compile_duration", 2.0),
            ("/jax/compilation_cache/cache_retrieval_time_sec", 1.5),
            ("/jax/compilation_cache/compile_time_saved_sec", 9.0)):
        jax.monitoring.record_event_duration_secs(event, seconds)
    jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    jax.monitoring.record_event("/jax/compilation_cache/cache_misses")
    totals = compile_watch.totals()
    # the trace and the lowering both ended "now": a union of 0.5 s
    assert totals.pop("trace_lower_cover_seconds") == pytest.approx(
        0.5, abs=0.05)
    assert totals == {
        "compiles": 1, "cache_misses": 0, "seconds_total": 2.0,
        "trace_seconds": 0.5, "lower_seconds": 0.25,
        "cache_read_seconds": 1.5, "persistent_cache_hits": 1,
        "persistent_cache_misses": 2, "kernel_traces": 0,
        "kernel_trace_seconds": 0.0}
    compile_watch.reset_counts()
    assert not any(compile_watch.totals().values())
