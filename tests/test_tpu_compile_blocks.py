"""Blocks of the main path compiled for a described TPU v5e at real widths
(tests/test_tpu_compile_kernels.py's way, and its docstring's caveats): what
the chip's compiler makes of an expert layer, an attention block, the head
and the embedding's gradient around their kernels. Also here: the one
compile-cache rule (``utils/compile_cache``) and the contract that
``chip_smoke.py`` fails without a chip."""

import math
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops import pallas_xent as px
from horovod_tpu.models import transformer
from tpu_compile_cases import (REPO, assert_no_scatter_into_the_table,
                               cell_step, compile_cache_off, described_v5e,
                               sum32)


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


@pytest.fixture(scope="module")
def v5e(topo):
    """Sharding on one device of it."""
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    with compile_cache_off():
        yield


def test_block_attention_stays_on_its_shard_of_a_mesh(topo,
                                                      no_compile_cache):
    """Under GSPMD, batch over dp and heads over tp: each device's kernels
    take its own rows and columns (a bare pallas_call would have q, k and
    v gathered onto every device)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("dp", "tp"))
    x = jax.ShapeDtypeStruct((128, 128, 16, 64), jnp.bfloat16,
                             sharding=NamedSharding(
                                 mesh, P("dp", None, "tp", None)))
    m = jax.ShapeDtypeStruct((128, 128), jnp.bool_,
                             sharding=NamedSharding(mesh, P("dp", None)))
    grad = jax.grad(lambda q, k, v, m: sum32(
        pa.block_attention(q, k, v, m)), (0, 1, 2))
    text = jax.jit(grad).lower(x, x, x, m).compile().as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 2 and all("bf16[64,128,512]" in c for c in calls)
    assert "all-gather" not in text and "all-reduce" not in text
    # the benchmark's reference check on four chips: replicated arrays
    # that do not split; every device runs the whole call
    rep = NamedSharding(Mesh(np.array(topo.devices).reshape(4, 1),
                             ("dp", "tp")), P())
    x = jax.ShapeDtypeStruct((2, 128, 16, 64), jnp.bfloat16, sharding=rep)
    m = jax.ShapeDtypeStruct((2, 128), jnp.bool_, sharding=rep)
    text = jax.jit(grad).lower(x, x, x, m).compile().as_text()
    assert text.count("bf16[2,128,1024]") and pa.BWD_NAME in text


# the LM head of the two flagship cells: activations, the float32 table as
# the parameters hold it (GPT's tied embedding [V, M], transposed; OLMoE's
# lm_head [M, V]) and the labels
HEADS = {
    "gpt-1.3b-widths.s2048": (4096, 2048, 50257, True),
    "olmoe-1b-7b.s4096": (8192, 2048, 50304, False),
}


@pytest.mark.parametrize("cell", sorted(HEADS))
def test_head_touches_the_logits_once(cell, v5e, no_compile_cache,
                                      monkeypatch):
    """The head as ``forward_loss_spmd`` writes it (the table cast to
    bf16, logits matmul, loss, both gradients) compiled for the v5e at a
    cell's shape: between the logits matmul and the two backward matmuls
    stands the kernel alone. No pad, no elementwise sweep over an
    ``[N, V]`` array (the parent had ``pad`` and a ``kLoop``
    ``multiply_convert_fusion``, 2 to 4 ms a step), and the temporaries
    are one ``[N, V]`` bf16 array and ``[N, M]`` ones."""
    n, m, v, tied = HEADS[cell]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def loss(x, table, labels):
        head = table.astype(jnp.bfloat16)
        return px.head_softmax_xent(x, head.T if tied else head,
                                    labels).mean()

    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
            for shape, dtype in (((n, m), jnp.bfloat16),
                                 ((v, m) if tied else (m, v), jnp.float32),
                                 ((n,), jnp.int32))]
    compiled = jax.jit(jax.grad(loss, (0, 1))).lower(*args).compile()
    entry = compiled.as_text().split("ENTRY ", 1)[1].splitlines()[1:]
    # name -> (result type + opcode, operands + attributes) of the entry's
    # instructions; a view of an array (an element of the kernel's result
    # tuple, a bitcast) is the array it views
    parts = {}
    for line in entry:
        name, eq, rest = line.strip().removeprefix("ROOT ").partition(" = ")
        if eq:
            result, _, operands = rest.partition("(%")
            parts[name] = result, "%" + operands
    views = ("get-tuple-element", "bitcast")
    wide = {name for name, (result, _) in parts.items()
            if f"[{n},{v}]" in result}
    touch = {name for name, (result, operands) in parts.items()
             if (name in wide or any(w + "," in operands or w + ")" in operands
                                     for w in wide))
             and not result.endswith(views)}
    kernels = {name for name in touch if "hvd_fused_xent" in name}
    assert len(kernels) == 1 and "custom-call" in parts[min(kernels)][0]
    # what else writes or reads an [N, V] array: the logits matmul and the
    # two backward matmuls (XLA:TPU's convolution fusions are kOutput)
    matmuls = touch - kernels
    assert len(matmuls) == 3, sorted(touch)
    for name in matmuls:
        result, operands = parts[name]
        assert result.endswith(" fusion") and "kind=kOutput" in operands, \
            (name, result)
    assert compiled.memory_analysis().temp_size_in_bytes \
        <= n * v * 2 + 4 * n * m * 4


# -- the forward flash kernel where its operands lie (ISSUE 50) ---------------

#: a checkpointed attention block, forward and backward, at a cell's real
#: widths: (TransformerConfig fields, the block's stack, positions).
#: glm-4.7-flash.s8192's latent block (20 heads of 256: ``flash_vmem_bytes``
#: of its 1024 x 1024 tile is 8.6 MiB of ``VMEM_BUDGET``'s 16) and
#: ouro-2.6b.s4096's plain one (16 heads of 128)
_ATTENTION_BLOCKS = {
    "latent block, 20 heads of 256": (dict(
        d_model=2048, n_heads=20, head_width=256, q_latent=768,
        kv_latent=512, rope_width=64, layer_pattern=(("latent",),)),
        "latent", 8192),
    "plain block, 16 heads of 128": (dict(d_model=2048, n_heads=16),
                                     None, 4096),
}


_block_texts = {}


def _attention_block_text(block, v5e):
    """(the compiled text of the checkpointed block's gradient, S, heads,
    head width); compiled once a block and module."""
    import numpy as np
    fields, stack, S = _ATTENTION_BLOCKS[block]
    cfg = transformer.TransformerConfig(
        n_layers=1, dtype=jnp.bfloat16, max_seq=S, vocab_size=1024, **fields)
    if block in _block_texts:
        return _block_texts[block], S, cfg.n_heads, cfg.head_dim
    layers = jax.eval_shape(lambda: transformer.init_params(
        np.random.RandomState(0), cfg, 1))["layers"]
    params = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape[2:], v.dtype, sharding=v5e),
        layers[stack] if stack else layers)
    h = jax.ShapeDtypeStruct((1, S, cfg.d_model), jnp.bfloat16, sharding=v5e)
    positions = jax.ShapeDtypeStruct((S,), jnp.int32, sharding=v5e)
    apply = (transformer._BLOCK_KINDS[stack].apply if stack else
             lambda p, x, pos, cfg, kind: (
                 transformer._attention_block(p, x, pos, cfg), None))
    kind = (stack,) if stack else transformer._PLAIN_LAYER

    def loss(p, h, positions):
        run = jax.checkpoint(lambda p, h: apply(p, h, positions, cfg,
                                                kind)[0])
        return sum32(jnp.square(run(p, h)))
    _block_texts[block] = jax.jit(jax.grad(loss, (0, 1))).lower(
        params, h, positions).compile().as_text()
    return _block_texts[block], S, cfg.n_heads, cfg.head_dim


@pytest.mark.parametrize("block", sorted(_ATTENTION_BLOCKS))
def test_attention_block_hands_the_forward_kernel_its_operands_in_place(
        block, v5e, no_compile_cache, monkeypatch):
    """``hvd_flash_attention`` reads q, k, v and writes o as ``[1, S, H *
    D]`` in both of a checkpointed block's calls, under the default scoped
    VMEM (no limit is asked for), and the program holds no heads-first
    copy of any of them: no ``[H, S, D]`` array (the parent's ``copy`` and
    ``transpose`` between ``[1, 8192, 20, 256]`` and ``[20, 8192, 256]``),
    so whatever lies beside the call moves ``[1, S, ..]`` arrays only."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, S, H, D = _attention_block_text(block, v5e)
    assert pa.flash_vmem_bytes(*pa.flash_blocks(S, S, D, jnp.bfloat16), D,
                               2) <= pa.VMEM_BUDGET
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and "hvd_flash_attention" in line.split(" = ")[0]]
    assert len(calls) == 2, calls          # the block's, and its recomputation
    where = f"bf16[1,{S},{H * D}]"
    for call in calls:
        result, operands = call.split(" custom-call(")
        operands = operands.split("), custom_call_target")[0]
        assert result.count(where) == 1 and f"f32[{H},1,{S}]" in result, call
        assert operands.count(",") == 2, call
    defined = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (\S+) ", text, re.M))
    for call in calls:
        for operand in re.findall(r"%[\w.\-]+", call.split(
                " custom-call(")[1].split(")")[0]):
            assert defined[operand].startswith(where), (operand, call)
    heads_first = re.findall(rf"\w+\[(?:1,)?{H},{S},{D}\]", text)
    assert not heads_first, sorted(set(heads_first))



@pytest.mark.parametrize("block", sorted(_ATTENTION_BLOCKS))
def test_the_flash_backward_s_row_sums_are_a_kernel_s(
        block, v5e, no_compile_cache, monkeypatch):
    """ISSUE 62: ``adj = sum_d do * o - dlse`` is ``hvd_flash_adj``'s. The
    kernel takes do as the o-projection's backward wrote it and o as the
    (recomputed) forward kernel did, both ``bf16[1, S, H * D]``, and hands
    ``hvd_flash_bwd`` its last operand; beside the three Pallas calls
    nothing of the phase ``hvd.attention.core`` makes a float32 value of
    o's size, in memory or inside a fusion (the parent's ``jax.numpy`` sums
    were a fusion of two ``convert`` and a ``multiply`` of ``f32[1, 8192,
    20, 256]`` in front of each backward call: 0.97 ms a call in
    glm-4.7-flash.s8192 for 0.2 ms of bytes)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, S, H, D = _attention_block_text(block, v5e)
    defined = dict(re.findall(r"^\s*(?:ROOT )?(%\S+) = (.*)$", text, re.M))

    def call(kernel):
        (found,) = [(name, line) for name, line in defined.items()
                    if 'custom_call_target="tpu_custom_call"' in line
                    and name.startswith("%" + kernel + ".")]
        name, line = found
        return name, re.findall(r"%[\w.\-]+", line.split(
            " custom-call(")[1].split(")")[0])
    adj, (do, o, _dlse) = call(pa.ADJ_NAME)
    _bwd, operands = call("hvd_flash_bwd")
    assert operands[-1] == adj and operands[1] == do, operands
    where = f"bf16[1,{S},{H * D}]"
    assert defined[do].startswith(where) and defined[o].startswith(where)
    # o is the forward kernel's own output (XLA's own move of a buffer to
    # on-chip memory and back apart)
    while (moved := re.search(r" copy-(?:done|start)\((%[\w.\-]+)\)",
                              defined[o])):
        o = moved.group(1)
    made_o = re.search(r"get-tuple-element\((%[\w.\-]+)\), index=0",
                       defined[o])
    assert made_o and "hvd_flash_attention" in made_o.group(1), defined[o]
    for line in text.splitlines():
        if "hvd.attention.core" not in line:
            continue
        for dims in re.findall(r" = f32\[([\d,]+)\]", line):
            assert math.prod(map(int, dims.split(","))) < S * H * D, line


@pytest.mark.parametrize("cell", ["gpt-1.3b-widths.s2048",
                                  "olmoe-1b-7b.s4096"])
def test_the_block_s_new_fields_leave_the_flagship_cells_alone(
        cell, topo, no_compile_cache, monkeypatch):
    """``n_loops``, ``post_norm``, ``ffn_gated`` at their defaults and
    ``remat=None`` on the single scan: the step the cell lowers is, to the
    letter, the one with every new field spelled out and no checkpoint, so
    it compiles to the same program and the same bytes."""
    import dataclasses
    from horovod_tpu.models import transformer
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, _shapes, _bytes = cell_step(cell, topo)
    lowered = step.lower(*args).as_text()
    real = transformer.TransformerConfig
    monkeypatch.setattr(
        transformer, "TransformerConfig",
        lambda **kw: dataclasses.replace(
            real(**kw), n_loops=1, post_norm=False, ffn_gated=False,
            remat=False))
    spelled_out, args, _shapes, _bytes = cell_step(cell, topo)
    assert spelled_out.lower(*args).as_text() == lowered


# -- the embedding's gradient (ISSUE 38) --------------------------------------

def test_untied_embedding_gradient_scatters_nothing_into_the_table(
        v5e, no_compile_cache):
    """The lookup and its gradient at the share cell's table (37 984 rows
    of 2560, 8192 tokens), a head of its own: no ``scatter`` has the table
    for its result (on a v5e that scatter of 8192 rows is 15 ms at this
    width, bf16 or float32; the float32 sums of the sorted ids' runs,
    gathered, are under 3: PERF.md §6, PR 38) and no bf16 copy of the
    table exists. The sums are added in float32. Cast the table before the lookup again, or drop the
    hand-written gradient, and this fails."""
    from horovod_tpu.models.transformer import (TransformerConfig,
                                                _embed_lookup)
    from horovod_tpu.profiling import scopes
    vocab, width, tokens = 37984, 2560, 8192
    cfg = TransformerConfig(vocab_size=vocab, d_model=width, n_heads=20,
                            n_layers=1, d_ff=width, max_seq=tokens,
                            dtype=jnp.bfloat16, tie_embeddings=False)

    def gradient(table, ids, cotangent):
        with jax.named_scope(scopes.EMBED):
            rows, back = jax.vjp(lambda e: _embed_lookup(e, ids, cfg), table)
        return rows, back(cotangent)[0]

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e)
    text = jax.jit(gradient).lower(
        spec((vocab, width), jnp.float32), spec((1, tokens), jnp.int32),
        spec((1, tokens, width), jnp.bfloat16)).compile().as_text()
    assert_no_scatter_into_the_table(text, vocab, width)
    sums = re.findall(r"= (\w+)\[\d+,\d+\]\S* scatter\(", text)
    assert sums and set(sums) <= {"f32", "s32"}, sums


@pytest.fixture
def cache_dir_updates(monkeypatch):
    """Record, without applying, what compile_cache.enable() would set."""
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    return updates


def test_compile_cache_env_set_sets_no_dir_in_code(cache_dir_updates,
                                                   monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    assert compile_cache.enable() is None
    assert cache_dir_updates == []


def test_compile_cache_env_unset_is_checkout_local(cache_dir_updates,
                                                   monkeypatch):
    from horovod_tpu.utils import compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable() == want
    assert cache_dir_updates == [("jax_compilation_cache_dir", want)]


def test_chip_smoke_fails_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert proc.returncode != 0, proc.stdout
    assert '"ok"' not in proc.stdout, proc.stdout
    assert "tpu" in proc.stderr.lower(), proc.stderr

