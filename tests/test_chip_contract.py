"""The contract between the product and the one client that measures it.

``benchmarks/chip/`` is how this repo is measured (``BENCHMARK.json``), and
tier-1 does not run it: a rename of anything it calls, or of a name it looks
for inside the program, would otherwise be found on the chip, as a cell that
fails.  The cases are collected from the benchmark's own source, so the list
cannot go stale:

* every ``(module, name)`` that ``run.py`` and the adapters import from
  ``horovod_tpu`` or reach through such an import (``hvd.init``,
  ``compile_watch.totals``) must resolve, and accept the positional count
  and the keywords of every call site (``scan_steps=1``, ``buffer_size=``);
* every kernel name, phase and host span that a metric file
  (``layer_metrics/``), a reader (``readers/``) or ``scope_reduce.py`` looks
  for must be a name the product gives: a kernel's ``name=`` / ``*_NAME`` in
  ``horovod_tpu/ops`` or ``parallel/moe.py``, a constant of
  ``profiling/scopes.py``;
* the door between ``BENCHMARK.json``'s ``per_layer`` entries, the files of
  ``layer_metrics/`` and ``run.read_layer_metric``, in the benchmark's own
  cases, which tier-1 takes a module at a time (``tests/chip_door.py``):
  ``tests/test_layer_metrics.py`` (every entry has its file, file and entry
  agree, every ``read`` dispatches) and ``tests/test_metric_lists.py``
  (every (metric, cell) pair finds in the cell's program what it reads).
  Here: a ``reader`` names a file under ``readers/`` whose imports from the
  product resolve like ``run.py``'s, and, for the cell of each adapter
  below, every metric that lists the cell reads a phase the program gives,
  a kernel its gate takes at the cell's shapes and a roofline function the
  harness finds (``chip_door.readable``: by what ``BENCHMARK.json`` says,
  no name, count or place of an entry restated);
* the reader that gives every device instruction one owner and one reason
  (``readers/step_owners.py``), by its own cases
  (``benchmarks/chip/tests/test_step_owners.py``, imported the same way):
  it reads the program's path components by name (``rematted_computation``,
  ``hvd.recompute``, every ``hvd.*`` phase a metric's ``read`` asks for).

* what an adapter reads by a string the walk cannot see: the ``ouro``
  adapter's leaves of ``init_params``' tree and the keys of the looped
  step's fourth output (traced at the ``tiny`` sizes, not compiled).

CPU only, nothing is compiled.
"""

import ast
import glob
import importlib
import inspect
import json
import os
import re

import pytest

import chip_door
from chip_door import CHIP, REPO

CLIENT_FILES = [os.path.join(CHIP, "run.py")] + sorted(
    glob.glob(os.path.join(CHIP, "adapters", "*.py"))
    + glob.glob(os.path.join(CHIP, "readers", "*.py")))
KERNEL_FILES = [os.path.join(REPO, "horovod_tpu", *p) for p in (
    ("ops", "pallas_attention.py"), ("ops", "pallas_sparse_attention.py"),
    ("ops", "pallas_xent.py"),
    ("ops", "pallas_ssm.py"), ("ops", "pallas_delta.py"),
    ("parallel", "moe.py"))]
#: read by name in a run's ``breakdown`` (PERF.md §3) until a metric file
#: names them
BLOCK_KERNELS = ("hvd_block_attention", "hvd_block_attention_bwd")


def _parse(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def _dotted(node, aliases):
    """``alias.attr`` -> ``(what alias stands for, attr)``; ``name`` imported
    from the product -> its ``(module, name)``."""
    if isinstance(node, ast.Attribute) and isinstance(node.value, ast.Name) \
            and node.value.id in aliases:
        return ".".join(aliases[node.value.id]), node.attr
    if isinstance(node, ast.Name) and node.id in aliases \
            and len(aliases[node.id]) == 2:
        return aliases[node.id]
    return None


def _imports():
    """{(module, name): [(file:line, n positional, keywords), ...]}: what
    the client files take from ``horovod_tpu``, with every call's shape."""
    uses = {}
    for path in CLIENT_FILES:
        tree, rel = _parse(path), os.path.relpath(path, REPO)
        aliases = {}      # local name -> (module,) or (module, name)
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name.split(".")[0] == "horovod_tpu":
                        aliases[a.asname or a.name] = (a.name,)
            elif isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.split(".")[0] == "horovod_tpu":
                for a in node.names:
                    aliases[a.asname or a.name] = (node.module, a.name)
                    uses.setdefault((node.module, a.name), [])
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and _dotted(node, aliases):
                uses.setdefault(_dotted(node, aliases), [])
            elif isinstance(node, ast.Call) and _dotted(node.func, aliases):
                uses.setdefault(_dotted(node.func, aliases), []).append((
                    f"{rel}:{node.lineno}",
                    sum(not isinstance(a, ast.Starred) for a in node.args),
                    [k.arg for k in node.keywords if k.arg]))
    return uses


IMPORTS = _imports()


def _resolve(module, name):
    try:
        return getattr(importlib.import_module(module), name)
    except AttributeError:
        return importlib.import_module(f"{module}.{name}")


@pytest.mark.parametrize("module,name", sorted(IMPORTS),
                         ids=lambda v: v.replace("horovod_tpu", "hvd"))
def test_what_the_benchmark_imports_resolves(module, name):
    obj = _resolve(module, name)
    for where, n_positional, keywords in IMPORTS[(module, name)]:
        try:
            inspect.signature(obj).bind_partial(
                *[None] * n_positional, **dict.fromkeys(keywords))
        except TypeError as e:
            pytest.fail(f"{where} calls {module}.{name} with "
                        f"{n_positional} positional and {keywords}: {e}")


def test_the_collection_sees_the_step_factories():
    """The walk above is only worth its cases if it finds the calls: the
    two it exists for, with the keyword that pins ``scan_steps``."""
    bert = IMPORTS[("horovod_tpu.models.bert", "make_bert_train_step")]
    assert bert and all("scan_steps" in kw for _w, _n, kw in bert), bert
    assert IMPORTS[("horovod_tpu.models.transformer", "make_train_step")]
    assert IMPORTS[("horovod_tpu", "init")]
    prefetch = IMPORTS[("horovod_tpu.data.data_loader", "device_prefetch")]
    assert any("buffer_size" in kw for _w, _n, kw in prefetch), prefetch


def _names_looked_for():
    """[(kind, name, where)]: kernel names, phases and host spans the
    benchmark's metric files and ``scope_reduce.py`` look for."""
    found = {}
    for path in sorted(glob.glob(os.path.join(CHIP, "layer_metrics",
                                              "*.json"))):
        with open(path) as f:
            read = json.load(f)["read"]
        rel = os.path.relpath(path, CHIP)
        ops = read.get("trace_ops")
        # (a name that another kernel's starts with ends in a lookahead
        # that leaves the other out: ``hvd_delta_scan(?!_bwd)``)
        ops = re.sub(r"\(\?!\w+\)$", "", ops) if isinstance(ops, str) else ops
        if isinstance(ops, str) and re.fullmatch(r"hvd_\w+", ops):
            found.setdefault(("kernel", ops), rel)
        # a cover's phase, and the phase of an owner (readers/step_owners.py)
        for phase in ((read.get("trace_scope") or {}).get("phase"),
                      read.get("phase") if "reader" in read else None):
            if phase and phase.startswith("hvd."):
                found.setdefault(("phase", phase), rel)
        if read.get("host_span"):
            found.setdefault(("span", read["host_span"]), rel)
    for node in ast.walk(_parse(os.path.join(CHIP, "scope_reduce.py"))):
        if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                and re.fullmatch(r"hvd(\.[a-z_]+)+", node.value):
            found.setdefault(("phase", node.value), "scope_reduce.py")
    for path in sorted(glob.glob(os.path.join(CHIP, "readers", "*.py"))):
        for node in ast.walk(_parse(path)):
            if isinstance(node, ast.Constant) and isinstance(node.value, str) \
                    and re.fullmatch(r"hvd(\.[a-z_]+)+", node.value):
                # a reader looks for host spans or for device phases
                found.setdefault(("name", node.value),
                                 os.path.relpath(path, CHIP))
    for name in BLOCK_KERNELS:
        found.setdefault(("kernel", name), "PERF.md §3")
    return [(kind, name, where) for (kind, name), where in sorted(
        found.items())]


def _kernel_names():
    """Every ``name="hvd_..."`` keyword and ``*_NAME = "hvd_..."`` constant
    in the files that hold the kernels."""
    names = set()
    for path in KERNEL_FILES:
        for node in ast.walk(_parse(path)):
            value = None
            if isinstance(node, ast.keyword) and node.arg == "name":
                value = node.value
            elif isinstance(node, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id.endswith("_NAME")
                    for t in node.targets):
                value = node.value
            if isinstance(value, ast.Constant) \
                    and isinstance(value.value, str):
                names.add(value.value)
    return names


LOOKED_FOR = _names_looked_for()
KERNEL_NAMES = _kernel_names()


@pytest.mark.parametrize("kind,name,where", LOOKED_FOR,
                         ids=[name for _kind, name, _where in LOOKED_FOR])
def test_what_the_benchmark_looks_for_is_what_the_program_says(
        kind, name, where):
    from horovod_tpu.profiling import scopes
    said = {"kernel": KERNEL_NAMES, "phase": scopes.DEVICE_PHASES,
            "span": scopes.HOST_SPANS,
            "name": scopes.DEVICE_PHASES + scopes.HOST_SPANS}[kind]
    assert name in said, (
        f"benchmarks/chip/{where} looks for the {kind} {name!r}; the "
        f"product has {sorted(said)}")


# -- the door: the readers ----------------------------------------------------
# (per_layer's entries, their files and the harness's dispatch are
# tests/test_layer_metrics.py's, the benchmark's own cases by module.)

_DOOR = chip_door.benchmarks_own("test_layer_metrics")

# The owner-and-reason reader's own cases, taken by import and not restated
# (its module fixture with them).
_OWNERS = chip_door.benchmarks_own("test_step_owners")
recorded = _OWNERS.recorded
for _name in dir(_OWNERS):
    if _name.startswith("test_"):
        globals()[_name.replace("test_", "test_step_owners_", 1)] = \
            getattr(_OWNERS, _name)


def test_the_reader_s_words_are_the_program_s():
    """``rematted_computation`` and ``hvd.recompute`` as
    ``profiling/scopes.py`` writes them, not the reader's fallback for a
    program from before them."""
    from horovod_tpu.profiling import scopes
    assert (_OWNERS.so.RECOMPUTE, _OWNERS.so.RECOMPUTED) == (
        scopes.RECOMPUTE, scopes.RECOMPUTED)
    with open(os.path.join(CHIP, "readers", "step_owners.py")) as f:
        text = f.read()
    assert f'"{scopes.RECOMPUTE}", "{scopes.RECOMPUTED}"' in text


@pytest.mark.parametrize("path", sorted(
    glob.glob(os.path.join(CHIP, "readers", "*.py"))), ids=os.path.basename)
def test_a_reader_is_a_file_with_a_read_that_some_metric_names(path):
    assert any(isinstance(n, ast.FunctionDef) and n.name == "read"
               for n in _parse(path).body), path
    name = os.path.basename(path)[:-len(".py")]
    assert any(_DOOR._spec(m)["read"].get("reader") == name
               for m in _DOOR.FILES), f"no metric reads readers/{name}.py"


def test_the_looped_step_gives_what_the_ouro_adapter_reads():
    """``adapters/ouro.py`` names leaves of the parameter tree
    (``_leaf_paths``, ``_init_function``) and reads ``exit_share`` from the
    step's fourth output; the configuration's fields reach
    ``TransformerConfig`` as ``n_loops``, ``post_norm``, ``ffn_gated``."""
    import jax
    import numpy as np
    from adapters import ouro
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    with open(os.path.join(CHIP, "configs", "ouro-2.6b.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads", "train.s4096.b1.json")) as f:
        job = json.load(f)
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = ouro._model_config(config, job)
    assert (cfg.n_loops, cfg.post_norm, cfg.ffn_gated) == (
        config["total_ut_steps"], True, True)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert {"exit_gate", "exit_gate_bias", "lm_head"} <= set(params)
    assert {"ln1_post", "ln2_post", "w3"} <= set(params["layers"])
    assert set(get_leaves(params, ouro._leaf_paths(cfg.n_layers))) == {
        "lm_head", "exit_gate", "first_query", "last_ffn_down",
        "last_post_norm"}
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = ouro.host_batch(config, job, 0, 0, 1)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "step_losses", "exit_share",
                        "gate_entropy"}
    assert aux["exit_share"].shape == (cfg.n_loops,)


def test_the_mixed_step_gives_what_the_smallthinker_adapter_reads():
    """``adapters/smallthinker.py`` names leaves of the parameter tree
    (``_leaf_paths``, ``_init_function``), reads ``held_rows`` and
    ``dropped`` from the step's fourth output and ``router_choices``; the
    configuration's fields reach ``TransformerConfig`` by keyword as
    ``head_width``, ``n_kv_heads``, ``layer_pattern``, ``moe_router_input``,
    ``moe_activation``, ``expert_share``; the two phase files look for the
    scopes of the layer kinds."""
    import jax
    import numpy as np
    from adapters import smallthinker
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(smallthinker, name)), name
    assert {"program_choices", "program_loss_and_grads", "compiled_step",
            "step"} <= set(dir(smallthinker.Cell))
    with open(os.path.join(CHIP, "configs", "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads", "train.s8192.b1.json")) as f:
        job = json.load(f)
    full = smallthinker._model_config(config, job)
    assert (full.head_width, full.n_kv_heads, full.n_heads, full.d_model,
            full.n_experts, full.held_experts, full.moe_top_k) == (
                128, 4, 28, 2560, 64, 16, 6)
    assert full.layer_pattern == ((None, False),) + ((4096, True),) * 3
    assert (full.moe_router_input, full.moe_activation, full.expert_share) \
        == ("block_input", "relu", (0, 4))
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = smallthinker._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    layers = params["layers"]
    assert layers["wq"].shape[-1] == cfg.n_heads * cfg.head_dim
    assert layers["wk"].shape[-1] == cfg.kv_heads * cfg.head_dim
    assert layers["we1"].shape[2] == cfg.held_experts
    assert layers["router"].shape[-1] == cfg.n_experts
    ours = jax.eval_shape(
        smallthinker._init_function(cfg, config["assumed"]["embedding_std"]),
        jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    sizes = smallthinker.shapes(config, job)
    assert set(get_leaves(params, smallthinker._leaf_paths(
        sizes["layer_windows"]))) == {
            "lm_head", "first_query", "window_key", "last_router",
            "last_experts_down"}
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = smallthinker.host_batch(config, job, 0, 0, 1)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    choices = jax.eval_shape(
        lambda p, tok: t.router_choices(p, tok, cfg), params,
        batch["tokens"])
    assert choices.shape == (cfg.n_layers, batch["tokens"].size,
                             cfg.moe_top_k)
    assert (scopes.ATTENTION_CORE_WINDOW, scopes.ATTENTION_CORE_FULL) == (
        "hvd.attention.core.window", "hvd.attention.core.full")


def test_the_hybrid_step_gives_what_the_nemotron_h_adapter_reads():
    """``adapters/nemotron_h.py`` names leaves of the by-word parameter tree
    (``_leaf_paths``, ``_init_function``), reads ``held_rows`` and
    ``dropped`` from the step's fourth output and ``router_choices``; the
    configuration's fields reach ``TransformerConfig`` by keyword as
    ``layer_pattern`` words, ``ssm_*``, ``moe_router_scores``,
    ``moe_routed_scale``, ``moe_shared_width``, ``moe_activation``; the
    phase files look for the mixer's and the shared expert's scopes, the
    roofline functions for the adapter's ``shapes()`` keys."""
    import jax
    import numpy as np
    from adapters import nemotron_h
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(nemotron_h, name)), name
    assert {"program_choices", "program_loss_and_grads", "compiled_step",
            "step"} <= set(dir(nemotron_h.Cell))
    with open(os.path.join(CHIP, "configs",
                           "nemotron-3-nano-30b-a3b.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           "train.s8192.b1.hybrid.json")) as f:
        job = json.load(f)
    full = nemotron_h._model_config(config, job)
    assert full.layer_pattern == (("mamba",), ("experts",)) * 3 + (
        ("mamba",), ("attention", None, False), ("experts",))
    assert (full.ssm_heads, full.ssm_head_dim, full.ssm_state,
            full.ssm_groups, full.ssm_conv, full.ssm_chunk) == (
                64, 64, 128, 8, 4, 128)
    assert (full.moe_router_scores, full.moe_routed_scale,
            full.moe_shared_width, full.moe_activation, full.moe_gated,
            full.expert_share, full.held_experts) == (
                "sigmoid", 2.5, 3712, "relu2", False, (0, 16), 8)
    phases, rooflined = chip_door.readable(
        "nemotron-3-nano-30b-a3b.s8192", nemotron_h.shapes(config, job))
    assert set(scopes.HYBRID_PHASES) <= phases
    assert {"hvd_moe_gmm", "hvd_flash_attention", "hvd_flash_bwd",
            "hvd_ssm_scan"} <= rooflined
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = nemotron_h._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert set(params["layers"]) == {"mamba", "experts", "attention"}
    assert set(params["layers"]["mamba"]) == {
        "ln1", "ssm_in", "ssm_conv_w", "ssm_conv_b", "ssm_dt_bias",
        "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out"}
    assert set(params["layers"]["experts"]) == {
        "ln2", "router", "router_bias", "we1", "we2", "ws1", "ws2"}
    assert set(params["layers"]["attention"]) == {"ln1", "wq", "wk", "wv",
                                                  "wo"}
    assert params["layers"]["mamba"]["ssm_in"].shape == (
        1, 8, cfg.d_model,
        2 * cfg.ssm_inner + 2 * cfg.ssm_groups * cfg.ssm_state
        + cfg.ssm_heads)
    ours = jax.eval_shape(nemotron_h._init_function(cfg, config),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    assert set(get_leaves(params, nemotron_h._leaf_paths(
        config["hybrid_override_pattern"]))) == {
            "lm_head", "first_ssm_in", "last_ssm_a_log", "attention_key",
            "last_router", "last_experts_down", "last_shared_down"}
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = nemotron_h.host_batch(config, job, 0, 0, 1)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    choices = jax.eval_shape(
        lambda p, tok: t.router_choices(p, tok, cfg), params,
        batch["tokens"])
    assert choices.shape == (
        config["hybrid_override_pattern"].count("E"), batch["tokens"].size,
        cfg.moe_top_k)
    assert scopes.HYBRID_PHASES == (
        "hvd.moe.shared", "hvd.ssm", "hvd.ssm.proj", "hvd.ssm.conv",
        "hvd.ssm.scan", "hvd.ssm.norm")


def test_the_latent_step_gives_what_the_glm4_moe_lite_adapter_reads():
    """``adapters/glm4_moe_lite.py`` names leaves of ``lead``, ``layers``
    and ``mtp`` (``_leaf_paths``, ``_init_function``), reads ``held_rows``,
    ``dropped``, ``main_loss`` and ``mtp_loss`` from the step's fourth
    output and ``router_choices``; the configuration's fields reach
    ``TransformerConfig`` by keyword as ``q_latent``, ``kv_latent``,
    ``rope_width``, ``lead_pattern``, ``dense_ff``, ``mtp_depth``,
    ``mtp_weight``; the phase files look for the latent projections' and
    the prediction module's scopes, the roofline functions for the
    adapter's ``shapes()`` keys."""
    import jax
    import numpy as np
    from adapters import glm4_moe_lite
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(glm4_moe_lite, name)), name
    assert {"program_choices", "program_loss_and_grads", "compiled_step",
            "step"} <= set(dir(glm4_moe_lite.Cell))
    with open(os.path.join(CHIP, "configs", "glm-4.7-flash.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           "train.s8192.b1.latent.json")) as f:
        job = json.load(f)
    full = glm4_moe_lite._model_config(config, job)
    assert (full.layer_pattern, full.lead_pattern) == (
        (("latent",), ("experts",)), (("latent",), ("dense",)))
    assert (full.q_latent, full.kv_latent, full.rope_width, full.head_dim,
            full.dense_ff, full.mtp_depth, full.mtp_weight,
            full.moe_shared_width, full.moe_gated, full.expert_share,
            full.held_experts) == (
                768, 512, 64, 256, 10240, 1, 0.3, 1536, True, (0, 8), 8)
    phases, rooflined = chip_door.readable(
        "glm-4.7-flash.s8192", glm4_moe_lite.shapes(config, job))
    assert set(scopes.LATENT_PHASES) <= phases
    assert {"hvd_moe_gmm", "hvd_flash_attention", "hvd_flash_bwd",
            "hvd_fused_xent"} <= rooflined
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = glm4_moe_lite._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert set(params) == {"embed", "ln_f", "lm_head", "layers", "lead",
                           "mtp"}
    assert set(params["layers"]) == set(params["mtp"]["layers"]) == {
        "latent", "experts"}
    assert set(params["lead"]) == {"latent", "dense"}
    assert set(params["mtp"]) == {"norm_h", "norm_e", "proj", "ln_f",
                                  "layers"}
    assert set(params["lead"]["latent"]) == {
        "ln1", "wqa", "q_latent_norm", "wqb", "wkva", "kv_latent_norm",
        "wkvb", "wo"}
    assert set(params["lead"]["dense"]) == {"ln2", "w1", "w2", "w3"}
    assert set(params["layers"]["experts"]) == {
        "ln2", "router", "router_bias", "we1", "we2", "we3", "ws1", "ws2",
        "ws3"}
    assert params["layers"]["latent"]["wkvb"].shape == (
        1, 2, cfg.kv_latent,
        cfg.n_heads * (2 * cfg.head_dim - cfg.rope_width))
    assert params["lead"]["latent"]["wkva"].shape == (
        1, cfg.d_model, cfg.kv_latent + cfg.rope_width)
    ours = jax.eval_shape(glm4_moe_lite._init_function(cfg, config),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    assert set(get_leaves(params, glm4_moe_lite._leaf_paths(2))) == {
        "lm_head", "first_query_down", "last_kv_down", "last_kv_up",
        "dense_down", "last_router", "last_experts_down", "mtp_proj"}
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = glm4_moe_lite.host_batch(config, job, 0, 0, 1)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows",
                        "main_loss", "mtp_loss"}
    choices = jax.eval_shape(
        lambda p, tok: t.router_choices(p, tok, cfg), params,
        batch["tokens"])
    assert choices.shape == (2, batch["tokens"].size, cfg.moe_top_k)
    assert scopes.LATENT_PHASES == (
        "hvd.attention.latent", "hvd.attention.latent.down",
        "hvd.attention.latent.up", "hvd.mtp", "hvd.mtp.proj")


def test_the_banded_step_gives_what_the_laguna_adapter_reads():
    """``adapters/laguna.py`` names the stacks of ``lead`` and ``layers`` by
    their attention shapes (``_stack``, ``_places``, ``_leaf_paths``,
    ``_init_function``), reads ``held_rows``, ``max_expert_load`` and
    ``dropped`` from the step's fourth output and ``router_choices``; the
    configuration's fields reach ``TransformerConfig`` as kinds
    ``("attention", window, Rope, heads, gated)`` of ``layer_pattern`` and
    ``lead_pattern``; the phase files look for the gate's scope, the roofline
    functions for the adapter's ``shapes()`` keys."""
    import jax
    import numpy as np
    from adapters import laguna
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.models._kinds import Rope, Yarn
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(laguna, name)), name
    assert {"program_choices", "program_loss_and_grads", "compiled_step",
            "step"} <= set(dir(laguna.Cell))
    with open(os.path.join(CHIP, "configs", "laguna-xs.2.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           "train.s8192.b1.banded.json")) as f:
        job = json.load(f)
    full = laguna._model_config(config, job)
    window_kind = ("attention", 512, Rope(10000.0), 64, True)
    full_kind = ("attention", None, Rope(500000.0, 64, Yarn(
        64.0, 4096, 64.0, 1.0, 1.4158883083359672)), 48, True)
    assert full.layer_pattern == (window_kind, ("experts",)) * 3 + (
        full_kind, ("experts",))
    assert full.lead_pattern == (full_kind, ("dense",))
    assert [t._stack_of(k) for k in (window_kind, full_kind)] == [
        "attention_64_gated", "attention_48_gated"] == [
            laguna._stack({"heads": h}, True) for h in (64, 48)]
    assert (full.head_dim, full.kv_heads, full.dense_ff, full.d_ff,
            full.moe_shared_width, full.moe_gated, full.expert_share,
            full.held_experts, full.moe_routed_scale, full.remat) == (
                128, 8, 8192, 512, 512, True, (0, 8), 32, 2.5,
                config["assumed"]["checkpoint_every_block"] or None)
    sizes = laguna.shapes(config, job)
    phases, rooflined = chip_door.readable("laguna-xs.2.s8192", sizes)
    assert {*scopes.GATED_PHASES, scopes.ATTENTION_CORE_WINDOW,
            scopes.ATTENTION_CORE_FULL} <= phases
    assert {"hvd_flash_attention", "hvd_flash_bwd", "hvd_moe_gmm",
            "hvd_fused_xent"} <= rooflined
    assert {"layer_heads", "layer_windows", "kv_heads", "held_experts",
            "first_expert", "d_expert", "dense_ff"} <= set(sizes)
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = laguna._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert set(params) == {"embed", "ln_f", "lm_head", "layers", "lead"}
    assert set(params["layers"]) == {"attention_8_gated",
                                     "attention_6_gated", "experts"}
    assert set(params["lead"]) == {"attention_6_gated", "dense"}
    assert set(params["lead"]["attention_6_gated"]) == {
        "ln1", "wq", "wk", "wv", "wo", "wg"}
    assert set(params["layers"]["experts"]) == {
        "ln2", "router", "router_bias", "we1", "we2", "we3", "ws1", "ws2",
        "ws3"}
    ours = jax.eval_shape(laguna._init_function(cfg, config),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    leaves = get_leaves(params, laguna._leaf_paths(config))
    assert set(leaves) == {
        "lm_head", "first_query", "window_key", "window_gate",
        "last_full_query", "dense_down", "last_router",
        "last_experts_down"}
    assert leaves["window_gate"].shape == (cfg.d_model, 8)
    assert leaves["last_full_query"].shape == (cfg.d_model, 6 * 16)
    # every layer's two blocks are where the adapter says the reference
    # finds them
    for (path, index), _ffn in laguna._places(config):
        assert params[path[0]][path[1]]["wq"][index].ndim == 2
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = laguna.host_batch(config, job, 0, 0, 1)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    choices = jax.eval_shape(
        lambda p, tok: t.router_choices(p, tok, cfg), params,
        batch["tokens"])
    assert choices.shape == (8, batch["tokens"].size, cfg.moe_top_k)
    assert scopes.GATED_PHASES == ("hvd.attention.gate",)


def test_the_short_conv_step_gives_what_the_lfm2_moe_adapter_reads():
    """``adapters/lfm2_moe.py`` names the stacks of ``lead`` and ``layers``
    by their words (``_places``, ``_leaf_paths``, ``_init_function``), reads
    ``held_rows``, ``max_expert_load`` and ``dropped`` from the step's
    fourth output and ``router_choices``; the configuration's fields reach
    ``TransformerConfig`` as the kind ``("conv",)``, ``conv_taps`` and
    ``qk_norm="head"``; the metrics that list the cell look for the mixer's
    three scopes, the expert layer's and ``hvd_moe_gmm``, its roofline
    function for the adapter's ``shapes()`` keys."""
    import jax
    import numpy as np
    from adapters import lfm2_moe
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(lfm2_moe, name)), name
    assert {"program_choices", "program_loss_and_grads", "compiled_step",
            "step"} <= set(dir(lfm2_moe.Cell))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name, cell = "lfm2-24b-a2b", "lfm2-24b-a2b.s8192"
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    listed = next(c for c in bench["configs"] if c["name"] == name)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        name, "train.s8192.b2", 1)
    assert listed["file"] == f"benchmarks/chip/configs/{name}.json"
    with open(os.path.join(REPO, listed["file"])) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           entry["traffic"] + ".json")) as f:
        job = json.load(f)
    assert config["source"] == listed["source"]
    assert config["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size"]
    assert (job["seq_len"], job["batch_per_chip"], job["prefetch"],
            job["max_ahead"], job["warmup_steps"], job["trace_steps"],
            job["mesh"], job["optimizer"]) == (
                8192, 2, 2, 2, 10, 10, {"dp": -1},
                {"name": "adamw", "learning_rate": 0.0001})
    full = lfm2_moe._model_config(config, job)
    assert full.layer_pattern == (("attention", None, True), ("experts",)) \
        + (("conv",), ("experts",)) * 3
    assert full.lead_pattern == (("conv",), ("dense",))
    assert (full.head_dim, full.kv_heads, full.dense_ff, full.d_ff,
            full.conv_taps, full.qk_norm, full.moe_shared_width,
            full.expert_share, full.held_experts, full.tie_embeddings,
            full.remat) == (
                64, 8, 11776, 1536, 3, "head", 0, (0, 8), 8, True,
                config["assumed"]["checkpoint_every_block"] or None)
    sizes = lfm2_moe.shapes(config, job)
    phases, rooflined = chip_door.readable(cell, sizes)
    assert {scopes.SHORT_CONV, scopes.SHORT_CONV_PROJ,
            scopes.SHORT_CONV_GATE, scopes.MOE} <= phases
    assert "hvd_moe_gmm" in rooflined
    assert {"layer_types", "layer_dense", "layer_windows", "kv_heads",
            "held_experts", "first_expert", "d_expert", "dense_ff",
            "routed_layers", "head_calls", "conv_taps"} <= set(sizes)
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = lfm2_moe._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert set(params) == {"embed", "ln_f", "layers", "lead"}
    assert set(params["layers"]) == {"attention", "conv", "experts"}
    assert set(params["lead"]) == {"conv", "dense"}
    assert set(params["lead"]["conv"]) == {"ln1", "conv_in", "conv_w",
                                           "conv_out"}
    assert set(params["layers"]["attention"]) == {
        "ln1", "wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    assert set(params["layers"]["experts"]) == {
        "ln2", "router", "router_bias", "we1", "we2", "we3"}
    ours = jax.eval_shape(lfm2_moe._init_function(cfg, config),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    leaves = get_leaves(params, lfm2_moe._leaf_paths(config))
    assert set(leaves) == {
        "embed", "first_conv_in", "dense_down", "attention_key",
        "attention_q_norm", "last_conv_taps", "last_conv_out",
        "last_router", "last_experts_down"}
    assert leaves["attention_q_norm"].shape == (cfg.head_dim,)
    assert leaves["last_conv_taps"].shape == (3, cfg.d_model)
    assert leaves["first_conv_in"].shape == (cfg.d_model, 3 * cfg.d_model)
    # every layer's two blocks are where the adapter says the reference
    # finds them
    for (path, index), (ffn, at) in lfm2_moe._places(config):
        assert params[path[0]][path[1]]["ln1"][index].ndim == 1
        assert params[ffn[0]][ffn[1]]["ln2"][at].ndim == 1
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = lfm2_moe.host_batch(config, job, 0, 0, 2)
    _loss, aux, _grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss", "load_balance_loss", "router_z_loss",
                        "max_expert_load", "dropped", "held_rows"}
    choices = jax.eval_shape(
        lambda p, tok: t.router_choices(p, tok, cfg), params,
        batch["tokens"])
    assert choices.shape == (8, batch["tokens"].size, cfg.moe_top_k)
    assert scopes.SHORT_CONV_PHASES == (
        "hvd.short_conv", "hvd.short_conv.proj", "hvd.short_conv.gate")
    assert set(scopes.SHORT_CONV_PHASES) <= set(scopes.DEVICE_PHASES)


#: sha256 of the GPT cell's tiny train step's jaxpr (``_gpt_tiny_jaxpr``),
#: taken at 4c983fc, before ``TransformerConfig`` had ``embed_scale``,
#: ``residual_scale``, ``attention_scale`` and ``logits_scale``: a
#: default-valued config traces no multiply for them. Take a new digest only
#: where the GPT block's program is *meant* to change
GPT_TINY_STEP_SHA256 = (
    "50e6d4e177ae45f171398f411328471683d5af60ea28c310a71f26126c47fc39")


def _gpt_tiny_jaxpr(**fields) -> str:
    """The jitted train step of ``gpt-1.3b-widths.s2048`` at its ``tiny``
    sizes as text, a function's address taken out and a ``frozenset``'s
    members in order (its print order is the process's hash seed's)."""
    import dataclasses
    import jax
    import run as harness
    from adapters import flagship
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as t
    _b, _entry, config, job = harness.load_cell("gpt-1.3b-widths.s2048",
                                                tiny=True)
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])
    tx = harness.make_optimizer(job)
    step, shapes = flagship.abstract_step(config, job, mesh, tx)
    if fields:
        cfg = dataclasses.replace(flagship._model_config(config, job),
                                  **fields)
        step = t.make_train_step(cfg, mesh, tx)
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(step)(*shapes)))
    return re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({%s})" % ", ".join(sorted(
            m.group(1).split(", "))), text)


def test_a_default_valued_config_traces_the_step_of_before_the_multipliers():
    import hashlib
    text = _gpt_tiny_jaxpr()
    assert hashlib.sha256(text.encode()).hexdigest() == GPT_TINY_STEP_SHA256
    # and each field, set, is in the program
    for field, value in (("embed_scale", 12.0), ("residual_scale", 0.22),
                         ("attention_scale", 1 / 64),
                         ("logits_scale", 1 / 8)):
        assert _gpt_tiny_jaxpr(**{field: value}) != text, field


def test_the_dense_hybrid_step_gives_what_the_granite_hybrid_adapter_reads():
    """``adapters/granite_hybrid.py`` hands the configuration's four
    multipliers to ``TransformerConfig`` by keyword (``embed_scale``,
    ``residual_scale``, ``attention_scale``, ``logits_scale``), builds a
    period of twenty one-sublayer kinds of ``("mamba",)``, ``("dense",)``
    and ``("attention", None, False)``, names leaves of the three stacks
    (``_leaf_paths``, ``_init_function``); the configuration, the traffic
    file and the metric files agree with ``BENCHMARK.json``; the roofline
    functions of the metrics that list the cell read the adapter's
    ``shapes()`` keys."""
    import jax
    import numpy as np
    from adapters import granite_hybrid
    from trees import get_leaves
    from horovod_tpu.models import transformer as t
    from horovod_tpu.parallel import build_mesh
    from horovod_tpu.profiling import scopes
    for name in ("shapes", "tokens_per_step", "flops_per_token",
                 "host_batch", "abstract_step", "Cell"):
        assert callable(getattr(granite_hybrid, name)), name
    fields = {f.name: f.default for f in
              __import__("dataclasses").fields(t.TransformerConfig)}
    assert (fields["embed_scale"], fields["residual_scale"],
            fields["attention_scale"], fields["logits_scale"]) == (
                1.0, 1.0, None, 1.0)
    cell, name = "granite-4.0-h-micro.s4096", "granite-4.0-h-micro"
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(w for w in bench["workloads"] if w["name"] == cell)
    listed = next(c for c in bench["configs"] if c["name"] == name)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        name, "train.s4096.b1.ssm", 1)
    assert listed["file"] == f"benchmarks/chip/configs/{name}.json"
    with open(os.path.join(REPO, listed["file"])) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           entry["traffic"] + ".json")) as f:
        job = json.load(f)
    assert config["source"] == listed["source"]
    assert config["reduced"] == listed["reduced"] == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert (job["seq_len"], job["batch_per_chip"], job["prefetch"],
            job["max_ahead"], job["warmup_steps"], job["trace_steps"],
            job["mesh"]) == (4096, 1, 2, 2, 10, 10, {"dp": -1})
    full = granite_hybrid._model_config(config, job)
    assert len(full.layer_pattern) == full.n_layers == 20
    assert [k[0] for k in full.layer_pattern].count("mamba") == 9
    assert full.layer_pattern[10] == ("attention", None, False)
    assert full.layer_pattern[1::2] == (("dense",),) * 10
    assert (full.ssm_groups, full.ssm_chunk, full.head_dim, full.kv_heads,
            full.embed_scale, full.residual_scale, full.attention_scale,
            full.logits_scale) == (1, 256, 64, 8, 12.0, 0.22, 1 / 64, 1 / 8)
    phases, rooflined = chip_door.readable(
        cell, granite_hybrid.shapes(config, job))
    # the mixer and its four parts, the FFN beside it (PR 51's two read
    # phases of these by owner and reason: readers/step_owners.py)
    assert {scopes.SSM, scopes.SSM_PROJ, scopes.SSM_CONV, scopes.SSM_SCAN,
            scopes.SSM_NORM, scopes.MLP} <= phases
    assert {"hvd_ssm_scan", "hvd_flash_attention", "hvd_flash_bwd",
            "hvd_fused_xent"} <= rooflined
    config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    cfg = granite_hybrid._model_config(config, job)
    params = t.init_params(np.random.RandomState(0), cfg, 1)
    assert set(params) == {"embed", "ln_f", "layers"}
    assert list(params["layers"]) == ["mamba", "dense", "attention"]
    assert set(params["layers"]["mamba"]) == {
        "ssm_dt_bias", "ln1", "ssm_in", "ssm_conv_w", "ssm_conv_b",
        "ssm_a_log", "ssm_d", "ssm_norm", "ssm_out"}
    assert set(params["layers"]["dense"]) == {"ln2", "w1", "w2", "w3"}
    assert set(params["layers"]["attention"]) == {"ln1", "wq", "wk", "wv",
                                                  "wo"}
    assert params["layers"]["mamba"]["ssm_in"].shape == (
        1, 9, cfg.d_model, 2 * cfg.ssm_inner + 2 * cfg.ssm_state
        + cfg.ssm_heads)
    assert params["layers"]["dense"]["w1"].shape == (1, 10, cfg.d_model,
                                                     cfg.dense_ff)
    assert params["layers"]["attention"]["wk"].shape == (
        1, 1, cfg.d_model, cfg.kv_heads * cfg.head_dim)
    ours = jax.eval_shape(granite_hybrid._init_function(cfg, config),
                          jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(lambda a: a.shape, ours) == \
        jax.tree_util.tree_map(lambda a: a.shape, params)
    assert set(get_leaves(params, granite_hybrid._leaf_paths(
        config["layer_types"]))) == {
            "table", "first_ssm_in", "first_ssm_norm", "last_ssm_a_log",
            "last_ssm_dt_bias", "attention_query", "attention_key",
            "last_ffn_gate"}
    mesh = build_mesh(devices=jax.devices()[:1], dp=-1)
    batch = granite_hybrid.host_batch(config, job, 0, 0, 1)
    _loss, aux, grads = jax.eval_shape(
        t.make_grad_fn(cfg, mesh), params, batch["tokens"], batch["targets"])
    assert set(aux) == {"aux_loss"}
    assert jax.tree_util.tree_structure(grads) == \
        jax.tree_util.tree_structure(params)
