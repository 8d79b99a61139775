"""The ``olmoe`` adapter's arithmetic by hand, the grouped matmul's roofline
function likewise, the new files' form, and both cells of PR 26 walked by
the harness at their tiny sizes on the CPU."""

import json
import os
import subprocess
import sys

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _cell(tiny: bool):
    config = _read(CHIP, "configs", "olmoe-1b-7b.json")
    job = _read(CHIP, "workloads", "train.s4096.b2.json")
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


def test_olmoe_flops_per_token_by_hand():
    from adapters import olmoe
    config, job = _cell(tiny=True)
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_hidden_layers"], config["num_experts"],
            config["num_experts_per_tok"], config["vocab_size"],
            job["seq_len"]) == (128, 64, 2, 8, 2, 512, 64)
    projections = 4 * 2 * 128 * 128      # 131072: q, k, v, o
    keys = (64 + 1) / 2                  # 32.5 keys a query, causal
    scores = 2 * 2 * keys * 128          # 16640: q k^T and p v
    router = 2 * 128 * 8                 # 2048
    experts = 2 * 3 * 2 * 128 * 64       # 98304: 2 experts x 3 matrices
    layer = projections + scores + router + experts
    assert layer == 248064
    head = 2 * 128 * 512                 # 131072, untied, every position
    assert olmoe.flops_per_token(config, job) == pytest.approx(
        3 * (2 * layer + head))


def test_olmoe_cell_flops_per_token_is_what_issue_26_says():
    from adapters import olmoe
    config, job = _cell(tiny=False)
    # 33.6 M projections + 16.8 M causal scores at 4096 + 0.26 M router
    # + 100.7 M (8 experts x 3 matrices) + 206.0 M head = 357.4 MFLOP, x 3
    assert olmoe.flops_per_token(config, job) == pytest.approx(
        3 * 357.4e6, rel=1e-3)
    assert olmoe.tokens_per_step(job, 1) == 2 * 4096
    shapes = olmoe.shapes(config, job)
    assert (shapes["experts"], shapes["experts_per_token"],
            shapes["d_expert"], shapes["d_model"], shapes["heads"],
            shapes["head_dim"], shapes["vocab"], shapes["layers"]) == (
                64, 8, 1024, 2048, 16, 128, 50304, 1)


def test_moe_gmm_roofline_by_hand():
    import roofline_moe_gmm
    shapes = {"batch": 2, "seq": 64, "experts_per_token": 2, "d_model": 128,
              "d_expert": 64, "experts": 8, "layers": 2}
    need = roofline_moe_gmm.moe_gmm(shapes)
    rows = 2 * 64 * 2                    # 256 assignments, none dropped
    one_call = 2 * rows * 128 * 64       # 4194304 FLOPs
    # gate, up, down x forward, input gradient, weight gradient x 2 layers
    assert need["flops"] == 18 * one_call
    # bfloat16: the rows on both sides of the matmul and the stacked weights
    assert need["bytes"] == 18 * 2 * (rows * 128 + rows * 64 + 8 * 128 * 64)
    # a step's required calls, forward and gradients under one name: the
    # function states no calls and the harness scales nothing by the trace
    assert "calls" not in need
    # a quarter of the experts held, ungated experts, the expert layers
    # after a leading dense one: rows, weights and calls follow
    held = roofline_moe_gmm.moe_gmm(
        {**shapes, "held_experts": 2, "expert_matrices": 2,
         "routed_layers": 1})
    assert held["flops"] == 6 * 2 * (rows / 4) * 128 * 64
    assert held["bytes"] == 6 * 2 * (rows / 4 * (128 + 64) + 2 * 128 * 64)
    # the cell: 2.47 TFLOP of grouped matmuls a step (ISSUE 26)
    from adapters import olmoe
    cell = roofline_moe_gmm.moe_gmm(olmoe.shapes(*_cell(tiny=False)))
    assert cell["flops"] == pytest.approx(2.474e12, rel=1e-3)


def test_the_configuration_holds_the_catalog_s_numbers():
    """Every key of the catalog entry's ``config`` under the same key, only
    the depth changed and listed."""
    published = {
        "attention_bias": False, "clip_qkv": None, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 1024,
        "max_position_embeddings": 4096, "model_type": "olmoe",
        "norm_topk_prob": False, "num_attention_heads": 16,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "tie_word_embeddings": False, "vocab_size": 50304}
    config = _read(CHIP, "configs", "olmoe-1b-7b.json")
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["reduced_from"] == {"num_hidden_layers": 16}
    entry = {c["name"]: c for c in _read(ROOT, "BENCHMARK.json")["configs"]}[
        "olmoe-1b-7b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]


def test_the_new_metric_files_are_well_formed():
    bench = _read(ROOT, "BENCHMARK.json")
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in ("moe.experts_ms", "moe_gmm_roofline"):
        spec = _read(CHIP, "layer_metrics", name + ".json")
        entry = declared[name]
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert spec["read"]["trace_ops"] == "hvd_moe_gmm"
    for name in ("step.moe_ms", "step.moe_router_ms", "step.moe_dispatch_ms",
                 "step.moe_experts_ms", "step.moe_combine_ms"):
        spec = _read(CHIP, "layer_metrics", name + ".json")
        assert spec["read"]["trace_scope"]["phase"].startswith("hvd.moe")
        # the driver's since PR 34, in both cells with an expert layer
        # then; a cell listed since (PR 59, PR 63) stands after them
        assert declared[name]["workloads"][:2] == [
            "olmoe-1b-7b.s4096", "smallthinker-21b-a3b.s8192"]


@pytest.mark.parametrize("cell, trace, expect", [
    ("olmoe-1b-7b.s4096", 0, "rehearsal.tokens_per_s_per_chip"),
    ("olmoe-1b-7b.s4096", 1, "rehearsal.host.dispatch_ms"),
    ("bert-large.s512", 0, "rehearsal.setup_s"),
    ("bert-large.s512", 1, "rehearsal.setup.compile_s rehearsal.hbm.compiled_gb"
                           " rehearsal.hbm.temporaries_gb"),
])
def test_the_new_cells_rehearse(tmp_path, cell, trace, expect):
    env = {**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"}
    run = subprocess.run(
        [sys.executable, os.path.join(CHIP, "run.py"), "--workload", cell,
         "--seed", "2147483659", "--seconds", "1", "--trace", str(trace),
         "--rehearse", "--out", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = run.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(expect.split()) <= set(result["metrics"])
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    for line in lines[:-1]:
        assert {"platform", "kind", "count"} <= set(json.loads(line))


def test_olmoe_reference_check_fails_without_the_qk_norm(monkeypatch):
    """The harness's check, at the tiny sizes: right as it is, wrong with a
    reference that leaves the two norms out."""
    import importlib
    import horovod_tpu as hvd
    import jax
    import run as harness
    config, job = _cell(tiny=True)
    adapter = importlib.import_module("adapters.olmoe")
    reference = importlib.import_module("reference.olmoe")
    mesh = hvd.build_mesh(devices=jax.devices()[:1], dp=-1)
    cell = adapter.Cell(config, job, mesh, 0)
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert check["ok"] and check["loss_rel"] < 1e-5, check
    assert set(check["grad_rel_l2"]) == {
        "lm_head", "first_query", "last_router", "last_experts_down"}
    real = reference._rms_norm

    def attention_without_qk_norm(p, x, sizes):
        bare = {**p, "q_norm": None, "k_norm": None}
        return real_attention(bare, x, sizes)
    real_attention = reference.attention
    monkeypatch.setattr(reference, "_rms_norm",
                        lambda x, g, eps: x if g is None else real(x, g, eps))
    monkeypatch.setattr(reference, "attention", attention_without_qk_norm)
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert not check["ok"], check
    assert check["grad_rel_l2"]["first_query"] > 0.2, check
