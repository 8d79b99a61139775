"""The ``ouro`` adapter's arithmetic by hand, both looped roofline functions
likewise, the new files' form, and the cell of PR 30 walked by the harness
at its tiny sizes on the CPU."""

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
    config = _read(CHIP, "configs", "ouro-2.6b.json")
    job = _read(CHIP, "workloads", "train.s4096.b1.json")
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


def test_ouro_flops_per_token_by_hand():
    from adapters import ouro
    config, job = _cell(tiny=True)
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_hidden_layers"], config["total_ut_steps"],
            config["vocab_size"], job["seq_len"]) == (128, 256, 2, 2, 512, 64)
    projections = 4 * 2 * 128 * 128      # 131072: q, k, v, o
    keys = (64 + 1) / 2                  # 32.5 keys a query, causal
    scores = 2 * 2 * keys * 128          # 16640: q k^T and p v
    ffn = 3 * 2 * 128 * 256              # 196608: gate, up, down
    block = projections + scores + ffn
    assert block == 344320
    head = 2 * 128 * 512                 # 131072, untied, every loop step
    gate = 2 * 128                       # one column, every loop step
    # 2 loop steps x (2 layers + head + gate), forward; x 3 with backward
    assert ouro.flops_per_token(config, job) == pytest.approx(
        3 * 2 * (2 * block + head + gate))


def test_ouro_cell_flops_per_token_is_what_issue_30_says():
    from adapters import ouro
    config, job = _cell(tiny=False)
    # a pass 33.55 M projections + 16.78 M causal scores at 4096 + 69.21 M
    # FFN = 119.54 MFLOP, x 24 passes; head 201.33 M x 4; gate 16 K:
    # 3 674.3 MFLOP forward, x 3
    m, f, v, s = 2048, 5632, 49152, 4096
    block = 8 * m * m + 4 * (s + 1) / 2 * m + 6 * m * f
    assert block == pytest.approx(119.54e6, rel=1e-4)
    assert ouro.flops_per_token(config, job) == pytest.approx(
        3 * (24 * block + 4 * 2 * m * v + 4 * 2 * m))
    assert ouro.flops_per_token(config, job) == pytest.approx(
        3 * 3674.3e6, rel=1e-4)
    assert ouro.tokens_per_step(job, 1) == 4096
    shapes = ouro.shapes(config, job)
    assert (shapes["layers"], shapes["loops"], shapes["d_model"],
            shapes["heads"], shapes["head_dim"], shapes["d_ff"],
            shapes["vocab"], shapes["batch"], shapes["seq"]) == (
                6, 4, 2048, 16, 128, 5632, 49152, 1, 4096)
    # the calls a step the compiled program makes (tier-1's
    # tests/test_tpu_compile.py holds the compiled step to them): every
    # pass's forward kernel runs again in its checkpointed backward
    assert shapes["attention_forward_calls"] == 48
    assert shapes["head_calls"] == 4


def test_loop_flash_attention_roofline_by_hand():
    import roofline
    import roofline_loop_flash_attention as mine
    shapes = {"batch": 1, "seq": 64, "heads": 4, "head_dim": 32,
              "causal": True, "layers": 2, "loops": 2,
              "attention_forward_calls": 8}
    need = mine.loop_flash_attention(shapes)
    # one call: q k^T and p v over 32.5 keys a query on average
    one_flops = 2 * 2 * 1 * 4 * 64 * 32.5 * 32
    # q, k, v read and o written in bfloat16, the float32 lse written
    one_bytes = 4 * 1 * 64 * 4 * 32 * 2 + 1 * 4 * 64 * 4
    assert need == {"flops": 8 * one_flops, "bytes": 8 * one_bytes}
    # the count is the compiled program's, not layers x loops
    assert need["flops"] == 4 * roofline.flash_attention_forward(
        shapes)["flops"]
    # the cell: 48 calls of 2 x 2 x 16 x 4096 x 2048.5 x 128 FLOPs
    from adapters import ouro
    cell = mine.loop_flash_attention(ouro.shapes(*_cell(tiny=False)))
    assert cell["flops"] == pytest.approx(48 * 68.75e9, rel=1e-3)


def test_loop_head_xent_roofline_by_hand():
    import roofline_loop_head_xent as mine
    shapes = {"batch": 1, "seq": 64, "vocab": 512, "head_calls": 2}
    need = mine.loop_head_xent(shapes)
    rows = 64
    # every bfloat16 logit read and its gradient written over it; a float32
    # loss and log-sum-exp written and the label read per row
    assert need["bytes"] == 2 * (2 * rows * 512 * 2 + 12 * rows)
    assert need["flops"] == 2 * 9 * rows * 512
    # the cell: four calls of 805 MB each
    from adapters import ouro
    cell = mine.loop_head_xent(ouro.shapes(*_cell(tiny=False)))
    assert cell["bytes"] == pytest.approx(4 * 805.4e6, rel=1e-3)
    # bytes bound it at the v5e's peaks
    peaks = _read(CHIP, "peaks.json")["TPU v5 lite"]
    assert cell["bytes"] / peaks["hbm_bytes_per_s"] \
        > cell["flops"] / peaks["bf16_flops_per_s"]


def test_the_configuration_holds_the_catalog_s_numbers():
    """Every key of the catalog entry's ``config`` under the same key, only
    the depth changed and listed."""
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "layer_types": ["full_attention"] * 48,
        "max_position_embeddings": 65536, "max_window_layers": 48,
        "model_type": "ouro", "num_attention_heads": 16,
        "num_hidden_layers": 48, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False, "vocab_size": 49152}
    config = _read(CHIP, "configs", "ouro-2.6b.json")
    differs = sorted(k for k, v in published.items() if config[k] != v)
    assert differs == config["reduced"] == ["num_hidden_layers"]
    assert config["reduced_from"] == {"num_hidden_layers": 48}
    assert config["num_hidden_layers"] == 6
    assert {"equations", "block", "loop", "exit_gate", "exit_distribution",
            "loss", "exit_entropy_weight", "optimizer", "data", "precision",
            "init"} <= set(config["assumed"])
    assert config["assumed"]["exit_entropy_weight"] == 0.1
    entry = {c["name"]: c for c in _read(ROOT, "BENCHMARK.json")["configs"]}[
        "ouro-2.6b"]
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"]
    assert entry["file"] == "benchmarks/chip/configs/ouro-2.6b.json"


def test_the_new_cell_and_metric_files_are_well_formed():
    bench = _read(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    assert (cells["ouro-2.6b.s4096"]["config"],
            cells["ouro-2.6b.s4096"]["traffic"],
            cells["ouro-2.6b.s4096"]["chips"]) == (
                "ouro-2.6b", "train.s4096.b1", 1)
    declared = {m["name"]: m for m in bench["per_layer"]}
    # (the names the looped cell's four kernel readings have since PR 63's
    # merge, each with the other cells that report the same read)
    kernels = {"flash_attention_fwd_ms": "hvd_flash_attention",
               "flash_attention_calls_roofline": "hvd_flash_attention",
               "head_xent_ms": "hvd_fused_xent",
               "head_xent_roofline": "hvd_fused_xent"}
    for name, kernel in kernels.items():
        spec = _read(CHIP, "layer_metrics", name + ".json")
        entry = declared[name]
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == entry[key], (name, key)
        assert "ouro-2.6b.s4096" in entry["workloads"]
        assert spec["read"]["trace_ops"] == kernel
        if name.endswith("_roofline"):
            import run as harness
            assert callable(harness.roofline_function(
                spec["read"]["roofline"]))
    for name, phase in (("step.loop_ms", "hvd.loop"),
                        ("step.loop_gate_ms", "hvd.loop.gate")):
        spec = _read(CHIP, "layer_metrics", name + ".json")
        assert spec["read"]["trace_scope"]["phase"] == phase
        assert declared[name]["workloads"] == [
            "ouro-2.6b.s4096"]       # the driver's since PR 34


@pytest.mark.parametrize("cell, trace, expect", [
    ("ouro-2.6b.s4096", 0, "rehearsal.tokens_per_s_per_chip"),
    ("ouro-2.6b.s4096", 1, "rehearsal.host.dispatch_ms"),
])
def test_the_new_cell_rehearses(tmp_path, cell, trace, expect):
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
    assert expect in result["metrics"]
    assert all(k.startswith("rehearsal.") for k in result["metrics"])
    for line in lines[:-1]:
        assert {"platform", "kind", "count"} <= set(json.loads(line))


def test_a_loop_metric_with_nothing_to_read_is_left_out():
    """On a program without the kernels (the CPU, or a parent commit
    without the loop) the readers return nothing and do not raise."""
    import run as harness
    for name in ("flash_attention_fwd_ms", "flash_attention_calls_roofline",
                 "head_xent_ms", "head_xent_roofline"):
        spec = _read(CHIP, "layer_metrics", name + ".json")
        assert harness.read_layer_metric(spec["read"], {"trace": None}) \
            is None


def test_ouro_reference_check_fails_without_the_entropy_term(monkeypatch):
    """The harness's check, at the tiny sizes: right as it is, wrong with a
    reference that drops ``- beta H(p)`` from the loss."""
    import importlib
    import horovod_tpu as hvd
    import jax
    import jax.numpy as jnp
    import run as harness
    config, job = _cell(tiny=True)
    adapter = importlib.import_module("adapters.ouro")
    reference = importlib.import_module("reference.ouro")
    mesh = hvd.build_mesh(devices=jax.devices()[:1], dp=-1)
    cell = adapter.Cell(config, job, mesh, 0)
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert check["ok"] and check["loss_rel"] < 1e-5, check
    assert set(check["grad_rel_l2"]) == {
        "lm_head", "exit_gate", "first_query", "last_ffn_down",
        "last_post_norm"}
    assert max(check["grad_rel_l2"].values()) < 1e-4, check
    monkeypatch.setattr(reference, "entropy",
                        lambda p: jnp.zeros_like(p[0]))
    check = harness.reference_check(adapter, reference, cell, config, job, 0)
    assert not check["ok"], check
    # the loss by beta x the entropy, and the gate's gradient with it
    assert check["loss_rel"] > 5e-3, check
    assert check["grad_rel_l2"]["exit_gate"] > 0.2, check


def test_the_precision_tool_rehearses_and_the_lower_precision_fails():
    """tools/ouro_precision.py at the tiny sizes: the sound program inside
    ``TOLERANCE``, the per-token losses in bfloat16 and the all-bfloat16
    reference outside it."""
    run = subprocess.run(
        [sys.executable, os.path.join(CHIP, "tools", "ouro_precision.py"),
         "--seeds", "2147483659,7", "--rehearse"],
        env={**os.environ, "PYTHONPATH": ROOT, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600)
    assert run.returncode == 0, run.stderr[-2000:]
    lines = [json.loads(x) for x in run.stdout.strip().splitlines()]
    assert lines[-1]["ok"] is True and lines[-1]["seeds"] == 2
    for row in lines[:-1]:
        assert row["sound"]["inside"] and row["rehearsal"] is True
        assert not row["losses_bf16"]["inside"]
        assert not row["reference_bf16"]["inside"]


def test_the_chip_s_way_through_the_reference_is_the_plain_one():
    """``loss_and_grads`` scans a loop step's layers and checkpoints each
    pass and each head; ``objective`` is Python loops alone: the same
    numbers."""
    import importlib
    import jax
    import numpy as np
    from trees import get_leaves
    config, job = _cell(tiny=True)
    adapter = importlib.import_module("adapters.ouro")
    reference = importlib.import_module("reference.ouro")
    sizes = adapter.shapes(config, job)
    cfg = adapter._model_config(config, job)
    params = jax.jit(adapter._init_function(cfg))(jax.random.PRNGKey(1))
    batch = jax.tree_util.tree_map(
        jax.numpy.asarray, adapter.host_batch(config, job, 1, 0, 2))
    specs = adapter._leaf_paths(config["num_hidden_layers"])
    loss, grads = reference.loss_and_grads(params, specs, batch, sizes)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(
            lambda p: reference.objective(p, batch, sizes)[0])(params)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-6)
    for name, leaf in get_leaves(want, specs).items():
        np.testing.assert_allclose(np.asarray(grads[name]), np.asarray(leaf),
                                   rtol=1e-4, atol=1e-7, err_msg=name)
