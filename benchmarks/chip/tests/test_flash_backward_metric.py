"""The backward flash kernel's roofline count by hand and at the three
causal cells' ``shapes()``, and the form of the two metric files."""

import json
import os
import re

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
CELLS = {  # cell -> (adapter, configuration, traffic, calls a step)
    "gpt-1.3b-widths.s2048": ("flagship", "gpt-1.3b-widths",
                              "train.s2048.b2", 6),
    "olmoe-1b-7b.s4096": ("olmoe", "olmoe-1b-7b", "train.s4096.b2", 1),
    "ouro-2.6b.s4096": ("ouro", "ouro-2.6b", "train.s4096.b1", 24),
}


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_flash_attention_backward_roofline_by_hand():
    import roofline_flash_attention_backward as mine
    shapes = {"batch": 1, "seq": 64, "heads": 4, "head_dim": 32,
              "causal": True, "layers": 2, "loops": 3}
    need = mine.flash_attention_backward(shapes)
    # five matmuls over 32.5 keys a query on average
    one_flops = 5 * 2 * 1 * 4 * 64 * 32.5 * 32
    # q, k, v, o, do read and dq, dk, dv written in bfloat16; the float32
    # log-sum-exp and row term read
    one_bytes = 8 * 1 * 64 * 4 * 32 * 2 + 2 * 1 * 4 * 64 * 4
    assert need == {"flops": 6 * one_flops, "bytes": 6 * one_bytes}
    assert mine.flash_attention_backward(
        {k: v for k, v in shapes.items() if k != "loops"}) == {
            "flops": 2 * one_flops, "bytes": 2 * one_bytes}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_count_at_the_cells_shapes(cell):
    import importlib
    import roofline
    import roofline_flash_attention_backward as mine
    adapter, config, traffic, calls = CELLS[cell]
    shapes = importlib.import_module(f"adapters.{adapter}").shapes(
        _read(CHIP, "configs", config + ".json"),
        _read(CHIP, "workloads", traffic + ".json"))
    assert shapes["layers"] * shapes.get("loops", 1) == calls
    need = mine.flash_attention_backward(shapes)
    forward_call = roofline.flash_attention_forward({**shapes, "layers": 1})
    assert need["flops"] == pytest.approx(
        calls * 2.5 * forward_call["flops"])
    # the MXU bounds it at the v5e's peaks, not the bytes
    peaks = _read(CHIP, "peaks.json")["TPU v5 lite"]
    assert need["flops"] / peaks["bf16_flops_per_s"] \
        > need["bytes"] / peaks["hbm_bytes_per_s"]
    if cell == "ouro-2.6b.s4096":
        # a call: 2.5 x 68.75 GFLOP, 0.87 ms at 197 TFLOP/s
        assert need["flops"] / calls == pytest.approx(171.9e9, rel=1e-3)


@pytest.mark.parametrize("name", ["flash_attention_bwd_ms",
                                  "flash_attention_bwd_roofline"])
def test_the_metric_files_and_their_entries(name):
    spec = _read(CHIP, "layer_metrics", name + ".json")
    assert spec["read"]["trace_ops"] == "hvd_flash_bwd"
    entry = {m["name"]: m for m in _read(ROOT, "BENCHMARK.json")[
        "per_layer"]}[name]
    # the three cells counted here first; a cell listed since stands after
    assert entry["workloads"][:3] == sorted(CELLS)
    for key in ("layer", "unit", "better", "source", "moves"):
        assert entry[key] == spec[key], key
    # the forward kernel's metrics must not catch the backward's name
    for other in ("flash_attention_roofline", "flash_attention_fwd_ms",
                  "flash_attention_calls_roofline",
                  "flash_attention_adj_ms"):
        pattern = _read(CHIP, "layer_metrics", other + ".json")[
            "read"]["trace_ops"]
        assert not re.search(pattern, "hvd_flash_bwd.3 custom-call")
    # nor the backward's the row sums' kernel's, nor the forward's
    for instruction in ("hvd_flash_adj.3 custom-call",
                        "hvd_flash_attention.3 custom-call"):
        assert not re.search(spec["read"]["trace_ops"], instruction)
