"""Each analytic FLOPs function against a count made by hand at the
configuration's tiny sizes, and the roofline functions likewise."""

import json
import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny(config: str, traffic: str):
    with open(os.path.join(CHIP, "configs", config + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(CHIP, "workloads", traffic + ".json")) as f:
        j = json.load(f)
    return {**c, **c["tiny"]}, {**j, **j["tiny"]}


def test_bert_flops_per_token_by_hand():
    from adapters import bert
    config, job = _tiny("bert-large", "train.s128.b64")
    # hidden 64, FFN 128, 2 layers, vocab 512, seq 16, 3 predictions
    assert (config["hidden_size"], config["intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            job["seq_len"], config["max_predictions_per_seq"]) == (
                64, 128, 2, 512, 16, 3)
    qkv = 3 * 2 * 64 * 64            # 24576
    scores = 2 * 16 * 64             # 2048: 4 heads x 16 keys x 16 dims x 2
    values = 2 * 16 * 64             # 2048
    out = 2 * 64 * 64                # 8192
    ffn = 2 * 64 * 128 * 2           # 32768
    layer = qkv + scores + values + out + ffn
    assert layer == 69632
    mlm = (2 * 64 * 64 + 2 * 64 * 512) * 3 / 16      # 73728 * 3 / 16
    nsp = (2 * 64 * 64 + 2 * 64 * 2) / 16
    forward = 2 * layer + mlm + nsp
    assert forward == 139264 + 13824 + 528
    assert bert.flops_per_token(config, job) == pytest.approx(3 * forward)


def test_bert_large_flops_per_token_is_what_perf_md_says():
    from adapters import bert
    with open(os.path.join(CHIP, "configs", "bert-large.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads", "train.s128.b64.json")) as f:
        job = json.load(f)
    # 24 x (8 H^2 + 4 S H + 4 H I) + MLM head at 20 of 128 positions
    assert bert.flops_per_token(config, job) == pytest.approx(1.880e9,
                                                              rel=1e-3)
    assert bert.tokens_per_step(job, 4) == 64 * 128 * 4


def test_flagship_flops_per_token_by_hand():
    from adapters import flagship
    config, job = _tiny("gpt-1.3b-widths", "train.s2048.b2")
    assert (config["n_embd"], config["n_inner"], config["n_layer"],
            config["vocab_size"], job["seq_len"]) == (256, 512, 2, 1000, 256)
    qkv = 3 * 2 * 256 * 256          # 393216
    keys = (256 + 1) / 2             # 128.5 keys a query, causal
    scores = 2 * keys * 256          # 65792
    values = 2 * keys * 256
    out = 2 * 256 * 256              # 131072
    ffn = 2 * 256 * 512 * 2          # 524288
    layer = qkv + scores + values + out + ffn
    assert layer == 1180160
    forward = 2 * layer + 2 * 256 * 1000
    assert flagship.flops_per_token(config, job) == pytest.approx(
        3 * forward)


def test_gpt_cell_flops_per_token_is_what_perf_md_says():
    from adapters import flagship
    with open(os.path.join(CHIP, "configs", "gpt-1.3b-widths.json")) as f:
        config = json.load(f)
    with open(os.path.join(CHIP, "workloads", "train.s2048.b2.json")) as f:
        job = json.load(f)
    assert flagship.flops_per_token(config, job) == pytest.approx(2.581e9,
                                                                  rel=1e-3)


def test_roofline_functions_by_hand():
    import roofline
    shapes = {"batch": 2, "seq": 256, "heads": 2, "head_dim": 128,
              "layers": 3, "vocab": 1000, "causal": True}
    need = roofline.flash_attention_forward(shapes)
    # a call: 2 matmuls x 2 FLOPs x (2*2 heads) x 256 queries x 128.5 keys
    # x 128 dims; q, k, v, o in bfloat16 + a float32 lse
    assert need["flops"] == 3 * (2 * 2 * 4 * 256 * 128.5 * 128)
    assert need["bytes"] == 3 * (4 * 2 * 256 * 2 * 128 * 2 + 2 * 2 * 256 * 4)
    full = roofline.flash_attention_forward({**shapes, "causal": False})
    assert full["flops"] == 3 * (2 * 2 * 4 * 256 * 256 * 128)
    need = roofline.fused_xent_forward(shapes)
    assert need["bytes"] == 512 * 1000 * 2 + 512 * 12
    assert need["flops"] == 4 * 512 * 1000


def test_every_peak_has_its_source():
    with open(os.path.join(CHIP, "peaks.json")) as f:
        peaks = json.load(f)
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_flops_per_s"], v5e["hbm_bytes_per_s"],
            v5e["ici_bits_per_s"]) == (197e12, 819e9, 1600e9)
    assert all("source" in row for kind, row in peaks.items()
               if not kind.startswith("_"))
