"""The ``keye_vl2`` adapter's analytic FLOPs against a count made by hand at
the configuration's tiny sizes and at the cell's own (test_flops.py's way,
in a file of this adapter's own: a ``model_config`` PR adds files)."""

import json
import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(tiny: bool):
    with open(os.path.join(CHIP, "configs", "keye-vl-2.0-30b-a3b.json")) as f:
        c = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           "train.s16384.b1.sparse.json")) as f:
        j = json.load(f)
    return ({**c, **c["tiny"]}, {**j, **j["tiny"]}) if tiny else (c, j)


def test_keye_vl2_flops_per_token_by_hand():
    from adapters import keye_vl2
    config, job = _cell(tiny=True)
    index = config["sa_config"]
    # hidden 64; 8 query heads of 16 on 2 k/v heads; 2 index heads of 8,
    # topk 16; 16 experts top-2 of width 32 of which 2 are held; 2 layers;
    # vocabulary 512; 64 positions
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            index["indexer_num_heads"], index["indexer_head_dim"],
            index["topk"], config["num_experts"], config["share"]["of"],
            config["num_experts_per_tok"], config["moe_intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            job["seq_len"]) == (64, 8, 2, 16, 2, 8, 16, 2, 8, 2, 32, 2, 512,
                                64)
    q_and_o = 2 * 2 * 64 * 128          # 32768
    k_and_v = 2 * 2 * 64 * 32           # 8192
    indexer = 2 * 64 * (16 + 8 + 2)     # 3328: queries, the key, the weights
    router = 2 * 64 * 16                # 2048
    experts = 2 * (2 / 16) * 3 * 2 * 64 * 32    # 3072: top-2, an eighth held
    dense = q_and_o + k_and_v + indexer + router + experts
    assert dense == 49408
    causal = (64 + 1) / 2               # 32.5 keys a query
    selected = (16 * 17 / 2 + 48 * 16) / 64     # 14.125: min(t + 1, 16)
    assert keye_vl2.mean_keys(64) == causal
    assert keye_vl2.mean_keys(64, 16) == selected
    # the score pass: every causal key forward, the selected ones twice
    # backward (the KL's gradient is zero elsewhere)
    scores = 2 * 16 * (causal + 2 * selected)   # 1944
    core = 3 * 2 * 2 * 128 * selected           # 21696: two products forward,
    #                                             four backward
    layer = 3 * dense + scores + core
    assert layer == 171864
    head = 3 * 2 * 64 * 512
    assert keye_vl2.flops_per_token(config, job) == pytest.approx(
        2 * layer + head) == pytest.approx(540336)


def test_keye_vl2_cell_flops_are_what_perf_md_says():
    from adapters import keye_vl2
    config, job = _cell(tiny=False)
    assert keye_vl2.mean_keys(16384, 2048) == 1920.0625
    shapes = keye_vl2.shapes(config, job)
    assert (shapes["experts"], shapes["held_experts"], shapes["vocab"],
            shapes["index_topk"]) == (128, 16, 18992, 2048)
    tokens = keye_vl2.tokens_per_step(job, 1)
    assert tokens == 16384
    step = tokens * keye_vl2.flops_per_token(config, job)
    # four layers: 3 x forward but for the score pass's backward, which
    # meets 2 x 1920 keys a query where its forward meets 8192.5
    assert config["num_hidden_layers"] == 4
    assert step == pytest.approx(2.189e13, rel=1e-3)
