"""The ``qwen3_next`` adapter's analytic FLOPs, and the two delta-scan
roofline functions' FLOPs and bytes, against counts made by hand at the
configuration's tiny sizes and at the cell's own (test_flops.py's way, in a
file of this adapter's own: a ``model_config`` PR adds files); and which
accepted flash share counts the cell's one grouped attention block right."""

import json
import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(name="qwen3-next-80b-a3b", traffic="train.s8192.b1.gdn",
          tiny=False):
    with open(os.path.join(CHIP, "configs", name + ".json")) as f:
        c = json.load(f)
    with open(os.path.join(CHIP, "workloads", traffic + ".json")) as f:
        j = json.load(f)
    return ({**c, **c["tiny"]}, {**j, **j["tiny"]}) if tiny else (c, j)


def test_qwen3_next_flops_per_token_by_hand():
    from adapters import qwen3_next
    config, job = _cell(tiny=True)
    # hidden 64; 2 key and 4 value delta heads of 16, chunk 8; 4 / 2
    # attention heads of 16; 16 experts top-2 of width 32 of which 2 are
    # held, a gated shared expert of 32; layers 0-3 (delta, delta, delta,
    # attention); vocabulary 512; 64 positions
    assert (config["hidden_size"], config["linear_num_key_heads"],
            config["linear_num_value_heads"], config["linear_key_head_dim"],
            config["delta_chunk"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["share"]["of"],
            config["num_experts_per_tok"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_hidden_layers"], config["vocab_size"],
            job["seq_len"]) == (64, 2, 4, 16, 8, 4, 2, 16, 2, 8, 2, 32, 32,
                                4, 512, 64)
    shapes = qwen3_next.shapes(config, job)
    assert shapes["layer_mixers"] == ["delta"] * 3 + ["attention"]
    assert shapes["rope_width"] == 4
    # -- a delta mixer: keys 2 x 16, values 4 x 16
    projections = 2 * 64 * (32 + 32 + 64 + 64) + 2 * 64 * 8 + 2 * 64 * 64
    assert projections == 33792
    # a KEY head and token at chunk 8, D = 16: k k^T over the 3.5 earlier
    # rows of a chunk on the mean, q k^T over 4.5; a VALUE head: the solve's
    # [K | V] (32 columns) and P R (16) over 4.5, three products with the
    # 16 x 16 state
    a_key_head = 2 * 16 * 3.5 + 2 * 16 * 4.5
    a_value_head = 2 * 32 * 4.5 + 2 * 16 * 4.5 + 3 * 2 * 16 * 16
    assert (a_key_head, a_value_head) == (256, 1968)
    scan = 2 * a_key_head + 4 * a_value_head
    assert scan == 8384 == qwen3_next.delta_scan_flops(shapes)
    delta = projections + scan
    # -- the attention block: queries AND gates, 32.5 causal keys a query
    attention = (2 * 64 * 2 * 4 * 16 + 2 * 2 * 64 * 2 * 16 + 2 * 4 * 16 * 64
                 + 2 * 4 * 2 * 16 * 32.5)
    assert attention == 41088
    experts = (2 * 64 * 16 + 3 * 2 * 64 * 32 + 2 * 64
               + 2 * (2 / 16) * 3 * 2 * 64 * 32)
    assert experts == 17536
    head = 2 * 64 * 512
    forward = 3 * delta + attention + 4 * experts + head
    assert qwen3_next.flops_per_token(config, job) == pytest.approx(
        3 * forward) == pytest.approx(909888)


def test_qwen3_next_cell_flops_are_what_perf_md_says():
    from adapters import qwen3_next
    config, job = _cell()
    shapes = qwen3_next.shapes(config, job)
    assert (shapes["experts"], shapes["held_experts"], shapes["vocab"],
            shapes["delta_layers"], shapes["attention_layers"],
            shapes["routed_layers"], shapes["delta_chunk"],
            shapes["delta_heads"], shapes["delta_key_heads"],
            shapes["heads"], shapes["kv_heads"], shapes["head_dim"],
            shapes["rope_width"]) == (512, 32, 18992, 3, 1, 4, 64, 32, 16,
                                      16, 2, 256, 64)
    # a key head and token at chunk 64, D = 128: 16 384; a value head
    # 123 264; 16 and 32 of them 4.21 M
    assert qwen3_next.delta_scan_flops(shapes) == 16 * 16384 + 32 * 123264
    tokens = qwen3_next.tokens_per_step(job, 1)
    assert tokens == 8192
    token = qwen3_next.flops_per_token(config, job)
    # forward: a delta mixer 71.58 M (67.37 projections, 4.21 the scan), the
    # attention block 121.65 M (67.12 of it the core), an expert block
    # 12.33 M, the head 77.79 M: the mixers 46 %, the attention block 26 %,
    # the head 17 %, the experts 11 %
    assert token / 3 == pytest.approx(463.49e6, rel=1e-4)
    assert 3 * 71.58e6 / (token / 3) == pytest.approx(0.463, abs=2e-3)
    assert tokens * token == pytest.approx(1.1391e13, rel=1e-3)


def test_the_delta_scan_roofline_counts_both_forms_by_hand():
    """Kimi's shapes give no ``delta_key_heads``, ``delta_decay_width`` or
    ``delta_forward_calls``: the defaults are its form."""
    import importlib
    from roofline_delta_scan import delta_scan
    from roofline_delta_scan_backward import delta_scan_backward
    tokens = 8192
    # a token and call: the scan's FLOPs, and bytes: q, k bfloat16 at the
    # key heads, v bfloat16, the log decay and beta float32, o float32
    for name, traffic, adapter, calls, layers, flops, bytes_in in (
            ("kimi-linear-48b-a3b", "train.s8192.b1.delta", "kimi_linear",
             8, 4, 32 * 139648,
             2 * 32 * 128 * 2 + 32 * 128 * 2 + 32 * 128 * 4 + 32 * 4),
            ("qwen3-next-80b-a3b", "train.s8192.b1.gdn", "qwen3_next",
             6, 3, 16 * 16384 + 32 * 123264,
             2 * 16 * 128 * 2 + 32 * 128 * 2 + 32 * 4 + 32 * 4)):
        shapes = importlib.import_module("adapters." + adapter).shapes(
            *_cell(name, traffic))
        written = 32 * 128 * 4
        assert delta_scan(shapes) == {
            "flops": calls * tokens * flops,
            "bytes": calls * tokens * (bytes_in + written)}, name
        assert delta_scan_backward(shapes) == {
            "flops": layers * tokens * 2 * flops,
            "bytes": layers * tokens * (2 * bytes_in + written)}, name


def test_which_flash_shares_count_the_grouped_block():
    """One attention block at 16 / 2 heads of 256 that keeps its
    activations: one forward and one backward call a step. The shares for
    stacks of kinds count one call a layer with k and v read once a
    key/value head: right, forward and backward. The calls' share counts
    every head's k and v (``flash_attention_forward``: 4 x 16 heads of bytes
    where the grouped block reads 2 x (16 + 2)): the cell does not join
    it."""
    from adapters import qwen3_next
    from roofline_loop_flash_attention import loop_flash_attention
    from roofline_mixed_flash_attention import (live_scores,
                                                mixed_flash_attention)
    from roofline_mixed_flash_attention_backward import (
        mixed_flash_attention_backward)
    shapes = qwen3_next.shapes(*_cell())
    s, h, kv, d = 8192, 16, 2, 256
    one_call = 2 * 2 * h * d * s * (s + 1) / 2
    assert live_scores(s, None) == s * (s + 1) / 2
    assert shapes["attention_forward_calls"] == 1
    forward = mixed_flash_attention(shapes)
    assert forward["flops"] == one_call
    assert forward["bytes"] == 2 * s * (h + kv) * d * 2 + h * s * 4
    backward = mixed_flash_attention_backward(shapes)
    assert backward["flops"] == 2.5 * one_call
    assert backward["bytes"] == 4 * s * (h + kv) * d * 2 + 2 * h * s * 4
    calls = loop_flash_attention(shapes)
    assert calls["flops"] == one_call
    assert calls["bytes"] == 4 * s * h * d * 2 + h * s * 4 > forward["bytes"]
