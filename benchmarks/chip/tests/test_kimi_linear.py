"""The ``kimi_linear`` adapter's analytic FLOPs against a count made by hand
at the configuration's tiny sizes and at the cell's own (test_flops.py's way,
in a file of this adapter's own: a ``model_config`` PR adds files)."""

import json
import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cell(tiny: bool):
    with open(os.path.join(CHIP, "configs", "kimi-linear-48b-a3b.json")) as f:
        c = json.load(f)
    with open(os.path.join(CHIP, "workloads",
                           "train.s8192.b1.delta.json")) as f:
        j = json.load(f)
    return ({**c, **c["tiny"]}, {**j, **j["tiny"]}) if tiny else (c, j)


def test_kimi_linear_flops_per_token_by_hand():
    from adapters import kimi_linear
    config, job = _cell(tiny=True)
    linear = config["linear_attn_config"]
    # hidden 64; 2 delta heads of 16, chunk 8; 4 latent heads of 12 + 4 with
    # values 8 on a latent of 16; a dense FFN of 96; 16 experts top-2 of
    # width 32 of which 2 are held, a shared expert of 32; layers 1-5 (delta,
    # delta, delta, latent, delta), the first dense; vocabulary 512; 64
    # positions
    assert (config["hidden_size"], linear["num_heads"], linear["head_dim"],
            config["delta_chunk"], config["num_attention_heads"],
            config["qk_nope_head_dim"], config["qk_rope_head_dim"],
            config["v_head_dim"], config["kv_lora_rank"],
            config["intermediate_size"], config["num_experts"],
            config["share"]["of"], config["num_experts_per_token"],
            config["moe_intermediate_size"], config["num_hidden_layers"],
            config["vocab_size"], job["seq_len"]) == (
                64, 2, 16, 8, 4, 12, 4, 8, 16, 96, 2, 8, 2, 32, 5, 512, 64)
    assert kimi_linear.shapes(config, job)["layer_mixers"] == [
        "delta", "delta", "delta", "latent", "delta"]
    # -- a delta mixer: H D = 32 channels
    projections = 3 * 2 * 64 * 32 + 2 * 32 * 64         # 16384: q k v, out
    gates = 2 * (2 * 64 * 16 + 2 * 16 * 32) + 2 * 64 * 2    # 6400: f, g, beta
    # a head and token at chunk 8, D = Dv = 16: k k^T over the 3.5 earlier
    # rows of a chunk on the mean, q k^T over 4.5, the solve's [K | V] (32
    # columns) and P R (16) over 4.5, three products with the 16 x 16 state
    scan = (2 * 16 * 3.5 + 2 * 16 * 4.5 + 2 * 32 * 4.5 + 2 * 16 * 4.5
            + 3 * 2 * 16 * 16)
    assert scan == 2224 == kimi_linear.delta_scan_flops(
        {"delta_chunk": 8, "delta_head_dim": 16})
    delta = projections + gates + 2 * scan
    assert delta == 27232
    # -- the latent block: keys of 16, values of 8, 32.5 causal keys a query
    latent = (2 * 64 * 4 * 16 + 2 * 64 * (16 + 4) + 2 * 16 * 4 * (12 + 8)
              + 2 * 4 * 8 * 64 + 2 * 4 * (16 + 8) * 32.5)
    assert latent == 23648
    dense = 3 * 2 * 64 * 96                              # 36864
    experts = 2 * 64 * 16 + 3 * 2 * 64 * 32 + 2 * (2 / 16) * 3 * 2 * 64 * 32
    assert experts == 17408
    head = 2 * 64 * 512
    forward = 4 * delta + latent + dense + 4 * experts + head
    assert kimi_linear.flops_per_token(config, job) == pytest.approx(
        3 * forward) == pytest.approx(913824)


def test_kimi_linear_cell_flops_are_what_perf_md_says():
    from adapters import kimi_linear
    config, job = _cell(tiny=False)
    shapes = kimi_linear.shapes(config, job)
    assert (shapes["experts"], shapes["held_experts"], shapes["vocab"],
            shapes["delta_layers"], shapes["attention_layers"],
            shapes["routed_layers"], shapes["delta_chunk"],
            shapes["qk_head_dim"], shapes["value_head_dim"],
            shapes["head_dim"]) == (256, 8, 20480, 4, 1, 4, 64, 192, 128, 256)
    # a head and token at chunk 64, D = 128: 139 648; 32 heads 4.47 M
    assert kimi_linear.delta_scan_flops(shapes) == 139648
    tokens = kimi_linear.tokens_per_step(job, 1)
    assert tokens == 8192
    token = kimi_linear.flops_per_token(config, job)
    # forward: a delta mixer 83.38 M (78.91 projections and gates, 4.47 the
    # scan), the latent block 142.11 M (83.90 of it the core at the
    # published 192 + 128), the dense FFN 127.40 M, an expert block 18.87 M,
    # the head 94.37 M
    assert token / 3 == pytest.approx(772.96e6, rel=1e-4)
    assert tokens * token == pytest.approx(1.8996e13, rel=1e-3)
