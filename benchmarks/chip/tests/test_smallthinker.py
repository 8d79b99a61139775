"""The ``smallthinker`` adapter's arithmetic by hand, the three roofline
functions of its cell likewise, the new files' form (by name: no entry is
held to a position in ``per_layer``, which later PRs append to), and the
reference's blocked attention against the plain form."""

import json
import os

import pytest

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
CELL = "smallthinker-21b-a3b.s8192"
# (the names the cell's six kernel readings have since PR 63's merge; only
# the share of the held experts' roofline is still this cell's alone)
NEW_METRICS = ("flash_attention_fwd_ms", "flash_attention_bwd_ms",
               "flash_attention_kinds_roofline",
               "flash_attention_kinds_bwd_roofline", "moe.experts_ms",
               "share.moe_gmm_roofline")


def _read(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _cell(tiny: bool):
    config = _read(CHIP, "configs", "smallthinker-21b-a3b.json")
    job = _read(CHIP, "workloads", "train.s8192.b1.json")
    if tiny:
        config, job = {**config, **config["tiny"]}, {**job, **job["tiny"]}
    return config, job


def test_mean_live_keys_by_hand():
    from adapters import smallthinker
    # positions 0..7, window 4: 1 + 2 + 3 + 4 + 4 + 4 + 4 + 4 = 26 pairs
    assert smallthinker.mean_live_keys(8, 4) == 26 / 8
    assert smallthinker.mean_live_keys(8, None) == 4.5      # (8 + 1) / 2
    assert smallthinker.mean_live_keys(8, 100) == 4.5
    # (4096 * 4097 / 2 + 4096 * 4096) / 8192; ISSUE 32 rounds it to 3072.5
    assert smallthinker.mean_live_keys(8192, 4096) == 3072.25
    assert smallthinker.mean_live_keys(8192, None) == 4096.5


def test_flops_per_token_by_hand_at_the_tiny_sizes():
    from adapters import smallthinker
    config, job = _cell(tiny=True)
    assert (config["hidden_size"], config["head_dim"],
            config["num_attention_heads"], config["num_key_value_heads"],
            config["moe_ffn_hidden_size"], config["num_hidden_layers"],
            config["sliding_window_size"], config["vocab_size"],
            job["seq_len"]) == (64, 16, 8, 2, 32, 8, 32, 512, 64)
    projections = 2 * 2 * 64 * 128 + 2 * 2 * 64 * 32    # 32768 + 8192
    full = 2 * 2 * 128 * 32.5           # 16640: 8 heads x 16 at (64 + 1) / 2
    # window 32 of 64: (32 * 33 / 2 + 32 * 32) / 64 = 24.25 keys a query
    window = 2 * 2 * 128 * 24.25        # 12416
    router = 2 * 64 * 8                 # 1024: all 8 experts' columns
    experts = 2 * (2 / 8) * 3 * 2 * 64 * 32     # 6144: top-2, 2 of 8 held
    head = 2 * 64 * 512                 # 65536
    forward = (8 * (projections + router + experts) + 2 * full + 6 * window
               + head)
    assert forward == 558336
    assert smallthinker.flops_per_token(config, job) == pytest.approx(
        3 * forward)


def test_the_cell_s_flops_per_token_is_what_issue_32_says():
    from adapters import smallthinker
    config, job = _cell(tiny=False)
    # 4 x 41.94 M projections + scores 4 x 3584 x (4096.5 + 3 x 3072.25)
    # + 4 x 0.33 M router + 4 x 1.5 x 11.80 M experts + 194.5 M head
    projections = 4 * (2 * 2 * 2560 * 3584 + 2 * 2 * 2560 * 512)
    scores = 4 * 3584 * (4096.5 + 3 * 3072.25)
    router = 4 * 2 * 2560 * 64
    experts = 4 * 1.5 * 3 * 2 * 2560 * 768
    head = 2 * 2560 * 37984
    assert projections == pytest.approx(4 * 41.94e6, rel=1e-3)
    assert head == pytest.approx(194.5e6, rel=1e-3)
    forward = projections + scores + router + experts + head
    assert forward == pytest.approx(625e6, rel=2e-3)
    assert smallthinker.flops_per_token(config, job) == pytest.approx(
        3 * forward)
    assert smallthinker.tokens_per_step(job, 1) == 8192
    shapes = smallthinker.shapes(config, job)
    assert (shapes["heads"], shapes["kv_heads"], shapes["head_dim"],
            shapes["d_model"], shapes["experts"], shapes["held_experts"],
            shapes["first_expert"], shapes["experts_per_token"],
            shapes["d_expert"], shapes["vocab"], shapes["layers"]) == (
                28, 4, 128, 2560, 64, 16, 0, 6, 768, 37984, 4)
    assert shapes["layer_windows"] == [None, 4096, 4096, 4096]
    assert shapes["layer_rope"] == [False, True, True, True]


def test_the_rooflines_by_hand():
    from roofline_mixed_flash_attention import (live_scores,
                                                mixed_flash_attention)
    from roofline_mixed_flash_attention_backward import \
        mixed_flash_attention_backward
    from roofline_share_moe_gmm import share_moe_gmm
    assert live_scores(8, 4) == 26 and live_scores(8, None) == 36
    shapes = {"batch": 2, "seq": 8, "heads": 4, "kv_heads": 2,
              "head_dim": 16, "layer_windows": [None, 4], "layers": 2,
              "d_model": 32, "d_expert": 8, "experts": 8, "held_experts": 2,
              "experts_per_token": 2}
    fwd = mixed_flash_attention(shapes)
    # 2 matmuls x 2 FLOPs x batch 2 x 4 heads x 16 x (36 + 26) pairs
    assert fwd["flops"] == 2 * 2 * 2 * 4 * 16 * (36 + 26) == 31744
    # a layer: q, o at 4 heads and k, v at 2, bf16, + float32 lse
    layer = 2 * 2 * 8 * (4 + 2) * 16 * 2 + 2 * 4 * 8 * 4
    assert fwd["bytes"] == 2 * layer == 12800
    bwd = mixed_flash_attention_backward(shapes)
    assert bwd["flops"] == 2.5 * 31744
    # q, o, do, dq at 4 heads; k, v, dk, dv at 2; lse and the row term
    assert bwd["bytes"] == 2 * (4 * 2 * 8 * (4 + 2) * 16 * 2
                                + 2 * 2 * 4 * 8 * 4)
    gmm = share_moe_gmm(shapes)
    rows = 2 * 8 * 2 * 2 / 8            # 8 of the 32 assignments
    assert gmm["flops"] == 18 * 2 * rows * 32 * 8
    assert gmm["bytes"] == 18 * 2 * (rows * 32 + rows * 8 + 2 * 32 * 8)
    # the cell: 30 of 36 tiles' worth of scores in a window layer
    from adapters import smallthinker
    cell = smallthinker.shapes(*_cell(tiny=False))
    assert live_scores(8192, 4096) / live_scores(8192, None) \
        == pytest.approx(25.2 / 33.6, rel=2e-3)
    assert mixed_flash_attention(cell)["flops"] == pytest.approx(
        4 * 28 * 128 * (33.56e6 + 3 * 25.17e6), rel=1e-3)
    assert share_moe_gmm(cell)["flops"] == pytest.approx(
        36 * 2 * 12288 * 2560 * 768)


def test_the_new_files_are_well_formed_and_named_in_the_benchmark():
    bench = _read(ROOT, "BENCHMARK.json")
    config = {c["name"]: c for c in bench["configs"]}["smallthinker-21b-a3b"]
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    cell = {w["name"]: w for w in bench["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "smallthinker-21b-a3b", "train.s8192.b1", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW_METRICS:
        spec = _read(CHIP, "layer_metrics", name + ".json")
        entry = entries[name]
        assert CELL in entry["workloads"], name
        for key in ("layer", "unit", "better", "source", "moves"):
            assert entry[key] == spec[key], (name, key)
        assert set(entry) == {"name", "layer", "unit", "better", "source",
                              "moves", "workloads"}
        if "roofline" in name:
            assert name.endswith("_roofline") and entry["unit"] == "%"
    for name, scope in (("step.attention_window_ms",
                         "hvd.attention.core.window"),
                        ("step.attention_full_ms",
                         "hvd.attention.core.full")):
        spec = _read(CHIP, "layer_metrics", name + ".json")
        assert spec["read"]["trace_scope"]["phase"] == scope
        # (the cell these two were made for stands first in their lists)
        assert entries[name]["workloads"][0] == CELL


def test_the_configuration_holds_the_catalog_s_numbers():
    """Every number of the source's config under its own key, but the three
    that ``reduced`` names; the lists whole."""
    config, _job = _cell(tiny=False)
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384, "moe_ffn_hidden_size": 768,
        "moe_num_active_primary_experts": 6, "num_attention_heads": 28,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1500000, "sliding_window_size": 4096}
    for key, value in published.items():
        assert config[key] == value, key
    assert config["rope_layout"] == config["sliding_window_layout"] \
        == [0, 1, 1, 1] * 13
    assert (config["num_hidden_layers"], config["moe_num_primary_experts"],
            config["vocab_size"]) == (4, 16, 37984)
    assert config["reduced_from"] == {
        "num_hidden_layers": 52, "moe_num_primary_experts": 64,
        "vocab_size": 151936}
    assert 151936 == 4 * 37984 and config["share"] == {"index": 0, "of": 4}
    # the cut's floors (model-configs guide, section 4)
    assert config["num_hidden_layers"] >= 4
    assert config["moe_num_primary_experts"] >= 8
    assert config["vocab_size"] * 8 >= 151936


def test_the_reference_s_blocked_attention_is_plain_attention():
    """``_attend`` in blocks of query rows (what lets the chip's check fit)
    against scores over the whole sequence at once."""
    import math
    import jax
    import jax.numpy as jnp
    from reference import smallthinker as reference
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 64, 2, 3, 8))
    k, v = (jax.random.normal(kk, (2, 64, 2, 8)) for kk in ks[1:])
    old, reference.ATTENTION_ROWS = reference.ATTENTION_ROWS, 16
    try:
        for window in (None, 24):
            got = reference._attend(q, k, v, window)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", q, k) / math.sqrt(8)
            t, j = jnp.arange(64)[:, None], jnp.arange(64)[None, :]
            live = (j <= t) if window is None else (j <= t) & (j > t - window)
            want = jnp.einsum("bhgqk,bkhd->bqhgd", jax.nn.softmax(
                jnp.where(live, s, -jnp.inf), -1), v).reshape(2, 64, -1)
            assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    finally:
        reference.ATTENTION_ROWS = old
