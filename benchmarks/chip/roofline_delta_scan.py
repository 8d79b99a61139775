"""What the gated delta rule's forward scan kernel's calls of one step need
at the least: the function ``delta_scan`` for
``layer_metrics/delta_scan_roofline.json`` (see roofline.py for the form)."""

from __future__ import annotations


def forms(shapes: dict) -> tuple:
    """(key heads, floats of log decay a value head and position, forward
    calls a step) of a cell's delta blocks: what an adapter says
    (``delta_key_heads``, ``delta_decay_width``, ``delta_forward_calls``),
    else the form with a decay a channel: a key head a value head, a float a
    key channel, and two forward calls a checkpointed block."""
    return (shapes.get("delta_key_heads", shapes["delta_heads"]),
            shapes.get("delta_decay_width", shapes["delta_head_dim"]),
            shapes.get("delta_forward_calls", 2 * shapes["delta_layers"]))


def call_flops(shapes: dict) -> float:
    """Matmul FLOPs a token of one forward call of the chunked rule at chunk
    ``C``, the triangular products over the rows they need on the mean (a
    position's ``(C - 1) / 2`` earlier rows of its chunk, ``(C + 1) / 2``
    with its own). A KEY head: ``k k^T`` ``2 D (C - 1) / 2`` and ``q k^T``
    ``2 D (C + 1) / 2`` (value heads that share a key head share the
    products; where the decay is a channel's every value head has its own
    key head). A value head: the solve applied to ``[K * decay | V]`` ``2
    (D + D) (C + 1) / 2``, ``P R`` ``2 D (C + 1) / 2``, and three products
    with the ``D x D`` state, ``2 D D`` each. The decays, the sums and the
    forward substitution are vector work and count 0."""
    c, d, heads = (shapes[k] for k in ("delta_chunk", "delta_head_dim",
                                       "delta_heads"))
    key_heads = forms(shapes)[0]
    return (key_heads * (d * (c - 1) + d * (c + 1))
            + heads * (2 * d * (c + 1) + d * (c + 1) + 3 * 2 * d * d))


def operand_bytes(shapes: dict) -> float:
    """Bytes a token of a call's operands, each once at its dtype: q and k
    (bfloat16) at the key heads, v (bfloat16), the log decay (float32: a
    float a head, or a key channel) and beta (float32) at the value
    heads."""
    d, heads = shapes["delta_head_dim"], shapes["delta_heads"]
    key_heads, decay_width, _ = forms(shapes)
    return (2 * key_heads * d * 2 + heads * d * 2 + heads * decay_width * 4
            + heads * 4)


def delta_scan(shapes: dict) -> dict:
    """``delta_forward_calls`` forward calls a step (a checkpointed block's
    forward runs again in the backward pass), each :func:`call_flops` and
    its operands read once and o written once in float32, over ``batch *
    seq`` tokens. It is the work of the rule, not of the kernels' grid: the
    same whatever a head tile reads or computes again, and the states the
    chunks start from, which a differentiated call writes for the backward
    kernel, are that kernel pair's own choice and count 0."""
    tokens = shapes["batch"] * shapes["seq"]
    calls = forms(shapes)[2]
    written = shapes["delta_heads"] * shapes["delta_head_dim"] * 4
    return {"flops": calls * tokens * call_flops(shapes),
            "bytes": calls * tokens * (operand_bytes(shapes) + written)}
