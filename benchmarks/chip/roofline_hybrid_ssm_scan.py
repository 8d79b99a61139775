"""What the Mamba-2 scan's kernel calls of one step need at the least: the
function ``hybrid_ssm_scan`` for
``layer_metrics/hybrid.ssm_scan_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations


def hybrid_ssm_scan(shapes: dict) -> dict:
    """Per Mamba block two forward calls (the block is checkpointed: its
    forward runs again in the backward pass) and one backward call, over
    ``tokens = batch * seq`` positions in chunks of ``ssm_chunk``.

    FLOPs a token and forward call, as ``adapters/nemotron_h.py:
    flops_per_token`` counts the scan (a position meets ``(Q + 1) / 2`` of
    its chunk): the scores ``c . b`` ``2 G N (Q + 1) / 2``, the scores
    times x ``2 H P (Q + 1) / 2``, a chunk's state ``x^T b`` ``2 H P N``
    and the carried state's part ``c . H`` ``2 H P N``. The backward call:
    two products for each of those four, and the scores and ``c . H`` made
    again (nothing ``[Q, Q]``-sized is kept).

    Bytes, each array once at its dtype: a forward call reads x (bfloat16),
    b and c (bfloat16), dt and the sums s (float32) and writes y (float32);
    the backward call reads those and y's cotangent (float32) and writes
    dx, db, dc (bfloat16), d dt and d s (float32); the states the chunks
    start from ``[seq / Q, H, P, N]`` float32 are written once (by the
    forward call under differentiation) and read once (by the backward
    call): what a form that keeps them costs at the least, not what the
    kernels happen to move. The float32 decays ``exp(s_i - s_j)`` of every
    head are vector work and count 0."""
    tokens = shapes["batch"] * shapes["seq"]
    heads, p, n, g, q = (shapes[k] for k in (
        "ssm_heads", "ssm_head_dim", "ssm_state", "ssm_groups", "ssm_chunk"))
    in_chunk = (q + 1) / 2
    scores = 2 * g * n * in_chunk
    from_state = 2 * heads * p * n
    forward = scores + 2 * heads * p * in_chunk + 2 * from_state
    backward = 2 * forward + scores + from_state
    operands = heads * p * 2 + 2 * g * n * 2 + 2 * heads * 4
    forward_bytes = operands + heads * p * 4
    backward_bytes = (operands + heads * p * 4
                      + heads * p * 2 + 2 * g * n * 2 + 2 * heads * 4)
    states = 2 * heads * p * n * 4 / q
    blocks = shapes["mamba_layers"]
    return {"flops": blocks * tokens * (2 * forward + backward),
            "bytes": blocks * tokens * (2 * forward_bytes + backward_bytes
                                        + states)}
