"""What the LM head's loss-and-gradient kernel needs at the least over the
head calls of one step of a looped model: the function ``loop_head_xent``
for ``layer_metrics/loop.head_xent_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations


def loop_head_xent(shapes: dict) -> dict:
    """``hvd_fused_xent`` as PR 29 built it, ``head_calls`` times a step:
    every bfloat16 logit read once and its gradient ``softmax - onehot``
    written over it, a float32 loss and log-sum-exp written and the label
    read per row (``2 * N * V * 2 + 12 * N`` bytes); about 9 vector
    operations a logit over its three sweeps (max; subtract, exp, add;
    subtract, exp, the label's compare and select, convert), none of them
    on the MXU, so bytes bound it."""
    rows, v = shapes["batch"] * shapes["seq"], shapes["vocab"]
    calls = shapes["head_calls"]
    return {"flops": calls * 9 * rows * v,
            "bytes": calls * (2 * rows * v * 2 + 12 * rows)}
