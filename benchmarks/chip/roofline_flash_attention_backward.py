"""What the backward flash kernel's calls of one step need at the least:
the function ``flash_attention_backward`` for
``layer_metrics/flash_attention_bwd_roofline.json`` (see roofline.py for
the form)."""

from __future__ import annotations

from roofline import flash_attention_forward


def flash_attention_backward(shapes: dict) -> dict:
    """One call is 2.5 times the FLOPs ``roofline.flash_attention_forward``
    counts for one call: five matmuls over the causal half (k q^T again, p^T
    do, do v^T, ds^T q, ds k) where the forward has two, nothing recomputed
    beyond that one score tile. Bytes: q, k, v, o and do read and dq, dk, dv
    written once in bfloat16, the float32 log-sum-exp and the float32 row
    term (the row sums of do * o, less the log-sum-exp's cotangent) read
    once. Times the calls a step: one a layer, and for a looped model one a
    layer pass (``loops``; a checkpointed pass recomputes its forward, not
    its backward)."""
    one = flash_attention_forward({**shapes, "layers": 1})
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    calls = shapes["layers"] * shapes.get("loops", 1)
    nbytes = 8 * b * s * h * d * 2 + 2 * b * h * s * 4
    return {"flops": calls * 2.5 * one["flops"], "bytes": calls * nbytes}
