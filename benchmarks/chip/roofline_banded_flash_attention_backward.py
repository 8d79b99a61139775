"""What the backward flash kernel's calls of one step need at the least in
a stack whose layers differ in window and in query heads: the function
``banded_flash_attention_backward`` for
``layer_metrics/banded.flash_attention_bwd_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention import live_scores


def banded_flash_attention_backward(shapes: dict) -> dict:
    """One call a layer (a checkpointed block runs its forward again, not
    its backward) is 2.5 times the forward's FLOPs for that layer's kind and
    head count: five matmuls over the live scores (k q^T again, p^T do, do
    v^T, ds^T q, ds k) where the forward has two. Bytes: q, o and do read
    and dq written once at the layer's heads, k and v read and dk and dv
    written once at ``kv_heads`` (the least: the kernel writes a query
    head's part and the group's sum is taken outside), in bfloat16; the
    float32 log-sum-exp and row term read once a query head."""
    b, s, d = (shapes[k] for k in ("batch", "seq", "head_dim"))
    kv = shapes["kv_heads"]
    flops = nbytes = 0
    for h, window in zip(shapes["layer_heads"], shapes["layer_windows"]):
        flops += 2.5 * 2 * 2 * b * h * d * live_scores(s, window)
        nbytes += 4 * b * s * (h + kv) * d * 2 + 2 * b * h * s * 4
    return {"flops": flops, "bytes": nbytes}
