"""What the backward flash kernel's calls of one step need at the least in
a stack of window and full layers with grouped heads: the function
``mixed_flash_attention_backward`` for
``layer_metrics/mixed.flash_attention_bwd_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention import mixed_flash_attention


def mixed_flash_attention_backward(shapes: dict) -> dict:
    """One call a layer is 2.5 times the FLOPs the forward's function
    counts for that layer's kind: five matmuls over the live scores (k q^T
    again, p^T do, do v^T, ds^T q, ds k) where the forward has two. Bytes:
    q, o and do read and dq written once at ``heads``, k and v read and dk
    and dv written once at ``kv_heads`` (the least: the kernel writes a
    query head's part and the group's sum is taken outside), in bfloat16;
    the float32 log-sum-exp and row term read once a query head."""
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    kv = shapes["kv_heads"]
    nbytes = 4 * b * s * (h + kv) * d * 2 + 2 * b * h * s * 4
    return {"flops": 2.5 * mixed_flash_attention(shapes)["flops"],
            "bytes": len(shapes["layer_windows"]) * nbytes}
