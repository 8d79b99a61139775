"""What the forward flash kernel's calls of one step need at the least in a
stack of window and full layers with grouped heads: the function
``mixed_flash_attention`` for
``layer_metrics/mixed.flash_attention_roofline.json`` (see roofline.py for
the form)."""

from __future__ import annotations


def live_scores(seq: int, window) -> float:
    """(query, key) pairs a causal head computes: a query at ``t`` meets
    ``t + 1`` keys, at most ``window``."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def mixed_flash_attention(shapes: dict) -> dict:
    """One call a layer: q k^T and p v, 2 FLOPs a multiply-add, over the
    live scores of the layer's kind (``layer_windows``: a window or None,
    the whole causal half) for each of ``heads`` query heads. Bytes: q read
    and o written once at ``heads``, k and v read once at ``kv_heads``
    (every query head of a group reads the same k/v head: the least is
    once), in bfloat16; the float32 log-sum-exp written once a query head.
    Softmax's exp and max count 0, as in roofline.py."""
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    kv = shapes["kv_heads"]
    flops = sum(2 * 2 * b * h * d * live_scores(s, w)
                for w in shapes["layer_windows"])
    nbytes = 2 * b * s * (h + kv) * d * 2 + b * h * s * 4
    return {"flops": flops, "bytes": len(shapes["layer_windows"]) * nbytes}
