"""What the forward flash kernel's calls of one step need at the least in a
stack whose layers differ in window AND in query heads: the function
``banded_flash_attention`` for
``layer_metrics/banded.flash_attention_roofline.json`` (see roofline.py for
the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention import live_scores


def layer_calls(shapes: dict) -> list:
    """``[(query heads, window or None)]`` of the forward kernel's calls of
    one step: one a layer at the layer's own head count
    (``layer_heads``) and kind (``layer_windows``), and where the attention
    blocks are checkpointed (``attention_forward_calls`` twice the layers)
    each once more."""
    layers = list(zip(shapes["layer_heads"], shapes["layer_windows"]))
    return layers * (shapes["attention_forward_calls"] // len(layers))


def banded_flash_attention(shapes: dict) -> dict:
    """A call: q k^T and p v, 2 FLOPs a multiply-add, over the LIVE scores
    of the layer's kind (a window of 512 under 8192 positions: 512 keys a
    query, not the tiles a kernel runs to cover them) for each of the
    layer's own query heads. Bytes: q read and o written once at the layer's
    heads, k and v read once at ``kv_heads`` (every query head of a group
    reads the same k/v head: the least is once), in bfloat16; the float32
    log-sum-exp written once a query head. Softmax's exp and max count 0, as
    in roofline.py."""
    b, s, d = (shapes[k] for k in ("batch", "seq", "head_dim"))
    kv = shapes["kv_heads"]
    flops = nbytes = 0
    for h, window in layer_calls(shapes):
        flops += 2 * 2 * b * h * d * live_scores(s, window)
        nbytes += 2 * b * s * (h + kv) * d * 2 + b * h * s * 4
    return {"flops": flops, "bytes": nbytes}
