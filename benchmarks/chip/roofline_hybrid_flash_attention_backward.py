"""What the backward flash kernel's calls of one step need at the least in
a stack of one-sublayer blocks: the function
``hybrid_flash_attention_backward`` for
``layer_metrics/hybrid.flash_attention_bwd_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention_backward import (
    mixed_flash_attention_backward)


def hybrid_flash_attention_backward(shapes: dict) -> dict:
    """One call an attention block, 2.5 times the forward's FLOPs; k and v
    read, dk and dv written at ``kv_heads``:
    ``mixed_flash_attention_backward``'s count over ``layer_windows``, a
    None for each attention block."""
    return mixed_flash_attention_backward(shapes)
