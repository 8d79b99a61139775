"""What the forward flash kernel's calls of one step need at the least in a
stack of one-sublayer blocks: the function ``hybrid_flash_attention`` for
``layer_metrics/hybrid.flash_attention_roofline.json`` (see roofline.py for
the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention import mixed_flash_attention


def hybrid_flash_attention(shapes: dict) -> dict:
    """One full causal call an attention block (``layer_windows`` holds a
    None for each: the adapter counts the attention blocks only), q read
    and o written at ``heads``, k and v read once at ``kv_heads``:
    ``mixed_flash_attention``'s count, which takes every layer by its
    kind."""
    return mixed_flash_attention(shapes)
