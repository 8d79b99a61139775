"""What the forward flash kernel's calls of one step need at the least in a
stack of latent attention blocks: the function ``latent_flash_attention``
for ``layer_metrics/latent.flash_attention_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_loop_flash_attention import loop_flash_attention


def latent_flash_attention(shapes: dict) -> dict:
    """Keys and values come up from the latent for every head before the
    core, so a call is plain multi-head attention at ``heads`` heads of
    ``head_dim`` = nope + rope channels, values as wide: one call's FLOPs
    and bytes as ``roofline.flash_attention_forward`` counts them (q k^T and
    p v over the causal half; q, k, v read and o written once in bfloat16,
    the float32 log-sum-exp written once), times
    ``attention_forward_calls``: one a latent attention block (the
    prediction module's among them) and one more for each block whose
    checkpointed backward runs the forward kernel again;
    ``loop_flash_attention``'s count."""
    return loop_flash_attention(shapes)
