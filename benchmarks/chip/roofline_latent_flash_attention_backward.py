"""What the backward flash kernel's calls of one step need at the least in
a stack of latent attention blocks: the function
``latent_flash_attention_backward`` for
``layer_metrics/latent.flash_attention_bwd_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_flash_attention_backward import flash_attention_backward


def latent_flash_attention_backward(shapes: dict) -> dict:
    """One call a latent attention block (``attention_layers``: the stack's
    and the prediction module's; a checkpointed block recomputes its
    forward, not its backward), each 2.5 times one forward call's FLOPs at
    ``heads`` heads of ``head_dim``: ``flash_attention_backward``'s
    count."""
    return flash_attention_backward(
        {**shapes, "layers": shapes["attention_layers"]})
