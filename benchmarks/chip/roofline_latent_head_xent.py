"""What the LM head's loss-and-gradient kernel needs at the least over the
head calls of one step of a model with a multi-token-prediction module: the
function ``latent_head_xent`` for
``layer_metrics/latent.head_xent_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations

from roofline_loop_head_xent import loop_head_xent


def latent_head_xent(shapes: dict) -> dict:
    """``hvd_fused_xent`` ``head_calls`` times a step, the main head's call
    and the prediction module's through the same head, each over every
    position and the vocabulary slice: ``loop_head_xent``'s count (every
    bfloat16 logit read once and its gradient written over it; bytes bound
    it)."""
    return loop_head_xent(shapes)
