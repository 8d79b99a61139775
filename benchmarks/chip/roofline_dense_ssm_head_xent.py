"""What the LM head's loss-and-gradient kernel needs at the least over the
tied, sliced table's logits: the function ``dense_ssm_head_xent`` for
``layer_metrics/dense_ssm.head_xent_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations

from roofline_loop_head_xent import loop_head_xent


def dense_ssm_head_xent(shapes: dict) -> dict:
    """``loop_head_xent``'s count at ``head_calls`` 1: one call of
    ``hvd_fused_xent`` at ``batch * seq`` rows of ``vocab`` logits, every
    bfloat16 logit read once and its gradient written over it; bytes bound
    it. (The head is not checkpointed: it runs once a step.)"""
    return loop_head_xent(shapes)
