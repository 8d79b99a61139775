"""What the backward flash kernel's calls of one step need at the least at
a head of 64: the function ``dense_ssm_flash_attention_backward`` for
``layer_metrics/dense_ssm.flash_attention_bwd_roofline.json`` (see
roofline.py for the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention_backward import (
    mixed_flash_attention_backward)


def dense_ssm_flash_attention_backward(shapes: dict) -> dict:
    """One call an attention block, 2.5 times ONE forward call's FLOPs; k
    and v read, dk and dv written at ``kv_heads``:
    ``mixed_flash_attention_backward``'s count over ``layer_windows``, a
    None for each attention block. Required FLOPs at ``head_dim`` 64
    against the chip's full peak: the share reads low."""
    return mixed_flash_attention_backward(shapes)
