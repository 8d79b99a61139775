"""What the forward flash kernel's call of one step needs at the least at a
head of 64: the function ``dense_ssm_flash_attention`` for
``layer_metrics/dense_ssm.flash_attention_roofline.json`` (see roofline.py
for the form)."""

from __future__ import annotations

from roofline_mixed_flash_attention import mixed_flash_attention


def dense_ssm_flash_attention(shapes: dict) -> dict:
    """One full causal call an attention block (``layer_windows`` holds a
    None for each; the block is not checkpointed), q read and o written at
    ``heads``, k and v read once at ``kv_heads``:
    ``mixed_flash_attention``'s count. The FLOPs are the required ones at
    ``head_dim`` 64 and are held against the chip's full peak, of which a
    contraction over 64 of the MXU's 128 rows reaches half at the most."""
    return mixed_flash_attention(shapes)
