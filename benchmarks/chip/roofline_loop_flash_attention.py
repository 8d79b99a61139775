"""What the forward flash kernel's calls of one step of a looped model need
at the least: the function ``loop_flash_attention`` for
``layer_metrics/loop.flash_attention_roofline.json`` (see roofline.py for
the form)."""

from __future__ import annotations

from roofline import flash_attention_forward


def loop_flash_attention(shapes: dict) -> dict:
    """One call's FLOPs and bytes as ``roofline.flash_attention_forward``
    counts them (q k^T and p v over the causal half; q, k, v read and o
    written once in bfloat16, the float32 log-sum-exp written once), times
    ``attention_forward_calls``: the calls a step the compiled program
    makes, one a layer pass and one more for each pass whose checkpointed
    backward runs the forward kernel again."""
    one = flash_attention_forward({**shapes, "layers": 1})
    calls = shapes["attention_forward_calls"]
    return {"flops": calls * one["flops"], "bytes": calls * one["bytes"]}
