"""What the gated delta rule's backward scan kernel's calls of one step need
at the least: the function ``delta_scan_backward`` for
``layer_metrics/delta_scan_bwd_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations

from roofline_delta_scan import call_flops, operand_bytes


def delta_scan_backward(shapes: dict) -> dict:
    """One call a delta block a step (``delta_layers``: a checkpointed block
    recomputes its forward, not its backward). FLOPs: two products for each
    of the forward call's (``roofline_delta_scan.call_flops``), as if what
    the forward made were at hand; that the kernel makes a chunk's pairs,
    inverse, ``W``, ``U`` and ``R`` again is its own choice and counts 0.
    Bytes, each array once at its dtype: the forward call's operands and o's
    cotangent (float32) read, the operands' cotangents written at the
    operands' dtypes and heads (dq and dk at the key heads); the states the
    chunks start from count 0, as in the forward's function."""
    tokens = shapes["batch"] * shapes["seq"]
    cotangent = shapes["delta_heads"] * shapes["delta_head_dim"] * 4
    return {"flops": shapes["delta_layers"] * tokens * 2 * call_flops(shapes),
            "bytes": shapes["delta_layers"] * tokens * (
                2 * operand_bytes(shapes) + cotangent)}
