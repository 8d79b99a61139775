"""What a kernel's calls of one step need at the least: FLOPs and bytes
from the shapes, kept with the benchmark. A function takes the adapter's
``shapes()`` (one chip's share of a step) and returns
``{"flops": .., "bytes": .., "bound": ..}`` for all of that kernel's
calls in one step; ``bound`` says which of the two the chip's peaks make
the larger time (decided by the caller from peaks.json).

A later PR adds a kernel's function as a new file ``roofline_<name>.py``
with a function ``<name>(shapes)``; a metric file names it in
``"roofline"`` and the harness looks here first, then there.
"""

from __future__ import annotations


def flash_attention_forward(shapes: dict) -> dict:
    """Forward attention over ``layers`` calls: q k^T and p v, 2 FLOPs a
    multiply-add, the causal half when ``causal`` (a query at position t
    meets t + 1 keys). Bytes: q, k, v read and o written once in
    bfloat16, the float32 log-sum-exp written once. Softmax's exp and
    max count 0: the matmuls dominate and the MXU peak is the bound that
    is compared."""
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    keys = (s + 1) / 2 if shapes["causal"] else s
    flops = 2 * 2 * b * h * s * keys * d
    nbytes = 4 * b * s * h * d * 2 + b * h * s * 4
    return {"flops": shapes["layers"] * flops,
            "bytes": shapes["layers"] * nbytes}


def fused_xent_forward(shapes: dict) -> dict:
    """Cross-entropy over the vocabulary, once a step: every bfloat16
    logit read once, a float32 loss and log-sum-exp written per row, the
    label read. About 4 elementwise operations a logit (subtract, exp,
    add, max), none of them on the MXU, so bytes bound it."""
    rows, v = shapes["batch"] * shapes["seq"], shapes["vocab"]
    return {"flops": 4 * rows * v, "bytes": rows * v * 2 + rows * 12}
