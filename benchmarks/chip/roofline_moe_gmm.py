"""What the expert layer's grouped matmuls of one step need at the least:
the function ``moe_gmm`` for ``layer_metrics/moe_gmm_roofline.json`` (see
roofline.py for the form)."""

from __future__ import annotations


def moe_gmm(shapes: dict) -> dict:
    """Every call the regex ``hvd_moe_gmm`` matches in a step: per layer and
    expert matrix (gate, up, down: three of ``d_model`` x ``d_expert``) a
    forward call, an input-gradient call and a weight-gradient call, each
    ``2 * rows * d_model * d_expert`` FLOPs over ``rows = batch * seq *
    experts_per_token`` rows whatever the groups' sizes (dropless: every
    assignment is a row). Bytes in bfloat16, the stacked weights of all
    ``experts`` read (or, the weight gradient, written) once a call, the
    rows read and written once: forward reads rows x in and the weights,
    writes rows x out; the input gradient mirrors it; the weight gradient
    reads both row matrices and writes the weights' shape."""
    rows = shapes["batch"] * shapes["seq"] * shapes["experts_per_token"]
    m, f, e = shapes["d_model"], shapes["d_expert"], shapes["experts"]
    calls = 3 * 3 * shapes["layers"]
    flops = calls * 2 * rows * m * f
    nbytes = calls * 2 * (rows * m + rows * f + e * m * f)
    return {"flops": flops, "bytes": nbytes}
