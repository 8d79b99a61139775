"""What the expert layer's grouped matmuls of one step need at the least
where the chip holds a share of every layer's experts: the function
``share_moe_gmm`` for ``layer_metrics/share.moe_gmm_roofline.json`` (see
roofline.py for the form)."""

from __future__ import annotations


def share_moe_gmm(shapes: dict) -> dict:
    """``roofline_moe_gmm.moe_gmm``'s count over the held experts: per layer
    and expert matrix (gate, up, down) a forward call, an input-gradient
    call and a weight-gradient call, each ``2 * rows * d_model * d_expert``
    FLOPs over ``rows = batch * seq * experts_per_token * held_experts /
    experts`` (the assignments that fall to the held experts under uniform
    routing: by arithmetic, not by the run's counts; the rows the dispatch
    gathers beyond them are zeros no kernel visits). Bytes in bfloat16: the
    stacked weights of the ``held_experts`` read (or, the weight gradient,
    written) once a call, the rows read and written once."""
    rows = (shapes["batch"] * shapes["seq"] * shapes["experts_per_token"]
            * shapes["held_experts"] / shapes["experts"])
    m, f, e = shapes["d_model"], shapes["d_expert"], shapes["held_experts"]
    calls = 3 * 3 * shapes["layers"]
    return {"flops": calls * 2 * rows * m * f,
            "bytes": calls * 2 * (rows * m + rows * f + e * m * f)}
