"""What the routed experts' grouped matmuls of one step need at the least
in a stack of one-sublayer blocks whose experts are ungated and of which
the chip holds a share: the function ``hybrid_moe_gmm`` for
``layer_metrics/hybrid.moe_gmm_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations


def hybrid_moe_gmm(shapes: dict) -> dict:
    """``roofline_share_moe_gmm.share_moe_gmm``'s count with two matrices
    an expert (up and down: ``relu(h W1)^2 W2``) and an expert layer only
    in the ``expert_layers`` expert blocks: per block and matrix a forward
    call, an input-gradient call and a weight-gradient call, six calls a
    block, each ``2 * rows * d_model * d_expert`` FLOPs over ``rows = batch
    * seq * experts_per_token * held_experts / experts`` (the assignments
    that fall to the held experts under uniform routing: by arithmetic, not
    by the run's counts; PERF.md section 6, PR 39 has the runs' ``held_rows``
    beside it). Bytes in bfloat16: the stacked weights of the
    ``held_experts`` read (or, the weight gradient, written) once a call,
    the rows read and written once. The shared expert's matmuls are dense
    and no call of this kernel."""
    rows = (shapes["batch"] * shapes["seq"] * shapes["experts_per_token"]
            * shapes["held_experts"] / shapes["experts"])
    m, f, e = shapes["d_model"], shapes["d_expert"], shapes["held_experts"]
    calls = 2 * 3 * shapes["expert_layers"]
    return {"flops": calls * 2 * rows * m * f,
            "bytes": calls * 2 * (rows * m + rows * f + e * m * f)}
