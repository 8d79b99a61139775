"""What the routed experts' grouped matmuls of one step need at the least
where a dense layer leads the expert layers and a prediction module adds
one: the function ``latent_moe_gmm`` for
``layer_metrics/latent.moe_gmm_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations

from roofline_share_moe_gmm import share_moe_gmm


def latent_moe_gmm(shapes: dict) -> dict:
    """``share_moe_gmm``'s count (gated experts: per layer and matrix a
    forward call, an input-gradient call and a weight-gradient call, nine
    calls a layer, over ``rows = batch * seq * experts_per_token *
    held_experts / experts`` by arithmetic; PERF.md section 6, PR 43 has the
    runs' ``held_rows`` beside it) over ``routed_layers``: the expert layers
    after the leading dense ones and the prediction module's. The shared
    expert's matmuls are dense and no call of this kernel."""
    return share_moe_gmm({**shapes, "layers": shapes["routed_layers"]})
