"""What the Mamba-2 scan's kernel calls of one step need at the least at ONE
group and chunk 256: the function ``dense_ssm_scan`` for
``layer_metrics/dense_ssm.ssm_scan_roofline.json`` (see roofline.py for the
form)."""

from __future__ import annotations

from roofline_hybrid_ssm_scan import hybrid_ssm_scan


def dense_ssm_scan(shapes: dict) -> dict:
    """``hybrid_ssm_scan``'s count at this cell's shapes (``ssm_groups`` 1,
    ``ssm_chunk`` 256, nine checkpointed Mamba blocks: two forward calls and
    one backward call each). It is the work of the algorithm, not of the
    kernels' grid: the scores ``c . b`` and the reads of b and c are counted
    once a group, whatever a head tile reads or computes again, so the share
    reads the same work whatever head tile implements it."""
    return hybrid_ssm_scan(shapes)
