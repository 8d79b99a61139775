"""The benchmark's own cases that a metric lists a cell only where the
cell's program gives its ``read`` something to read
(``benchmarks/chip/tests/test_metric_lists.py``), taken whole: one case a
(metric, cell) pair of ``BENCHMARK.json``, whatever the pairs are, so a pair
or a case that a ``benchmark`` PR adds there is tier-1's without an edit
here. A file of its own: a cell's ``tiny`` step is lowered once a process,
and under ``--dist loadfile`` that is one worker's load and no part of
another's (CPU only, nothing is compiled).

The benchmark's ``_gate`` knows the kernels its own PRs met. The sparse
core's three (PR 65, a ``perf_opt`` PR: it adds metric files and edits no
file of the benchmark's) are answered here by the program's own gate,
``sparse_path``, until a ``benchmark`` PR gives ``_gate`` that branch; so
are the delta rule's two (PR 67, the same kind of PR), by
``delta_scan_path`` at the cell's ``shapes()``: the forward's metric reads
``hvd_delta_scan(?!_bwd)``, a pattern, since the backward's name starts
with the forward's. The index score pass's backward (PR 69, again a
``perf_opt`` PR) is answered by ``sparse_path`` and the index's own shape
rule, ``index_kernel_shapes``."""

import chip_door

chip_door.take("test_metric_lists", globals())

_own = chip_door.benchmarks_own("test_metric_lists")
_benchmarks_gate = _own._gate


def _gate(kernel: str, sizes: dict, monkeypatch) -> bool:
    import jax
    from horovod_tpu.ops import pallas_sparse_attention as ps
    from horovod_tpu.ops import pallas_delta, sparse_attention
    if kernel in (pallas_delta.FWD_NAME + "(?!_bwd)", pallas_delta.BWD_NAME):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        width = sizes.get("delta_head_dim", 0)
        return sizes.get("delta_layers", 0) > 0 \
            and pallas_delta.delta_scan_path(
                sizes["seq"], sizes["delta_heads"], width, width,
                sizes["delta_chunk"]) == "kernels"
    if kernel in (ps.FWD_NAME, ps.MEAN_NAME, ps.BWD_NAME, ps.INDEX_BWD_NAME):
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        return sizes.get("index_topk", 0) > 0 and sparse_attention.sparse_path(
            sizes["seq"], sizes["heads"], sizes["kv_heads"],
            sizes["head_dim"]) == "pallas" and (
                kernel != ps.INDEX_BWD_NAME or ps.index_kernel_shapes(
                    ps.ROWS, sizes["index_heads"], sizes["index_head_dim"]))
    return _benchmarks_gate(kernel, sizes, monkeypatch)


_own._gate = _gate
