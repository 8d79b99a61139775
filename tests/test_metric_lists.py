"""The benchmark's own cases that a metric lists a cell only where the
cell's program gives its ``read`` something to read
(``benchmarks/chip/tests/test_metric_lists.py``), taken whole: one case a
(metric, cell) pair of ``BENCHMARK.json``, whatever the pairs are, so a pair
or a case that a ``benchmark`` PR adds there is tier-1's without an edit
here. A file of its own: a cell's ``tiny`` step is lowered once a process,
and under ``--dist loadfile`` that is one worker's load and no part of
another's (CPU only, nothing is compiled)."""

import chip_door

chip_door.take("test_metric_lists", globals())
