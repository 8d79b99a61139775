"""The benchmark's own cases of the door between ``BENCHMARK.json``'s
``per_layer`` entries, the files of ``layer_metrics/`` and
``run.read_layer_metric`` (``benchmarks/chip/tests/test_layer_metrics.py``),
taken whole with their module fixture: every entry has its file, file and
entry agree, every ``read`` is a kind the harness dispatches (two cases a
metric, however many metrics there are), an unknown kind raises, a reader
the harness lacks is a file, and the recorded step reads through the
harness as ``scope_reduce`` reads it alone. Not taken: the case that pins
which entries stand in which places, which is the benchmark's to keep or
change."""

import chip_door

chip_door.take("test_layer_metrics", globals(), fixtures=("recorded",),
               but=("test_the_accepted_entries_stand_first_and_as_they_were",))
