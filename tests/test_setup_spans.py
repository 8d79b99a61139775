"""The benchmark's own cases of ``readers/setup_spans.py``
(``benchmarks/chip/tests/test_setup_spans.py``), taken whole with their
fixture: the eight ``setup.*`` metrics of PR 68 on a ring written as a
run's set-up leaves it, a missing record, a program without the spans."""

import chip_door

chip_door.take("test_setup_spans", globals(),
               fixtures=("_leave_the_ring_empty",))
