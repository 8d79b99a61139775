"""Tests of the chip benchmark's own arithmetic. They run on the CPU
(``python -m pytest benchmarks/chip/tests``) and load nothing of the TPU at
import time. Tier-1 takes three of the modules since PR 60
(``test_layer_metrics.py`` and ``test_metric_lists.py`` whole through
``tests/chip_door.py``, ``test_step_owners.py``'s cases through
``tests/test_chip_contract.py``) and runs no other."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (ROOT, CHIP):
    if path not in sys.path:
        sys.path.insert(0, path)
