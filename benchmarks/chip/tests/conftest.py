"""Tests of the chip benchmark's own arithmetic. They run on the CPU
(``python -m pytest benchmarks/chip/tests``), are no part of tier-1, and
load nothing of the TPU at import time."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(CHIP))
for path in (ROOT, CHIP):
    if path not in sys.path:
        sys.path.insert(0, path)
