"""Back-compat shim: the compression subsystem moved to
:mod:`horovod_tpu.compression` (quantizers, error feedback, Pallas
kernels, wire paths).

This module keeps the original import surface
(``horovod_tpu.train.compression.Compression`` et al., mirroring the
reference's ``horovod/torch/compression.py``) alive for existing
callers; new code should import from ``horovod_tpu.compression``.
"""

from __future__ import annotations

from horovod_tpu.compression import (  # noqa: F401
    BF16Compressor,
    Compression,
    Compressor,
    ErrorFeedback,
    FP16Compressor,
    NoneCompressor,
)
from horovod_tpu.compression.base import _astype  # noqa: F401
