"""Gradient compression subsystem.

Replaces the cast-only module the reference shipped
(``horovod/torch/compression.py``) with a real codec layer:

* :mod:`~horovod_tpu.compression.base` — the ``Compressor`` contract +
  fp16/bf16 dtype casts (reference parity),
* :mod:`~horovod_tpu.compression.quantizers` — block-wise int8
  (EQuARX-style, Pallas-accelerated on TPU), fp8 (e4m3/e5m2) and 1-bit
  sign+mean codecs,
* :mod:`~horovod_tpu.compression.error_feedback` — residual-carrying EF
  so lossy codecs converge,
* :mod:`~horovod_tpu.compression.metrics` — pre/wire byte counters and
  the compression-ratio gauge on ``/metrics``.

Transport integration: ``DistributedGradTransform(compression=...)``
(and ``DistributedOptimizer``) accept any of these — including
``ErrorFeedback(...)``-wrapped codecs;
``ops.collectives.quantized_allreduce`` and
``ops.mesh_collectives.device_allreduce(compression=...)`` are the
quantized wire paths.
"""

from horovod_tpu.compression.base import (  # noqa: F401
    BF16Compressor,
    Compressor,
    FP16Compressor,
    NoneCompressor,
)
from horovod_tpu.compression.quantizers import (  # noqa: F401
    BlockInt8Quantizer,
    FP8Quantizer,
    OneBitQuantizer,
    Quantized,
    QuantSpec,
    Quantizer,
    fp8_supported,
    resolve_compressor,
)
from horovod_tpu.compression.error_feedback import (  # noqa: F401
    EFState,
    ErrorFeedback,
    ef_apply,
    error_feedback_transform,
    init_residual,
)
from horovod_tpu.compression.metrics import (  # noqa: F401
    compression_ratio,
    record_compression,
)


class Compression:
    """Namespace matching the reference's public surface
    (``hvd.Compression.none`` / ``.fp16``; ``compression.py:65-75``),
    grown with the quantizing codecs. ``int8``/``onebit`` are default
    instances; construct :class:`BlockInt8Quantizer` /
    :class:`FP8Quantizer` directly for non-default block sizes or
    flavors."""

    none = NoneCompressor
    fp16 = FP16Compressor
    bf16 = BF16Compressor
    int8 = BlockInt8Quantizer()
    onebit = OneBitQuantizer()


if fp8_supported():
    Compression.fp8_e4m3 = FP8Quantizer("e4m3")
    Compression.fp8_e5m2 = FP8Quantizer("e5m2")
    Compression.fp8 = Compression.fp8_e4m3
