"""Block-wise int8 quantize/dequantize as Pallas TPU kernels.

The hot path of a quantized gradient exchange is the codec itself: for a
gradient of N floats the quantizer reads N floats and writes N bytes +
N/block scales, and the dequantizer does the reverse — both pure
streaming passes that XLA happily splits into several HBM sweeps
(abs, max-reduce, divide, round, cast). Each kernel here does its whole
block's work in one VMEM round trip: a [rows, block] tile is read once,
the per-row absmax/scale is computed in registers, and the int8 payload
plus the fp32 scale column are written back — one read, two writes,
nothing rematerialized.

Layout contract (same convention as :mod:`ops.pallas_xent`): operands
are 2-D ``[n_blocks, block]`` with ``block`` on the lane dimension
(multiple of 128) and blocks tiled ``ROWS`` at a time on the sublane
dimension (32, the int8 sublane tile). Scales ride as ``[n_blocks, 1]``.

A pure-XLA fallback with the same semantics (round-half-to-even, same
zero-block guard) runs on CPU or when shapes defeat the tiling; scales
agree with the kernel to 1 ULP of the ``absmax/127`` division, payloads
to ±1 code. ``interpret=True`` exercises the kernel itself off-TPU
(tier-1 CI).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# int8 native tile is (32, 128): 32 blocks per grid step, lane dim must
# be a 128-multiple for the kernel to engage.
ROWS = 32


def _quantize_kernel(x_ref, vals_ref, scales_ref):
    """One [ROWS, block] tile: per-row absmax -> scale -> rounded int8."""
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    # all-zero (or padding) blocks quantize through scale 1 -> zeros
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    scales_ref[:] = scale
    vals_ref[:] = jnp.clip(jnp.round(x / scale), -127.0, 127.0
                           ).astype(jnp.int8)


def _dequantize_kernel(vals_ref, scales_ref, out_ref):
    out_ref[:] = vals_ref[...].astype(jnp.float32) * scales_ref[...]


def _xla_quantize(blocks):
    """Fallback with the SAME semantics as the kernel (jnp.round is
    round-half-to-even on both paths; scales agree to 1 ULP)."""
    x = blocks.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    vals = jnp.clip(jnp.round(x / scale), -127.0, 127.0).astype(jnp.int8)
    return vals, scale


def _xla_dequantize(vals, scales):
    return vals.astype(jnp.float32) * scales


def _kernel_ok(n_blocks: int, block: int, interpret: bool) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    return (on_tpu or interpret) and block % 128 == 0 and n_blocks > 0


def block_quantize(blocks: jax.Array, interpret: bool = False):
    """``[n_blocks, block]`` floats -> ``(int8 values [n_blocks, block],
    fp32 scales [n_blocks, 1])`` with per-block scale ``absmax/127``.

    Engages the fused kernel on TPU (or under ``interpret=True``
    anywhere); other backends and non-128-multiple blocks take the
    numerically identical XLA path. Rows are padded to the 32-row int8
    tile internally and stripped on return.
    """
    n_blocks, block = blocks.shape
    if not _kernel_ok(n_blocks, block, interpret):
        return _xla_quantize(blocks)
    pad = (-n_blocks) % ROWS
    if pad:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((pad, block), blocks.dtype)], axis=0)
    n = n_blocks + pad
    vals, scales = pl.pallas_call(
        _quantize_kernel,
        grid=(n // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, block), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_block_quantize",
    )(blocks)
    if pad:
        vals, scales = vals[:n_blocks], scales[:n_blocks]
    return vals, scales


def _quantize_ef_kernel(x_ref, vals_ref, scales_ref, res_ref):
    """Quantize + error-feedback residual in ONE pass: the residual
    (``x − codes·scale``) is what a separate dequantize would have to
    re-read the whole payload to compute — here it falls out of the
    registers that just produced the codes."""
    x = x_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.where(absmax > 0.0, absmax / 127.0, 1.0)
    scales_ref[:] = scale
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    vals_ref[:] = q.astype(jnp.int8)
    res_ref[:] = x - q * scale


def _xla_quantize_ef(blocks):
    x = blocks.astype(jnp.float32)
    vals, scale = _xla_quantize(blocks)
    return vals, scale, x - vals.astype(jnp.float32) * scale


def block_quantize_ef(blocks: jax.Array, interpret: bool = False):
    """``[n_blocks, block]`` floats -> ``(int8 values, fp32 scales
    [n_blocks, 1], fp32 residual [n_blocks, block])`` where ``residual =
    blocks − dequantize(values, scales)`` — the error-feedback carry,
    produced in the same VMEM round trip as the codes instead of by a
    second dequantize sweep (:mod:`train.fused_apply`)."""
    n_blocks, block = blocks.shape
    if not _kernel_ok(n_blocks, block, interpret):
        return _xla_quantize_ef(blocks)
    pad = (-n_blocks) % ROWS
    if pad:
        blocks = jnp.concatenate(
            [blocks, jnp.zeros((pad, block), blocks.dtype)], axis=0)
    n = n_blocks + pad
    vals, scales, res = pl.pallas_call(
        _quantize_ef_kernel,
        grid=(n // ROWS,),
        in_specs=[pl.BlockSpec((ROWS, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, block), jnp.int8),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, block), jnp.float32),
        ],
        interpret=interpret,
        name="hvd_block_quantize_ef",
    )(blocks)
    if pad:
        vals, scales, res = vals[:n_blocks], scales[:n_blocks], \
            res[:n_blocks]
    return vals, scales, res


def block_dequantize(vals: jax.Array, scales: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """Inverse of :func:`block_quantize`: ``values * scale`` per block,
    returned as float32 ``[n_blocks, block]``."""
    n_blocks, block = vals.shape
    if not _kernel_ok(n_blocks, block, interpret):
        return _xla_dequantize(vals, scales)
    pad = (-n_blocks) % ROWS
    if pad:
        vals = jnp.concatenate(
            [vals, jnp.zeros((pad, block), vals.dtype)], axis=0)
        scales = jnp.concatenate(
            [scales, jnp.ones((pad, 1), scales.dtype)], axis=0)
    n = n_blocks + pad
    out = pl.pallas_call(
        _dequantize_kernel,
        grid=(n // ROWS,),
        in_specs=[
            pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
            pl.BlockSpec((ROWS, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((ROWS, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, block), jnp.float32),
        interpret=interpret,
        name="hvd_block_dequantize",
    )(vals, scales)
    return out[:n_blocks] if pad else out


# ---------------------------------------------------------------------------
# Fused dequantize + optimizer apply (the tail of train/overlap.py)
#
# After a quantized gradient exchange the tail used to be three separate
# HBM sweeps: dequantize codes -> fp32 gradient, momentum update, delta.
# Each kernel below reads the int8 codes + scales + the optimizer
# moments ONCE, does the whole dequantize->moment->delta chain in
# registers, and writes the delta + new moments back — one VMEM round
# trip for the entire optimizer tail. Scalar hyperparameters ride in
# SMEM. The delta is optax-convention (``params += delta``), so the
# caller's ``optax.apply_updates`` add fuses with the surrounding graph.
# ---------------------------------------------------------------------------


def _fused_sgd0_kernel(h_ref, vals_ref, scales_ref, delta_ref):
    # h = [lr]
    g = vals_ref[...].astype(jnp.float32) * scales_ref[...]
    delta_ref[:] = -h_ref[0] * g


def _fused_sgd_kernel(h_ref, vals_ref, scales_ref, mom_ref,
                      delta_ref, nmom_ref):
    # h = [lr, momentum]; optax.sgd trace: t = g + mu*t_prev
    g = vals_ref[...].astype(jnp.float32) * scales_ref[...]
    m = g + h_ref[1] * mom_ref[...]
    nmom_ref[:] = m
    delta_ref[:] = -h_ref[0] * m


def _fused_adam_kernel(h_ref, vals_ref, scales_ref, m_ref, v_ref,
                       delta_ref, nm_ref, nv_ref):
    # h = [lr, b1, b2, eps, bc1, bc2] with bcK = 1 - bK**t (optax
    # bias_correction at count t, computed by the caller)
    g = vals_ref[...].astype(jnp.float32) * scales_ref[...]
    b1, b2 = h_ref[1], h_ref[2]
    m = b1 * m_ref[...] + (1.0 - b1) * g
    v = b2 * v_ref[...] + (1.0 - b2) * g * g
    nm_ref[:] = m
    nv_ref[:] = v
    delta_ref[:] = -h_ref[0] * (m / h_ref[4]) / \
        (jnp.sqrt(v / h_ref[5]) + h_ref[3])


def _xla_fused_sgd(h, vals, scales, mom):
    g = vals.astype(jnp.float32) * scales
    if mom is None:
        return -h[0] * g, None
    m = g + h[1] * mom
    return -h[0] * m, m


def _xla_fused_adam(h, vals, scales, m, v):
    g = vals.astype(jnp.float32) * scales
    m = h[1] * m + (1.0 - h[1]) * g
    v = h[2] * v + (1.0 - h[2]) * g * g
    delta = -h[0] * (m / h[4]) / (jnp.sqrt(v / h[5]) + h[3])
    return delta, m, v


def _pad_rows(x, pad, fill=0.0):
    return jnp.concatenate(
        [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0)


def fused_sgd_apply(vals: jax.Array, scales: jax.Array, mom, lr, momentum,
                    interpret: bool = False):
    """int8 codes + scales (+ momentum blocks) -> ``(delta, new_mom)``:
    dequantize and the optax ``sgd(lr, momentum)`` update in one fused
    pass. ``mom=None`` selects the momentum-free variant (``new_mom`` is
    None). ``lr``/``momentum`` may be traced scalars."""
    n_blocks, block = vals.shape
    if not _kernel_ok(n_blocks, block, interpret):
        h = jnp.stack([jnp.float32(lr), jnp.float32(momentum)])
        return _xla_fused_sgd(h, vals, scales, mom)
    from jax.experimental.pallas import tpu as pltpu
    pad = (-n_blocks) % ROWS
    if pad:
        vals = _pad_rows(vals, pad)
        scales = _pad_rows(scales, pad, 1.0)
        if mom is not None:
            mom = _pad_rows(mom, pad)
    n = n_blocks + pad
    tile = lambda r: pl.BlockSpec((ROWS, r), lambda i: (i, 0))  # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    if mom is None:
        h = jnp.stack([jnp.float32(lr)])
        delta = pl.pallas_call(
            _fused_sgd0_kernel,
            grid=(n // ROWS,),
            in_specs=[smem, tile(block), tile(1)],
            out_specs=tile(block),
            out_shape=jax.ShapeDtypeStruct((n, block), jnp.float32),
            interpret=interpret,
            name="hvd_fused_sgd_apply",
        )(h, vals, scales)
        new_mom = None
    else:
        h = jnp.stack([jnp.float32(lr), jnp.float32(momentum)])
        delta, new_mom = pl.pallas_call(
            _fused_sgd_kernel,
            grid=(n // ROWS,),
            in_specs=[smem, tile(block), tile(1), tile(block)],
            out_specs=[tile(block), tile(block)],
            out_shape=[jax.ShapeDtypeStruct((n, block), jnp.float32),
                       jax.ShapeDtypeStruct((n, block), jnp.float32)],
            interpret=interpret,
            name="hvd_fused_sgd_apply",
        )(h, vals, scales, mom)
        new_mom = new_mom[:n_blocks] if pad else new_mom
    return (delta[:n_blocks] if pad else delta), new_mom


def fused_adam_apply(vals: jax.Array, scales: jax.Array, m: jax.Array,
                     v: jax.Array, lr, b1, b2, eps, bc1, bc2,
                     interpret: bool = False):
    """int8 codes + scales + Adam moments -> ``(delta, new_m, new_v)``
    with optax.adam numerics (``bc1``/``bc2`` are the caller-computed
    ``1 − βₖᵗ`` bias corrections — traced scalars are fine)."""
    n_blocks, block = vals.shape
    h = jnp.stack([jnp.float32(lr), jnp.float32(b1), jnp.float32(b2),
                   jnp.float32(eps), jnp.float32(bc1), jnp.float32(bc2)])
    if not _kernel_ok(n_blocks, block, interpret):
        return _xla_fused_adam(h, vals, scales, m, v)
    from jax.experimental.pallas import tpu as pltpu
    pad = (-n_blocks) % ROWS
    if pad:
        vals = _pad_rows(vals, pad)
        scales = _pad_rows(scales, pad, 1.0)
        m = _pad_rows(m, pad)
        v = _pad_rows(v, pad)
    n = n_blocks + pad
    tile = lambda r: pl.BlockSpec((ROWS, r), lambda i: (i, 0))  # noqa: E731
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    delta, nm, nv = pl.pallas_call(
        _fused_adam_kernel,
        grid=(n // ROWS,),
        in_specs=[smem, tile(block), tile(1), tile(block), tile(block)],
        out_specs=[tile(block), tile(block), tile(block)],
        out_shape=[jax.ShapeDtypeStruct((n, block), jnp.float32)] * 3,
        interpret=interpret,
        name="hvd_fused_adam_apply",
    )(h, vals, scales, m, v)
    if pad:
        delta, nm, nv = delta[:n_blocks], nm[:n_blocks], nv[:n_blocks]
    return delta, nm, nv
