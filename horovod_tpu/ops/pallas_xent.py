"""Fused softmax cross-entropy as a Pallas TPU kernel.

The LM-training loss over a large vocabulary is memory-bound: XLA's
unfused path materializes [N, V] intermediates several times (shifted
logits, exp, normalizer broadcast). This kernel streams V-blocks through
VMEM keeping a flash-style running (max, sum) pair plus the label's
logit in scratch, so the forward reads the logits ONCE from HBM and
writes O(N) outputs (per-row loss + log-sum-exp residual).

  grid = (N/BLOCK_N, V/BLOCK_V)   — V-block innermost
  per row-block: for each v-block: online-softmax update; pick the
  label logit with an iota mask; at the last block emit
  loss = (m + log l) - z_label.

Differentiable via ``jax.custom_vjp``: the backward is the closed form
``dlogits = g · (softmax(logits) - onehot(labels))`` computed from the
saved log-sum-exp in one fused elementwise pass (no re-reduction) — the
dense [N, V] gradient write is unavoidable, everything else is O(N).

Same contract as :mod:`ops.pallas_attention` (reference analog: the
"write the hot op yourself" role of ``cuda_kernels.cu``): a pure-XLA
fallback runs on CPU or when shapes defeat the TPU tiling; a
non-multiple vocab is padded with ``NEG_INF`` columns inside the wrapper
(softmax ignores them), so the kernel still engages for real tokenizers'
vocab sizes (e.g. 30522, 32000).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

BLOCK_N = 128
BLOCK_V = 512


def _xent_kernel(labels_ref, logits_ref, loss_ref, lse_ref, m_ref, l_ref,
                 z_ref, *, block_v: int, n_v_blocks: int):
    """One (row-block, v-block) step; grid (nn, nv) with v innermost.

    All operands/scratch are kept >= 2-D ([bn, 1] trailing unit dims, the
    same Mosaic-friendly layout convention as ``_flash_kernel``)."""
    v_idx = pl.program_id(1)

    @pl.when(v_idx == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        z_ref[:] = jnp.zeros_like(z_ref)

    s = logits_ref[...].astype(jnp.float32)            # [bn, bv]
    labels = labels_ref[...]                           # [bn, 1]
    off = v_idx * block_v
    cols = off + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    # the label's logit lives in exactly one v-block per row; an
    # out-of-range label never matches -> z stays 0 and loss = lse
    hit = cols == labels
    z_ref[:] = z_ref[...] + jnp.sum(jnp.where(hit, s, 0.0), axis=1,
                                    keepdims=True)

    m_prev = m_ref[...]                                # [bn, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    l_ref[:] = l_ref[...] * jnp.exp(m_prev - m_new) + \
        jnp.sum(jnp.exp(s - m_new), axis=1, keepdims=True)
    m_ref[:] = m_new

    @pl.when(v_idx == n_v_blocks - 1)
    def _emit():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[:] = lse
        loss_ref[:] = lse - z_ref[...]


def _xent_fwd_impl(logits, labels, block_n: int, block_v: int,
                   interpret: bool):
    n, v = logits.shape
    nn, nv = n // block_n, v // block_v
    loss, lse = pl.pallas_call(
        functools.partial(_xent_kernel, block_v=block_v, n_v_blocks=nv),
        grid=(nn, nv),
        in_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, block_v), lambda i, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_n, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_n, 1), jnp.float32),   # l (running sum)
            pltpu.VMEM((block_n, 1), jnp.float32),   # z (label logit)
        ],
        interpret=interpret,
        name="hvd_fused_xent",
    )(labels[:, None], logits)
    return loss[:, 0], lse[:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused_xent(logits, labels, block_n, block_v, interpret):
    loss, _ = _xent_fwd_impl(logits, labels, block_n, block_v, interpret)
    return loss


def _fused_xent_fwd(logits, labels, block_n, block_v, interpret):
    loss, lse = _xent_fwd_impl(logits, labels, block_n, block_v, interpret)
    return loss, (logits, labels, lse)


def _fused_xent_bwd(block_n, block_v, interpret, res, g):
    logits, labels, lse = res
    # one fused elementwise pass off the saved normalizer — XLA fuses
    # this into a single HBM sweep; the dense write is the gradient
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
              == labels[:, None])
    d = (p - onehot.astype(jnp.float32)) * g[:, None]
    return d.astype(logits.dtype), None


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


def _xla_xent(logits, labels):
    """Fallback with the SAME semantics as the kernel — deliberately NOT
    optax (which clips the gather index): an out-of-range label
    contributes no label logit, so loss = lse on BOTH paths and a CPU
    debug run reproduces the TPU loss bit-for-bit in that edge case."""
    lf = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(lf, axis=-1)
    v = lf.shape[-1]
    ok = (labels >= 0) & (labels < v)
    z = jnp.take_along_axis(
        lf, jnp.clip(labels, 0, v - 1)[..., None], axis=-1)[..., 0]
    return lse - jnp.where(ok, z, 0.0)


def fused_softmax_xent(logits: jax.Array, labels: jax.Array,
                       block_n: int = BLOCK_N, block_v: int = BLOCK_V,
                       interpret: bool = False) -> jax.Array:
    """Per-row ``-log softmax(logits)[label]`` with a single-pass fused
    TPU kernel; ``[..., V]`` logits and integer ``[...]`` labels of any
    leading shape. Vocab sizes that are not a ``block_v`` multiple are
    padded with ``NEG_INF`` columns (softmax-invisible) so the kernel
    still engages; rows that don't tile, or non-TPU backends without
    ``interpret=True``, fall back to the numerically identical XLA path.
    """
    v = logits.shape[-1]
    lead = logits.shape[:-1]
    n = 1
    for d in lead:
        n *= d
    flat = logits.reshape(n, v)
    flat_labels = labels.reshape(n).astype(jnp.int32)

    on_tpu = jax.default_backend() == "tpu"
    if (not on_tpu and not interpret) or n % block_n != 0:
        return _xla_xent(flat, flat_labels).reshape(lead)

    pad = (-v) % block_v
    if pad:
        flat = jnp.concatenate(
            [flat, jnp.full((n, pad), NEG_INF, flat.dtype)], axis=1)
    out = _fused_xent(flat, flat_labels, block_n, block_v, interpret)
    return out.reshape(lead)
