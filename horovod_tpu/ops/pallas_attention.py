"""Flash attention as a Pallas TPU kernel — the hot op of the transformer
path.

No reference analog (the reference's only kernel is a batched-memcpy .cu,
``horovod/common/ops/cuda/cuda_kernels.cu``); on TPU the analogous "write
the hot loop yourself" target is attention. The kernel streams K/V blocks
through VMEM while Q stays resident, maintaining the flash running-softmax
(m, l, acc) in VMEM scratch so HBM traffic is O(S·D) instead of O(S²):

  grid = (batch·heads, Sq/BLOCK_Q, Sk/BLOCK_K)   — K-block innermost
  per (q-block): for each k-block: s = q @ kᵀ; online-softmax update

The kernel is DIFFERENTIABLE: a ``jax.custom_vjp`` pairs the forward
kernel (which also emits the per-row log-sum-exp residual) with a
blockwise backward pass that recomputes attention probabilities one
K-block at a time from (q, k, v, o, lse) — the standard flash-attention
backward (Dao et al.), memory-bounded at O(S·block_k) instead of O(S²),
so training through the kernel never materializes the score matrix.

Falls back to the pure-XLA implementation on CPU or when shapes don't meet
TPU tiling constraints (last dim 128-multiple, block-divisible sequence).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

BLOCK_Q = 128
BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, scale: float, causal: bool, block_q: int,
                  block_k: int):
    """One (q-block, k-block) step; grid (BH, nq, nk) with k innermost."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)           # [bq, D]
        k = k_ref[0].astype(jnp.float32)           # [bk, D]
        v = v_ref[0].astype(jnp.float32)           # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = q_idx * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            kpos = kv_idx * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[:]
        l_prev = l_ref[:]
        m_cur = jnp.max(s, axis=-1)[:, None]       # [bq, 1]
        m_next = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_next)                    # [bq, bk]
        alpha = jnp.exp(m_prev - m_next)
        l_next = l_prev * alpha + jnp.sum(p, -1)[:, None]
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_next
        l_ref[:] = l_next

    if causal:
        # skip fully-masked k-blocks (strictly above the diagonal)
        @pl.when(kv_idx * block_k <= q_idx * block_q + block_q - 1)
        def _run():
            body()
    else:
        body()

    @pl.when(kv_idx == pl.num_programs(2) - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # log-sum-exp residual for the backward pass: lse = m + log(l),
        # written lane-dense as a [1, bq] row of the [BH, 1, Sq] output
        # (a (1, bq) block of a 2-D [BH, Sq] array is not tile-aligned
        # and the TPU lowering refuses it)
        lse_ref[0, 0] = (m_ref[:] + jnp.log(l_safe))[:, 0]


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret):
    """Run the kernel; q/k/v [B, S, H, D] → (o [B, S, H, D], lse [BH, Sq])."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    # layout: fold batch & heads; blocks over sequence
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)

    nq = Sq // block_q
    nk = Sk // block_k
    grid = (B * H, nq, nk)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),   # acc
            pltpu.VMEM((block_q, 1), jnp.float32),   # m (running max)
            pltpu.VMEM((block_q, 1), jnp.float32),   # l (running sum)
        ],
        interpret=interpret,
        name="hvd_flash_attention",
    )(qf, kf, vf)
    return (out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3),
            lse.reshape(B * H, Sq))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                           interpret)


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k,
                             interpret)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    """Blockwise flash backward (Dao et al.): recompute p = exp(s - lse)
    one K-block at a time; dv = pᵀdo, ds = p⊙(do·vᵀ − Δ + dlse), dq +=
    ds·k, dk = dsᵀq. Peak extra memory O(Sq·block_k) per (batch·head).
    The lse cotangent enters through ∂lse/∂s_j = p_j (lse is the row
    log-partition), which is what makes the (o, lse) pair usable as a
    mergeable partial result (ring attention)."""
    do, dlse = cts
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bk = block_k
    nk = Sk // bk

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D).astype(jnp.float32)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D).astype(jnp.float32)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D).astype(jnp.float32)
    of = o.transpose(0, 2, 1, 3).reshape(B * H, Sq, D).astype(jnp.float32)
    dof = do.transpose(0, 2, 1, 3).reshape(B * H, Sq, D).astype(jnp.float32)

    if dlse is None:
        dlse = jnp.zeros_like(lse)
    # ds = p ⊙ (dp − Δ + dlse): fold the lse cotangent into the row term
    adj = jnp.sum(dof * of, axis=-1) - dlse.astype(jnp.float32)  # [BH, Sq]

    dq = jnp.zeros_like(qf)
    dk = jnp.zeros_like(kf)
    dv = jnp.zeros_like(vf)

    if causal and nk <= 64:
        # Statically-unrolled loop with per-block row restriction: K-block
        # j only reaches q rows >= j*bk (the rest are masked in the
        # forward), so slicing the q side halves the backward FLOPs —
        # mirroring the forward kernel's diagonal block-skip. Unrolling is
        # bounded (<= 64 blocks) to keep compile time sane; longer
        # sequences take the dynamic full-row loop below.
        for j in range(nk):
            r0 = j * bk                                     # first live row
            qs, dos = qf[:, r0:], dof[:, r0:]
            kb, vb = kf[:, r0:r0 + bk], vf[:, r0:r0 + bk]
            s = jnp.einsum("bqd,bkd->bqk", qs, kb) * scale  # [BH,Sq-r0,bk]
            qpos = r0 + jnp.arange(Sq - r0)
            kpos = r0 + jnp.arange(bk)
            s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            p = jnp.exp(s - lse[:, r0:, None])
            dvb = jnp.einsum("bqk,bqd->bkd", p, dos)
            dp = jnp.einsum("bqd,bkd->bqk", dos, vb)
            ds = p * (dp - adj[:, r0:, None]) * scale
            dq = dq.at[:, r0:].add(jnp.einsum("bqk,bkd->bqd", ds, kb))
            dk = dk.at[:, r0:r0 + bk].set(
                jnp.einsum("bqk,bqd->bkd", ds, qs))
            dv = dv.at[:, r0:r0 + bk].set(dvb)
    else:
        qpos = jnp.arange(Sq)

        def block(j, carry):
            dq, dk, dv = carry
            kb = lax.dynamic_slice_in_dim(kf, j * bk, bk, axis=1)
            vb = lax.dynamic_slice_in_dim(vf, j * bk, bk, axis=1)
            s = jnp.einsum("bqd,bkd->bqk", qf, kb) * scale  # [BH,Sq,bk]
            if causal:
                kpos = j * bk + jnp.arange(bk)
                s = jnp.where(qpos[:, None] >= kpos[None, :], s, NEG_INF)
            p = jnp.exp(s - lse[..., None])                 # [BH,Sq,bk]
            dvb = jnp.einsum("bqk,bqd->bkd", p, dof)
            dp = jnp.einsum("bqd,bkd->bqk", dof, vb)
            ds = p * (dp - adj[..., None]) * scale
            dq = dq + jnp.einsum("bqk,bkd->bqd", ds, kb)
            dkb = jnp.einsum("bqk,bqd->bkd", ds, qf)
            dk = lax.dynamic_update_slice_in_dim(dk, dkb, j * bk, axis=1)
            dv = lax.dynamic_update_slice_in_dim(dv, dvb, j * bk, axis=1)
            return dq, dk, dv

        dq, dk, dv = lax.fori_loop(0, nk, block, (dq, dk, dv))

    def unfold(x, S):
        return x.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    return (unfold(dq, Sq).astype(q.dtype), unfold(dk, Sk).astype(k.dtype),
            unfold(dv, Sk).astype(v.dtype))


_flash_lse.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                             interpret: bool = False):
    """Flash attention returning ``(o, lse)``: the normalized output plus
    the per-row log-partition (``lse`` shaped ``[B*H, Sq]``). The pair is
    a mergeable partial softmax — two results over disjoint key sets
    combine exactly via logaddexp (ring attention's per-step merge).
    Differentiable in both outputs."""
    D = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: int = BLOCK_Q, block_k: int = BLOCK_K,
                        interpret: bool = False) -> jax.Array:
    """q/k/v: [B, S, H, D] → [B, S, H, D]. Requires S % block == 0 and
    D % 128 == 0 (use :func:`attend` for the auto-fallback wrapper).
    Differentiable (custom VJP with blockwise recompute backward)."""
    D = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)[0]


def attend(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
           scale: Optional[float] = None) -> jax.Array:
    """Attention with automatic kernel selection: the Pallas flash kernel on
    TPU when shapes satisfy its tiling constraints, else the fused-XLA
    fallback. Differentiable on both paths."""
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    on_tpu = jax.default_backend() == "tpu"
    ok = (D % 128 == 0 and Sq % BLOCK_Q == 0 and Sk % BLOCK_K == 0)
    if on_tpu and ok:
        return flash_attention_tpu(q, k, v, causal, scale)
    from horovod_tpu.parallel.ring_attention import _plain_attention
    return _plain_attention(q, k, v, causal, scale)
