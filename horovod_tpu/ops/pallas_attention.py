"""Flash attention as a Pallas TPU kernel — the hot op of the transformer
path.

No reference analog (the reference's only kernel is a batched-memcpy .cu,
``horovod/common/ops/cuda/cuda_kernels.cu``); on TPU the analogous "write
the hot loop yourself" target is attention. The kernel streams K/V tiles
through VMEM while a Q tile stays resident, maintaining the flash
running-softmax (m, l, acc) in VMEM scratch so HBM traffic is O(S·D)
instead of O(S²):

  grid = (batch·heads, Sq/block_q, Sk/block_k)   — K tile innermost
  per (q tile): for each k tile: s = q @ kᵀ; online-softmax update

**Tiles come from the shape** (:func:`flash_blocks`): the largest of
1024/512/256/128 that divide ``Sq`` / ``Sk``, the q tile halved until
the working set fits ``VMEM_BUDGET``. A grid step costs about 0.35 µs
whatever it does, so a 128 × 128 tile (0.04 µs of MXU work at bf16) is
all overhead: at B2·S2048·H16·D128 causal a call takes 2.59 ms with
128 × 128 tiles and 0.48 ms with 1024 × 1024 (v5e; PERF.md, PR 25).
Callers pass no block; ``block_q`` / ``block_k`` are overrides for
tests. The contract to callers is only ``MIN_BLOCK``: sequence lengths
and head_dim are multiples of 128.

**Dtypes.** q·kᵀ and p·v multiply operands in the dtype the caller passed
(bf16 in training: what the MXU multiplies; float32 inputs give float32
matmuls) and accumulate in float32; ``p`` is cast to ``v``'s dtype for
p·v. The scale, the mask, the running max ``m``, the running sum ``l``,
the accumulator and the log-sum-exp are float32 for every input dtype.

**Causal.** A tile strictly above the diagonal runs no work and fetches
nothing: the K/V index maps clamp to the q tile's last live tile, and
Pallas issues no DMA for a block index that repeats. Only the tiles the
diagonal crosses build the iota mask; those below it skip it.

The kernel is DIFFERENTIABLE: a ``jax.custom_vjp`` pairs the forward
kernel (which also emits the per-row log-sum-exp residual) with a
blockwise backward pass that recomputes attention probabilities one
K-block at a time from (q, k, v, o, lse) — the standard flash-attention
backward (Dao et al.), memory-bounded at O(S·BWD_BLOCK_K) instead of
O(S²), so training through the kernel never materializes the score
matrix. The backward is XLA code with a block of its own
(``BWD_BLOCK_K``): its float32 temporaries grow with the block, so the
forward's tile does not reach it.

Falls back to the pure-XLA implementation on CPU or when shapes don't meet
TPU tiling constraints (last dim 128-multiple, 128-divisible sequence).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: what callers are held to: Sq, Sk and head_dim are multiples of this
#: (the TPU's lane count; the smallest tile)
MIN_BLOCK = 128
#: K-block of the XLA backward (``_flash_bwd``): sets its float32
#: ``[B·H, Sq − r0, bk]`` temporaries and the count of unrolled einsums
BWD_BLOCK_K = 128

TILES = (1024, 512, 256, MIN_BLOCK)
#: bytes the forward's working set may take by ``flash_vmem_bytes``: the
#: v5e's default scoped-VMEM limit (16 MiB of 128), so no limit is asked
#: for. The estimate counts ``s`` and ``p`` apart where the compiler shares
#: their room: tiles it puts at 22 MiB still compile under that limit
VMEM_BUDGET = 16 * 1024 * 1024


def flash_vmem_bytes(block_q: int, block_k: int, D: int, itemsize: int) -> int:
    """Working set of one grid step: q, k, v and o tiles double-buffered
    by the pipeline, the float32 ``s`` and ``p`` tiles and ``p`` cast for
    p·v, the float32 accumulator, and ``m`` / ``l`` (a ``[bq, 1]`` float32
    array takes whole 128-lane tiles)."""
    io = 2 * (2 * block_q + 2 * block_k) * D * itemsize
    tiles = block_q * block_k * (4 + 4 + itemsize)
    scratch = block_q * D * 4 + 2 * block_q * 128 * 4
    return io + tiles + scratch


def flash_blocks(Sq: int, Sk: int, D: int, dtype) -> Tuple[int, int]:
    """``(block_q, block_k)`` of the forward kernel for q ``[.., Sq, D]``
    and k/v ``[.., Sk, D]``: the largest of ``TILES`` that divide the
    sequence lengths, halved until the working set fits ``VMEM_BUDGET``,
    the q tile first. The v5e sweep (PERF.md, PR 25) put a kernel call at
    0.35 µs a grid step + 4.3 µs a million ``s`` elements + a cost per q
    row and step that a wide k tile spreads thin, so ``block_k`` is the
    one to keep."""
    if Sq % MIN_BLOCK or Sk % MIN_BLOCK:
        raise ValueError(f"Sq={Sq} and Sk={Sk} must be multiples of "
                         f"{MIN_BLOCK}")
    itemsize = jnp.dtype(dtype).itemsize
    bq = next(t for t in TILES if Sq % t == 0)
    bk = next(t for t in TILES if Sk % t == 0)
    while (flash_vmem_bytes(bq, bk, D, itemsize) > VMEM_BUDGET
           and max(bq, bk) > MIN_BLOCK):
        if bq > MIN_BLOCK:
            bq //= 2
        else:
            bk //= 2
    return bq, bk


def flash_grid(B: int, H: int, Sq: int, Sk: int, block_q: int,
               block_k: int) -> Tuple[int, int, int]:
    """The forward kernel's grid; its product is the grid steps of a call."""
    return (B * H, Sq // block_q, Sk // block_k)


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                  l_ref, *, scale: float, causal: bool, block_q: int,
                  block_k: int):
    """One (q-tile, k-tile) step; grid (BH, nq, nk) with k innermost."""
    kv_idx = pl.program_id(2)
    q_idx = pl.program_id(1)

    @pl.when(kv_idx == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def body(masked: bool):
        q = q_ref[0]                               # [bq, D]
        k = k_ref[0]                               # [bk, D]
        v = v_ref[0]                               # [bk, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
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
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[:] = m_next
        l_ref[:] = l_next

    if causal:
        first_row = q_idx * block_q
        first_col = kv_idx * block_k
        last_col = first_col + block_k - 1
        # the diagonal crosses the tile: some of it is masked, not all
        crossed = jnp.logical_and(first_col <= first_row + block_q - 1,
                                  last_col > first_row)
        pl.when(crossed)(functools.partial(body, True))
        # wholly at or below the diagonal: no mask to build. Tiles
        # strictly above it run nothing (and fetch nothing: kv_index)
        pl.when(last_col <= first_row)(functools.partial(body, False))
    else:
        body(False)

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
    # layout: fold batch & heads; tiles over sequence
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Sk, D)

    if causal:
        # above the diagonal the block index repeats the q tile's last
        # live tile, so the pipeline issues no DMA for a skipped step
        def kv_index(b, i, j):
            return (b, jnp.minimum(j, (i * block_q + block_q - 1) // block_k),
                    0)
    else:
        def kv_index(b, i, j):
            return (b, j, 0)

    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        grid=flash_grid(B, H, Sq, Sk, block_q, block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, D), kv_index),
            pl.BlockSpec((1, block_k, D), kv_index),
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
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
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
    ds·k, dk = dsᵀq. Peak extra memory O(Sq·BWD_BLOCK_K) per (batch·head).
    The lse cotangent enters through ∂lse/∂s_j = p_j (lse is the row
    log-partition), which is what makes the (o, lse) pair usable as a
    mergeable partial result (ring attention). ``block_q`` / ``block_k``
    are the forward's tile and set nothing here."""
    do, dlse = cts
    q, k, v, o, lse = res
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    bk = BWD_BLOCK_K
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


def _call(q, k, v, causal, scale, block_q, block_k, interpret):
    D = q.shape[-1]
    scale = float(scale) if scale is not None else float(1.0 / (D ** 0.5))
    if block_q is None or block_k is None:
        bq, bk = flash_blocks(q.shape[1], k.shape[1], D, q.dtype)
        block_q, block_k = block_q or bq, block_k or bk
    return _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret)


def flash_attention_with_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                             causal: bool = True,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             interpret: bool = False):
    """Flash attention returning ``(o, lse)``: the normalized output plus
    the per-row log-partition (``lse`` shaped ``[B*H, Sq]``). The pair is
    a mergeable partial softmax — two results over disjoint key sets
    combine exactly via logaddexp (ring attention's per-step merge).
    Differentiable in both outputs. ``block_q`` / ``block_k`` override the
    forward tile of :func:`flash_blocks` (tests)."""
    return _call(q, k, v, causal, scale, block_q, block_k, interpret)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: Optional[float] = None,
                        block_q: Optional[int] = None,
                        block_k: Optional[int] = None,
                        interpret: bool = False) -> jax.Array:
    """q/k/v: [B, S, H, D] → [B, S, H, D]. Requires S % 128 == 0 and
    D % 128 == 0 (use :func:`attend` for the auto-fallback wrapper).
    Differentiable (custom VJP with blockwise recompute backward)."""
    return _call(q, k, v, causal, scale, block_q, block_k, interpret)[0]


def flash_eligible(Sq: int, Sk: int, D: int) -> bool:
    """The kernel's contract to callers: every length a multiple of
    ``MIN_BLOCK`` (the tile is then :func:`flash_blocks`' to choose)."""
    return D % MIN_BLOCK == 0 and Sq % MIN_BLOCK == 0 and Sk % MIN_BLOCK == 0


def attend(q: jax.Array, k: jax.Array, v: jax.Array, causal: bool = True,
           scale: Optional[float] = None) -> jax.Array:
    """Attention with automatic kernel selection: the Pallas flash kernel on
    TPU when shapes satisfy its tiling constraints, else the fused-XLA
    fallback. Differentiable on both paths."""
    if jax.default_backend() == "tpu" and flash_eligible(
            q.shape[1], k.shape[1], q.shape[-1]):
        return flash_attention_tpu(q, k, v, causal, scale)
    from horovod_tpu.parallel.ring_attention import _plain_attention
    return _plain_attention(q, k, v, causal, scale)
