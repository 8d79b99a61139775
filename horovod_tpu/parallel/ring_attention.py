"""Ring attention — context parallelism for long sequences over the ``sp``
mesh axis.

No reference analog (SURVEY.md §2.6: sequence/context parallelism is absent
in the reference; ``alltoall`` is its only related primitive). Here it is
first-class: the sequence dim is sharded over ``sp``; K/V blocks rotate
around the ring via ``lax.ppermute`` while every device accumulates its
queries' attention with an online-softmax (flash-style log-sum-exp) update,
so peak memory is O(S/sp) and the ICI transfer overlaps with compute.

Algorithm (Liu et al., Ring Attention; blockwise parallel transformers):
for step t in [0, sp):  partner block = (my_index - t) mod sp
    acc, m, l ← online_softmax_update(acc, m, l, Q_local, K_t, V_t)
    (K_t, V_t) ← ppermute ring shift
Causal masking uses absolute block offsets so the result is bit-equivalent
to full attention with a causal mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu._compat import axis_size, shard_map

NEG_INF = -1e30


def _block_attend(q, k, v, bias, scale):
    """One blockwise attention contribution with running-softmax stats.

    q: [B, Sq, H, D]; k/v: [B, Sk, H, D]; bias: [B, H, Sq, Sk] or None.
    Returns (scores_max [B,H,Sq], exp_scores [B,H,Sq,Sk], weighted_v
    [B,Sq,H,D] un-normalized).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)                      # [B,H,Sq]
    p = jnp.exp(s - m[..., None])                # [B,H,Sq,Sk]
    pv = jnp.einsum("bhqk,bkhd->bqhd", p, v)     # [B,Sq,H,D]
    l = jnp.sum(p, axis=-1)                      # [B,H,Sq]
    return m, l, pv


def _online_update(acc, m_run, l_run, m_new, l_new, pv_new):
    """Flash-attention accumulator merge of two partial softmaxes."""
    m_next = jnp.maximum(m_run, m_new)
    a = jnp.exp(m_run - m_next)                  # rescale old
    b = jnp.exp(m_new - m_next)                  # rescale new
    l_next = l_run * a + l_new * b
    # acc: [B,Sq,H,D]; a/b: [B,H,Sq] → [B,Sq,H,1]
    a_ = jnp.transpose(a, (0, 2, 1))[..., None]
    b_ = jnp.transpose(b, (0, 2, 1))[..., None]
    acc_next = acc * a_ + pv_new * b_
    return acc_next, m_next, l_next


def ring_attention_spmd(q: jax.Array, k: jax.Array, v: jax.Array,
                        axis_name: str = "sp", causal: bool = True,
                        scale: Optional[float] = None,
                        use_flash: Optional[bool] = None,
                        interpret: bool = False) -> jax.Array:
    """SPMD body: call inside ``shard_map`` with sequence sharded on
    ``axis_name``. Shapes (local): q/k/v ``[B, S_local, H, D]``.

    The K/V pair travels the ring; accumulation order is fixed by absolute
    block index so causal masking stays exact.

    ``use_flash`` selects the Pallas flash kernel for each ring step's
    local block attention (auto: on TPU when tiling permits): every step
    returns a normalized ``(o, lse)`` partial which merges exactly via
    logaddexp, so the O(Sq·Sk_local) score matrix is never materialized.
    Ring causal masking needs no in-kernel offsets — a step's K/V block
    is fully visible (earlier block), diagonal (own block: standard
    causal), or fully masked (later block: skipped).
    """
    B, Sq, H, D = q.shape
    n = axis_size(axis_name)
    my = lax.axis_index(axis_name)
    scale = scale if scale is not None else (1.0 / (D ** 0.5))

    if use_flash is None:
        from horovod_tpu.ops.pallas_attention import flash_eligible
        use_flash = (jax.default_backend() == "tpu"
                     and flash_eligible(Sq, k.shape[1], D))
    if use_flash:
        return _ring_flash(q, k, v, axis_name, causal, scale, n, my,
                           interpret)

    acc = jnp.zeros((B, Sq, H, D), jnp.float32)
    m_run = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l_run = jnp.zeros((B, H, Sq), jnp.float32)

    qf = q.astype(jnp.float32)

    def attend(t, acc, m_run, l_run, k_t, v_t):
        src_block = (my - t) % n                  # whose K/V we hold now
        if causal:
            # absolute positions: q row i ↔ my*Sq+i; k col j ↔ src*Sk+j
            qpos = my * Sq + jnp.arange(Sq)
            kpos = src_block * k_t.shape[1] + jnp.arange(k_t.shape[1])
            mask = qpos[:, None] >= kpos[None, :]
            bias = jnp.where(mask, 0.0, NEG_INF)[None, None]
        else:
            bias = None
        m_new, l_new, pv = _block_attend(qf, k_t.astype(jnp.float32),
                                         v_t.astype(jnp.float32), bias, scale)
        return _online_update(acc, m_run, l_run, m_new, l_new, pv)

    def body(t, carry):
        acc, m_run, l_run, k_t, v_t = carry
        acc, m_run, l_run = attend(t, acc, m_run, l_run, k_t, v_t)
        # rotate K/V to the next device (ring); overlapped with next block's
        # compute by XLA's async collective scheduling on TPU
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return acc, m_run, l_run, k_t, v_t

    # n-1 rotate-and-attend steps, then the final block without the wasted
    # last rotation (its result would be discarded)
    acc, m_run, l_run, k_t, v_t = lax.fori_loop(
        0, n - 1, body, (acc, m_run, l_run, k, v))
    acc, m_run, l_run = attend(n - 1, acc, m_run, l_run, k_t, v_t)
    # normalize: acc / l  (l: [B,H,Sq] → [B,Sq,H,1]); guard fully-masked rows
    l_ = jnp.transpose(l_run, (0, 2, 1))[..., None]
    out = acc / jnp.maximum(l_, 1e-30)
    return out.astype(q.dtype)


def _ring_flash(q, k, v, axis_name, causal, scale, n, my, interpret):
    """Flash-kernel ring body: per step, the local block attention runs in
    the Pallas kernel and the normalized ``(o, lse)`` partials merge via
    logaddexp (``o_tot = Σ o_i · exp(lse_i − lse_tot)``)."""
    from horovod_tpu.ops.pallas_attention import flash_attention_with_lse

    B, Sq, H, D = q.shape

    def attend_step(t, acc, lse_run, k_t, v_t):
        def full(kv):
            return flash_attention_with_lse(q, kv[0], kv[1], causal=False,
                                            scale=scale, interpret=interpret)

        def diag(kv):
            return flash_attention_with_lse(q, kv[0], kv[1], causal=True,
                                            scale=scale, interpret=interpret)

        def skip(kv):
            return (jnp.zeros((B, Sq, H, D), q.dtype),
                    jnp.full((B * H, Sq), NEG_INF, jnp.float32))

        if causal:
            src = (my - t) % n                    # whose K/V we hold now
            idx = jnp.where(src == my, 1, jnp.where(src < my, 0, 2))
            o_t, lse_t = lax.switch(idx, [full, diag, skip], (k_t, v_t))
        else:
            o_t, lse_t = full((k_t, v_t))

        lse_new = jnp.logaddexp(lse_run, lse_t)   # [BH, Sq]
        # weights: [BH,Sq] → [B,Sq,H,1] (finite NEG_INF keeps this NaN-free)
        def w(x):
            return jnp.exp(x - lse_new).reshape(B, H, Sq).transpose(
                0, 2, 1)[..., None]
        acc = acc * w(lse_run) + o_t.astype(jnp.float32) * w(lse_t)
        return acc, lse_new

    acc = jnp.zeros((B, Sq, H, D), jnp.float32)
    lse_run = jnp.full((B * H, Sq), NEG_INF, jnp.float32)

    def body(t, carry):
        acc, lse_run, k_t, v_t = carry
        acc, lse_run = attend_step(t, acc, lse_run, k_t, v_t)
        perm = [(i, (i + 1) % n) for i in range(n)]
        k_t = lax.ppermute(k_t, axis_name, perm)
        v_t = lax.ppermute(v_t, axis_name, perm)
        return acc, lse_run, k_t, v_t

    acc, lse_run, k_t, v_t = lax.fori_loop(
        0, n - 1, body, (acc, lse_run, k, v))
    acc, _ = attend_step(n - 1, acc, lse_run, k_t, v_t)
    return acc.astype(q.dtype)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, mesh: Mesh,
                   axis_name: str = "sp", causal: bool = True,
                   scale: Optional[float] = None,
                   batch_axis: Optional[str] = "dp",
                   use_flash: Optional[bool] = None,
                   interpret: bool = False) -> jax.Array:
    """Array-level ring attention: global ``[B, S, H, D]`` inputs with S
    sharded over ``axis_name`` (and optionally B over ``batch_axis``)."""
    from horovod_tpu.parallel.mesh import mesh_axis_size
    if mesh_axis_size(mesh, axis_name) == 1:
        # degenerate ring: plain attention
        return _plain_attention(q, k, v, causal, scale)
    b_ax = batch_axis if (batch_axis and mesh_axis_size(mesh, batch_axis) > 1) \
        else None
    spec = P(b_ax, axis_name)

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec,) * 3,
                       out_specs=spec, check_vma=False)
    def run(ql, kl, vl):
        return ring_attention_spmd(ql, kl, vl, axis_name, causal, scale,
                                   use_flash=use_flash, interpret=interpret)

    return run(q, k, v)


def _plain_attention(q, k, v, causal=True, scale=None):
    """Single-device reference attention (the correctness oracle for the
    ring; also the sp=1 fast path)."""
    B, S, H, D = q.shape
    scale = scale if scale is not None else (1.0 / (D ** 0.5))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        mask = jnp.tril(jnp.ones((S, k.shape[1]), bool))
        s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
