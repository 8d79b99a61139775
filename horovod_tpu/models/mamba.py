"""The Mamba-2 mixer (arXiv:2405.21060) of ``models/transformer.py``'s
``("mamba",)`` blocks, :data:`KIND` in its table of block kinds: the leaves,
the block, and the selective scan in its chunked form. Local shapes, the
whole sequence on this device (no sp, pp or tp):
  ssm_in          [M, inner + (inner + 2 G N) + H]: ``[z | x B C | dt]``
  convolution     causal, depthwise over ``x B C``; taps ``[tap, channel]``,
                  tap ``ssm_conv - 1`` on the current position
  scan            heads ``[B, S, H, P]``, B and C ``[B, S, G, N]``; the time
                  steps, every decay and the carried state ``[P, N]`` a head
                  in float32; on a TPU the chunks run in the kernels of
                  ``ops/pallas_ssm.py`` where the shapes fit their tiles
  gate + norm     ``rmsnorm(y * silu(z)) * ssm_norm`` over each of G groups
                  of channels, in float32, in ``jax.numpy`` on every backend
                  (:func:`_gated_norm`): ``[B, S, inner]`` row-major as the
                  scan writes y and the in-projection z, a group's sum and
                  its factor's way back products with a 0/1 matrix, so no
                  array has the groups on an axis of their own; the backward
                  pass is autodiff's (a checkpointed block keeps its input
                  and makes the tail again)
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models._kinds import (BlockKind, Leaf, normal, ones, remat,
                                       rmsnorm, scaled)
from horovod_tpu.ops import pallas_ssm
from horovod_tpu.profiling import scopes


#: the range a Mamba-2 head's time step ``softplus(dt_bias)`` is drawn
#: from, log-uniformly, its floor, and the range of ``-A`` (the reference
#: implementation's defaults, which Nemotron-H's config repeats)
SSM_DT_RANGE, SSM_DT_FLOOR, SSM_A_RANGE = (1e-3, 1e-1), 1e-4, (1.0, 16.0)


def draw_dt_bias(rng, shape):
    """A time step's bias: ``softplus(dt_bias) = dt``, ``dt`` log-uniform
    over ``SSM_DT_RANGE`` and no less than ``SSM_DT_FLOOR``."""
    dt = np.exp(rng.uniform(*np.log(SSM_DT_RANGE), size=shape))
    return np.log(np.expm1(np.maximum(dt, SSM_DT_FLOOR))).astype(np.float32)


def draw_a_log(rng, shape):
    """``log(-A)``, ``-A`` uniform over ``SSM_A_RANGE``."""
    return np.log(rng.uniform(*SSM_A_RANGE, size=shape)).astype(np.float32)


def draw_taps(K: int):
    """A causal depthwise convolution's ``K`` taps, uniform in ``(-1, 1) /
    sqrt(K)``."""
    def taps(rng, shape):
        return (rng.uniform(-1, 1, shape) / np.sqrt(K)).astype(np.float32)
    return taps


def _leaves(cfg):
    """A Mamba block's leaves, in the order they are drawn (a head's time
    step before the matrices)."""
    M, H, inner, K = cfg.d_model, cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv
    wide = cfg.ssm_conv_width
    dt_bias, a_log, taps = draw_dt_bias, draw_a_log, draw_taps(K)
    yield Leaf("ssm_dt_bias", (H,), dt_bias)
    yield Leaf("ln1", (M,), ones)
    yield Leaf("ssm_in", (M, inner + wide + H), normal())
    yield Leaf("ssm_conv_w", (K, wide), taps)
    yield Leaf("ssm_conv_b", (wide,), taps)
    yield Leaf("ssm_a_log", (H,), a_log)
    yield Leaf("ssm_d", (H,), ones)
    yield Leaf("ssm_norm", (inner,), ones)
    yield Leaf("ssm_out", (inner, M), normal())


def _causal_conv(x, taps, bias):
    """Depthwise causal convolution over the sequence: ``y[t] = bias +
    sum_j taps[j] * x[t - (K - 1) + j]`` with zeros before the start. x
    ``[B, S, C]``, taps ``[K, C]``; K shifted multiply-adds in float32.
    ``bias`` None: none is added (``models/short_conv.py``)."""
    K, S = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    y = None if bias is None else bias.astype(jnp.float32)
    for j in range(K):
        term = (taps[j].astype(jnp.float32)
                * padded[:, j:j + S].astype(jnp.float32))
        y = term if y is None else y + term
    return y


def _ssm_decay(log_decay):
    """``exp`` of a sum of ``dt_t a`` (never positive), in float32 as it
    comes: the one place the scan's decays are made (a test swaps it for
    the nearest precision below)."""
    return jnp.exp(log_decay)


def _carried_states(whole, states):
    """The state each chunk starts from, ``[B, n, ...]``: ``H <- whole_c H
    + states_c`` over the ``n`` chunks from ``H = 0``, in float32. whole
    ``[B, n, G, R]`` a chunk's whole decay ``exp(s_Q)``, states ``[B, n, G,
    R, P, N]`` what a chunk's own positions leave behind."""
    def carry(h, chunk):
        decay, state = chunk
        return decay[..., None, None] * h + state, h
    _, before = lax.scan(
        carry, jnp.zeros_like(states[:, 0]),
        (jnp.moveaxis(whole, 1, 0), jnp.moveaxis(states, 1, 0)))
    return jnp.moveaxis(before, 0, 1)


def _gated_norm(y, z, weight, groups: int, eps: float):
    """``rmsnorm(y * silu(z)) * weight`` in float32, the gate before the
    norm and the norm over each of ``groups`` groups of channels. y, z
    ``[B, S, C]``.

    A group's sum of squares and the way of its factor back to the group's
    channels are products with the 0/1 matrix ``[C, groups]`` of which channel
    is in which group, at ``Precision.HIGHEST`` (float32 to the last bit or
    two: the matrix is exact in bfloat16), so that every array keeps the
    shape ``[B, S, C]`` it comes in: a reshape to ``[.., groups, C /
    groups]`` costs XLA:TPU a copy to a groups-major layout each way and a
    ``[B, S, groups, C / groups]`` float32 broadcast of the factors in
    memory (PERF.md section 6, PR 47). One group needs no matrix."""
    C = y.shape[-1]
    if groups == 1:
        # one group: a row's mean is a sum along its own lanes
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        mean = jnp.mean(jnp.square(y), axis=-1, keepdims=True)
        return y * lax.rsqrt(mean + eps) * weight.astype(jnp.float32)
    member = (jnp.arange(C)[:, None] // (C // groups)
              == jnp.arange(groups)).astype(jnp.float32)
    y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    mean = jnp.einsum("bsc,cg->bsg", jnp.square(y), member,
                      precision=lax.Precision.HIGHEST) * (groups / C)
    factor = jnp.einsum("bsg,cg->bsc", lax.rsqrt(mean + eps), member,
                        precision=lax.Precision.HIGHEST)
    return y * factor * weight.astype(jnp.float32)


def _within_chunks(x, b, c, s, dt):
    """``y_i = sum_{j <= i} exp(s_i - s_j) (c_i . b_j) dt_j x_j`` inside
    every chunk: the scores, decays and their product are ``[B, n, G, R, Q,
    Q]`` (at 8192 positions and 64 heads of chunk 128, 268 MB in float32).
    x ``[B, n, Q, G, R, P]``, b and c ``[B, n, Q, G, N]``, s and dt ``[B,
    n, G, R, Q]`` float32; returns float32 ``[B, n, Q, G, R, P]``."""
    chunk = x.shape[2]
    scores = jnp.einsum("bnigs,bnjgs->bngij", c, b,
                        preferred_element_type=jnp.float32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = _ssm_decay(jnp.where(
        causal, s[..., :, None] - s[..., None, :], -jnp.inf))
    weights = (scores[:, :, :, None] * decay * dt[..., None, :]
               ).astype(x.dtype)
    return jnp.einsum("bngrij,bnjgrp->bnigrp", weights, x,
                      preferred_element_type=jnp.float32)


def _chunk_sums(steps, chunk: int):
    """``s_i = sum_{t <= i} steps_t`` inside every chunk of ``chunk``
    positions, ``[B, S, H]`` float32."""
    B, S, H = steps.shape
    return jnp.cumsum(steps.reshape(B, S // chunk, chunk, H), axis=2
                      ).reshape(B, S, H)


def ssm_chunked(x, dt, a, b, c, chunk: int, interpret: bool = False):
    """The selective state-space recurrence of Mamba-2 in its chunked
    (dual) form (arXiv:2405.21060, section 6). Per head, with ``a_t = dt_t
    a`` (``a`` < 0) and the state ``H`` ``[P, N]``:

        H_t = exp(a_t) H_{t-1} + dt_t x_t (x) b_t        y_t = H_t c_t

    Inside a chunk of ``chunk`` positions, ``s_i = sum_{t <= i} a_t``:

        y_i = sum_{j <= i} exp(s_i - s_j) (c_i . b_j) dt_j x_j
              + exp(s_i) c_i . H_prev
        H_next = exp(s_Q) H_prev + sum_j exp(s_Q - s_j) dt_j x_j (x) b_j

    so a chunk is three batches of matmuls (scores ``c b^T``, scores times
    x, x^T times b) and the sequence a loop over chunks that carries
    ``H``. The time steps, the sums ``s``, every decay and the carried
    state are float32; the matmuls take operands of ``x.dtype`` and
    accumulate in float32, the decays and ``dt`` multiplied into the
    scores before they are cast.

    x ``[B, S, H, P]``; dt ``[B, S, H]`` float32, after its softplus; a
    ``[H]`` float32; b, c ``[B, S, G, N]``, head h reading group ``h // (H
    / G)``. Returns y ``[B, S, H, P]`` float32 (without the skip ``D x``).

    On a TPU (and under ``interpret``) the chunks run in the Pallas kernels
    of ``ops/pallas_ssm.py`` wherever the shapes fit their tiles
    (:func:`pallas_ssm.ssm_eligible`): the same algorithm at the same
    precision, the sums ``s`` made here, nothing of a chunk's inside in
    HBM. Elsewhere, and as what the kernels are held against, the
    ``jax.numpy`` form below (:func:`_ssm_chunked_numpy`).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    if S % chunk:
        raise ValueError(f"ssm_chunk={chunk} does not divide the sequence "
                         f"of {S} positions")
    s = _chunk_sums(dt * a, chunk)
    if interpret or (jax.default_backend() == "tpu"
                     and pallas_ssm.ssm_eligible(S, H, P, G, N, chunk)):
        return pallas_ssm.ssm_scan(x, dt, s, b, c, chunk, interpret)
    return _ssm_chunked_numpy(x, dt, s, b, c, chunk)


def _ssm_chunked_numpy(x, dt, s, b, c, chunk: int):
    """:func:`ssm_chunked` from the sums ``s`` ``[B, S, H]`` on, in
    ``jax.numpy`` and differentiated by JAX: the scores, decays and their
    product inside a chunk, the chunks' own states and the states they
    start from are arrays of their own."""
    B, S, H, P = x.shape
    G, N = b.shape[2:]
    n, R = S // chunk, H // G
    x = x.reshape(B, n, chunk, G, R, P)
    b, c = (v.reshape(B, n, chunk, G, N) for v in (b, c))
    # [B, n, G, R, Q]: a head's positions last
    dt, s = (v.reshape(B, n, chunk, G, R).transpose(0, 1, 3, 4, 2)
             for v in (dt, s))

    y = _within_chunks(x, b, c, s, dt)

    # -- a chunk's own state, and the state each chunk starts from ---------
    to_end = (_ssm_decay(s[..., -1:] - s) * dt).transpose(0, 1, 4, 2, 3)
    states = jnp.einsum("bnjgrp,bnjgs->bngrps",
                        (x.astype(jnp.float32) * to_end[..., None]
                         ).astype(x.dtype), b,
                        preferred_element_type=jnp.float32)
    since_start = _ssm_decay(s)                 # exp(s_i); the last: exp(s_Q)
    before = _carried_states(since_start[..., -1], states)
    y = y + (jnp.einsum("bnigs,bngrps->bnigrp", c, before.astype(x.dtype),
                        preferred_element_type=jnp.float32)
             * since_start.transpose(0, 1, 4, 2, 3)[..., None])
    return y.reshape(B, S, H, P)


def ssm_path(cfg, seq_len: int) -> str:
    """How a Mamba block's scan and its gate + norm run at ``seq_len``
    positions and what the backward pass keeps of the block
    (``chip_smoke.py`` prints it, as it does ``attend``'s choice)."""
    kept = ("each Mamba block checkpointed: its input kept, the block run "
            "again in the backward pass"
            if remat(cfg, KIND.checkpointed) else
            "everything kept for the backward pass")
    how = pallas_ssm.ssm_scan_path(
        seq_len, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
        cfg.ssm_state, cfg.ssm_chunk)
    norm = (f"{cfg.ssm_groups} groups of "
            f"{cfg.ssm_inner // cfg.ssm_groups} channels summed through a "
            "0/1 matrix (no axis for the groups)" if cfg.ssm_groups > 1 else
            f"one group of {cfg.ssm_inner} channels, a row's own mean")
    return (f"{how}; chunked scan, {seq_len // cfg.ssm_chunk} chunks of "
            f"{cfg.ssm_chunk}, float32 sums, decays and carried state "
            f"[{cfg.ssm_heads}, {cfg.ssm_head_dim}, {cfg.ssm_state}]; gate "
            f"+ norm in jax.numpy, {norm}; {kept}")


def _mamba_block(p, x, cfg):
    """``x + mamba2(norm(x))``, x ``[B', S', M]`` with the whole sequence
    here (no sp). The mixer: ``[z | x B C | dt] = h W_in``; x, B and C
    through the causal convolution and silu; ``dt = softplus(dt +
    dt_bias)``, ``a = -exp(a_log)`` a head; the scan (:func:`ssm_chunked`)
    plus the skip ``d x``; ``rmsnorm(y * silu(z))`` over each of the
    ``ssm_groups`` groups of channels (:func:`_gated_norm`: every array
    ``[B, S, inner]`` as the scan wrote y, z read where the in-projection
    wrote it); ``W_out``. Nothing of the tail is kept for the backward pass
    but what autodiff keeps inside a checkpointed block's second forward."""
    B, S, M = x.shape
    H, P, G, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups, cfg.ssm_state
    inner, wide = cfg.ssm_inner, cfg.ssm_conv_width
    with scopes.scope(scopes.SSM):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        with scopes.scope(scopes.SSM_PROJ):
            zxbcdt = h @ p["ssm_in"].astype(h.dtype)
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:inner + wide],
                      zxbcdt[..., inner + wide:])
        with scopes.scope(scopes.SSM_CONV):
            xbc = jax.nn.silu(_causal_conv(
                xbc, p["ssm_conv_w"], p["ssm_conv_b"]).astype(h.dtype))
        xs = xbc[..., :inner].reshape(B, S, H, P)
        b = xbc[..., inner:inner + G * N].reshape(B, S, G, N)
        c = xbc[..., inner + G * N:].reshape(B, S, G, N)
        with scopes.scope(scopes.SSM_SCAN):
            dt = jax.nn.softplus(dt.astype(jnp.float32)
                                 + p["ssm_dt_bias"].astype(jnp.float32))
            a = -jnp.exp(p["ssm_a_log"].astype(jnp.float32))
            y = ssm_chunked(xs, dt, a, b, c, cfg.ssm_chunk)
            y = y + (p["ssm_d"].astype(jnp.float32)[:, None]
                     * xs.astype(jnp.float32))
        with scopes.scope(scopes.SSM_NORM):
            y = _gated_norm(y.reshape(B, S, inner), z, p["ssm_norm"], G,
                            cfg.norm_eps).astype(h.dtype)
        with scopes.scope(scopes.SSM_PROJ):
            o = y @ p["ssm_out"].astype(h.dtype)
        return x + scaled(o, cfg.residual_scale)


def _validate(cfg) -> None:
    if cfg.ssm_heads < 1 or cfg.ssm_heads % cfg.ssm_groups:
        raise ValueError(
            f"layer_pattern has (\"mamba\",) blocks: ssm_groups="
            f"{cfg.ssm_groups} does not divide ssm_heads={cfg.ssm_heads}")


#: the row of ``transformer._BLOCK_KINDS``. The single pass checkpoints the
#: block: its float32 chunk states, decays and gate keep 1.2 GB at 8192
#: positions (PERF.md section 6, PR 39). The train step finishes its
#: gradients before the optimizer reads them: with this block's gate + norm
#: XLA:TPU's update fused with the weight gradients reads VMEM out of range
#: on the chip (PERF.md section 6, PR 47)
KIND = BlockKind(
    length=1, leaves=_leaves,
    apply=lambda p, x, positions, cfg, kind: (_mamba_block(p, x, cfg), None),
    validate=_validate, checkpointed=True, gradients_first=True,
    refuses=("sp", "pp", "tp"),
    refusal="the convolution and the scan's carried state run over the "
            "whole sequence on one device (no hand-over between sp shards), "
            "its heads and groups are not split over tp, and no pipeline "
            "schedule has run it")
