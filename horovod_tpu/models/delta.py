"""A gated delta-rule mixer in its two published forms, chosen by
``delta_decay``: a decay a channel (Kimi Delta Attention, arXiv:2510.26692
section 3; the public implementation is ``fla/layers/kda.py`` of
``fla-org/flash-linear-attention``) and a decay a head (Gated DeltaNet,
arXiv:2412.06464, as ``transformers``' ``qwen3_next`` model builds it), as
``models/transformer.py``'s ``("delta",)`` blocks, :data:`KIND` in its table
of block kinds: the leaves, the block, and the recurrence in its chunked
form. Per head, keys and values ``delta_head_dim`` wide, the state ``S``
``[key, value]`` zero at the start of a sequence:

    S' = Diag(alpha_t) S_{t-1}              alpha_t = exp(g_t) in (0, 1)^D
    S_t = S' + beta_t k_t (v_t - S'^T k_t)^T
    o_t = S_t^T q_t

where a Mamba-2 head (``models/mamba.py``) has one scalar decay and *adds*
an outer product, this rule first takes away what the decayed state already
predicts for the key, and decays every key channel at its own rate. Local
shapes, the whole sequence on this device (no sp, pp or tp), ``H`` heads of
``D`` channels, ``M`` the model's width:
  wq, wk, wv      [M, H D] each, then a causal depthwise convolution of
                  ``delta_taps`` taps (``conv_q``, ``conv_k``, ``conv_v``
                  ``[tap, H D]``, no bias: ``mamba._causal_conv``) and silu;
                  q and k L2-normed over a head's channels, q times
                  ``D^-1/2``
  decay           ``g = -exp(a_log) softplus((h wf_down) wf_up + dt_bias)``
                  float32 ``[B, S, H D]``: ``wf_down`` ``[M, D]``, ``wf_up``
                  ``[D, H D]``, ``a_log`` ``[H]`` (a head's rate repeated
                  over its channels), ``dt_bias`` ``[H D]``
  beta            ``sigmoid(h w_beta)``, ``w_beta`` ``[M, H]``: a scalar a head
  output          ``rmsnorm(o; norm [D]) * sigmoid((h wg_down) wg_up)`` a
                  head, then ``wo`` ``[H D, M]``
**A decay a head** (``delta_decay="head"``): ``alpha_t`` is one scalar a
value head, ``Diag(alpha_t)`` a multiple of the identity; ``delta_key_heads``
key heads ``Hk`` are read by ``H`` value heads (value head ``h`` reads key
head ``h // (H / Hk)``), and the block is Gated DeltaNet's:
  w_in            [M, 2 Hk D + 2 H D]: q | k | v | z in one matrix; one causal
                  depthwise convolution (``conv`` ``[tap, 2 Hk D + H D]``, no
                  bias) over q | k | v, then silu; q and k L2-normed a key
                  head, q times ``D^-1/2``
  w_ba            [M, 2 H]: ``beta = sigmoid(b)``, ``g = -exp(a_log)
                  softplus(a + dt_bias)`` float32 ``[B, S, H]``, ``a_log``
                  and ``dt_bias`` ``[H]``
  output          ``rmsnorm(o; norm [D]) * silu(z)`` a value head (``norm``
                  is never zero-centred), then ``wo`` ``[H D, M]``
**One layout** (PR 71): between the in-projection's output and the
out-projection's input every array of the block is ``[B, S, heads D]`` as
the projections write it, a head a run of ``D`` lanes and never an axis: a
head's sum of squares (the two L2 norms, the output norm's mean) is a product
of the squared float32 array with the 0/1 matrix ``[heads D, heads]`` and the
head's factor goes back to its channels by the transpose, both at ``HIGHEST``
(:func:`_head_sums`, :func:`_to_channels`, as ``mamba._gated_norm``); a
constant a head (``exp(a_log)``, ``norm``) reaches the channels as a ``[H D]``
vector. On a TPU a float32 ``[B, S, H, D]`` view lies in tiles of 8 heads x
128 channels, a copy of the whole array away from the projections' tiles of 8
positions x 128 channels, each way. What crosses into :func:`delta_chunked`
is a ``reshape`` view ``[B, S, heads, D]`` on which nothing is computed: the
kernels read and write ``[B, S, heads D]`` and XLA folds the two reshapes
(``tests/test_tpu_compile_delta.py`` reads the compiled step for it).

The scan is :func:`delta_chunked`: on a TPU at whole chunks and heads of
whole lane tiles two Pallas kernels (``ops/pallas_delta.py``:
``hvd_delta_scan``, ``hvd_delta_scan_bwd``, a chunk's pairs, inverse, ``W``,
``U`` and the carried state in VMEM), else :func:`_delta_chunked_numpy`,
``jax.numpy`` over chunks differentiated by JAX but for the triangular
inverse, whose backward is by hand. ``pallas_delta.delta_scan_path`` names
the form from the backend and the shapes alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models._kinds import (BlockKind, Leaf, normal, ones,
                                       rmsnorm, scaled, zeros)
from horovod_tpu.models.mamba import (_causal_conv, draw_a_log,
                                      draw_dt_bias, draw_taps)
from horovod_tpu.ops import pallas_delta
from horovod_tpu.profiling import scopes

#: rows of a chunk's sub-block: pairs inside one are decayed pairwise, pairs
#: of two sub-blocks relative to the later one's first row (the public
#: kernel's secondary chunking), so that no exponent is ever positive
SUB = 16
#: added to a head's sum of squares before the L2 norm's ``rsqrt``
L2_EPS = 1e-6
_HIGHEST = lax.Precision.HIGHEST


def _key_heads(cfg) -> int:
    return cfg.delta_key_heads or cfg.delta_heads


def _leaves(cfg):
    """A delta block's leaves, in the order they are drawn (the decay's
    bias and rate first, as a Mamba block's time step is)."""
    M, H, D, K = cfg.d_model, cfg.delta_heads, cfg.delta_head_dim, \
        cfg.delta_taps
    dt_bias, a_log, taps = draw_dt_bias, draw_a_log, draw_taps(K)
    if cfg.delta_decay == "head":
        keys = _key_heads(cfg) * D
        yield Leaf("dt_bias", (H,), dt_bias)
        yield Leaf("a_log", (H,), a_log)
        yield Leaf("ln1", (M,), zeros if cfg.zero_centred_norms else ones)
        yield Leaf("w_in", (M, 2 * keys + 2 * H * D), normal())
        yield Leaf("conv", (K, 2 * keys + H * D), taps)
        yield Leaf("w_ba", (M, 2 * H), normal())
        yield Leaf("norm", (D,), ones)
        yield Leaf("wo", (H * D, M), normal())
        return
    yield Leaf("dt_bias", (H * D,), dt_bias)
    yield Leaf("a_log", (H,), a_log)
    yield Leaf("ln1", (M,), zeros if cfg.zero_centred_norms else ones)
    for name in ("wq", "wk", "wv"):
        yield Leaf(name, (M, H * D), normal())
    for name in ("conv_q", "conv_k", "conv_v"):
        yield Leaf(name, (K, H * D), taps)
    yield Leaf("wf_down", (M, D), normal())
    yield Leaf("wf_up", (D, H * D), normal())
    yield Leaf("w_beta", (M, H), normal())
    yield Leaf("wg_down", (M, D), normal())
    yield Leaf("wg_up", (D, H * D), normal())
    yield Leaf("norm", (D,), ones)
    yield Leaf("wo", (H * D, M), normal())


# ---------------------------------------------------------------------------
# the inverse of a unit lower triangular matrix
# ---------------------------------------------------------------------------

def _blocks_on_diagonal(blocks):
    """``[.., m, s, s]`` as the block diagonal of ``[.., m s, m s]``."""
    m, s = blocks.shape[-3:-1]
    eye = jnp.eye(m, dtype=blocks.dtype)[:, None, :, None]
    full = eye * blocks[..., :, :, None, :]
    return full.reshape(blocks.shape[:-3] + (m * s, m * s))


def _inverse(a, sub: int):
    """``(I + a)^-1`` of a strictly lower triangular ``a`` ``[.., C, C]``,
    float32: forward substitution a row at a time inside the ``C / sub``
    diagonal blocks (all at once), then the blocks merged: with ``d`` the
    block diagonal's inverse and ``n`` what lies under it, ``e = d n`` is
    nilpotent of order ``C / sub`` and ``(I + e)^-1 = (I - e)(I + e^2)(I +
    e^4)..``, a few powers (no power of ``a`` itself, whose entries grow
    like binomials). Matmuls at ``HIGHEST``."""
    C = a.shape[-1]
    m = C // sub
    lead = a.shape[:-2]
    blocks = a.reshape(lead + (m, sub, m, sub))
    eye_m = jnp.eye(m, dtype=a.dtype)
    diag = jnp.einsum("...isjt,ij->...ist", blocks, eye_m)
    eye_s = jnp.eye(sub, dtype=a.dtype)
    x = jnp.broadcast_to(eye_s, diag.shape)
    for r in range(1, sub):
        # rows >= r of x are still the identity's and diag[r, t >= r] = 0
        row = eye_s[r] - jnp.sum(diag[..., r, :, None] * x, axis=-2)
        x = x.at[..., r, :].set(row)
    d = _blocks_on_diagonal(x)
    if m == 1:
        return d
    under = (blocks * (1 - eye_m)[:, None, :, None]).reshape(a.shape)
    eye = jnp.eye(C, dtype=a.dtype)
    e = jnp.matmul(d, under, precision=_HIGHEST)
    inv, power, e_p = eye - e, 2, e
    while power < m:
        e_p = jnp.matmul(e_p, e_p, precision=_HIGHEST)
        inv = jnp.matmul(inv, eye + e_p, precision=_HIGHEST)
        power *= 2
    return jnp.matmul(inv, d, precision=_HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a, sub: int):
    """:func:`_inverse` with the backward pass of an inverse: for ``t = (I
    + a)^-1``, ``da = -(t^T dt t^T)`` under the diagonal. Only ``t`` is
    kept."""
    return _inverse(a, sub)


def _inverse_fwd(a, sub):
    t = _inverse(a, sub)
    return t, t


def _inverse_bwd(sub, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=_HIGHEST), tt,
                     precision=_HIGHEST)
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ---------------------------------------------------------------------------
# the chunked scan
# ---------------------------------------------------------------------------

def _decay(log_decay):
    """``exp`` of a difference of the sums ``Gamma`` (never positive where
    it is read), in float32 as it comes: the one place the scan's decay
    factors are made (a test swaps it for the nearest precision below)."""
    return jnp.exp(log_decay)


def _carry(x):
    """The carried state as the scan keeps it from chunk to chunk: float32
    (a test swaps it for the nearest precision below)."""
    return x


@jax.checkpoint
def _pairs(qb, kb, gamma_b, gamma, k):
    """The decayed products of a chunk's rows, ``[.., C, C]`` float32, lower
    triangular with the diagonal: ``sum_c x_i[c] k_j[c] exp(Gamma_i[c] -
    Gamma_j[c])`` for ``x`` = q and for ``x`` = k, every exponent <= 0.

    Rows ``i`` of sub-block ``I`` against rows ``j`` of an earlier
    sub-block: both sides relative to ``I``'s first row ``r``, ``(x_i
    exp(Gamma_i - Gamma_r)) . (k_j exp(Gamma_r - Gamma_j))``, matmuls of
    operands in the compute dtype. Rows of one sub-block: the difference
    itself, ``[sub, sub, D]`` a sub-block, products and sum in float32.
    Checkpointed: the backward pass makes the decay factors again from q, k
    and Gamma (kept, they are two float32 ``[chunks, H, C, sub, D]`` arrays
    a block, 4.2 GB at 8192 positions, 32 heads of 128 and sub-blocks of
    16). qb, kb, gamma_b ``[.., m, sub, D]``, the sub-blocks of gamma, k
    ``[.., C, D]`` (cut by the caller: with the reshapes inside the
    checkpoint the cell's step compiles 0.68 GB larger, 14.90 for 14.22 GB,
    PERF.md section 6, PR 66)."""
    m, sub, D = kb.shape[-3:]
    C, dtype = m * sub, k.dtype
    first = gamma_b[..., :, 0, :]                           # [.., m, D]
    left = _decay(gamma_b - first[..., None, :])
    earlier = (jnp.arange(C)[None, :] < sub * jnp.arange(m)[:, None])
    right = _decay(jnp.where(earlier[..., None],
                             first[..., :, None, :] - gamma[..., None, :, :],
                             -jnp.inf))                     # [.., m, C, D]
    k_right = (k.astype(jnp.float32)[..., None, :, :] * right).astype(dtype)

    def across(xb):
        x_left = (xb.astype(jnp.float32) * left).astype(dtype)
        return jnp.einsum("...msd,...mcd->...msc", x_left, k_right,
                          preferred_element_type=jnp.float32)

    lower = jnp.tril(jnp.ones((sub, sub), bool))
    within = _decay(jnp.where(
        lower[..., None],
        gamma_b[..., :, None, :] - gamma_b[..., None, :, :], -jnp.inf))
    k_within = kb.astype(jnp.float32)[..., None, :, :] * within

    def inside(xb):
        return jnp.sum(xb.astype(jnp.float32)[..., :, None, :] * k_within,
                       axis=-1)                             # [.., m, s, s]

    def whole(xb):
        pairs = across(xb).reshape(xb.shape[:-3] + (C, C))
        return pairs + _blocks_on_diagonal(inside(xb))
    return whole(qb), whole(kb)


def _pairs_a_head(q, k, gamma):
    """:func:`_pairs` for a decay a head: the scalar factors out of the
    products, ``(x_i . k_j) exp(Gamma_i - Gamma_j)``, one matmul of operands
    in the compute dtype and a ``[C, C]`` float32 factor with no positive
    exponent (nothing under the diagonal is read above it). q, k ``[.., C,
    D]``, gamma ``[.., C]``."""
    C = gamma.shape[-1]
    lower = jnp.tril(jnp.ones((C, C), bool))
    factor = _decay(jnp.where(lower, gamma[..., :, None] - gamma[..., None, :],
                              -jnp.inf))

    def whole(x):
        return factor * jnp.einsum("...id,...jd->...ij", x, k,
                                   preferred_element_type=jnp.float32)
    return whole(q), whole(k)


def delta_chunked(q, k, v, g, beta, chunk: int, sub: int = SUB,
                  interpret: bool = False):
    """The gated delta rule of the module's docstring in its chunked form
    (:func:`_delta_chunked_numpy` states it), on the Pallas kernels of
    ``ops/pallas_delta.py`` where :func:`pallas_delta.delta_scan_path` says
    so from the backend and the shapes alone (a TPU, whole chunks, heads of
    whole lane tiles), else in ``jax.numpy``. ``interpret`` runs the kernels
    off the chip (tests). Same arguments, same results. A ``g`` ``[B, S,
    H]`` is a decay a head, and q and k may then have fewer heads than v
    (value head ``h`` reads key head ``h // (H / Hk)``): the kernels read a
    key head's block for its value heads and ``g`` as it comes."""
    S, D = q.shape[1], q.shape[3]
    H = v.shape[2]
    if interpret or pallas_delta.delta_scan_path(
            S, H, D, v.shape[-1], chunk, q.dtype, sub) == "kernels":
        o, last = _scan_kernels(q, k, v, g, beta, chunk, sub, interpret)
        return o, lax.stop_gradient(jnp.min(last))
    return _delta_chunked_numpy(q, k, v, g, beta, chunk, sub)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _scan_kernels(q, k, v, g, beta, chunk, sub, interpret):
    """One jit around the kernels' call, so that a stack's delta blocks
    share one tracing and one Mosaic lowering of each kernel."""
    return pallas_delta.delta_scan(q, k, v, g, beta, chunk, sub, interpret)


def _delta_chunked_numpy(q, k, v, g, beta, chunk: int, sub: int = SUB):
    """The chunked form in ``jax.numpy``: the CPU's path, the fall-back for
    shapes the kernels refuse, and what the tests hold the kernels to.
    With ``Gamma_i = sum_{j <= i} g_j`` inside a chunk of ``C`` positions
    (a channel, float32) and ``S`` the state the chunk starts from:

        A[i, j] = beta_i sum_c k_i[c] k_j[c] exp(Gamma_i[c] - Gamma_j[c])   j < i
        T = (I + A)^-1 Diag(beta)       W = T (K * exp Gamma)      U = T V
        R = U - W S
        O = (Q * exp Gamma) S + tril(P) R       P as A with q_i for beta_i k_i, j <= i
        S_next = Diag(exp Gamma_C) S + (K * exp(Gamma_C - Gamma))^T R

    ``A``, ``P``, ``T``, ``W`` and ``U`` of every chunk at once; one
    ``lax.scan`` over the chunks carries ``S`` (two matmuls a step) and
    gives every chunk's ``S`` and ``R``; ``O`` of every chunk at once. No
    factor ``exp(-Gamma_j)`` is ever formed alone (:func:`_pairs`). ``g``,
    ``Gamma``, every decay factor, the inverse and the carried state are
    float32; the matmuls take operands of ``q.dtype`` and accumulate in
    float32.

    q, k ``[B, S, H, D]``, v ``[B, S, H, Dv]``; g ``[B, S, H, D]`` float32,
    never positive; beta ``[B, S, H]`` float32. Returns (o ``[B, S, H, Dv]``
    float32, the most negative ``Gamma_C`` of any chunk, head and
    channel).

    A decay a head, g ``[B, S, H]``: ``Gamma`` is a scalar a row, every
    ``exp`` above a number a row (or a pair of rows: :func:`_pairs_a_head`),
    and q, k ``[B, S, Hk, D]`` are repeated to the value heads that read
    them."""
    B, S, H = beta.shape
    D = q.shape[-1]
    a_head = g.ndim == 3
    if q.shape[2] != H:
        if not a_head or H % q.shape[2]:
            raise ValueError(f"{q.shape[2]} key heads for {H} value heads "
                             "with a decay a channel")
        q, k = (jnp.repeat(x, H // x.shape[2], axis=2) for x in (q, k))
    if S % chunk:
        raise ValueError(f"delta_chunk={chunk} does not divide the sequence "
                         f"of {S} positions")
    sub = min(sub, chunk)
    if chunk % sub:
        raise ValueError(f"delta_chunk={chunk} is not whole sub-blocks of "
                         f"{sub} rows")
    n, m, dtype = S // chunk, chunk // sub, q.dtype

    def chunks(x):      # [B, S, H, ..] -> [n, B, H, C, ..]
        x = x.reshape((B, n, chunk) + x.shape[2:])
        return jnp.swapaxes(jnp.moveaxis(x, 1, 0), 2, 3)

    def blocks(x):      # [.., C, D] -> [.., m, sub, D]
        return x.reshape(x.shape[:-2] + (m, sub, x.shape[-1]))
    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta)                                     # [n, B, H, C]
    gamma = jnp.cumsum(g, axis=3)
    if a_head:
        p, a = _pairs_a_head(q, k, gamma)
        gamma = gamma[..., None]        # a row's one factor over its channels
    else:
        p, a = _pairs(blocks(q), blocks(k), blocks(gamma), gamma, k)
    t = unit_lower_inverse(jnp.tril(a, -1) * beta[..., None], sub)
    t = (t * beta[..., None, :]).astype(dtype)
    since_start = _decay(gamma)                             # exp(Gamma_i)
    k_start = (k.astype(jnp.float32) * since_start).astype(dtype)
    w = jnp.einsum("nbhij,nbhjd->nbhid", t, k_start,
                   preferred_element_type=jnp.float32).astype(dtype)
    u = jnp.einsum("nbhij,nbhjd->nbhid", t, v,
                   preferred_element_type=jnp.float32)
    k_end = (k.astype(jnp.float32) * _decay(gamma[..., -1:, :] - gamma)
             ).astype(dtype)
    whole = since_start[..., -1, :]                         # exp(Gamma_C)

    def carry(state, chunk_):
        w_c, u_c, k_end_c, whole_c = chunk_
        r = u_c - jnp.einsum("bhcd,bhdv->bhcv", w_c, state.astype(dtype),
                             preferred_element_type=jnp.float32)
        after = whole_c[..., None] * state + jnp.einsum(
            "bhcd,bhcv->bhdv", k_end_c, r.astype(dtype),
            preferred_element_type=jnp.float32)
        return _carry(after), (state, r)
    zero = _carry(jnp.zeros((B, H, D, v.shape[-1]), jnp.float32))
    _, (before, r) = lax.scan(carry, zero, (w, u, k_end, whole))
    q_start = (q.astype(jnp.float32) * since_start).astype(dtype)
    o = (jnp.einsum("nbhcd,nbhdv->nbhcv", q_start, before.astype(dtype),
                    preferred_element_type=jnp.float32)
         + jnp.einsum("nbhij,nbhjv->nbhiv", p.astype(dtype), r.astype(dtype),
                      preferred_element_type=jnp.float32))
    o = jnp.moveaxis(jnp.swapaxes(o, 2, 3), 0, 1)           # [B, n, C, H, Dv]
    return (o.reshape(B, S, H, -1),
            lax.stop_gradient(jnp.min(gamma[..., -1, :])))


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _short_conv(x, taps):
    """``silu`` of the causal depthwise convolution of x ``[B, S, C]`` with
    taps ``[K, C]`` (no bias), float32."""
    return jax.nn.silu(_causal_conv(x, taps, None))


def _members(channels: int, heads: int):
    """The 0/1 matrix ``[channels, heads]`` of which channel is in which
    head: head ``h`` is the ``h``-th run of ``channels / heads`` channels."""
    return (jnp.arange(channels)[:, None] // (channels // heads)
            == jnp.arange(heads)).astype(jnp.float32)


def _head_sums(x, member):
    """Every head's sum over its channels, ``[.., H D]`` float32 to ``[..,
    H]``: a product with :func:`_members` at ``HIGHEST`` (float32 to the
    last bit or two: the matrix is exact in bfloat16), so that no array has
    the heads on an axis (``mamba._gated_norm``'s way: a ``[.., H, D]``
    float32 view costs XLA:TPU a copy to a heads-major layout each way,
    PERF.md section 6, PR 71)."""
    return jnp.einsum("...c,ch->...h", x, member, precision=_HIGHEST)


def _to_channels(x, member):
    """A number a head at each of the head's channels, ``[.., H]`` float32
    to ``[.., H D]``: :func:`_head_sums` the other way."""
    return jnp.einsum("...h,ch->...c", x, member, precision=_HIGHEST)


def _l2norm(x, heads: int = 1):
    """``x / |x|`` over each of the ``heads`` runs of channels of the last
    dimension, float32; one head is the last dimension whole."""
    x = x.astype(jnp.float32)
    if heads == 1:
        return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + L2_EPS)
    member = _members(x.shape[-1], heads)
    return x * _to_channels(
        lax.rsqrt(_head_sums(jnp.square(x), member) + L2_EPS), member)


def _head_norm(o, weight, heads: int, eps: float):
    """``rmsnorm`` of every head of o ``[B, S, H D]`` float32 over its ``D``
    channels, times ``weight`` ``[D]`` (the same for every head)."""
    member = _members(o.shape[-1], heads)
    mean = _head_sums(jnp.square(o), member) * (heads / o.shape[-1])
    return (o * _to_channels(lax.rsqrt(mean + eps), member)
            * jnp.tile(weight.astype(jnp.float32), heads))


def _log_decay(rate, a_log, dt_bias):
    """``g = -exp(a_log) softplus(rate + dt_bias)`` float32, ``a_log``
    ``[H]`` a head: with a rate a channel (``rate``, ``dt_bias`` ``[.., H
    D]``) a head's factor is repeated over its channels, a vector of a few
    KB, and nothing leaves ``[B, S, H D]``."""
    speed = jnp.repeat(jnp.exp(a_log.astype(jnp.float32)),
                       rate.shape[-1] // a_log.shape[0])
    return -speed * jax.nn.softplus(rate + dt_bias.astype(jnp.float32))


def _scan_to_residual(p, x, q, k, v, g, beta, gate, activation, cfg):
    """What both forms share, called under the mixer's scope: the scan, the
    head's RMSNorm on its output times ``activation(gate)``, the
    out-projection and the residual add; and the step's most negative
    ``Gamma_C``. q, k, v, gate and a decay a channel ``[B, S, heads D]`` as
    the projections wrote them (q and k of the form with a decay a head come
    from their L2 norm as ``[B, S, Hk, D]``): the scan is handed ``[B, S,
    heads, D]`` views on which nothing is computed (``pallas_delta`` reads
    them flat again, and XLA folds a reshape of a reshape), and its o is flat
    from there on."""
    B, S, _ = v.shape
    H, D = cfg.delta_heads, cfg.delta_head_dim

    def heads(y):
        return y.reshape(B, S, -1, D)
    with scopes.scope(scopes.DELTA_SCAN):
        o, min_log_decay = delta_chunked(
            heads(q), heads(k), heads(v),
            g if cfg.delta_decay == "head" else heads(g), beta,
            cfg.delta_chunk)
        o = o.reshape(B, S, H * D)
    with scopes.scope(scopes.DELTA_NORM):
        y = (_head_norm(o, p["norm"], H, cfg.norm_eps)
             * activation(gate.astype(jnp.float32))).astype(x.dtype)
    with scopes.scope(scopes.DELTA_PROJ):
        out = y @ p["wo"].astype(x.dtype)
    return (x + scaled(out, cfg.residual_scale),
            {"delta_min_log_decay": min_log_decay})


def _delta_block(p, x, cfg):
    """``x + delta(norm(x))``, x ``[B', S', M]`` with the whole sequence
    here (no sp); and the step's most negative ``Gamma_C``. Between the
    in-projections and the out-projection a head is a run of ``D`` lanes of
    ``[B, S, H D]`` and never an axis."""
    if cfg.delta_decay == "head":
        return _delta_block_a_head(p, x, cfg)
    H, D = cfg.delta_heads, cfg.delta_head_dim
    with scopes.scope(scopes.DELTA):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, cfg.zero_centred_norms)
        with scopes.scope(scopes.DELTA_PROJ):
            q, k, v = (h @ p[name].astype(h.dtype)
                       for name in ("wq", "wk", "wv"))
        with scopes.scope(scopes.DELTA_CONV):
            q, k, v = (_short_conv(y, p[name])
                       for y, name in ((q, "conv_q"), (k, "conv_k"),
                                       (v, "conv_v")))
            q = (_l2norm(q, H) * D ** -0.5).astype(h.dtype)
            k = _l2norm(k, H).astype(h.dtype)
            v = v.astype(h.dtype)
        with scopes.scope(scopes.DELTA_GATES):
            rate = jnp.matmul(
                h @ p["wf_down"].astype(h.dtype),
                p["wf_up"].astype(h.dtype),
                preferred_element_type=jnp.float32)
            g = _log_decay(rate, p["a_log"], p["dt_bias"])
            beta = jax.nn.sigmoid(jnp.matmul(
                h, p["w_beta"].astype(h.dtype),
                preferred_element_type=jnp.float32))
            gate = (h @ p["wg_down"].astype(h.dtype)
                    ) @ p["wg_up"].astype(h.dtype)
        return _scan_to_residual(p, x, q, k, v, g, beta, gate,
                                 jax.nn.sigmoid, cfg)


def _delta_block_a_head(p, x, cfg):
    """:func:`_delta_block` with a decay a head (the module's docstring):
    one in-projection, one convolution over q | k | v, ``b`` and ``a`` from
    one projection, the output norm times ``silu(z)``. The convolution
    runs on the column ranges of q, k and v apart (a depthwise convolution is
    its ranges'), so that none of them waits on a float32 q | k | v. **The
    one head axis left in a delta block:** q and k are L2-normed as ``[B, S,
    Hk, D]``. Flat, in any of the forms tried (the products, their bfloat16
    pieces, a ``repeat`` for the way back, q | k at once), the cell
    qwen3-next-80b-a3b.s8192's step is scheduled as Kimi's is, the head's
    weight update after the last layer's experts, its logits' cotangent
    (312 MB) alive until then, and compiles to 12.574 GB for the parent's
    12.412 (PERF.md section 6 and 7, PR 71)."""
    B, S, _ = x.shape
    H, Hk, D = cfg.delta_heads, _key_heads(cfg), cfg.delta_head_dim
    keys = Hk * D
    with scopes.scope(scopes.DELTA):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps, cfg.zero_centred_norms)
        with scopes.scope(scopes.DELTA_PROJ):
            qkvz = h @ p["w_in"].astype(h.dtype)
            qkv, z = qkvz[..., :2 * keys + H * D], qkvz[..., 2 * keys + H * D:]
        with scopes.scope(scopes.DELTA_CONV):
            q, k, v = (_short_conv(qkv[..., cols], p["conv"][:, cols])
                       for cols in (slice(keys), slice(keys, 2 * keys),
                                    slice(2 * keys, None)))
            q, k = (_l2norm(y.reshape(B, S, Hk, D)) for y in (q, k))
            q = (q * D ** -0.5).astype(h.dtype)
            k = k.astype(h.dtype)
            v = v.astype(h.dtype)
        with scopes.scope(scopes.DELTA_GATES):
            ba = jnp.matmul(h, p["w_ba"].astype(h.dtype),
                            preferred_element_type=jnp.float32)
            beta = jax.nn.sigmoid(ba[..., :H])
            g = _log_decay(ba[..., H:], p["a_log"], p["dt_bias"])
        return _scan_to_residual(p, x, q, k, v, g, beta, z, jax.nn.silu, cfg)


def _validate(cfg) -> None:
    if cfg.delta_heads < 1 or cfg.delta_taps < 1 or cfg.delta_chunk < 1:
        raise ValueError(
            f"layer_pattern has (\"delta\",) blocks and delta_heads="
            f"{cfg.delta_heads}, delta_taps={cfg.delta_taps}, delta_chunk="
            f"{cfg.delta_chunk}: the mixer has at least one head, one tap "
            "and one position a chunk")
    if cfg.delta_decay not in ("channel", "head"):
        raise ValueError(f"delta_decay={cfg.delta_decay!r}: a decay a "
                         "\"channel\" or a \"head\"")
    if cfg.delta_heads % _key_heads(cfg) or (
            cfg.delta_key_heads and cfg.delta_decay != "head"):
        raise ValueError(
            f"delta_key_heads={cfg.delta_key_heads} with delta_heads="
            f"{cfg.delta_heads}, delta_decay={cfg.delta_decay!r}: key heads "
            "divide the value heads, and the form with a decay a channel "
            "has a key head a value head")
    if cfg.n_loops > 1:
        raise NotImplementedError(
            f"a (\"delta\",) block with n_loops={cfg.n_loops}: a looped "
            "stack stacks one kind of auxiliary terms a pass, the experts', "
            "not a mixer's own, and nobody has said whether the state "
            "starts from zero at every loop step")


#: the row of ``transformer._BLOCK_KINDS``. The single pass checkpoints the
#: block: every chunk's float32 pairs, inverse, states and decays would
#: otherwise be kept for the backward pass
KIND = BlockKind(
    length=1, leaves=_leaves, validate=_validate,
    apply=lambda p, x, positions, cfg, kind: _delta_block(p, x, cfg),
    checkpointed=True, refuses=("sp", "pp", "tp"),
    refusal="the convolutions and the scan's carried state run over the "
            "whole sequence on one device (no hand-over of the last taps "
            "and of the state between sp shards), its heads (with a decay a "
            "head: the value heads and the key heads they share) are not "
            "split over tp, and no pipeline schedule has run it (its stages "
            "carry one auxiliary column, the experts', not a mixer's own "
            "terms)")
