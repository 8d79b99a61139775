"""The gated delta rule's chunked scan as Pallas TPU kernels: what is inside
a chunk stays on the chip.

``models/delta.py:delta_chunked`` states the algorithm (Kimi Delta
Attention, arXiv:2510.26692 section 3). Per head, with ``Gamma_i`` the
running sum of ``g`` inside a chunk of ``C`` positions (a channel, float32)
and ``S`` the state ``[D, Dv]`` the chunk starts from:

    A[i, j] = sum_c k_i[c] k_j[c] exp(Gamma_i[c] - Gamma_j[c])          j < i
    P[i, j] = sum_c q_i[c] k_j[c] exp(Gamma_i[c] - Gamma_j[c])          j <= i
    T = (I + Diag(beta) A)^-1 Diag(beta)    W = T (K * exp Gamma)    U = T V
    R = U - W S
    O = (Q * exp Gamma) S + P R
    S_next = Diag(exp Gamma_C) S + (K * exp(Gamma_C - Gamma))^T R

In ``jax.numpy`` every factor of that is an array in HBM (at 8192 positions,
32 heads of 128 and chunks of 64 each ``[128, 32, 64, 128]`` float32
intermediate is 134 MB, some fifty of them a forward pass, and as many
relayouts between ``[S, H D]`` and per-head chunks). Here:

  grid = (batch, heads / ``R``, chunks) — the chunk axis sequential
  forward  ``hvd_delta_scan``:     a step reads a chunk's q, k, g ``[C, R D]``
      and v ``[C, R Dv]`` out of ``[B, S, H D]`` as the block's projections
      write them (``R`` heads side by side, :func:`delta_head_tile`) and
      beta ``[C, R]``; makes ``Gamma``, the pairs, the inverse, ``W``,
      ``U``, ``R`` in VMEM and writes o ``[C, R Dv]`` float32 and the chunk's
      last ``Gamma`` (a row a chunk: ``delta_min_log_decay``); a head's
      state, kept transposed ``[Dv, D]`` float32 (the decay of a channel is
      then a row over the lanes), lives in a VMEM scratch across the chunk
      axis, zeroed at chunk 0.
  backward ``hvd_delta_scan_bwd``: the same grid from the last chunk to the
      first, the state's cotangent in the scratch; everything of the chunk
      is made again from q, k, v, g, beta and the state the chunk started
      from, which the forward writes under differentiation (``[B, n, H, Dv,
      D]`` float32: the one array of the chunks' size that reaches HBM); the
      inverse's cotangent by hand (``-T^T dT T^T`` under the diagonal);
      gives dq, dk, dv, dg (the cotangent of ``Gamma`` summed back over a
      chunk's later rows) and dbeta.

**What crosses the boundary.** :func:`delta_scan` takes q, k ``[B, S, Hk,
D]``, v ``[B, S, H, Dv]`` and a decay a channel ``[B, S, H, D]`` and gives o
``[B, S, H, Dv]``, but a delta block computes nothing on those shapes: they
are ``reshape`` views of its ``[B, S, heads D]`` arrays, :func:`_flat` views
them flat again for the kernels, o and the cotangents leave flat and are
viewed with heads for the caller, who flattens them; XLA folds each pair of
reshapes, so the compiled step has no array with a head axis and no relayout
between the projections' tiles (8 positions x 128 channels) and a head-axis
float32 array's (8 heads x 128 channels) (:data:`LAYOUT`; PERF.md section 6,
PR 71; the block with a decay a head L2-norms its q and k on a head axis
first, ``models/delta.py`` says why). beta, and g with a decay a head, are ``[B, S, H]`` and go in as ``[B,
H / R, S, R]`` (:func:`_columns`, 1 MB).

**The pairs.** As ``delta._pairs``: rows of two sub-blocks of ``sub`` rows
are decayed relative to the later one's first row and multiplied on the MXU
(operands in the compute dtype); rows of one sub-block pairwise in float32
on the vector unit, an earlier row ``t`` against the later rows of its
sub-block at a time: ``E_t[i] = exp(Gamma_i - Gamma_t)`` for ``i >= t``
(``[sub, D]``; from ``t``'s sublane tile on: the 8 rows before it see
nothing of row 8 and later), the products' sums over the lanes a column over
``i``: column ``t`` of the sub-block of ``P`` and of ``A`` as they lie. No
exponent is ever positive. That column of ``A`` times beta is what forward
substitution's step ``t`` takes away from the later rows (``x[i] -= L[i, t]
x[t]``), so the diagonal blocks of ``A`` are laid out for the backward pass
only; the four sub-blocks' substitutions run side by side on the lanes of one
``[sub, C]`` array. The backward pass walks the same columns again with the
factors ``E_t`` kept in a VMEM scratch: what reaches the later rows' q and k
adds up over ``t``, what reaches ``k_t`` is a sum over the later rows, and
the cotangent of ``Gamma`` is ``q dq + k dk`` of the later rows less ``k dk``
of the earlier one.

**Two heads a grid step**, written stage by stage side by side
(:func:`_interleaved`): one head's chain of dependent ``HIGHEST`` matmuls
stands beside the other's vector work in the kernel's text, which is where
the scheduler finds it (PERF.md section 6, PR 67).

**Precision** (``configs/kimi-linear-48b-a3b.json`` ``assumed.delta_scan``):
g, ``Gamma`` (g's three bfloat16 pieces against a triangle of ones: exact
products, float32 sums), every decay factor, the inverse (float32 at
``HIGHEST``) and the carried state are float32; matmul
operands (k · decay, the inverse times beta, the state where a matmul reads
it) in ``q.dtype`` with float32 accumulation; o float32. The cotangent of o
is cast to ``q.dtype`` for its matmuls, as XLA's default precision does with
the float32 cotangent of the ``jax.numpy`` form.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.profiling.compile_watch import kernel_call

LANES = 128
FWD_NAME = "hvd_delta_scan"
BWD_NAME = "hvd_delta_scan_bwd"
#: bytes one grid step of the backward may take by :func:`delta_vmem_bytes`:
#: the v5e's default scoped-VMEM limit, which no call asks to raise
VMEM_BUDGET = 16 * 1024 * 1024
#: heads a grid step works on at most: the loop over them is unrolled (a
#: kernel's text and compile time grow with it) and their chains of
#: dependent ``HIGHEST`` matmuls interleave
HEAD_TILE = 2
#: and with a decay a head, whose bodies are shorter and keep no scratch
#: beside the state: at the cell qwen3-next-80b-a3b.s8192's shape a forward
#: call and a forward + backward pair take 8.07 / 19.62 ms at one value head a
#: step, 5.54 / 12.96 at two and 4.46 / 10.60 at four (host clock over ten
#: calls back to back, my chip run, PR 70: the chains of two key heads' four
#: value heads interleave), for 0.6 s more of lowering a shape
HEAD_TILE_A_HEAD = 4

_HIGHEST = lax.Precision.HIGHEST
_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b
_F32 = jnp.float32


def _dot(a, b, dims, precision=None):
    return lax.dot_general(a, b, dims, precision=precision,
                           preferred_element_type=_F32)


def _sums_over_rows(ones, x):
    """``ones @ x`` for a matrix of zeros and ones and a float32 ``x``, to
    float32: ``x`` in its three bfloat16 pieces (24 bits of mantissa), each
    a single pass of the MXU against the ones, which are exact in bfloat16;
    ``HIGHEST`` would split the ones as well and run six."""
    ones = ones.astype(jnp.bfloat16)
    high = x.astype(jnp.bfloat16)
    rest = x - high.astype(_F32)
    middle = rest.astype(jnp.bfloat16)
    low = (rest - middle.astype(_F32)).astype(jnp.bfloat16)
    return (_dot(ones, high, _NN) + _dot(ones, middle, _NN)
            + _dot(ones, low, _NN))


# -- what runs where ----------------------------------------------------------

def delta_vmem_bytes(chunk: int, D: int, Dv: int, R: int, itemsize: int,
                     sub: int = 16) -> int:
    """Working set of one grid step of the backward at ``R`` heads: q, k, g
    and their cotangents, v, do, dv, the state the chunk started from, each
    double-buffered by the pipeline; a head's state cotangent in its
    scratch; the sub-blocks' decay factors kept between the two walks over
    a chunk's rows (``[C, sub, D]`` float32) and three ``[C, D]`` float32
    accumulators; some thirty ``[C, D]`` / ``[C, C]`` float32 values a head
    in flight."""
    blocks = R * chunk * (D * (2 * itemsize + 4 + 2 * itemsize + 4)
                          + Dv * (2 * itemsize + 4)) + R * D * Dv * 4
    scratch = R * (D * Dv + chunk * sub * D + 3 * chunk * D) * 4
    return 2 * blocks + scratch + 30 * chunk * max(D, Dv, LANES) * 4


def delta_head_tile(H: int, D: int, Dv: int, chunk: int,
                    itemsize: int = 2, most: int = HEAD_TILE) -> int:
    """Heads one grid step works on: the most up to ``most`` (:data:`HEAD_TILE`;
    :data:`HEAD_TILE_A_HEAD` for a decay a head) that divide ``H`` and fit
    :data:`VMEM_BUDGET`; 0 where one head does not."""
    for R in range(min(most, H), 0, -1):
        if H % R == 0 and delta_vmem_bytes(chunk, D, Dv, R,
                                           itemsize) <= VMEM_BUDGET:
            return R
    return 0


def delta_eligible(S: int, H: int, D: int, Dv: int, chunk: int, sub: int,
                   itemsize: int = 2) -> bool:
    """The kernels' contract to callers: whole chunks, heads of whole lane
    tiles, chunks of whole sub-blocks of whole (bfloat16) sublane tiles, and
    a head's step inside the VMEM budget."""
    return (S % chunk == 0 and D % LANES == 0 and Dv % LANES == 0
            and chunk % sub == 0 and sub % 16 == 0
            and delta_head_tile(H, D, Dv, chunk, itemsize) > 0)


def delta_scan_path(S: int, H: int, D: int, Dv: int, chunk: int,
                    dtype=jnp.bfloat16, sub: int = 16) -> str:
    """Which form ``delta_chunked`` takes, from the backend and the shapes
    alone: ``"kernels"`` or ``"xla"``."""
    if jax.default_backend() != "tpu":
        return "xla"
    sub = min(sub, chunk)
    return "kernels" if delta_eligible(
        S, H, D, Dv, chunk, sub, jnp.dtype(dtype).itemsize) else "xla"


#: how a delta block (``models/delta.py``) keeps what it hands the scan and
#: takes from it, on either path: what the kernels read and write; and the
#: one exception, in the block with a decay a head
LAYOUT = ("heads on the lanes: q, k, v, a decay a channel and o are [B, S, "
          "heads x D] from the in-projection to the out-projection, a head's "
          "sums products with a 0/1 matrix (no axis for the heads)")
LAYOUT_A_HEAD = LAYOUT + "; q and k L2-normed as [B, S, key heads, D]"


def describe(S: int, H: int, D: int, Dv: int, chunk: int,
             dtype=jnp.bfloat16, sub: int = 16, a_head: bool = False) -> str:
    """:func:`delta_scan_path` with the kernels' grid and blocks and the
    block's layout round them (what ``chip_smoke.py`` prints); ``a_head``:
    the form with a decay a head."""
    path = delta_scan_path(S, H, D, Dv, chunk, dtype, sub)
    layout = LAYOUT_A_HEAD if a_head else LAYOUT
    if path != "kernels":
        return f"xla (backend {jax.default_backend()}, heads of {D} / {Dv}, " \
               f"chunks of {chunk}); {layout}"
    itemsize = jnp.dtype(dtype).itemsize
    R = delta_head_tile(H, D, Dv, chunk, itemsize,
                        HEAD_TILE_A_HEAD if a_head else HEAD_TILE)
    return (f"kernels {FWD_NAME} / {BWD_NAME}: grid ({H // R} head tiles of "
            f"{R}, {S // chunk} chunks), q, k, g blocks {chunk}x{R * D}, "
            f"sub-blocks of {min(sub, chunk)} rows, carried state "
            f"{R}x{Dv}x{D} float32 in VMEM "
            f"({delta_vmem_bytes(chunk, D, Dv, R, itemsize) / 2**20:.1f} MiB "
            f"a step of {VMEM_BUDGET / 2**20:.0f}); {layout}")


# -- the pieces a test swaps for a wrong one ----------------------------------

def _decay(log_decay):
    """``exp`` of a difference of the sums ``Gamma`` (never positive where
    it is read) in float32 as it comes: the one place the kernels make a
    decay."""
    return jnp.exp(log_decay)


def _carry(state, whole, own):
    """The state the next chunk starts from: ``Diag(exp Gamma_C) S`` plus
    what this chunk's positions leave behind (decay first, then the
    correction)."""
    return state * whole + own


def _reaches(later, earlier):
    """Whether position ``earlier`` of a chunk reaches position ``later``
    (the causal mask, the diagonal with it)."""
    return earlier <= later


# -- one chunk of one head ----------------------------------------------------

def _iota(shape, dim):
    return lax.broadcasted_iota(jnp.int32, shape, dim)


class _Chunk(NamedTuple):
    """What the forward pass of a chunk makes and the backward reads."""
    gamma: jax.Array        # [C, D] float32
    since_start: jax.Array  # exp(Gamma)
    until_end: jax.Array    # exp(Gamma_C - Gamma)
    whole: jax.Array        # [1, D] exp(Gamma_C)
    lefts: tuple            # a later sub-block's (left, right, x_left, k_right)
    pairs: jax.Array        # [C, C] q's pairs, [i, j], lower with diagonal
    k_pairs: jax.Array      # k's of two sub-blocks, before beta
    k_diag: jax.Array       # k's inside the sub-blocks (backward only)
    t0: jax.Array           # (I + Diag(beta) A)^-1
    beta_row: jax.Array     # [1, C]
    t: jax.Array            # t0 Diag(beta), compute dtype
    k_start: jax.Array
    k_end: jax.Array
    q_start: jax.Array
    w: jax.Array            # compute dtype
    r: jax.Array            # float32
    o_state: jax.Array      # (Q * exp Gamma) S
    state_low: jax.Array    # the state [Dv, D], compute dtype


def _row_of(column):
    """A ``[C, 1]`` column as the row ``[1, C]``."""
    C = column.shape[0]
    eye = _iota((C, C), 0) == _iota((C, C), 1)
    return jnp.sum(jnp.where(eye, column, 0.0), axis=0, keepdims=True)


def _column_of(row):
    C = row.shape[1]
    eye = _iota((C, C), 0) == _iota((C, C), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _interleaved(*steps):
    """Run generators in turns, a stage of each at a time, and return what
    they return: the heads of a grid step are written stage by stage side
    by side, so that the scheduler finds one head's vector work next to the
    other's chain of dependent matmuls."""
    steps, done = list(steps), {}
    while len(done) < len(steps):
        for n, gen in enumerate(steps):
            if n not in done:
                try:
                    next(gen)
                except StopIteration as end:
                    done[n] = end.value
    return [done[n] for n in range(len(steps))]


def _tile_of(t: int) -> int:
    """The first row of the float32 sublane tile (8 rows) that holds row
    ``t``: an earlier row ``t`` reaches the later rows from there on."""
    return t - t % 8


def _set_from(x, first: int, rows):
    """``x`` with its rows from ``first`` on replaced by ``rows``."""
    return rows if first == 0 else jnp.concatenate([x[:first], rows], axis=0)


def _chunk_forward(q, k, v, g, beta, state, sub: int, e_scr=None):
    """A generator (see :func:`_interleaved`) that returns a :class:`_Chunk`.
    q, k ``[C, D]``, v ``[C, Dv]`` compute dtype; g ``[C, D]``, beta
    ``[C, 1]`` float32; state ``[Dv, D]`` float32. ``e_scr``: a ``[C, sub,
    D]`` float32 scratch that takes every later row's decay factors
    against its sub-block (the backward pass reads them again), and asks for
    k's diagonal pairs laid out."""
    C, D = q.shape
    m, dtype = C // sub, q.dtype
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    gamma = _sums_over_rows(rows >= cols, g)
    qf, kf = q.astype(_F32), k.astype(_F32)
    last = gamma[C - 1:C]
    since_start = _decay(gamma)
    until_end = _decay(last - gamma)
    whole = _decay(last)

    # rows of two sub-blocks: relative to the later one's first row
    zero_rows = jnp.zeros((sub, C), _F32)
    p_rows, a_rows, lefts = [zero_rows], [zero_rows], [None]
    for blk in range(1, m):
        lo = blk * sub
        first = gamma[lo:lo + 1]
        left = _decay(gamma[lo:lo + sub] - first)
        right = _decay(first - gamma[:lo])
        k_right = jnp.concatenate(
            [(kf[:lo] * right).astype(dtype),
             jnp.zeros((C - lo, D), dtype)], axis=0)
        x_left = jnp.concatenate(
            [(qf[lo:lo + sub] * left).astype(dtype),
             (kf[lo:lo + sub] * left).astype(dtype)], axis=0)
        both = _dot(x_left, k_right, _NT)                   # [2 sub, C]
        p_rows.append(both[:sub])
        a_rows.append(both[sub:])
        lefts.append((left, right, x_left, k_right))
        yield
    pairs = jnp.concatenate(p_rows, axis=0)
    k_pairs = jnp.concatenate(a_rows, axis=0)

    # rows of one sub-block pairwise, an earlier row t against the later
    # rows of its sub-block at a time: column t of the blocks, which is what
    # forward substitution's step t takes away from the later rows
    lane = _iota((sub, C), 1)
    block_of_lane = lane // sub
    x = (lane % sub == _iota((sub, C), 0)).astype(_F32)  # the blocks' [i, c]
    p_tiles = [zero_rows] * m
    a_tiles = [zero_rows] * m
    for t in range(sub):
        # the later rows from t's sublane tile on (fresh iotas: a slice of
        # one that is constant along the sublanes does not lower)
        first = _tile_of(t)
        n_later = sub - first
        column = jnp.zeros((n_later, C), _F32)
        lane_t = _iota((n_later, C), 1)
        for blk in range(m):
            lo = blk * sub
            j, later = lo + t, slice(lo + first, lo + sub)
            e = _decay(jnp.where(
                _reaches(_iota((n_later, D), 0) + first, t),
                gamma[later] - gamma[j:j + 1], -jnp.inf))   # [n_later, D]
            if e_scr is not None:
                e_scr[j, first:] = e
            ke = e * kf[j:j + 1]
            col_p = jnp.sum(qf[later] * ke, axis=1, keepdims=True)
            col_a = jnp.sum(kf[later] * ke, axis=1, keepdims=True)
            p_tiles[blk] = _set_from(p_tiles[blk], first, jnp.where(
                lane_t == j, col_p, p_tiles[blk][first:]))
            if e_scr is not None:
                a_tiles[blk] = _set_from(a_tiles[blk], first, jnp.where(
                    lane_t == j, col_a, a_tiles[blk][first:]))
            column = jnp.where(lane_t // sub == blk, col_a * beta[later],
                               column)
        if t < sub - 1:
            x = _set_from(x, first, x[first:] - jnp.where(
                _iota((n_later, C), 0) + first > t, column, 0.0)
                * x[t:t + 1])
        yield
    pairs = pairs + jnp.concatenate(p_tiles, axis=0)
    k_diag = (jnp.concatenate(a_tiles, axis=0)
              if e_scr is not None else None)

    # the blocks merged: (I + e)^-1 d with e = d under, nilpotent of order m.
    # (I + e)^-1 = (I - e)(I + e^2)(I + e^4).. as in ``delta._inverse``,
    # the factors applied to d from the right-most on, so that the powers
    # of e and the products with d are two chains side by side and not one
    d = jnp.concatenate(
        [jnp.where(block_of_lane == blk, x, 0.0) for blk in range(m)],
        axis=0)
    t0 = d
    if m > 1:
        e_p = _dot(d, k_pairs * beta, _NN, _HIGHEST)
        yield
        t0, power = d - _dot(e_p, d, _NN, _HIGHEST), 2
        while power < m:
            e_p = _dot(e_p, e_p, _NN, _HIGHEST)
            yield
            t0 = t0 + _dot(e_p, t0, _NN, _HIGHEST)
            power *= 2
        yield
    beta_row = _row_of(beta)
    t = (t0 * beta_row).astype(dtype)
    k_start = (kf * since_start).astype(dtype)
    k_end = (kf * until_end).astype(dtype)
    q_start = (qf * since_start).astype(dtype)
    w = _dot(t, k_start, _NN).astype(dtype)
    u = _dot(t, v, _NN)
    yield
    state_low = state.astype(dtype)
    from_state = _dot(jnp.concatenate([w, q_start], axis=0), state_low, _NT)
    return _Chunk(gamma, since_start, until_end, whole, tuple(lefts), pairs,
                  k_pairs, k_diag, t0, beta_row, t, k_start, k_end, q_start,
                  w, u - from_state[:C], from_state[C:], state_low)


def _chunk_output(c: _Chunk, dtype):
    return c.o_state + _dot(c.pairs.astype(dtype), c.r.astype(dtype), _NN)


def _chunk_next_state(c: _Chunk, state, dtype):
    return _carry(state, c.whole, _dot(c.r.astype(dtype), c.k_end, _TN))


# -- forward ------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, last_ref, *rest,
                R: int, D: int, Dv: int, sub: int):
    """One chunk of ``R`` heads. ``rest``: the output of the states the
    chunks start from (under differentiation only), then the scratch."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    C, dtype = q_ref.shape[1], q_ref.dtype
    heads = [(slice(h * D, (h + 1) * D), slice(h * Dv, (h + 1) * Dv))
             for h in range(R)]
    starts = [state[h] for h in range(R)]
    if len(rest) == 2:
        for h in range(R):
            rest[0][0, 0, h] = starts[h]
    chunks = _interleaved(*(
        _chunk_forward(q_ref[0, :, cols], k_ref[0, :, cols],
                       v_ref[0, :, vcols], g_ref[0, :, cols],
                       beta_ref[0, 0, :, h:h + 1], starts[h], sub)
        for h, (cols, vcols) in enumerate(heads)))
    for h, ((cols, vcols), c) in enumerate(zip(heads, chunks)):
        o_ref[0, :, vcols] = _chunk_output(c, dtype)
        last_ref[0, 0, :, cols] = c.gamma[C - 1:C]
        state[h] = _chunk_next_state(c, starts[h], dtype)


class _Layout(NamedTuple):
    B: int
    S: int
    H: int
    D: int
    Dv: int
    chunk: int
    sub: int
    R: int      # heads a grid step works on
    Hk: int     # key heads: H, or fewer with a decay a head

    @property
    def n(self) -> int:
        return self.S // self.chunk

    @property
    def group(self) -> int:
        """Value heads that read one key head."""
        return self.H // self.Hk

    @property
    def key_tile(self) -> int:
        """Key heads a grid step's value heads read."""
        return max(1, self.R // self.group)

    @property
    def steps(self) -> int:
        return self.H // self.R


def _layout(q, v, chunk: int, sub: int, head_tile: Optional[int] = None,
            a_head: bool = False) -> _Layout:
    B, S, Hk, D = q.shape
    H, Dv = v.shape[2:]
    sub = min(sub, chunk)
    if S % chunk or chunk % sub:
        raise ValueError(f"chunk {chunk} does not divide {S} positions, or "
                         f"sub-blocks of {sub} rows the chunk")
    if H % Hk:
        raise ValueError(f"{Hk} key heads do not divide {H} value heads")
    group = H // Hk

    def splits(R):  # a step's value heads are whole groups or within one
        return R % group and group % R
    R = head_tile or delta_head_tile(
        H, D, Dv, chunk, q.dtype.itemsize,
        HEAD_TILE_A_HEAD if a_head else HEAD_TILE)
    if R and not head_tile and splits(R):
        R = 1
    if not R or H % R or splits(R):
        raise ValueError(f"a head tile of {R} heads does not divide {H} "
                         f"heads of {D} / {Dv} on {Hk} key heads at chunks "
                         f"of {chunk}")
    return _Layout(B, S, H, D, Dv, chunk, sub, R, Hk)


def _flat(x, lay: _Layout):
    return x.reshape(lay.B, lay.S, -1)


def _columns(beta, lay: _Layout):
    """beta ``[B, S, H]`` with a grid step's heads last: ``[B, H / R, S,
    R]``."""
    return beta.reshape(lay.B, lay.S, lay.steps, lay.R).transpose(0, 2, 1, 3)


def _specs(lay: _Layout, chunk_of):
    """Block specs of a chunk's (q-like, v-like, beta-like, states, last
    Gamma) arrays; ``chunk_of(j)`` the chunk a grid step works on."""
    C, R = lay.chunk, lay.R
    return (
        pl.BlockSpec((1, C, R * lay.D), lambda i, h, j: (i, chunk_of(j), h)),
        pl.BlockSpec((1, C, R * lay.Dv), lambda i, h, j: (i, chunk_of(j), h)),
        pl.BlockSpec((1, 1, C, R), lambda i, h, j: (i, h, chunk_of(j), 0)),
        pl.BlockSpec((1, 1, R, lay.Dv, lay.D),
                     lambda i, h, j: (i, chunk_of(j), h, 0, 0)),
        pl.BlockSpec((1, 1, 1, R * lay.D),
                     lambda i, h, j: (i, chunk_of(j), 0, h)))


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _forward(q, k, v, g, beta, chunk, sub, interpret, head_tile, save: bool):
    """(o ``[B, S, H, Dv]`` float32, every chunk's last ``Gamma`` ``[B, n,
    1, H D]`` float32, the states the chunks start from ``[B, n, H, Dv, D]``
    float32 if ``save`` else None)."""
    lay = _layout(q, v, chunk, sub, head_tile, g.ndim == 3)
    if g.ndim == 3:
        return _forward_a_head(q, k, v, g, beta, lay, interpret, save)
    if lay.group != 1:
        raise ValueError(f"{lay.Hk} key heads for {lay.H} value heads with "
                         "a decay a channel")
    wide, vwide, col, at_start, last = _specs(lay, lambda j: j)
    shapes = [
        jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * lay.Dv), _F32),
        jax.ShapeDtypeStruct((lay.B, lay.n, 1, lay.H * lay.D), _F32),
        jax.ShapeDtypeStruct((lay.B, lay.n, lay.H, lay.Dv, lay.D), _F32)]
    out_specs = [vwide, last, at_start]
    if not save:
        shapes, out_specs = shapes[:2], out_specs[:2]
    out = kernel_call(pl.pallas_call,
        functools.partial(_fwd_kernel, R=lay.R, D=lay.D, Dv=lay.Dv,
                          sub=lay.sub),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[wide, wide, vwide, wide, col],
        out_specs=out_specs, out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((lay.R, lay.Dv, lay.D), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=FWD_NAME,
    )(_flat(q, lay), _flat(k, lay), _flat(v, lay), _flat(g, lay),
      _columns(beta, lay))
    o = out[0].reshape(lay.B, lay.S, lay.H, lay.Dv)
    return o, out[1], (out[2] if save else None)


# -- backward -----------------------------------------------------------------

def _chunk_backward(q, k, v, beta, state, c: _Chunk, do, dstate, sub: int,
                    e_scr, dq_scr, dk_scr, dgamma_scr):
    """A generator (see :func:`_interleaved`) that returns the cotangents
    of one chunk of one head: (dq, dk ``[C, D]``, dv ``[C,
    Dv]``, dg ``[C, D]`` float32, dbeta ``[C, 1]``, the cotangent of the
    state the chunk started from ``[Dv, D]``). ``do`` ``[C, Dv]`` float32,
    ``dstate`` ``[Dv, D]`` float32 the cotangent of the state handed on.
    The three ``[C, D]`` float32 scratches take what is added a sub-block or
    a row at a time: dq and dk before their decays from the chunk's start,
    and the cotangent of ``Gamma``."""
    C, D = q.shape
    m, dtype = C // sub, q.dtype
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    later = _iota((sub, D), 0)
    qf, kf = q.astype(_F32), k.astype(_F32)
    do_low, dnext_low = do.astype(dtype), dstate.astype(dtype)
    r_low = c.r.astype(dtype)
    # o = q_start S + P R
    dq_start = _dot(do_low, c.state_low, _NN)
    dstart = _dot(do_low, c.q_start, _TN)
    dpairs = _dot(do_low, r_low, _NT)                       # [i, j]
    dr = _dot(c.pairs.astype(dtype), do_low, _TN)
    # S_next = Diag(whole) S + k_end^T R
    yield
    dwhole = jnp.sum(dstate * state, axis=0, keepdims=True)     # [1, D]
    dstart = _carry(dstate, c.whole, dstart)
    dk_end = _dot(r_low, dnext_low, _NN)
    dr = dr + _dot(c.k_end, dnext_low, _NT)
    # R = U - W S;  W = T k_start, U = T v
    dr_low = dr.astype(dtype)
    dw_low = (-_dot(dr_low, c.state_low, _NN)).astype(dtype)
    dstart = dstart - _dot(dr_low, c.w, _TN)
    dt = _dot(dw_low, c.k_start, _NT) + _dot(dr_low, v, _NT)
    dk_start = _dot(c.t, dw_low, _TN)
    dv = _dot(c.t, dr_low, _TN)
    yield
    # T = t0 Diag(beta), t0 = (I + L)^-1, L = Diag(beta) tril(A, -1)
    dbeta_row = jnp.sum(dt * c.t0, axis=0, keepdims=True)
    dt0 = dt * c.beta_row
    dl = jnp.where(rows > cols, -_dot(
        _dot(c.t0, dt0, _TN, _HIGHEST), c.t0, _NT, _HIGHEST), 0.0)
    dbeta = jnp.sum(dl * (c.k_pairs + c.k_diag), axis=1, keepdims=True)
    dk_pairs = dl * beta
    yield

    # the decays from the chunk's start and to its end
    end_part = dk_end * kf * c.until_end
    at_last = jnp.sum(end_part, axis=0, keepdims=True) + dwhole * c.whole
    dq_scr[...] = dq_start * c.since_start
    dk_scr[...] = dk_start * c.since_start + dk_end * c.until_end
    dgamma_scr[...] = ((dq_start * qf + dk_start * kf) * c.since_start
                       - end_part
                       + jnp.where(_iota((C, 1), 0) == C - 1, at_last, 0.0))

    # rows of two sub-blocks
    for blk in range(1, m):
        lo = blk * sub
        left, right, x_left, k_right = c.lefts[blk]
        both = jnp.concatenate([dpairs[lo:lo + sub], dk_pairs[lo:lo + sub]],
                               axis=0).astype(dtype)        # [2 sub, C]
        dx_left = _dot(both, k_right, _NN)                  # [2 sub, D]
        dk_right = _dot(both, x_left, _TN)[:lo]             # [lo, D]
        dq_left, dk_left = dx_left[:sub], dx_left[sub:]
        q_b, k_b = qf[lo:lo + sub], kf[lo:lo + sub]
        dq_scr[lo:lo + sub] += dq_left * left
        dk_scr[lo:lo + sub] += dk_left * left
        from_left = (dq_left * q_b + dk_left * k_b) * left
        dk_scr[:lo] += dk_right * right
        from_right = dk_right * kf[:lo] * right
        dgamma_scr[lo:lo + sub] += from_left
        dgamma_scr[:lo] -= from_right
        dgamma_scr[lo:lo + 1] += (
            jnp.sum(from_right, axis=0, keepdims=True)
            - jnp.sum(from_left, axis=0, keepdims=True))
        yield

    # rows of one sub-block, an earlier row t against the later rows at a
    # time: what reaches the later rows' q and k adds up over t, what
    # reaches k_t is a sum over the later rows. The decays' cotangent is
    # q dq + k dk of the later rows less k dk of the earlier one
    for blk in range(m):
        lo = blk * sub
        q_b, k_b = qf[lo:lo + sub], kf[lo:lo + sub]
        dq_later = jnp.zeros((sub, D), _F32)
        dk_later = jnp.zeros((sub, D), _F32)
        dk_earlier = jnp.zeros((sub, D), _F32)
        for t in range(sub):
            j, first = lo + t, _tile_of(t)
            rows_t = slice(lo + first, lo + sub)
            e = e_scr[j, first:]
            ke = e * kf[j:j + 1]
            dp_col = dpairs[rows_t, j:j + 1]                # [sub - first, 1]
            da_col = dk_pairs[rows_t, j:j + 1]
            dq_later = _set_from(dq_later, first,
                                 dq_later[first:] + dp_col * ke)
            dk_later = _set_from(dk_later, first,
                                 dk_later[first:] + da_col * ke)
            dk_t = jnp.sum((dp_col * q_b[first:] + da_col * k_b[first:]) * e,
                           axis=0, keepdims=True)
            dk_earlier = jnp.where(later == t, dk_t, dk_earlier)
            if t % 4 == 3:
                yield
        dq_scr[lo:lo + sub] += dq_later
        dk_scr[lo:lo + sub] += dk_later + dk_earlier
        dgamma_scr[lo:lo + sub] += (dq_later * q_b
                                    + (dk_later - dk_earlier) * k_b)
    dg = _sums_over_rows(rows <= cols, dgamma_scr[...])
    return (dq_scr[...], dk_scr[...], dv, dg,
            dbeta + _column_of(dbeta_row), dstart)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, start_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, e_scr,
                dq_scr, dk_scr, dgamma_scr, *, R: int, D: int, Dv: int,
                sub: int):
    """One chunk of ``R`` heads, the chunks walked from the last to the
    first; ``dstate`` the cotangent of the state a chunk hands on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    heads = [(slice(h * D, (h + 1) * D), slice(h * Dv, (h + 1) * Dv))
             for h in range(R)]
    operands = [(q_ref[0, :, cols], k_ref[0, :, cols], v_ref[0, :, vcols],
                 beta_ref[0, 0, :, h:h + 1], start_ref[0, 0, h])
                for h, (cols, vcols) in enumerate(heads)]
    chunks = _interleaved(*(
        _chunk_forward(q, k, v, g_ref[0, :, cols], beta, start, sub,
                       e_scr.at[h])
        for h, ((cols, _), (q, k, v, beta, start)) in enumerate(
            zip(heads, operands))))
    cotangents = _interleaved(*(
        _chunk_backward(*operands[h], chunks[h], do_ref[0, :, vcols],
                        dstate[h], sub, e_scr.at[h], dq_scr.at[h],
                        dk_scr.at[h], dgamma_scr.at[h])
        for h, (_, vcols) in enumerate(heads)))
    for h, ((cols, vcols), (dq, dk, dv, dg, dbeta, dstart)) in enumerate(
            zip(heads, cotangents)):
        dq_ref[0, :, cols] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, cols] = dk.astype(dk_ref.dtype)
        dv_ref[0, :, vcols] = dv.astype(dv_ref.dtype)
        dg_ref[0, :, cols] = dg
        dbeta_ref[0, 0, :, h:h + 1] = dbeta
        dstate[h] = dstart


def _backward(q, k, v, g, beta, states, do, chunk, sub, interpret, head_tile):
    lay = _layout(q, v, chunk, sub, head_tile, g.ndim == 3)
    if g.ndim == 3:
        return _backward_a_head(q, k, v, g, beta, states, do, lay, interpret)
    wide, vwide, col, at_start, _ = _specs(lay, lambda j: lay.n - 1 - j)
    C, D = lay.chunk, lay.D
    dq, dk, dv, dg, dbeta = kernel_call(pl.pallas_call,
        functools.partial(_bwd_kernel, R=lay.R, D=D, Dv=lay.Dv, sub=lay.sub),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[wide, wide, vwide, wide, col, vwide, at_start],
        out_specs=[wide, wide, vwide, wide, col],
        out_shape=[
            jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * D), q.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * D), k.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * lay.Dv), v.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * D), _F32),
            jax.ShapeDtypeStruct((lay.B, lay.steps, lay.S, lay.R), _F32)],
        scratch_shapes=[pltpu.VMEM((lay.R, lay.Dv, D), _F32),
                        pltpu.VMEM((lay.R, C, lay.sub, D), _F32),
                        pltpu.VMEM((lay.R, C, D), _F32),
                        pltpu.VMEM((lay.R, C, D), _F32),
                        pltpu.VMEM((lay.R, C, D), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=BWD_NAME,
    )(_flat(q, lay), _flat(k, lay), _flat(v, lay), _flat(g, lay),
      _columns(beta, lay), _flat(do.astype(_F32), lay), states)
    dbeta = dbeta.transpose(0, 2, 1, 3).reshape(beta.shape)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
            dg.reshape(g.shape).astype(g.dtype), dbeta.astype(beta.dtype))


# -- a decay a head -----------------------------------------------------------
#
# ``g`` ``[B, S, H]``: ``Gamma`` is one number a row, so the decay factors
# out of a chunk's pairs, ``A[i, j] = exp(Gamma_i - Gamma_j) (k_i . k_j)``:
# one ``[2 C, D] x [D, C]`` matmul a KEY head (q over k against k), shared
# by the value heads that read it, and a ``[C, C]`` float32 factor a value
# head with no positive exponent under the diagonal. No pairwise pass over
# sub-blocks, no lane reductions over the channels; forward substitution
# reads its columns out of ``A`` as it lies. ``H`` value heads read ``Hk``
# key heads (value head ``h`` key head ``h // (H / Hk)``): a grid step's
# block of q and k is its key heads', taken out of ``[B, S, Hk D]`` by the
# index map, and their cotangents are summed over a key head's value heads
# in VMEM.

def _running_sum(column):
    """``[C, 1]`` float32: row ``i`` the sum of rows ``<= i``, exact
    float32 sums on the vector unit."""
    C = column.shape[0]
    return jnp.sum(jnp.where(_iota((C, C), 0) >= _iota((C, C), 1),
                             _row_of(column), 0.0), axis=1, keepdims=True)


def _sum_of_later(column):
    """Row ``i`` the sum of rows ``>= i``: ``_running_sum`` transposed."""
    C = column.shape[0]
    return jnp.sum(jnp.where(_iota((C, C), 1) >= _iota((C, C), 0),
                             _row_of(column), 0.0), axis=1, keepdims=True)


def _chunk_forward_a_head(products, q, k, v, g, beta, state, sub: int):
    """:func:`_chunk_forward` for a decay a head, a generator that returns
    a :class:`_Chunk` whose ``gamma`` and decays are ``[C, 1]``, whose
    ``lefts`` is the pairs' factor ``exp(Gamma_i - Gamma_j)`` (lower with the
    diagonal) and whose ``k_pairs`` is all of ``A`` under the diagonal.
    ``products`` ``[2 C, C]`` float32: the key head's ``q k^T`` over its ``k
    k^T``; g, beta ``[C, 1]``."""
    C, D = k.shape
    m, dtype = C // sub, k.dtype
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    gamma = _running_sum(g)
    factor = _decay(jnp.where(_reaches(rows, cols), gamma - _row_of(gamma),
                              -jnp.inf))
    last = gamma[C - 1:C]
    since_start = _decay(gamma)
    until_end = _decay(last - gamma)
    whole = _decay(last)
    pairs = products[:C] * factor
    k_pairs = jnp.where(rows > cols, products[C:] * factor, 0.0)
    lower = k_pairs * beta
    yield

    # the sub-blocks on the diagonal by forward substitution, side by side
    # on the lanes of one ``[sub, C]`` array: step t takes column t of every
    # block (nothing on or above the diagonal) times row t from the rows
    lane = _iota((sub, C), 1)
    block_of_lane = lane // sub
    x = (lane % sub == _iota((sub, C), 0)).astype(_F32)
    diag = jnp.zeros((sub, C), _F32)
    for blk in range(m):
        diag = jnp.where(block_of_lane == blk,
                         lower[blk * sub:(blk + 1) * sub], diag)
    for t in range(sub - 1):
        column = jnp.zeros((sub, C), _F32)
        for blk in range(m):
            at = blk * sub + t
            column = jnp.where(block_of_lane == blk, diag[:, at:at + 1],
                               column)
        x = x - column * x[t:t + 1]
        if t % 4 == 3:
            yield
    d = jnp.concatenate(
        [jnp.where(block_of_lane == blk, x, 0.0) for blk in range(m)],
        axis=0)
    t0 = d
    if m > 1:       # the blocks merged, as in :func:`_chunk_forward`
        under = jnp.where(rows // sub > cols // sub, lower, 0.0)
        e_p = _dot(d, under, _NN, _HIGHEST)
        yield
        t0, power = d - _dot(e_p, d, _NN, _HIGHEST), 2
        while power < m:
            e_p = _dot(e_p, e_p, _NN, _HIGHEST)
            yield
            t0 = t0 + _dot(e_p, t0, _NN, _HIGHEST)
            power *= 2
        yield
    qf, kf = q.astype(_F32), k.astype(_F32)
    beta_row = _row_of(beta)
    t = (t0 * beta_row).astype(dtype)
    k_start = (kf * since_start).astype(dtype)
    k_end = (kf * until_end).astype(dtype)
    q_start = (qf * since_start).astype(dtype)
    w = _dot(t, k_start, _NN).astype(dtype)
    u = _dot(t, v, _NN)
    yield
    state_low = state.astype(dtype)
    from_state = _dot(jnp.concatenate([w, q_start], axis=0), state_low, _NT)
    return _Chunk(gamma, since_start, until_end, whole, factor, pairs,
                  k_pairs, None, t0, beta_row, t, k_start, k_end, q_start,
                  w, u - from_state[:C], from_state[C:], state_low)


def _key_products(q_ref, k_ref, lay):
    """A grid step's key heads: (q, k, ``[q k^T ; k k^T]`` float32) each."""
    keys = []
    for head in range(lay.key_tile):
        cols = slice(head * lay.D, (head + 1) * lay.D)
        q, k = q_ref[0, :, cols], k_ref[0, :, cols]
        keys.append((q, k, _dot(jnp.concatenate([q, k], axis=0), k, _NT)))
    return keys


def _fwd_kernel_a_head(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest,
                       lay):
    """One chunk of ``R`` value heads. ``rest``: the output of the states
    the chunks start from (under differentiation only), then the scratch."""
    state = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    R, Dv, dtype = lay.R, lay.Dv, q_ref.dtype
    starts = [state[h] for h in range(R)]
    if len(rest) == 2:
        for h in range(R):
            rest[0][0, 0, h] = starts[h]
    keys = _key_products(q_ref, k_ref, lay)
    chunks = _interleaved(*(
        _chunk_forward_a_head(
            keys[h // lay.group][2], *keys[h // lay.group][:2],
            v_ref[0, :, h * Dv:(h + 1) * Dv], g_ref[0, 0, :, h:h + 1],
            beta_ref[0, 0, :, h:h + 1], starts[h], lay.sub)
        for h in range(R)))
    for h, c in enumerate(chunks):
        o_ref[0, :, h * Dv:(h + 1) * Dv] = _chunk_output(c, dtype)
        state[h] = _chunk_next_state(c, starts[h], dtype)


def _chunk_backward_a_head(q, k, v, beta, state, c: _Chunk, do, dstate):
    """:func:`_chunk_backward` for a decay a head: (dq, dk ``[C, D]``
    float32 without the pairs' part, dv ``[C, Dv]``, dg, dbeta ``[C, 1]``,
    the cotangent of the state the chunk started from ``[Dv, D]``, the
    cotangent of the key head's products ``[2 C, C]`` float32)."""
    C, D = k.shape
    dtype = k.dtype
    rows, cols = _iota((C, C), 0), _iota((C, C), 1)
    qf, kf = q.astype(_F32), k.astype(_F32)
    do_low, dnext_low = do.astype(dtype), dstate.astype(dtype)
    r_low = c.r.astype(dtype)
    # o = q_start S + P R
    dq_start = _dot(do_low, c.state_low, _NN)
    dstart = _dot(do_low, c.q_start, _TN)
    dpairs = _dot(do_low, r_low, _NT)                       # [i, j]
    dr = _dot(c.pairs.astype(dtype), do_low, _TN)
    yield
    # S_next = whole S + k_end^T R
    dwhole = jnp.sum(jnp.sum(dstate * state, axis=0, keepdims=True),
                     axis=1, keepdims=True)                 # [1, 1]
    dstart = _carry(dstate, c.whole, dstart)
    dk_end = _dot(r_low, dnext_low, _NN)
    dr = dr + _dot(c.k_end, dnext_low, _NT)
    # R = U - W S;  W = T k_start, U = T v
    dr_low = dr.astype(dtype)
    dw_low = (-_dot(dr_low, c.state_low, _NN)).astype(dtype)
    dstart = dstart - _dot(dr_low, c.w, _TN)
    dt = _dot(dw_low, c.k_start, _NT) + _dot(dr_low, v, _NT)
    dk_start = _dot(c.t, dw_low, _TN)
    dv = _dot(c.t, dr_low, _TN)
    yield
    # T = t0 Diag(beta), t0 = (I + L)^-1, L = Diag(beta) tril(A, -1)
    dbeta_row = jnp.sum(dt * c.t0, axis=0, keepdims=True)
    dt0 = dt * c.beta_row
    dl = jnp.where(rows > cols, -_dot(
        _dot(c.t0, dt0, _TN, _HIGHEST), c.t0, _NT, _HIGHEST), 0.0)
    dbeta = jnp.sum(dl * c.k_pairs, axis=1, keepdims=True)
    dk_pairs = dl * beta
    yield
    # the decays from the chunk's start and to its end: a number a row
    end_part = jnp.sum(dk_end * kf, axis=1, keepdims=True) * c.until_end
    at_last = jnp.sum(end_part, axis=0, keepdims=True) + dwhole * c.whole
    dq = dq_start * c.since_start
    dk = dk_start * c.since_start + dk_end * c.until_end
    # the pairs' factor: exp(Gamma_i - Gamma_j) gives row i and takes from
    # row j what the pair's cotangent times the pair is
    through = dpairs * c.pairs + dk_pairs * c.k_pairs
    dgamma = (jnp.sum(dq_start * qf + dk_start * kf, axis=1, keepdims=True)
              * c.since_start - end_part
              + jnp.where(_iota((C, 1), 0) == C - 1, at_last, 0.0)
              + jnp.sum(through, axis=1, keepdims=True)
              - _column_of(jnp.sum(through, axis=0, keepdims=True)))
    dproducts = jnp.concatenate([dpairs * c.lefts, dk_pairs * c.lefts],
                                axis=0)
    return (dq, dk, dv, _sum_of_later(dgamma),
            dbeta + _column_of(dbeta_row), dstart, dproducts)


def _bwd_kernel_a_head(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref,
                       start_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref,
                       dstate, *, lay):
    """One chunk of ``R`` value heads, the chunks walked from the last to
    the first; ``dstate`` the cotangent of the state a chunk hands on."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    R, D, Dv, C = lay.R, lay.D, lay.Dv, lay.chunk
    dtype = q_ref.dtype
    keys = _key_products(q_ref, k_ref, lay)
    operands = [(*keys[h // lay.group][:2], v_ref[0, :, h * Dv:(h + 1) * Dv],
                 beta_ref[0, 0, :, h:h + 1], start_ref[0, 0, h])
                for h in range(R)]
    chunks = _interleaved(*(
        _chunk_forward_a_head(keys[h // lay.group][2], q, k, v,
                              g_ref[0, 0, :, h:h + 1], beta, start, lay.sub)
        for h, (q, k, v, beta, start) in enumerate(operands)))
    cotangents = _interleaved(*(
        _chunk_backward_a_head(*operands[h], chunks[h],
                               do_ref[0, :, h * Dv:(h + 1) * Dv], dstate[h])
        for h in range(R)))
    for h, (_dq, _dk, dv, dg, dbeta, dstart, _) in enumerate(cotangents):
        dv_ref[0, :, h * Dv:(h + 1) * Dv] = dv.astype(dv_ref.dtype)
        dg_ref[0, 0, :, h:h + 1] = dg
        dbeta_ref[0, 0, :, h:h + 1] = dbeta
        dstate[h] = dstart
    for head, (q, k, _) in enumerate(keys):
        mine = [cot for h, cot in enumerate(cotangents)
                if h // lay.group == head]
        dq, dk, dproducts = (sum(cot[i] for cot in mine) for i in (0, 1, 6))
        dproducts = dproducts.astype(dtype)
        # products = [q ; k] k^T
        dq = dq + _dot(dproducts[:C], k, _NN)
        dk = (dk + _dot(dproducts[:C], q, _TN) + _dot(dproducts[C:], k, _NN)
              + _dot(dproducts[C:], k, _TN))
        cols = slice(head * D, (head + 1) * D)
        dq_ref[0, :, cols] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, cols] = dk.astype(dk_ref.dtype)


def _specs_a_head(lay: _Layout, chunk_of):
    """Block specs of a chunk's (q-like, v-like, beta-like, states)
    arrays: the q-like block is the step's key heads'."""
    C, R = lay.chunk, lay.R
    per_key_block = lay.group * lay.key_tile    # value heads a key block
    return (
        pl.BlockSpec((1, C, lay.key_tile * lay.D),
                     lambda i, h, j: (i, chunk_of(j), h * R // per_key_block)),
        pl.BlockSpec((1, C, R * lay.Dv), lambda i, h, j: (i, chunk_of(j), h)),
        pl.BlockSpec((1, 1, C, R), lambda i, h, j: (i, h, chunk_of(j), 0)),
        pl.BlockSpec((1, 1, R, lay.Dv, lay.D),
                     lambda i, h, j: (i, chunk_of(j), h, 0, 0)))


def _forward_a_head(q, k, v, g, beta, lay: _Layout, interpret, save: bool):
    key, vwide, col, at_start = _specs_a_head(lay, lambda j: j)
    shapes = [
        jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * lay.Dv), _F32),
        jax.ShapeDtypeStruct((lay.B, lay.n, lay.H, lay.Dv, lay.D), _F32)]
    out_specs = [vwide, at_start]
    if not save:
        shapes, out_specs = shapes[:1], out_specs[:1]
    out = kernel_call(pl.pallas_call,
        functools.partial(_fwd_kernel_a_head, lay=lay),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[key, key, vwide, col, col],
        out_specs=out_specs, out_shape=shapes,
        scratch_shapes=[pltpu.VMEM((lay.R, lay.Dv, lay.D), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=FWD_NAME,
    )(_flat(q, lay), _flat(k, lay), _flat(v, lay), _columns(g, lay),
      _columns(beta, lay))
    o = out[0].reshape(lay.B, lay.S, lay.H, lay.Dv)
    # every chunk's last Gamma: a sum of g's 1 MB, XLA's
    last = jnp.sum(g.reshape(lay.B, lay.n, lay.chunk, lay.H), axis=2)
    return o, last[:, :, None, :], (out[1] if save else None)


def _backward_a_head(q, k, v, g, beta, states, do, lay: _Layout, interpret):
    key, vwide, col, at_start = _specs_a_head(lay, lambda j: lay.n - 1 - j)
    keys_out = lay.steps * lay.key_tile     # key heads written, a step its own
    small = jax.ShapeDtypeStruct((lay.B, lay.steps, lay.S, lay.R), _F32)
    key_out = pl.BlockSpec((1, lay.chunk, lay.key_tile * lay.D),
                           lambda i, h, j: (i, lay.n - 1 - j, h))
    dq, dk, dv, dg, dbeta = kernel_call(pl.pallas_call,
        functools.partial(_bwd_kernel_a_head, lay=lay),
        grid=(lay.B, lay.steps, lay.n),
        in_specs=[key, key, vwide, col, col, vwide, at_start],
        out_specs=[key_out, key_out, vwide, col, col],
        out_shape=[
            jax.ShapeDtypeStruct((lay.B, lay.S, keys_out * lay.D), q.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.S, keys_out * lay.D), k.dtype),
            jax.ShapeDtypeStruct((lay.B, lay.S, lay.H * lay.Dv), v.dtype),
            small, small],
        scratch_shapes=[pltpu.VMEM((lay.R, lay.Dv, lay.D), _F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret, name=BWD_NAME,
    )(_flat(q, lay), _flat(k, lay), _flat(v, lay), _columns(g, lay),
      _columns(beta, lay), _flat(do.astype(_F32), lay), states)

    def heads(x):       # [B, H / R, S, R] -> [B, S, H]
        return x.transpose(0, 2, 1, 3).reshape(beta.shape)

    def key_heads(x):   # a key head's steps' parts summed
        x = x.reshape(lay.B, lay.S, lay.Hk, keys_out // lay.Hk, lay.D)
        return (x[:, :, :, 0] if keys_out == lay.Hk
                else jnp.sum(x.astype(_F32), axis=3).astype(x.dtype))
    return (key_heads(dq), key_heads(dk), dv.reshape(v.shape),
            heads(dg).astype(g.dtype), heads(dbeta).astype(beta.dtype))


# -- the differentiable call --------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def delta_scan(q, k, v, g, beta, chunk: int, sub: int = 16,
               interpret: bool = False, head_tile: Optional[int] = None):
    """The scan of ``delta_chunked`` on the kernels. q, k ``[B, S, H, D]``,
    v ``[B, S, H, Dv]``; g ``[B, S, H, D]`` float32, never positive; beta
    ``[B, S, H]`` float32. Returns (o ``[B, S, H, Dv]`` float32, every
    chunk's last ``Gamma`` ``[B, n, 1, H D]`` float32, which carries no
    gradient). Differentiable in all five. ``head_tile`` overrides
    :func:`delta_head_tile` (tests, sweeps). A decay a head: g ``[B, S,
    H]``, q and k ``[B, S, Hk, D]`` with ``Hk`` dividing ``H``, the last
    ``Gamma`` ``[B, n, 1, H]``; the kernels' bodies are the form's own."""
    return _forward(q, k, v, g, beta, chunk, sub, interpret, head_tile,
                    save=False)[:2]


def _scan_fwd(q, k, v, g, beta, chunk, sub, interpret, head_tile):
    o, last, states = _forward(q, k, v, g, beta, chunk, sub, interpret,
                               head_tile, save=True)
    return (o, last), (q, k, v, g, beta, states)


def _scan_bwd(chunk, sub, interpret, head_tile, res, cotangents):
    return _backward(*res, cotangents[0], chunk, sub, interpret, head_tile)


delta_scan.defvjp(_scan_fwd, _scan_bwd)
