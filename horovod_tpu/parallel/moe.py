"""Mixture-of-Experts with expert parallelism over the ``ep`` mesh axis.

The reference's users build MoE from ``alltoall`` + process sets (SURVEY.md
§2.6: EP "absent as a strategy; alltoall + process sets are the primitives").
Here the strategy ships, **dropless**: every (token, choice) assignment is
computed, whatever the imbalance, by sorting the assignments by expert and
running one grouped matmul per expert matrix over the ragged groups
(MegaBlocks, arXiv:2211.15841; how OLMoE is trained, arXiv:2409.02060).
All shapes are static: ``G * k`` rows, ``E`` group sizes that sum to it.

Dataflow per shard (G local tokens, E experts, k choices a token):
  probs    = softmax(x @ router_w)            float32            [G, E]
  weights, experts = top_k(probs, k)                             [G, k]
  order    = stable argsort of the G*k assignments by expert
  rows     = x[order // k]                                       [G*k, M]
  rows     = expert_fn(expert_params, rows, group_sizes)   grouped matmuls
             (on a TPU the megablox Pallas kernels, named ``hvd_moe_gmm``)
  y        = sum_j weights[:, j] * rows[inverse(order)[j::k]]    [G, M]
Both row movements are gathers in both directions (the permutation one
way, its inverse the other), so no scatter-add is traced; every row-sized
array is 2-D in sorted order (``[G * k, M]``, the rows no expert here
computes a contiguous tail) or k token-major slabs end to end
(``[k, G, M]``), never ``[G, k, M]``; both backward passes are written by
hand (:func:`_dispatch`, :func:`_combine`).

Of the ``G * k`` sorted rows only the first ``held = sum(group_sizes)`` are
an expert's here (all of them, unless the experts are sharded or the device
holds a ``share``), and nothing reads a sorted row behind them: the grouped
matmuls visit their groups' tiles only and leave the rest unwritten; the
row-wise passes (the experts' activation between the kernels, its backward
pass and the sum of the rows' two cotangents, :func:`expert_ffn`; the
combine's backward pass) run over the row chunks that hold a held row
(:func:`_over_held_chunks`, a loop with a device trip count; one pass and
no loop where every row is held); and what the token-major gathers
(:func:`_by_choice`) fetch from there is replaced by zeros. Those gathers
read the held rows out of a prefix of the sorted rows that XLA:TPU gathers
from at its fast rate, where the held rows lie within it.

With ``ep`` > 1 the experts are sharded and the tokens of the ep group are
exchanged the simplest static-shape way: every shard gathers the group's
tokens and routing (``all_gather``), computes the rows of its own experts
(sorted to the front; the rows behind them are the other shards' to compute,
and count as zeros in this shard's sums) and the weighted partial sums
return to their home shards by ``psum_scatter``.

A device can also be told which experts it holds with no ``ep`` axis live
(``share=(i, of)``: experts ``[i * E / of, (i + 1) * E / of)``, one chip of
an expert-parallel group run alone): it routes over all ``E``, sorts its own
experts' assignments to the front as an ``ep`` shard does, and its weighted
sum is its part of the layer's output. That is the ``ep`` path without its
``all_gather`` / ``psum_scatter``; nothing stands in for the absent chips.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu._compat import axis_size, shard_map
from horovod_tpu.profiling import scopes
from horovod_tpu.profiling.compile_watch import kernel_trace


class MoEMetrics(NamedTuple):
    #: ``E * sum_e f_e * P_e``: f_e the share of tokens that chose expert e
    #: among their k (sums to k), P_e the mean router probability
    load_balance_loss: jax.Array
    #: ``mean(logsumexp(logits) ** 2)``
    router_z_loss: jax.Array
    #: largest group over the mean group
    max_expert_load: jax.Array
    #: assignments to experts held here (every assignment, unless the
    #: device holds a ``share`` of the experts) that no grouped matmul row
    #: was computed for: always 0
    dropped: jax.Array
    #: assignments of the global batch to the experts held here: ``G * k``
    #: of it unless the device holds a ``share`` of the experts
    held_rows: jax.Array
    #: the k experts each of the shard's tokens chose, ``[G, k]`` (the others
    #: are scalars of the global batch)
    experts: jax.Array


#: the name of the three Pallas calls on the device: ``hvd_moe_gmm`` forward,
#: ``transpose_jvp_hvd_moe_gmm`` the input's and the weights' gradients
GMM_NAME = "hvd_moe_gmm"
#: what one call's blocks may take of the 16 MiB of scoped VMEM (megablox's
#: ``pallas_call`` asks for no limit of its own): two buffers of each input
#: and output block and the float32 accumulator. The rest is Mosaic's, for
#: the operands it copies and masks inside the kernel; the compiles that
#: drew the line are in PERF.md section 6 (PR 33)
GMM_VMEM_BUDGET = 14 * 2 ** 20
#: row tiles in the order a call prefers them. A group that does not start
#: on a row tile visits one tile more, so with its weights resident a small
#: tile costs no bandwidth and wastes fewer rows: 256 before 128, which
#: fills the MXU's passes worse (the sweep in PERF.md section 6, PR 33)
GMM_ROW_TILES = (256, 128)
#: where the contraction is split the weight blocks are fetched again every
#: row tile, and the largest row tile pays for them best
GMM_ROW_TILES_SPLIT = (512, 256, 128)


class GmmTiles(NamedTuple):
    """megablox's ``(tm, tk, tn)`` for each of the three calls: rows, the
    call's own contraction, the call's own output columns; and which way
    round the calls read the weights."""
    #: ``gmm``: rows ``[N, K]`` against the weights, contraction K, columns F
    forward: Tuple[int, int, int]
    #: ``gmm``: cotangent ``[N, F]`` against the same weights, contraction
    #: F, columns K: the other way round (of the two, the one that meets the
    #: weights with its contraction last is the ``transpose_rhs`` call)
    input_grad: Tuple[int, int, int]
    #: ``tgmm``: a group's rows streamed through in ``tm`` steps into its
    #: ``[tk, tn]`` block of the result, ``[E, K, F]`` or ``[E, F, K]``
    weight_grad: Tuple[int, int, int]
    #: the calls take and return the weights as ``[E, F, K]``
    #: (:func:`_stored_transposed`)
    transposed: bool = False


def _lane_tiles(width: int):
    """The 128-multiples that divide ``width``, widest first (the TPU
    lowering wants a block's last dimension a multiple of 128 lanes, or the
    array's whole dimension); where none does and the width is at least a
    lane tile, the whole width as one block (1856 = 2^6 * 29)."""
    tiles = [t for t in range(width - width % 128, 0, -128)
             if width % t == 0]
    return tiles or ([width] if width >= 128 else [])


def _fits(lhs, rhs, out, itemsize: int) -> bool:
    """Whether a call's blocks ``(rows, columns)`` stay under
    :data:`GMM_VMEM_BUDGET`: two buffers of each and a float32 accumulator
    of the output block's shape."""
    elements = sum(r * c for r, c in (lhs, rhs, out))
    return (2 * itemsize * elements + 4 * out[0] * out[1]
            <= GMM_VMEM_BUDGET)


def _gmm_call_tile(n_rows: int, k: int, n: int, itemsize: int):
    """Tile of one ``gmm`` call with contraction ``k`` and ``n`` output
    columns. The weight block's index is (group, k tile, n tile): with the
    whole contraction in one block, consecutive row tiles of a group ask for
    the same block and it stays in VMEM, read once a group instead of once
    a row tile. So: the widest contraction first, then the widest columns
    (the rows are read once a column tile), then the row tile, all under
    :data:`GMM_VMEM_BUDGET`."""
    for tk in _lane_tiles(k):
        row_tiles = GMM_ROW_TILES if tk == k else GMM_ROW_TILES_SPLIT
        for tn in _lane_tiles(n):
            for tm in row_tiles:
                if n_rows % tm == 0 and _fits((tm, tk), (tk, tn), (tm, tn),
                                              itemsize):
                    return tm, tk, tn
    return None


def _tgmm_call_tile(n_rows: int, k: int, n: int, itemsize: int):
    """Tile of the ``tgmm`` call: the largest ``[tk, tn]`` output block
    under the budget (a group's rows are read ``k / tk`` and ``n / tn``
    times, ``tk * tn / (tk + tn)`` FLOPs a byte), with the first row tile
    of :data:`GMM_ROW_TILES` that fits beside it."""
    best = None
    for tk in _lane_tiles(k):
        for tn in _lane_tiles(n):
            for tm in GMM_ROW_TILES:
                if n_rows % tm == 0 and _fits((tm, tk), (tm, tn), (tk, tn),
                                              itemsize):
                    rank = (tk * tn, tk * tn / (tk + tn))
                    if best is None or rank > best[0]:
                        best = rank, (tm, tk, tn)
                    break
    return best and best[1]


def _stored_transposed(k: int, f: int) -> bool:
    """Whether XLA:TPU keeps weights ``[E, k, f]`` with ``k`` on the lanes:
    its layout for an array whose last dimension is no multiple of 128 while
    the one before it is (``f32[8,2688,1856]{1,2,0}``; 1920 columns get the
    plain ``{2,1,0}``). ``jnp.swapaxes(weights, -1, -2)`` is then the array
    as it lies in memory, row-major, which is how a Pallas call wants its
    operand; handed ``[E, k, f]`` itself, the step copies the weights and
    both moments to row-major and back for the update (six copies of 638 MB
    in the hybrid cell, 12.15 ms: PERF.md section 6, PR 41)."""
    return f % 128 != 0 and k % 128 == 0


def _gmm_tile(n_rows: int, k: int, f: int, itemsize: int):
    """:class:`GmmTiles` for rows ``[n_rows, k]`` of ``itemsize`` bytes an
    element against weights ``[E, k, f]``, each call's tile from that call's
    own shape (a ``tgmm`` that writes ``[E, f, k]`` has ``f`` for its
    ``tk``); None where the kernels do not apply (rows or a width that no
    128-multiple divides: XLA's ``ragged_dot``)."""
    transposed = _stored_transposed(k, f)
    written = (f, k) if transposed else (k, f)
    tiles = (_gmm_call_tile(n_rows, k, f, itemsize),
             _gmm_call_tile(n_rows, f, k, itemsize),
             _tgmm_call_tile(n_rows, *written, itemsize))
    return GmmTiles(*tiles, transposed) if all(tiles) else None


def gmm_path(n_rows: int, k: int, f: int, dtype=jnp.bfloat16) -> str:
    """Which implementation :func:`grouped_matmul` takes on the default
    backend for rows ``[n_rows, k]`` of ``dtype`` and weights ``[E, k, f]``,
    which way round the calls read the weights, the tile of each of the
    three calls and why (``chip_smoke.py`` prints it, as it does
    ``attend``'s choice)."""
    tiles = _gmm_tile(n_rows, k, f, jnp.dtype(dtype).itemsize)
    if tiles is None:
        return (f"xla ragged_dot (no 128-multiple tile divides rows "
                f"{n_rows} and widths {k}, {f})")
    if jax.default_backend() != "tpu":
        return f"xla ragged_dot (backend {jax.default_backend()})"

    def say(tile, contraction):
        resident = (", a group's weights resident"
                    if tile[1] == contraction else "")
        return "x".join(map(str, tile)) + resident
    if tiles.transposed:
        read = (f"weights read as stored, [E, {f}, {k}] ({f} columns are no "
                f"multiple of 128 lanes and {k} rows are, so the chip keeps "
                f"the rows minor: transpose_rhs forward, the weight "
                f"gradient written [E, {f}, {k}])")
    else:
        read = (f"weights read as stored, [E, {k}, {f}] row-major "
                f"(transpose_rhs in the input gradient)")
    return (f"pallas {GMM_NAME} {read}: forward {say(tiles.forward, k)}; "
            f"input gradient {say(tiles.input_grad, f)}; "
            f"weight gradient {'x'.join(map(str, tiles.weight_grad))} "
            f"(whole contraction first, else the widest blocks under "
            f"{GMM_VMEM_BUDGET >> 20} MiB of VMEM)")


def _megablox():
    """megablox's two kernels without their own ``jit``, whose name would
    else stand in the instruction's name in place of :data:`GMM_NAME`."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm
    return [getattr(f, "__wrapped__", f) for f in (gmm, tgmm)]


def _zero_beyond(rows, group_sizes):
    """Zeros in the rows beyond the groups, which XLA's ``ragged_dot`` fills
    with zeros on the CPU and with products on a TPU (``chip_smoke.py``
    reads both; PERF.md section 6, PR 26)."""
    inside = jnp.arange(rows.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(inside[:, None], rows, 0)


def _ragged_dot(rows, weights, group_sizes):
    return lax.ragged_dot(rows, weights.astype(rows.dtype), group_sizes,
                          preferred_element_type=jnp.float32
                          ).astype(rows.dtype)


def _as_read(weights, tiles: GmmTiles, dtype):
    """The weights as the three calls read them: in the rows' ``dtype``,
    ``[E, F, K]`` where they are stored that way (a bitcast there, not a
    copy)."""
    if tiles.transposed:
        weights = jnp.swapaxes(weights, -1, -2)
    return weights.astype(dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _gmm(rows, weights, group_sizes, tiles, interpret):
    """``tiles`` None: XLA's ``ragged_dot``, zeros beyond the groups; else
    the megablox kernels at :class:`GmmTiles`, which visit the groups' tiles
    only: a row beyond the groups is never written, forward or backward,
    and never read into a weight's gradient."""
    if tiles is None:
        return _zero_beyond(_ragged_dot(rows, weights, group_sizes),
                            group_sizes)
    gmm, _tgmm = _megablox()
    with jax.named_scope(GMM_NAME), kernel_trace(GMM_NAME):
        return gmm(rows, _as_read(weights, tiles, rows.dtype), group_sizes,
                   rows.dtype, tiles.forward, transpose_rhs=tiles.transposed,
                   interpret=interpret)


def _gmm_fwd(rows, weights, group_sizes, tiles, interpret):
    return (_gmm(rows, weights, group_sizes, tiles, interpret),
            (rows, weights, group_sizes))


def _gmm_d_rows(tiles, interpret, rows, weights, group_sizes, g):
    """The cotangent of :func:`_gmm`'s ``rows`` (of which only shape and
    dtype are read) under its result's cotangent ``g``."""
    if tiles is None:
        # what XLA does with a row beyond the groups is its own: no such
        # row's cotangent may reach a gradient
        (d_rows,) = jax.linear_transpose(
            lambda r: _ragged_dot(r, weights, group_sizes),
            jax.ShapeDtypeStruct(rows.shape, rows.dtype))(
                _zero_beyond(g, group_sizes))
        return _zero_beyond(d_rows, group_sizes)
    gmm, _ = _megablox()
    with jax.named_scope(GMM_NAME), kernel_trace(GMM_NAME):
        return gmm(g, _as_read(weights, tiles, rows.dtype), group_sizes,
                   rows.dtype, tiles.input_grad,
                   transpose_rhs=not tiles.transposed, interpret=interpret)


def _gmm_d_weights(tiles, interpret, rows, weights, group_sizes, g):
    """The cotangent of :func:`_gmm`'s ``weights`` under ``g``."""
    if tiles is None:
        (d_weights,) = jax.vjp(
            lambda w: _ragged_dot(rows, w, group_sizes), weights)[1](
                _zero_beyond(g, group_sizes))
        return d_weights
    _, tgmm = _megablox()
    with jax.named_scope(GMM_NAME), kernel_trace(GMM_NAME):
        # [E, K, F] is rows^T g; read as stored it is [E, F, K], g^T rows
        # swapped back: the array the update reads beside the weights
        # and their moments, in their layout
        lhs, rhs = (g, rows) if tiles.transposed else (rows, g)
        d_weights = tgmm(lhs.swapaxes(0, 1), rhs, group_sizes, rows.dtype,
                         tiles.weight_grad, interpret=interpret)
        if tiles.transposed:
            d_weights = jnp.swapaxes(d_weights, -1, -2)
    return d_weights.astype(weights.dtype)


def _gmm_bwd(tiles, interpret, res, g):
    rows, weights, group_sizes = res
    return (_gmm_d_rows(tiles, interpret, rows, weights, group_sizes, g),
            _gmm_d_weights(tiles, interpret, rows, weights, group_sizes, g),
            None)


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def _gmm_tiles(n_rows: int, weights, dtype, interpret: bool):
    """:func:`_gmm`'s tiles for ``n_rows`` rows of ``dtype`` against
    ``weights``: the kernels' on a TPU (and under ``interpret``) where they
    apply, else None."""
    on_kernels = interpret or jax.default_backend() == "tpu"
    return _gmm_tile(n_rows, *weights.shape[1:],
                     jnp.dtype(dtype).itemsize) if on_kernels else None


def grouped_matmul(rows: jax.Array, weights: jax.Array,
                   group_sizes: jax.Array, interpret: bool = False
                   ) -> jax.Array:
    """``rows[start_g:end_g] @ weights[g]`` for each group g of consecutive
    rows. rows ``[N, K]``, weights ``[E, K, F]`` (cast to ``rows.dtype``),
    group_sizes ``[E]`` int32; float32 accumulation, the result in
    ``rows.dtype``. Rows beyond ``sum(group_sizes)`` belong to no group
    and add nothing to the weights' gradient. What the result and the rows'
    gradient hold there is for no one to read: zeros from ``ragged_dot``,
    whatever the buffer held from the kernels, which never write them (a
    select over every row to zero a tail the expert layer does not read
    was 7.5 ms a step of the share cell: PERF.md section 6, PR 37).

    On a TPU (and under ``interpret``) the megablox Pallas kernels of the
    installed JAX, kept over XLA's ``ragged_dot`` kernels by the sweeps in
    PERF.md, each of the three calls at a tile from its own shape and
    ``rows.dtype`` (:func:`_gmm_tile`), reading the weights, and writing
    their gradient, the way round the chip stores them
    (:func:`_stored_transposed`: by the two widths modulo 128, so that no
    call makes the step copy a parameter); elsewhere, or where no row tile
    divides ``N`` or a width is no multiple of 128 lanes,
    ``jax.lax.ragged_dot``, the same function as plain XLA (the pattern of
    ``pallas_attention.attend``; :func:`gmm_path` says which and why)."""
    return _gmm(rows, weights, group_sizes.astype(jnp.int32),
                _gmm_tiles(rows.shape[0], weights, rows.dtype, interpret),
                interpret)


def route(logits: jax.Array, k: int, renormalize: bool,
          scores: str = "softmax", bias: Optional[jax.Array] = None,
          scale: float = 1.0) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Float32 scores over the experts, then top-k. Returns the scores
    ``[G, E]``, the k weights (divided by their sum when ``renormalize``)
    and the k expert indices ``[G, k]``. ``scores`` "softmax": the softmax
    over the experts. "sigmoid" (DeepSeek-V3's router, arXiv:2412.19437,
    which Nemotron-H's experts take): each expert's own ``sigmoid(logit)``;
    the choice is the top-k of ``score + bias`` (``bias`` ``[E]``, the
    correction that balances the load: it moves the choice and never a
    weight, so no gradient reaches it), the weights are the chosen scores,
    then ``scale`` times them."""
    if scores == "softmax":
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        weights, experts = lax.top_k(probs, k)
    elif scores == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, experts = lax.top_k(
            probs if bias is None else probs + bias.astype(jnp.float32), k)
        weights = jnp.take_along_axis(probs, experts, axis=-1)
    else:
        raise ValueError(f"router scores {scores!r}")
    if renormalize:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    if scale != 1.0:
        weights = weights * scale
    return probs, weights, experts


#: the most rows one pass of the sorted-order loop takes. The last chunk of
#: the held rows is half dead on average, so a chunk is a small part of a
#: share's rows; a loop step costs microseconds, so it is no smaller (1024
#: and 4096 read within 0.2 % of it: PERF.md section 6, PR 37)
ROW_CHUNK = 2048
#: the most bytes of a row gather's source that XLA:TPU is known to copy
#: into on-chip memory and gather from there: 49 152 rows out of a source
#: of 94 MB take 0.50 ms on a v5e, out of one of 126 MB 2.5 (PERF.md
#: section 6, PR 37)
GATHER_SOURCE_BYTES = 90 * 2 ** 20


def _row_chunk(n_rows: int) -> int:
    """Rows a pass of the sorted-order loop: the largest divisor of
    ``n_rows`` up to :data:`ROW_CHUNK`, so that every chunk is whole; rows
    with no divisor within an eighth of that go in one piece."""
    chunk = next(c for c in range(min(ROW_CHUNK, n_rows), 0, -1)
                 if n_rows % c == 0)
    return chunk if chunk * 8 >= min(ROW_CHUNK, n_rows) else n_rows


def _over_held_chunks(held, n_rows: int, body, init):
    """``body(start, chunk, carry) -> carry`` over the row chunks ``[start,
    start + chunk)`` of the ``n_rows`` sorted rows that hold a row below
    ``held``: a device scalar, the loop's trip count ``ceil(held / chunk)``,
    or None where every row is held, which is one pass over them all and
    no loop. The last chunk's rows from ``held`` on are nobody's: a body
    may leave anything there that nothing reads."""
    if held is None:
        return body(0, n_rows, init)
    chunk = _row_chunk(n_rows)
    return lax.fori_loop(0, (held + chunk - 1) // chunk,
                         lambda i, carry: body(i * chunk, chunk, carry), init)


def held_chunks_path(n_rows: int, held: Optional[int]) -> str:
    """What the expert layer's row-wise passes (:func:`expert_ffn`'s, the
    combine's backward) run of ``n_rows`` sorted rows of which ``held`` are
    an expert's here, None for all of them (``chip_smoke.py`` prints it
    beside :func:`gmm_path`)."""
    if held is None:
        return f"one pass over all {n_rows} rows (every row held: no loop)"
    chunk = _row_chunk(n_rows)
    return (f"loops over {-(-held // chunk)} of {n_rows // chunk} chunks of "
            f"{chunk} rows ({held} of {n_rows} rows held)")


def rows_held(group_sizes, n_experts: int):
    """How many of the sorted rows are an expert's here, for
    :func:`_over_held_chunks`: the groups' sum, a device scalar, where
    ``group_sizes`` are fewer than the layer's ``n_experts`` (an ``ep``
    shard's, a ``share``'s); None where they are all of them, so that every
    row is held."""
    return None if group_sizes.shape[0] == n_experts else jnp.sum(group_sizes)


def _in_held_rows(fn, held, *arrays):
    """``arrays`` (each ``[N, ..]``) with, in the chunks of rows that hold a
    row below ``held``, the first of them written over by ``fn`` of those
    rows of all of them: ``fn(*chunks)`` returns a chunk for each array it
    replaces, cast to that array's dtype. The arrays are the loop's carry
    and a chunk is read out of the carry before it is written, so an array
    nothing else reads is updated where it lies (:func:`_combine_bwd`'s
    loop, by name)."""
    def body(start, chunk, carry):
        new = fn(*(lax.dynamic_slice_in_dim(a, start, chunk) for a in carry))
        written = tuple(
            lax.dynamic_update_slice_in_dim(a, piece.astype(a.dtype), start, 0)
            for a, piece in zip(carry, new))
        return written + carry[len(written):]
    return _over_held_chunks(held, arrays[0].shape[0], body, arrays)


def _hidden(activation, h1, h3=None):
    """What an expert hands its way down: ``activation(h1)``, times ``h3``
    where the expert is gated (``h1`` its gate's product, ``h3`` its up
    projection's)."""
    return activation(h1) if h3 is None else activation(h1) * h3


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _ffn_held(rows, we1, we3, we2, group_sizes, held, activation, tiles,
              interpret):
    """:func:`expert_ffn` where only the sorted rows below ``held`` are an
    expert's: every row-wise pass between the kernels is a loop over those
    rows' chunks, forward and backward. ``tiles``: the way up's and the
    way down's."""
    return _ffn_held_fwd(rows, we1, we3, we2, group_sizes, held, activation,
                         tiles, interpret)[0]


def _ffn_held_fwd(rows, we1, we3, we2, group_sizes, held, activation, tiles,
                  interpret):
    up, down = tiles
    hs = tuple(_gmm(rows, w, group_sizes, up, interpret)
               for w in (we1, we3) if w is not None)
    # the rows from ``held`` on stay what the buffer held: the way down
    # visits its groups' tiles only, as the kernels that wrote ``hs`` did
    hidden, *_ = _in_held_rows(lambda _, *hs: (_hidden(activation, *hs),),
                               held, lax.empty(hs[0].shape, hs[0].dtype), *hs)
    out = _gmm(hidden, we2, group_sizes, down, interpret)
    # Of the layer's ``[N, F]`` arrays the kernels' outputs alone are kept:
    # the backward pass makes the hidden rows and the activation's
    # derivative again from them, a chunk at a time. Kept behind a barrier:
    # under ``jax.checkpoint`` (a share's ``gathered``) JAX rounds a kept
    # value that the forward pass also reads to its own precision
    # (``reduce_precision``), an identity that XLA:TPU fuses into a fusion
    # that made the value and runs as a pass over all N rows behind a
    # kernel that did (tests/test_tpu_compile.py)
    return out, (rows, we1, we3, we2, group_sizes, held,
                 lax.optimization_barrier(hs))


def _ffn_held_bwd(activation, tiles, interpret, res, g):
    rows, we1, we3, we2, group_sizes, held, hs = res
    up, down = tiles
    d_hidden = _gmm_d_rows(down, interpret, hs[0], we2, group_sizes, g)

    def cotangents(*chunks):
        # of a chunk: the activation's derivative in float32, the rows again
        *hs, d_hidden = chunks
        d_hs = jax.vjp(functools.partial(_hidden, activation),
                       *(h.astype(jnp.float32) for h in hs))[1](
                           d_hidden.astype(jnp.float32))
        with scopes.scope(scopes.RECOMPUTE):     # as the forward made them
            return (*d_hs, _hidden(activation, *hs))
    # each written over what it was made from, which nothing reads after:
    # the cotangents over ``hs``, the hidden rows over their own cotangent
    *d_hs, hidden = _in_held_rows(cotangents, held, *hs, d_hidden)
    d_we2 = _gmm_d_weights(down, interpret, hidden, we2, group_sizes, g)
    d_rows, d_we1, _ = _gmm_bwd(up, interpret, (rows, we1, group_sizes),
                                d_hs[0])
    d_we3 = None
    if we3 is not None:
        d_rows3, d_we3, _ = _gmm_bwd(up, interpret, (rows, we3, group_sizes),
                                     d_hs[1])
        # the rows' two cotangents summed where the first lies (autodiff's
        # sum of them is a pass over all N rows)
        d_rows, _ = _in_held_rows(lambda a, b: (a + b,), held, d_rows,
                                  d_rows3)
    return d_rows, d_we1, d_we3, d_we2, None, None


_ffn_held.defvjp(_ffn_held_fwd, _ffn_held_bwd)


def expert_ffn(rows: jax.Array, we1: jax.Array, we3: Optional[jax.Array],
               we2: jax.Array, group_sizes: jax.Array, held,
               activation: Callable, interpret: bool = False) -> jax.Array:
    """The experts' feed-forward over the sorted ``rows`` ``[N, M]``, an
    ``expert_fn``'s body: ``activation(rows @ we1[g]) @ we2[g]`` for each
    group g, the hidden rows times ``rows @ we3[g]`` where the experts are
    gated (``we3`` not None). The matmuls are :func:`grouped_matmul`'s,
    ``activation`` is row-wise, ``held`` is :func:`rows_held` of the groups.

    None (every row is an expert's here): the plain expression, one fused
    pass between the kernels, differentiated by JAX. A device scalar (the
    rows from ``held`` on are other devices' to compute: seven eighths of
    them in the cell glm-4.7-flash.s8192): the activation, its backward
    pass and the sum of the rows' two cotangents each run over the chunks
    that hold a held row (:func:`_over_held_chunks`), in the same
    arithmetic, the backward pass in float32 within a chunk, and of the
    ``[N, F]`` arrays only the kernels' outputs are kept for it. What the
    result's rows from ``held`` on hold is for no one to read, as
    :func:`grouped_matmul`'s are."""
    if held is None:
        h = grouped_matmul(rows, we1, group_sizes, interpret)
        if we3 is None:
            h = activation(h)
        else:
            h = activation(h) * grouped_matmul(rows, we3, group_sizes,
                                               interpret)
        return grouped_matmul(h, we2, group_sizes, interpret)
    tiles = tuple(_gmm_tiles(rows.shape[0], w, rows.dtype, interpret)
                  for w in (we1, we2))
    return _ffn_held(rows, we1, we3, we2, group_sizes.astype(jnp.int32), held,
                     activation, tiles, interpret)


def _all_but_gathers(prim, *_, **__) -> bool:
    """Checkpoint policy of a held share's experts: every value is kept
    for the backward but what a gather moved (:func:`_dispatch`'s rows)."""
    return prim is not lax.gather_p


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _dispatch(x, order, inverse, held, k):
    """Row r of the result is the token of sorted assignment r: all
    ``T * k`` of them, one gather out of the tokens, which costs less than
    a loop over the held rows and the array it would start from (PERF.md
    section 6, PR 37)."""
    del inverse, held
    return x[order // k]


def _dispatch_fwd(x, order, inverse, held, k):
    return x[order // k], (inverse, held)


def _by_choice(rows, inverse, held, k, pin=False):
    """The sorted rows ``[T * k, M]`` back at their tokens, ``[k, T, M]``:
    slab j holds every token's j-th row, zeros where that row is none of
    the ``held`` (None: every row is), so that what the sorted rows hold
    behind those reaches no result. With k the leading axis the split of
    ``[k * T, M]`` is free and a sum over it is one fused pass; beside ``M``
    a k under the sublane tile (6 of 8 float32 rows) would make ``[T, k,
    M]`` a padded copy (PERF.md section 6, PR 36).

    What the gather costs is set by its source: one of up to
    :data:`GATHER_SOURCE_BYTES` XLA:TPU brings on the chip first and
    gathers from at five times the rate. The held rows are the first of
    the sorted ones, so where they lie within the largest such prefix the
    gather is out of that prefix alone. On a TPU the gather is a pass of
    its own and takes nothing in: the select that makes the zeros comes
    after it (and after the barrier, where the caller asks to ``pin`` the
    gathered slabs), for the pass that reads the slabs to take into its
    own fusion."""
    at = inverse.reshape(-1, k).T
    prefix = GATHER_SOURCE_BYTES // (rows.shape[1] * rows.dtype.itemsize)
    prefix -= prefix % 8        # whole sublane tiles
    if held is None or not 0 < prefix < rows.shape[0]:
        slabs = rows[at]
    else:
        slabs = lax.cond(held <= prefix,
                         lambda: rows[:prefix][jnp.minimum(at, prefix - 1)],
                         lambda: rows[at])
    if pin:
        slabs = lax.optimization_barrier(slabs)
    if held is None:
        return slabs
    return jnp.where((at < held)[:, :, None], slabs, 0)


def _dispatch_bwd(k, res, g):
    inverse, held = res
    total = jnp.sum(_by_choice(g, inverse, held, k).astype(jnp.float32),
                    axis=0)
    return total.astype(g.dtype), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


def _permuted(scalars, to):
    """``scalars[i]`` at place ``to[i]``, which is ``scalars[back]`` for
    ``to``'s inverse permutation ``back``, as a sort by ``to``: a gather of
    single float32 elements is the slow way on a TPU (49 152 of them 0.42
    ms, the sort 0.06: PERF.md section 6, PR 36)."""
    return lax.sort((to, scalars), num_keys=1)[1]


def _products(rows, by):
    """The combine's arithmetic, forward and backward: float32 products of
    rows ``[R, M]`` in the compute dtype with float32 weights ``[R, 1]``
    (summed over a token's k rows: the output; alone: a row's cotangent) or
    with other rows ``[R, M]`` (summed over M: a weight's cotangent)."""
    return rows.astype(jnp.float32) * by.astype(jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _combine(rows, weights, order, inverse, held, axis_name, dtype):
    """``y[t] = sum_j weights[t, j] * rows[inverse[t * k + j]]`` in
    ``dtype`` over the choices whose row is one of the ``held`` (None: over
    every choice): the :func:`_products` of the sorted rows
    :func:`_by_choice` with their weights, summed over the choices in
    float32; with a live ``axis_name`` each home shard then sums, in
    float32, what the expert shards computed for it. Autodiff's cotangent
    of this is a float32 broadcast to every row; the one written here goes
    to sorted order in the compute dtype, through one pass, over the held
    rows' chunks only. The cast to ``dtype`` is taken inside so that the
    cotangent arrives in ``dtype`` and is gathered (and with ``axis_name``
    all-gathered) at that width."""
    del order
    k = weights.shape[1]
    # the barrier keeps the float32 cast behind the split of the major
    # dimension: XLA:TPU else hoists it and leaves it outside the sum's
    # fusion, a float32 [k * T, M] array (tests/test_tpu_compile.py)
    by_choice = _by_choice(rows, inverse, held, k, pin=True)
    y = jnp.sum(_products(by_choice, weights.T[:, :, None]), axis=0)
    if axis_name:
        y = lax.psum_scatter(y, axis_name, scatter_dimension=0, tiled=True)
    return y.astype(dtype)


def _combine_fwd(rows, weights, order, inverse, held, axis_name, dtype):
    return (_combine(rows, weights, order, inverse, held, axis_name, dtype),
            (rows, weights, order, inverse, held))


def _combine_bwd(axis_name, dtype, res, g):
    rows, weights, order, inverse, held = res
    k = weights.shape[1]
    if axis_name:
        g = lax.all_gather(g, axis_name, axis=0, tiled=True)
    # sorted row r is of token order[r] // k, under weight order[r]
    source = order // k
    w_sorted = _permuted(weights.reshape(-1), inverse)
    n_rows, width = rows.shape

    def cotangents(start, chunk, carry):
        # the rows' cotangent is written over the rows, a chunk at a time:
        # nothing reads them after their dots with it
        d_rows, row_dots = carry
        g_sorted = g[lax.dynamic_slice(source, (start,), (chunk,))]
        d = _products(g_sorted, lax.dynamic_slice(
            w_sorted, (start,), (chunk,))[:, None]).astype(rows.dtype)
        dots = jnp.sum(_products(g_sorted, lax.dynamic_slice(
            d_rows, (start, 0), (chunk, width))), axis=1)
        return (lax.dynamic_update_slice(d_rows, d, (start, 0)),
                lax.dynamic_update_slice(
                    row_dots, dots.astype(row_dots.dtype), (start,)))
    d_rows, row_dots = _over_held_chunks(
        held, n_rows, cotangents, (rows, jnp.zeros(n_rows, jnp.float32)))
    if held is not None:
        # a choice whose row is not held gets no gradient from here
        row_dots = jnp.where(jnp.arange(n_rows) < held, row_dots, 0)
    d_weights = _permuted(row_dots, order).reshape(weights.shape)
    return d_rows, d_weights.astype(weights.dtype), None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_layer_spmd(x: jax.Array, router_w: jax.Array,
                   expert_fn: Callable[..., jax.Array], expert_params,
                   axis_name: Optional[str] = "ep", k: int = 2,
                   renormalize: bool = False,
                   stat_axes: Sequence[str] = (),
                   logits: Optional[jax.Array] = None,
                   share: Tuple[int, int] = (0, 1),
                   scores: str = "softmax",
                   bias: Optional[jax.Array] = None, scale: float = 1.0
                   ) -> Tuple[jax.Array, MoEMetrics]:
    """SPMD MoE (inside shard_map). Local shapes:

    x: [G, M] local tokens; router_w: [M, E] (replicated); expert_params:
    pytree with leading dim E_local = E/ep (this shard's experts).
    ``expert_fn(expert_params, rows [N, M], group_sizes [E_local]) ->
    [N, M]``: rows sorted by local expert, group g the next
    ``group_sizes[g]`` of them; the rows beyond the groups (with ``ep`` > 1,
    the other shards' to compute) are not for this device, and what comes
    back in their place is not read (:func:`expert_ffn` is such a
    function's body, told how many rows are by :func:`rows_held` of the
    groups it is handed). ``stat_axes``: the mesh axes the
    tokens are sharded over, so that the metrics are those of the global
    batch and the same on every layout. ``logits``: the router's float32
    logits ``[G, E]`` where the caller computed them from something other
    than ``x`` (a router that reads the block's input); else ``x @
    router_w`` here. ``share=(index, of)`` with no live ``axis_name``: this
    device holds the experts ``[index * E / of, (index + 1) * E / of)`` as
    ``expert_params``' leading dimension, and the result is their part of
    the layer's output. ``scores``, ``bias``, ``scale``: :func:`route`'s."""
    n = axis_size(axis_name) if axis_name else 1
    G, M = x.shape
    E = router_w.shape[1]
    index, of = share
    if n > 1 and (index, of) != (0, 1):
        raise ValueError(
            f"share={share} with a live {axis_name!r} axis: a device "
            "holds a share of the experts either by its place on the axis "
            "or by being told, not both")
    if E % (n * of) != 0 or not 0 <= index < of:
        raise ValueError(f"ep axis size ({n}) must divide n_experts ({E})"
                         if of == 1 else
                         f"share={share} does not divide n_experts ({E})")
    e_local = E // (n * of)
    held = {a.shape[0] for a in jax.tree_util.tree_leaves(expert_params)}
    if held != {e_local}:
        raise ValueError(f"expert_params hold {sorted(held)} experts, the "
                         f"layout {e_local} of {E}")

    with scopes.scope(scopes.MOE_ROUTER):
        if logits is None:
            logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        # (the plain router by its three arguments, as every caller of
        # ``route`` and the tests that swap it know it)
        plain = scores == "softmax" and bias is None and scale == 1.0
        other = {} if plain else {"scores": scores, "bias": bias,
                                  "scale": scale}
        probs, weights, experts = route(logits, k, renormalize, **other)

    with scopes.scope(scopes.MOE_DISPATCH):
        if n > 1:
            # the ep group's tokens, and which experts each chose
            x_all = lax.all_gather(x, axis_name, axis=0, tiled=True)
            experts_all = lax.all_gather(experts, axis_name, axis=0,
                                         tiled=True)
            first = lax.axis_index(axis_name) * e_local
        else:
            x_all, experts_all, first = x, experts, index * e_local
        # this shard's experts sort to the front, the others behind them
        local = (experts_all.reshape(-1) - first) % E
        order = jnp.argsort(local, stable=True)
        inverse = jnp.argsort(order)
        group_sizes = jnp.sum(
            local[:, None] == jnp.arange(e_local, dtype=local.dtype),
            axis=0, dtype=jnp.int32)
        # the sorted rows that are an expert's here are the first ``held``;
        # None where that is every row, which needs no device scalar
        held = rows_held(group_sizes, E)

    def gathered(x_all, expert_params):
        with scopes.scope(scopes.MOE_DISPATCH):
            rows = _dispatch(x_all, order, inverse, held, k)
        with scopes.scope(scopes.MOE_EXPERTS):
            return expert_fn(expert_params, rows, group_sizes)
    if of > 1:
        # of a share's G * k gathered rows all but 1 / of lie behind the
        # groups: the backward gathers them again (a gather, no FLOPs)
        # rather than keep them (at 8192 tokens, top-6 and 2560 columns
        # 252 MB a layer); everything the experts compute is kept
        gathered = jax.checkpoint(gathered, policy=_all_but_gathers)
    rows = gathered(x_all, expert_params)

    with scopes.scope(scopes.MOE_COMBINE):
        if n > 1:
            weights_all = lax.all_gather(weights, axis_name, axis=0,
                                         tiled=True)
        else:
            weights_all = weights
        # with ep > 1 each home shard sums what the expert shards computed
        y = _combine(rows, weights_all, order, inverse, held,
                     axis_name if n > 1 else None, x.dtype)

    with scopes.scope(scopes.MOE_ROUTER):
        def total(v):
            return lax.psum(v, tuple(stat_axes)) if stat_axes else v
        tokens = total(jnp.float32(G))
        counts = total(jnp.sum(
            experts.reshape(-1)[:, None] == jnp.arange(E), axis=0,
            dtype=jnp.float32))
        mean_probs = total(jnp.sum(probs, axis=0)) / tokens
        z = jax.nn.logsumexp(logits, axis=-1)
        computed = jnp.sum(group_sizes).astype(jnp.float32)
        if n > 1:
            # over ep each assignment of the group is computed once
            computed = lax.psum(computed, axis_name) / n
        if of == 1:
            held_rows = G * k
        else:   # counted from the choices, not from the sort's groups
            held_rows = jnp.sum(
                (experts >= first) & (experts < first + e_local),
                dtype=jnp.float32)
        metrics = MoEMetrics(
            load_balance_loss=E * jnp.sum(counts / tokens * mean_probs),
            router_z_loss=total(jnp.sum(jnp.square(z))) / tokens,
            max_expert_load=jnp.max(counts) * E / (tokens * k),
            dropped=total(held_rows - computed),
            held_rows=total(jnp.float32(held_rows)), experts=experts)
    return y, metrics


def moe_layer(x: jax.Array, router_w: jax.Array, expert_fn: Callable,
              expert_params, mesh: Mesh, axis_name: str = "ep",
              k: int = 2, renormalize: bool = False,
              token_axes: Tuple[Optional[str], ...] = ("dp",)
              ) -> Tuple[jax.Array, MoEMetrics]:
    """Array-level MoE: x ``[T, M]`` tokens sharded over ``token_axes``;
    expert_params leading dim E sharded over ``axis_name``; ``expert_fn``
    as :func:`moe_layer_spmd` takes it."""
    from horovod_tpu.parallel.mesh import mesh_axis_size
    n = mesh_axis_size(mesh, axis_name)
    tok_ax = tuple(a for a in token_axes if mesh_axis_size(mesh, a) > 1)
    tok_spec = P(tok_ax or None)
    ep_ax = axis_name if n > 1 else None

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(tok_spec, P(), P(ep_ax)),
        out_specs=(tok_spec, MoEMetrics(P(), P(), P(), P(), P(), tok_spec)),
        check_vma=False)
    def run(xl, rw, ep_params):
        return moe_layer_spmd(xl, rw, expert_fn, ep_params, ep_ax, k,
                              renormalize, stat_axes=tok_ax)

    return run(x, router_w, expert_params)
