"""Fused softmax cross-entropy as a Pallas TPU kernel: the LM head's loss
AND its gradient in one pass over the logits.

The loss over a large vocabulary is memory-bound: an ``[N, V]`` array is
the largest of a causal LM's step, and every sweep over it costs its bytes
at HBM's rate. This kernel touches it once. A grid step holds ``bn`` WHOLE
rows in VMEM (a bf16 row of 50304 logits is 100 KB) and sweeps them three
times, all on chip:

  1. the row maximum,
  2. ``sum exp(s - max)`` and the label's logit (an iota compare), which
     give ``lse`` and ``loss = lse - z_label``,
  3. ``d = softmax - onehot = exp(s - lse) - onehot``, written over the
     logits themselves (``input_output_aliases``).

  grid = (N / bn,);  ``(bn, chunk)`` from the shape, :func:`xent_blocks`.

A sweep walks the row in lane-aligned pieces of eight vregs with
elementwise accumulators (one cross-lane reduction a sweep), ``chunk``
columns a loop iteration. The logits are read as the matmul wrote them:
the block's last dimension is the whole vocabulary, so any ``V`` is legal
(50257, 30522) and nothing is padded or copied; a last piece narrower than
the others is a static slice of its own.

``d`` (in the logits' dtype, unscaled) is the only ``[N, V]`` residual:
the logits are not kept, and the cotangent ``g`` of the per-row loss is
applied where it is cheap. :func:`head_softmax_xent` takes the head's
operands ``x [N, M]`` and ``w [M, V]`` and is differentiable in both
through one ``custom_vjp``: ``dx = (d @ w.T) * g`` and ``dw = (x * g).T @
d``, ``g`` meeting ``[N, M]`` arrays only; it is what the flagship
transformer calls. :func:`fused_softmax_xent` takes logits and returns
``d * g``. Not differentiated, either runs sweeps 1 and 2 only and writes
``O(N)`` bytes.

Same contract as :mod:`ops.pallas_attention` (reference analog: the
"write the hot op yourself" role of ``cuda_kernels.cu``): the pure-XLA
:func:`_xla_xent` runs off the TPU and where the rows do not tile,
numerically the same; :func:`xent_path` says which, from the shape alone.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.profiling.compile_watch import kernel_call

NEG_INF = -1e30

LANES = 128
#: rows of a grid step, most first
ROWS = (64, 32, 16, 8)
#: bytes of a row block worth asking for: the v5e sweep (PERF.md, PR 29)
#: found 16 and 32 rows of 100 KB alike, at 80 % of HBM's rate
BLOCK_BYTES = 2 * 1024 * 1024
#: columns a loop iteration and float32 elements a piece of it (eight
#: vregs a value, so what a sweep holds of a piece stays in registers). The
#: same sweep: an iteration of one piece is all latency (4.7 ms at 256 x 32
#: where 4096 x 32 takes 1.26); from 4096 columns on nothing moves
CHUNK = 4096
PIECE_ELEMS = 8192
#: the v5e's default scoped-VMEM limit, and the most a call asks for
#: instead where a row block of a very large vocabulary needs it (of 128)
VMEM_DEFAULT = 16 * 1024 * 1024
VMEM_MOST = 64 * 1024 * 1024


def xent_vmem_bytes(bn: int, v: int, itemsize: int) -> int:
    """Working set of one grid step: the row block in and the gradient
    block out, each double-buffered by the pipeline (rows padded to whole
    lanes), the ``[bn, 1]`` labels and loss (whole 128-lane tiles), and
    2 MiB of room for the compiler's own scratch."""
    row = -(-v // LANES) * LANES * itemsize
    return 4 * bn * row + 4 * bn * LANES * 4 + 2 * 1024 * 1024


def xent_blocks(n: int, v: int, dtype) -> Tuple[int, int]:
    """``(bn, chunk)`` of the kernel for ``[n, v]`` logits: ``bn`` whole
    rows a grid step, the most of ``ROWS`` that divide ``n`` in whole
    packed tiles of ``dtype`` (16 bf16 rows, 8 float32 rows) within
    ``BLOCK_BYTES``, or the fewest if a row is longer than that; swept
    ``chunk`` columns a loop iteration. A grid step costs ~0.35 µs
    (PERF.md, PR 25), so whole rows (hundreds of steps) and not 128 x 512
    tiles (thousands)."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 8 * max(1, 4 // itemsize)
    legal = [bn for bn in ROWS if n % bn == 0 and bn % sublanes == 0]
    fits = [bn for bn in legal if bn * v * itemsize <= BLOCK_BYTES]
    bn = fits[0] if fits else legal[-1] if legal else None
    if bn is None or xent_vmem_bytes(bn, v, itemsize) > VMEM_MOST:
        raise ValueError(
            f"no row block of {ROWS} divides n={n} in whole "
            f"{jnp.dtype(dtype).name} tiles and fits VMEM at v={v}")
    return bn, CHUNK


def xent_path(n: int, v: int, dtype,
              interpret: bool = False) -> Tuple[str, str]:
    """Which implementation the loss takes for ``[n, v]`` logits, from the
    shape alone: ``("kernel", "<bn> rows x <chunk>-column chunks, <steps>
    steps")`` or ``("xla", reason)``. (With ``tp`` live the flagship's
    vocabulary is sharded and it never calls this module.)"""
    if jax.default_backend() != "tpu" and not interpret:
        return "xla", "off the TPU"
    try:
        bn, chunk = xent_blocks(n, v, dtype)
    except ValueError as e:
        return "xla", str(e)
    return "kernel", f"{bn} rows x {chunk}-column chunks, {n // bn} steps"


def _xent_kernel(labels_ref, logits_ref, loss_ref, *grad_ref, chunk: int,
                 piece: int):
    """One block of whole rows. ``grad_ref`` is the gradient's block (the
    logits' own buffer) or absent. Operands are >= 2-D (``[bn, 1]``
    trailing unit dims, ``_flash_kernel``'s convention)."""
    bn, v = logits_ref.shape
    labels = labels_ref[...]                                # [bn, 1]

    def load(start, width):
        """Columns ``[start, start + width)`` in float32, and where they
        start."""
        return logits_ref[:, pl.ds(start, width)].astype(jnp.float32), start

    def at_label(s, start):
        """Where the label's column is among those of ``s``. It is exactly
        one column of a row; a label out of range matches none, so its
        logit counts 0 and loss = lse."""
        return jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) == labels - start

    def sweep(body, init):
        """``body(*load(start, width), carry) -> carry`` over the columns,
        a piece at a time: ``chunk`` columns a loop iteration (Mosaic
        unrolls a loop wholly or not at all, and an iteration of one piece
        is all latency), then what is left of the row. Returns the carry of
        the whole pieces (elementwise, ``init(piece)`` wide) and that of
        the narrower last one, those there are."""
        def pieces(start, n, acc):
            for j in range(n):
                acc = body(*load(start + j * piece, piece), acc)
            return acc

        n_loop, left = divmod(v, chunk)
        n_left, tail = divmod(left, piece)
        parts = []
        if n_loop or n_left:
            acc = init(piece)
            if n_loop:
                acc = jax.lax.fori_loop(
                    0, n_loop, lambda c, acc: pieces(
                        pl.multiple_of(c * chunk, chunk), chunk // piece, acc),
                    acc)
            parts.append(pieces(n_loop * chunk, n_left, acc))
        if tail:
            parts.append(body(*load(v - tail, tail), init(tail)))
        return parts

    def over_lanes(parts, reduce, combine):
        return functools.reduce(combine, [reduce(p, axis=1, keepdims=True)
                                          for p in parts])

    m = over_lanes(
        sweep(lambda s, start, acc: jnp.maximum(acc, s),
              lambda w: jnp.full((bn, w), NEG_INF, jnp.float32)),
        jnp.max, jnp.maximum)

    parts = sweep(
        lambda s, start, acc: (
            acc[0] + jnp.exp(s - m),
            acc[1] + jnp.where(at_label(s, start), s, 0.0)),
        lambda w: (jnp.zeros((bn, w), jnp.float32),) * 2)
    l, z = (over_lanes([p[i] for p in parts], jnp.sum, jnp.add)
            for i in (0, 1))
    lse = m + jnp.log(l)
    loss_ref[...] = lse - z
    if not grad_ref:
        return
    d_ref, = grad_ref

    def write_grad(s, start, acc):
        p = jnp.exp(s - lse)
        d_ref[:, pl.ds(start, s.shape[1])] = jnp.where(
            at_label(s, start), p - 1.0, p).astype(d_ref.dtype)
        return acc

    sweep(write_grad, lambda w: 0)


def _xent_call(logits, labels, blocks: Tuple[int, int], with_grad: bool,
               interpret: bool):
    """The kernel on ``[n, v]`` logits: the per-row loss ``[n]`` and, if
    ``with_grad``, ``softmax - onehot`` in the logits' buffer."""
    n, v = logits.shape
    bn, chunk = blocks
    piece = min(chunk, PIECE_ELEMS // bn)
    if n % bn or chunk % piece:
        raise ValueError(f"rows {n} in blocks of {bn}, chunks of {chunk} "
                         f"columns in pieces of {piece}: neither may "
                         f"leave a remainder")
    need = xent_vmem_bytes(bn, v, logits.dtype.itemsize)
    rows = pl.BlockSpec((bn, 1), lambda i: (i, 0))
    whole = pl.BlockSpec((bn, v), lambda i: (i, 0))
    out = kernel_call(pl.pallas_call,
        functools.partial(_xent_kernel, chunk=chunk, piece=piece),
        grid=(n // bn,),
        in_specs=[rows, whole],
        out_specs=[rows] + [whole] * with_grad,
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.float32)]
        + [jax.ShapeDtypeStruct((n, v), logits.dtype)] * with_grad,
        input_output_aliases={1: 1} if with_grad else {},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=need if need > VMEM_DEFAULT else None),
        interpret=interpret,
        name="hvd_fused_xent",
    )(labels[:, None], logits)
    return (out[0][:, 0], out[1]) if with_grad else out[0][:, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _fused_xent(logits, labels, blocks, interpret):
    return _xent_call(logits, labels, blocks, False, interpret)


def _fused_xent_fwd(logits, labels, blocks, interpret):
    return _xent_call(logits, labels, blocks, True, interpret)


def _fused_xent_bwd(blocks, interpret, d, g):
    return (d * g[:, None]).astype(d.dtype), None


_fused_xent.defvjp(_fused_xent_fwd, _fused_xent_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _head_xent(x, w, labels, blocks, interpret):
    return _xent_call(x @ w, labels, blocks, False, interpret)


def _head_xent_fwd(x, w, labels, blocks, interpret):
    loss, d = _xent_call(x @ w, labels, blocks, True, interpret)
    return loss, (x, w, d)


def _head_xent_bwd(blocks, interpret, res, g):
    """``g`` meets ``[N, M]`` arrays only; both matmuls accumulate in
    float32 and round once to their operand's dtype, as autodiff's do."""
    x, w, d = res
    g = g[:, None]
    dx = jnp.dot(d, w.T, preferred_element_type=jnp.float32) * g
    xg = (x.astype(jnp.float32) * g).astype(x.dtype)
    dw = jnp.dot(xg.T, d, preferred_element_type=jnp.float32)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


_head_xent.defvjp(_head_xent_fwd, _head_xent_bwd)


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


def _blocks_or_none(n: int, v: int, dtype, block_n: Optional[int],
                    chunk: Optional[int], interpret: bool):
    """The kernel's ``(bn, chunk)`` where :func:`xent_path` says kernel
    (``block_n`` / ``chunk`` given: that tile, for a sweep on the chip),
    else None."""
    if xent_path(n, v, dtype, interpret)[0] != "kernel":
        return None
    bn, ch = xent_blocks(n, v, dtype)
    return block_n or bn, chunk or ch


def fused_softmax_xent(logits: jax.Array, labels: jax.Array,
                       block_n: Optional[int] = None,
                       chunk: Optional[int] = None,
                       interpret: bool = False) -> jax.Array:
    """Per-row ``-log softmax(logits)[label]`` with the one-pass TPU
    kernel; ``[..., V]`` logits of any vocabulary size and integer
    ``[...]`` labels of any leading shape. Rows that do not tile, or
    non-TPU backends without ``interpret=True``, fall back to the
    numerically identical XLA path (:func:`xent_path`). Differentiable in
    the logits; where they come from a matmul, :func:`head_softmax_xent`
    is the form that never sweeps ``[N, V]`` a second time."""
    v = logits.shape[-1]
    lead = logits.shape[:-1]
    flat = logits.reshape(-1, v)
    flat_labels = labels.reshape(-1).astype(jnp.int32)
    blocks = _blocks_or_none(flat.shape[0], v, flat.dtype, block_n, chunk,
                             interpret)
    if blocks is None:
        return _xla_xent(flat, flat_labels).reshape(lead)
    return _fused_xent(flat, flat_labels, blocks, interpret).reshape(lead)


def head_softmax_xent(x: jax.Array, w: jax.Array, labels: jax.Array,
                      block_n: Optional[int] = None,
                      chunk: Optional[int] = None,
                      interpret: bool = False) -> jax.Array:
    """:func:`fused_softmax_xent` of the logits ``x @ w`` for activations
    ``x [..., M]``, a head ``w [M, V]`` and labels ``[...]``, differentiable
    in ``x`` and ``w``: the kernel leaves ``softmax - onehot`` in the
    logits' buffer, the two backward matmuls read it, and the loss's
    cotangent scales their ``[N, M]`` side. Falls back like
    :func:`fused_softmax_xent`, to autodiff through ``x @ w``."""
    lead = x.shape[:-1]
    flat = x.reshape(-1, x.shape[-1])
    flat_labels = labels.reshape(-1).astype(jnp.int32)
    blocks = _blocks_or_none(flat.shape[0], w.shape[1],
                             jnp.result_type(x, w), block_n, chunk, interpret)
    if blocks is None:
        return _xla_xent(flat @ w, flat_labels).reshape(lead)
    return _head_xent(flat, w, flat_labels, blocks, interpret).reshape(lead)
