"""Pipeline parallelism (GPipe / 1F1B / interleaved-1F1B) over ``pp``.

Absent from the reference (SURVEY.md §2.6). TPU-native design: all stages
run the same SPMD program under ``shard_map``; stage-to-stage transfer is a
``lax.ppermute`` ring shift of the activation; microbatches flow for
``M + S - 1`` ticks (fill + steady state + drain). Stage parameters are the
same pytree with a leading stage dim sharded over ``pp`` — so the schedule
is a compiled ``lax.scan``, with no host round-trips between ticks (the
whole pipeline is one XLA program; ICI transfers overlap with stage compute).

Schedule cost model: because the
program is SPMD, every device executes every tick's full body with
invalid units masked — masked compute costs the same time as real
compute. A combined forward+backward tick (the 1F1B family) therefore
pays the fill AND drain bubble on the combined tick cost, while
GPipe-by-autodiff pays each bubble once per pass; 1F1B's win on real
workloads is bounded activation memory (a ``min(2S-1, M)`` ring vs a
residual stack that grows with ``M``), and interleaved 1F1B's win is a
``~1/v`` smaller bubble at the same ``M``. The analytic tick counts are
exposed via :func:`schedule_ticks` / the ``ParallelPlan.bubble_fraction``
seam so benches and the autotuner can reason about them.

Gradient-correctness note (the ``replicate_from_stage`` helper): code
that differentiates a REPLICATED loss inside ``shard_map`` (with
``check_vma=False``) seeds one cotangent per shard; a plain masked
``lax.psum`` replication then delivers the SUM of those ``S`` identical
seeds to the source stage — every parameter reached through the psum
gets gradients scaled by ``S``. ``replicate_from_stage`` is the
differentiation-safe replication for that in-graph pattern: forward is
the masked psum, backward delivers the per-shard cotangent to the
source stage exactly once.
"""

from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from horovod_tpu._compat import axis_size, shard_map


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def replicate_from_stage(val, axis_name: str, src_stage: int):
    """Replicate ``val`` from shard ``src_stage`` of ``axis_name`` to all
    shards, safely differentiable from INSIDE ``shard_map``.

    Forward is the masked-psum idiom (zero every shard but the source,
    sum). Backward returns the incoming cotangent on the source shard
    and zeros elsewhere — NOT ``psum`` of the per-shard seeds, which is
    what a plain ``lax.psum`` transposes to under ``check_vma=False``
    and which over-counts a replicated consumer by the axis size (see
    module docstring)."""
    idx = lax.axis_index(axis_name)
    return lax.psum(jnp.where(idx == src_stage, val, jnp.zeros_like(val)),
                    axis_name)


def _replicate_fwd(val, axis_name, src_stage):
    return replicate_from_stage(val, axis_name, src_stage), None


def _replicate_bwd(axis_name, src_stage, _res, g):
    idx = lax.axis_index(axis_name)
    return (jnp.where(idx == src_stage, g, jnp.zeros_like(g)),)


replicate_from_stage.defvjp(_replicate_fwd, _replicate_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_cotangent(val, axis_name: str):
    """Identity forward; backward ``psum``\\ s the cotangent over
    ``axis_name``. Feed pipeline INPUTS through this when the producing
    computation is replicated over the pipeline axis (e.g. a replicated
    embedding): the input cotangent materializes only on the stage that
    consumes it (stage 0), and this replicates it so every shard's
    producer parameters see the same, correct gradient."""
    return val


def _psum_ct_fwd(val, axis_name):
    return val, None


def _psum_ct_bwd(axis_name, _res, g):
    return (lax.psum(g, axis_name),)


psum_cotangent.defvjp(_psum_ct_fwd, _psum_ct_bwd)


def pipeline_spmd(stage_fn: Callable, stage_params, x_microbatches: jax.Array,
                  axis_name: str = "pp") -> jax.Array:
    """SPMD body (inside shard_map over ``axis_name``).

    stage_params: this stage's params — pytree, leaves ``[1, ...]`` (leading
    stage dim sharded to size 1 locally).
    x_microbatches: ``[M, mb, ...]`` all microbatches (stage 0 consumes them;
    other stages ignore).
    Returns ``[M, mb, ...]`` outputs (valid on every shard after the final
    cross-stage reduction).
    """
    S = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    my_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    mb_shape = x_microbatches.shape[1:]
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]

    def tick(carry, t):
        act, ys = carry
        prev = lax.ppermute(act, axis_name, fwd_perm)
        feed = x_microbatches[jnp.clip(t, 0, M - 1)]
        cur = jnp.where(stage == 0, feed, prev)
        out = stage_fn(my_params, cur)
        emit = t - (S - 1)
        is_emit = (stage == S - 1) & (emit >= 0) & (emit < M)
        idx = jnp.clip(emit, 0, M - 1)
        ys = ys.at[idx].set(jnp.where(is_emit, out, ys[idx]))
        return (out, ys), None

    act0 = jnp.zeros(mb_shape, x_microbatches.dtype)
    ys0 = jnp.zeros((M,) + mb_shape, x_microbatches.dtype)
    (act, ys), _ = lax.scan(tick, (act0, ys0), jnp.arange(M + S - 1))
    # Only the last stage holds real outputs; replicate to every shard.
    # replicate_from_stage (not a bare masked psum) keeps this schedule
    # correct under GPipe-by-autodiff — differentiating a replicated
    # loss inside shard_map otherwise scales every stage gradient by S
    # (see module docstring).
    return replicate_from_stage(ys, axis_name, S - 1)


def _pipeline_prep(stage_params, x: jax.Array, mesh: Mesh,
                   n_microbatches: int, axis_name: str,
                   batch_axis: Optional[str]):
    """Shared validation + microbatching for the array-level schedules:
    returns (S, xm, b_ax)."""
    from horovod_tpu.parallel.mesh import mesh_axis_size
    S = mesh_axis_size(mesh, axis_name)
    leading = {leaf.shape[0] for leaf in
               jax.tree_util.tree_leaves(stage_params)}
    if leading != {S}:
        raise ValueError(
            f"stage_params leading dims {sorted(leading)} must all equal the "
            f"'{axis_name}' mesh axis size ({S}); restack the stages for "
            f"this mesh (stage_stacked) instead of silently dropping some.")
    T = x.shape[0]
    if T % n_microbatches != 0:
        raise ValueError(f"batch {T} not divisible by microbatches "
                         f"{n_microbatches}")
    xm = x.reshape((n_microbatches, T // n_microbatches) + x.shape[1:])
    b_ax = batch_axis if (batch_axis and mesh_axis_size(mesh, batch_axis) > 1) \
        else None
    return S, xm, b_ax


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, mesh: Mesh,
                   n_microbatches: int, axis_name: str = "pp",
                   batch_axis: Optional[str] = "dp") -> jax.Array:
    """Array-level GPipe.

    stage_fn(params_for_one_stage, microbatch) -> microbatch (same shape).
    stage_params: pytree with leading dim = pp size, sharded over ``pp``.
    x: ``[T, ...]`` global batch; split into ``n_microbatches``.
    """
    S, xm, b_ax = _pipeline_prep(stage_params, x, mesh, n_microbatches,
                                 axis_name, batch_axis)
    if S == 1:
        one = jax.tree_util.tree_map(lambda p: p[0], stage_params)
        return stage_fn(one, x)
    T = x.shape[0]
    x_spec = P(None, b_ax)
    out_spec = P(None, b_ax)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis_name), x_spec),
                       out_specs=out_spec, check_vma=False)
    def run(params_l, xm_l):
        return pipeline_spmd(stage_fn, params_l, xm_l, axis_name)

    ym = run(stage_params, xm)
    return ym.reshape((T,) + ym.shape[2:])


def stage_stacked(params_per_stage: list):
    """Stack a list of per-stage parameter pytrees into the leading-dim
    layout ``pipeline_apply`` expects (shard the result over ``pp``)."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves), *params_per_stage)


# ---------------------------------------------------------------------------
# 1F1B-family schedule: eager backward with bounded activation memory
# ---------------------------------------------------------------------------

def pipeline_1f1b_spmd(stage_fn: Callable, loss_fn: Callable, stage_params,
                       x_microbatches: jax.Array, targets: jax.Array,
                       axis_name: str = "pp"):
    """Forward AND backward in one compiled schedule with backward starting
    as soon as each microbatch clears the last stage (1F1B family; GPipe
    runs all M forwards first, so its live-activation set grows with M).

    Memory: each stage stores only the INPUTS of its in-flight
    microbatches — a ring of ``min(2S-1, M)`` entries — and rematerializes
    the stage forward inside the backward tick (``jax.vjp``), the standard
    TPU recompute trade. GPipe-by-autodiff (differentiating
    :func:`pipeline_spmd`) keeps all ``M`` per-tick residuals live.

    Schedule (full tick t = one forward phase + one backward phase):
    stage s runs forward of microbatch ``t - s`` and backward of
    microbatch ``t - (2S - 2 - s)``; the last stage seeds the loss
    gradient in the same tick its forward completes. Total ticks:
    ``M + 2S - 2``.

    Returns ``(mean_loss, grads)`` where grads has this stage's parameter
    gradients (summed over microbatches, caller scales).
    """
    S = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    my_params = jax.tree_util.tree_map(lambda p: p[0], stage_params)
    D = min(2 * S - 1, M)
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]
    mb_shape = x_microbatches.shape[1:]

    def tick(carry, t):
        fwd_act, bwd_grad, in_buf, grad_acc, loss_acc = carry
        # ---- forward phase -------------------------------------------------
        prev = lax.ppermute(fwd_act, axis_name, fwd_perm)
        m_f = t - stage
        f_valid = (m_f >= 0) & (m_f < M)
        mf_c = jnp.clip(m_f, 0, M - 1)
        x_in = jnp.where(stage == 0, x_microbatches[mf_c], prev)
        out = stage_fn(my_params, x_in)
        slot_f = mf_c % D
        in_buf = in_buf.at[slot_f].set(
            jnp.where(f_valid, x_in, in_buf[slot_f]))
        # last stage: loss value + gradient seed for the SAME-tick backward
        tgt = targets[mf_c]
        loss_m, g_seed = jax.value_and_grad(
            lambda y: loss_fn(y, tgt))(out)
        loss_acc = loss_acc + jnp.where(
            (stage == S - 1) & f_valid, loss_m, 0.0)

        # ---- backward phase ------------------------------------------------
        g_in = lax.ppermute(bwd_grad, axis_name, bwd_perm)  # from s+1
        m_b = t - (2 * S - 2 - stage)
        b_valid = (m_b >= 0) & (m_b < M)
        mb_c = jnp.clip(m_b, 0, M - 1)
        x_b = in_buf[mb_c % D]
        g_out = jnp.where(stage == S - 1, g_seed, g_in)
        _, pullback = jax.vjp(stage_fn, my_params, x_b)  # remat forward
        g_params, g_x = pullback(g_out)
        grad_acc = jax.tree_util.tree_map(
            lambda a, g: a + jnp.where(b_valid, g, jnp.zeros_like(g)),
            grad_acc, g_params)
        bwd_next = jnp.where(b_valid, g_x, jnp.zeros_like(g_x))
        return (out, bwd_next, in_buf, grad_acc, loss_acc), None

    carry0 = (jnp.zeros(mb_shape, x_microbatches.dtype),
              jnp.zeros(mb_shape, x_microbatches.dtype),
              jnp.zeros((D,) + mb_shape, x_microbatches.dtype),
              jax.tree_util.tree_map(jnp.zeros_like, my_params),
              jnp.asarray(0.0, jnp.float32))
    (_, _, _, grads, loss_sum), _ = lax.scan(
        tick, carry0, jnp.arange(M + 2 * S - 2))
    # every shard returns the mean loss (only the last stage accumulated)
    mean_loss = lax.psum(loss_sum, axis_name) / M
    return mean_loss, grads


def _dp_reduce(grads, b_ax: Optional[str], dp_reducer: Optional[Callable]):
    """Reduce stage gradients over the data axis.

    ``dp_reducer`` is the composed-step seam (ISSUE 11 satellite): when
    given, it is called with the gradient pytree INSIDE ``shard_map``
    (the ``b_ax`` axis is live) and owns the mean-reduction — e.g.
    ``bucketed_grad_sync`` with buckets / hierarchical collectives /
    codecs / telemetry. The default is the exact-parity fallback: one
    dense ``lax.pmean`` per leaf."""
    if b_ax is None:
        return grads
    if dp_reducer is not None:
        return dp_reducer(grads)
    return jax.tree_util.tree_map(lambda g: lax.pmean(g, b_ax), grads)


def pipeline_1f1b_apply(stage_fn: Callable, loss_fn: Callable, stage_params,
                        x: jax.Array, targets: jax.Array, mesh: Mesh,
                        n_microbatches: int, axis_name: str = "pp",
                        batch_axis: Optional[str] = "dp",
                        dp_reducer: Optional[Callable] = None):
    """Array-level 1F1B: returns ``(mean_loss, grads)`` with grads in the
    same stage-stacked layout as ``stage_params`` (per-microbatch-mean
    scale, matching ``jax.grad`` of the mean loss).

    ``dp_reducer``: optional mean-reducer for the gradient pytree over
    the ``batch_axis`` (called inside ``shard_map``); defaults to the
    exact dense ``lax.pmean``. Pass the composed step's bucketed sync so
    dp gradient traffic stops bypassing bucketing/compression — see
    :func:`horovod_tpu.train.pipeline.make_pipeline_train_step`."""
    S, xm, b_ax = _pipeline_prep(stage_params, x, mesh, n_microbatches,
                                 axis_name, batch_axis)
    T = x.shape[0]
    tm = targets.reshape((n_microbatches, T // n_microbatches)
                         + targets.shape[1:])
    if S == 1:
        one = jax.tree_util.tree_map(lambda p: p[0], stage_params)

        def total(p):
            losses = jax.vmap(lambda xb, tb: loss_fn(stage_fn(p, xb), tb))(
                xm, tm)
            return losses.mean()
        loss, g = jax.value_and_grad(total)(one)
        return loss, jax.tree_util.tree_map(lambda v: v[None], g)
    data_spec = P(None, b_ax)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis_name), data_spec, data_spec),
                       out_specs=(P(), P(axis_name)), check_vma=False)
    def run(params_l, xm_l, tm_l):
        loss, grads = pipeline_1f1b_spmd(stage_fn, loss_fn, params_l,
                                         xm_l, tm_l, axis_name)
        # per-microbatch mean -> same scale as jax.grad of the mean loss;
        # with a sharded batch axis the per-shard loss_fn already averaged
        # over local rows, so also average gradients across it
        grads = jax.tree_util.tree_map(lambda g: g[None] / n_microbatches,
                                       grads)
        if b_ax is not None:
            loss = lax.pmean(loss, b_ax)
            grads = _dp_reduce(grads, b_ax, dp_reducer)
        return loss, grads

    return run(stage_params, xm, tm)


# ---------------------------------------------------------------------------
# Interleaved 1F1B: v virtual stage chunks per device (arxiv 2412.14374)
# ---------------------------------------------------------------------------

def _min_ring(intervals) -> int:
    """Smallest ring size R such that no two live intervals [a, c] whose
    keys collide mod R overlap (slot m%R must not be overwritten while
    its previous occupant is still unconsumed)."""
    if not intervals:
        return 1
    keys = sorted(intervals)
    for R in range(1, max(m for m, _, _ in keys) + 2):
        ok = True
        for i, (m1, a1, c1) in enumerate(keys):
            for (m2, a2, c2) in keys[i + 1:]:
                if m1 % R == m2 % R and a1 <= c2 and a2 <= c1:
                    ok = False
                    break
            if not ok:
                break
        if ok:
            return R
    return max(m for m, _, _ in keys) + 1


@functools.lru_cache(maxsize=256)
def interleaved_tables(S: int, v: int, M: int):
    """Static schedule tables for the interleaved 1F1B schedule.

    Stage ``q = j*S + d`` is virtual chunk ``j`` on device ``d`` (the
    standard interleaved placement: every stage-to-stage transfer is the
    same +1 ring shift, with the chunk index incrementing on the wrap).
    A greedy list scheduler assigns each device at most one forward and
    one backward unit per combined tick, forwards deepest-stage-first
    (drive the critical chain), backwards oldest-microbatch-first
    (drain the rings); the resulting tick count beats the plain-1F1B
    ``v*(M + 2S - 2)`` sub-tick equivalent for ``S > 2`` and equals it
    at ``S = 2``.

    Returns a dict of numpy tables (execution + receive-side, shape
    ``[T, S]``), ring sizes, the tick count ``T`` and the analytic
    bubble fraction ``1 - v*M/T``."""
    V = v * S
    ef, eb = {}, {}
    fw_rows, bw_rows = [], []
    t, done_f, done_b, total = 0, 0, 0, V * M
    limit = 4 * (V + M) * max(v, 1) + 64
    while (done_f < total or done_b < total) and t < limit:
        frow, brow = [], []
        for d in range(S):
            cands = []
            for j in range(v):
                q = j * S + d
                for m in range(M):        # microbatches in order per chunk
                    if (q, m) in ef:
                        continue
                    if q == 0 or ef.get((q - 1, m), limit) < t:
                        cands.append((j, m, q))
                    break
            if cands:
                j, m, q = max(cands, key=lambda c: (c[2], -c[1]))
                ef[(q, m)] = t
                done_f += 1
                frow.append((j, m, 1))
            else:
                frow.append((0, 0, 0))
        for d in range(S):
            cands = []
            for j in range(v):
                q = j * S + d
                for m in range(M):
                    if (q, m) in eb:
                        continue
                    if (q, m) not in ef or ef[(q, m)] > t:
                        continue
                    # last stage seeds its own backward the tick its
                    # forward lands (the fwd phase precedes the bwd
                    # phase inside one tick, like plain 1F1B)
                    if q == V - 1 or eb.get((q + 1, m), limit) < t:
                        cands.append((j, m, q))
                    break
            if cands:
                j, m, q = min(cands, key=lambda c: (c[1], -c[2]))
                eb[(q, m)] = t
                done_b += 1
                brow.append((j, m, 1))
            else:
                brow.append((0, 0, 0))
        fw_rows.append(frow)
        bw_rows.append(brow)
        t += 1
    if done_f != total or done_b != total:
        raise AssertionError(
            f"interleaved scheduler wedged at S={S} v={v} M={M} "
            f"({done_f}/{total} fwd, {done_b}/{total} bwd)")
    T = t

    # receive-side tables: what device d's incoming ppermute carries at
    # tick t (= the neighbour's unit from tick t-1) — derived here so no
    # indices ever travel on the wire
    fr = np.zeros((T, S, 3), np.int32)
    br = np.zeros((T, S, 3), np.int32)
    for tick in range(1, T):
        for d in range(S):
            s = (d - 1) % S
            j_s, m_s, ok = fw_rows[tick - 1][s]
            if ok and j_s * S + s != V - 1:
                fr[tick, d] = (j_s + (1 if s == S - 1 else 0), m_s, 1)
            s = (d + 1) % S
            j_s, m_s, ok = bw_rows[tick - 1][s]
            if ok and j_s * S + s != 0:
                br[tick, d] = (j_s - (1 if s == 0 else 0), m_s, 1)

    # ring capacities from the simulated live intervals
    act_live, store_live, grad_live, seed_live = [], [], [], []
    for (q, m), tf_ in ef.items():
        j, d = divmod(q, S)
        if q > 0:
            act_live.append((m, ef[(q - 1, m)] + 1, tf_))
        store_live.append((m, tf_, eb[(q, m)]))
        if q == V - 1:
            seed_live.append((m, tf_, eb[(q, m)]))
        if q < V - 1:
            grad_live.append((m, eb[(q + 1, m)] + 1, eb[(q, m)]))
    tables = {
        "fj": np.asarray([[u[0] for u in row] for row in fw_rows], np.int32),
        "fm": np.asarray([[u[1] for u in row] for row in fw_rows], np.int32),
        "fv": np.asarray([[u[2] for u in row] for row in fw_rows], np.int32),
        "bj": np.asarray([[u[0] for u in row] for row in bw_rows], np.int32),
        "bm": np.asarray([[u[1] for u in row] for row in bw_rows], np.int32),
        "bv": np.asarray([[u[2] for u in row] for row in bw_rows], np.int32),
        "frj": fr[:, :, 0], "frm": fr[:, :, 1], "frv": fr[:, :, 2],
        "brj": br[:, :, 0], "brm": br[:, :, 1], "brv": br[:, :, 2],
    }
    rings = {"act": _min_ring(act_live), "store": _min_ring(store_live),
             "grad": _min_ring(grad_live), "seed": _min_ring(seed_live)}
    return {"tables": tables, "rings": rings, "ticks": T,
            "bubble_fraction": 1.0 - (v * M) / T}


def pipeline_interleaved_spmd(stage_fn: Callable, loss_fn: Callable,
                              chunk_params, x_microbatches: jax.Array,
                              targets: jax.Array, v: int,
                              axis_name: str = "pp"):
    """Interleaved 1F1B (v virtual stage chunks per device), extending
    :func:`pipeline_1f1b_spmd`'s remat ring-buffer design.

    ``chunk_params``: this device's ``v`` chunks — pytree, leaves
    ``[v, ...]``; chunk ``j`` holds stage ``j*S + device``. Both
    directions of traffic are one ``ppermute`` per tick; which (chunk,
    microbatch) each payload belongs to is a STATIC schedule table
    (:func:`interleaved_tables`), so only activations travel. Each
    stage stores only the inputs of its in-flight microbatches (per-
    chunk rings) and rematerializes the chunk forward inside the
    backward phase, exactly like plain 1F1B — the bubble shrinks
    because a microbatch finishes a 1/v-sized chunk per tick, so fill
    and drain cost ``~1/v`` of a full device stage each.

    Returns ``(mean_loss, chunk_grads)`` with grads summed over
    microbatches (caller scales), leaves ``[v, ...]``."""
    S = axis_size(axis_name)
    stage = lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    sched = interleaved_tables(S, int(v), M)
    tb = {k: jnp.asarray(a) for k, a in sched["tables"].items()}
    rings = sched["rings"]
    T = sched["ticks"]
    mb_shape = x_microbatches.shape[1:]
    dtype = x_microbatches.dtype
    fwd_perm = [(i, (i + 1) % S) for i in range(S)]
    bwd_perm = [(i, (i - 1) % S) for i in range(S)]

    def tick(carry, t):
        (fwd_pay, bwd_pay, fwd_in, bwd_in, in_store, seed_buf,
         grad_acc, loss_acc) = carry
        # ---- receive: neighbours' tick t-1 payloads -----------------------
        f_act = lax.ppermute(fwd_pay, axis_name, fwd_perm)
        g_act = lax.ppermute(bwd_pay, axis_name, bwd_perm)
        frj, frm = tb["frj"][t, stage], tb["frm"][t, stage]
        frv = tb["frv"][t, stage] == 1
        fwd_in = fwd_in.at[frj, frm % rings["act"]].set(
            jnp.where(frv, f_act, fwd_in[frj, frm % rings["act"]]))
        brj, brm = tb["brj"][t, stage], tb["brm"][t, stage]
        brv = tb["brv"][t, stage] == 1
        bwd_in = bwd_in.at[brj, brm % rings["grad"]].set(
            jnp.where(brv, g_act, bwd_in[brj, brm % rings["grad"]]))

        # ---- forward phase ------------------------------------------------
        j, m = tb["fj"][t, stage], tb["fm"][t, stage]
        f_valid = tb["fv"][t, stage] == 1
        is_q0 = (stage == 0) & (j == 0)
        x_in = jnp.where(is_q0, x_microbatches[m],
                         fwd_in[j, m % rings["act"]])
        p_j = jax.tree_util.tree_map(lambda p: p[j], chunk_params)
        out = stage_fn(p_j, x_in)
        in_store = in_store.at[j, m % rings["store"]].set(
            jnp.where(f_valid, x_in, in_store[j, m % rings["store"]]))
        # last stage: loss value + same-tick gradient seed
        is_lastq = (stage == S - 1) & (j == v - 1)
        loss_m, g_seed = jax.value_and_grad(
            lambda y: loss_fn(y, targets[m]))(out)
        loss_acc = loss_acc + jnp.where(is_lastq & f_valid, loss_m, 0.0)
        seed_buf = seed_buf.at[m % rings["seed"]].set(
            jnp.where(is_lastq & f_valid, g_seed,
                      seed_buf[m % rings["seed"]]))
        fwd_pay = out  # receivers mask by their own table row

        # ---- backward phase -----------------------------------------------
        jb, mb = tb["bj"][t, stage], tb["bm"][t, stage]
        b_valid = tb["bv"][t, stage] == 1
        is_lastq_b = (stage == S - 1) & (jb == v - 1)
        g_out = jnp.where(is_lastq_b, seed_buf[mb % rings["seed"]],
                          bwd_in[jb, mb % rings["grad"]])
        x_b = in_store[jb, mb % rings["store"]]
        p_b = jax.tree_util.tree_map(lambda p: p[jb], chunk_params)
        _, pullback = jax.vjp(stage_fn, p_b, x_b)   # remat chunk forward
        g_params, g_x = pullback(g_out)
        grad_acc = jax.tree_util.tree_map(
            lambda a, g: a.at[jb].add(
                jnp.where(b_valid, g, jnp.zeros_like(g))),
            grad_acc, g_params)
        bwd_pay = jnp.where(b_valid, g_x, jnp.zeros_like(g_x))
        return (fwd_pay, bwd_pay, fwd_in, bwd_in, in_store, seed_buf,
                grad_acc, loss_acc), None

    zeros_mb = jnp.zeros(mb_shape, dtype)
    carry0 = (
        zeros_mb, zeros_mb,
        jnp.zeros((v, rings["act"]) + mb_shape, dtype),
        jnp.zeros((v, rings["grad"]) + mb_shape, dtype),
        jnp.zeros((v, rings["store"]) + mb_shape, dtype),
        jnp.zeros((rings["seed"],) + mb_shape, dtype),
        jax.tree_util.tree_map(jnp.zeros_like, chunk_params),
        jnp.asarray(0.0, jnp.float32),
    )
    (_, _, _, _, _, _, grads, loss_sum), _ = lax.scan(
        tick, carry0, jnp.arange(T))
    mean_loss = lax.psum(loss_sum, axis_name) / M
    return mean_loss, grads


def pipeline_interleaved_apply(stage_fn: Callable, loss_fn: Callable,
                               stage_params, x: jax.Array,
                               targets: jax.Array, mesh: Mesh,
                               n_microbatches: int, virtual_stages: int = 2,
                               axis_name: str = "pp",
                               batch_axis: Optional[str] = "dp",
                               dp_reducer: Optional[Callable] = None):
    """Array-level interleaved 1F1B.

    ``stage_params``: pytree with leading dim ``V = virtual_stages * S``
    in stage order (stage ``q`` is chunk ``q // S`` on device ``q % S``).
    Returns ``(mean_loss, grads)`` in the same stage-stacked layout,
    per-microbatch-mean scale (matching ``jax.grad`` of the mean loss).
    ``dp_reducer`` as in :func:`pipeline_1f1b_apply`."""
    from horovod_tpu.parallel.mesh import mesh_axis_size
    v = int(virtual_stages)
    if v < 1:
        raise ValueError(f"virtual_stages must be >= 1, got {v}")
    S = mesh_axis_size(mesh, axis_name)
    V = v * S
    leading = {leaf.shape[0] for leaf in
               jax.tree_util.tree_leaves(stage_params)}
    if leading != {V}:
        raise ValueError(
            f"stage_params leading dims {sorted(leading)} must all equal "
            f"virtual_stages * {axis_name} size = {V}")
    T = x.shape[0]
    if T % n_microbatches != 0:
        raise ValueError(f"batch {T} not divisible by microbatches "
                         f"{n_microbatches}")
    xm = x.reshape((n_microbatches, T // n_microbatches) + x.shape[1:])
    tm = targets.reshape((n_microbatches, T // n_microbatches)
                         + targets.shape[1:])
    b_ax = batch_axis if (batch_axis and mesh_axis_size(mesh, batch_axis) > 1) \
        else None
    if S == 1:
        one_chunks = stage_params  # [V, ...]: all chunks local

        def total(pl):
            def one_mb(xb, tb_):
                h = xb
                for q in range(V):
                    h = stage_fn(jax.tree_util.tree_map(
                        lambda p, q=q: p[q], pl), h)
                return loss_fn(h, tb_)
            return jax.vmap(one_mb)(xm, tm).mean()
        loss, g = jax.value_and_grad(total)(one_chunks)
        return loss, g

    # stage q = j*S + d  ->  device-major layout [S, v, ...] so the pp
    # shards receive their own v chunks
    def to_device_major(p):
        return jnp.moveaxis(
            p.reshape((v, S) + p.shape[1:]), 1, 0)

    def from_device_major(p):
        return jnp.moveaxis(p, 0, 1).reshape((V,) + p.shape[2:])

    dm_params = jax.tree_util.tree_map(to_device_major, stage_params)
    data_spec = P(None, b_ax)

    @functools.partial(shard_map, mesh=mesh,
                       in_specs=(P(axis_name), data_spec, data_spec),
                       out_specs=(P(), P(axis_name)), check_vma=False)
    def run(params_l, xm_l, tm_l):
        chunks = jax.tree_util.tree_map(lambda p: p[0], params_l)
        loss, grads = pipeline_interleaved_spmd(
            stage_fn, loss_fn, chunks, xm_l, tm_l, v, axis_name)
        grads = jax.tree_util.tree_map(
            lambda g: g[None] / n_microbatches, grads)
        if b_ax is not None:
            loss = lax.pmean(loss, b_ax)
            grads = _dp_reduce(grads, b_ax, dp_reducer)
        return loss, grads

    loss, dm_grads = run(dm_params, xm, tm)
    return loss, jax.tree_util.tree_map(from_device_major, dm_grads)


def schedule_ticks(schedule: str, S: int, M: int, v: int = 1):
    """Analytic (ticks, ideal_ticks) for one training step of a
    schedule, in that schedule's own tick units (a combined
    forward+backward tick for the 1F1B family; forward-pass + transposed
    backward-pass tick-slots for GPipe-by-autodiff). ``1 - ideal/ticks``
    is the pipeline bubble fraction the bench artifact records."""
    if S <= 1:
        return max(M, 1), max(M, 1)
    if schedule == "gpipe":
        return 2 * (M + S - 1), 2 * M
    if schedule == "1f1b":
        return M + 2 * S - 2, M
    if schedule == "interleaved":
        sched = interleaved_tables(S, max(int(v), 1), M)
        return sched["ticks"], v * M
    raise ValueError(f"unknown schedule {schedule!r}; expected "
                     "gpipe | 1f1b | interleaved")


def bubble_fraction(schedule: str, S: int, M: int, v: int = 1) -> float:
    """Analytic fill+drain bubble fraction for ``schedule`` at pipeline
    depth ``S``, ``M`` microbatches, ``v`` virtual chunks per device."""
    ticks, ideal = schedule_ticks(schedule, S, M, v)
    return 1.0 - ideal / ticks
