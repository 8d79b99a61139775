"""Unified parallelism plan battery (ISSUE 11): ParallelPlan
validation / fingerprint / cache roundtrip, the compile seam's
pjit-vs-shard_map dispatch, interleaved == 1f1b == jax.grad parity
across the (pp, dp, M, v) grid, composed DP x PP loss-trajectory parity
with pure DP (incl. the int8 wire codec), the order of the schedules'
bubble fractions, and the extended autotune search locking a full parallelism
plan (warm cache => zero trials).

CPU note: everything runs on the 8-device virtual mesh under
tests/conftest.py with the persistent XLA compile cache at its default
of DISABLED (the known warm-cache heap-corruption constraint)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu.parallel import build_mesh, dp_pp_mesh
from horovod_tpu.parallel.pipeline import (bubble_fraction,
                                           interleaved_tables,
                                           pipeline_1f1b_apply,
                                           pipeline_interleaved_apply,
                                           replicate_from_stage,
                                           schedule_ticks, stage_stacked)
from horovod_tpu.parallel.plan import (SCHEDULES, ParallelPlan,
                                       compile_step_with_plan,
                                       plan_from_dict)
from horovod_tpu.train.autotune import (AutotuneOptions, Plan, PlanCache,
                                        make_parallel_train_step,
                                        parallel_candidate_plans,
                                        plan_fingerprint, topology_key)
from horovod_tpu.train.pipeline import (make_pipeline_train_step,
                                        stage_layout_permutation)
from horovod_tpu.common.topology import flat_topology


# -- ParallelPlan validation / identity -------------------------------------

def test_parallel_plan_roundtrip_and_key():
    p = ParallelPlan(dp=2, pp=4, schedule="interleaved", n_microbatches=8,
                     virtual_stages=2, comms=Plan(1 << 20, "psum", "int8"))
    assert ParallelPlan.from_dict(p.to_dict()) == p
    assert "dp2xpp4" in p.key and "interleavedv2" in p.key
    assert p.world == 8 and p.total_stages == 8
    # the comm facade the shared controller/CSV/gauges read
    assert p.codec == "int8" and p.algorithm == "psum"
    bare = ParallelPlan(dp=8, pp=1)
    assert bare.codec == "none" and bare.bucket_bytes == 0


@pytest.mark.parametrize("kw", [
    dict(dp=0),
    dict(pp=0),
    dict(schedule="pipedream"),
    dict(pp=2, n_microbatches=1),                       # pure bubble
    dict(virtual_stages=2, schedule="1f1b"),            # v needs interleaved
    dict(n_microbatches=0),
    dict(comms="int8"),                                 # not a Plan
])
def test_parallel_plan_validation_rejects(kw):
    base = dict(dp=2, pp=2, n_microbatches=4)
    base.update(kw)
    with pytest.raises(ValueError):
        ParallelPlan(**base)


def test_plan_from_dict_dispatch():
    comm = Plan(4096, "ring", "none")
    par = ParallelPlan(dp=4, pp=2, n_microbatches=4, comms=comm)
    assert plan_from_dict(comm.to_dict()) == comm
    revived = plan_from_dict(par.to_dict())
    assert isinstance(revived, ParallelPlan) and revived == par
    assert revived.comms == comm


def test_bubble_fraction_analytics():
    # plain 1F1B pays the combined fill+drain bubble; interleaving with
    # v chunks strictly shrinks it at the same M (the tentpole claim,
    # deterministic tick counts)
    for S, M, v in [(4, 8, 2), (4, 8, 4), (8, 8, 2), (2, 8, 2)]:
        plain = bubble_fraction("1f1b", S, M)
        inter = bubble_fraction("interleaved", S, M, v)
        t_plain = v * schedule_ticks("1f1b", S, M)[0]  # sub-tick equiv
        t_inter = schedule_ticks("interleaved", S, M, v)[0]
        assert t_inter <= t_plain, (S, M, v)
        if S > 2:
            assert inter < plain, (S, M, v)
    assert bubble_fraction("gpipe", 1, 4) == 0.0
    assert ParallelPlan(dp=2, pp=4, n_microbatches=8).bubble_fraction() \
        == bubble_fraction("1f1b", 4, 8)


def test_interleaved_tables_are_a_valid_schedule():
    """Replay the static tables and assert every dependency: forwards
    in stage order with one-tick transfer delay, backwards after the
    successor's backward, the last stage seeding same-tick, and at most
    one unit per device per phase per tick (the scheduler's contract —
    the numerics tests would catch corruption, this catches an invalid
    schedule that happens to mask itself)."""
    for S, v, M in [(2, 2, 4), (4, 2, 8), (2, 4, 8), (4, 3, 5)]:
        sched = interleaved_tables(S, v, M)
        tb = sched["tables"]
        V = S * v
        ef, eb = {}, {}
        for t in range(sched["ticks"]):
            for d in range(S):
                if tb["fv"][t][d]:
                    q = tb["fj"][t][d] * S + d
                    m = tb["fm"][t][d]
                    assert (q, m) not in ef
                    if q > 0:
                        assert ef[(q - 1, m)] < t, (S, v, M, q, m, t)
                    ef[(q, m)] = t
            for d in range(S):
                if tb["bv"][t][d]:
                    q = tb["bj"][t][d] * S + d
                    m = tb["bm"][t][d]
                    assert (q, m) not in eb
                    assert ef[(q, m)] <= t
                    if q < V - 1:
                        assert eb[(q + 1, m)] < t
                    eb[(q, m)] = t
        assert len(ef) == V * M and len(eb) == V * M
        assert 0.0 < sched["bubble_fraction"] < 1.0


def test_stage_layout_permutation_roundtrip():
    perm = stage_layout_permutation(8, pp=2, virtual_stages=2)
    # device 0: chunk0 = stages 0 (layers 0,1), chunk1 = stage 2
    # (layers 4,5); device 1: stage 1 (2,3) then stage 3 (6,7)
    assert perm.tolist() == [0, 1, 4, 5, 2, 3, 6, 7]
    assert stage_layout_permutation(8, pp=4).tolist() == list(range(8))
    with pytest.raises(ValueError):
        stage_layout_permutation(8, pp=3)


# -- fingerprint / cache ----------------------------------------------------

def test_topology_key_pp_dimension():
    topo = flat_topology(8)
    tree = {"w": jnp.zeros((4, 4))}
    comm_fp = plan_fingerprint(tree, topology_key(topo), 8)
    pipe_fp = plan_fingerprint(tree, topology_key(topo, pp=0), 8)
    under_pp = plan_fingerprint(tree, topology_key(topo, pp=4), 8)
    # a comm plan tuned under one pp split can never shadow the
    # parallel-plan entry (pp=0 sentinel) or another split's entry
    assert len({comm_fp, pipe_fp, under_pp}) == 3


def test_cache_roundtrips_parallel_plan(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = ParallelPlan(dp=2, pp=4, schedule="interleaved",
                        n_microbatches=8, virtual_stages=2,
                        comms=Plan(1 << 20, "psum", "int8"))
    assert cache.store("a" * 64, plan)
    got = cache.load("a" * 64)
    assert isinstance(got, ParallelPlan) and got == plan
    # comm plans still roundtrip as comm plans
    cache.store("b" * 64, Plan(4096, "ring", "none"))
    assert cache.load("b" * 64) == Plan(4096, "ring", "none")


# -- compile seam -----------------------------------------------------------

def test_compile_seam_pjit_path():
    mesh = build_mesh(dp=8)
    sh = NamedSharding(mesh, P("dp"))
    rep = NamedSharding(mesh, P())

    def step(x):
        return x * 2.0, jnp.sum(x)

    fn = compile_step_with_plan(step, mesh, in_shardings=(sh,),
                                out_shardings=(sh, rep))
    x = jnp.arange(16.0)
    y, s = fn(x)
    np.testing.assert_allclose(np.asarray(y), np.arange(16.0) * 2)
    assert float(s) == np.arange(16.0).sum()
    assert y.sharding.is_equivalent_to(sh, y.ndim)


def test_compile_seam_shard_map_path():
    mesh = build_mesh(dp=8)

    def body(x):     # map-style SPMD: a named-axis collective
        return lax.psum(jnp.sum(x), "dp")

    fn = compile_step_with_plan(body, mesh, in_specs=(P("dp"),),
                                out_specs=P())
    assert float(fn(jnp.ones(16))) == 16.0


def test_compile_seam_single_device_fallback():
    mesh = build_mesh(dp=1, devices=jax.devices()[:1])
    fn = compile_step_with_plan(lambda x: x + 1, mesh)
    assert float(fn(jnp.asarray(1.0))) == 2.0


def test_compile_seam_rejects_mixed_and_half_args():
    mesh = build_mesh(dp=8)
    sh = NamedSharding(mesh, P("dp"))
    with pytest.raises(ValueError, match="BOTH in_shardings"):
        compile_step_with_plan(lambda x: x, mesh, in_shardings=(sh,))
    with pytest.raises(ValueError, match="BOTH in_specs"):
        compile_step_with_plan(lambda x: x, mesh, out_specs=P())
    with pytest.raises(ValueError, match="not both"):
        compile_step_with_plan(lambda x: x, mesh, in_shardings=(sh,),
                               out_shardings=(sh,), in_specs=(P("dp"),),
                               out_specs=P())


def test_replicate_from_stage_grads_inside_shard_map():
    """Differentiating a replicated consumer INSIDE shard_map: the
    masked-psum idiom over-counts by the axis size (every shard seeds
    its replicated loss); replicate_from_stage must not — this is the
    GPipe-by-autodiff / transformer-pp gradient-scale regression test."""
    import functools
    from horovod_tpu._compat import shard_map
    mesh = build_mesh(dp=1, pp=4, devices=jax.devices()[:4])
    w = jnp.asarray(np.random.RandomState(0).randn(4).astype(np.float32))

    @functools.partial(shard_map, mesh=mesh, in_specs=(P("pp"),),
                       out_specs=P("pp"), check_vma=False)
    def grads(wl):
        def loss(wl):
            stage = lax.axis_index("pp")
            val = jnp.where(stage == 3, wl[0] * 2.0, wl[0])
            y = replicate_from_stage(val, "pp", 3)
            return y ** 2
        return jax.grad(loss)(wl)

    g = np.asarray(grads(w))
    # only stage 3 feeds the replicated output; its gradient must be
    # d/dw (2w)^2 = 8w — once, not 4x
    np.testing.assert_allclose(g[3], 8.0 * w[3], rtol=1e-6)
    np.testing.assert_allclose(g[:3], 0.0, atol=1e-7)


# -- schedule numerics: interleaved == 1f1b == jax.grad ---------------------

def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def _mse(y, t):
    return jnp.mean((y - t) ** 2)


def _grid_case(pp, dp, M, v, H=8):
    V = pp * v
    T = M * 4
    rng = np.random.RandomState(7)
    stages = [{"w": jnp.asarray(rng.randn(H, H), jnp.float32) * 0.4,
               "b": jnp.asarray(rng.randn(H), jnp.float32) * 0.1}
              for _ in range(V)]
    x = jnp.asarray(rng.randn(T, H), jnp.float32)
    tgt = jnp.asarray(rng.randn(T, H), jnp.float32)
    stacked = stage_stacked(stages)

    def oracle(pl):
        xm = x.reshape(M, T // M, H)
        tm = tgt.reshape(M, T // M, H)

        def one_mb(xb, tb):
            h = xb
            for s in range(V):
                h = _stage_fn(jax.tree_util.tree_map(
                    lambda p, s=s: p[s], pl), h)
            return _mse(h, tb)
        return jax.vmap(one_mb)(xm, tm).mean()

    ref_loss, ref_g = jax.value_and_grad(oracle)(stacked)
    mesh = build_mesh(dp=dp, pp=pp, devices=jax.devices()[:dp * pp])
    loss, g = pipeline_interleaved_apply(
        _stage_fn, _mse, stacked, x, tgt, mesh, n_microbatches=M,
        virtual_stages=v)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(ref_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)
    if v == 1:
        # at v=1 the interleaved machinery must agree with the plain
        # 1F1B implementation too (same schedule, different codepath)
        l2, g2 = pipeline_1f1b_apply(_stage_fn, _mse, stacked, x, tgt,
                                     mesh, n_microbatches=M)
        np.testing.assert_allclose(float(l2), float(loss), rtol=1e-5)
        for a, b in zip(jax.tree_util.tree_leaves(g2),
                        jax.tree_util.tree_leaves(g)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("pp,dp,M,v", [(2, 2, 4, 2), (4, 2, 8, 1)])
def test_interleaved_matches_jax_grad(pp, dp, M, v):
    _grid_case(pp, dp, M, v)


@pytest.mark.slow
@pytest.mark.parametrize("pp,dp,M,v", [
    (4, 2, 8, 2),      # the acceptance 2x4 layout, v=2
    (2, 4, 8, 4),      # deep virtual interleave
    (4, 1, 3, 2),      # M < 2S-1: ragged fill/drain
    (2, 2, 5, 3),      # M coprime with S and v
])
def test_interleaved_matches_jax_grad_heavy(pp, dp, M, v):
    _grid_case(pp, dp, M, v)


def test_dp_reducer_seam_matches_dense_pmean():
    """Satellite 1: the dp reduction seam. Routed through the bucketed
    sync, gradients must equal the exact dense-pmean fallback (Average
    psum per bucket == pmean per leaf, fp32)."""
    from horovod_tpu.train.overlap import bucketed_grad_sync
    pp, dp, M = 2, 4, 4
    rng = np.random.RandomState(3)
    stages = [{"w": jnp.asarray(rng.randn(8, 8), jnp.float32) * 0.4,
               "b": jnp.asarray(rng.randn(8), jnp.float32) * 0.1}
              for _ in range(pp)]
    stacked = stage_stacked(stages)
    x = jnp.asarray(rng.randn(16, 8), jnp.float32)
    tgt = jnp.asarray(rng.randn(16, 8), jnp.float32)
    mesh = build_mesh(dp=dp, pp=pp)
    dense_loss, dense_g = pipeline_1f1b_apply(
        _stage_fn, _mse, stacked, x, tgt, mesh, n_microbatches=M)

    def reducer(grads):
        return bucketed_grad_sync(grads, "dp", bucket_bytes=64)

    loss, g = pipeline_1f1b_apply(
        _stage_fn, _mse, stacked, x, tgt, mesh, n_microbatches=M,
        dp_reducer=reducer)
    np.testing.assert_allclose(float(loss), float(dense_loss), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(g),
                    jax.tree_util.tree_leaves(dense_g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-7)


# -- composed DP x PP vs pure DP (the factory) ------------------------------

_L, _D = 8, 16


def _layer_model():
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(_L, _D, _D), jnp.float32) * 0.4,
              "b": jnp.asarray(rng.randn(_L, _D), jnp.float32) * 0.1}

    def layer_fn(lp, x):
        return jnp.tanh(x @ lp["w"] + lp["b"])

    x = jnp.asarray(rng.randn(64, _D), jnp.float32)
    tgt = jnp.asarray(rng.randn(64, _D), jnp.float32)
    return params, layer_fn, (x, tgt)


def _trajectory(schedule, pp, M, v=1, steps=6, compression=None,
                params=None, batch=None, layer_fn=None, tx=None):
    step = make_pipeline_train_step(
        layer_fn, _mse, tx, n_layers=_L, schedule=schedule, pp=pp,
        n_micro=M, virtual_stages=v, compression=compression,
        donate=False, autotune=False)
    p = step.prepare_params(params)
    s = step.prepare_params(tx.init(params))
    losses = []
    for _ in range(steps):
        p, s, loss = step(p, s, batch)
        losses.append(float(loss))
    return losses, step.restore_params(p)


@pytest.mark.parametrize("schedule,pp,M,v", [
    ("1f1b", 4, 8, 1),            # acceptance layout dp2 x pp4
    ("interleaved", 2, 8, 2),     # acceptance layout dp4 x pp2
])
def test_composed_dp_pp_matches_pure_dp_trajectory(schedule, pp, M, v):
    """ISSUE 11 acceptance: on the 8-device mesh the composed DP x PP
    step (stage grads through bucketed_grad_sync over dp) must match
    the pure-DP (pp=1, overlap-engine) loss trajectory to fp32
    tolerance, parameters included."""
    params, layer_fn, batch = _layer_model()
    tx = optax.adam(1e-2)
    kw = dict(params=params, batch=batch, layer_fn=layer_fn, tx=tx)
    ref_losses, ref_p = _trajectory("1f1b", 1, M, **kw)
    losses, p = _trajectory(schedule, pp, M, v, **kw)
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(ref_p)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_composed_dp_pp_gpipe_and_int8_trajectories():
    """The gpipe schedule and the int8 wire codec through the composed
    step: gpipe matches pure DP exactly (same fp32 math); with the int8
    codec on the dp hop, both layouts quantize (different bucket
    boundaries), so the gate is a converging trajectory that tracks the
    exact one within a loose band — the codec's documented contract,
    not bit parity."""
    from horovod_tpu.compression.quantizers import resolve_compressor
    params, layer_fn, batch = _layer_model()
    tx = optax.adam(1e-2)
    kw = dict(params=params, batch=batch, layer_fn=layer_fn, tx=tx)
    ref_losses, _ = _trajectory("1f1b", 1, 8, **kw)
    g_losses, _ = _trajectory("gpipe", 4, 8, **kw)
    np.testing.assert_allclose(g_losses, ref_losses, rtol=1e-4, atol=1e-5)
    q = resolve_compressor("int8")
    q_losses, _ = _trajectory("1f1b", 4, 8, steps=8, compression=q, **kw)
    assert q_losses[-1] < q_losses[0] * 0.8, q_losses
    exact, _ = _trajectory("1f1b", 1, 8, steps=8, **kw)
    assert abs(q_losses[-1] - exact[-1]) < 0.1 * abs(exact[0]), (
        q_losses, exact)


def test_factory_rejects_bad_layouts():
    params, layer_fn, batch = _layer_model()
    tx = optax.sgd(1e-2)
    with pytest.raises(ValueError, match="does not divide"):
        make_pipeline_train_step(layer_fn, _mse, tx, n_layers=_L,
                                 schedule="1f1b", pp=3, n_micro=4)
    with pytest.raises(ValueError, match="not divisible"):
        make_pipeline_train_step(layer_fn, _mse, tx, n_layers=6,
                                 schedule="1f1b", pp=4, n_micro=4)
    step = make_pipeline_train_step(layer_fn, _mse, tx, n_layers=_L,
                                    schedule="1f1b", pp=2, n_micro=4,
                                    donate=False, autotune=False)
    p = step.prepare_params(params)
    s = tx.init(p)
    bad = (jnp.ones((30, _D)), jnp.ones((30, _D)))   # 30 % (dp*M) != 0
    with pytest.raises((ValueError, TypeError)):
        step(p, s, bad)


# -- the schedule sweep ------------------------------------------------------

def test_schedule_sweep_interleaved_beats_plain_1f1b():
    """ISSUE 11 acceptance, as far as counts go: at the sweep's shape
    (pp=4, M=8, v=2) the analytic bubble fractions order the schedules,
    GPipe-by-autodiff below interleaved below plain 1F1B (the ~1/v
    bubble).  What a step costs under each is for a chip to say."""
    bubble = {s: bubble_fraction(s, 4, 8, 2 if s == "interleaved" else 1)
              for s in SCHEDULES}
    assert bubble["gpipe"] < bubble["interleaved"] < bubble["1f1b"], bubble


# -- the extended autotune search -------------------------------------------

def _tune_model():
    rng = np.random.RandomState(0)
    params = {"w": jnp.asarray(rng.randn(_L, 32, 32), jnp.float32) * 0.4}

    def layer_fn(lp, x):
        return jnp.tanh(x @ lp["w"])

    x = jnp.asarray(rng.randn(64, 32), jnp.float32)
    tgt = jnp.asarray(rng.randn(64, 32), jnp.float32)
    return params, layer_fn, (x, tgt)


def test_parallel_candidate_plans_shape():
    plans = parallel_candidate_plans(8, 8)
    assert plans[0] == ParallelPlan(dp=8, pp=1)    # baseline first
    keys = {p.key for p in plans}
    assert len(keys) == len(plans)                 # deduplicated
    assert any(p.pp == 4 and p.schedule == "interleaved" for p in plans)
    assert any(p.comms is not None and p.comms.codec == "int8"
               for p in plans)
    # pp must divide both the world and the layer count
    assert all(8 % p.pp == 0 and 8 % p.total_stages == 0 for p in plans)
    assert all(p.pp <= 4 for p in parallel_candidate_plans(8, 4))


def test_parallel_autotune_warm_cache_zero_trials(tmp_path):
    """A cached ParallelPlan must lock on the FIRST call with zero
    search trials (fast path of the acceptance; the full search is the
    slow test below)."""
    params, layer_fn, batch = _tune_model()
    tx = optax.sgd(1e-2)
    topo = flat_topology(8)
    fp = plan_fingerprint(params, topology_key(topo, pp=0), 8)
    want = ParallelPlan(dp=2, pp=4, schedule="interleaved",
                        n_microbatches=8, virtual_stages=2)
    PlanCache(str(tmp_path)).store(fp, want)
    opts = AutotuneOptions(budget_steps=40, cache_dir=str(tmp_path))
    step = make_parallel_train_step(layer_fn, _mse, tx, n_layers=_L,
                                    autotune=opts, donate=False)
    p, s = params, tx.init(params)
    p, s, loss = step(p, s, batch)
    ctl = step.autotune
    assert ctl.from_cache and ctl.trials == 0
    assert ctl.locked_plan == want
    assert step.pin() is not None
    assert np.isfinite(float(loss))


def test_parallel_autotune_stale_cached_plan_retunes(tmp_path):
    """The fingerprint covers tree+world but NOT the batch: a cached
    plan tuned at another global batch must be rejected with a warning
    and a fresh search, never crash the first step (the documented
    cache contract)."""
    params, layer_fn, batch = _tune_model()   # global batch 64
    tx = optax.sgd(1e-2)
    topo = flat_topology(8)
    fp = plan_fingerprint(params, topology_key(topo, pp=0), 8)
    # m=48 cannot tile 64/2=32 rows per replica
    stale = ParallelPlan(dp=2, pp=4, schedule="1f1b", n_microbatches=48)
    PlanCache(str(tmp_path)).store(fp, stale)
    opts = AutotuneOptions(
        plans=[ParallelPlan(dp=8, pp=1),
               ParallelPlan(dp=2, pp=4, schedule="1f1b",
                            n_microbatches=8)],
        budget_steps=20, steps_per_trial=1, cache_dir=str(tmp_path))
    step = make_parallel_train_step(layer_fn, _mse, tx, n_layers=_L,
                                    autotune=opts, donate=False)
    p, s = params, tx.init(params)
    for _ in range(30):
        p, s, loss = step(p, s, batch)
        if step.autotune is not None and step.autotune.done:
            break
    ctl = step.autotune
    assert ctl.done and not ctl.from_cache and ctl.trials > 0
    assert ctl.locked_plan != stale
    # the retune overwrote the stale entry with a plan that DOES tile
    assert PlanCache(str(tmp_path)).load(fp) == ctl.locked_plan


def test_csv_trace_rotates_old_schema(tmp_path):
    from horovod_tpu.train.autotune import AutotuneController
    log_path = str(tmp_path / "trace.csv")
    with open(log_path, "w") as f:
        f.write("round,bucket_bytes,algorithm,codec,small_floor,"
                "step_s,final\n0,1,psum,none,0,0.001000,1\n")
    a, b = Plan(1, "psum", "none"), Plan(2, "psum", "none")
    ctl = AutotuneController([a, b], budget_steps=50, steps_per_trial=1,
                             log_path=log_path)
    while not ctl.done:
        ctl.end_step({a: 0.002, b: 0.009}[ctl.begin_step()])
    lines = open(log_path).read().strip().splitlines()
    assert lines[0] == ("round,bucket_bytes,algorithm,codec,"
                        "small_floor,plan,step_s,final")
    assert all(ln.count(",") == 7 for ln in lines)
    old = open(log_path + ".v1").read()
    assert "0.001000" in old   # the old audit trail survives, apart


@pytest.mark.slow
def test_parallel_autotune_converges_and_warm_cache_skips_search(
        tmp_path):
    """ISSUE 11 acceptance: the extended search — (pp, n_microbatches,
    schedule) joining bucket x algorithm x codec — locks a full
    parallelism plan within its step budget, and a second run against
    the warm cache locks the SAME plan with zero trials."""
    params, layer_fn, batch = _tune_model()
    tx = optax.sgd(1e-2)
    plans = parallel_candidate_plans(8, _L)[:8]
    opts = AutotuneOptions(plans=plans, budget_steps=60,
                           steps_per_trial=1, cache_dir=str(tmp_path))
    step = make_parallel_train_step(layer_fn, _mse, tx, n_layers=_L,
                                    autotune=opts, donate=False)
    p, s = params, tx.init(params)
    for _ in range(80):
        p, s, loss = step(p, s, batch)
        if step.autotune is not None and step.autotune.done:
            break
    ctl = step.autotune
    assert ctl.done and ctl.steps_used <= opts.budget_steps
    assert ctl.trials > 0 and not ctl.from_cache
    assert ctl.locked_plan in plans
    # training continued through the search on one state
    assert np.isfinite(float(loss))

    warm = make_parallel_train_step(layer_fn, _mse, tx, n_layers=_L,
                                    autotune=opts, donate=False)
    wp, ws = params, tx.init(params)
    warm(wp, ws, batch)
    assert warm.autotune.from_cache and warm.autotune.trials == 0
    assert warm.autotune.locked_plan == ctl.locked_plan


def test_factory_env_autotune_default(monkeypatch):
    """HVD_TPU_AUTOTUNE_MESH=1 flips the pipeline factory to the
    parallel searcher without touching call sites; explicit plan= or
    autotune=False still wins."""
    from horovod_tpu.common.config import reset_config
    from horovod_tpu.train.autotune import ParallelAutotunedStep
    params, layer_fn, batch = _tune_model()
    tx = optax.sgd(1e-2)
    monkeypatch.setenv("HVD_TPU_AUTOTUNE_MESH", "1")
    reset_config()
    try:
        step = make_pipeline_train_step(layer_fn, _mse, tx, n_layers=_L)
        assert isinstance(step, ParallelAutotunedStep)
        pinned = make_pipeline_train_step(
            layer_fn, _mse, tx, n_layers=_L,
            plan=ParallelPlan(dp=4, pp=2, n_microbatches=4))
        assert not isinstance(pinned, ParallelAutotunedStep)
        plain = make_pipeline_train_step(layer_fn, _mse, tx, n_layers=_L,
                                         autotune=False, pp=2, n_micro=4)
        assert not isinstance(plain, ParallelAutotunedStep)
    finally:
        reset_config()


def test_dp_pp_mesh_helper():
    mesh = dp_pp_mesh(pp=4)
    assert mesh.shape["pp"] == 4 and mesh.shape["dp"] == 2
    mesh2 = dp_pp_mesh(dp=2, pp=2, devices=jax.devices()[:4])
    assert mesh2.shape["dp"] == 2 and mesh2.shape["pp"] == 2
