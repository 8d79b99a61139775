"""Mesh-path communication autotuner battery (ISSUE 8): plan space,
successive-halving controller, fingerprinting, persistent plan cache
hygiene (corrupt/stale entries retune, never crash), the
DistributedOptimizer warm-start seam, and the acceptance gates — the
online search converges within its step budget, and a second run with
a warm plan cache performs ZERO search trials.

CPU note: these trials run under tests/conftest.py, which keeps the
persistent XLA compile cache DISABLED by default — required on the
8-device CPU mesh (known warm-cache heap-corruption signature)."""

import json
import os

import numpy as np
import pytest

from horovod_tpu.train.autotune import (AutotuneController,
                                        AutotuneOptions, Plan, PlanCache,
                                        candidate_plans,
                                        plan_fingerprint)
from horovod_tpu.common.topology import MeshTopology, flat_topology


# -- Plan -------------------------------------------------------------------

def test_plan_roundtrip_and_key():
    p = Plan(1 << 20, "hier", "int8", 4096)
    assert Plan.from_dict(p.to_dict()) == p
    assert "hier/int8" in p.key


@pytest.mark.parametrize("kw", [
    dict(bucket_bytes=0),
    dict(bucket_bytes=1, algorithm="tree"),
    dict(bucket_bytes=1, codec="int4"),
    dict(bucket_bytes=1, algorithm="ring", codec="int8"),
    dict(bucket_bytes=1, small_floor=-1),
])
def test_plan_validation_rejects(kw):
    with pytest.raises(ValueError):
        Plan(**kw)


def test_candidate_plans_shape():
    flat = candidate_plans(flat_topology(8))
    assert all(p.algorithm != "hier" for p in flat)
    hier = candidate_plans(MeshTopology(2, 4))
    assert any(p.algorithm == "hier" for p in hier)
    assert len(set(hier)) == len(hier)  # deduplicated
    # floor variants never duplicate the dense flat path
    assert not any(p.algorithm == "psum" and p.codec == "none"
                   and p.small_floor > 0 for p in hier)
    base = Plan(123456, "ring", "none")
    assert candidate_plans(flat_topology(8), baseline=base)[0] == base


# -- controller -------------------------------------------------------------

def _drive(ctl, times):
    """Run the controller to lock against a fixed per-plan step time."""
    guard = 0
    while not ctl.done and guard < 10_000:
        plan = ctl.begin_step()
        ctl.end_step(times[plan])
        guard += 1
    assert ctl.done, "controller never locked"


def test_controller_picks_fastest_plan():
    a, b, c = (Plan(1, "psum", "none"), Plan(2, "psum", "none"),
               Plan(3, "psum", "none"))
    ctl = AutotuneController([a, b, c], budget_steps=100,
                             steps_per_trial=2)
    _drive(ctl, {a: 0.010, b: 0.004, c: 0.020})
    assert ctl.locked_plan == b
    assert ctl.best_seconds == pytest.approx(0.004)
    assert ctl.trials > 0 and ctl.steps_used <= 100
    assert not ctl.from_cache


def test_controller_warmup_steps_not_scored():
    a = Plan(1, "psum", "none")
    ctl = AutotuneController([a], budget_steps=10, steps_per_trial=2)
    ctl.begin_step()
    ctl.end_step(99.0)  # warmup (compile) — must not poison the score
    while not ctl.done:
        ctl.begin_step()
        ctl.end_step(0.005)
    assert ctl.best_seconds == pytest.approx(0.005)


def test_controller_budget_exhaustion_locks_best_scored():
    plans = [Plan(i + 1, "psum", "none") for i in range(10)]
    times = {p: 0.010 - 0.0005 * i for i, p in enumerate(plans)}
    # budget fits only 2 plans at 3 steps each (1 warmup + 2 scored)
    ctl = AutotuneController(plans, budget_steps=6, steps_per_trial=2)
    _drive(ctl, times)
    assert ctl.locked_plan in plans[:2]  # trimmed tail never ran
    assert ctl.steps_used <= 6


def test_controller_trims_to_budget_with_warning(caplog):
    plans = [Plan(i + 1, "psum", "none") for i in range(8)]
    import logging
    with caplog.at_level(logging.WARNING):
        ctl = AutotuneController(plans, budget_steps=9,
                                 steps_per_trial=2)
    assert len(ctl._survivors) == 3
    assert any("dropping" in r.message for r in caplog.records)


def test_controller_csv_trace(tmp_path):
    a, b = Plan(1, "psum", "none"), Plan(2, "ring", "none")
    log_path = str(tmp_path / "trace.csv")
    ctl = AutotuneController([a, b], budget_steps=50,
                             steps_per_trial=2, log_path=log_path)
    _drive(ctl, {a: 0.002, b: 0.009})
    lines = open(log_path).read().strip().splitlines()
    assert lines[0].startswith("round,bucket_bytes,algorithm")
    assert lines[-1].endswith(",1")  # final-choice row
    assert any(",ring," in ln for ln in lines)


# -- fingerprint ------------------------------------------------------------

def test_fingerprint_sensitivity():
    import jax.numpy as jnp
    tree = {"w": jnp.zeros((4, 4)), "b": jnp.zeros((4,))}
    fp = plan_fingerprint(tree, {"dp": 8}, 8)
    assert fp == plan_fingerprint(tree, {"dp": 8}, 8)  # stable
    assert fp != plan_fingerprint(tree, {"dp": 4}, 4)  # world
    assert fp != plan_fingerprint(tree, {"dp": 4, "tp": 2}, 4)  # mesh
    other = {"w": jnp.zeros((4, 5)), "b": jnp.zeros((4,))}
    assert fp != plan_fingerprint(other, {"dp": 8}, 8)  # structure
    cast = {"w": jnp.zeros((4, 4), jnp.bfloat16), "b": jnp.zeros((4,))}
    assert fp != plan_fingerprint(cast, {"dp": 8}, 8)  # dtype


# -- plan cache hygiene (satellite: never crash init) -----------------------

def test_cache_store_load_roundtrip(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = Plan(1 << 20, "hier", "int8", 4096)
    path = cache.store("f" * 64, plan, meta={"trials": 7})
    assert path and os.path.exists(path)
    assert cache.load("f" * 64) == plan
    assert cache.load("0" * 64) is None  # unknown fingerprint


def test_cache_truncated_json_retunes(tmp_path, caplog):
    import logging
    cache = PlanCache(str(tmp_path))
    cache.store("a" * 64, Plan(1, "psum", "none"))
    with open(cache.path("a" * 64), "w") as f:
        f.write('{"version": 1, "plan": {"bucket')  # torn mid-write
    with caplog.at_level(logging.WARNING):
        assert cache.load("a" * 64) is None
    assert any("retuning" in r.message for r in caplog.records)


def test_cache_fingerprint_mismatch_retunes(tmp_path, caplog):
    import logging
    cache = PlanCache(str(tmp_path))
    cache.store("b" * 64, Plan(1, "psum", "none"))
    # a stale rename: file for one fingerprint served under another
    os.replace(cache.path("b" * 64), cache.path("c" * 64))
    with caplog.at_level(logging.WARNING):
        assert cache.load("c" * 64) is None
    assert any("mismatch" in r.message for r in caplog.records)


def test_cache_wrong_version_retunes(tmp_path):
    cache = PlanCache(str(tmp_path))
    with open(cache.path("d" * 64), "w") as f:
        json.dump({"version": 999, "fingerprint": "d" * 64,
                   "plan": {"bucket_bytes": 1}}, f)
    assert cache.load("d" * 64) is None


def test_cache_invalid_plan_retunes(tmp_path):
    cache = PlanCache(str(tmp_path))
    with open(cache.path("e" * 64), "w") as f:
        json.dump({"version": 1, "fingerprint": "e" * 64,
                   "plan": {"bucket_bytes": 1, "algorithm": "warp"}}, f)
    assert cache.load("e" * 64) is None


def test_cache_unwritable_dir_degrades(tmp_path):
    target = tmp_path / "blocked"
    target.write_text("a file where the cache dir should be")
    cache = PlanCache(str(target))  # makedirs will fail
    assert cache.store("f" * 64, Plan(1, "psum", "none")) is None


def test_controller_try_cache_locks_with_zero_trials(tmp_path):
    cache = PlanCache(str(tmp_path))
    plan = Plan(7, "ring", "none")
    cache.store("9" * 64, plan)
    ctl = AutotuneController([Plan(1, "psum", "none")], budget_steps=10,
                             cache=cache, fingerprint="9" * 64)
    assert ctl.try_cache()
    assert ctl.locked_plan == plan
    assert ctl.from_cache and ctl.trials == 0
    # begin/end are no-ops once locked
    assert ctl.begin_step() == plan
    ctl.end_step(1.0)
    assert ctl.trials == 0


# -- DistributedOptimizer warm-start seam -----------------------------------

def test_distributed_optimizer_autotune_warm_start(hvd, tmp_path,
                                                   monkeypatch):
    import jax.numpy as jnp
    import optax
    from horovod_tpu.common.config import reset_config

    from horovod_tpu.train.autotune import topology_key

    params = {"w": jnp.ones((4, 4)), "b": jnp.zeros((4,))}
    # the seam reconstructs the fingerprint from the CANONICAL topology
    # key (axis-name-free), so a plan the mesh search stored for this
    # model at this world size is found regardless of axis naming
    topo = flat_topology(hvd.size())
    fp = plan_fingerprint(params, topology_key(topo), hvd.size())
    PlanCache(str(tmp_path)).store(fp, Plan(4096, "psum", "int8"))
    monkeypatch.setenv("HVD_TPU_AUTOTUNE_CACHE_DIR", str(tmp_path))
    reset_config()
    try:
        from horovod_tpu.metrics.registry import default_registry
        hits = default_registry().counter(
            "hvd_autotune_cache_hits_total",
            help="runs that started from a cached tuned plan with zero "
                 "search trials")
        before = hits.value
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), autotune=True)
        state = opt.init(params)
        assert hits.value == before + 1
        grads = {"w": jnp.full((4, 4), 0.5), "b": jnp.ones((4,))}
        updates, state = opt.update(grads, state, params)
        # the cached int8 codec is applied under error feedback: the
        # update is the (lossily quantized) gradient scaled by -lr
        w = np.asarray(updates["w"])
        assert np.abs(w + 0.05).max() < 0.01
    finally:
        reset_config()


def test_distributed_optimizer_autotune_miss_keeps_settings(
        hvd, tmp_path, monkeypatch):
    import jax.numpy as jnp
    import optax
    from horovod_tpu.common.config import reset_config

    monkeypatch.setenv("HVD_TPU_AUTOTUNE_CACHE_DIR", str(tmp_path))
    reset_config()
    try:
        params = {"w": jnp.ones((3, 3))}
        opt = hvd.DistributedOptimizer(optax.sgd(0.1), autotune=True)
        state = opt.init(params)
        grads = {"w": jnp.full((3, 3), 0.5)}
        updates, state = opt.update(grads, state, params)
        np.testing.assert_allclose(np.asarray(updates["w"]), -0.05,
                                   rtol=1e-6)
    finally:
        reset_config()


def test_distributed_optimizer_autotune_rejects_adasum(hvd):
    import optax
    with pytest.raises(ValueError, match="standard sync path"):
        hvd.DistributedOptimizer(optax.sgd(0.1),
                                 op=hvd.ReduceOp.ADASUM, autotune=True)


def test_autotune_mesh_env_enables_search_by_default(hvd, monkeypatch):
    """HVD_TPU_AUTOTUNE_MESH=1 flips every make_overlap_train_step to
    the searching wrapper without touching call sites; Adasum under the
    fleet-wide env default is skipped, not an init crash."""
    import optax
    from horovod_tpu.common.config import reset_config
    from horovod_tpu.train.autotune import AutotunedStep
    from horovod_tpu.train.overlap import make_overlap_train_step

    import jax.numpy as jnp

    monkeypatch.setenv("HVD_TPU_AUTOTUNE_MESH", "1")
    reset_config()
    try:
        mesh = hvd.build_mesh(dp=-1)

        def loss_fn(p, b):
            return jnp.mean((b @ p["w"]) ** 2)

        step = make_overlap_train_step(loss_fn, optax.sgd(0.1), mesh)
        assert isinstance(step, AutotunedStep)
        # the candidate builder must pin autotune OFF — under the env
        # default it would otherwise recurse into the searcher forever
        params = {"w": jnp.ones((4, 4))}
        tx_state = optax.sgd(0.1).init(params)
        batch = jnp.ones((8, 4))
        step(params, tx_state, batch)  # must not RecursionError
        assert step.autotune is not None
        # explicit opt-out still wins
        plain = make_overlap_train_step(lambda p, b: 0.0, optax.sgd(0.1),
                                        mesh, autotune=False)
        assert not isinstance(plain, AutotunedStep)
        # env-driven default skips incompatible paths instead of raising
        hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.ReduceOp.ADASUM)
    finally:
        reset_config()


# -- acceptance: convergence vs the hand-set sweep + warm zero-trial --------

def test_autotune_converges_and_warm_cache_skips_search(
        hvd, tmp_path, monkeypatch):
    """ISSUE 8 acceptance. On the 8-device CPU mesh the online search
    must (a) lock one of its candidates within its step budget, and
    (b) a second run against the warm plan cache must lock the same
    plan with zero search trials. Which plan is fastest is a question
    for a chip, not for this mesh."""
    import jax.numpy as jnp
    import optax
    from horovod_tpu.train.overlap import make_overlap_train_step

    monkeypatch.setenv("HVD_TPU_VIRTUAL_HOSTS", "2")  # enable hier
    mesh = hvd.build_mesh(dp=-1)
    from horovod_tpu.common.topology import detect_topology
    topo = detect_topology(mesh, "dp")
    assert topo.is_hierarchical

    plans = [
        Plan(1 << 20, "psum", "none"),
        Plan(4096, "psum", "int8"),
        Plan(1 << 20, "ring", "none"),
        Plan(1 << 20, "hier", "none"),
    ]

    rng = np.random.RandomState(0)
    params = {f"w{i}": jnp.asarray(rng.randn(64, 64).astype(np.float32)
                                   / 8.0) for i in range(4)}

    def loss_fn(p, xy):
        x, y = xy
        h = x
        for i in range(4):
            h = jnp.tanh(h @ p[f"w{i}"])
        return jnp.mean((h - y) ** 2)

    tx = optax.sgd(1e-3)
    x = jnp.asarray(rng.randn(64, 64).astype(np.float32))
    y = jnp.asarray(rng.randn(64, 64).astype(np.float32))
    opts = AutotuneOptions(plans=plans, budget_steps=40,
                           steps_per_trial=3,
                           cache_dir=str(tmp_path))

    step = make_overlap_train_step(loss_fn, tx, mesh, "dp", n_micro=2,
                                   autotune=opts, donate=False)
    p, s = params, tx.init(params)
    for _ in range(60):
        p, s, loss = step(p, s, (x, y))
        if step.autotune is not None and step.autotune.done:
            break
    ctl = step.autotune
    assert ctl.done, "search must converge within its budget"
    assert ctl.steps_used <= opts.budget_steps
    assert ctl.trials > 0 and not ctl.from_cache

    assert ctl.locked_plan in plans

    # the winner is in the persistent cache; a fresh step warm-starts
    # with ZERO trials and the same plan
    warm = make_overlap_train_step(loss_fn, tx, mesh, "dp", n_micro=2,
                                   autotune=opts, donate=False)
    p2, s2 = params, tx.init(params)
    for _ in range(2):
        p2, s2, _ = warm(p2, s2, (x, y))
    ctl2 = warm.autotune
    assert ctl2.from_cache and ctl2.trials == 0
    assert ctl2.locked_plan == ctl.locked_plan
