"""ResNet model family tests (reference analog: the synthetic benchmark
models in examples/; here unit-level so the bench harness model is
covered off-TPU), including the MLPerf-style space-to-depth stem."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.resnet import (ResNet, ResNet50, batch_sharding,
                                       create_resnet_state,
                                       make_resnet_train_step,
                                       space_to_depth)


def test_space_to_depth_layout():
    x = jnp.arange(2 * 4 * 4 * 3, dtype=jnp.float32).reshape(2, 4, 4, 3)
    y = space_to_depth(x, 2)
    assert y.shape == (2, 2, 2, 12)
    # block (0,0) of image 0: pixels (0,0),(0,1),(1,0),(1,1) channel-major
    np.testing.assert_array_equal(
        np.asarray(y)[0, 0, 0],
        np.concatenate([np.asarray(x)[0, 0, 0], np.asarray(x)[0, 0, 1],
                        np.asarray(x)[0, 1, 0], np.asarray(x)[0, 1, 1]]))


@pytest.mark.parametrize("stem", ["conv", "s2d"])
def test_resnet_stems_same_geometry(stem):
    """Both stems produce the identical downstream geometry (112x112x64
    after the stem at 224 input; logits shape equal). A shape, so the
    network is described (``jax.eval_shape``), not run."""
    model = ResNet([1, 1, 1, 1], num_classes=10, dtype=jnp.float32,
                   stem=stem)

    def run(x):
        variables = model.init(jax.random.PRNGKey(0), x, train=True)
        logits, _ = model.apply(variables, x, train=True,
                                mutable=["batch_stats"])
        return logits
    logits = jax.eval_shape(
        run, jax.ShapeDtypeStruct((2, 64, 64, 3), jnp.float32))
    assert logits.shape == (2, 10)


def test_resnet101_deeper_than_50():
    """ResNet-101 shares the implementation; only stage depths differ
    (reference benchmark trio: docs/benchmarks.rst:13-14)."""
    from horovod_tpu.models.resnet import ResNet101
    assert ResNet101().stage_sizes == [3, 4, 23, 3]
    assert ResNet50().stage_sizes == [3, 4, 6, 3]


def test_vgg16_trains(hvd):
    """VGG-16 (the reference's gradient-bandwidth stress model) trains
    under the same GSPMD-auto contract as the ResNet family."""
    from horovod_tpu.models.vgg import VGG, create_vgg_state, \
        make_vgg_train_step
    mesh = hvd.build_mesh(dp=-1)
    # thin VGG (same topology, fewer channels) keeps the CPU test fast
    model = VGG(stages=((1, 8), (1, 16), (1, 16), (1, 32), (1, 32)),
                num_classes=8, dtype=jnp.float32, dropout=0.0)
    params = create_vgg_state(model, jax.random.PRNGKey(0), image_size=64,
                              mesh=mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = jax.jit(tx.init)(params)
    step = make_vgg_train_step(model, tx, mesh)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(16, 64, 64, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 8, (16,)), jnp.int32),
                            batch_sharding(mesh))
    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state, images, labels)
        loss.block_until_ready()
        losses.append(float(loss))
    assert losses[-1] < losses[0]


@pytest.mark.slow  # ~12s double compile; tier-1 budget (models tier
#                    runs it unfiltered)
def test_vgg_scan_steps_matches_sequential_dropout_indices(hvd):
    """The INDEXED scan variant (dropout models): scanned step i must use
    dropout index step_idx * scan_steps + i, so a scan_steps=2 dispatch
    with step_idx=0 equals sequential calls with step_idx=0 then 1."""
    from horovod_tpu.models.vgg import VGG, create_vgg_state, \
        make_vgg_train_step
    mesh = hvd.build_mesh(dp=-1)
    # real dropout so identical masks would be detectable
    model = VGG(stages=((1, 8), (1, 16), (1, 16), (1, 32), (1, 32)),
                num_classes=8, dtype=jnp.float32, dropout=0.5)
    tx = optax.sgd(0.05, momentum=0.9)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(16, 64, 64, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 8, (16,)), jnp.int32),
                            batch_sharding(mesh))

    def init():
        params = create_vgg_state(model, jax.random.PRNGKey(0),
                                  image_size=64, mesh=mesh)
        return params, jax.jit(tx.init)(params)

    step1 = make_vgg_train_step(model, tx, mesh)
    p, o = init()
    for i in range(2):
        p, o, loss_seq = step1(p, o, images, labels, step_idx=i)
        loss_seq.block_until_ready()

    step2 = make_vgg_train_step(model, tx, mesh, scan_steps=2)
    p2, o2 = init()
    p2, o2, loss_scan = step2(p2, o2, images, labels, step_idx=0)
    loss_scan.block_until_ready()

    np.testing.assert_allclose(float(loss_scan), float(loss_seq), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # ~19s double compile; tier-1 budget (models tier
#                    runs it unfiltered)
def test_scan_steps_matches_sequential(hvd):
    """scan_steps=2 (one dispatch, two in-graph optimizer steps) must
    produce the same params/loss as two sequential scan_steps=1 calls —
    the bench's multi-step chain changes dispatch count, not training."""
    mesh = hvd.build_mesh(dp=-1)
    model = ResNet([1, 1, 1, 1], num_classes=8, dtype=jnp.float32)
    tx = optax.sgd(0.05, momentum=0.9)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(16, 64, 64, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 8, (16,)), jnp.int32),
                            batch_sharding(mesh))

    def init():
        params, batch_stats = create_resnet_state(
            model, jax.random.PRNGKey(0), image_size=64, mesh=mesh)
        return params, batch_stats, jax.jit(tx.init)(params)

    step1 = make_resnet_train_step(model, tx, mesh)
    p, bs, o = init()
    for _ in range(2):
        p, bs, o, loss_seq = step1(p, bs, o, images, labels)
        loss_seq.block_until_ready()

    step2 = make_resnet_train_step(model, tx, mesh, scan_steps=2)
    p2, bs2, o2 = init()
    p2, bs2, o2, loss_scan = step2(p2, bs2, o2, images, labels)
    loss_scan.block_until_ready()

    np.testing.assert_allclose(float(loss_scan), float(loss_seq),
                               rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.slow  # ~12s double compile; tier-1 budget (models tier
#                    runs it unfiltered)
def test_resnet_remat_matches_plain(hvd):
    """remat=True (jax.checkpoint per block) changes memory, not math:
    one train step produces the same loss and params as the plain model."""
    mesh = hvd.build_mesh(dp=-1)
    tx = optax.sgd(0.05, momentum=0.9)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(8, 64, 64, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 8, (8,)), jnp.int32),
                            batch_sharding(mesh))

    outs = []
    for remat in (False, True):
        model = ResNet([1, 1, 1, 1], num_classes=8, dtype=jnp.float32,
                       remat=remat)
        params, batch_stats = create_resnet_state(
            model, jax.random.PRNGKey(0), image_size=64, mesh=mesh)
        step = make_resnet_train_step(model, tx, mesh)
        p, bs, _, loss = step(params, batch_stats,
                              jax.jit(tx.init)(params), images, labels)
        loss.block_until_ready()
        outs.append((p, bs, float(loss)))
    (p0, bs0, l0), (p1, bs1, l1) = outs
    np.testing.assert_allclose(l0, l1, rtol=1e-6)
    # params AND the mutable batch_stats (running mean/var updated inside
    # the checkpointed blocks) must agree
    for tree0, tree1 in ((p0, p1), (bs0, bs1)):
        for a, b in zip(jax.tree_util.tree_leaves(tree0),
                        jax.tree_util.tree_leaves(tree1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_resnet_s2d_trains(hvd):
    """Five steps of the space-to-depth stem's network over the 8-device
    mesh lower the loss: values, which only a run gives (the one compile of
    that step in this file)."""
    mesh = hvd.build_mesh(dp=-1)
    model = ResNet([1, 1, 1, 1], num_classes=8, dtype=jnp.float32,
                   stem="s2d")
    params, batch_stats = create_resnet_state(
        model, jax.random.PRNGKey(0), image_size=64, mesh=mesh)
    tx = optax.sgd(0.05, momentum=0.9)
    opt_state = jax.jit(tx.init)(params)
    step = make_resnet_train_step(model, tx, mesh)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(16, 64, 64, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 8, (16,)), jnp.int32),
                            batch_sharding(mesh))
    losses = []
    for _ in range(5):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
        loss.block_until_ready()
        losses.append(float(loss))
    assert losses[-1] < losses[0]
