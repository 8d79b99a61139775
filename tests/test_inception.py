"""Inception V3 model tests (reference benchmark table parity:
docs/benchmarks.rst:13-14 — Inception V3 / ResNet-101 / VGG-16)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.inception import (InceptionV3,
                                          create_inception_state,
                                          make_inception_train_step)
from horovod_tpu.models.resnet import batch_sharding


@pytest.mark.slow  # ~30s XLA:CPU compile; tier-1 budget (models tier
#                    runs it unfiltered)
def test_inception_v3_trains(hvd):
    """Geometry + one GSPMD-auto train step (small input keeps the CPU
    test fast; 95 is the smallest size the VALID-padded stem and the two
    reduction stages all accept)."""
    mesh = hvd.build_mesh(dp=-1)
    model = InceptionV3(num_classes=8, dtype=jnp.float32, dropout=0.0)
    params, batch_stats = create_inception_state(
        model, jax.random.PRNGKey(0), image_size=95, mesh=mesh)
    tx = optax.sgd(0.01, momentum=0.9)
    opt_state = jax.jit(tx.init)(params)
    step = make_inception_train_step(model, tx, mesh)
    rng = np.random.RandomState(0)
    images = jax.device_put(
        jnp.asarray(rng.rand(8, 95, 95, 3), jnp.float32),
        batch_sharding(mesh))
    labels = jax.device_put(
        jnp.asarray(rng.randint(0, 8, (8,)), jnp.int32),
        batch_sharding(mesh))
    params, batch_stats, opt_state, loss = step(
        params, batch_stats, opt_state, images, labels)
    assert np.isfinite(float(loss))


def test_inception_v3_channel_geometry():
    """Stage output channels match the canonical architecture:
    35x35 stages end at 288, 17x17 at 768, 8x8 at 2048. Shapes only, so
    each stage is described (``jax.eval_shape`` over ``init`` and
    ``apply``), not run."""
    from horovod_tpu.models.inception import (InceptionA, ReductionA,
                                              InceptionB, ReductionB,
                                              InceptionC)

    def through(m, x):
        def run(x):
            v = m.init(jax.random.PRNGKey(0), x, train=False)
            return m.apply(v, x, train=False)
        return jax.eval_shape(run, x)

    x = jax.ShapeDtypeStruct((1, 35, 35, 192), jnp.float32)
    for pf, want in ((32, 256), (64, 288), (64, 288)):
        x = through(InceptionA(pf, jnp.float32), x)
        assert x.shape[-1] == want
    x = through(ReductionA(jnp.float32), x)
    assert x.shape == (1, 17, 17, 768)
    x = through(InceptionB(128, jnp.float32), x)
    assert x.shape[-1] == 768
    x = through(ReductionB(jnp.float32), x)
    assert x.shape == (1, 8, 8, 1280)
    x = through(InceptionC(jnp.float32), x)
    assert x.shape[-1] == 2048
