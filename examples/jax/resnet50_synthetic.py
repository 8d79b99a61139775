"""ResNet-50 synthetic-data throughput (reference analog:
``examples/pytorch/pytorch_synthetic_benchmark.py`` /
``examples/tensorflow2/tensorflow2_synthetic_benchmark.py``).

Prints img/sec like the reference's synthetic benchmarks.  The CNNs
(ResNet, VGG, Inception in ``horovod_tpu.models``) have no cell in
``BENCHMARK.json``, so the repo records no number for them; this script
is how to run ResNet-50.
"""

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
import optax

import horovod_tpu as hvd
from horovod_tpu.models import init_opt_state
from horovod_tpu.models.resnet import (ResNet50, batch_sharding,
                                       create_resnet_state,
                                       make_resnet_train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch-size", type=int, default=256,
                    help="per-chip batch size")
    ap.add_argument("--num-iters", type=int, default=10)
    ap.add_argument("--num-warmup", type=int, default=3)
    args = ap.parse_args()

    hvd.init()
    mesh = hvd.build_mesh(dp=-1)
    n_chips = jax.device_count()
    B = args.batch_size * n_chips

    model = ResNet50(dtype=jnp.bfloat16 if jax.default_backend() == "tpu"
                     else jnp.float32)
    params, stats = create_resnet_state(model, jax.random.PRNGKey(0),
                                        mesh=mesh)
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = init_opt_state(tx, params, mesh)
    step = make_resnet_train_step(model, tx, mesh)

    rng = np.random.RandomState(0)
    images = jax.device_put(jnp.asarray(rng.rand(B, 224, 224, 3),
                                        model.dtype), batch_sharding(mesh))
    labels = jax.device_put(jnp.asarray(rng.randint(0, 1000, (B,)),
                                        jnp.int32), batch_sharding(mesh))

    for _ in range(args.num_warmup):
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              images, labels)
    float(loss)  # drain (block_until_ready is unreliable on this platform)

    t0 = time.perf_counter()
    for _ in range(args.num_iters):
        params, stats, opt_state, loss = step(params, stats, opt_state,
                                              images, labels)
    float(loss)
    dt = time.perf_counter() - t0
    img_sec = B * args.num_iters / dt
    if hvd.rank() == 0:
        print(f"Total img/sec: {img_sec:.1f} "
              f"({img_sec / n_chips:.1f} per chip, {n_chips} chips)")
    hvd.shutdown()


if __name__ == "__main__":
    main()
