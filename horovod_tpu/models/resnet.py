"""ResNet-50 (v1.5) — the reference's headline benchmark model family
(reference: ``examples/pytorch/pytorch_imagenet_resnet50.py``,
``docs/benchmarks.rst``: ResNet-class CNNs at 90% scaling efficiency).

TPU-native: flax module in bf16 with fp32 BN statistics, trained
data-parallel in GSPMD-auto mode — batch sharded over ``dp``, params
replicated; XLA inserts the gradient all-reduce the reference does with
NCCL ring-allreduce (``nccl_operations.cc:156-214``). NHWC layout (TPU
conv-friendly); matmul-heavy bottlenecks land on the MXU.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
import flax.linen as nn
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from horovod_tpu.models.scan_util import multi_step


class BottleneckBlock(nn.Module):
    filters: int
    strides: Tuple[int, int] = (1, 1)
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = True):
        needs_proj = x.shape[-1] != self.filters * 4 or self.strides != (1, 1)
        residual = x
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype)
        bn = functools.partial(nn.BatchNorm, use_running_average=not train,
                               momentum=0.9, epsilon=1e-5, dtype=self.dtype)
        y = conv(self.filters, (1, 1))(x)
        y = bn()(y)
        y = nn.relu(y)
        y = conv(self.filters, (3, 3), self.strides)(y)
        y = bn()(y)
        y = nn.relu(y)
        y = conv(self.filters * 4, (1, 1))(y)
        y = bn(scale_init=nn.initializers.zeros)(y)
        if needs_proj:
            residual = conv(self.filters * 4, (1, 1), self.strides)(residual)
            residual = bn()(residual)
        return nn.relu(y + residual)


def space_to_depth(x: jax.Array, block: int = 2) -> jax.Array:
    """[B, H, W, C] -> [B, H/b, W/b, C*b*b] (pixel-shuffle inverse)."""
    B, H, W, C = x.shape
    x = x.reshape(B, H // block, block, W // block, block, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        B, H // block, W // block, C * block * block)


class ResNet(nn.Module):
    """``stem="conv"`` is the textbook 7x7/s2 stem. ``stem="s2d"`` is the
    MLPerf-TPU space-to-depth stem: the 7x7/s2 conv over C=3 tiles the MXU
    terribly (3 input channels against a 128-wide systolic array);
    space-to-depth(2) turns it into a 4x4/s1 conv over 12 channels with
    the same receptive field and output shape, cutting the stem's padding
    waste 4x.
    """

    stage_sizes: Sequence[int]
    num_classes: int = 1000
    dtype: Any = jnp.bfloat16
    stem: str = "conv"
    remat: bool = False  # jax.checkpoint each block: HBM for recompute,
    #                      unlocking larger per-chip batches (PERF.md (b))
    remat_prevent_cse: bool = True  # pass False when the step runs inside
    #                      lax.scan (scan_steps>1): flax documents the CSE
    #                      barrier as unnecessary there, and it costs

    @nn.compact
    def __call__(self, x, train: bool = True):
        if self.stem == "s2d":
            x = space_to_depth(x, 2)  # [B, 112, 112, 12]
            x = nn.Conv(64, (4, 4), (1, 1), padding="SAME",
                        use_bias=False, dtype=self.dtype)(x)
        elif self.stem == "conv":
            x = nn.Conv(64, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                        use_bias=False, dtype=self.dtype)(x)
        else:
            raise ValueError(
                f"unknown stem {self.stem!r}; expected 'conv' or 's2d'")
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5, dtype=self.dtype)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        # static_argnums counts (self, x, train): train must be passed
        # POSITIONALLY for the lifted remat to see it as static. The
        # explicit name pins the param path to the PLAIN class's
        # auto-name, so init RNG streams and checkpoints are identical
        # whether remat is on or off.
        block_cls = nn.remat(
            BottleneckBlock, static_argnums=(2,),
            prevent_cse=self.remat_prevent_cse) \
            if self.remat else BottleneckBlock
        block_idx = 0
        for i, n_blocks in enumerate(self.stage_sizes):
            for j in range(n_blocks):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(64 * 2 ** i, strides, self.dtype,
                              name=f"BottleneckBlock_{block_idx}")(x, train)
                block_idx += 1
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=jnp.float32)(x)
        return x


def ResNet50(num_classes: int = 1000, dtype=jnp.bfloat16,
             stem: str = "conv", remat: bool = False,
             remat_prevent_cse: bool = True) -> ResNet:
    return ResNet([3, 4, 6, 3], num_classes, dtype, stem, remat,
                  remat_prevent_cse)


def ResNet101(num_classes: int = 1000, dtype=jnp.bfloat16,
              stem: str = "conv", remat: bool = False,
              remat_prevent_cse: bool = True) -> ResNet:
    return ResNet([3, 4, 23, 3], num_classes, dtype, stem, remat,
                  remat_prevent_cse)


def create_resnet_state(model: ResNet, rng_key, image_size: int = 224,
                        mesh: Mesh = None):
    """Init params/batch_stats, replicated over the mesh."""
    variables = model.init(rng_key, jnp.zeros((1, image_size, image_size, 3),
                                              model.dtype), train=True)
    if mesh is not None:
        rep = NamedSharding(mesh, P())
        variables = jax.tree_util.tree_map(
            lambda x: jax.device_put(x, rep), variables)
    return variables["params"], variables["batch_stats"]


def make_resnet_train_step(model: ResNet, optimizer, mesh: Mesh,
                           scan_steps: int = 1):
    """Data-parallel train step (GSPMD-auto): batch sharded over every
    data-like axis; gradient reduction inserted by XLA from shardings —
    functionally identical to the reference's DistributedOptimizer loop
    (``torch/optimizer.py:314-325``) with fusion/overlap done by the
    compiler instead of the background thread.

    ``scan_steps > 1`` runs that many optimizer steps per call via
    ``lax.scan`` inside ONE compiled program: a single dispatch covers
    the whole chain, taking host→device launch latency off the
    critical path. Every scanned step
    consumes the SAME ``images``/``labels`` batch (the scan carries only
    the training state — ``scan_util.multi_step``): right for
    throughput measurement, NOT a substitute for multi-batch training —
    feed a fresh batch per call with ``scan_steps=1`` for real epochs.
    The returned loss is the LAST scanned step's.

    ``params``/``batch_stats``/``opt_state`` buffers are DONATED: the
    update happens in place on device, so keep only the returned state
    (the inputs are invalidated after the call on TPU)."""

    def one_step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            one_hot = jax.nn.one_hot(labels, logits.shape[-1])
            loss = optax.softmax_cross_entropy(logits, one_hot).mean()
            return loss, mut["batch_stats"]
        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_stats, opt_state, loss

    chain = multi_step(one_step, n_carry=3, scan_steps=scan_steps)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, batch_stats, opt_state, images, labels):
        return chain(params, batch_stats, opt_state, images, labels)

    return step


def batch_sharding(mesh: Mesh) -> NamedSharding:
    axes = tuple(a for a in ("dp", "ep", "sp", "pp", "tp")
                 if mesh.shape.get(a, 1) > 1)
    return NamedSharding(mesh, P(axes if axes else None))
