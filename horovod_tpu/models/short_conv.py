"""The gated short-convolution mixer of ``models/transformer.py``'s
``("conv",)`` blocks (LFM2, arXiv:2511.23404; ``transformers``'
``Lfm2ShortConv``), :data:`KIND` in its table of block kinds: no scan and no
state past ``conv_taps - 1`` positions. Local shapes, the whole sequence on
this device (no sp, pp or tp):
  conv_in         [M, 3 M]: ``[B | C | u] = h W_in``, the thirds in that order
  gate chain      ``z = B * u``; ``c[t] = sum_j w[j] * z[t - (K - 1) + j]``
                  (depthwise, causal, zeros before the start, tap ``K - 1``
                  on the current position, no bias, no activation:
                  ``mamba._causal_conv``); ``y = C * c``; all of it in
                  float32 on the projection's bf16 thirds, rounded once to
                  the compute dtype before ``conv_out``
  conv_out        [M, M]
Two matmuls with bandwidth-bound vector work over ``[tokens, 3 M]``
between them: what the chain costs is whether XLA fuses it into its
neighbours (``scopes.SHORT_CONV_GATE`` says).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from horovod_tpu.models._kinds import (BlockKind, Leaf, normal, ones, rmsnorm,
                                       scaled)
from horovod_tpu.models.mamba import _causal_conv
from horovod_tpu.profiling import scopes


def _leaves(cfg):
    """A short-convolution block's leaves, in the order they are drawn."""
    M, K = cfg.d_model, cfg.conv_taps

    def taps(rng, shape):   # as a Mamba block's are drawn
        return (rng.uniform(-1, 1, shape) / np.sqrt(K)).astype(np.float32)
    yield Leaf("ln1", (M,), ones)
    yield Leaf("conv_in", (M, 3 * M), normal())
    yield Leaf("conv_w", (K, M), taps)
    yield Leaf("conv_out", (M, M), normal())


def gate_chain(bcu, taps):
    """``C * conv(B * u)`` of the in-projection's output ``[B', S, 3 M]``
    (thirds ``[B | C | u]``), taps ``[K, M]``: float32 ``[B', S, M]``."""
    b, c, u = jnp.split(bcu, 3, axis=-1)
    z = b.astype(jnp.float32) * u.astype(jnp.float32)
    return c.astype(jnp.float32) * _causal_conv(z, taps, None)


def _conv_block(p, x, cfg):
    """``x + short_conv(norm(x))``, x ``[B', S', M]`` with the whole
    sequence here (no sp)."""
    with scopes.scope(scopes.SHORT_CONV):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        with scopes.scope(scopes.SHORT_CONV_PROJ):
            bcu = h @ p["conv_in"].astype(h.dtype)
        with scopes.scope(scopes.SHORT_CONV_GATE):
            y = gate_chain(bcu, p["conv_w"]).astype(h.dtype)
        with scopes.scope(scopes.SHORT_CONV_PROJ):
            o = y @ p["conv_out"].astype(h.dtype)
        return x + scaled(o, cfg.residual_scale)


def _validate(cfg) -> None:
    if cfg.conv_taps < 1:
        raise ValueError(
            f"layer_pattern has (\"conv\",) blocks and conv_taps="
            f"{cfg.conv_taps}: the mixer's convolution has at least one tap")


#: the row of ``transformer._BLOCK_KINDS``. Not checkpointed by the single
#: pass: the block keeps its normed input, the in-projection's three thirds
#: and the chain's output, bf16 ``[tokens, 5 M]``
KIND = BlockKind(
    length=1, leaves=_leaves, validate=_validate,
    apply=lambda p, x, positions, cfg, kind: (_conv_block(p, x, cfg), None),
    refuses=("sp", "pp", "tp"),
    refusal="the convolution runs over the whole sequence on one device (no "
            "hand-over of the last taps between sp shards), the gates' "
            "thirds are not split over tp, and no pipeline schedule has run "
            "it")
