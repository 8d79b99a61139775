"""What a parameter leaf and a kind of block are: the declarations that
``models/transformer.py`` builds ``init_params``' tree, ``param_shardings``'
specs and the stack's scan from. It imports neither that file nor the mixers
(``models/mamba.py``) that declare theirs with it.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Iterable, NamedTuple, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp


class Leaf(NamedTuple):
    """One parameter. A block's leaves are a function of the config that
    yields them in the order ``init_params`` draws them; the ``if`` around a
    ``yield`` is the one place that says when a config has the leaf."""
    name: str
    shape: Tuple[int, ...]      # without the stack's leading [stage, block]
    draw: Callable              # (rng, the whole shape) -> float32 array
    spec: Tuple[Optional[str], ...] = ()    # "tp", "ep" or None for each of
    #                             ``shape``'s dimensions; (): not split


class BlockKind(NamedTuple):
    """A block of ONE sublayer, ``x + mixer(norm(x))``: a row of
    ``transformer._BLOCK_KINDS``."""
    length: int                 # of its kind tuple in ``layer_pattern``
    leaves: Callable[[Any], Iterable[Leaf]]     # of the config
    apply: Callable             # (p, x, positions, cfg, kind) -> (x, the
    #                             block's auxiliary terms or None)
    validate: Callable[[Any], None] = lambda cfg: None  # ValueError for a
    #                             config without the fields the block reads
    checkpointed: bool = False  # by the single pass, if ``cfg.remat`` is None
    refuses: Tuple[str, ...] = ()   # mesh axes it does not run on live (with
    #                             "pp": its stack is whole on every device)
    refusal: str = ""           # and why: ``param_shardings``' error
    gradients_first: bool = False   # the train step finishes every gradient
    #                             as an array of its own before the
    #                             optimizer reads any (``make_train_step``)
    optional: int = 0           # further fields a kind of it may give, each
    #                             of which changes a leaf's shape: such kinds
    #                             are stacks of their own
    #                             (``transformer._stack_of``)


def ones(rng, shape):
    return np.ones(shape, np.float32)


def zeros(rng, shape):
    return np.zeros(shape, np.float32)


def normal(scale=None):
    """A normal draw at ``scale``, or at ``1 / sqrt(rows)`` of the matrix
    the last two dimensions are."""
    def draw(rng, shape):
        std = scale if scale is not None else 1.0 / np.sqrt(shape[-2])
        return (rng.randn(*shape) * std).astype(np.float32)
    return draw


def remat(cfg, needed: bool) -> bool:
    """Whether a path checkpoints its blocks: what the config says, or
    where it says nothing, whether the path needs it to fit."""
    return needed if cfg.remat is None else cfg.remat


def scaled(x, factor: float):
    """``x * factor`` rounded once to ``x.dtype``, the factor in float32
    (0.22 as a bfloat16 is 0.2197: every sublayer 0.12 % short, which the
    chip's check read as a loss 9e-5 off on every seed, PERF.md section 6,
    PR 49); ``x`` itself where the factor is 1 (a config that leaves a
    multiplier at its default traces no multiply)."""
    if factor == 1.0:
        return x
    return (x.astype(jnp.float32) * factor).astype(x.dtype)


class Yarn(NamedTuple):
    """YaRN's change to a rotary table (arXiv:2309.00071, as ``transformers``
    computes it): the slow frequencies divided by ``factor``, the fast ones
    kept, a linear ramp between the channels that turn ``beta_fast`` and
    ``beta_slow`` times over ``original`` positions; cos and sin times
    ``attention_factor``."""
    factor: float
    original: int
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None    # None: 0.1 ln(factor) + 1


class Rope(NamedTuple):
    """A rotary table of a layer kind's own, where the kind's rope is not
    just on (``True``: the whole head at ``cfg.rope_theta``) or off."""
    theta: float
    width: Optional[int] = None     # the head's first channels that rotate,
    #                             the others pass through. None: the whole head
    yarn: Optional[Yarn] = None


def yarn_ramp(table: Rope, width: int):
    """(low, high) of YaRN's ramp over the ``width // 2`` frequencies of
    ``table``: channel ``i`` keeps its frequency for ``i <= low`` and has it
    divided by ``factor`` for ``i >= high``."""
    y = table.yarn

    def turns(n):   # the channel that turns n times over ``original``
        return (width * math.log(y.original / (n * 2 * math.pi))
                / (2 * math.log(table.theta)))
    return (max(math.floor(turns(y.beta_fast)), 0),
            min(math.ceil(turns(y.beta_slow)), width - 1))


def rope_table(table: Rope, head_dim: int):
    """(the ``width // 2`` float32 frequencies of ``table`` for a head of
    ``head_dim``, the factor on cos and sin), computed on the host in
    float64."""
    width = head_dim if table.width is None else table.width
    if width % 2 or not 0 < width <= head_dim:
        raise ValueError(f"{table}: {width} rotated channels of a head of "
                         f"{head_dim}")
    i = np.arange(width // 2, dtype=np.float64)
    freqs = table.theta ** (-2.0 * i / width)
    if table.yarn is None:
        return freqs.astype(np.float32), 1.0
    y = table.yarn
    low, high = yarn_ramp(table, width)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    freqs = freqs / y.factor * ramp + freqs * (1.0 - ramp)
    factor = (0.1 * math.log(y.factor) + 1.0 if y.attention_factor is None
              else y.attention_factor)
    return freqs.astype(np.float32), float(factor)


def rope(x, positions, theta=10000.0):
    """Rotary embedding, halves layout; x [B, S, H, D], positions [S]
    absolute. ``theta``: the base of the default table over the whole head,
    or a :class:`Rope` (the halves layout within its rotated channels)."""
    B, S, H, D = x.shape
    if isinstance(theta, Rope):
        # (a branch of its own: the plain table below is traced as it was,
        # to the jaxpr's letter, for every config that names no table)
        freqs, factor = rope_table(theta, D)
        width = 2 * len(freqs)
        ang = positions[:, None].astype(jnp.float32) * freqs[None, :]
        cos = (jnp.cos(ang) * factor)[None, :, None, :].astype(x.dtype)
        sin = (jnp.sin(ang) * factor)[None, :, None, :].astype(x.dtype)
        x1, x2 = x[..., :width // 2], x[..., width // 2:width]
        return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                                x[..., width:]], -1)
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions[:, None].astype(jnp.float32) * freqs[None, :]  # [S, half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    cos = cos[None, :, None, :].astype(x.dtype)
    sin = sin[None, :, None, :].astype(x.dtype)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def rmsnorm(x, g, eps=1e-6, zero_centred: bool = False):
    """``x * rsqrt(mean(x^2) + eps) * g``, the statistics in float32. A
    ``zero_centred`` weight's scale is ``1 + g``, formed and applied in
    float32 (``TransformerConfig.zero_centred_norms``)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    if zero_centred:
        return (normed * (1.0 + g.astype(jnp.float32))).astype(x.dtype)
    return normed.astype(x.dtype) * g.astype(x.dtype)


def layernorm(x, g, b, eps=1e-6):
    """LayerNorm with weight and bias over the last dimension, its
    statistics in float32."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * g.astype(x.dtype) + b.astype(x.dtype))
