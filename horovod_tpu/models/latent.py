"""Latent attention (MLA: DeepSeek-V2, arXiv:2405.04434 section 2.1; the
form GLM-4.7-Flash takes) as ``models/transformer.py``'s ``("latent",)``
blocks, :data:`KIND` in its table of block kinds: the leaves and the block.
Queries, keys and values are low-rank: the block's input goes down to a
latent of ``q_latent`` channels and one of ``kv_latent``, each normed, and up
from there to ``n_heads`` heads of ``head_dim``. A head's last ``rope_width``
channels carry the positions and its first ``head_dim - rope_width`` none;
on the key side the rope part is ONE head of ``rope_width`` channels, taken
beside the latent, that every query head reads. Local shapes, the whole
sequence and every head on this device (no sp, pp or tp):
  wqa             [M, q_latent]                 q_latent_norm [q_latent]
  wqb             [q_latent, H * D]             a head ``[nope | rope]``
  wkva            [M, kv_latent + rope_width]   ``[c_kv | k_r]``; the norm
                                                kv_latent_norm on c_kv only
  wkvb            [kv_latent, H * (nope + D)]   a head ``[k_nope | v]``
  wo              [H * D, M]
Training computes every head's keys and values from the latent (no absorbed
form), so the core is ``attend`` at ``H`` / ``H`` heads of ``D``.

Three fields make the form Kimi-Linear takes (no query latent, no positions,
values narrower than keys):
  q_latent == 0   the queries come straight from the block's input: one leaf
                  ``wq`` ``[M, H * D]`` in place of ``wqa``, ``q_latent_norm``
                  and ``wqb``
  latent_rope     False: nothing is rotated; the ``rope_width`` channels are
                  there, the key's still one shared head, as projected
  value_width     a value's channels where they are not ``head_dim``:
                  ``wkvb``'s columns a head ``[k_nope | v]`` at ``nope +
                  value_width``, ``wo`` from ``H * value_width``. The core
                  then runs on q, k and v padded with zero channels to whole
                  lane tiles (:func:`padded_core`): scores and outputs are
                  exact, the kernels do ``2 * padded`` where the published
                  core does ``head_dim + value_width`` (ROADMAP B15)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.models._kinds import (BlockKind, Leaf, normal, ones,
                                       rmsnorm, rope, scaled)
from horovod_tpu.profiling import scopes


def _value_width(cfg) -> int:
    return cfg.value_width or cfg.head_dim


def _leaves(cfg):
    M, H, D = cfg.d_model, cfg.n_heads, cfg.head_dim
    nope, V = D - cfg.rope_width, _value_width(cfg)
    yield Leaf("ln1", (M,), ones)
    if cfg.q_latent:
        yield Leaf("wqa", (M, cfg.q_latent), normal())
        yield Leaf("q_latent_norm", (cfg.q_latent,), ones)
        yield Leaf("wqb", (cfg.q_latent, H * D), normal())
    else:
        yield Leaf("wq", (M, H * D), normal())
    yield Leaf("wkva", (M, cfg.kv_latent + cfg.rope_width), normal())
    yield Leaf("kv_latent_norm", (cfg.kv_latent,), ones)
    yield Leaf("wkvb", (cfg.kv_latent, H * (nope + V)), normal())
    yield Leaf("wo", (H * V, M), normal())


def _validate(cfg) -> None:
    if not (cfg.q_latent >= 0 and cfg.kv_latent > 0
            and 0 < cfg.rope_width < cfg.head_dim
            and (cfg.rope_width % 2 == 0 or not cfg.latent_rope)
            and _value_width(cfg) > 0):
        raise ValueError(
            f"layer_pattern has (\"latent\",) blocks and q_latent="
            f"{cfg.q_latent}, kv_latent={cfg.kv_latent}, rope_width="
            f"{cfg.rope_width} at head_dim={cfg.head_dim}, value_width="
            f"{cfg.value_width}: the keys' latent is wider than 0 (the "
            "queries' may be 0: none), the rope part is a share of a head, "
            "even where it is rotated, and a value has channels")
    if cfg.kv_heads != cfg.n_heads or cfg.qk_norm or cfg.post_norm:
        raise ValueError(
            "a (\"latent\",) block with n_kv_heads, qk_norm or post_norm: "
            "every head's key comes up from the one latent, whose norms are "
            "the latents' own")


def _down(p, h, cfg):
    """The two latents, normed, and the shared rope key as it is projected
    (no norm): ``(c_q [B, S, q_latent], c_kv [B, S, kv_latent], k_r [B, S,
    rope_width])``; without a query latent ``c_q`` is the block's normed
    input itself."""
    c_q = h
    if cfg.q_latent:
        c_q = rmsnorm(h @ p["wqa"].astype(h.dtype), p["q_latent_norm"],
                      cfg.norm_eps)
    c_kv, k_r = jnp.split(h @ p["wkva"].astype(h.dtype), [cfg.kv_latent],
                          axis=-1)
    return c_q, rmsnorm(c_kv, p["kv_latent_norm"], cfg.norm_eps), k_r


def _rotate(q, k_r, positions, cfg):
    """Positions onto the last ``rope_width`` channels of every query head
    ``[B, S, H, D]`` and onto the one key head ``k_r`` ``[B, S, 1,
    rope_width]``; the first ``D - rope_width`` channels carry none."""
    nope = q.shape[-1] - cfg.rope_width
    q = jnp.concatenate(
        [q[..., :nope], rope(q[..., nope:], positions, cfg.rope_theta)], -1)
    return q, rope(k_r, positions, cfg.rope_theta)


def _up(p, c_q, c_kv, k_r, positions, cfg):
    """Heads from the latents: q and k ``[B, S, H, D]`` with their rope
    parts rotated (``latent_rope``), the key's broadcast over the heads, and
    v ``[B, S, H, value width]``. ``wkvb``'s columns are a head's ``[k_nope
    | v]``: the two are taken as two products of the latent with the
    weight's two parts, so no ``[B, S, H, nope + D]`` activation is cut at
    a channel that is no multiple of the lanes."""
    B, S, _ = c_q.shape
    H, D = cfg.n_heads, cfg.head_dim
    nope = D - cfg.rope_width
    wq = p["wqb" if cfg.q_latent else "wq"]
    q = (c_q @ wq.astype(c_q.dtype)).reshape(B, S, H, D)
    wkvb = p["wkvb"].astype(c_kv.dtype).reshape(
        -1, H, nope + _value_width(cfg))
    k_nope = jnp.einsum("bsc,chd->bshd", c_kv, wkvb[..., :nope])
    v = jnp.einsum("bsc,chd->bshd", c_kv, wkvb[..., nope:])
    k_r = k_r[:, :, None, :]
    if cfg.latent_rope:
        q, k_r = _rotate(q, k_r, positions, cfg)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (B, S, H, cfg.rope_width))], -1)
    return q, k, v


def padded_core(q, k, v, scale: float):
    """Causal attention of q, k ``[B, S, H, D]`` on v ``[B, S, H, V]``, ``V``
    not ``D``: the three padded with zero channels to the next whole lane
    tile that holds both, ``attend`` as it is at that width, the output's
    first ``V`` channels. A zero channel adds nothing to a score and its
    output is zero, so scores and outputs are the unpadded core's; ``scale``
    is the unpadded head's (``attend`` would take the padded width's)."""
    from horovod_tpu.ops.pallas_attention import LANES, attend
    D, V = q.shape[-1], v.shape[-1]
    wide = -(-max(D, V) // LANES) * LANES

    def pad(x):
        return jnp.pad(x, ((0, 0),) * 3 + ((0, wide - x.shape[-1]),))
    return attend(pad(q), pad(k), pad(v), causal=True, scale=scale)[..., :V]


def _latent_block(p, x, positions, cfg):
    """``x + attention(norm(x))``, x ``[B', S', M]`` with the whole sequence
    and every head here. Scores are ``q . k / sqrt(head_dim)``, rope part
    and position-free part together."""
    from horovod_tpu.ops.pallas_attention import attend
    B, S, _ = x.shape
    with scopes.scope(scopes.ATTENTION):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        with scopes.scope(scopes.ATTENTION_LATENT):
            with scopes.scope(scopes.ATTENTION_LATENT_DOWN):
                c_q, c_kv, k_r = _down(p, h, cfg)
            with scopes.scope(scopes.ATTENTION_LATENT_UP):
                q, k, v = _up(p, c_q, c_kv, k_r, positions, cfg)
        with scopes.scope(scopes.ATTENTION_CORE), \
                scopes.scope(scopes.ATTENTION_CORE_FULL):
            if v.shape[-1] == q.shape[-1]:
                o = attend(q, k, v, causal=True, scale=cfg.attention_scale)
            else:
                o = padded_core(q, k, v, cfg.attention_scale
                                or q.shape[-1] ** -0.5)
        return x + scaled(o.reshape(B, S, -1) @ p["wo"].astype(x.dtype),
                          cfg.residual_scale)


KIND = BlockKind(
    length=1, leaves=_leaves, validate=_validate,
    apply=lambda p, x, positions, cfg, kind: (
        _latent_block(p, x, positions, cfg), None),
    checkpointed=True, refuses=("sp", "pp", "tp"),
    refusal="the one shared key (rotated or not) and the latents (the "
            "queries' where there is one) are whole on every device "
            "(no split of the heads that come up from them over tp), "
            "ring_attention_spmd has not run its keys, and no pipeline "
            "schedule has run it")
