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
form), so the core is ``attend`` at ``H`` / ``H`` heads of ``D``: a value is
as wide as q . k here (``v_head_dim == head_dim``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from horovod_tpu.models._kinds import (BlockKind, Leaf, normal, ones,
                                       rmsnorm, rope, scaled)
from horovod_tpu.profiling import scopes


def _leaves(cfg):
    M, H, D = cfg.d_model, cfg.n_heads, cfg.head_dim
    nope = D - cfg.rope_width
    yield Leaf("ln1", (M,), ones)
    yield Leaf("wqa", (M, cfg.q_latent), normal())
    yield Leaf("q_latent_norm", (cfg.q_latent,), ones)
    yield Leaf("wqb", (cfg.q_latent, H * D), normal())
    yield Leaf("wkva", (M, cfg.kv_latent + cfg.rope_width), normal())
    yield Leaf("kv_latent_norm", (cfg.kv_latent,), ones)
    yield Leaf("wkvb", (cfg.kv_latent, H * (nope + D)), normal())
    yield Leaf("wo", (H * D, M), normal())


def _validate(cfg) -> None:
    if not (cfg.q_latent > 0 and cfg.kv_latent > 0
            and 0 < cfg.rope_width < cfg.head_dim
            and cfg.rope_width % 2 == 0):
        raise ValueError(
            f"layer_pattern has (\"latent\",) blocks and q_latent="
            f"{cfg.q_latent}, kv_latent={cfg.kv_latent}, rope_width="
            f"{cfg.rope_width} at head_dim={cfg.head_dim}: both latents "
            "are wider than 0 and the rope part is an even share of a head")
    if cfg.kv_heads != cfg.n_heads or cfg.qk_norm or cfg.post_norm:
        raise ValueError(
            "a (\"latent\",) block with n_kv_heads, qk_norm or post_norm: "
            "every head's key comes up from the one latent, whose norms are "
            "the latents' own")


def _down(p, h, cfg):
    """The two latents, normed, and the shared rope key as it is projected
    (no norm): ``(c_q [B, S, q_latent], c_kv [B, S, kv_latent], k_r [B, S,
    rope_width])``."""
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
    parts rotated, the key's broadcast over the heads, and v ``[B, S, H,
    D]``. ``wkvb``'s columns are a head's ``[k_nope | v]``: the two are
    taken as two products of the latent with the weight's two parts, so no
    ``[B, S, H, nope + D]`` activation is cut at a channel that is no
    multiple of the lanes."""
    B, S, _ = c_q.shape
    H, D = cfg.n_heads, cfg.head_dim
    nope = D - cfg.rope_width
    q = (c_q @ p["wqb"].astype(c_q.dtype)).reshape(B, S, H, D)
    wkvb = p["wkvb"].astype(c_kv.dtype).reshape(-1, H, nope + D)
    k_nope = jnp.einsum("bsc,chd->bshd", c_kv, wkvb[..., :nope])
    v = jnp.einsum("bsc,chd->bshd", c_kv, wkvb[..., nope:])
    q, k_r = _rotate(q, k_r[:, :, None, :], positions, cfg)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_r, (B, S, H, cfg.rope_width))], -1)
    return q, k, v


def _latent_block(p, x, positions, cfg):
    """``x + attention(norm(x))``, x ``[B', S', M]`` with the whole sequence
    and every head here. Scores are ``q . k / sqrt(head_dim)``, rope part
    and position-free part together."""
    from horovod_tpu.ops.pallas_attention import attend
    B, S, _ = x.shape
    with jax.named_scope(scopes.ATTENTION):
        h = rmsnorm(x, p["ln1"], cfg.norm_eps)
        with jax.named_scope(scopes.ATTENTION_LATENT):
            with jax.named_scope(scopes.ATTENTION_LATENT_DOWN):
                c_q, c_kv, k_r = _down(p, h, cfg)
            with jax.named_scope(scopes.ATTENTION_LATENT_UP):
                q, k, v = _up(p, c_q, c_kv, k_r, positions, cfg)
        with jax.named_scope(scopes.ATTENTION_CORE), \
                jax.named_scope(scopes.ATTENTION_CORE_FULL):
            o = attend(q, k, v, causal=True, scale=cfg.attention_scale)
        return x + scaled(o.reshape(B, S, -1) @ p["wo"].astype(x.dtype),
                          cfg.residual_scale)


KIND = BlockKind(
    length=1, leaves=_leaves, validate=_validate,
    apply=lambda p, x, positions, cfg, kind: (
        _latent_block(p, x, positions, cfg), None),
    checkpointed=True, refuses=("sp", "pp", "tp"),
    refusal="the one rope key and both latents are whole on every device "
            "(no split of the heads that come up from them over tp), "
            "ring_attention_spmd has not run its keys, and no pipeline "
            "schedule has run it")
