"""Generative decode: the KV-cache forward of the plain dense GPT block over
the serving engine's paged pool, and the oracle the engine is held against.

The serving-side decode path (``horovod_tpu/serving/generate/``) runs the
SAME weights the training step of ``models/transformer.py`` produced, but at
token granularity: one fixed-shape decode step over a static slot array, with
K/V history in block-granular pages. A model of its own, single-device math
in fp32 (serving replicas are world_size=1; bitwise-stable greedy decode is
the parity contract ``tests/test_generate.py`` enforces); what it does not
implement of ``TransformerConfig`` every entry point refuses by name, and the
refusal is closed: a field outside ``_ALLOWED_FIELDS`` must be at its
default, a harmless one too (``moe_z_weight`` with no experts is refused).
  k_pages / v_pages  [L, total_pages + 1, page_tokens, H*Dh]
      (+1 = the scratch page inactive/padded lanes write into, so
      membership churn never changes the compiled shape)
  page_table         [slots, pages_per_slot] int32 — a slot's j-th
      page holds its token positions [j*page_tokens, (j+1)*page_tokens);
      gathered back, position p of a slot lands at flat index p.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models._kinds import rmsnorm, rope
from horovod_tpu.models.transformer import (TransformerConfig,
                                            _model_leaves, _row)

#: the fields of ``TransformerConfig`` a served config may set: the sizes and
#: constants the decode paths read (from the config or the tree's shapes),
#: and three they never read, which say how training stores and schedules the
#: same function (param_dtype, n_microbatches, remat). Every other field
#: is refused unless it is at its default, the plain dense GPT block
#: (multi-head attention over the whole causal history with rope on every
#: layer, a gelu FFN, pre-norms, the tied head): a field this module has
#: never heard of is refused without an edit here
_ALLOWED_FIELDS = (
    "vocab_size", "d_model", "n_heads", "n_layers", "d_ff", "max_seq",
    "head_width", "norm_eps", "rope_theta", "dtype", "param_dtype",
    "n_microbatches", "remat")
_PLAIN = TransformerConfig()


def _plain_gpt_only(cfg: TransformerConfig) -> None:
    """Refuse, by name, what the decode paths would silently compute
    otherwise: every entry point below calls it before anything else."""
    off = [field.name for field in dataclasses.fields(_PLAIN)
           if field.name not in _ALLOWED_FIELDS and field.name != "n_kv_heads"
           and getattr(cfg, field.name) != getattr(_PLAIN, field.name)]
    if cfg.kv_heads != cfg.n_heads:
        off.append("n_kv_heads")
    if off:
        raise NotImplementedError(
            f"paged decode does not implement {', '.join(off)}: its cache "
            "holds n_heads k/v heads of every position (a Mamba or delta-rule "
            "block's recurrent state is no page of keys, nor is a latent, "
            "rotated or not, or a short convolution's last positions), its "
            "layers attend to all of them with rope, and the block is the "
            "dense GPT one (pre-norms, a gelu FFN, the tied head, no scalar "
            "multipliers, one pass and one token a step)")


def kv_cache_spec(cfg: TransformerConfig) -> Tuple[int, int, Any]:
    """(n_layers, per-token K width, cache dtype) — the model
    fingerprint the page planner sizes pages from."""
    _plain_gpt_only(cfg)
    return cfg.n_layers, cfg.n_heads * cfg.head_dim, jnp.float32


def flatten_decode_params(params: Dict) -> Dict:
    """Collapse the stacked-stage layout ``[pp, L/pp, ...]`` to
    ``[L, ...]`` — decode scans all layers on one device; the pipeline
    split is a training-time concern. The tree comes from outside, beside
    its config: one that is not the default config's is refused as its
    config would have been."""
    layers = params["layers"]
    other = (set(params) ^ {"layers", *(leaf.name for leaf in
                                        _model_leaves(_PLAIN))}
             ) | (set(layers) ^ {leaf.name for leaf in _row(
                 _PLAIN.layer_pattern[0]).leaves(_PLAIN)})
    if other:
        raise NotImplementedError(
            f"paged decode supports the dense GPT block's tree, and this "
            f"one differs in {sorted(other)}: the tree of a config that "
            "sets one of n_experts, qk_norm, tie_embeddings, post_norm, "
            "ffn_gated, n_loops or another field ``kv_cache_spec`` refuses")
    flat = {k: jnp.asarray(v).reshape((-1,) + tuple(np.shape(v)[2:]))
            for k, v in layers.items()}
    return {"embed": jnp.asarray(params["embed"]),
            "ln_f": jnp.asarray(params["ln_f"]),
            "layers": flat}


def _rope_rows(x, pos, theta):
    """``_kinds.rope`` for one token a row, each at its own absolute
    position: x [N, H, D], pos [N]."""
    return rope(x[None], pos, theta)[0]


def _paged_stack(params, x, q_pos, k_pages, v_pages, dest_page, offs,
                 gather_rows, key_mask, cfg: TransformerConfig):
    """Every block over paged KV, then ``ln_f``: each writes this call's K/V
    into the pool, gathers the full history back, attends, FFN.

    x [N, M] (N = slots for decode, chunk for prefill) at positions
    ``q_pos``; ``dest_page``/``offs`` [N] address each row's write;
    ``gather_rows`` indexes the pages to read back ([N, P] per-row for
    decode, [P] shared for prefill); ``key_mask`` [N, T] marks the attended
    positions. Returns (x, k_pages, v_pages)."""
    H, Dh = cfg.n_heads, cfg.head_dim
    N = x.shape[0]
    # the gathered keys: [T, H, Dh] shared, or [N, T, H, Dh] a row
    keys = "thd" if gather_rows.ndim == 1 else "nthd"

    def layer(x, layer_p):
        lp, kp, vp = layer_p
        h = rmsnorm(x, lp["ln1"].astype(jnp.float32), cfg.norm_eps)
        q = _rope_rows((h @ lp["wq"].astype(jnp.float32)).reshape(N, H, Dh),
                       q_pos, cfg.rope_theta)
        k = _rope_rows((h @ lp["wk"].astype(jnp.float32)).reshape(N, H, Dh),
                       q_pos, cfg.rope_theta)
        v = (h @ lp["wv"].astype(jnp.float32))
        kp = kp.at[dest_page, offs].set(k.reshape(N, H * Dh))
        vp = vp.at[dest_page, offs].set(v)
        k_all = kp[gather_rows].reshape(gather_rows.shape[:-1] + (-1, H, Dh))
        v_all = vp[gather_rows].reshape(gather_rows.shape[:-1] + (-1, H, Dh))
        scores = jnp.einsum(f"nhd,{keys}->nht", q, k_all)
        scores = scores / np.sqrt(Dh).astype(np.float32)
        scores = jnp.where(key_mask[:, None, :], scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum(f"nht,{keys}->nhd", probs, v_all)
        x = x + o.reshape(N, H * Dh) @ lp["wo"].astype(jnp.float32)
        h2 = rmsnorm(x, lp["ln2"].astype(jnp.float32), cfg.norm_eps)
        f = jax.nn.gelu(h2 @ lp["w1"].astype(jnp.float32))
        return x + f @ lp["w2"].astype(jnp.float32), (kp, vp)

    x, (k_pages, v_pages) = lax.scan(
        layer, x, (params["layers"], k_pages, v_pages))
    x = rmsnorm(x, params["ln_f"].astype(jnp.float32), cfg.norm_eps)
    return x, k_pages, v_pages


def decode_step_paged(params: Dict, k_pages, v_pages, page_table,
                      lengths, last_token, active,
                      cfg: TransformerConfig):
    """ONE decode step for every slot at once — the function the engine
    jits exactly once, whatever joins or leaves between calls.

    Shapes (all static): page_table [S, P] int32, lengths/last_token
    [S] int32, active [S] bool.  Each active slot embeds its last
    token, appends its K/V at position ``lengths[s]``, attends over its
    own gathered history, and emits the greedy next token.  Inactive
    slots compute masked garbage into the scratch page — their lanes
    exist only to keep the shape constant.  Returns
    ``(next_token [S] int32, k_pages, v_pages)``."""
    _plain_gpt_only(cfg)
    pt = k_pages.shape[2]
    scratch = k_pages.shape[1] - 1
    emb = params["embed"].astype(jnp.float32)
    page_idx = jnp.clip(lengths // pt, 0, page_table.shape[1] - 1)
    dest = jnp.take_along_axis(page_table, page_idx[:, None], axis=1)[:, 0]
    dest = jnp.where(active, dest, scratch)
    T = page_table.shape[1] * pt
    key_mask = jnp.arange(T)[None, :] <= lengths[:, None]  # incl. new token
    x, k_pages, v_pages = _paged_stack(
        params, emb[last_token], lengths, k_pages, v_pages, dest,
        lengths % pt, page_table, key_mask, cfg)
    logits = x @ emb.T                                     # [S, V]
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), k_pages, v_pages


def prefill_chunk_paged(params: Dict, k_pages, v_pages, page_row,
                        tokens, pos0, valid, cfg: TransformerConfig):
    """Prefill ONE ``chunk``-token slice of ONE slot's prompt (fixed
    chunk shape — the last chunk arrives padded with ``valid`` marking
    the real tokens).  Writes the chunk's K/V into the slot's pages and
    returns the greedy next token after the last VALID position — the
    first generated token once the final chunk lands.  Returns
    ``(next_token scalar int32, k_pages, v_pages)``."""
    _plain_gpt_only(cfg)
    C = tokens.shape[0]
    pt = k_pages.shape[2]
    scratch = k_pages.shape[1] - 1
    emb = params["embed"].astype(jnp.float32)
    pos = pos0 + jnp.arange(C, dtype=jnp.int32)
    live = jnp.arange(C) < valid
    dest = jnp.where(live,
                     page_row[jnp.clip(pos // pt, 0,
                                       page_row.shape[0] - 1)],
                     scratch)
    T = page_row.shape[0] * pt
    # causal within the chunk AND over every earlier chunk's positions
    key_mask = jnp.arange(T)[None, :] <= pos[:, None]
    x, k_pages, v_pages = _paged_stack(
        params, emb[tokens], pos, k_pages, v_pages, dest, pos % pt,
        page_row, key_mask, cfg)
    x_last = x[jnp.clip(valid - 1, 0, C - 1)]
    logits = x_last @ emb.T                                # [V]
    return jnp.argmax(logits).astype(jnp.int32), k_pages, v_pages


def reference_greedy_decode(params: Dict, cfg: TransformerConfig,
                            prompt, max_new: int) -> list:
    """Sequential non-paged oracle: recompute full-history attention
    for every emitted token (no cache, no paging, no batching).  Slow
    on purpose — this is the ground truth the paged continuous engine
    must match token-for-token (tests/test_generate.py)."""
    _plain_gpt_only(cfg)
    flat = flatten_decode_params(params)
    H, Dh, L = cfg.n_heads, cfg.head_dim, cfg.n_layers
    toks = [int(t) for t in np.asarray(prompt).reshape(-1)]
    out = []
    for _ in range(int(max_new)):
        ids = jnp.asarray(toks, dtype=jnp.int32)
        Tn = ids.shape[0]
        emb = flat["embed"].astype(jnp.float32)
        x = emb[ids]
        pos = jnp.arange(Tn, dtype=jnp.int32)
        for li in range(L):
            lp = {k: v[li] for k, v in flat["layers"].items()}
            h = rmsnorm(x, lp["ln1"].astype(jnp.float32), cfg.norm_eps)
            q = _rope_rows((h @ lp["wq"].astype(jnp.float32))
                           .reshape(Tn, H, Dh), pos, cfg.rope_theta)
            k = _rope_rows((h @ lp["wk"].astype(jnp.float32))
                           .reshape(Tn, H, Dh), pos, cfg.rope_theta)
            v = (h @ lp["wv"].astype(jnp.float32)).reshape(Tn, H, Dh)
            scores = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(Dh)
            mask = pos[None, :] <= pos[:, None]
            scores = jnp.where(mask[None, :, :], scores, -1e30)
            probs = jax.nn.softmax(scores, axis=-1)
            o = jnp.einsum("hqk,khd->qhd", probs, v).reshape(Tn, H * Dh)
            x = x + o @ lp["wo"].astype(jnp.float32)
            h2 = rmsnorm(x, lp["ln2"].astype(jnp.float32), cfg.norm_eps)
            f = jax.nn.gelu(h2 @ lp["w1"].astype(jnp.float32))
            x = x + f @ lp["w2"].astype(jnp.float32)
        x = rmsnorm(x, flat["ln_f"].astype(jnp.float32), cfg.norm_eps)
        nxt = int(jnp.argmax(x[-1] @ emb.T))
        out.append(nxt)
        toks.append(nxt)
    return out
