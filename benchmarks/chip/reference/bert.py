"""Plain reference for the ``bert`` adapter: BERT's forward pass and
pretraining loss from the published equations (Devlin et al. 2018,
google-research/bert modeling.py and run_pretraining.py), in jax.numpy,
float32, matmuls at "highest" precision. Imports nothing of the program.

It reads the program's parameter tree by the program's names (the arrays
are shared, not copied) and follows the departures the configuration
file states: no dropout, no pooler, no decoder bias, LayerNorm eps 1e-6,
gelu in the tanh form (which is also modeling.py's).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from trees import get_leaves, with_leaves

#: How far the program may be from this reference, and why.
#: The program multiplies in bfloat16 (8 bits of mantissa, relative
#: rounding 2^-9 = 2e-3 an operand), accumulates in float32 and rounds its
#: logits to bfloat16; the reference is float32 throughout. Measured on
#: the chip at BERT-Large widths over 31 runs, each another seed (PERF.md
#: section 6, PR 23): the loss differs by 4e-6 to 2.0e-3 relative (two
#: sequences hold ~40 masked positions, so the rounding of their logits
#: does not average out), a gradient leaf by 1.0-3.2% of its L2 norm. The
#: bounds sit 5 and 3 times above the worst seen, because a false alarm
#: costs a whole run. A dropped loss term moves the loss by 0.69 of ~11
#: (6e-2); a dropped bias, a wrong scale or a bfloat16 accumulation over
#: 1024-4096 terms moves gradients by tens of percent: all fail.
TOLERANCE = {"loss_rel": 1e-2, "grad_rel_l2": 1e-1}

LN_EPS = 1e-6


def _layer_norm(x, p):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _xent(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return lse - picked


def layer(x, p, mask):
    a = p["attention"]
    q, k, v = (jnp.einsum("bsh,hnd->bsnd", x, a[n]["kernel"])
               + a[n]["bias"] for n in ("query", "key", "value"))
    s = jnp.einsum("bqnd,bknd->bnqk", q, k) / math.sqrt(q.shape[-1])
    s = jnp.where(mask, s, -1e9)
    o = jnp.einsum("bnqk,bknd->bqnd", jax.nn.softmax(s, -1), v)
    o = jnp.einsum("bqnd,ndh->bqh", o, a["out"]["kernel"]) + a["out"]["bias"]
    x = _layer_norm(x + o, p["ln_att"])
    h = _gelu(x @ p["ffn_in"]["kernel"] + p["ffn_in"]["bias"])
    h = h @ p["ffn_out"]["kernel"] + p["ffn_out"]["bias"]
    return _layer_norm(x + h, p["ln_ffn"])


def encoder(params, batch, n_layers: int):
    ids = batch["input_ids"]
    x = (params["word_embeddings"]["embedding"][ids]
         + params["position_embeddings"]["embedding"][
             jnp.arange(ids.shape[1])][None]
         + params["token_type_embeddings"]["embedding"][
             batch["token_type_ids"]])
    x = _layer_norm(x, params["ln_emb"])
    mask = batch["attention_mask"][:, None, None, :]
    # the layers are alike, so one traced layer is scanned over their
    # stacked weights: the same arithmetic as a loop, a 24th of the
    # program to compile and to read back from the cache
    stacked = jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs),
        *(params[f"layer_{i}"] for i in range(n_layers)))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p, mask), None), x, stacked)
    return x


def mlm_loss(params, x, batch):
    t = params["mlm_transform"]
    h = _layer_norm(_gelu(x @ t["kernel"] + t["bias"]), params["mlm_ln"])
    logits = h @ params["word_embeddings"]["embedding"].T
    w = batch["mlm_mask"]
    return jnp.sum(_xent(logits, batch["mlm_labels"]) * w) \
        / jnp.maximum(jnp.sum(w), 1.0)


def nsp_loss(params, x, batch):
    logits = x[:, 0] @ params["nsp"]["kernel"] + params["nsp"]["bias"]
    return jnp.mean(_xent(logits, batch["nsp_labels"]))


def loss(params, batch, n_layers: int):
    x = encoder(params, batch, n_layers)
    return mlm_loss(params, x, batch) + nsp_loss(params, x, batch)


def loss_and_grads(params, leaf_specs: dict, batch, sizes: dict):
    """Loss, and its gradients by ``jax.grad`` over the named leaves
    only."""
    n_layers = sizes["layers"]

    @jax.jit
    def fn(leaves, params, batch):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda lv: loss(with_leaves(params, leaf_specs, lv),
                                batch, n_layers))(leaves)
    return fn(get_leaves(params, leaf_specs), params, batch)
