"""The quickest proof that the trainer still starts on the chip.

    python chip_smoke.py              # one TPU chip (what the driver runs)
    python chip_smoke.py --chips 4    # one host's four chips, that path only
    python chip_smoke.py --rehearse   # off-chip walk of every phase, tiny

One process, the only one that touches JAX (a chip belongs to one process).
Each phase prints its own JSON lines; the last stdout line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and is printed only after every phase passed on a TPU. Without a TPU the
script says why on stderr and exits 2 without training and without that
line. ``--rehearse`` is the off-chip rehearsal: the same phases at tiny
sizes with interpret-mode kernels; every line it prints says
``"rehearsal": true``, it ends ``"ok": false`` and exits 3. A failed check
raises :class:`SmokeFailure`; nothing on the path catches it.

Phases (default, one chip):
  device   jax.devices(); platform must be tpu.
  train    BERT-Large at published widths (24 layers, hidden 1024, 16 heads,
           FFN 4096, vocab 30522, bf16), batch 64 x seq 128, optax.adamw,
           through hvd.init / hvd.build_mesh / init_bert /
           make_bert_train_step: one compiling step + 30 steps on a fixed
           batch; every loss finite, the last below the first, no compile
           after the first step. (adamw at 1e-4 with no warmup overshoots
           for its first ~5 steps on this model, on the CPU in float32 as
           on the chip; after that the loss falls steadily, so the run is
           long enough to see past the transient.)
           The line's attention_path says how the attention core ran
           (at 128 x 128 scores XLA's: the block kernels do not win there).
  kernels  every Pallas kernel of the main path, compiled (tpu_custom_call),
           against its XLA reference at real widths (the block attention
           kernels at the shapes of both BERT cells, with padded keys and
           a row of nothing else; the flash kernel's gradients through
           hvd_flash_bwd and, where a head is whole lane tiles,
           hvd_flash_adj, which makes its adj rows; o, lse and the gradients
           again at the four causal
           cells' own shapes, the latent cell's 20 heads of 256 and the share
           cell's window and seven query heads a key/value head among them,
           attention_path saying the forward's form (operands in place or
           heads first), the blocks a tile on the diagonal or on a band's
           edge runs, what the band leaves of a head's tiles, the group and
           who made adj,
           with the blocks the latent cell's step checkpoints); the sparse
           core's three kernels (hvd_sparse_fwd, hvd_sparse_mean,
           hvd_sparse_bwd) at keye-vl-2.0-30b-a3b.s16384's shape whole, a
           seeded selection of 2048 keys a query, against the jax.numpy
           sums a block of 128 queries at a time (sparse_path names the
           form the program takes there), and the index score pass's
           backward (hvd_index_bwd) there, 16 index heads of 64 under the
           selection of their own scores and a seeded target, against
           autodiff of the jax.numpy expression at "highest"; then two
           steps of the flagship transformer at
           head_dim 128 with the four kernels asserted in the compiled
           program. xent_path says how the LM loss ran (the
           kernel's rows and chunk and its grid steps, or why XLA).
           The untied embedding's lookup and hand-written gradient at two
           cells' tables, Zipf ids, against float64 sums.
           The Mamba-2 scan on its kernels (hvd_ssm_scan, hvd_ssm_scan_bwd)
           at the hybrid cell's heads against the recurrence one position
           at a time and against the jax.numpy form; ssm_path says the
           kernels' tiles, the chunk count and what the backward pass
           keeps. The block's gate and grouped norm (a group's sum through
           a 0/1 matrix at Precision.HIGHEST, no axis for the groups)
           against the same with the groups on an axis of their own, result
           and gradients in float32.
           The kernels at the dense hybrid cell's shapes
           (granite-4.0-h-micro.s4096): the scan at ONE group of 64 heads
           and chunk 256 in head tiles, the flash pair at 32 / 8 heads of 64
           (heads first) with the scores times 1/64, each against its
           jax.numpy form; ssm_scan_path and attention_path at the cell's
           shapes must name the kernels.

``--chips 4`` runs only the four-chip phase and what it is compared with:
BERT-Large dp=4 against one device at 2 x 512 tokens a chip (the block
attention kernels on each chip's own rows: both in the compiled step, no
all-gather), the two n=4 layouts of
``__graft_entry__`` against one device / a 4-virtual-device CPU mesh, and
ring attention (flash kernel per step) over sp=4 against plain attention.

The printed seconds and bytes are smoke prints, not benchmark metrics.

Tolerances (all stated here, none tuned per run):
  flash attention   max|got-ref| / max|ref| <= 2e-2 (bf16 outputs and
                    grads; the reference runs at "highest" precision);
                    the block attention kernels and the sparse core's
                    three (o, lse, the heads' mean attention, dq, dk, dv)
                    the same: at the cell's shape they read 5e-8 (the
                    mean), 2e-5 (lse) and 3e-3 to 5e-3 (PERF.md, PR 65)
  fused xent        loss (f32) abs <= 2e-3 on values ~ log(vocab);
                    dlogits (bf16), and dx / dw through the head form,
                    normalized <= 1e-2
  mamba-2 scan      bf16 operands, float32 decays and state, against the
                    float32 recurrence: max|got-ref| / max|ref| <= 2e-2,
                    forward and every operand's gradient
  embedding         the untied table's gradient (float32 sums of bf16 rows)
                    against float64 sums on the host, normalized <= 1e-5;
                    the rows taken bit for bit the cast table's
  int8 codec        scales rtol 1e-6; codes within +-1 (a division that
                    lands on a rounding boundary), <= 0.1% of them off;
                    residual equal to x - codes*scale of the kernel's own
                    codes; dequantize / fused sgd / fused adam rtol 1e-4
  losses across meshes  BERT-Large bf16, dp=4 vs one device: rel 1e-2 on each
                    of 3 steps (the all-reduce changes the summation order);
                    flagship f32 layouts: abs 2e-2 on a loss ~ 5.5 (TPU f32
                    matmuls run as bf16 passes at default precision).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import statistics
import sys
import time

FLASH_TOL = 2e-2
# bf16 rows and weights against a float32 "highest" reference, as FLASH_TOL
GMM_TOL = 2e-2
# the chunked scan's bf16 matmul operands against a float32 recurrence
SSM_TOL = 2e-2
XENT_LOSS_ATOL = 2e-3
XENT_GRAD_TOL = 1e-2
CODEC_RTOL = 1e-4
CODE_MISMATCH_MAX = 1e-3
BERT_MESH_RTOL = 1e-2
#: float32 sums of bf16 rows against float64 sums, max|got - ref| / max|ref|
#: (the bf16 sums of the tied form read 4e-3 to 1e-2 on the same ids)
EMBED_GRAD_TOL = 1e-5
BLOCK_KERNELS = ("hvd_block_attention", "hvd_block_attention_bwd")
TRAIN_STEPS = 30
LAYOUT_ATOL = 2e-2
#: a gradient leaf's relative L2 distance between two layouts, float32 at
#: matmul precision "highest" (measured on four chips: 1.4e-6). At the
#: chip's default precision a float32 matmul is one bfloat16 pass, the
#: layouts round differently, a few of the 256 tokens' near-tied router
#: choices flip, and the same leaves are 7-11 % apart (PERF.md section 6,
#: PR 26): that would hide the fault this check is for.
LAYOUT_GRAD_RTOL = 1e-3


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What a phase runs at: the real widths, or the rehearsal's."""
    bert: dict            # BertConfig overrides ({} = bert_large())
    bert_batch: int
    bert_seq: int
    bert4: tuple          # four-chip BERT (global batch, seq)
    attn: tuple           # flash check q/k/v [B, S, H, D]
    cells: tuple          # flash checks at the causal cells' shapes, each
    #                       (B, S, H, k/v heads, D, window)
    block: tuple          # block attention checks, each [B, S, H, D]
    xent: tuple           # fused xent check [rows, vocab]
    blocks: tuple         # codec check [n_blocks, block]
    gmm: tuple            # grouped matmul checks, each (rows, in, out,
    #                       groups, rows in the groups)
    embed: tuple          # embedding checks, each (vocab, width, tokens)
    ssm: tuple            # Mamba-2 scan check (S, heads, head width, groups,
    #                       state, chunk)
    dense_ssm: tuple      # the same at ONE group in head tiles
    sparse: tuple         # sparse core check (S, H, k/v heads, D, topk, k
    #                       tile, index heads, their width)
    delta: tuple          # delta-rule scan check (S, heads, head width,
    #                       value width, chunk)
    narrow: tuple         # flash check at a head of 64 with grouped heads
    #                       and a scale of its own: (B, S, H, k/v heads, D,
    #                       window, scale)
    short_conv: tuple     # gated short-convolution mixer check (B, S, M,
    #                       taps)
    gpt: dict             # flagship TransformerConfig fields
    gpt_batch: int
    ring: tuple           # four-chip ring attention [B, S, H, D]


REAL = Sizes(
    bert={}, bert_batch=64, bert_seq=128,
    # 512 positions: the shape at which attend picks the block kernels
    bert4=(8, 512),
    attn=(8, 2048, 8, 128),
    # the attention core of glm-4.7-flash.s8192, ouro-2.6b.s4096,
    # smallthinker-21b-a3b.s8192 (a full layer and a window layer: seven
    # query heads a k/v head, a window of four 1024-tiles under eight) and
    # gpt-1.3b-widths.s2048, whole: the float32 reference goes a query head
    # at a time (a head's scores at 8192 keys are 268 MB); then
    # laguna-xs.2.s8192's two shapes on the same eight k/v heads: a window
    # layer (64 query heads, groups of 8, a window of 512 under 1024 x 1024
    # tiles: every live tile whole under its mask) and a full layer (48:
    # groups of 6, no power of two)
    cells=((1, 8192, 20, 20, 256, None), (1, 4096, 16, 16, 128, None),
           (1, 8192, 28, 4, 128, None), (1, 8192, 28, 4, 128, 4096),
           (2, 2048, 16, 16, 128, None),
           (1, 8192, 64, 8, 128, 512), (1, 8192, 48, 8, 128, None)),
    # the attention core of bert-large.s128 and bert-large.s512
    block=((64, 128, 16, 64), (8, 512, 16, 64)),
    xent=(16384, 32000), blocks=(8192, 256),
    # OLMoE's widths; the hybrid cell's way up, whose 1856 columns are no
    # multiple of 128 lanes: the weights read as the chip stores them
    gmm=((16384, 2048, 1024, 64, 12288), (49152, 2688, 1856, 8, 12288)),
    # the tables of smallthinker-21b-a3b.s8192 and of olmoe-1b-7b.s4096
    embed=((37984, 2560, 8192), (50304, 2048, 8192)),
    # nemotron-3-nano-30b-a3b.s8192's heads, an eighth of its length
    ssm=(1024, 64, 64, 8, 128, 128),
    # granite-4.0-h-micro.s4096's heads in ONE group at chunk 256, a quarter
    # of its length; and its attention block whole: 32 / 8 heads of 64 over
    # 4096 keys, scores times 1/64 (the float32 reference's scores are 2.1
    # GB)
    dense_ssm=(1024, 64, 64, 1, 128, 256),
    narrow=(1, 4096, 32, 8, 64, None, 1 / 64),
    # keye-vl-2.0-30b-a3b.s16384's sparse core whole: one sequence, 32 / 4
    # heads of 128, 2048 of a query's causal keys, k tiles of 1024, 16 index
    # heads of 64
    sparse=(16384, 32, 4, 128, 2048, 1024, 16, 64),
    # kimi-linear-48b-a3b.s8192's scan whole: one sequence, 32 heads of 128,
    # chunks of 64
    delta=(8192, 32, 128, 128, 64),
    # lfm2-24b-a2b.s8192's mixer at the cell's batch, whole
    short_conv=(2, 8192, 2048, 3),
    # depth cut to 4 layers: this phase checks kernels in place, not a model
    gpt=dict(vocab_size=32000, d_model=1024, n_heads=8, n_layers=4,
             d_ff=4096, max_seq=2048),
    gpt_batch=8, ring=(2, 2048, 4, 128))
TINY = Sizes(
    bert=dict(vocab_size=512, hidden_size=64, num_layers=2, num_heads=4,
              intermediate_size=128, max_position=64),
    bert_batch=8, bert_seq=16, bert4=(8, 16),
    attn=(1, 256, 2, 128),
    cells=((1, 256, 2, 2, 256, None), (1, 512, 4, 2, 128, None),
           (1, 512, 4, 2, 128, 256), (2, 256, 2, 2, 128, None),
           (1, 512, 6, 1, 128, 128)),
    block=((2, 128, 2, 64),),
    xent=(256, 1000), blocks=(64, 128),
    gmm=((256, 128, 128, 4, 192), (256, 128, 192, 4, 192)),
    embed=((64, 2560, 48),),
    ssm=(64, 4, 8, 2, 16, 16),
    dense_ssm=(64, 8, 8, 1, 16, 16), narrow=(1, 256, 4, 1, 64, None, 1 / 64),
    sparse=(512, 4, 2, 128, 48, 256, 4, 64),
    delta=(64, 2, 128, 128, 32),
    short_conv=(2, 64, 32, 3),
    gpt=dict(vocab_size=1000, d_model=256, n_heads=2, n_layers=2,
             d_ff=256, max_seq=256),
    gpt_batch=2, ring=(1, 512, 2, 128))


class Smoke:
    """One run: the parsed options, the sizes, and the line printer."""

    def __init__(self, args):
        self.chips = args.chips
        self.seed = args.seed
        self.rehearsal = args.rehearse
        self.sizes = TINY if args.rehearse else REAL

    @property
    def on_chip(self) -> bool:
        return not self.rehearsal

    def emit(self, phase: str, **fields) -> None:
        doc = {"phase": phase, **fields}
        if self.rehearsal:
            doc["rehearsal"] = True
        print(json.dumps(doc), flush=True)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def phase_device(smoke: Smoke) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs)}
    if smoke.rehearsal:
        check(d.platform != "tpu", "--rehearse is the off-chip rehearsal; "
              "run without it on a machine that has the chip")
    elif d.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (devices: {devs}); this script "
              "never trains on the CPU. Run it on a machine with the chip, "
              "or rehearse off-chip with --rehearse.", file=sys.stderr)
        sys.exit(2)
    check(len(devs) == smoke.chips,
          f"expected {smoke.chips} device(s), JAX reports {len(devs)}")
    smoke.emit("device", **device,
               compile_cache=jax.config.jax_compilation_cache_dir)
    return device


# ---------------------------------------------------------------------------
# train: BERT-Large through the normal entry points
# ---------------------------------------------------------------------------

def _bert_setup(hvd, mesh, smoke: Smoke, shape=None):
    """The construction of benchmarks/chip/adapters/bert.py and
    examples/jax/bert_pretrain_synthetic.py --large, scan_steps=1, at
    ``shape`` = (batch, seq) or the train phase's."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    import optax
    from horovod_tpu.models import init_opt_state
    from horovod_tpu.models.bert import (Bert, bert_large, init_bert,
                                         make_bert_train_step)

    z = smoke.sizes
    cfg = dataclasses.replace(bert_large(), **z.bert)
    model = Bert(cfg)
    B, S = shape or (z.bert_batch, z.bert_seq)
    params = init_bert(model, jax.random.PRNGKey(smoke.seed), S, mesh)
    tx = optax.adamw(1e-4)
    opt_state = init_opt_state(tx, params, mesh)
    step = make_bert_train_step(model, tx, mesh, scan_steps=1)

    rng = np.random.RandomState(smoke.seed)
    sh = hvd.batch_sharding(mesh)

    def put(x, dtype):
        return jax.device_put(jnp.asarray(x, dtype), sh)

    batch = {
        "input_ids": put(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "token_type_ids": put(np.zeros((B, S)), jnp.int32),
        "attention_mask": put(np.ones((B, S)), bool),
        "mlm_labels": put(rng.randint(0, cfg.vocab_size, (B, S)), jnp.int32),
        "mlm_mask": put(rng.rand(B, S) < 0.15, jnp.float32),
        "nsp_labels": put(rng.randint(0, 2, (B,)), jnp.int32),
    }
    return cfg, step, params, opt_state, batch


def _persistent_cache_events() -> dict:
    """Live counts of JAX's persistent-compilation-cache hits and misses
    from here on (the backend-compile event fires for both)."""
    import jax.monitoring
    counts = {"hits": 0, "misses": 0}

    def listener(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    jax.monitoring.register_event_listener(listener)
    return counts


def _memory(device) -> dict:
    """What the runtime reports for live arrays on the device (None off
    the chip). A program's temporaries are not in it: see _step_bytes."""
    stats = device.memory_stats() or {}
    return {k: stats.get(k) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _step_bytes(mem) -> dict:
    """Per-device bytes of one compiled step, from memory_analysis():
    arguments + outputs - aliased (donated) + temporaries."""
    doc = {k: getattr(mem, f"{k}_size_in_bytes")
           for k in ("argument", "output", "alias", "temp")}
    doc["total"] = (doc["argument"] + doc["output"] - doc["alias"]
                    + doc["temp"])
    return doc


def phase_train(smoke: Smoke, hvd) -> None:
    import math
    import jax
    from horovod_tpu.profiling import compile_watch

    mesh = hvd.build_mesh(dp=-1)
    cfg, step, params, opt_state, batch = _bert_setup(hvd, mesh, smoke)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    z = smoke.sizes
    # hvd.init() installed the watcher; without it (HVD_TPU_COMPILE_METRICS=0)
    # the zero-compiles check below would pass on empty counters
    check(compile_watch.ensure_installed(), "compile metrics are disabled")

    # the watcher's totals feed hvd_compile_total / _cache_miss_total:
    # backend compiles (a read from the persistent cache counts and is
    # timed as one), tracing-cache misses, compile seconds
    cache = _persistent_cache_events()
    before = compile_watch.totals()
    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, batch)
    losses = [float(loss)]
    first_step_s = time.perf_counter() - t0
    after_first = compile_watch.totals()
    compile_s = after_first["seconds_total"] - before["seconds_total"]
    cache_hit = cache["hits"] > 0 and cache["misses"] == 0

    step_s, dispatch_s, readback_s = [], [], []
    for _ in range(TRAIN_STEPS):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, batch)
        t_dispatch = time.perf_counter()
        jax.block_until_ready(loss)
        t_ready = time.perf_counter()
        losses.append(float(loss))
        t_read = time.perf_counter()
        dispatch_s.append(t_dispatch - t0)
        step_s.append(t_ready - t0)
        readback_s.append(t_read - t_ready)
    at_end = compile_watch.totals()

    check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    check(losses[-1] < losses[0],
          f"loss did not fall on a fixed batch: {losses}")
    check(at_end == after_first,
          f"compiled again after the first step: {after_first} -> {at_end}")
    # if block_until_ready returned before the step ended, the readback
    # that follows it would carry the step's time
    med_step, med_read = (statistics.median(step_s),
                          statistics.median(readback_s))
    ready_waits = med_read < 0.05 * med_step
    if smoke.on_chip:
        check(ready_waits, "jax.block_until_ready(loss) returned before the "
              f"step ended: float(loss) then took {med_read:.4f}s of a "
              f"{med_step:.4f}s step")

    # after the compile check: lowering again traces again. The step's
    # own temporaries are in this analysis, not in memory_stats()
    compiled = step.lower(params, opt_state, batch).compile()
    mem = compiled.memory_analysis()
    heads = cfg.num_heads
    path = _attention_path((z.bert_batch, z.bert_seq, heads,
                            cfg.hidden_size // heads), causal=False,
                           masked=True)
    d = jax.devices()[0]
    smoke.emit(
        "train", model="bert_large" if not z.bert else "bert_tiny",
        layers=cfg.num_layers, hidden=cfg.hidden_size, heads=cfg.num_heads,
        ffn=cfg.intermediate_size, vocab=cfg.vocab_size,
        dtype=str(cfg.dtype.__name__), n_params=n_params,
        batch=z.bert_batch, seq=z.bert_seq, optimizer="optax.adamw(1e-4)",
        attention_path=path,
        device_kind=d.device_kind,
        first_step_seconds=round(first_step_s, 3),
        compile_or_cache_read_seconds=round(compile_s, 3),
        step_program="read from the persistent cache (warm)" if cache_hit
        else "compiled (cold)",
        losses=[round(x, 4) for x in losses],
        compiles_after_first_step=0,
        median_step_seconds=med_step,
        median_dispatch_seconds=statistics.median(dispatch_s),
        median_float_after_ready_seconds=med_read,
        block_until_ready_waits_for_the_step=ready_waits,
        compiled_step_bytes=_step_bytes(mem), hbm_live_arrays=_memory(d))


# ---------------------------------------------------------------------------
# kernels: compiled Pallas against the XLA reference of the same file
# ---------------------------------------------------------------------------

def _flash_bwd_kernels(head_dim: int) -> tuple:
    """The flash backward's calls at heads of ``head_dim``: the kernel and,
    where a head is whole lane tiles, the one that makes the ``adj`` rows
    it reads from do and o (a head of 64 keeps the ``jax.numpy`` sums)."""
    from horovod_tpu.ops import pallas_attention as pa
    return ("hvd_flash_bwd",) + (
        () if head_dim % pa.MIN_BLOCK else (pa.ADJ_NAME,))


def _has_kernel(compiled, kernel: str) -> bool:
    """Whether the compiled program holds a tpu_custom_call instruction
    named after ``kernel`` (the pallas_call's ``name``)."""
    return any(kernel in line for line in compiled.as_text().splitlines()
               if 'custom_call_target="tpu_custom_call"' in line)


def _reference_attention(q, k, v):
    """Plain causal attention at "highest" matmul precision."""
    import jax
    from horovod_tpu.parallel.ring_attention import _plain_attention
    with jax.default_matmul_precision("highest"):
        return _plain_attention(q, k, v, True)


def _weighted_sum(attn, w):
    """Scalar loss of an attention function with a fixed cotangent ``w``."""
    import jax.numpy as jnp
    return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)


def _run_compiled(smoke: Smoke, fn, args, kernel):
    """Compile ``fn`` for the attached device, require the named kernel
    (or kernels; none if none is named) as a tpu_custom_call in the program
    (on the chip), and run that program."""
    import jax
    compiled = jax.jit(fn).lower(*args).compile()
    names = (kernel,) if isinstance(kernel, str) else tuple(kernel or ())
    for name in names if smoke.on_chip else ():
        check(_has_kernel(compiled, name),
              f"{name}: no such tpu_custom_call in the compiled program")
    return compiled(*args)


def _rel_err(got, want) -> float:
    """max|got - want| / max|want|, computed on the device."""
    import jax.numpy as jnp
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    check(bool(jnp.all(jnp.isfinite(g))), "non-finite kernel output")
    return float(jnp.max(jnp.abs(g - w)) / jnp.maximum(
        jnp.max(jnp.abs(w)), 1e-30))


def _kernel_line(smoke: Smoke, kernel: str, what: str, err: float,
                 tol: float, **more) -> None:
    more.setdefault("ran",
                    "tpu_custom_call" if smoke.on_chip else "interpret")
    smoke.emit("kernels", kernel=kernel, what=what, err=err, tol=tol, **more)
    check(err <= tol, f"{kernel} {what}: err {err} > tol {tol}")


def _check_flash(smoke: Smoke, shape: tuple) -> None:
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_attention import flash_attention_tpu

    interpret = smoke.rehearsal
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[:3])
    w = jax.random.normal(keys[3], shape, jnp.float32)  # cotangent

    def kernel(q, k, v):
        return flash_attention_tpu(q, k, v, True, interpret=interpret)

    got = _run_compiled(smoke, kernel, (q, k, v), "hvd_flash_attention")
    want = jax.jit(_reference_attention)(q, k, v)
    _kernel_line(smoke, "flash_attention", "fwd", _rel_err(got, want),
                 FLASH_TOL, shape=shape, dtype="bfloat16",
                 attention_path=_attention_path(shape))
    got = _run_compiled(smoke, jax.grad(_weighted_sum(kernel, w), (0, 1, 2)),
                        (q, k, v), _flash_bwd_kernels(shape[-1]))
    want = jax.jit(jax.grad(_weighted_sum(_reference_attention, w),
                            (0, 1, 2)))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        _kernel_line(smoke, "flash_attention", f"grad {name}",
                     _rel_err(g, r), FLASH_TOL)


def _check_flash_cells(smoke: Smoke) -> None:
    """o, lse and the three gradients of the flash kernels at the causal
    cells' own shapes (``Sizes.cells``) against plain float32 attention at
    "highest", a query head at a time: the forward's operands where the
    projections write them, the bands of its tiles on the diagonal and on
    a window's edge, a k/v head read by its group and dk, dv summed over
    it. The cotangents weigh o and lse both."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_attention import flash_attention_with_lse

    for n, (B, S, H, Hkv, D, window) in enumerate(smoke.sizes.cells):
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 50 + n), 5)
        q = jax.random.normal(keys[0], (B, S, H, D), jnp.bfloat16)
        k, v = (jax.random.normal(kk, (B, S, Hkv, D), jnp.bfloat16)
                for kk in keys[1:3])
        w = jax.random.normal(keys[3], (B, S, H, D), jnp.float32)
        u = jax.random.normal(keys[4], (B, H, S), jnp.float32)

        def kernel(q, k, v):
            o, lse = flash_attention_with_lse(
                q, k, v, True, interpret=smoke.rehearsal, window=window)
            return o, lse.reshape(B, H, S)

        def reference(q, k, v):     # one head each: [B, S, D]
            q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
            with jax.default_matmul_precision("highest"):
                s = jnp.einsum("bqd,bkd->bqk", q, k) / D ** 0.5
                t, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
                live = j <= t
                if window is not None:
                    live = jnp.logical_and(live, j > t - window)
                lse = jax.nn.logsumexp(jnp.where(live, s, -jnp.inf), -1)
                o = jnp.einsum("bqk,bkd->bqd",
                               jnp.where(live, jnp.exp(s - lse[..., None]),
                                         0.0), v)
            return o, lse

        def weighed(f, w, u):
            def total(q, k, v):
                o, lse = f(q, k, v)
                return jnp.sum(o.astype(jnp.float32) * w) + jnp.sum(lse * u)
            return total

        @jax.jit
        def head(q, k, v, w, u):
            return reference(q, k, v), jax.grad(
                weighed(reference, w, u), (0, 1, 2))(q, k, v)

        o, lse = _run_compiled(smoke, kernel, (q, k, v),
                               "hvd_flash_attention")
        dq, dk, dv = _run_compiled(
            smoke, jax.grad(weighed(kernel, w, u), (0, 1, 2)), (q, k, v),
            _flash_bwd_kernels(D))
        errs = dict.fromkeys(
            ("fwd o", "fwd lse", "grad dq", "grad dk", "grad dv"), 0.0)
        group = H // Hkv
        for g in range(Hkv):
            dk_ref = dv_ref = 0.0
            for h in range(g * group, (g + 1) * group):
                (o_h, lse_h), (dq_h, dk_h, dv_h) = head(
                    q[:, :, h], k[:, :, g], v[:, :, g], w[:, :, h], u[:, h])
                dk_ref, dv_ref = dk_ref + dk_h, dv_ref + dv_h
                for name, got, want in (("fwd o", o[:, :, h], o_h),
                                        ("fwd lse", lse[:, h], lse_h),
                                        ("grad dq", dq[:, :, h], dq_h)):
                    errs[name] = max(errs[name], _rel_err(got, want))
            for name, got, want in (("grad dk", dk[:, :, g], dk_ref),
                                    ("grad dv", dv[:, :, g], dv_ref)):
                errs[name] = max(errs[name], _rel_err(got, want))
        more = dict(shape=(B, S, H, D), dtype="bfloat16",
                    attention_path=_attention_path((B, S, H, D), kv_heads=Hkv,
                                                   window=window))
        if n == 0:      # glm-4.7-flash.s8192's
            more.update(_latent_cell(smoke))
        for what, err in errs.items():
            _kernel_line(smoke, "flash_attention", what, err, FLASH_TOL,
                         **more)
            more = {}


def _latent_cell(smoke: Smoke) -> dict:
    """What the cell ``glm-4.7-flash.s8192`` takes, from its configuration
    as the benchmark's adapter reads it: the attention core's path and
    tiles at its 20 heads of 256 over 8192 keys, and the kinds of block its
    step checkpoints."""
    chip = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "benchmarks", "chip")
    if chip not in sys.path:
        sys.path.insert(0, chip)
    import run as harness
    from adapters import glm4_moe_lite
    from horovod_tpu.models import transformer as t
    _bench, _entry, config, job = harness.load_cell("glm-4.7-flash.s8192",
                                                    tiny=False)
    cfg = glm4_moe_lite._model_config(config, job)
    path = _attention_path((job["batch_per_chip"], job["seq_len"],
                            cfg.n_heads, cfg.head_dim))
    if smoke.on_chip:
        check(path.startswith("pallas hvd_flash_attention "), path)
    kinds = dict.fromkeys(cfg.lead_pattern + cfg.layer_pattern)
    return dict(
        attention_path_at_the_latent_cell=path,
        recomputation_at_the_latent_cell={
            kind[0]: "checkpointed" if t.remat(cfg, t._checkpointed(cfg, kind))
            else "kept" for kind in kinds})


def _check_banded(smoke: Smoke, sizes: tuple, what: str) -> None:
    """The flash kernels with grouped heads and a scale of their own
    against the XLA form of the same function at "highest": a k/v head read
    by its group, dk and dv summed over it. ``sizes``: ``Sizes.narrow`` (a
    head of 64, heads first), the scores' multiplier last."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_attention import (_banded_attention,
                                                  flash_attention_tpu)

    B, S, H, Hkv, D, window, scale = sizes
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 7), 4)
    q = jax.random.normal(keys[0], (B, S, H, D), jnp.bfloat16)
    k, v = (jax.random.normal(kk, (B, S, Hkv, D), jnp.bfloat16)
            for kk in keys[1:3])
    w = jax.random.normal(keys[3], (B, S, H, D), jnp.float32)

    def kernel(q, k, v):
        return flash_attention_tpu(q, k, v, True, scale,
                                   interpret=smoke.rehearsal, window=window)

    def reference(q, k, v):
        with jax.default_matmul_precision("highest"):
            return _banded_attention(q, k, v, window, scale)

    got = _run_compiled(smoke, kernel, (q, k, v), "hvd_flash_attention")
    _kernel_line(smoke, "flash_attention", f"{what} fwd",
                 _rel_err(got, jax.jit(reference)(q, k, v)), FLASH_TOL,
                 shape=(B, S, H, D), dtype="bfloat16",
                 attention_path=_attention_path((B, S, H, D), kv_heads=Hkv,
                                                window=window))
    got = _run_compiled(smoke, jax.grad(_weighted_sum(kernel, w), (0, 1, 2)),
                        (q, k, v), _flash_bwd_kernels(D))
    want = jax.jit(jax.grad(_weighted_sum(reference, w), (0, 1, 2)))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        _kernel_line(smoke, "flash_attention", f"{what} grad {name}",
                     _rel_err(g, r), FLASH_TOL)


def _check_sparse(smoke: Smoke) -> None:
    """The sparse core's three kernels (``ops/pallas_sparse_attention.py``)
    at ``Sizes.sparse`` under a seeded selection (``topk`` of a query's
    causal keys, every causal key of the first ``topk`` queries: what the
    selection leaves the core), against the ``jax.numpy`` form of the same
    sums at "highest", a block of 128 queries at a time: o, the rows'
    log-sum-exp, the heads' mean attention, and dq, dk, dv under a seeded
    cotangent of o. bf16 operands on both sides; the kernels round the
    weights to bf16 in front of their matmuls, the reference nothing."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.ops import pallas_sparse_attention as ps
    from horovod_tpu.ops import sparse_attention as sa

    S, H, Hkv, D, topk, block_k = smoke.sizes.sparse[:6]
    kern = ps.Kernels(block_k, interpret=smoke.rehearsal)
    scale, n = D ** -0.5, S // ps.ROWS
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 90), 5)
    q, w = (jax.random.normal(kk, (S, H, D), jnp.bfloat16)
            for kk in keys[:2])
    k, v = (jax.random.normal(kk, (S, Hkv, D), jnp.bfloat16)
            for kk in keys[2:4])
    t0 = ps.ROWS * jnp.arange(n, dtype=jnp.int32)
    blocked = (q.reshape(n, ps.ROWS, H, D), w.reshape(n, ps.ROWS, H, D), t0)

    def selection(t0):
        scores = jax.random.uniform(jax.random.fold_in(keys[4], t0),
                                    (ps.ROWS, S))
        t = t0 + jnp.arange(ps.ROWS, dtype=jnp.int32)
        return ps.pack_selection(sa.select(scores, t, topk), block_k)
    mask = jax.jit(lambda: lax.map(selection, t0))()

    def kernels(q, k, v, mask):
        flat = (k.reshape(S, Hkv * D), v.reshape(S, Hkv * D))

        def block(x):
            (qb, _, t0), mb = x
            qb = qb.reshape(ps.ROWS, H * D)
            o, lse = ps.sparse_forward(qb, *flat, mb, t0, scale=scale, head_dim=D,
                                       kern=kern)
            return o, lse, ps.heads_mean(
                qb, flat[0], lse, mb, t0, scale=scale, head_dim=D, kern=kern)
        o, lse, p = lax.map(block, (blocked, mask))
        o = o.reshape(S, H, D)
        lse = lse.transpose(1, 2, 0, 3).reshape(H, 1, S)
        return (o, lse, p) + ps.sparse_backward(q, k, v, o, lse, mask,
                                                w, scale, kern)

    def block_reference(qb, k, v, chosen):
        f32 = jnp.float32
        s = jnp.einsum("rhgd,khd->hgrk",
                       qb.astype(f32).reshape(ps.ROWS, Hkv, -1, D),
                       k.astype(f32)) * scale
        s = jnp.where(chosen, s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        e = jnp.exp(s - lse[..., None])
        o = jnp.einsum("hgrk,khd->rhgd", e, v.astype(f32))
        return (o.reshape(ps.ROWS, H, D), lse.reshape(H, ps.ROWS),
                jnp.sum(e, axis=(0, 1)) / H)

    def reference(q, k, v, mask):
        def block(carry, x):
            (qb, wb, _), mb = x
            chosen = ps.unpack_selection(mb, block_k)
            with jax.default_matmul_precision("highest"):
                out, back = jax.vjp(
                    lambda qb, k, v: block_reference(qb, k, v, chosen),
                    qb, k, v)
                dq, dk, dv = back((wb.astype(jnp.float32),
                                   jnp.zeros_like(out[1]),
                                   jnp.zeros_like(out[2])))
            return (carry[0] + dk, carry[1] + dv), out + (dq,)
        zero = jnp.zeros((S, Hkv, D), jnp.float32)
        (dk, dv), (o, lse, p, dq) = lax.scan(block, (zero, zero),
                                             (blocked, mask))
        return (o.reshape(S, H, D), lse.transpose(1, 0, 2).reshape(H, 1, S),
                p, dq.reshape(S, H, D), dk, dv)

    got = _run_compiled(smoke, kernels, (q, k, v, mask),
                        (ps.FWD_NAME, ps.MEAN_NAME, ps.BWD_NAME))
    want = jax.jit(reference)(q, k, v, mask)
    more = dict(shape=(S, H, Hkv, D), topk=topk, block_k=block_k,
                dtype="bfloat16", sparse_path=sa.sparse_path(S, H, Hkv, D),
                selected_keys=float(jnp.mean(jnp.sum(
                    want[2] > 0, axis=-1, dtype=jnp.float32))))
    if smoke.on_chip:
        check(more["sparse_path"] == "pallas", str(more))
    for what, g, r in zip(("fwd o", "fwd lse", "mean p", "grad dq",
                           "grad dk", "grad dv"), got, want):
        _kernel_line(smoke, "sparse_attention", what, _rel_err(g, r),
                     FLASH_TOL, **more)
        more = {}
    _check_index_backward(smoke, kern)


def _check_index_backward(smoke: Smoke, kern) -> None:
    """The index score pass's backward kernel (``hvd_index_bwd``) at
    ``Sizes.sparse``: for every block of 128 queries the gradient of ``ct *
    KL(target || softmax_chosen(I))`` to the index queries, weights and keys
    (the keys' summed over the blocks in float32, as the step sums them),
    under the exact selection of the block's own scores and a seeded target
    on it, against autodiff of the ``jax.numpy`` expression at "highest" on
    the same bf16 operands."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from horovod_tpu.ops import pallas_sparse_attention as ps
    from horovod_tpu.ops import sparse_attention as sa

    S, topk, Hi, Di = (smoke.sizes.sparse[n] for n in (0, 4, 6, 7))
    check(ps.index_kernel_shapes(ps.ROWS, Hi, Di), f"index heads {Hi, Di}")
    n, f32 = S // ps.ROWS, jnp.float32
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 91), 5)
    qi = jax.random.normal(keys[0], (n, ps.ROWS, Hi, Di), jnp.bfloat16)
    w = jax.random.normal(keys[1], (n, ps.ROWS, Hi), f32) * (Hi * Di) ** -0.5
    ki = jax.random.normal(keys[2], (S, Di), jnp.bfloat16)
    blocked = (qi, w, ps.ROWS * jnp.arange(n, dtype=jnp.int32))

    def given(q, ww, t0):
        t = t0 + jnp.arange(ps.ROWS, dtype=jnp.int32)
        scores = sa.index_scores(q, ww, ki)
        chosen = sa.select(scores, t, topk)
        seeded = jax.random.normal(jax.random.fold_in(keys[3], t0),
                                   chosen.shape, f32)
        target = jax.nn.softmax(jnp.where(chosen, seeded, -jnp.inf), axis=-1)
        return scores, chosen, target, 1.0 + jax.random.uniform(
            jax.random.fold_in(keys[4], t0))

    def kernels(blocked, ki):
        placed = ps.place_keys(ki, Di, S)

        def block(x):
            scores, chosen, target, ct = given(*x)
            return ps.index_backward(
                x[0], x[1], placed, target,
                ps.pack_selection(chosen, kern.block_k),
                ps.index_rows(target, scores, chosen), ct, x[2], S, kern)
        dq, dw, dk = lax.map(block, blocked)
        return dq, dw, jnp.sum(dk, axis=0)

    def reference(blocked, ki):
        def block(dk, x):
            _, chosen, target, ct = given(*x)
            with jax.default_matmul_precision("highest"):
                dq, dw, more = jax.grad(
                    lambda q, ww, k: ct * sa._index_loss(
                        target, sa.index_scores(q, ww, k), chosen),
                    (0, 1, 2))(x[0].astype(f32), x[1], ki.astype(f32))
            return dk + more, (dq, dw)
        dk, (dq, dw) = lax.scan(block, jnp.zeros((S, Di), f32), blocked)
        return dq, dw, dk

    got = _run_compiled(smoke, kernels, (blocked, ki), ps.INDEX_BWD_NAME)
    want = jax.jit(reference)(blocked, ki)
    more = dict(shape=(S, Hi, Di), topk=topk, block_k=kern.block_k,
                dtype="bfloat16")
    for what, g, r in zip(("grad dqi", "grad dw", "grad dki"), got, want):
        _kernel_line(smoke, "index_scores", what, _rel_err(g, r), FLASH_TOL,
                     **more)
        more = {}


def _check_block(smoke: Smoke) -> None:
    """The block attention kernels (BERT's core: non-causal, a key mask,
    head_dim 64) against the XLA core in float32 at "highest", forward and
    gradients, at the shapes of both BERT cells. Row 0 has half its keys
    padded, row 1 all of them: it attends evenly and moves no q or k."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_attention import (_key_masked_attention,
                                                  block_attention)

    for shape in smoke.sizes.block:
        B, S, _H, _D = shape
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed + S), 4)
        q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
                   for kk in keys[:3])
        w = jax.random.normal(keys[3], shape, jnp.float32)
        mask = jnp.ones((B, S), bool).at[0, S // 2:].set(False)
        mask = mask.at[1, :].set(False)

        def kernel(q, k, v):
            return block_attention(q, k, v, mask, interpret=smoke.rehearsal)

        def reference(q, k, v):
            with jax.default_matmul_precision("highest"):
                return _key_masked_attention(
                    *(x.astype(jnp.float32) for x in (q, k, v)), mask)

        got = _run_compiled(smoke, kernel, (q, k, v), BLOCK_KERNELS[0])
        _kernel_line(smoke, "block_attention", "fwd",
                     _rel_err(got, jax.jit(reference)(q, k, v)), FLASH_TOL,
                     shape=shape, dtype="bfloat16",
                     attention_path=_attention_path(shape, False, True))
        got = _run_compiled(
            smoke, jax.grad(_weighted_sum(kernel, w), (0, 1, 2)), (q, k, v),
            BLOCK_KERNELS[1])
        want = jax.jit(jax.grad(_weighted_sum(reference, w),
                                (0, 1, 2)))(q, k, v)
        for name, g, r in zip(("dq", "dk", "dv"), got, want):
            _kernel_line(smoke, "block_attention", f"grad {name}",
                         _rel_err(g, r), FLASH_TOL)
        check(float(jnp.max(jnp.abs(got[0][1].astype(jnp.float32)))) == 0
              and float(jnp.max(jnp.abs(got[1][1].astype(jnp.float32)))) == 0,
              "block_attention: a row of padded keys moved q or k")


def _xent_path(n: int, vocab: int, interpret: bool = False) -> str:
    """Which implementation the LM loss takes for ``[n, vocab]`` bf16
    logits on the default backend and, for the kernel, its tile and grid
    steps (``pallas_xent.xent_path``)."""
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_xent import xent_path
    path, detail = xent_path(n, vocab, jnp.bfloat16, interpret)
    return (f"pallas hvd_fused_xent {detail}" if path == "kernel"
            else f"xla _xla_xent ({detail})")


def _check_xent(smoke: Smoke) -> None:
    """The loss kernel against ``_xla_xent``: the loss and its gradient
    through the logits form, and through the head form the flagship runs
    (``x @ w`` inside; dx and dw under a per-row cotangent with zeros)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops.pallas_xent import (
        _xla_xent, fused_softmax_xent, head_softmax_xent)

    interpret = smoke.rehearsal
    n, vocab = smoke.sizes.xent
    k1, k2, k3, k4, k5 = jax.random.split(jax.random.PRNGKey(smoke.seed + 1),
                                          5)
    logits = (4.0 * jax.random.normal(k1, (n, vocab), jnp.float32)
              ).astype(jnp.bfloat16)
    labels = jax.random.randint(k2, (n,), 0, vocab, jnp.int32)

    def kernel(lg, y):   # whole rows a block: 32000 is not padded
        return fused_softmax_xent(lg, y, interpret=interpret)

    got = _run_compiled(smoke, kernel, (logits, labels), "hvd_fused_xent")
    want = jax.jit(_xla_xent)(logits, labels)
    err = float(jnp.max(jnp.abs(got - want)))
    _kernel_line(smoke, "fused_xent", "fwd (abs)", err, XENT_LOSS_ATOL,
                 shape=(n, vocab), dtype="bfloat16",
                 xent_path=_xent_path(n, vocab, interpret))
    got = _run_compiled(
        smoke, jax.grad(lambda lg, y: kernel(lg, y).sum()),
        (logits, labels), "hvd_fused_xent")
    want = jax.jit(jax.grad(lambda lg, y: _xla_xent(lg, y).sum()))(
        logits, labels)
    _kernel_line(smoke, "fused_xent", "grad dlogits", _rel_err(got, want),
                 XENT_GRAD_TOL)

    width = 256
    x = jax.random.normal(k3, (n, width), jnp.bfloat16)
    w = (0.1 * jax.random.normal(k4, (width, vocab), jnp.float32)
         ).astype(jnp.bfloat16)
    g = jax.random.uniform(k5, (n,), jnp.float32).at[::7].set(0.0)

    def head(loss):
        return jax.grad(lambda x, w, y: jnp.sum(loss(x, w, y) * g), (0, 1))

    got = _run_compiled(
        smoke, head(lambda x, w, y: head_softmax_xent(
            x, w, y, interpret=interpret)), (x, w, labels), "hvd_fused_xent")
    want = jax.jit(head(lambda x, w, y: _xla_xent(x @ w, y)))(x, w, labels)
    for name, got_, want_ in zip(("dx", "dw"), got, want):
        _kernel_line(smoke, "fused_xent", f"head grad {name}",
                     _rel_err(got_, want_), XENT_GRAD_TOL)


def _check_gmm(smoke: Smoke) -> None:
    """The expert layer's grouped matmul (parallel/moe.py), forward and both
    gradients, on ragged groups with empty ones among them that end before
    the rows do, as an ep shard's and a held share's do: on the megablox
    kernels named hvd_moe_gmm at each of ``sizes.gmm`` (aligned widths, read
    row-major; the hybrid cell's way up, whose weights the calls read and
    whose gradient they write the other way round, as the chip stores them:
    ISSUE 41), and at a 64-wide expert on XLA's ragged_dot, which is where a
    TPU falls back to. On ragged_dot's path the rows beyond the groups must
    be zero, forward and in d_rows (what XLA's ragged_dot alone gives there
    is printed); the kernels never write them and the expert layer reads
    none (ISSUE 37), so there they are compared with nothing, and kept out
    of the weights' gradient. The reference is ragged_dot in float32 on the
    groups' rows alone. Prints which path ``grouped_matmul`` takes, which
    way round it reads the weights and the tile of each of its three calls,
    at each size, at the OLMoE cell's, at the share cell's, at the hybrid
    cell's, at the latent cell's and at the banded cell's (Laguna: 32 held
    experts of 2048 x 512 under 65 536 assignments), and of how many of
    their chunks of sorted rows the expert layer's row-wise passes run
    there."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.parallel.moe import (GMM_NAME, gmm_path, grouped_matmul,
                                          held_chunks_path)

    interpret = smoke.rehearsal
    olmoe = gmm_path(65536, 2048, 1024)
    share = gmm_path(49152, 2560, 768)
    hybrid = gmm_path(49152, 2688, 1856)
    latent = gmm_path(4 * 8192, 2048, 1536)
    banded = gmm_path(8 * 8192, 2048, 512)
    # the rows each share holds by arithmetic: 16 of 64, 8 of 128, 8 of 64,
    # 32 of 256 experts (ISSUE 44: the chunks the row-wise passes run of
    # those there are)
    chunks = {"share": held_chunks_path(49152, 49152 * 16 // 64),
              "hybrid": held_chunks_path(49152, 49152 * 8 // 128),
              "latent": held_chunks_path(4 * 8192, 4 * 8192 * 8 // 64),
              "banded": held_chunks_path(8 * 8192, 8 * 8192 * 32 // 256),
              "olmoe": held_chunks_path(65536, None)}
    if smoke.on_chip:
        check(olmoe.startswith(f"pallas {GMM_NAME} ") and "row-major" in olmoe
              and share.startswith(f"pallas {GMM_NAME} ")
              and hybrid.startswith(f"pallas {GMM_NAME} ")
              and "[E, 1856, 2688]" in hybrid
              and latent.startswith(f"pallas {GMM_NAME} ")
              and banded.startswith(f"pallas {GMM_NAME} "),
              olmoe + share + hybrid + latent + banded)
    narrow = smoke.sizes.gmm[0][:2] + (64,) + smoke.sizes.gmm[0][3:]
    cases = [(GMM_NAME, size) for size in smoke.sizes.gmm] + [(None, narrow)]
    for case, (kernel, (rows, d_in, width, groups, inside)) in enumerate(cases):
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 2), 3)
        x = jax.random.normal(keys[0], (rows, d_in), jnp.bfloat16)
        rng = np.random.default_rng(smoke.seed + case)
        part = rng.dirichlet(np.full(groups, 0.5)) \
            * (rng.random(groups) > 0.25)
        sizes = rng.multinomial(inside, part / part.sum()).astype(np.int32)
        gs = jnp.asarray(sizes)

        def ours(x, w):
            return grouped_matmul(x, w, gs, interpret=interpret)

        def reference(x, w):
            with jax.default_matmul_precision("highest"):
                out = jax.lax.ragged_dot(x[:inside].astype(jnp.float32), w,
                                         gs)
            return jnp.pad(out, ((0, rows - inside), (0, 0)))

        def beyond(a):
            return float(jnp.max(jnp.abs(a[inside:].astype(jnp.float32))))
        w = jax.random.normal(keys[1], (groups, d_in, width),
                              jnp.float32) / np.sqrt(d_in)
        ct = jax.random.normal(keys[2], (rows, width), jnp.float32)
        path = gmm_path(rows, d_in, width)
        if smoke.on_chip:
            check(path.startswith(f"pallas {GMM_NAME} " if kernel
                                  else "xla ragged_dot"), path)
        name = "moe_gmm" if kernel else "moe_gmm on xla ragged_dot"
        ran = {} if kernel else {"ran": "xla"}

        def loss(f):
            return lambda x, w: jnp.sum(f(x, w).astype(jnp.float32) * ct)
        # the kernels' rows beyond the groups are compared with nothing
        read = slice(0, inside if kernel else rows)
        got = _run_compiled(smoke, ours, (x, w), kernel)
        raw = jax.jit(jax.lax.ragged_dot)(x, w.astype(x.dtype), gs)
        _kernel_line(smoke, name, "fwd",
                     _rel_err(got[read], jax.jit(reference)(x, w)[read]),
                     GMM_TOL,
                     shape=(rows, d_in, width, groups), dtype="bfloat16",
                     gmm_path=path, gmm_path_at_the_olmoe_cell=olmoe,
                     gmm_path_at_the_share_cell=share,
                     gmm_path_at_the_hybrid_cell=hybrid,
                     gmm_path_at_the_latent_cell=latent,
                     gmm_path_at_the_banded_cell=banded,
                     row_wise_passes_at_the_cells=chunks,
                     rows_in_groups=inside, largest_group=int(sizes.max()),
                     empty_groups=int((sizes == 0).sum()),
                     beyond_the_groups=None if kernel else beyond(got),
                     plain_ragged_dot_beyond_the_groups=beyond(raw), **ran)
        check(kernel or beyond(got) == 0,
              f"{name}: rows beyond the groups not zero")
        got = _run_compiled(smoke, jax.grad(loss(ours), (0, 1)), (x, w),
                            kernel)
        want = jax.jit(jax.grad(loss(reference), (0, 1)))(x, w)
        for leaf, g, r in zip(("d_rows", "d_weights"),
                              (got[0][read], got[1]), (want[0][read], want[1])):
            _kernel_line(smoke, name, f"grad {leaf}", _rel_err(g, r),
                         GMM_TOL, **ran)
        check(kernel or beyond(got[0]) == 0,
              f"{name}: d_rows beyond the groups not zero")


#: float32 sums in another order; a product that ran at one bfloat16 pass
#: on the MXU would read 4e-3
NORM_TOL = 1e-5


def _check_dense_hybrid(smoke: Smoke) -> None:
    """The kernels at the cell ``granite-4.0-h-micro.s4096``'s shapes, each
    alone against its ``jax.numpy`` form: the scan at ONE group of 64 heads
    and chunk 256 in head tiles, the flash pair at 32 / 8 heads of 64 with
    the scores times 1/64; ``ssm_path`` and ``attention_path`` say what
    runs, and on the chip neither may say ``jax.numpy`` or ``xla``."""
    import jax.numpy as jnp
    from horovod_tpu.ops import pallas_ssm
    z = smoke.sizes
    _check_ssm(smoke, z.dense_ssm, "dense_hybrid", 4 * z.dense_ssm[0])
    _check_banded(smoke, z.narrow, "a head of 64")
    B, S, H, Hkv, D = z.narrow[:5]
    path = _attention_path((B, S, H, D), kv_heads=Hkv)
    scan = pallas_ssm.ssm_scan_path(4 * z.dense_ssm[0], *z.dense_ssm[1:])
    if smoke.on_chip:
        check(path.startswith("pallas hvd_flash_attention ")
              and scan.startswith("kernels hvd_ssm_scan "), f"{path}; {scan}")
    smoke.emit("kernels", cell="granite-4.0-h-micro.s4096",
               attention_path_at_the_dense_hybrid_cell=path,
               ssm_scan_path_at_the_dense_hybrid_cell=scan,
               head_tile=pallas_ssm.ssm_head_tile(
                   *z.dense_ssm[1:], jnp.dtype(jnp.bfloat16).itemsize))


def _check_gated_norm(smoke: Smoke) -> None:
    """models/mamba.py:_gated_norm on the device against the definition with
    a group's channels on an axis of their own (what XLA:TPU pays copies and
    broadcasts for), float32 operands: the result and the three gradients.
    The group sums go through the MXU; the bound holds them to float32."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import mamba
    S, H, P, G = smoke.sizes.ssm[:4]
    C, eps = H * P, 1e-5
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 6), 4)
    y = 3.0 * jax.random.normal(keys[0], (1, S, C), jnp.float32)
    z = 2.0 * jax.random.normal(keys[1], (1, S, C), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(keys[2], (C,), jnp.float32)
    ct = jax.random.normal(keys[3], (1, S, C), jnp.float32)

    def by_axis(y, z, w):
        g = (y * jax.nn.silu(z)).reshape(1, S, G, C // G)
        g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), -1, keepdims=True)
                              + eps)
        return g.reshape(1, S, C) * w

    def both(norm):
        out, pull = jax.vjp(norm, y, z, w)
        return (out,) + pull(ct)
    got = jax.jit(lambda: both(
        lambda y, z, w: mamba._gated_norm(y, z, w, G, eps)))()
    want = jax.jit(lambda: both(by_axis))()
    for what, g, r in zip(("fwd", "grad d_y", "grad d_z", "grad d_ssm_norm"),
                          got, want):
        _kernel_line(smoke, "mamba-2 gate + norm", what, _rel_err(g, r),
                     NORM_TOL, shape=(S, C, G), ran="xla")


def _check_delta_heads_on_lanes(smoke: Smoke) -> None:
    """models/delta.py's L2 norm and output norm on the device at the delta
    cells' heads, ``[1, S, H D]`` with a head's sums products with a 0/1
    matrix, against the same with a head's channels on an axis of their own
    (what XLA:TPU pays a copy each way for), float32 operands: the results
    and the gradients. The sums go through the MXU; the bound holds them to
    float32 (a single bfloat16 pass reads ~3e-3)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import delta
    S, H, D = smoke.sizes.delta[:3]
    C, eps = H * D, 1e-5
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 9), 3)
    x = 3.0 * jax.random.normal(keys[0], (1, S, C), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(keys[1], (D,), jnp.float32)
    ct = jax.random.normal(keys[2], (1, S, C), jnp.float32)

    def l2_by_axis(x, w):
        return delta._l2norm(x.reshape(1, S, H, D)).reshape(1, S, C)

    def norm_by_axis(x, w):
        y = x.reshape(1, S, H, D)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), -1, keepdims=True)
                              + eps) * w
        return y.reshape(1, S, C)

    def both(f):
        out, pull = jax.vjp(f, x, w)
        return (out,) + pull(ct)
    # zip stops at the leaves named: the L2 norm has no weight to differentiate
    for name, leaves, flat, by_axis in (
            ("L2 norm", ("fwd", "grad d_x"),
             lambda x, w: delta._l2norm(x, H), l2_by_axis),
            ("output norm", ("fwd", "grad d_x", "grad d_norm"),
             lambda x, w: delta._head_norm(x, w, H, eps), norm_by_axis)):
        got, want = jax.jit(lambda: both(flat))(), \
            jax.jit(lambda: both(by_axis))()
        for what, g, r in zip(leaves, got, want):
            _kernel_line(smoke, f"delta {name}, heads on the lanes", what,
                         _rel_err(g, r), NORM_TOL, shape=(S, H, D), ran="xla")


def _check_ssm(smoke: Smoke, sizes=None, cell: str = "hybrid",
               length: int = 8192) -> None:
    """The Mamba-2 scan on its kernels (ops/pallas_ssm.py: hvd_ssm_scan,
    hvd_ssm_scan_bwd, through models/mamba.py:ssm_chunked as a Mamba
    block calls it), bfloat16 operands with float32 time steps, sums, decays
    and carried state, against the recurrence one position at a time in
    float32, forward and every operand's gradient, at the hybrid cell's
    heads; and against the ``jax.numpy`` form on the same operands. Prints
    ``ssm_path`` at the cell's length: the kernels' tiles, the chunk count
    and what the backward pass keeps of a block. ``sizes``, ``cell``,
    ``length``: another cell's heads than ``Sizes.ssm`` (``Sizes.dense_ssm``:
    ONE group, whose heads go in head tiles)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import mamba
    from horovod_tpu.models.mamba import ssm_chunked, ssm_path
    from horovod_tpu.models.transformer import TransformerConfig
    from horovod_tpu.ops import pallas_ssm
    S, H, P, G, N, chunk = sizes or smoke.sizes.ssm
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 5), 6)
    x = jax.random.normal(keys[0], (1, S, H, P), jnp.bfloat16)
    b, c = (jax.random.normal(k, (1, S, G, N), jnp.bfloat16) / N ** 0.25
            for k in keys[1:3])
    dt = jax.nn.softplus(jax.random.normal(keys[3], (1, S, H)) - 3.0)
    a = -jnp.exp(jax.random.uniform(keys[4], (H,), minval=0.0, maxval=2.7))
    ct = jax.random.normal(keys[5], (1, S, H, P), jnp.float32)

    def stepwise(x, dt, a, b, c):
        x, b, c = (v.astype(jnp.float32) for v in (x, b, c))
        b, c = (jnp.repeat(v, H // G, axis=2) for v in (b, c))

        def step(h, at):
            x_t, dt_t, b_t, c_t = at
            h = (jnp.exp(dt_t * a)[..., None, None] * h
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
            return h, jnp.sum(h * c_t[:, :, None, :], axis=-1)
        _, y = jax.lax.scan(step, jnp.zeros((1, H, P, N)), tuple(
            jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)))
        return jnp.moveaxis(y, 0, 1)

    def kernels(*ops):
        return ssm_chunked(*ops, chunk, interpret=smoke.rehearsal)

    def numpy_form(x, dt, a, b, c):
        return mamba._ssm_chunked_numpy(
            x, dt, mamba._chunk_sums(dt * a, chunk), b, c, chunk)

    def loss(f):
        return lambda *ops: jnp.sum(f(*ops) * ct)
    ops = (x, dt, a, b, c)
    cfg = TransformerConfig(ssm_heads=H, ssm_head_dim=P, ssm_state=N,
                            ssm_groups=G, ssm_chunk=chunk)
    got = _run_compiled(smoke, kernels, ops, pallas_ssm.FWD_NAME)
    _kernel_line(smoke, "mamba-2 scan", "fwd",
                 _rel_err(got, jax.jit(stepwise)(*ops)), SSM_TOL,
                 shape=(S, H, P, G, N),
                 against_the_numpy_form=_rel_err(
                     got, jax.jit(numpy_form)(*ops)),
                 **{f"ssm_path_at_the_{cell}_cell": ssm_path(cfg, length)})
    leaves = (0, 1, 2, 3, 4)
    got = _run_compiled(smoke, jax.grad(loss(kernels), leaves), ops,
                        pallas_ssm.BWD_NAME)
    want = jax.jit(jax.grad(loss(stepwise), leaves))(*ops)
    other = jax.jit(jax.grad(loss(numpy_form), leaves))(*ops)
    for leaf, g, r, n in zip(("d_x", "d_dt", "d_a", "d_b", "d_c"), got, want,
                             other):
        _kernel_line(smoke, "mamba-2 scan", f"grad {leaf}", _rel_err(g, r),
                     SSM_TOL, against_the_numpy_form=_rel_err(g, n))


def _check_delta(smoke: Smoke, key_heads: int = 0) -> None:
    """The gated delta rule's scan on its kernels (ops/pallas_delta.py:
    hvd_delta_scan, hvd_delta_scan_bwd, through models/delta.py:
    delta_chunked as a delta block calls it) at ``Sizes.delta``, the cell
    kimi-linear-48b-a3b.s8192's shape whole: bfloat16 q, k, v with float32
    log decays, sums, decay factors, inverse and carried state, against the
    ``jax.numpy`` form on the same operands with its matmuls at "highest",
    o and every operand's gradient; decays as fast as the cell's (the most
    negative chunk sum is printed). Prints ``delta_scan_path``. Bound as
    ``_check_ssm``'s. With ``key_heads`` the form with a decay a head, the
    cell qwen3-next-80b-a3b.s8192's: a float32 log decay ``[S, H]`` and q
    and k at ``key_heads`` heads, each read by ``H / key_heads`` value
    heads (the kernels' other bodies)."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.models import delta
    from horovod_tpu.ops import pallas_delta
    S, H, D, Dv, chunk = smoke.sizes.delta
    Hk, what = key_heads or H, "delta scan, a decay a head" if key_heads \
        else "delta scan"
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 8), 7)
    q = (delta._l2norm(jax.random.normal(keys[0], (1, S, Hk, D)))
         * D ** -0.5).astype(jnp.bfloat16)
    k = delta._l2norm(jax.random.normal(keys[1], (1, S, Hk, D))
                      ).astype(jnp.bfloat16)
    v = jax.nn.silu(jax.random.normal(keys[2], (1, S, H, Dv))
                    ).astype(jnp.bfloat16)
    rate = jnp.exp(jax.random.uniform(keys[3], (H, 1), minval=-4.0,
                                      maxval=2.0))
    g = -rate * jax.nn.softplus(jax.random.normal(keys[4], (1, S, H, D)))
    if key_heads:
        g = g[..., 0]
    beta = jax.nn.sigmoid(jax.random.normal(keys[5], (1, S, H)))
    ct = jax.random.normal(keys[6], (1, S, H, Dv), jnp.float32)
    ops = (q, k, v, g, beta)

    def kernels(*ops):
        return delta.delta_chunked(*ops, chunk, interpret=smoke.rehearsal)

    def numpy_form(*ops):
        with jax.default_matmul_precision("highest"):
            return delta._delta_chunked_numpy(*ops, chunk)

    def loss(f):
        return lambda *ops: jnp.sum(f(*ops)[0] * ct)
    path = pallas_delta.describe(S, H, D, Dv, chunk, a_head=bool(key_heads))
    if smoke.on_chip:
        check(pallas_delta.delta_scan_path(S, H, D, Dv, chunk) == "kernels",
              path)
    got, low = _run_compiled(smoke, kernels, ops, pallas_delta.FWD_NAME)
    want, want_low = jax.jit(numpy_form)(*ops)
    _kernel_line(smoke, what, "fwd", _rel_err(got, want), SSM_TOL,
                 shape=(S, H, D, Dv, chunk), key_heads=Hk,
                 delta_scan_path=path,
                 min_log_decay=[float(low), float(want_low)])
    leaves = (0, 1, 2, 3, 4)
    got = _run_compiled(smoke, jax.grad(loss(kernels), leaves), ops,
                        (pallas_delta.FWD_NAME, pallas_delta.BWD_NAME))
    want = jax.jit(jax.grad(loss(numpy_form), leaves))(*ops)
    for leaf, g_, w in zip(("d_q", "d_k", "d_v", "d_g", "d_beta"), got, want):
        _kernel_line(smoke, what, f"grad {leaf}", _rel_err(g_, w), SSM_TOL)


#: bf16 operands and a bf16 round before the out-projection against float32
#: at "highest", as FLASH_TOL
SHORT_CONV_TOL = 2e-2


def _check_short_conv(smoke: Smoke) -> None:
    """The gated short-convolution mixer alone (models/short_conv.py: a
    ``("conv",)`` block, norm to residual add, as the cell
    ``lfm2-24b-a2b.s8192`` runs it: bf16 matmuls round a float32 chain ``C *
    conv(B * u)``) against the same equations in ``jax.numpy``, float32,
    matmuls at "highest": the output and the gradients of the input and of
    every leaf. No kernel: XLA runs both. On the chip prints the
    milliseconds of forward + backward (host clock over calls back to back:
    a smoke print, a gate and no metric), the pair a later kernel for the
    chain has to time."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import short_conv
    from horovod_tpu.models.transformer import TransformerConfig
    B, S, M, K = smoke.sizes.short_conv
    cfg = TransformerConfig(d_model=M, n_heads=max(M // 64, 1), conv_taps=K,
                            layer_pattern=(("conv",), ("dense",)),
                            n_layers=2, norm_eps=1e-5, dtype=jnp.bfloat16)
    rng = np.random.RandomState(smoke.seed + 7)
    p = {leaf.name: jnp.asarray(leaf.draw(rng, leaf.shape))
         for leaf in short_conv.KIND.leaves(cfg)}
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 7))
    x = jax.random.normal(keys[0], (B, S, M), jnp.float32)
    ct = jax.random.normal(keys[1], (B, S, M), jnp.float32)

    def plain(p, x):
        h = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + cfg.norm_eps) * p["ln1"]
        b, c, u = jnp.split(h @ p["conv_in"], 3, axis=-1)
        z = jnp.pad(b * u, ((0, 0), (K - 1, 0), (0, 0)))
        taps = sum(p["conv_w"][j] * z[:, j:j + S] for j in range(K))
        return x + (c * taps) @ p["conv_out"]

    def both(block):
        def run(p, x):
            out, pull = jax.vjp(block, p, x)
            return (out,) + pull(ct.astype(out.dtype))
        return run
    program = jax.jit(both(lambda p, x: short_conv._conv_block(
        p, x.astype(cfg.dtype), cfg)))
    got = jax.block_until_ready(program(p, x))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(both(plain))(p, x)
    more = {}
    if smoke.on_chip:
        t0 = time.perf_counter()
        for _ in range(10):
            last = program(p, x)
        jax.block_until_ready(last)
        more["fwd_and_bwd_host_ms"] = round(
            (time.perf_counter() - t0) / 10 * 1e3, 3)
    names = ["fwd", "grad d_x"] + [f"grad d_{k}" for k in sorted(p)]
    flat = lambda out: [out[0], out[2]] + [out[1][k] for k in sorted(p)]
    for what, g, r in zip(names, flat(got), flat(want)):
        _kernel_line(smoke, "short conv mixer", what, _rel_err(g, r),
                     SHORT_CONV_TOL, shape=(B, S, M, K), dtype="bfloat16",
                     ran="xla", **(more if what == "fwd" else {}))


def _check_embed(smoke: Smoke) -> None:
    """The lookup of a model whose head has a table of its own, and its
    hand-written gradient (models/transformer.py:_table_rows), at the
    cells' tables with token ids as text has them (Zipf: the commonest id
    some 700 times in 8192, a tenth of the ids more than once): the rows
    bit for bit the cast table's, the table's gradient against the float64
    sum of the bf16 cotangent rows, made on the host. No leaf of a cell's
    ``correct`` is the table's, and two layouts of one step share this
    backward, so this is where a wrong sum would show. Prints the
    milliseconds of lookup + gradient (host clock over calls back to back:
    a smoke print) with the rows added in pieces of ``SUM_COLUMNS`` and
    whole, the two readings the constant rests on."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import transformer
    from horovod_tpu.models.transformer import TransformerConfig

    def timed(columns, table, ids, cot, cfg, calls=10):
        was, transformer.SUM_COLUMNS = transformer.SUM_COLUMNS, columns
        try:
            def both(table, ids, cot):
                rows, back = jax.vjp(
                    lambda e: transformer._embed_lookup(e, ids, cfg), table)
                return rows, back(cot)[0]
            f = jax.jit(both)
            out = jax.block_until_ready(f(table, ids, cot))
        finally:
            transformer.SUM_COLUMNS = was
        t0 = time.perf_counter()
        for _ in range(calls):
            last = f(table, ids, cot)
        jax.block_until_ready(last)
        return out, (time.perf_counter() - t0) / calls * 1e3

    rng = np.random.default_rng(smoke.seed + 5)
    for vocab, width, tokens in smoke.sizes.embed:
        cfg = TransformerConfig(
            vocab_size=vocab, d_model=width, n_heads=width // 128,
            n_layers=1, d_ff=width, max_seq=tokens, dtype=jnp.bfloat16,
            tie_embeddings=False)
        share = 1.0 / np.arange(1, vocab + 1)
        ids = rng.permutation(vocab)[rng.choice(
            vocab, tokens, p=share / share.sum())].astype(np.int32)
        keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 5))
        table = jax.random.normal(keys[0], (vocab, width), jnp.float32)
        cot = jax.random.normal(keys[1], (1, tokens, width), jnp.bfloat16)
        (rows, grad), ms = timed(transformer.SUM_COLUMNS, table,
                                 jnp.asarray(ids)[None], cot, cfg)
        _whole, ms_whole = timed(width, table, jnp.asarray(ids)[None], cot,
                                 cfg)
        want = np.zeros((vocab, width), np.float64)
        np.add.at(want, ids, np.asarray(cot[0], np.float64))
        err = float(np.abs(np.asarray(grad, np.float64) - want).max()
                    / np.abs(want).max())
        same = bool(jnp.array_equal(rows, table.astype(jnp.bfloat16)[ids][None]))
        _kernel_line(smoke, "embed lookup", "grad table", err,
                     EMBED_GRAD_TOL, shape=(vocab, width, tokens),
                     ids="zipf", commonest_id=int(np.bincount(ids).max()),
                     distinct_ids=int(np.unique(ids).size),
                     rows_are_the_cast_table_s=same, ran="xla",
                     sum_columns=transformer.SUM_COLUMNS,
                     lookup_and_grad_host_ms=round(ms, 3),
                     lookup_and_grad_host_ms_rows_added_whole=round(
                         ms_whole, 3))
        check(same, "embed lookup: rows differ from table.astype(bf16)[ids]")


def _check_codec(smoke: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops import pallas_quantize as pq

    interpret = smoke.rehearsal
    shape = smoke.sizes.blocks
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 2), 3)
    x = jax.random.normal(keys[0], shape, jnp.float32)
    mom = jax.random.normal(keys[1], shape, jnp.float32)
    nu = jnp.square(jax.random.normal(keys[2], shape, jnp.float32))

    def codes_line(kernel, codes, scales, ref_codes, ref_scales, **more):
        diff = jnp.abs(codes.astype(jnp.int32) - ref_codes.astype(jnp.int32))
        check(int(jnp.max(diff)) <= 1,
              f"{kernel}: a code is off by {int(jnp.max(diff))}")
        off = float(jnp.mean((diff > 0).astype(jnp.float32)))
        _kernel_line(smoke, kernel, "codes off by one (fraction)", off,
                     CODE_MISMATCH_MAX, shape=shape, **more)
        _kernel_line(smoke, kernel, "scales", _rel_err(scales, ref_scales),
                     1e-6)

    ref_codes, ref_scales = jax.jit(pq._xla_quantize)(x)
    codes, scales = _run_compiled(
        smoke, lambda b: pq.block_quantize(b, interpret=interpret), (x,),
        "hvd_block_quantize")
    codes_line("block_quantize", codes, scales, ref_codes, ref_scales)

    codes_ef, scales_ef, res = _run_compiled(
        smoke, lambda b: pq.block_quantize_ef(b, interpret=interpret), (x,),
        "hvd_block_quantize_ef")
    codes_line("block_quantize_ef", codes_ef, scales_ef, ref_codes,
               ref_scales)
    _kernel_line(smoke, "block_quantize_ef", "residual",
                 _rel_err(res, x - pq._xla_dequantize(codes_ef, scales_ef)),
                 CODEC_RTOL)

    got = _run_compiled(
        smoke, lambda c, s: pq.block_dequantize(c, s, interpret=interpret),
        (codes, scales), "hvd_block_dequantize")
    _kernel_line(smoke, "block_dequantize", "values",
                 _rel_err(got, pq._xla_dequantize(codes, scales)), CODEC_RTOL)

    h = jnp.asarray([0.1, 0.9], jnp.float32)
    got = _run_compiled(
        smoke, lambda c, s, m: pq.fused_sgd_apply(
            c, s, m, h[0], h[1], interpret=interpret),
        (codes, scales, mom), "hvd_fused_sgd_apply")
    want = jax.jit(pq._xla_fused_sgd)(h, codes, scales, mom)
    for name, g, r in zip(("delta", "momentum"), got, want):
        _kernel_line(smoke, "fused_sgd_apply", name, _rel_err(g, r),
                     CODEC_RTOL)

    h = jnp.asarray([1e-3, 0.9, 0.999, 1e-8, 0.1, 0.001], jnp.float32)
    got = _run_compiled(
        smoke, lambda c, s, m, v: pq.fused_adam_apply(
            c, s, m, v, *h, interpret=interpret),
        (codes, scales, mom, nu), "hvd_fused_adam_apply")
    want = jax.jit(pq._xla_fused_adam)(h, codes, scales, mom, nu)
    for name, g, r in zip(("delta", "m", "v"), got, want):
        _kernel_line(smoke, "fused_adam_apply", name, _rel_err(g, r),
                     CODEC_RTOL)


def _attention_path(shape, causal=True, masked=False, kv_heads=None,
                    window=None) -> str:
    """Which implementation ``attend`` picks for q of ``shape`` (k/v with
    ``kv_heads`` heads if given, a key mask if ``masked``, a causal
    ``window``) on the default backend, read from the lowered program; for
    a kernel, what its rule picks for one call: the flash kernel's tile
    and grid steps, forward and backward, the form of the forward's
    operands (where they lie, or heads first) and the blocks it runs of a
    tile on the diagonal and of one on a band's edge, whether the backward
    keeps a head's dq in VMEM or goes over the q rows in ranges, the tiles a
    band leaves of a head's causal tiles and the query heads a k/v head
    serves;
    the block kernels' batch rows and heads a grid step and their VMEM
    estimate."""
    import math
    import jax
    import jax.numpy as jnp
    from horovod_tpu.ops import pallas_attention as pa
    B, S, H, D = shape
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, kv_heads or H, D), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((B, S), bool) if masked else None
    text = jax.jit(jax.grad(lambda q, k, v, m: jnp.sum(pa.attend(
        q, k, v, causal=causal, key_mask=m, window=window).astype(
            jnp.float32)), (0, 1, 2))).lower(x, kv, kv, mask).as_text()
    if "tpu_custom_call" not in text:
        if window is not None or kv_heads not in (None, H):
            return "xla _banded_attention"
        return ("xla _key_masked_attention" if masked
                else "xla _plain_attention")
    if pa.attention_path(S, S, H, D, causal, masked) == "block":
        rows = pa.block_rows(B, S, S, jnp.bfloat16)
        mib = pa.block_vmem_bytes(rows, S, S, 2) / 2 ** 20
        return (f"pallas {' + '.join(BLOCK_KERNELS)}, {rows} rows x "
                f"{pa.LANES // D} heads a step, "
                f"{math.prod(pa.block_grid(B, H, D, rows))} steps, "
                f"VMEM estimate {mib:.1f} MiB")
    adj = "jax.numpy sums, no kernel"
    if pa.ADJ_NAME in text:
        rows, heads = pa.flash_adj_blocks(S, H, D, jnp.bfloat16)
        adj = (f"{pa.ADJ_NAME} from do and o {[B, S, H * D]} where they "
               f"lie, {rows} rows x {heads} heads and "
               f"{B * S // rows * H // heads} steps")
    return (_flash_call((B, S, H, D), kv_heads, window)
            + f"; adj = sum do * o - dlse by {adj}")


def _flash_call(shape, kv_heads=None, window=None) -> str:
    """What the flash kernels' rules pick for one causal call at q of
    ``shape`` (``_attention_path``), from the functions the kernels call:
    tile and grid steps forward and backward, and under a window the grid
    steps and the tiles run a head."""
    import math
    import jax.numpy as jnp
    from horovod_tpu.ops import pallas_attention as pa
    B, S, H, D = shape
    bq, bk = pa.flash_blocks(S, S, D, jnp.bfloat16, window)
    grid = pa.flash_grid(B, H, S, S, bq, bk, window)
    bwd = pa.flash_bwd_blocks(S, S, D, jnp.bfloat16, window)
    bwd_grid = pa.flash_bwd_grid(B, H, S, S, bwd, window)
    ranges = S // bwd.rows
    form = ("dq resident" if ranges == 1
            else f"dq in {ranges} q ranges of {bwd.rows} rows")
    def pieces(of, bq, bk):
        """A kernel's tile in pieces, and what of a crossed tile runs."""
        def blocks(crossed):
            ran, every = pa.tile_piece_blocks(bq, bk, crossed)
            return f"{ran} of {every} blocks"
        text = (f"{of} in {len(pa.tile_pieces(bq, bk))} pieces of "
                f"{pa.piece_rows(bk)} k rows")
        if not pa.banded_tiles(bq, bk, window):
            return text + (", all q rows of a tile the diagonal or the "
                           "band's edge crosses")
        text += f", on the diagonal {blocks('diagonal')}"
        if window is not None:
            text += f", on the band's edge {blocks('edge')}"
        return text
    fwd = ("operands in place " + str([B, S, H * D]) if D % pa.MIN_BLOCK == 0
           else "operands heads first " + str([B * H, S, D]))
    fwd += (f", scores [k, q] with m, l [1, {bq}] and acc [{D}, {bq}] along "
            f"the lanes, {pieces('a tile', bq, bk)}")
    band = ""
    if window is not None:
        def tiles(grid, bq, bk):
            every, live, edge = pa.band_tile_counts(S, bq, bk, window)
            return (f"{math.prod(grid[1:])} steps and {live} of {every} "
                    f"causal tiles a head, {edge} on the edge")
        band += (f"; window {window}: forward {tiles(grid, bq, bk)}, "
                 f"backward {tiles(bwd_grid, bwd.block_q, bwd.block_k)}")
    if kv_heads not in (None, H):
        band += f"; kv heads {kv_heads}, group {H // kv_heads}"
    crossed = pieces("a crossed tile", *bwd[:2])
    return (f"pallas hvd_flash_attention {bq}x{bk}, {math.prod(grid)} steps, "
            f"{fwd}; hvd_flash_bwd {bwd.block_q}x{bwd.block_k}, {form}, "
            f"{math.prod(bwd_grid)} steps, "
            f"{crossed}, sT and dpT written {pa.BWD_AHEAD} ahead, a clean "
            f"tile in {len(pa.bwd_tile_pieces(*bwd[:2], False))}, dqT "
            f"[{D}, {bwd.block_q}] a q tile, VMEM "
            f"estimate {pa.flash_bwd_vmem_bytes(*bwd, D, 2) / 2 ** 20:.1f} "
            f"MiB{band}")


def _check_flagship(smoke: Smoke, hvd) -> None:
    """Two steps of the flagship transformer through make_train_step at
    head_dim 128, with the attention and cross-entropy kernels asserted in
    the compiled program."""
    import math
    import numpy as np
    import jax
    import optax
    from horovod_tpu.models.transformer import (
        TransformerConfig, init_opt_state, init_params, make_train_step,
        shard_batch, shard_params)

    z = smoke.sizes
    cfg = TransformerConfig(**z.gpt)
    mesh = hvd.build_mesh(dp=-1)
    params = shard_params(
        init_params(np.random.RandomState(smoke.seed), cfg, 1), cfg, mesh)
    tx = optax.adamw(1e-4)
    opt_state = init_opt_state(tx, params, mesh, cfg)
    rng = np.random.RandomState(smoke.seed + 1)
    B, S = z.gpt_batch, cfg.max_seq
    tokens = rng.randint(0, cfg.vocab_size, (B, S)).astype(np.int32)
    tokens, targets = shard_batch(tokens, np.roll(tokens, -1, 1), mesh)

    step = make_train_step(cfg, mesh, tx)
    t0 = time.perf_counter()
    compiled = step.lower(params, opt_state, tokens, targets).compile()
    compile_s = time.perf_counter() - t0
    in_program = {k: _has_kernel(compiled, k)
                  for k in ("hvd_flash_attention",
                            *_flash_bwd_kernels(cfg.head_dim),
                            "hvd_fused_xent")}
    if smoke.on_chip:
        check(all(in_program.values()),
              f"kernels missing from the flagship step: {in_program}")
    losses = []
    for _ in range(2):
        params, opt_state, loss, _aux = compiled(params, opt_state, tokens,
                                                 targets)
        losses.append(float(loss))
    check(all(math.isfinite(x) for x in losses), f"non-finite: {losses}")

    # the same d_model over four times the heads (1024 / 32 heads,
    # head_dim 32): a causal shape the kernels leave to XLA (a head of 64
    # they take: _check_banded at Sizes.narrow)
    shapes = ((B, S, cfg.n_heads, cfg.head_dim),
              (B, S, 4 * cfg.n_heads, cfg.head_dim // 4))
    paths = {f"{s[2]} heads x head_dim {s[3]}": _attention_path(s)
             for s in shapes}
    if smoke.on_chip:
        kernel, xla = paths.values()
        check(kernel.startswith("pallas hvd_flash_attention ")
              and xla == "xla _plain_attention", str(paths))
    smoke.emit("kernels", model="flagship transformer", **z.gpt,
               batch=B, compile_seconds=round(compile_s, 3),
               kernels_in_compiled_step=in_program if smoke.on_chip
               else "not checked (rehearsal: the CPU takes the XLA paths)",
               losses=[round(x, 4) for x in losses],
               attention_path=paths,
               xent_path=_xent_path(B * S, cfg.vocab_size),
               compiled_step_bytes=_step_bytes(compiled.memory_analysis()),
               hbm_live_arrays=_memory(jax.devices()[0]))


def phase_kernels(smoke: Smoke, hvd) -> None:
    _check_flash(smoke, smoke.sizes.attn)
    _check_flash_cells(smoke)
    _check_sparse(smoke)
    _check_block(smoke)
    _check_xent(smoke)
    _check_gmm(smoke)
    _check_ssm(smoke)
    _check_delta(smoke)
    _check_delta(smoke, key_heads=smoke.sizes.delta[1] // 2)
    _check_delta_heads_on_lanes(smoke)
    _check_dense_hybrid(smoke)
    _check_gated_norm(smoke)
    _check_short_conv(smoke)
    _check_embed(smoke)
    _check_codec(smoke)
    _check_flagship(smoke, hvd)


# ---------------------------------------------------------------------------
# four chips: what exists only across chips, and what it is compared with
# ---------------------------------------------------------------------------

def _bert_three_steps(smoke: Smoke, hvd, mesh) -> tuple:
    """(losses, compiled step, facts about placement) of 3 BERT steps."""
    import jax
    _cfg, step, params, opt_state, batch = _bert_setup(hvd, mesh, smoke,
                                                       smoke.sizes.bert4)
    compiled = step.lower(params, opt_state, batch).compile()
    n_mesh = mesh.devices.size
    leaves = jax.tree_util.tree_leaves(params)
    facts = {
        "batch_shard_devices": sorted(
            s.device.id for s in batch["input_ids"].addressable_shards),
        "params_replicated_on": min(
            len(x.sharding.device_set) if x.sharding.is_fully_replicated
            else 0 for x in leaves),
        "mesh_devices": n_mesh,
        "global_batch_and_seq": smoke.sizes.bert4,
    }
    losses = []
    for _ in range(3):
        params, opt_state, loss = compiled(params, opt_state, batch)
        losses.append(float(loss))
    facts["hbm_live_arrays_per_device"] = {
        str(d.id): _memory(d) for d in mesh.devices.flat}
    facts["compiled_step_bytes_per_device"] = _step_bytes(
        compiled.memory_analysis())
    return losses, compiled, facts


def _four_bert(smoke: Smoke, hvd) -> None:
    import math
    import jax
    mesh4 = hvd.build_mesh(dp=-1)
    losses4, step4, facts4 = _bert_three_steps(smoke, hvd, mesh4)
    check(len(set(facts4["batch_shard_devices"])) == 4,
          f"batch is not on 4 distinct devices: {facts4}")
    check(facts4["params_replicated_on"] == 4,
          f"params are not replicated on all 4 devices: {facts4}")
    text = step4.as_text()
    check("all-reduce" in text, "no all-reduce in the dp=4 compiled step")
    kernels = {k: _has_kernel(step4, k) for k in BLOCK_KERNELS}
    if smoke.on_chip:
        # each chip's kernels on its own rows: were the call not split,
        # q, k and v would be gathered onto every chip
        check(all(kernels.values()) and "all-gather" not in text,
              f"dp=4 step: kernels {kernels}, all-gather "
              f"{'all-gather' in text}")
    del step4, text
    smoke.emit("four_chips", what="bert dp=4", losses=losses4,
               all_reduce_in_step=True,
               attention_kernels_in_step=kernels if smoke.on_chip
               else "not checked (rehearsal: the CPU takes the XLA path)",
               **facts4)

    mesh1 = hvd.build_mesh(dp=-1, devices=jax.devices()[:1])
    losses1, _step1, facts1 = _bert_three_steps(smoke, hvd, mesh1)
    rel = [abs(a - b) / abs(b) for a, b in zip(losses4, losses1)]
    smoke.emit("four_chips", what="bert one device (same seed and batch)",
               losses=losses1, rel_diff_vs_dp4=rel, tol=BERT_MESH_RTOL,
               **facts1)
    check(all(math.isfinite(x) for x in losses4 + losses1), "non-finite")
    check(max(rel) <= BERT_MESH_RTOL,
          f"dp=4 and one-device losses differ by {rel}: "
          f"{losses4} vs {losses1}")


def _loss_and_grads(cfg, mesh_kwargs, B, S):
    """Loss and a few gradient leaves of the flagship on one layout, on
    __graft_entry__._run_layout's weights and batch. The flagship's
    gradient sync sums over the data shards where the loss averaged
    (ROADMAP A17): divided out here, so that layouts compare."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from horovod_tpu.models import (init_params, make_grad_fn, shard_batch,
                                    shard_params)
    from horovod_tpu.parallel.mesh import build_mesh
    mesh = build_mesh(**mesh_kwargs)
    params = shard_params(
        init_params(np.random.RandomState(0), cfg, 1), cfg, mesh)
    tokens = np.random.RandomState(1).randint(0, cfg.vocab_size, (B, S))
    tokens, targets = shard_batch(
        jnp.asarray(tokens, jnp.int32),
        jnp.asarray(np.roll(tokens, -1, 1), jnp.int32), mesh)
    loss, aux, grads = jax.jit(make_grad_fn(cfg, mesh))(params, tokens,
                                                        targets)
    leaves = {k: np.asarray(grads["layers"][k], np.float64) / mesh.size
              for k in ("router", "we1", "we2", "wq")}
    return float(loss + aux["aux_loss"]), leaves


def _four_layouts(smoke: Smoke) -> None:
    """The two n=4 layouts of __graft_entry__._dryrun_child, on the real
    devices, first-step loss against one device; for the MoE, at widths
    the hvd_moe_gmm kernels take, the gradients too."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import __graft_entry__ as graft
    from horovod_tpu.parallel.moe import GMM_NAME, gmm_path

    cfg = graft._flagship_cfg(tiny=True)
    B, S = 8, 32
    one = dict(dp=1, devices=jax.devices()[:1])

    dense4 = graft._run_layout(cfg, dict(dp=2, tp=2), 1, B, S)
    dense1 = graft._run_layout(cfg, one, 1, B, S)
    smoke.emit("four_chips", what="flagship dense dp=2 tp=2 vs one device",
               loss_4=dense4, loss_1=dense1, diff=abs(dense4 - dense1),
               tol=LAYOUT_ATOL)
    check(abs(dense4 - dense1) <= LAYOUT_ATOL, "dense layout loss differs")

    # the expert layer is dropless, so no capacity per token group stands
    # between the layouts: ep x sp computes the one-device loss, and its
    # gradients. 128-wide, so that on the chip the experts run on the
    # kernels: an ep shard's groups end before its rows do, and what the
    # kernel leaves unwritten there must reach no gradient.
    moe_cfg = dataclasses.replace(cfg, n_experts=4, n_microbatches=1,
                                  d_model=128, d_ff=128)
    k = moe_cfg.moe_top_k
    paths = {"ep=2 sp=2": gmm_path(B * S * k // 2, 128, 128, jnp.float32),
             "one device": gmm_path(B * S * k, 128, 128, jnp.float32)}
    if smoke.on_chip:
        check(all(p.startswith(f"pallas {GMM_NAME} ")
                  for p in paths.values()), str(paths))
    with jax.default_matmul_precision("highest"):
        moe4, grads4 = _loss_and_grads(moe_cfg, dict(ep=2, sp=2), B, S)
        moe1, grads1 = _loss_and_grads(moe_cfg, one, B, S)
    grad_err = {
        name: float(np.linalg.norm(grads4[name] - g)
                    / np.linalg.norm(g)) for name, g in grads1.items()}
    smoke.emit("four_chips", what="flagship MoE ep=2 sp=2 vs one device",
               loss_4=moe4, loss_1=moe1, diff=abs(moe4 - moe1),
               tol=LAYOUT_ATOL, matmul_precision="highest",
               gmm_path=paths, grad_rel_l2=grad_err,
               grad_tol=LAYOUT_GRAD_RTOL)
    check(abs(moe4 - moe1) <= LAYOUT_ATOL, "MoE layout loss differs")
    check(all(e <= LAYOUT_GRAD_RTOL for e in grad_err.values()),
          f"MoE layout gradients differ: {grad_err}")


def _four_ring(smoke: Smoke, hvd) -> None:
    """Ring attention over sp=4 with the flash kernel as each step's block
    attention (its (o, lse) pair merged by logaddexp), against plain
    attention on the whole sequence."""
    import jax
    import jax.numpy as jnp
    from horovod_tpu.parallel.ring_attention import ring_attention

    mesh = hvd.build_mesh(dp=1, sp=4)
    shape = smoke.sizes.ring
    keys = jax.random.split(jax.random.PRNGKey(smoke.seed + 3), 4)
    q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16) for kk in keys[:3])
    w = jax.random.normal(keys[3], shape, jnp.float32)

    def ring(q, k, v):
        return ring_attention(q, k, v, mesh, "sp", causal=True,
                              use_flash=True, interpret=smoke.rehearsal)

    compiled = jax.jit(jax.grad(_weighted_sum(ring, w), (0, 1, 2))).lower(
        q, k, v).compile()
    if smoke.on_chip:
        check(all(_has_kernel(compiled, k) for k in (
            "hvd_flash_attention", *_flash_bwd_kernels(shape[-1]))),
              "the flash kernels are not all in the ring attention "
              "program")
        check("collective-permute" in compiled.as_text(),
              "no ring permute in the program")
    got = compiled(q, k, v)
    want = jax.jit(jax.grad(_weighted_sum(_reference_attention, w),
                            (0, 1, 2)))(q, k, v)
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        err = _rel_err(g, r)
        smoke.emit("four_chips", what=f"ring attention sp=4 grad {name}",
                   shape=shape, err=err, tol=FLASH_TOL)
        check(err <= FLASH_TOL, f"ring attention {name}: {err}")


def phase_four_chips(smoke: Smoke, hvd) -> None:
    _four_bert(smoke, hvd)
    _four_layouts(smoke)
    _four_ring(smoke, hvd)


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-chip phase and its comparison")
    ap.add_argument("--rehearse", action="store_true",
                    help="off-chip walk of the phases at tiny sizes; "
                         "ends ok: false, exit 3")
    ap.add_argument("--seed", type=int, default=0)
    smoke = Smoke(ap.parse_args(argv))

    if smoke.chips == 4:
        # the MoE layout's reference mesh (and the rehearsal) needs four
        # host devices; must be set before JAX starts a backend
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4").strip()

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    if smoke.on_chip:   # the rehearsal's CPU programs are not worth keeping
        compile_cache.enable()
    device = phase_device(smoke)
    hvd.init()
    try:
        if smoke.chips == 4:
            phase_four_chips(smoke, hvd)
        else:
            phase_train(smoke, hvd)
            phase_kernels(smoke, hvd)
    finally:
        hvd.shutdown()
    if smoke.rehearsal:
        print(json.dumps({"ok": False, "rehearsal": True,
                          "note": "every phase walked off-chip; nothing "
                                  "here ran on a TPU", "device": device}),
              flush=True)
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
