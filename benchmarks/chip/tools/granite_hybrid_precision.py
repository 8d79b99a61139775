"""The two readings ``reference/granite_hybrid.py``'s bounds lie between, on
the check's own batch at the cell's widths:

    python3 benchmarks/chip/tools/granite_hybrid_precision.py \
        --seeds <n>,<n>,... [--low <part>] [--rehearse]

For every seed, ``run.py``'s own statistics against the float32 reference:
the loss's relative distance and every named leaf's gradient's relative L2
distance, of

* ``sound``: the program as it is (bfloat16 operands; float32 parameters,
  per-token losses, the scan's time steps, sums, decays and carried state,
  the gate + norm and every norm's statistics);
* ``reference_bf16``: the reference itself computed in bfloat16 throughout
  (parameters, activations, the recurrence's decays and state, logits,
  log-sum-exp, the mean), the nearest precision below the configuration's.

``--low <part>`` puts ONE of the parts the configuration states as float32
into bfloat16 in the program before the ``sound`` reading: ``decays`` (every
``exp`` of a sum of ``dt a``, in the kernels and in the ``jax.numpy`` form),
``carried_state`` (the state a chunk hands on), ``sums`` (the cumulative
sums of ``dt a``), ``gate_norm`` (the gate and the norm over 4096 channels),
``parameters`` (every leaf rounded to bfloat16).

One JSON line a seed, then one with the ranges; every line names the device.
Exits 1 unless every ``sound`` seed is inside both of ``TOLERANCE``'s bounds
and every ``reference_bf16`` seed is outside at least one of them: with
``--low`` an exit of 1 says that the bounds see that part. How TOLERANCE's
numbers were taken; ``tools/nemotron_h_precision.py``'s way, with the
gradients held too (no router here: no leaf hangs on a near-tied choice).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

PARTS = ("decays", "carried_state", "sums", "gate_norm", "parameters")


def lower(part: str) -> None:
    """Put one float32 part of the program into bfloat16, at the seams the
    tests swap (``tests/test_pallas_ssm.py``, ``tests/test_nemotron_h.py``)."""
    import jax.numpy as jnp
    from horovod_tpu.models import mamba
    from horovod_tpu.ops import pallas_ssm

    def rounded(f):
        return lambda *a, **k: f(*a, **k).astype(jnp.bfloat16).astype(
            jnp.float32)
    if part == "decays":
        def decay(log_decay):
            return jnp.exp(log_decay.astype(jnp.bfloat16)).astype(jnp.float32)
        pallas_ssm._decay = mamba._ssm_decay = decay
    elif part == "carried_state":
        pallas_ssm._carry = rounded(pallas_ssm._carry)
        mamba._carried_states = rounded(mamba._carried_states)
    elif part == "sums":
        mamba._chunk_sums = rounded(mamba._chunk_sums)
    elif part == "gate_norm":
        norm = mamba._gated_norm
        mamba._gated_norm = lambda y, z, w, groups, eps: norm(
            y.astype(jnp.bfloat16), z.astype(jnp.bfloat16),
            w.astype(jnp.bfloat16), groups, eps).astype(jnp.bfloat16)
    elif part != "parameters":
        raise ValueError(part)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="granite-4.0-h-micro.s4096")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--low", choices=PARTS)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import run as harness
    _bench, _entry, config, job = harness.load_cell(args.workload,
                                                    args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache
    from trees import get_leaves, with_leaves
    d0 = jax.devices()[0]
    if not args.rehearse and d0.platform != "tpu":
        raise harness.BenchFailure("no TPU; --rehearse walks the tiny sizes")
    if not args.rehearse:
        compile_cache.enable()
    device = {"platform": d0.platform, "kind": d0.device_kind, "count": 1}
    hvd.init()
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    reference = importlib.import_module(f"reference.{config['adapter']}")
    sizes = adapter.shapes(config, job)
    bounds = reference.TOLERANCE
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])
    if args.low:
        lower(args.low)

    def bf16(tree):
        return jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), tree)

    @jax.jit
    def distances(got_loss, got, want_loss, want):
        def rel_l2(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.linalg.norm((g - w).ravel()) / jnp.linalg.norm(
                w.ravel())
        return (jnp.abs(got_loss.astype(jnp.float32) - want_loss)
                / jnp.abs(want_loss),
                {k: rel_l2(got[k], want[k]) for k in want})

    def reading(got, want):
        loss, grads = jax.device_get(distances(*got, *want))
        grads = {k: float(v) for k, v in grads.items()}
        worst = max(grads, key=grads.get)
        return {"loss_rel": float(loss), "grad_rel_l2": grads[worst],
                "worst_leaf": worst, "grads": grads}

    def outside(r) -> bool:
        return (r["loss_rel"] > bounds["loss_rel"]
                or r["grad_rel_l2"] > bounds["grad_rel_l2"])

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = adapter.Cell(config, job, mesh, seed)
        batch = jax.device_put(
            adapter.host_batch(config, job, seed, -1,
                               cell.check_sequences()),
            cell.check_sharding())
        params = cell.plain_params()
        want = reference.loss_and_grads(params, cell.leaf_paths, batch, sizes)
        if args.low == "parameters":
            cell.params = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16).astype(a.dtype), params)
        row = {"seed": seed, "loss_reference": float(want[0]),
               "sound": reading(cell.program_loss_and_grads(batch), want)}
        low_loss, low_grads = jax.jit(
            lambda p, b: jax.value_and_grad(
                lambda lv: reference.loss(
                    with_leaves(p, cell.leaf_paths, lv), b, sizes))(
                        get_leaves(p, cell.leaf_paths)))(
                        bf16(params), batch)
        row["reference_bf16"] = reading((low_loss, low_grads), want)
        rows.append(row)
        print(json.dumps({"event": "precision", **device, "low": args.low,
                          "rehearsal": args.rehearse, **row}), flush=True)
        del cell, params, want
    hvd.shutdown()
    result = {
        "cell": args.workload, "device": device, "rehearsal": args.rehearse,
        "low": args.low, "bounds": bounds, "seeds": len(rows),
        **{f"{name}.{key}": [min(r[name][key] for r in rows),
                             max(r[name][key] for r in rows)]
           for name in ("sound", "reference_bf16")
           for key in ("loss_rel", "grad_rel_l2")},
        "sound_outside": sum(outside(r["sound"]) for r in rows),
        "reference_bf16_outside": sum(outside(r["reference_bf16"])
                                      for r in rows)}
    result["ok"] = bool(result["sound_outside"] == 0
                        and result["reference_bf16_outside"] == len(rows))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
