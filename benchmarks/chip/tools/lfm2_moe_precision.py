"""The two readings ``reference/lfm2_moe.py``'s loss bound lies between, on
the check's own batch at the cell's widths:

    python3 benchmarks/chip/tools/lfm2_moe_precision.py \
        --seeds <n>,<n>,... [--steps <n>] [--embedding-std <x>]
        [--router-std <x>] [--rehearse]

For every seed, the relative distance of a loss from the float32
reference's (``run.py``'s own statistic):

* ``sound``: the program as it is (bfloat16 operands; a float32 per-token
  loss, router, combine, heads' norms and mixer gate chain), with the
  largest of its named gradients' distances beside it (``sound_grad_max``,
  by leaf in ``grad_rel_l2``);
* ``reference_bf16``: the reference itself computed in bfloat16 throughout
  (parameters, activations, the rotary table, the gate chain, router,
  logits, log-sum-exp, the mean), the nearest precision below the
  configuration's, with the largest of ITS named gradients' distances
  beside it (``reference_bf16_grad_max``): the second reading of the
  gradient bound.

With ``--steps n`` each seed's cell then takes ``n`` AdamW steps on the
cell's own batches and a line says what the routers did at the first, at
every tenth and at the last of them: ``max_expert_load`` (the fullest
expert's share of a layer's assignments over the mean share) and
``held_rows`` (the assignments to the experts held here, four expert
layers of 16 384 tokens at top-4 of which an eighth fall here: 32 768 by
arithmetic, 1024 rows an expert), which is how ``assumed.embedding_std`` and
``assumed.router_std`` were found (``--embedding-std`` and ``--router-std``
draw the table and the routers at another scale than the configuration's).

One JSON line a seed, then one with both ranges; every line names the
device. Exits 1 unless every ``sound`` seed is inside both of
``TOLERANCE``'s bounds and ``reference_bf16`` is outside ``TOLERANCE
["loss_rel"]`` on every seed (it need not be outside the gradient bound: one
of the cell's limits fails the lower precision, not each). The gradient
bound admits the router's near-ties: the sound program's leaves are printed
for it and tests/test_lfm2_moe.py holds the gradients, in float32 at 1e-4.
How TOLERANCE's numbers were taken; ``tools/laguna_precision.py``'s way.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="lfm2-24b-a2b.s8192")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--embedding-std", type=float)
    ap.add_argument("--router-std", type=float)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import run as harness
    _bench, _entry, config, job = harness.load_cell(args.workload,
                                                    args.rehearse)
    for key, value in (("embedding_std", args.embedding_std),
                       ("router_std", args.router_std)):
        if value is not None:
            config = {**config, "assumed": {**config["assumed"], key: value}}
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache
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
    bound = reference.TOLERANCE["loss_rel"]
    grad_bound = reference.TOLERANCE["grad_rel_l2"]
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])

    @jax.jit
    def rel_l2(g, w):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        return jnp.linalg.norm((g - w).ravel()) / jnp.linalg.norm(w.ravel())

    def low(params, paths, batch):
        # (the reference's cos and sin take the dtype of what they rotate)
        return reference.loss_and_grads(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params),
            paths, batch, sizes)

    def loads(cell, seed):
        """The routers' counters at the first and the last of ``--steps``
        AdamW steps on the cell's batches."""
        cell.init_optimizer(harness.make_optimizer(job))
        said = {}
        for i in range(args.steps):
            batch = jax.device_put(
                adapter.host_batch(config, job, seed, i,
                                   job["batch_per_chip"]),
                cell.batch_sharding())
            loss = cell.step(batch)
            if i % 10 == 0 or i == args.steps - 1:
                said[f"step_{i}"] = {
                    "loss": float(loss),
                    **{k: float(cell.last_aux[k]) for k in (
                        "max_expert_load", "held_rows", "dropped")}}
        return said

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = adapter.Cell(config, job, mesh, seed)
        batch = jax.device_put(
            adapter.host_batch(config, job, seed, -1,
                               cell.check_sequences()),
            cell.check_sharding())
        want, want_grads = reference.loss_and_grads(
            cell.plain_params(), cell.leaf_paths, batch, sizes)
        want = float(want)
        got, got_grads = cell.program_loss_and_grads(batch)
        row = {"seed": seed, "loss_reference": want,
               "grad_rel_l2": {k: float(rel_l2(got_grads[k], want_grads[k]))
                               for k in want_grads}}
        row["sound_grad_max"] = max(row["grad_rel_l2"].values())
        low_loss, low_grads = low(cell.plain_params(), cell.leaf_paths, batch)
        row["reference_bf16_grad_max"] = max(
            float(rel_l2(low_grads[k], want_grads[k])) for k in want_grads)
        for name, got in (("sound", got), ("reference_bf16", low_loss)):
            row[name] = abs(float(got) - want) / abs(want)
        rows.append(row)
        print(json.dumps({"event": "precision", **device,
                          "rehearsal": args.rehearse, **row}), flush=True)
        if args.steps:
            print(json.dumps({"event": "loads", **device, "seed": seed,
                              "rehearsal": args.rehearse,
                              **loads(cell, seed)}), flush=True)
        del cell
    hvd.shutdown()
    outside = sum(r["reference_bf16"] > bound for r in rows)
    result = {
        "cell": args.workload, "device": device, "rehearsal": args.rehearse,
        "loss_rel_bound": bound, "grad_rel_l2_bound": grad_bound,
        "seeds": len(rows),
        "embedding_std": config["assumed"]["embedding_std"],
        "router_std": config["assumed"]["router_std"],
        **{name: [min(r[name] for r in rows), max(r[name] for r in rows)]
           for name in ("sound", "sound_grad_max", "reference_bf16",
                        "reference_bf16_grad_max")},
        "reference_bf16_outside": outside}
    result["ok"] = bool(all(r["sound"] <= bound
                            and r["sound_grad_max"] <= grad_bound
                            for r in rows) and outside == len(rows))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
