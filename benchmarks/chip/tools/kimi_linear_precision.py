"""The two readings ``reference/kimi_linear.py``'s bounds lie between, on the
check's own batch at the cell's widths:

    python3 benchmarks/chip/tools/kimi_linear_precision.py \
        --seeds <n>,<n>,... [--steps <n>] [--embedding-std <x>] \
        [--loads-only] [--rehearse]

For every seed, ``run.py``'s own statistics (the loss's relative distance,
each named leaf's gradient's relative L2 distance) from the float32
reference's, of

* ``sound``: the program as it is (bfloat16 operands; the delta rule's sums,
  decay factors, triangular inverse and carried state, the router and the
  per-token losses in float32);
* ``forced``: the same against the reference told the program's expert
  choices (what of ``sound`` a router's near-ties explain);
* ``below``: the reference itself computed in bfloat16 throughout
  (parameters, activations, the decays, the recurrence's products and its
  carried state, router, logits, log-sum-exp, the mean), the nearest
  precision below the configuration's;
* ``scan_below``: ISSUE 66's narrower reading: the reference in float32 at
  "highest" but for the delta rule, every product inside the recurrence on
  operands rounded to bfloat16 (float32 accumulation) and the state carried
  in bfloat16 from position to position. It moves the loss by 1e-6 and the
  named gradients by under 3 % (PERF.md section 6, PR 66): less than the
  sound program's own distance, so no bound can lie between the two and it
  is reported, not held.

With ``--steps n`` each seed's cell then takes ``n`` AdamW steps on the
cell's own batches and a line says what the routers and the decays did at
the first, at every tenth and at the last of them: ``max_expert_load`` (the
fullest expert's share of a layer's assignments over the mean share),
``held_rows`` (the assignments to the experts held here, four expert
layers: 4 x 2048 by arithmetic) and ``delta_min_log_decay`` (the most
negative sum of a chunk's log decays), which is how
``assumed.embedding_std`` was found (``--embedding-std`` draws the table at
another scale than the configuration's; ``--loads-only`` skips the
readings above).

One JSON line a seed, then one with the ranges; every line names the device.
Exits 1 unless every ``sound`` seed is inside both of ``TOLERANCE``'s bounds
and ``below`` is outside at least one of them on at least three seeds of
four. ``tools/glm4_moe_lite_precision.py``'s way.
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
    ap.add_argument("--workload", default="kimi-linear-48b-a3b.s8192")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--steps", type=int, default=0)
    ap.add_argument("--embedding-std", type=float)
    ap.add_argument("--loads-only", action="store_true")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    import run as harness
    _bench, _entry, config, job = harness.load_cell(args.workload,
                                                    args.rehearse)
    if args.embedding_std is not None:
        config = {**config, "assumed": {**config["assumed"],
                                        "embedding_std": args.embedding_std}}
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
    tol = reference.TOLERANCE
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])

    def below(params, paths, batch):
        """The reference in bfloat16 throughout."""
        return reference.loss_and_grads(
            jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), params),
            paths, batch, sizes)

    def scan_below(params, paths, batch):
        """The reference with the recurrence's products and state in
        bfloat16."""
        product, state = reference._product, reference._state

        def rounded(a, b, subscripts):
            return jnp.einsum(subscripts, a.astype(jnp.bfloat16),
                              b.astype(jnp.bfloat16),
                              preferred_element_type=jnp.float32)
        reference._product = rounded
        reference._state = lambda x: x.astype(jnp.bfloat16).astype(x.dtype)
        try:
            return reference.loss_and_grads(params, paths, batch, sizes)
        finally:
            reference._product, reference._state = product, state

    @jax.jit
    def errors(got_loss, got, want_loss, want):
        def rel_l2(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return jnp.linalg.norm((g - w).ravel()) / jnp.linalg.norm(
                w.ravel())
        return (jnp.abs(got_loss - want_loss) / jnp.abs(want_loss),
                {k: rel_l2(got[k], want[k]) for k in want})

    def distance(got, want):
        loss_rel, grad_rel = jax.device_get(errors(*got, *want))
        return {"loss_rel": float(loss_rel),
                "grad_rel_l2": {k: float(v) for k, v in grad_rel.items()}}

    def loads(cell, seed):
        """The routers' and the decays' counters over ``--steps`` AdamW
        steps on the cell's batches."""
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
                        "max_expert_load", "held_rows", "dropped",
                        "delta_min_log_decay")}}
        return said

    def inside(row) -> bool:
        return (row["loss_rel"] <= tol["loss_rel"]
                and max(row["grad_rel_l2"].values()) <= tol["grad_rel_l2"])

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = adapter.Cell(config, job, mesh, seed)
        batch = jax.device_put(
            adapter.host_batch(config, job, seed, -1,
                               cell.check_sequences()),
            cell.check_sharding())
        if not args.loads_only:
            params, paths = cell.plain_params(), cell.leaf_paths
            want = reference.loss_and_grads(params, paths, batch, sizes)
            got = cell.program_loss_and_grads(batch)
            row = {"seed": seed, "loss_reference": float(want[0]),
                   "sound": distance(got, want),
                   "forced": distance(got, reference.loss_and_grads(
                       params, paths, batch, sizes,
                       choices=cell.program_choices(batch))),
                   "below": distance(below(params, paths, batch), want),
                   "scan_below": distance(scan_below(params, paths, batch),
                                          want)}
            rows.append(row)
            print(json.dumps({"event": "precision", **device,
                              "rehearsal": args.rehearse, **row}),
                  flush=True)
        if args.steps:
            print(json.dumps({"event": "loads", **device, "seed": seed,
                              "rehearsal": args.rehearse,
                              **loads(cell, seed)}), flush=True)
        del cell
    hvd.shutdown()
    if args.loads_only:
        return 0

    def span(name, of):
        values = [of(r[name]) for r in rows]
        return [min(values), max(values)]
    outside = sum(not inside(r["below"]) for r in rows)
    result = {
        "cell": args.workload, "device": device, "rehearsal": args.rehearse,
        "tolerance": tol, "seeds": len(rows),
        "embedding_std": config["assumed"]["embedding_std"],
        **{f"{name}.loss_rel": span(name, lambda r: r["loss_rel"])
           for name in ("sound", "forced", "below", "scan_below")},
        **{f"{name}.grad_rel_l2.worst": span(
            name, lambda r: max(r["grad_rel_l2"].values()))
           for name in ("sound", "forced", "below", "scan_below")},
        "below_outside": outside}
    result["ok"] = bool(all(inside(r["sound"]) for r in rows)
                        and 4 * outside >= 3 * len(rows))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
