"""The readings ``reference/keye_vl2.py``'s bounds lie between, on the check's
own batch at the cell's widths:

    python3 benchmarks/chip/tools/keye_vl2_precision.py \
        --workload keye-vl-2.0-30b-a3b.s16384 --seeds <n>,<n>,... [--rehearse]

The hazard is the selection: the indexer's scores are bfloat16 products in
the program, so where a row's ``topk``-th and next scores lie within that
rounding the program attends to another key than the float32 reference. For
every seed, with ``run.py``'s own statistics (the loss's relative distance,
a gradient leaf's relative L2 distance):

* ``flipped``: the share of the program's (query, key) choices that the
  reference's own selection does not make (bits of ``index_selections``
  against ``reference.losses``' bits, over the rows that select);
* ``sound``: the program as it is against the reference as it is;
* ``forced``: the same with the reference told the program's selection
  (``loss_and_grads(.., selection=..)``): what is left is rounding, what went
  is what the differing choices explain;
* ``reference_bf16``: the reference itself computed in bfloat16 throughout
  (parameters, activations, scores, both softmaxes, the KL, the logits),
  the nearest precision below the configuration's: the loss only.

One JSON line a seed, then one with the ranges; every line names the device.
Exits 1 unless every ``sound`` seed is inside ``TOLERANCE`` (loss and every
leaf) and ``reference_bf16`` is outside the loss bound on at least three
seeds of four. How TOLERANCE's numbers were taken.
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
    ap.add_argument("--workload", default="keye-vl-2.0-30b-a3b.s16384")
    ap.add_argument("--seeds", required=True)
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

    @jax.jit
    def own_selection(params, batch):
        with jax.default_matmul_precision("highest"):
            return reference.losses(params, batch, sizes)[3]

    # the reference's rotary angles are float32 and would carry every later
    # activation up with them: rope returns what it was given
    rope = reference._rope

    @jax.jit
    def low(params, batch):
        low_params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params)
        reference._rope = lambda x, theta: rope(x, theta).astype(x.dtype)
        try:
            return reference.losses(low_params, batch, sizes)[0]
        finally:
            reference._rope = rope

    @jax.jit
    def flipped(ours, theirs):
        """Of the program's choices in the rows that select (more causal
        keys than ``topk``), the share the reference does not make."""
        rows = jnp.arange(ours.shape[2]) >= sizes["index_topk"]
        count = jax.lax.population_count
        away = jnp.sum(count(ours & ~theirs) * rows[:, None],
                       dtype=jnp.float32)
        return away / jnp.sum(count(ours) * rows[:, None], dtype=jnp.float32)

    def distances(got_loss, got, want_loss, want):
        def rel_l2(g, w):
            g, w = g.astype(jnp.float32), w.astype(jnp.float32)
            return float(jnp.linalg.norm((g - w).ravel())
                         / jnp.linalg.norm(w.ravel()))
        return {"loss_rel": abs(float(got_loss) - float(want_loss))
                / abs(float(want_loss)),
                "grad_rel_l2": {k: rel_l2(got[k], want[k]) for k in want}}

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = adapter.Cell(config, job, mesh, seed)
        batch = jax.device_put(
            adapter.host_batch(config, job, seed, -1,
                               cell.check_sequences()),
            cell.check_sharding())
        params = cell.plain_params()
        got_loss, got = cell.program_loss_and_grads(batch)
        ours = cell.program_selection(batch)
        row = {"seed": seed,
               "flipped": float(flipped(ours, own_selection(params, batch)))}
        for name, selection in (("sound", None), ("forced", ours)):
            want_loss, want = reference.loss_and_grads(
                params, cell.leaf_paths, batch, sizes, selection=selection)
            row[name] = distances(got_loss, got, want_loss, want)
            row["loss_reference" if selection is None
                else "loss_reference_forced"] = float(want_loss)
        row["reference_bf16"] = abs(float(low(params, batch))
                                    - row["loss_reference"]) / abs(
                                        row["loss_reference"])
        rows.append(row)
        print(json.dumps({"event": "precision", **device,
                          "rehearsal": args.rehearse, **row}), flush=True)
        del cell, params, got, ours
    hvd.shutdown()

    def span(values):
        values = list(values)
        return [min(values), max(values)]
    outside = sum(r["reference_bf16"] > tol["loss_rel"] for r in rows)
    result = {
        "cell": args.workload, "device": device, "rehearsal": args.rehearse,
        "tolerance": tol, "seeds": len(rows),
        "flipped": span(r["flipped"] for r in rows),
        "reference_bf16": span(r["reference_bf16"] for r in rows),
        "reference_bf16_outside": outside}
    for name in ("sound", "forced"):
        result[name] = {
            "loss_rel": span(r[name]["loss_rel"] for r in rows),
            "grad_rel_l2": {k: span(r[name]["grad_rel_l2"][k] for r in rows)
                            for k in rows[0][name]["grad_rel_l2"]}}
    result["ok"] = bool(
        all(r["sound"]["loss_rel"] <= tol["loss_rel"]
            and max(r["sound"]["grad_rel_l2"].values()) <= tol["grad_rel_l2"]
            for r in rows) and 4 * outside >= 3 * len(rows))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
