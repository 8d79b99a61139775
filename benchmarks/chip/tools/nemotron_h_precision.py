"""The two readings ``reference/nemotron_h.py``'s loss bound lies between,
on the check's own batch at the cell's widths:

    python3 benchmarks/chip/tools/nemotron_h_precision.py \
        --seeds <n>,<n>,... [--rehearse]

For every seed, the relative distance of a loss from the float32
reference's (``run.py``'s own statistic):

* ``sound``: the program as it is (bfloat16 operands; float32 per-token
  losses, router, combine, and the scan's time steps, decays and carried
  state);
* ``reference_bf16``: the reference itself computed in bfloat16 throughout
  (parameters, activations, the recurrence's decays and state, logits,
  log-sum-exp, the mean), the nearest precision below the configuration's.

One JSON line a seed, then one with both ranges; every line names the
device. Exits 1 unless every ``sound`` seed is inside ``TOLERANCE
["loss_rel"]`` and ``reference_bf16`` is outside it on at least three seeds
of four (a bfloat16 loss can round to within the bound of the float32 one).
The gradient bound admits the router's near-ties and is not held here
(tests/test_nemotron_h.py holds the gradients, in float32 at 1e-4). How
TOLERANCE's numbers were taken; ``tools/smallthinker_precision.py``'s way.
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
    ap.add_argument("--workload", default="nemotron-3-nano-30b-a3b.s8192")
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
    bound = reference.TOLERANCE["loss_rel"]
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])

    @jax.jit
    def plain(params, batch):
        with jax.default_matmul_precision("highest"):
            return reference.losses(params, batch, sizes)[0]

    @jax.jit
    def low(params, batch):
        return reference.losses(jax.tree_util.tree_map(
            lambda a: a.astype(jnp.bfloat16), params), batch, sizes)[0]

    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        cell = adapter.Cell(config, job, mesh, seed)
        batch = jax.device_put(
            adapter.host_batch(config, job, seed, -1,
                               cell.check_sequences()),
            cell.check_sharding())
        want = float(plain(cell.plain_params(), batch))
        row = {"seed": seed, "loss_reference": want}
        for name, got in (
                ("sound", cell.program_loss_and_grads(batch)[0]),
                ("reference_bf16", low(cell.plain_params(), batch))):
            row[name] = abs(float(got) - want) / abs(want)
        rows.append(row)
        print(json.dumps({"event": "precision", **device,
                          "rehearsal": args.rehearse, **row}), flush=True)
        del cell
    hvd.shutdown()
    outside = sum(r["reference_bf16"] > bound for r in rows)
    result = {
        "cell": args.workload, "device": device, "rehearsal": args.rehearse,
        "loss_rel_bound": bound, "seeds": len(rows),
        **{name: [min(r[name] for r in rows), max(r[name] for r in rows)]
           for name in ("sound", "reference_bf16")},
        "reference_bf16_outside": outside}
    result["ok"] = bool(all(r["sound"] <= bound for r in rows)
                        and 4 * outside >= 3 * len(rows))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
