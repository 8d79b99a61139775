"""What differing router choices explain of the ``olmoe`` reference check,
and the expert layer's counters over a few train steps.

    python3 benchmarks/chip/tools/olmoe_routing.py --workload olmoe-1b-7b.s4096 \
        --seed <n> [--steps 20] [--rehearse]

The program's router reads a bfloat16 residual, the reference a float32
one, so a token whose k-th and (k+1)-th probabilities lie within that
rounding chooses another expert on each side (reference/olmoe.py,
``TOLERANCE``). This tool makes the check's comparison three ways on the
check's own batch: against the reference as ``run.py`` calls it (its own
choices), against the reference forced to the program's choices
(``loss_and_grads(.., choices=..)``), and counts the (token, slot)
assignments that differ. Then ``--steps`` train steps, from which it
reports ``max_expert_load`` and ``dropped`` (the step's auxiliary output).
The last line of standard output is one JSON object; every line names the
device. How TOLERANCE's numbers in PERF.md were taken.

The cell's ``correct`` has to let the differing choices through (15 % of a
gradient leaf), which is wider than a precision fault is (a bfloat16 router
softmax or combine). With the choices forced, what is left is the
program's rounding, 1.2-1.5 % on the chip: exits 1 if a leaf is further
than :data:`FORCED_GRAD_TOL` from the forced reference, or the loss further
than the cell's own bound. So a precision regression is caught on the chip
by this tool, and off it by tests/test_olmoe.py in float32.
"""

from __future__ import annotations

import argparse
import functools
import importlib
import itertools
import json
import os
import sys
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE))]

import run as harness       # noqa: E402

#: a gradient leaf against the reference forced to the program's choices:
#: twice the worst measured on the chip (1.5 %; PERF.md section 6, PR 26)
FORCED_GRAD_TOL = 3e-2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    _bench, entry, config, job = harness.load_cell(args.workload,
                                                  args.rehearse)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.data.data_loader import device_prefetch
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
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])
    cell = adapter.Cell(config, job, mesh, args.seed)
    sizes = adapter.shapes(config, job)
    batch = jax.device_put(
        adapter.host_batch(config, job, args.seed, -1,
                           cell.check_sequences()), cell.check_sharding())
    ours = cell.program_choices(batch)
    theirs = jax.jit(lambda p, b: reference.losses(p, b, sizes)[4])(
        cell.plain_params(), batch)
    # a slot differs if the reference did not choose that expert for the token
    differ = 1.0 - float(jnp.mean(jnp.any(
        ours[..., :, None] == theirs[..., None, :], axis=-1)))
    tokens_differ = float(jnp.mean(jnp.any(
        jnp.sort(ours, -1) != jnp.sort(theirs, -1), axis=-1)))
    # run.py's own check, on the same batch, as it is and with the
    # reference held to the program's choices
    forced_reference = types.SimpleNamespace(
        TOLERANCE=reference.TOLERANCE,
        loss_and_grads=functools.partial(reference.loss_and_grads,
                                         choices=ours))
    own, forced = (
        harness.reference_check(adapter, ref, cell, config, job, args.seed)
        for ref in (reference, forced_reference))
    result = {
        "cell": args.workload, "seed": args.seed, "device": device,
        "rehearsal": args.rehearse, "sequences": cell.check_sequences(),
        "assignments": int(ours.size),
        "assignments_differ_share": differ,
        "tokens_with_a_differing_choice_share": tokens_differ,
        "against_reference_own_choices": own,
        "against_reference_forced_to_program_choices": forced,
    }
    print(json.dumps({"event": "routing", **device, **result}), flush=True)

    cell.init_optimizer(harness.make_optimizer(job))
    batches = device_prefetch(
        (adapter.host_batch(config, job, args.seed, i,
                            job["batch_per_chip"])
         for i in itertools.count()),
        cell.batch_sharding(), buffer_size=job["prefetch"])
    losses, aux = [], []
    for _ in range(args.steps):
        losses.append(cell.step(next(batches)))
        aux.append(cell.last_aux)
    losses = [float(x) for x in jax.device_get(losses)]
    aux = jax.device_get(aux)
    result["steps"] = {
        "n": args.steps, "first_loss": losses[0], "last_loss": losses[-1],
        **{k: [float(a[k]) for a in aux]
           for k in ("max_expert_load", "dropped", "load_balance_loss",
                     "router_z_loss")}}
    hvd.shutdown()
    result["forced_grad_tol"] = FORCED_GRAD_TOL
    result["ok"] = bool(
        forced["loss_rel"] <= forced["tolerance"]["loss_rel"]
        and max(forced["grad_rel_l2"].values()) <= FORCED_GRAD_TOL
        and not any(result["steps"]["dropped"]))
    print(json.dumps(result), flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except harness.BenchFailure as e:
        print(f"benchmarks/chip/tools/olmoe_routing.py: {e}",
              file=sys.stderr)
        sys.exit(2)
