"""The two readings ``reference/qwen3_next.py``'s bounds lie between, on the
check's own batch at the cell's widths:

    python3 benchmarks/chip/tools/qwen3_next_precision.py \
        --seeds <n>,<n>,... [--steps <n>] [--embedding-std <x>] \
        [--loads-only] [--rehearse]

is ``tools/kimi_linear_precision.py`` on ``qwen3-next-80b-a3b.s8192``: one
tool for both forms of the delta rule (its lines, options and exit code are
described there). ``reference/qwen3_next.py``'s recurrence is written on
that reference's seams, so ``scan_below`` rounds the operands of every
product of a position to bfloat16, the decay factor among them, and carries
the state in bfloat16; ``held_rows`` is 4 x 5120 by arithmetic here.

    python3 benchmarks/chip/tools/qwen3_next_precision.py --through-check \
        --seeds <n>,<n>,... [--rehearse]

puts the control (the reference in bfloat16 throughout: parameters,
activations, decays, state, router, logits, log-sum-exp, the mean) in the
program's place in ``run.py``'s own ``reference_check``, the function that
decides a run's ``correct``, and prints what it said, one JSON line a seed.
Exits 1 unless the check refuses the control on every seed and the control's
loss is a bfloat16 number (a float32 table on the way lifts everything after
it to float32, and the control then reads like the reference).
"""

from __future__ import annotations

import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [HERE, os.path.dirname(os.path.dirname(HERE)),
                os.path.join(HERE, "tools")]

WORKLOAD = "qwen3-next-80b-a3b.s8192"


def through_check(seeds: list, rehearse: bool) -> int:
    import run as harness
    _bench, _entry, config, job = harness.load_cell(WORKLOAD, rehearse)
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ.setdefault("HVD_TPU_PROFILE_ON_ANOMALY", "0")
    import jax
    import jax.numpy as jnp
    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache
    d0 = jax.devices()[0]
    if not rehearse and d0.platform != "tpu":
        raise harness.BenchFailure("no TPU; --rehearse walks the tiny sizes")
    if not rehearse:
        compile_cache.enable()
    hvd.init()
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    reference = importlib.import_module(f"reference.{config['adapter']}")
    sizes = adapter.shapes(config, job)
    mesh = hvd.build_mesh(devices=jax.devices()[:1], **job["mesh"])

    class ControlAsProgram:
        """The cell, the control where ``reference_check`` asks for the
        program's loss and gradients."""

        def __init__(self, cell):
            self.cell, self.dtypes = cell, None

        def __getattr__(self, name):
            return getattr(self.cell, name)

        def program_loss_and_grads(self, batch):
            low = jax.tree_util.tree_map(
                lambda a: a.astype(jnp.bfloat16), self.cell.plain_params())
            loss, grads = reference.loss_and_grads(
                low, self.cell.leaf_paths, batch, sizes)
            self.dtypes = {str(x.dtype) for x in [loss, *grads.values()]}
            return loss, grads

    refused = 0
    for seed in seeds:
        control = ControlAsProgram(adapter.Cell(config, job, mesh, seed))
        check = harness.reference_check(adapter, reference, control, config,
                                        job, seed)
        low = control.dtypes == {"bfloat16"}
        refused += (not check["ok"]) and low
        print(json.dumps({
            "event": "control_through_reference_check", "seed": seed,
            "platform": d0.platform, "kind": d0.device_kind,
            "rehearsal": rehearse, "control_dtypes": sorted(control.dtypes),
            **check}), flush=True)
        del control
    hvd.shutdown()
    print(json.dumps({"cell": WORKLOAD, "seeds": len(seeds),
                      "control_refused": refused,
                      "ok": refused == len(seeds)}), flush=True)
    return 0 if refused == len(seeds) else 1


def main() -> int:
    argv = sys.argv[1:]
    if "--through-check" in argv:
        seeds = [int(s) for s in argv[argv.index("--seeds") + 1].split(",")]
        return through_check(seeds, "--rehearse" in argv)
    if "--workload" not in argv:
        sys.argv[1:1] = ["--workload", WORKLOAD]
    import kimi_linear_precision
    return kimi_linear_precision.main()


if __name__ == "__main__":
    sys.exit(main())
