"""The rehearsals that cost no chip time (README.md lists the commands).

    python3 benchmarks/chip/rehearse.py compile <cell>

compiles the cell's real train step, at its real sizes, for a *described*
``v5e:2x2`` (no chip attached), and prints ``memory_analysis()`` per
device, how that stands to the fit ``run.py`` holds on the chip (the limit,
the margin, the headroom), the collectives and the Pallas kernels found in
the program. A
compile is not a chip run: it says that the TPU compiler accepts the step
and what it needs, nothing about results or times.

The other two rehearsals are ``run.py --rehearse`` (a cell's tiny sizes on
the CPU; a four-chip cell on four virtual devices).

The kernels' gate asks ``jax.default_backend()``; this script answers
"tpu" while it lowers, so that the step it compiles is the chip's.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def compile_cell(name: str) -> dict:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path[:0] = [HERE, ROOT]
    import jax
    from jax.experimental import topologies
    import horovod_tpu as hvd
    import run as harness

    _bench, entry, config, job = harness.load_cell(name, tiny=False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = hvd.build_mesh(devices=topo.devices[:entry["chips"]],
                          **job["mesh"])
    adapter = importlib.import_module(f"adapters.{config['adapter']}")
    step, shapes = adapter.abstract_step(config, job, mesh,
                                         harness.make_optimizer(job))

    real_backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    t0 = time.perf_counter()
    try:
        compiled = step.lower(*shapes).compile()
    finally:
        jax.default_backend = real_backend
    text = compiled.as_text()
    nbytes = harness.step_bytes(compiled.memory_analysis())
    try:        # the fit run.py holds every run on the chip to
        fit = harness.hold_fit(
            nbytes, harness.read_json(HERE, "peaks.json")["TPU v5 lite"])
    except harness.BenchFailure as e:
        fit = {"refused": str(e)}
    collectives = {}
    for op in re.findall(r"= \S+ (all-reduce|reduce-scatter|all-gather|"
                         r"collective-permute|all-to-all)[-a-z]*\(", text):
        collectives[op] = collectives.get(op, 0) + 1
    kernels = sorted(set(re.findall(
        r"%(\w+)[.\d]* = [^\n]*custom_call_target=\"tpu_custom_call\"",
        text)))
    return {
        "cell": name, "compiled_for": "described v5e:2x2, no chip",
        "devices": int(mesh.size), "compile_seconds": time.perf_counter() - t0,
        "per_device_gb": {k: v / 1e9 for k, v in nbytes.items()},
        "fit": fit,
        "collectives_in_program": collectives,
        "pallas_kernels_in_program": kernels,
    }


if __name__ == "__main__":
    if len(sys.argv) != 3 or sys.argv[1] != "compile":
        sys.exit(__doc__)
    print(json.dumps(compile_cell(sys.argv[2]), indent=1))
