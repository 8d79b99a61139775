#!/usr/bin/env python
"""The jaxpr of a benchmark cell's jitted train step (gradient and update) at
the cell's real shapes, as text: what a refactor of the model compares between
two checkouts to show that it left the cell's program alone. Usage:

    PYTHONHASHSEED=0 python ci/cell_jaxpr.py <checkout> <cell> <out file>

for a ``git archive`` of the parent and for the tree, then ``cmp`` the two
files. Nothing is allocated or compiled: the arguments are the adapter's
``abstract_step`` shapes on one described v5e device (no chip), and
``jax.default_backend`` answers "tpu" so that the kernels' paths are the
chip's. ``PYTHONHASHSEED`` fixes the order a ``frozenset`` prints in; a
function's address in a parameter (``<function f at 0x..>``) is taken out.
Several at once need ``ALLOW_MULTIPLE_LIBTPU_LOAD=1``.
"""

import importlib
import os
import re
import sys

root, cell, out = sys.argv[1:4]
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path[:0] = [os.path.join(root, "benchmarks", "chip"), root]

import jax                                              # noqa: E402
from jax.experimental import topologies                 # noqa: E402
import horovod_tpu as hvd                               # noqa: E402
import run as harness                                   # noqa: E402

assert hvd.__file__.startswith(os.path.abspath(root)), hvd.__file__
_bench, entry, config, job = harness.load_cell(cell, tiny=False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
mesh = hvd.build_mesh(devices=topo.devices[:entry["chips"]], **job["mesh"])
adapter = importlib.import_module(f"adapters.{config['adapter']}")
step, shapes = adapter.abstract_step(config, job, mesh,
                                     harness.make_optimizer(job))
jax.default_backend = lambda: "tpu"
text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(step)(*shapes)))
with open(out, "w") as f:
    f.write(text)
print(cell, len(text), "bytes", text.count("\n"), "lines")
