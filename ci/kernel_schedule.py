#!/usr/bin/env python
"""The static schedule of a Mamba-2 scan kernel (or of the index score pass's
backward kernel) compiled for a described TPU v5e, off the chip: the bundles of one grid step (the kernel's body is one
straight line a step, so bundles are about its cycles, the pipeline's waits
apart) and how many slots of each unit they fill, spills and fills apart.
From libtpu's own dump of its last passes; nothing runs and no time is
measured. PR 61 read the parent's and the change's ``hvd_ssm_scan_bwd`` this
way before the chip had said anything: 5235 and 4264 bundles a step at the
hybrid cell's shape for 2.349 and 1.881 ms a call on the chip (ratios 1.228
and 1.249), so a form that does not lower the count is not worth a chip call.

    python ci/kernel_schedule.py <checkout> fwd|bwd <heads a step> <chunk> \\
        <groups> [<positions>=4 chunks]
    python ci/kernel_schedule.py <checkout> index <index heads> <their width> \\
        <k tiles of the call> [<k tile>=1024]

e.g. ``. bwd 8 128 8`` (the cell nemotron-3-nano-30b-a3b.s8192's step) and
``. bwd 16 256 1`` (granite-4.0-h-micro.s4096's); ``. index 16 64 16`` is
``hvd_index_bwd`` (``ops/pallas_sparse_attention.py``) at the cell
keye-vl-2.0-30b-a3b.s16384's shapes, a live k tile a grid step (PR 69: 12 897
bundles, the MXU's slots 90 % filled, for 39.9 ms a step of 4352 live tiles
on the chip: 1.07 bundles' time a tile). The dump's flags are read
when libtpu starts, so the compile runs in a child process; beside a
described-topology test or another of these set
``ALLOW_MULTIPLE_LIBTPU_LOAD=1``. libtpu aborts after the dump (a report
template it does not ship); the files are whole by then.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys
import tempfile

_CHILD = r"""
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
root, which, heads, chunk, groups, positions = sys.argv[1:7]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from horovod_tpu.ops import pallas_ssm as ps
heads, chunk, groups, S = int(heads), int(chunk), int(groups), int(positions)
H, P, N = 64, 64, 128
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
def arg(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
x = arg((1, S, H, P), jnp.bfloat16)
dt = s = arg((1, S, H), jnp.float32)
b = c = arg((1, S, groups, N), jnp.bfloat16)
if which == "fwd":
    jax.jit(lambda *v: ps._forward(*v, chunk, False, heads, True)).lower(
        x, dt, s, b, c).compile()
else:
    lay = ps._layout(x, b, chunk, heads)
    states = arg((1, lay.n, lay.steps, N, lay.R * P), jnp.float32)
    dy = arg((1, S, H, P), jnp.float32)
    jax.jit(lambda x, dt, s, b, c, st, dy: ps._backward(
        x, dt, s, b, c, st, dy, chunk, False, heads)).lower(
        x, dt, s, b, c, states, dy).compile()
"""


_INDEX_CHILD = r"""
import os, sys
os.environ.setdefault("TPU_LOG_DIR", "disabled")
root, _, heads, dim, tiles, bk = sys.argv[1:7]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from horovod_tpu.ops import pallas_sparse_attention as ps
heads, dim, tiles, bk = int(heads), int(dim), int(tiles), int(bk)
jax.config.update("jax_enable_compilation_cache", False)
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
chip = SingleDeviceSharding(topo.devices[0])
def arg(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
span, f32 = tiles * bk, jnp.float32
jax.jit(lambda *v: ps.index_backward(*v, span, ps.Kernels(bk))).lower(
    arg((ps.ROWS, heads, dim), jnp.bfloat16), arg((ps.ROWS, heads), f32),
    arg((ps.MIN_BLOCK // dim, span, ps.MIN_BLOCK), jnp.bfloat16),
    arg((ps.ROWS, span), f32), arg((tiles, ps.PIECE, ps.ROWS), jnp.int8),
    arg((2, ps.ROWS), f32), arg((), f32), arg((), jnp.int32)).compile()
"""


def schedule(root: str, which: str, heads: int, chunk: int, groups: int,
             positions: int | None = None) -> dict:
    """{"bundles": .., "slots": {unit: filled slots}, "capacity": {unit: a
    bundle's}} of the kernel's final schedule, or {"error": ..}. ``which``
    ``"index"``: (index heads, their width, k tiles, the k tile) in the
    arguments' places."""
    if which == "index":
        from horovod_tpu.ops import pallas_sparse_attention
        name, child = pallas_sparse_attention.INDEX_BWD_NAME, _INDEX_CHILD
        positions = positions or 1024
    else:
        from horovod_tpu.ops import pallas_ssm
        name = pallas_ssm.BWD_NAME if which == "bwd" else pallas_ssm.FWD_NAME
        child = _CHILD
    with tempfile.TemporaryDirectory(prefix="kernel_schedule_") as out:
        env = dict(os.environ, JAX_PLATFORMS="cpu", LIBTPU_INIT_ARGS=(
            os.environ.get("LIBTPU_INIT_ARGS", "")
            + f" --xla_jf_dump_to={out} --xla_jf_dump_llo_text=true"
            " --xla_jf_dump_llo_pass_label_regex=final").strip())
        done = subprocess.run(
            [sys.executable, "-c", child, os.path.abspath(root), which,
             str(heads), str(chunk), str(groups),
             str(positions or 4 * chunk)],
            env=env, capture_output=True, text=True)
        # the forward's name is the start of the backward's
        found = {
            kind: [f for f in glob.glob(os.path.join(out, f"*{tail}"))
                   if re.search(rf"-{name}\.\d+-", f)]
            for kind, tail in (
                ("analysis", "schedule-analysis_final_bundles.txt"),
                ("slots", "final_hlo-static-per-bundle-utilization.txt"))}
        if not all(found.values()):
            return {"error": (done.stdout + done.stderr)[-600:]}
        with open(found["analysis"][0]) as f:
            bundles = int(re.search(r"total scheduled bundles:\s+(\d+)",
                                    f.read(400)).group(1))
        with open(found["slots"][0]) as f:
            lines = f.read().splitlines()
    units = [u.strip() for u in lines[1].split(",")]
    capacity = [int(v) for v in lines[2].split()]
    filled = [0] * len(units)
    for line in lines[4:]:
        row = line.split()
        if len(row) == len(units):
            filled = [a + int(v) for a, v in zip(filled, row)]
    return {"bundles": bundles, "slots": dict(zip(units, filled)),
            "capacity": dict(zip(units, capacity))}


if __name__ == "__main__":
    if len(sys.argv) < 6:
        sys.exit(__doc__)
    root, which = sys.argv[1:3]
    sys.path.insert(0, os.path.abspath(root))
    print(json.dumps(schedule(root, which, *map(int, sys.argv[3:7]))))
