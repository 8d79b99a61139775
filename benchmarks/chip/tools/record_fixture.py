"""Records the small trace kept as tests/fixture_trace.xplane.pb: four
steps of a tiny program on two TPU devices with one named Pallas kernel
(hvd_flash_attention), one collective (a psum over the two devices) and
the host loop's bench.* annotations. Needs two chips; run once, by hand:

    python3 benchmarks/chip/tools/record_fixture.py <out.xplane.pb>
"""
import glob, os, shutil, sys, tempfile, time
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from horovod_tpu._compat import shard_map
from horovod_tpu.ops.pallas_attention import flash_attention_tpu

out = sys.argv[1]
mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
q = jax.device_put(jnp.ones((2, 256, 2, 128), jnp.bfloat16), NamedSharding(mesh, P("dp")))
w = jax.device_put(jnp.ones((256, 2048), jnp.bfloat16), NamedSharding(mesh, P()))

def body(q, w):
    o = flash_attention_tpu(q, q, q, True)
    g = jnp.einsum("bsd,df->sf", o.reshape(o.shape[0], 256, 256), w)
    g = jax.lax.psum(g, "dp")                      # the collective
    return jnp.tanh(g @ w.T).sum()[None]

step = jax.jit(shard_map(body, mesh=mesh, in_specs=(P("dp"), P()), out_specs=P("dp"), check_vma=False))
jax.block_until_ready(step(q, w))
logdir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace(logdir, profiler_options=opts)
outs = []
for i in range(4):
    with jax.profiler.TraceAnnotation("bench.input"):
        time.sleep(0.001)
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        outs.append(step(q, w))
    with jax.profiler.TraceAnnotation("bench.wait"):
        jax.block_until_ready(outs[-1])
jax.profiler.stop_trace()
path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
shutil.copy(path, out)
print("fixture", out, os.path.getsize(path), "bytes")
