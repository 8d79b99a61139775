"""Records the small trace kept as tests/fixture_scopes.xplane.pb and the
compiled step's text kept as tests/fixture_scopes.hlo.txt: three steps of
the program's own flagship train step (models/transformer.py:
make_train_step, AdamW) at a tiny size on one TPU chip, head_dim 128 so
that both Pallas kernels engage, two layers under the scan, batches
through data_loader.device_prefetch, the host loop's bench.* annotations
around it. The step carries the program's hvd.* scopes, the input path
its hvd.input.* spans. Needs one chip; run once, by hand:

    python3 benchmarks/chip/tools/record_fixture_scopes.py <out prefix>

writes <out prefix>.xplane.pb and <out prefix>.hlo.txt. No compile cache
is set: an executable read from a cache carries the metadata of the
build that wrote it (scope_reduce.py's docstring).
"""
import glob, itertools, os, shutil, sys, tempfile
sys.path.insert(0, os.getcwd())
import jax, jax.numpy as jnp, numpy as np, optax
import horovod_tpu as hvd
from horovod_tpu.data.data_loader import device_prefetch
from horovod_tpu.models.transformer import (
    TransformerConfig, data_sharding_spec, init_opt_state, init_params,
    make_train_step, shard_params)
from jax.sharding import NamedSharding

out = sys.argv[1]
hvd.init()
cfg = TransformerConfig(vocab_size=512, d_model=256, n_heads=2, n_layers=2,
                        d_ff=512, max_seq=256)
mesh = hvd.build_mesh(devices=jax.devices()[:1], dp=-1)
params = shard_params(init_params(np.random.RandomState(0), cfg, 1), cfg, mesh)
tx = optax.adamw(1e-4)
opt_state = init_opt_state(tx, params, mesh, cfg)
step = make_train_step(cfg, mesh, tx)

def host_batches():
    rng = np.random.default_rng(0)
    for _ in itertools.count():
        tokens = rng.integers(0, cfg.vocab_size, (2, 256), dtype=np.int32)
        yield {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}

batches = device_prefetch(host_batches(), NamedSharding(mesh, data_sharding_spec(mesh)), buffer_size=2)
for _ in range(2):                                  # compile, warm up
    b = next(batches)
    params, opt_state, loss, _aux = step(params, opt_state, b["tokens"], b["targets"])
jax.block_until_ready(loss)
logdir = tempfile.mkdtemp()
opts = jax.profiler.ProfileOptions(); opts.python_tracer_level = 0
jax.profiler.start_trace(logdir, profiler_options=opts)
for i in range(3):
    with jax.profiler.TraceAnnotation("bench.input"):
        b = next(batches)
    with jax.profiler.TraceAnnotation("bench.dispatch"):
        params, opt_state, loss, _aux = step(params, opt_state, b["tokens"], b["targets"])
    with jax.profiler.TraceAnnotation("bench.wait"):
        jax.block_until_ready(loss)
jax.profiler.stop_trace()
path = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")[0]
os.makedirs(os.path.dirname(out) or ".", exist_ok=True)
shutil.copy(path, out + ".xplane.pb")
b = next(batches)
text = step.lower(params, opt_state, b["tokens"], b["targets"]).compile().as_text()
with open(out + ".hlo.txt", "w") as f:
    f.write(text)
print("fixture", out, os.path.getsize(path), "bytes of trace,", len(text), "of text, loss", float(loss))
hvd.shutdown()
