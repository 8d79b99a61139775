"""CI-executes the documented examples end-to-end under a REAL hvdrun
launch (reference analog: Buildkite running test/integration/
test_static_run.py over the example scripts). The examples themselves
stay TPU-first (no CPU forcing inside them); the harness wraps each in
a bootstrap that pins the CPU platform the same way every worker script
in tests/ does (``JAX_PLATFORMS=cpu``), so the workers never contend for
a chip."""

import os
import subprocess
import sys

import pytest

from horovod_tpu.core import core_available

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

needs_core = pytest.mark.skipif(not core_available(),
                                reason="libhvdcore.so not built")


def _cpu_bootstrap(example_rel_path, argv=()):
    """A ``python -c`` command that forces the CPU platform, then runs
    the example as ``__main__`` with the given argv."""
    path = os.path.join(REPO, example_rel_path)
    return [
        sys.executable, "-c",
        "import os, sys\n"
        "os.environ.setdefault('XLA_FLAGS',"
        " '--xla_force_host_platform_device_count=1')\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        f"sys.argv = [{path!r}] + {list(argv)!r}\n"
        "import runpy\n"
        f"runpy.run_path({path!r}, run_name='__main__')\n",
    ]


def _hvdrun(launch_args, example, argv=(), timeout=420):
    cmd = ([sys.executable, "-m", "horovod_tpu.runner.launch"]
           + launch_args + _cpu_bootstrap(example, argv))
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


@needs_core
def test_example_mnist_dp_two_procs():
    """examples/jax/mnist_dp.py under ``hvdrun -np 2``: the documented
    hello-world trains 3 epochs data-parallel and prints rank-0 loss."""
    r = _hvdrun(["-np", "2", "-H", "localhost:2"],
                "examples/jax/mnist_dp.py")
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "epoch 2: loss" in r.stdout, r.stdout[-2000:]


@needs_core
def test_example_torch_synthetic_benchmark_two_procs():
    """examples/torch/torch_synthetic_benchmark.py under 2-proc hvdrun
    with tiny shapes: must print the canonical img/sec lines."""
    r = _hvdrun(["-np", "2", "-H", "localhost:2"],
                "examples/torch/torch_synthetic_benchmark.py",
                argv=["--batch-size", "8", "--image-size", "16",
                      "--num-warmup-batches", "1",
                      "--num-batches-per-iter", "2", "--num-iters", "2"])
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "Img/sec per process" in r.stdout, r.stdout[-2000:]


@needs_core
@pytest.mark.slow  # ~17s elastic launch; tier-1 budget (examples tier
#                    runs it unfiltered)
def test_example_keras_elastic_two_procs():
    """examples/keras/keras_elastic_mnist.py under an ELASTIC hvdrun
    (fixed 2-host world): model.fit with the elastic callback trio runs
    its 3 epochs and reports completion."""
    r = _hvdrun(["-np", "2", "--min-np", "2", "--max-np", "2",
                 "-H", "localhost:2"],
                "examples/keras/keras_elastic_mnist.py", timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    assert "done at epoch 3" in r.stdout, r.stdout[-2000:]
