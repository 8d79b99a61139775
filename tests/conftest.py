"""Test configuration: force an 8-device virtual CPU mesh
(``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8``, set
before JAX starts a backend).

The TPU analog of the reference's test strategy (SURVEY.md §4): parallel
collective numerics are validated on a multi-device host platform the way the
reference runs Gloo/MPI on localhost.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Anomaly findings arm real device-trace captures by default (ISSUE 9,
# docs/OBSERVABILITY.md "Deep profiling"); any test that provokes one
# must not drop trace directories into the repo checkout — default the
# retention dir to a per-run tmp location (tests that assert capture
# behavior point it at their own tmp_path)
import tempfile  # noqa: E402

os.environ.setdefault(
    "HVD_TPU_PROFILE_DIR",
    os.path.join(tempfile.gettempdir(), f"hvd_profile_test_{os.getpid()}"))

# Same treatment for autopsy bundles (ISSUE 10 satellite): chaos kills /
# hang autopsies flush flight rings to HVD_TPU_AUTOPSY_DIR, which
# defaults to ./hvd_autopsy — debris in the checkout. Tests that assert
# on bundle contents point it at their own tmp_path.
os.environ.setdefault(
    "HVD_TPU_AUTOPSY_DIR",
    os.path.join(tempfile.gettempdir(), f"hvd_autopsy_test_{os.getpid()}"))

import jax  # noqa: E402

# OPT-IN persistent compilation cache (HVD_TEST_COMPILE_CACHE=1), placed
# by the repo's one rule (utils/compile_cache: JAX_COMPILATION_CACHE_DIR
# if set, else <checkout>/.jax_cache). It cuts the hot suite's XLA:CPU
# compile time ~35% (InceptionV3 70s -> 46s), but the faster warm-cache
# dispatch can pile up multi-device executions on a starved host and trip
# the known XLA:CPU co-scheduling SIGABRT (see .claude/skills/verify
# gotchas) — observed twice at ~90% of the full suite with the cache on,
# never with it off. Default off: suite determinism outranks wall clock.
if os.environ.get("HVD_TEST_COMPILE_CACHE") == "1":
    from horovod_tpu.utils import compile_cache
    compile_cache.enable()

import pytest  # noqa: E402

assert jax.device_count() == 8, (
    f"tests require the 8-device virtual CPU mesh, got {jax.devices()}")

# Build the native core if it isn't present (kept out of git; ~20 s once).
import subprocess  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SO = os.path.join(_REPO, "horovod_tpu", "core", "libhvdcore.so")
if not os.path.exists(_SO):
    try:
        subprocess.run(["make", "-j4"], cwd=os.path.join(_REPO, "cpp"),
                       check=False, capture_output=True, timeout=300)
    except Exception:
        pass  # core tests skip cleanly when the .so is absent


@pytest.fixture(autouse=True, scope="session")
def _no_artifact_debris_in_checkout():
    """Regression guard for the PR 9/10 cleanup (ISSUE 13 satellite): no
    test may leave autopsy bundles, flight dumps, or profiler trace
    dirs in the repo checkout.  The env defaults above route everything
    to tmp; a test overriding them must use its own tmp_path.  Runs at
    session teardown so one stray writer fails the run visibly instead
    of silently re-accumulating debris."""
    import glob

    def debris():
        out = []
        for pat in ("hvd_autopsy", "hvd_profile*",
                    "hvd_flight_rank*.json", "autopsy_rank*",
                    "summary_rank*.json"):
            out += glob.glob(os.path.join(_REPO, pat))
        return sorted(out)

    before = debris()
    yield
    leaked = [p for p in debris() if p not in before]
    assert not leaked, (
        f"test run left autopsy/flight artifacts in the checkout: "
        f"{leaked}; point HVD_TPU_AUTOPSY_DIR / HVD_TPU_PROFILE_DIR / "
        f"flight dumps at tmp_path instead")


@pytest.fixture
def hvd():
    import horovod_tpu as hvd
    hvd.shutdown()
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture
def mesh8():
    import horovod_tpu as hvd
    return hvd.build_mesh(dp=2, pp=1, ep=1, sp=2, tp=2)
