"""Driver-contract tests: dryrun_multichip must compile+run at every device
count the driver may choose, and entry() must produce a jittable forward."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cpu_snippet(n_devices: int, tail: str) -> str:
    """Shared env bootstrap for subprocess tests (kept in one place so a
    future env requirement can't drift between snippets)."""
    return f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
os.environ["JAX_PLATFORMS"] = "cpu"
import sys
sys.path.insert(0, {REPO!r})
""" + tail


def test_driver_call_path(capsys, monkeypatch):
    """EXACTLY what the driver does: import the module and call
    dryrun_multichip(8) — no env bootstrap, no subprocess wrapper. The
    function must self-bootstrap a forced-CPU child regardless of this
    process's JAX state. The scaling-curve phase must emit its
    ``[scaling] {json}`` artifact line (one world here keeps the test
    inside the tier-1 budget; the driver's real run measures 1,2,4,8)."""
    monkeypatch.setenv("HVD_DRYRUN_SCALING_WORLDS", "2")
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(8)
    finally:
        sys.path.remove(REPO)
    out = capsys.readouterr().out
    assert "[dryrun] OK" in out
    # the last "[scaling] " line that is JSON (progress lines are not)
    curve = None
    for line in out.splitlines():
        if line.startswith("[scaling] {"):
            curve = json.loads(line[len("[scaling] "):])
    assert curve and curve["scaling_curve"][0]["world"] == 2
    assert curve["scaling_curve"][0]["samples_per_sec"] > 0
    assert curve["scaling_curve"][0]["samples_per_sec_int8"] > 0


@pytest.mark.parametrize("n", [2, 4, 16])
def test_dryrun_device_counts(n, monkeypatch):
    """A forced-CPU child of ``n`` devices runs every layout's step to its
    loss: a mesh of 2, 4 or 16 devices is built, not described, and the
    child is the driver's own call. The function self-bootstraps; call it
    directly at every driver-plausible device count (scaling is the
    driver-artifact phase, covered by test_driver_call_path — skip it
    here)."""
    monkeypatch.setenv("HVD_DRYRUN_SCALING", "0")
    sys.path.insert(0, REPO)
    try:
        import __graft_entry__
        __graft_entry__.dryrun_multichip(n)
    finally:
        sys.path.remove(REPO)


def test_entry_compiles_on_cpu():
    code = _cpu_snippet(1, """
import jax
from __graft_entry__ import entry
fn, args = entry()
out = jax.jit(fn)(*args)
print("entry loss:", float(out))
assert float(out) > 0
""")
    rc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                        timeout=900, cwd=REPO)
    assert rc.returncode == 0, rc.stdout.decode() + rc.stderr.decode()
