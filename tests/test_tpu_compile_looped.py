"""The cell ouro-2.6b.s4096's whole step compiled for a described TPU v5e
(tests/test_tpu_compile_kernels.py's way)."""

import jax
import jax.numpy as jnp
import pytest

from horovod_tpu.ops import pallas_attention as pa
from horovod_tpu.ops import pallas_xent as px
from tpu_compile_cases import cell_step, compile_cache_off, described_v5e


@pytest.fixture(scope="module")
def topo():
    return described_v5e()


@pytest.fixture
def no_compile_cache():
    with compile_cache_off():
        yield


def test_looped_step_compiles_for_v5e_with_both_kernels(
        topo, no_compile_cache, monkeypatch):
    """The cell ouro-2.6b.s4096's step, 6 layers looped 4 times at 4096
    tokens: both kernels engage, the calls a step are what the adapter's
    ``shapes()`` tells the roofline functions (the forward flash kernel
    once a layer pass in the forward scan and once more in the backward
    scan, which recomputes the checkpointed pass; the head's kernel once a
    loop step), the loop's scopes are in the program, and the step fits
    with the room the issue asks for."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    step, args, shapes, step_bytes = cell_step("ouro-2.6b.s4096", topo)
    b, s, h, d = (shapes[k] for k in ("batch", "seq", "heads", "head_dim"))
    assert pa.attention_path(s, s, h, d, True, False) == "flash"
    assert px.xent_path(b * s, shapes["vocab"], jnp.bfloat16)[0] == "kernel"
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    passes = shapes["layers"] * shapes["loops"]
    # a call site in a scan's body runs once a layer pass
    assert sum("hvd_flash_attention" in c for c in calls) * passes \
        == shapes["attention_forward_calls"]
    assert sum("hvd_fused_xent" in c for c in calls) == shapes["head_calls"]
    from horovod_tpu.profiling import scopes
    names = "\n".join(line for line in text.splitlines()
                      if "op_name=" in line)
    assert scopes.LOOP + "/" in names and scopes.LOOP_GATE + "/" in names
    assert step_bytes(compiled.memory_analysis())["total"] < 15.0e9
