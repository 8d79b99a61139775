"""The Mamba-2 scan's Pallas kernels (``ops/pallas_ssm.py``: ``hvd_ssm_scan``,
``hvd_ssm_scan_bwd``) in interpret mode on the CPU, in float32, against the
``jax.numpy`` form of ``models/mamba.py:ssm_chunked`` and against the
recurrence one position at a time (the benchmark's plain reference), at
shapes that cross three chunk boundaries and have two groups; one of them
has two lane tiles of two heads a group.

TOL is ``tests/test_nemotron_h.py``'s: both sides are float32 and differ in
the order of their sums (1e-8 to 3e-5 here); what TOL must not let through
(a decay in bfloat16, a state that is not carried, a missing causal mask)
reads 4.5 times it (the bfloat16 decays) and more.
"""

import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from horovod_tpu.models import mamba, transformer as t
from horovod_tpu.ops import pallas_ssm as ps

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHIP = os.path.join(_REPO, "benchmarks", "chip")
if _CHIP not in sys.path:
    sys.path.insert(0, _CHIP)

from reference import nemotron_h as reference         # noqa: E402

TOL = 1e-4
#: (B, S, H, P, G, N, chunk): four chunks, two groups; heads that share a
#: lane tile (2 or 4 of them), a head that is a tile, two tiles a group
SHAPES = {
    "two heads a tile": (2, 64, 4, 8, 2, 16, 16),
    "four heads a tile": (1, 64, 8, 8, 2, 16, 16),
    "two tiles of two heads": (1, 64, 8, 64, 2, 16, 16),
    "a head a tile": (1, 64, 4, 128, 2, 8, 16),
    "sixteen heads a step, chunk 256": (1, 512, 16, 64, 1, 16, 256),
}
NAMES = ("x", "dt", "a", "b", "c")


def _operands(shape, seed=0, dtype=jnp.float32):
    B, S, H, P, G, N, _chunk = shape
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(B, S, H, P), dtype)
    dt = jax.nn.softplus(jnp.asarray(rng.randn(B, S, H) - 1, jnp.float32))
    a = -jnp.exp(jnp.asarray(rng.uniform(0, 2.5, H), jnp.float32))
    b, c = (jnp.asarray(rng.randn(B, S, G, N), dtype) for _ in range(2))
    return x, dt, a, b, c


def _weight(shape, seed=1):
    B, S, H, P = shape[:4]
    return jnp.asarray(np.random.RandomState(seed).randn(B, S, H, P),
                       jnp.float32)


def _kernels(x, dt, a, b, c, chunk):
    return mamba.ssm_chunked(x, dt, a, b, c, chunk, interpret=True)


def _stepwise(x, dt, a, b, c):
    heads, groups = x.shape[2], b.shape[2]
    return reference.recurrence(
        x, dt, a, *(jnp.repeat(v, heads // groups, axis=2) for v in (b, c)))


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.linalg.norm(got - want) / (np.linalg.norm(want) or 1.0)


def _sums(dt, a, chunk):
    return mamba._chunk_sums(dt * a, chunk)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_the_forward_kernel_is_the_numpy_form_and_the_recurrence(shape):
    ops, chunk = _operands(shape), shape[-1]
    tiles = ps.ssm_tiles(*shape[2:5])
    assert tiles.tiles * tiles.heads_per_tile == shape[2] // shape[4]
    got = _kernels(*ops, chunk)
    assert got.dtype == jnp.float32 and got.shape == ops[0].shape
    assert _rel(got, mamba.ssm_chunked(*ops, chunk)) < TOL
    assert _rel(got, _stepwise(*ops)) < TOL


def _cotangents(f, weight, ops, of=(0, 1, 2, 3, 4)):
    """The cotangents ``of`` the operands under ``sum(f(*ops) weight)``,
    as one traced and compiled program."""
    return jax.jit(jax.grad(lambda *v: jnp.sum(f(*v) * weight), of))(*ops)


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_every_cotangent_is_autodiff_s_of_the_numpy_form(shape):
    """x, dt (through the sums and directly), a, b and c."""
    ops, chunk, weight = _operands(shape), shape[-1], _weight(shape)
    got = _cotangents(lambda *v: _kernels(*v, chunk), weight, ops)
    want = _cotangents(lambda *v: mamba.ssm_chunked(*v, chunk), weight, ops)
    stepwise = _cotangents(_stepwise, weight, ops)
    for name, g, w, r in zip(NAMES, got, want, stepwise):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rel(g, w) < TOL, name
        assert _rel(g, r) < TOL, name


def _ddt_ds_a_head_at_a_time(d_dt_inside, d_since_start, d_to_end, d_whole,
                             dt, since_start, until_end, to_end, whole):
    """``ps._ddt_ds`` as the backward kernel had it until PR 61: inside the
    loop over a step's heads, on the head's ``[Q, 1]`` columns."""
    Q, R = dt.shape
    last = jax.lax.broadcasted_iota(jnp.int32, (Q, 1), 0) == Q - 1
    ddt, ds = [None] * R, [None] * R
    for r in range(R):
        (d_dt_inside_r, d_since_start_r, d_to_end_r, d_whole_r, dt_r,
         since_start_r, until_end_r, to_end_r, whole_r) = (
            v[:, r:r + 1] for v in (
                d_dt_inside, d_since_start, d_to_end, d_whole, dt,
                since_start, until_end, to_end, whole))
        ddt[r] = d_dt_inside_r + d_to_end_r * until_end_r
        at_end = (jnp.sum(d_to_end_r * to_end_r, axis=0, keepdims=True)
                  + d_whole_r * whole_r)
        ds[r] = (d_since_start_r * since_start_r - dt_r * d_dt_inside_r
                 - d_to_end_r * to_end_r + jnp.where(last, at_end, 0.0))
    return ps._columns(ddt, R), ps._columns(ds, R)


@pytest.mark.parametrize("shape", ["two tiles of two heads", "a head a tile"])
def test_the_step_s_columns_are_the_per_head_form_to_the_bit(monkeypatch,
                                                             shape):
    """d dt and d s of all of a step's heads in one ``[Q, R]`` expression
    against a head at a time: the same float32 operations in the same order
    on every element, so not one bit differs (and the per-head form was
    really run: handed a wrong sign it does differ)."""
    shape = SHAPES[shape]
    (x, dt, a, b, c), chunk, weight = _operands(shape), shape[-1], _weight(
        shape)
    s = _sums(dt, a, chunk)

    def cotangents():
        return _cotangents(
            lambda dt, s: ps.ssm_scan(x, dt, s, b, c, chunk, True),
            weight, (dt, s), (0, 1))
    got = cotangents()
    monkeypatch.setattr(ps, "_ddt_ds", _ddt_ds_a_head_at_a_time)
    want = cotangents()
    monkeypatch.setattr(ps, "_ddt_ds", lambda *v: tuple(
        -g for g in _ddt_ds_a_head_at_a_time(*v)))
    wrong = cotangents()
    for name, g, w, bad in zip(("dt", "s"), got, want, wrong):
        assert np.array_equal(np.asarray(g), np.asarray(w)), name
        assert not np.array_equal(np.asarray(g), np.asarray(bad)), name


def test_dt_s_and_the_sums_cotangents_apart():
    """The kernels take dt and the sums as two operands and return a
    cotangent for each: each against autodiff of the ``jax.numpy`` form
    from the same two operands."""
    shape = SHAPES["two heads a tile"]
    (x, dt, a, b, c), chunk, weight = _operands(shape), 16, _weight(shape)
    s = _sums(dt, a, chunk)
    got = _cotangents(lambda dt, s: ps.ssm_scan(x, dt, s, b, c, chunk, True),
                      weight, (dt, s), (0, 1))
    want = _cotangents(lambda dt, s: mamba._ssm_chunked_numpy(
        x, dt, s, b, c, chunk), weight, (dt, s), (0, 1))
    for name, g, w in zip(("dt", "s"), got, want):
        assert _rel(g, w) < TOL, name
    # neither is the other's, nor small beside it
    assert _rel(got[0], got[1]) > 0.5


def test_one_chunk_is_the_whole_sequence_and_the_state_crosses_chunks():
    shape = SHAPES["two heads a tile"]
    ops = _operands(shape, seed=2)
    whole = _kernels(*ops, 64)
    assert _rel(_kernels(*ops, 16), whole) < TOL
    x, dt, a, b, c = ops
    alone = _kernels(x[:, 16:32], dt[:, 16:32], a, b[:, 16:32], c[:, 16:32],
                     16)
    assert _rel(alone, whole[:, 16:32]) > 1e-2
    assert _rel(_kernels(x[:, :16], dt[:, :16], a, b[:, :16], c[:, :16], 16),
                whole[:, :16]) < TOL
    weight = _weight(shape)
    one, four = (_cotangents(lambda *v: _kernels(*v, chunk), weight, ops)
                 for chunk in (64, 16))
    for name, g, w in zip(NAMES, four, one):
        assert _rel(g, w) < TOL, name


# -- what TOL must not let through -------------------------------------------

def _bf16_decay(log_decay):
    return jnp.exp(log_decay.astype(jnp.bfloat16)).astype(jnp.float32)


def _not_carried(state, whole, own):
    return jnp.zeros_like(own)


def _no_mask(Q, transposed=False):
    return jnp.ones((Q, Q), bool)


def _decays_capped(log_decay):
    """Without the mask a decay's exponent is positive above the diagonal;
    capped so that the wrong sum stays finite."""
    return jnp.exp(jnp.minimum(log_decay, 3.0))


@pytest.mark.parametrize("what, patches", [
    ("the decays made in bfloat16", {"_decay": _bf16_decay}),
    ("a state that is not carried", {"_carry": _not_carried}),
    ("no causal mask inside a chunk", {"_causal": _no_mask,
                                       "_decay": _decays_capped}),
])
def test_a_wrong_piece_fails_on_the_kernels(monkeypatch, what, patches):
    """The forward and, beside it, the gradients (the backward kernel makes
    its decays, mask and carried cotangent from the same three pieces)."""
    shape = SHAPES["two heads a tile"]
    ops, weight = _operands(shape), _weight(shape)

    def errors():
        forward = _rel(_kernels(*ops, 16), _stepwise(*ops))
        got = _cotangents(lambda *v: _kernels(*v, 16), weight, ops)
        want = _cotangents(_stepwise, weight, ops)
        return forward, max(_rel(g, w) for g, w in zip(got, want))
    assert max(errors()) < TOL
    for name, wrong in patches.items():
        monkeypatch.setattr(ps, name, wrong)
    forward, backward = errors()
    assert forward > 4 * TOL, (what, forward)
    assert backward > 4 * TOL, (what, backward)


def test_bfloat16_operands_keep_float32_sums_decays_and_state():
    """The cell's dtypes: y float32, every cotangent in its operand's
    dtype, and both within bfloat16's rounding of the ``jax.numpy`` form at
    the same dtypes (which rounds the same operands in another order)."""
    shape = SHAPES["two tiles of two heads"]
    ops, weight = _operands(shape, dtype=jnp.bfloat16), _weight(shape)
    got = _kernels(*ops, 16)
    assert got.dtype == jnp.float32
    assert _rel(got, mamba.ssm_chunked(*ops, 16)) < 1e-2
    grads = _cotangents(lambda *v: _kernels(*v, 16), weight, ops)
    want = _cotangents(lambda *v: mamba.ssm_chunked(*v, 16), weight, ops)
    for name, g, w, op in zip(NAMES, grads, want, ops):
        assert g.dtype == op.dtype, name
        assert _rel(g, w) < 2e-2, name


# -- which form runs -----------------------------------------------------------

CELL = (8192, 64, 64, 8, 128, 128)           # S, H, P, G, N, chunk


def _calls_a_kernel(shape):
    B, S, H, P, G, N, chunk = shape
    x = jax.ShapeDtypeStruct((B, S, H, P), jnp.bfloat16)
    dt = jax.ShapeDtypeStruct((B, S, H), jnp.float32)
    a = jax.ShapeDtypeStruct((H,), jnp.float32)
    b = jax.ShapeDtypeStruct((B, S, G, N), jnp.bfloat16)
    return "pallas_call" in str(jax.make_jaxpr(
        lambda *v: mamba.ssm_chunked(*v, chunk))(x, dt, a, b, b))


def test_the_kernels_run_on_a_tpu_where_the_tiles_fit(monkeypatch):
    cfg = t.TransformerConfig(layer_pattern=(("mamba",),), ssm_heads=64,
                              ssm_head_dim=64, ssm_state=128, ssm_groups=8,
                              ssm_chunk=128)
    assert ps.ssm_eligible(*CELL)
    assert ps.ssm_tiles(64, 64, 8) == ps.SsmTiles(2, 64, 4)
    assert "jax.numpy (backend cpu)" in mamba.ssm_path(cfg, 8192)
    assert not _calls_a_kernel((1, 256, 64, 64, 8, 128, 128))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    said = mamba.ssm_path(cfg, 8192)
    assert ps.FWD_NAME in said and ps.BWD_NAME in said
    assert "64 chunks" in said and "4 lane tiles of 2 heads" in said
    assert "128x512" in said and "checkpointed" in said
    assert "gate + norm in jax.numpy, 8 groups of 512 channels" in said
    assert _calls_a_kernel((1, 256, 64, 64, 8, 128, 128))


@pytest.mark.parametrize("what, change", [
    ("a chunk of 64", {"ssm_chunk": 64}),
    ("a state of 64", {"ssm_state": 64}),
    ("a tile of one head of 64", {"ssm_heads": 8}),
    ("heads of 96", {"ssm_head_dim": 96}),
])
def test_a_shape_the_tiles_do_not_fit_takes_the_numpy_form(monkeypatch, what,
                                                           change):
    import dataclasses
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(t.TransformerConfig(
        layer_pattern=(("mamba",),), ssm_heads=64, ssm_head_dim=64,
        ssm_state=128, ssm_groups=8, ssm_chunk=128), **change)
    shape = (1, 256, cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_groups,
             cfg.ssm_state, cfg.ssm_chunk)
    assert not ps.ssm_eligible(256, *shape[2:]), what
    said = mamba.ssm_path(cfg, 256)
    assert said.startswith("jax.numpy (") and ps.FWD_NAME not in said, what
    assert not _calls_a_kernel(shape), what
    with pytest.raises(ValueError, match="ssm_chunk=48"):
        _calls_a_kernel((1, 256, 64, 64, 8, 128, 48))


# -- a group's heads in head tiles (a grid axis; PR 49) ------------------------

#: (shape, heads a grid step): ONE group at chunk 256 in four head tiles of a
#: lane tile each and in two of two, two groups of two head tiles each,
#: heads that are a lane tile, and the dense hybrid cell's tile of 16 heads
HEAD_TILES = {
    "one group, chunk 256, four tiles of two heads":
        ((1, 512, 8, 64, 1, 128, 256), 2),
    "one group, chunk 256, two tiles of four heads":
        ((1, 512, 8, 64, 1, 128, 256), 4),
    "two groups of two tiles of two heads": ((2, 64, 8, 64, 2, 16, 16), 2),
    "one group, four tiles of a head of 128": ((1, 64, 4, 128, 1, 8, 16), 1),
    "one group, chunk 256, two tiles of sixteen heads":
        ((1, 512, 32, 64, 1, 16, 256), 16),
}


def _tiled(shape, head_tile, dtype=jnp.float32, seed=3):
    x, dt, a, b, c = _operands(shape, seed, dtype)
    return (x, dt, _sums(dt, a, shape[-1]), b, c), shape[-1]


@pytest.mark.parametrize("shape, head_tile", HEAD_TILES.values(),
                         ids=HEAD_TILES.keys())
def test_head_tiles_give_the_numpy_form_s_output(shape, head_tile):
    ops, chunk = _tiled(shape, head_tile)
    lay = ps._layout(ops[0], ops[3], chunk, head_tile)
    assert lay.head_tiles >= 2 and lay.steps == shape[2] // head_tile
    got = ps.ssm_scan(*ops, chunk, True, head_tile)
    assert got.dtype == jnp.float32
    assert _rel(got, mamba._ssm_chunked_numpy(*ops, chunk)) < TOL
    # and what the group's heads in one block give
    assert _rel(got, ps.ssm_scan(*ops, chunk, True, shape[2] // shape[4])
                ) < TOL


@pytest.mark.parametrize("shape, head_tile", HEAD_TILES.values(),
                         ids=HEAD_TILES.keys())
def test_head_tiles_give_every_cotangent(shape, head_tile):
    """dx, d dt, d s, and db and dc summed over the group's head tiles,
    against autodiff of the ``jax.numpy`` form from the same operands."""
    ops, chunk = _tiled(shape, head_tile)
    weight = _weight(shape)
    got = _cotangents(lambda *v: ps.ssm_scan(*v, chunk, True, head_tile),
                      weight, ops)
    want = _cotangents(lambda *v: mamba._ssm_chunked_numpy(*v, chunk),
                       weight, ops)
    for name, g, w in zip(("x", "dt", "s", "b", "c"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert _rel(g, w) < TOL, name


def test_a_head_tile_s_parts_of_db_are_summed_in_float32():
    """bfloat16 operands: the parts leave the kernel in float32 and the
    group's sum is rounded once, so four head tiles and one block differ
    by a rounding of the result and not of every part."""
    shape, head_tile = (1, 512, 8, 64, 1, 128, 256), 2
    ops, chunk = _tiled(shape, head_tile, jnp.bfloat16)
    weight = _weight(shape)

    def grads(tile):
        return _cotangents(lambda *v: ps.ssm_scan(*v, chunk, True, tile),
                           weight, ops, (3, 4))
    for name, g, w in zip(("b", "c"), grads(head_tile), grads(8)):
        assert g.dtype == jnp.bfloat16, name
        assert _rel(g.astype(jnp.float32), w.astype(jnp.float32)) < 4e-3, name


def test_a_head_tile_that_is_no_whole_lane_tiles_of_the_group_is_refused():
    ops, chunk = _tiled((1, 64, 8, 64, 2, 16, 16), 2)
    for bad in (1, 3, 8):
        with pytest.raises(ValueError, match="head tile"):
            ps.ssm_scan(*ops, chunk, True, bad)


#: (S, H, P, G, N, chunk) of the cells that run a scan -> heads a grid step
CELL_HEAD_TILES = {
    "nemotron-3-nano-30b-a3b.s8192": ((8192, 64, 64, 8, 128, 128), 8),
    "granite-4.0-h-micro.s4096": ((4096, 64, 64, 1, 128, 256), 16),
}


@pytest.mark.parametrize("cell", sorted(CELL_HEAD_TILES))
def test_the_head_tile_at_the_cells_shapes(cell, monkeypatch):
    (S, H, P, G, N, chunk), heads = CELL_HEAD_TILES[cell]
    assert ps.ssm_eligible(S, H, P, G, N, chunk)
    assert ps.ssm_head_tile(H, P, G, N, chunk) == heads
    assert ps.ssm_vmem_bytes(chunk, heads * P, N, 2, P) <= ps.VMEM_BUDGET
    assert ps.VMEM_BUDGET == 16 * 2 ** 20   # the default limit: none asked
    # a whole group of the hybrid cell is one block; ONE group of 64 heads
    # at chunk 256 is not, and the estimate lies above what the compiler
    # takes of the scoped VMEM on every row it is held to (MiB, compiled
    # for a v5e; 16 heads fit the default limit, 32 do not), within 3 %
    assert (heads == H // G) == (G == 8)
    for (q, r), taken_mib in {(128, 8): 4.60, (128, 16): 7.89,
                              (128, 32): 14.27, (256, 8): 9.05,
                              (256, 16): 14.76}.items():
        estimate = ps.ssm_vmem_bytes(q, r * 64, 128, 2) / 2 ** 20
        assert taken_mib <= estimate < 1.03 * taken_mib, (q, r, estimate)
    assert ps.ssm_vmem_bytes(256, 16 * 64, 128, 2) < ps.VMEM_BUDGET
    assert ps.ssm_vmem_bytes(256, 32 * 64, 128, 2) > 26 * 2 ** 20
    assert _calls_a_kernel((1, 256, H, P, G, N, chunk)) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert _calls_a_kernel((1, 2 * chunk, H, P, G, N, chunk))


def test_the_hybrid_cell_s_scan_path_line_to_the_letter(monkeypatch):
    """What ``chip_smoke.py`` printed for the hybrid cell before the kernels
    had head tiles: its blocks are what they were."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ps.ssm_scan_path(*CELL) == (
        "kernels hvd_ssm_scan / hvd_ssm_scan_bwd: grid (8 groups, 64 "
        "chunks), x and y blocks 128x512 in 4 lane tiles of 2 heads, b and "
        "c 128x128, carried state 128x512 float32 in VMEM")


def test_the_dense_hybrid_cell_s_scan_path(monkeypatch):
    cfg = t.TransformerConfig(
        layer_pattern=(("mamba",), ("dense",)), n_layers=2, ssm_heads=64,
        ssm_head_dim=64, ssm_state=128, ssm_groups=1, ssm_chunk=256)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    said = mamba.ssm_path(cfg, 4096)
    heads = CELL_HEAD_TILES["granite-4.0-h-micro.s4096"][1]
    assert ps.FWD_NAME in said and ps.BWD_NAME in said
    assert f"{64 // heads} head tiles of {heads} heads, 16 chunks" in said
    assert f"x and y blocks 256x{heads * 64}" in said
    assert "db and dc summed over them" in said
    assert "one group of 4096 channels, a row's own mean" in said


def test_one_group_s_gate_and_norm_is_the_matrix_form_s():
    """``_gated_norm`` at one group takes a row's own mean; the 0/1-matrix
    form at one group is the same function."""
    rng = np.random.RandomState(5)
    y, z = (jnp.asarray(rng.randn(2, 16, 64), jnp.float32) for _ in range(2))
    w = jnp.asarray(rng.rand(64) + 0.5, jnp.float32)
    got = mamba._gated_norm(y, z, w, 1, 1e-5)
    g = y * jax.nn.silu(z)
    want = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + 1e-5) * w
    assert _rel(got, want) < 1e-6
    two = mamba._gated_norm(y, z, w, 2, 1e-5)
    assert _rel(two, want) > 1e-2
    assert "dot_general" not in str(jax.make_jaxpr(
        lambda y, z: mamba._gated_norm(y, z, w, 1, 1e-5))(y, z))
