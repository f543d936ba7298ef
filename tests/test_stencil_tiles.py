"""The stencil kernels' tiles: the default tile and the halos each input
brings change no value, and only the stencil builds ask Mosaic for a
scoped VMEM limit (sized to the tile)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import get_kernel
from repro.kernels import _stencil_common as sc
from repro.kernels import advec_u as advec_mod
from repro.kernels import decode_attention as da_mod
from repro.obs import runtime as obs
from repro.tuner.runner import tolerances

SCAL = np.array([[1.1, 0.9, 1.3, 0.0]], np.float32)
OLD_TILE = {"block_z": 8, "block_y": 16}
MiB = 2**20


def _stencil_args(name, problem, seed=7):
    rng = np.random.default_rng(seed)
    u, v, w = (rng.standard_normal(problem).astype(np.float32)
               for _ in range(3))
    if name == "advec_u":
        return u, v, w, SCAL
    return u, v, w, rng.standard_normal(problem).astype(np.float32) ** 2, SCAL


def _leaves(out):
    return [np.asarray(o) for o in (out if isinstance(out, tuple) else (out,))]


def _interpreted(builder, config, args):
    # XLA:CPU's backend optimisations compile an interpreted grid of one
    # program apart from a grid of several (a multiply and an add may be
    # contracted into one FMA in one and not the other): without them
    # the kernel's float32 arithmetic runs as written, tile by tile.
    fn = jax.jit(builder.make(config, args, interpret=True))
    return _leaves(fn.lower(*args).compile(
        {"xla_backend_optimization_level": 0})(*args))


@pytest.mark.parametrize("problem", [(32, 32, 32), (32, 32, 128),
                                     (64, 64, 128)])
@pytest.mark.parametrize("name", ["advec_u", "diff_uvw"])
def test_default_tile_matches_old_tile_bit_for_bit(name, problem,
                                                   monkeypatch):
    b = get_kernel(name)
    args = _stencil_args(name, problem)
    new = _interpreted(b, b.default_config(), args)
    old = _interpreted(b, b.default_config() | OLD_TILE, args)
    for got, was in zip(new, old, strict=True):
        np.testing.assert_array_equal(got, was)
    rtol, atol = tolerances("float32")
    for got, want in zip(new, _leaves(b.make_reference()(*args)),
                         strict=True):
        np.testing.assert_allclose(got, np.asarray(want), rtol=rtol,
                                   atol=atol)
    if name == "advec_u":
        # v's z slabs and w's y slabs, which the stencil never reads
        monkeypatch.setattr(advec_mod, "FIELD_HALOS", [sc.BOTH] * 3)
        five_refs = _interpreted(b, b.default_config(), args)
        np.testing.assert_array_equal(five_refs[0], new[0])


def test_halo_read_factor():
    assert sc.halo_read_factor(8, 16, [sc.BOTH]) == 3.0
    assert sc.halo_read_factor(32, 32, [sc.BOTH]) == 1.75
    # advec_u at (32, 32): u 1.75, v 1.5, w 1.25
    assert sc.halo_read_factor(32, 32, advec_mod.FIELD_HALOS) == 1.5


def test_build_sets_gauges():
    was = obs.enabled()
    reg, _ = obs.enable(trace=False)
    try:
        for name in ("advec_u", "diff_uvw"):
            b = get_kernel(name)
            b.make(b.default_config(), _stencil_args(name, (32, 32, 128)),
                   interpret=True)
        gauges = reg.snapshot()["gauges"]
    finally:
        if not was:
            obs.disable()
    assert gauges["stencil.halo_read_factor{kernel=advec_u}"] == 1.5
    assert gauges["stencil.halo_read_factor{kernel=diff_uvw}"] == 1.75
    for name, n_in, n_out in (("advec_u", 3, 1), ("diff_uvw", 4, 3)):
        assert gauges[f"stencil.vmem_limit_bytes{{kernel={name}}}"] == \
            sc.stencil_vmem_limit((32, 32, 128), 32, 32, n_in, n_out)


@pytest.fixture()
def v5e(monkeypatch):
    """Lower kernels with Mosaic compiler params, as on the chip."""
    monkeypatch.setenv("KERNEL_LAUNCHER_DEVICE", "tpu-v5e")


@pytest.fixture()
def compiler_params(monkeypatch):
    """The ``compiler_params`` of every ``pl.pallas_call`` made."""
    seen = []
    real = pl.pallas_call

    def spy(*args, **kwargs):
        seen.append(kwargs.get("compiler_params"))
        return real(*args, **kwargs)

    monkeypatch.setattr(pl, "pallas_call", spy)
    return seen


@pytest.mark.parametrize("tile", [(8, 16), (16, 32), (32, 32), (32, 64)])
@pytest.mark.parametrize("n,dtype", [(512, jnp.float32), (256, jnp.bfloat16)])
@pytest.mark.parametrize("name,n_in,n_out", [("advec_u", 3, 1),
                                             ("diff_uvw", 4, 3)])
def test_stencil_vmem_limit_covers_the_tile(name, n_in, n_out, n, dtype,
                                            tile, v5e, compiler_params):
    b = get_kernel(name)
    problem = (n, n, n)
    meta = [jax.ShapeDtypeStruct(problem, dtype)] * n_in + [
        jax.ShapeDtypeStruct((1, 4), jnp.float32)]
    b.make(b.default_config() | {"block_z": tile[0], "block_y": tile[1]},
           meta)
    (cp,) = compiler_params
    assert isinstance(cp, pltpu.CompilerParams)
    working_set = sc.stencil_vmem_bytes(problem, *tile, n_in, n_out,
                                        dtype_bytes=4)
    assert cp.vmem_limit_bytes >= working_set
    assert 16 * MiB <= cp.vmem_limit_bytes <= sc.V5E_VMEM_BYTES


def test_stencil_vmem_limit_is_capped():
    assert sc.stencil_vmem_limit((512, 512, 512), 32, 256, 4, 3) \
        == sc.V5E_VMEM_BYTES == 128 * MiB


def _build_default(name, problem, dtype):
    b = get_kernel(name)
    b.make(b.default_config(), b.make_probe_args(problem, dtype))


def test_other_kernels_keep_their_compiler_params(v5e, compiler_params):
    _build_default("matmul", (256, 256, 256), "float32")
    _build_default("flash_attention_causal", (2, 2, 256, 128), "bfloat16")
    _build_default("decode_attention", (2, 4, 2, 128, 256), "bfloat16")
    _build_default("mla_attention", (2, 4, 256, 128, 64, 640), "bfloat16")
    arena = jax.ShapeDtypeStruct((2, 2, 2, 128, 256), jnp.bfloat16)
    row = jax.ShapeDtypeStruct((2, 2, 2, 128), jnp.bfloat16)
    jax.eval_shape(da_mod.write_position, arena, arena, row, row,
                   jax.ShapeDtypeStruct((), jnp.int32))
    want = [("parallel", "parallel", "arbitrary"),      # matmul
            ("arbitrary", "arbitrary", "arbitrary"),    # flash
            ("parallel", "parallel", "arbitrary"),      # decode attention
            ("parallel", "arbitrary"),                  # mla attention
            ("parallel",) * 3]                          # kv write
    assert compiler_params == [pltpu.CompilerParams(dimension_semantics=ds)
                               for ds in want]
    assert all(cp.vmem_limit_bytes is None for cp in compiler_params)
