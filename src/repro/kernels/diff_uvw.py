"""diff_uvw — the paper's second MicroHH kernel (§5.2): Smagorinsky-style
diffusion of (u, v, w) with a variable eddy viscosity, halo-1 stencil.

Extra tunable vs advec_u: ``fuse_outputs`` — compute all three tendencies in
one kernel (inputs read once) vs three single-output passes (lower VMEM
pressure, 3x input traffic). This is the TPU-shaped analogue of the paper's
observation that algorithmic variants belong in the search space.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

import numpy as np

from repro.core import KernelBuilder, Workload, register
from repro.core.builder import probe_array

from . import ref as _ref
from ._stencil_common import (BOTH, FieldView, check_blocks, field_specs,
                              field_views, operands, out_spec, stencil_grid,
                              stencil_hbm_bytes, stencil_lowering,
                              stencil_vmem_bytes)


builder = KernelBuilder("diff_uvw", source="repro.kernels.diff_uvw")
# Every input is shifted along all three axes, so each brings both halo
# pairs and is read 1 + 2·HALO_Z/bz + 2·HALO_Y/by times
# (_stencil_common.halo_read_factor): 1.75 at the default (32, 32), which
# tiles every grid down to 32³. Each build asks Mosaic for its tile's
# VMEM (_stencil_common.stencil_vmem_limit), since tiles this large
# outgrow the 16 MiB scoped default.
builder.tune("block_z", (4, 8, 16, 32), default=32)
builder.tune("block_y", (8, 16, 32, 64, 128, 256), default=32)
builder.tune("traversal", ("zy", "yz"), default="zy")
builder.tune("unroll_z", (1, 2, 4), default=1)
builder.tune("fuse_outputs", (True, False), default=True)
builder.tune("dim_semantics", ("arbitrary", "parallel"), default="arbitrary")
builder.restriction("block_z % unroll_z == 0")


@builder.problem_size
def _problem(u, v, w, evisc, scal):
    return tuple(int(d) for d in u.shape)


def _axis_shifts(view: FieldView, rows):
    return (lambda s: view.sx(s, rows), lambda s: view.sy(s, rows),
            lambda s: view.sz(s, rows))


def _fused_kernel(unroll_z, *refs):
    scal_ref, (ut_ref, vt_ref, wt_ref) = refs[0], refs[-3:]
    dxi, dyi, dzi = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
    fu, fv, fw, fe = field_views(refs[1:-3], [BOTH] * 4)
    bz = fu.bz
    rows_per = bz // unroll_z
    for c in range(unroll_z):
        rows = slice(c * rows_per, (c + 1) * rows_per)
        se = _axis_shifts(fe, rows)
        for view, out in ((fu, ut_ref), (fv, vt_ref), (fw, wt_ref)):
            sf = _axis_shifts(view, rows)
            ft = _ref.diff_field(*sf, *se, dxi, dyi, dzi)
            out[rows] = ft.astype(out.dtype)


def _single_kernel(unroll_z, *refs):
    scal_ref, out_ref = refs[0], refs[-1]
    dxi, dyi, dzi = scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2]
    ff, fe = field_views(refs[1:-1], [BOTH] * 2)
    bz = ff.bz
    rows_per = bz // unroll_z
    for c in range(unroll_z):
        rows = slice(c * rows_per, (c + 1) * rows_per)
        ft = _ref.diff_field(*_axis_shifts(ff, rows), *_axis_shifts(fe, rows),
                             dxi, dyi, dzi)
        out_ref[rows] = ft.astype(out_ref.dtype)


@builder.build
def _build(config, problem, meta, interpret: bool = False):
    nz, ny, nx = problem
    bz, by = config["block_z"], config["block_y"]
    if not check_blocks(problem, bz, by):
        raise ValueError(f"blocks ({bz},{by}) do not tile problem {problem}")
    grid, to_zy = stencil_grid(problem, bz, by, config["traversal"])
    scal_spec = pl.BlockSpec((1, 4), lambda a, b: (0, 0))
    fspecs = field_specs(problem, bz, by, to_zy)
    ospec = out_spec(problem, bz, by, to_zy)
    dtype = meta[0].dtype
    oshape = jax.ShapeDtypeStruct((nz, ny, nx), dtype)

    if config["fuse_outputs"]:
        kwargs = stencil_lowering("diff_uvw", config, problem, [BOTH] * 4,
                                  n_out_fields=3, interpret=interpret)
        call = pl.pallas_call(
            functools.partial(_fused_kernel, config["unroll_z"]),
            grid=grid,
            in_specs=[scal_spec] + fspecs * 4,
            out_specs=[ospec] * 3,
            out_shape=[oshape] * 3,
            interpret=interpret, **kwargs)

        def run(u, v, w, evisc, scal):
            return tuple(call(scal, *operands((u, v, w, evisc),
                                              [BOTH] * 4)))

        return run

    kwargs = stencil_lowering("diff_uvw", config, problem, [BOTH] * 2,
                              n_out_fields=1, interpret=interpret)
    call = pl.pallas_call(
        functools.partial(_single_kernel, config["unroll_z"]),
        grid=grid,
        in_specs=[scal_spec] + fspecs * 2,
        out_specs=ospec,
        out_shape=oshape,
        interpret=interpret, **kwargs)

    def run(u, v, w, evisc, scal):
        return tuple(call(scal, *operands((f, evisc), [BOTH] * 2))
                     for f in (u, v, w))

    return run


builder.reference(_ref.diff_uvw_ref)


@builder.probe
def _probe(problem, dtype):
    rng = np.random.default_rng(0)
    u, v, w = (probe_array(rng, problem, dtype) for _ in range(3))
    # eddy viscosity is physically nonnegative
    evisc = np.abs(probe_array(rng, problem, dtype)) + np.asarray(
        0.1, dtype=u.dtype)
    scal = np.array([[1.1, 0.9, 1.3, 0.0]], np.float32)
    return u, v, w, evisc, scal


@builder.workload
def _workload(config, problem, dtype):
    nz, ny, nx = problem
    bz, by = config["block_z"], config["block_y"]
    if not check_blocks(problem, bz, by):
        return Workload(0, 0, 0, 0, valid=False)
    b = 2 if dtype in ("bfloat16", "float16") else 4
    pts = nz * ny * nx
    flops = pts * _ref.DIFF_FLOPS_PER_POINT_PER_FIELD * 3
    grid = (nz // bz) * (ny // by)
    reuse = 0.92 if config["traversal"] == "zy" else 1.06
    if config["dim_semantics"] == "parallel":
        reuse *= 0.98
    if config["fuse_outputs"]:
        vmem = stencil_vmem_bytes(problem, bz, by, 4, 3, 4)
        hbm = stencil_hbm_bytes(problem, bz, by, 4, 3, b)
    else:
        # three passes: each reads its field + evisc, writes one output
        vmem = stencil_vmem_bytes(problem, bz, by, 2, 1, 4)
        hbm = 3 * stencil_hbm_bytes(problem, bz, by, 2, 1, b)
        grid *= 3
    return Workload(
        flops=flops, hbm_bytes=hbm, vmem_bytes=int(vmem), grid=grid,
        mxu_tile=None, lane_extent=nx, sublane_extent=by,
        unroll_ways=config["unroll_z"], reuse=reuse,
        notes={"bz": bz, "by": by, "fused": config["fuse_outputs"]})


register(builder)
