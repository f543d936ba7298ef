"""advec_u — the paper's first MicroHH kernel (§5.2), as a tunable Pallas
TPU kernel: flux-form advection with 5th-order interpolation on a periodic
3-D grid.

TPU adaptation of the paper's Table 2 parameters (see DESIGN.md §2):
  block_z/block_y      <- Block size X/Y/Z   (X stays whole: lane dim)
  traversal            <- Unravel permutation (grid-major order)
  unroll_z             <- Loop unrolling / tile factor
  dim_semantics        <- scheduling freedom given to Mosaic
The paper's register-pressure axis (min blocks per SM) becomes the VMEM
feasibility restriction enforced by the workload model.
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
from ._stencil_common import (BOTH, check_blocks, field_specs, field_views,
                              operands, out_spec, stencil_grid,
                              stencil_hbm_bytes, stencil_lowering,
                              stencil_vmem_bytes)


builder = KernelBuilder("advec_u", source="repro.kernels.advec_u")
# A field with both halo pairs is read 1 + 2·HALO_Z/bz + 2·HALO_Y/by
# times (_stencil_common.halo_read_factor): 1.75 at the default (32, 32),
# which tiles every grid down to 32³. Each build asks Mosaic for its
# tile's VMEM (_stencil_common.stencil_vmem_limit), since tiles this
# large outgrow the 16 MiB scoped default.
builder.tune("block_z", (4, 8, 16, 32), default=32)
builder.tune("block_y", (8, 16, 32, 64, 128, 256), default=32)
builder.tune("traversal", ("zy", "yz"), default="zy")
builder.tune("unroll_z", (1, 2, 4), default=1)
builder.tune("dim_semantics", ("arbitrary", "parallel"), default="arbitrary")
builder.restriction("block_z % unroll_z == 0")


#: The halo pairs each input brings: the stencil shifts u along every
#: axis, v along y alone and w along z alone.
FIELD_HALOS = [BOTH, ("y",), ("z",)]


@builder.problem_size
def _problem(u, v, w, scal):
    return tuple(int(d) for d in u.shape)


def _kernel_body(unroll_z: int, field_halos, refs):
    scal_ref, out_ref = refs[0], refs[-1]
    fu, fv, fw = field_views(refs[1:-1], field_halos)
    dxi = scal_ref[0, 0]
    dyi = scal_ref[0, 1]
    dzi = scal_ref[0, 2]
    bz = fu.bz
    rows_per = bz // unroll_z
    for c in range(unroll_z):           # python loop == unrolled code
        rows = slice(c * rows_per, (c + 1) * rows_per)
        ut = _ref.advec_terms(
            su_x=lambda s: fu.sx(s, rows), su_y=lambda s: fu.sy(s, rows),
            su_z=lambda s: fu.sz(s, rows), sv_y=lambda s: fv.sy(s, rows),
            sw_z=lambda s: fw.sz(s, rows), dxi=dxi, dyi=dyi, dzi=dzi)
        out_ref[rows] = ut.astype(out_ref.dtype)


@builder.build
def _build(config, problem, meta, interpret: bool = False):
    nz, ny, nx = problem
    bz, by = config["block_z"], config["block_y"]
    if not check_blocks(problem, bz, by):
        raise ValueError(f"blocks ({bz},{by}) do not tile problem {problem}")
    grid, to_zy = stencil_grid(problem, bz, by, config["traversal"])
    scal_spec = pl.BlockSpec((1, 4), lambda a, b: (0, 0))
    field_halos = FIELD_HALOS
    in_specs = [scal_spec]
    for halos in field_halos:
        in_specs += field_specs(problem, bz, by, to_zy, halos)
    kwargs = stencil_lowering("advec_u", config, problem, field_halos,
                              n_out_fields=1, interpret=interpret)

    dtype = meta[0].dtype
    call = pl.pallas_call(
        functools.partial(_pallas_entry, config["unroll_z"], field_halos),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec(problem, bz, by, to_zy),
        out_shape=jax.ShapeDtypeStruct((nz, ny, nx), dtype),
        interpret=interpret,
        **kwargs,
    )

    def run(u, v, w, scal):
        return call(scal, *operands((u, v, w), field_halos))

    return run


def _pallas_entry(unroll_z, field_halos, *refs):
    _kernel_body(unroll_z, field_halos, refs)


builder.reference(_ref.advec_u_ref)


@builder.probe
def _probe(problem, dtype):
    rng = np.random.default_rng(0)
    u, v, w = (probe_array(rng, problem, dtype) for _ in range(3))
    scal = np.array([[1.1, 0.9, 1.3, 0.0]], np.float32)
    return u, v, w, scal


@builder.workload
def _workload(config, problem, dtype):
    nz, ny, nx = problem
    bz, by = config["block_z"], config["block_y"]
    if not check_blocks(problem, bz, by):
        return Workload(0, 0, 0, 0, valid=False)
    b = 2 if dtype in ("bfloat16", "float16") else 4
    pts = nz * ny * nx
    # compute in f32 inside the kernel -> VMEM holds f32 working set
    vmem = stencil_vmem_bytes(problem, bz, by, n_in_fields=3,
                              n_out_fields=1, dtype_bytes=4)
    hbm = stencil_hbm_bytes(problem, bz, by, 3, 1, b)
    grid = (nz // bz) * (ny // by)
    # y-minor traversal streams HBM-adjacent blocks consecutively
    reuse = 0.92 if config["traversal"] == "zy" else 1.06
    if config["dim_semantics"] == "parallel":
        reuse *= 0.98  # scheduler may overlap epilogues
    return Workload(
        flops=pts * _ref.ADVEC_FLOPS_PER_POINT,
        hbm_bytes=hbm, vmem_bytes=int(vmem), grid=grid,
        mxu_tile=None, lane_extent=nx, sublane_extent=by,
        unroll_ways=config["unroll_z"], reuse=reuse,
        notes={"bz": bz, "by": by})


register(builder)
