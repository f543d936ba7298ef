"""Shared machinery for 3-D periodic stencil kernels (advec_u, diff_uvw).

TPU adaptation of the paper's MicroHH kernels: the X axis is the contiguous
lane dimension and is kept whole inside each block; the grid tiles (Z, Y).
Halos are passed as *separate side-slab refs* (each at least the stencil
radius 3 thick) with wrapped (periodic) index maps — TPU has no overlapping
BlockSpec reads, so each field arrives as five refs:

    center (bz, by, X), z-lo (4, by, X), z-hi, y-lo (bz, 8, X), y-hi

Y is the sublane dimension of every block, so the y-halo is one full
8-row sublane tile (``HALO_Y = 8``); Mosaic refuses a 4-row block there.
Z is a leading (untiled) dimension and keeps the thinner ``HALO_Z = 4``.

The stencil math only ever shifts along one axis at a time, so no corner
slabs are needed. Inside the kernel, per-axis extended views are assembled by
concatenation and shifts become static slices; X shifts are periodic
``jnp.roll`` over the full lane extent.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax.experimental import pallas as pl

HALO_Z = 4            # z side-slab thickness (leading dim, untiled)
HALO_Y = 8            # y side-slab thickness (one sublane tile)
STENCIL_RADIUS = 3    # 5th-order interpolation reach


def divides(a: int, b: int) -> bool:
    return b % a == 0


def stencil_grid(problem: tuple[int, int, int], bz: int, by: int,
                 traversal: str) -> tuple[tuple[int, int], Callable]:
    """Returns (grid, to_zy) where to_zy maps grid program ids -> (iz, iy)."""
    nz, ny, _ = problem
    gz, gy = nz // bz, ny // by
    if traversal == "zy":        # z major, y minor (y-adjacent = HBM-adjacent)
        return (gz, gy), lambda a, b: (a, b)
    elif traversal == "yz":      # y major, z minor
        return (gy, gz), lambda a, b: (b, a)
    raise ValueError(f"bad traversal {traversal!r}")


def field_specs(problem: tuple[int, int, int], bz: int, by: int,
                to_zy: Callable) -> list[pl.BlockSpec]:
    """The five BlockSpecs (center, z-lo, z-hi, y-lo, y-hi) for one field."""
    nz, ny, nx = problem
    hz, hy = nz // HALO_Z, ny // HALO_Y
    rz, ry = bz // HALO_Z, by // HALO_Y

    def center(a, b):
        iz, iy = to_zy(a, b)
        return (iz, iy, 0)

    def z_lo(a, b):
        iz, iy = to_zy(a, b)
        return ((iz * rz - 1) % hz, iy, 0)

    def z_hi(a, b):
        iz, iy = to_zy(a, b)
        return ((iz * rz + rz) % hz, iy, 0)

    def y_lo(a, b):
        iz, iy = to_zy(a, b)
        return (iz, (iy * ry - 1) % hy, 0)

    def y_hi(a, b):
        iz, iy = to_zy(a, b)
        return (iz, (iy * ry + ry) % hy, 0)

    return [
        pl.BlockSpec((bz, by, nx), center),
        pl.BlockSpec((HALO_Z, by, nx), z_lo),
        pl.BlockSpec((HALO_Z, by, nx), z_hi),
        pl.BlockSpec((bz, HALO_Y, nx), y_lo),
        pl.BlockSpec((bz, HALO_Y, nx), y_hi),
    ]


def out_spec(problem: tuple[int, int, int], bz: int, by: int,
             to_zy: Callable) -> pl.BlockSpec:
    nx = problem[2]

    def center(a, b):
        iz, iy = to_zy(a, b)
        return (iz, iy, 0)

    return pl.BlockSpec((bz, by, nx), center)


class FieldView:
    """Kernel-side view of one field: center + per-axis extended arrays.
    Takes plain (already loaded, already cast) block arrays."""

    def __init__(self, center, zlo, zhi, ylo, yhi):
        self.c = center
        self.ext_z = jnp.concatenate([zlo, self.c, zhi], axis=0)
        self.ext_y = jnp.concatenate([ylo, self.c, yhi], axis=1)
        self.bz = self.c.shape[0]
        self.by = self.c.shape[1]

    @classmethod
    def from_refs(cls, center_ref, zlo_ref, zhi_ref, ylo_ref, yhi_ref,
                  dtype=jnp.float32):
        return cls(*(r[...].astype(dtype)
                     for r in (center_ref, zlo_ref, zhi_ref,
                               ylo_ref, yhi_ref)))

    def sx(self, s: int, rows: slice | None = None):
        """Shift along x by s cells (periodic over the full lane extent)."""
        a = self.c if rows is None else self.c[rows]
        return a if s == 0 else jnp.roll(a, -s, axis=2)

    def sy(self, s: int, rows: slice | None = None):
        a = self.ext_y if rows is None else self.ext_y[rows]
        return a[:, HALO_Y + s: HALO_Y + s + self.by, :]

    def sz(self, s: int, rows: slice | None = None):
        lo = HALO_Z + s + (0 if rows is None else rows.start)
        n = self.bz if rows is None else rows.stop - rows.start
        return self.ext_z[lo: lo + n]


def check_blocks(problem: tuple[int, int, int], bz: int, by: int) -> bool:
    """Static feasibility of a (bz, by) tiling for a (nz, ny, nx) problem."""
    nz, ny, _ = problem
    return (divides(HALO_Z, bz) and divides(HALO_Y, by)
            and bz <= nz and by <= ny
            and divides(bz, nz) and divides(by, ny)
            and divides(HALO_Z, nz) and divides(HALO_Y, ny))


def stencil_vmem_bytes(problem, bz: int, by: int, n_in_fields: int,
                       n_out_fields: int, dtype_bytes: int,
                       buffers: int = 2) -> int:
    """Per-program VMEM working set for the 5-ref stencil layout."""
    nx = problem[2]
    per_field = (bz * by + 2 * HALO_Z * by + 2 * bz * HALO_Y) * nx
    out = bz * by * nx
    return (n_in_fields * per_field + n_out_fields * out) \
        * dtype_bytes * buffers


def stencil_hbm_bytes(problem, bz: int, by: int, n_in_fields: int,
                      n_out_fields: int, dtype_bytes: int) -> float:
    nz, ny, nx = problem
    pts = nz * ny * nx
    halo_overhead = 2 * HALO_Z / bz + 2 * HALO_Y / by
    return (n_in_fields * pts * (1.0 + halo_overhead)
            + n_out_fields * pts) * dtype_bytes
