"""Shared machinery for 3-D periodic stencil kernels (advec_u, diff_uvw).

TPU adaptation of the paper's MicroHH kernels: the X axis is the contiguous
lane dimension and is kept whole inside each block; the grid tiles (Z, Y).
Halos are passed as *separate side-slab refs* (each at least the stencil
radius 3 thick) with wrapped (periodic) index maps — TPU has no overlapping
BlockSpec reads, so a field shifted along both tiled axes arrives as five
refs:

    center (bz, by, X), z-lo (4, by, X), z-hi, y-lo (bz, 8, X), y-hi

and a field shifted along one of them brings only that axis's pair.
Y is the sublane dimension of every block, so the y-halo is one full
8-row sublane tile (``HALO_Y = 8``); Mosaic refuses a 4-row block there.
Z is a leading (untiled) dimension and keeps the thinner ``HALO_Z = 4``.

Every slab is read again by the neighbouring tile, so a field with both
pairs is read ``1 + 2·HALO_Z/bz + 2·HALO_Y/by`` times per launch
(:func:`halo_read_factor`): 3.0 at (bz, by) = (8, 16), 1.75 at (32, 32).
Large tiles need more VMEM than Mosaic's scoped default (16 MiB), so each
build asks for its tile's working set (:func:`stencil_vmem_limit`),
capped at one v5e core's VMEM.

The stencil math only ever shifts along one axis at a time, so no corner
slabs are needed. Inside the kernel, per-axis extended views are assembled by
concatenation and shifts become static slices; X shifts are periodic
``jnp.roll`` over the full lane extent.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.obs import runtime as obs

from ._lowering import lowering_kwargs

HALO_Z = 4            # z side-slab thickness (leading dim, untiled)
HALO_Y = 8            # y side-slab thickness (one sublane tile)
STENCIL_RADIUS = 3    # 5th-order interpolation reach
#: The halo pairs of a field shifted along both tiled axes.
BOTH = ("z", "y")
#: Mosaic's scoped VMEM limit where a kernel asks for none.
MOSAIC_DEFAULT_VMEM_BYTES = 16 * 2**20
#: One TPU v5e core's VMEM: 128 MiB ("TPU v5 lite" in JAX's
#: ``jax/_src/pallas/mosaic/tpu_info.py``, ``vmem_capacity_bytes``).
V5E_VMEM_BYTES = 128 * 2**20


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
                to_zy: Callable, halos: tuple[str, ...] = BOTH
                ) -> list[pl.BlockSpec]:
    """The BlockSpecs of one field: the center, then (z-lo, z-hi) where
    ``"z"`` is in ``halos``, then (y-lo, y-hi) where ``"y"`` is."""
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

    specs = [pl.BlockSpec((bz, by, nx), center)]
    if "z" in halos:
        specs += [pl.BlockSpec((HALO_Z, by, nx), z_lo),
                  pl.BlockSpec((HALO_Z, by, nx), z_hi)]
    if "y" in halos:
        specs += [pl.BlockSpec((bz, HALO_Y, nx), y_lo),
                  pl.BlockSpec((bz, HALO_Y, nx), y_hi)]
    return specs


def n_refs(halos: tuple[str, ...]) -> int:
    """Refs of one field: its center and two slabs per halo axis."""
    return 1 + 2 * len(halos)


def operands(fields, field_halos: list[tuple[str, ...]]) -> tuple:
    """Each field repeated once per ref :func:`field_specs` gives it."""
    return tuple(f for f, halos in zip(fields, field_halos)
                 for _ in range(n_refs(halos)))


def field_views(refs, field_halos: list[tuple[str, ...]]) -> list:
    """One :class:`FieldView` per field from its consecutive refs."""
    views, i = [], 0
    for halos in field_halos:
        views.append(FieldView.from_refs(refs[i:i + n_refs(halos)], halos))
        i += n_refs(halos)
    return views


def out_spec(problem: tuple[int, int, int], bz: int, by: int,
             to_zy: Callable) -> pl.BlockSpec:
    nx = problem[2]

    def center(a, b):
        iz, iy = to_zy(a, b)
        return (iz, iy, 0)

    return pl.BlockSpec((bz, by, nx), center)


class FieldView:
    """Kernel-side view of one field: center + per-axis extended arrays.
    Takes plain (already loaded, already cast) block arrays; an axis
    whose halo pair is ``None`` has no extended array and cannot be
    shifted along."""

    def __init__(self, center, zlo=None, zhi=None, ylo=None, yhi=None):
        self.c = center
        self.ext_z = (None if zlo is None
                      else jnp.concatenate([zlo, self.c, zhi], axis=0))
        self.ext_y = (None if ylo is None
                      else jnp.concatenate([ylo, self.c, yhi], axis=1))
        self.bz = self.c.shape[0]
        self.by = self.c.shape[1]

    @classmethod
    def from_refs(cls, refs, halos: tuple[str, ...] = BOTH,
                  dtype=jnp.float32):
        """``refs`` in :func:`field_specs` order for the same ``halos``."""
        arrays = [r[...].astype(dtype) for r in refs]
        axes = [a for a in BOTH if a in halos]
        pairs = dict(zip(axes, zip(arrays[1::2], arrays[2::2])))
        return cls(arrays[0], *pairs.get("z", (None, None)),
                   *pairs.get("y", (None, None)))

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


def halo_read_factor(bz: int, by: int,
                     field_halos: list[tuple[str, ...]]) -> float:
    """Mean HBM reads per input field of one launch at tile (bz, by):
    each field's center once plus, per halo pair it brings, the slabs
    that overlap a neighbouring tile (``2·HALO_Z/bz``, ``2·HALO_Y/by``)."""
    per_axis = {"z": 2 * HALO_Z / bz, "y": 2 * HALO_Y / by}
    return sum(1.0 + sum(per_axis[a] for a in halos)
               for halos in field_halos) / len(field_halos)


def stencil_vmem_limit(problem, bz: int, by: int, n_in_fields: int,
                       n_out_fields: int) -> int:
    """The scoped VMEM limit a stencil tile asks Mosaic for.

    The double-buffered blocks of :func:`stencil_vmem_bytes` counted at
    float32, plus the kernel's float32 copies of each input
    (:class:`FieldView`'s center, ``ext_z`` and ``ext_y``), floored at
    Mosaic's 16 MiB default and capped at one v5e core's VMEM.
    """
    nx = problem[2]
    copies = n_in_fields * 4 * nx * (
        bz * by + (bz + 2 * HALO_Z) * by + bz * (by + 2 * HALO_Y))
    need = stencil_vmem_bytes(problem, bz, by, n_in_fields, n_out_fields,
                              dtype_bytes=4) + copies
    return int(min(V5E_VMEM_BYTES, max(MOSAIC_DEFAULT_VMEM_BYTES, need)))


def stencil_lowering(kernel: str, config, problem,
                     field_halos: list[tuple[str, ...]], n_out_fields: int,
                     interpret: bool) -> dict:
    """The ``pl.pallas_call`` kwargs of one stencil build, gated on the
    active DeviceSpec.backend (not on whether pltpu merely imports):
    Mosaic dimension semantics and the tile's VMEM limit reach only a
    TPU lowering, Triton warps/stages only a GPU one, nothing the
    interpreter. Sets the build's gauges (a compile-cache miss only):
    ``stencil.halo_read_factor`` and ``stencil.vmem_limit_bytes``."""
    bz, by = config["block_z"], config["block_y"]
    limit = stencil_vmem_limit(problem, bz, by, len(field_halos),
                               n_out_fields)
    m = obs.metrics()
    if m is not None:
        m.gauge("stencil.halo_read_factor", kernel=kernel).set(
            halo_read_factor(bz, by, field_halos))
        m.gauge("stencil.vmem_limit_bytes", kernel=kernel).set(limit)
    return lowering_kwargs(
        dimension_semantics=(config["dim_semantics"],) * 2,
        num_warps=8 if by >= 64 else 4,
        num_stages=min(4, 1 + config["unroll_z"]),
        vmem_limit_bytes=limit, interpret=interpret)


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
