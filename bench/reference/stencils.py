"""Plain reference of the MicroHH time step: advec_u, diff_uvw and the
field update, in ``jax.numpy`` on periodic fields, a slab of z-planes at
a time.

The stencil formulas are copied from the program's oracle
(``src/repro/kernels/ref.py``) so that they stay fixed when the program
changes. Arithmetic is in ``compute_dtype`` (float32 unless a control asks
for less) and results are stored in the field dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_C0, _C1, _C2 = 37.0 / 60.0, -8.0 / 60.0, 1.0 / 60.0


SLAB = 16     # z-planes per block of the blockwise evaluation
HALO = 3      # stencil reach


def _xy_shift(f, axis):
    """Shift of a slab's centre planes along x or y (periodic)."""
    c = f[HALO:-HALO]
    return lambda s: c if s == 0 else jnp.roll(c, -s, axis)


def _z_shift(f):
    """Shift along z, read from the slab's halo planes."""
    n = f.shape[0] - 2 * HALO
    return lambda s: f[HALO + s:HALO + s + n]


def _blockwise(fn, *fields):
    """``fn`` over z-slabs of SLAB planes, each with HALO periodic planes
    on either side, so that the temporaries of one slab at a time fit
    beside the fields; the same arithmetic per point as on whole fields."""
    nz = fields[0].shape[0]
    slab = min(SLAB, nz)
    if nz % slab:
        raise ValueError(f"{nz} planes do not split into slabs of {slab}")

    def one(b):
        zs = (b * slab + jnp.arange(-HALO, slab + HALO)) % nz
        return fn(*(jnp.take(f, zs, axis=0) for f in fields))

    out = jax.lax.map(one, jnp.arange(nz // slab))
    return jax.tree.map(lambda o: o.reshape((nz,) + o.shape[2:]), out)


def _interp(s, o):
    # 5th-order interpolation to the face between cells o-1 and o
    return (_C0 * (s(o - 1) + s(o)) + _C1 * (s(o - 2) + s(o + 1))
            + _C2 * (s(o - 3) + s(o + 2)))


def advec_terms(su_x, su_y, su_z, sv_y, sw_z, dxi, dyi, dzi):
    fx_p = 0.5 * (su_x(0) + su_x(1)) * _interp(su_x, 1)
    fx_m = 0.5 * (su_x(-1) + su_x(0)) * _interp(su_x, 0)
    fy_p = 0.5 * (sv_y(0) + sv_y(1)) * _interp(su_y, 1)
    fy_m = 0.5 * (sv_y(-1) + sv_y(0)) * _interp(su_y, 0)
    fz_p = 0.5 * (sw_z(0) + sw_z(1)) * _interp(su_z, 1)
    fz_m = 0.5 * (sw_z(-1) + sw_z(0)) * _interp(su_z, 0)
    return -(dxi * (fx_p - fx_m) + dyi * (fy_p - fy_m)
             + dzi * (fz_p - fz_m))


def diff_term(sf, se, di):
    ev_p = 0.5 * (se(0) + se(1))
    ev_m = 0.5 * (se(-1) + se(0))
    return (di * di) * (ev_p * (sf(1) - sf(0)) - ev_m * (sf(0) - sf(-1)))


def diff_field(sf_x, sf_y, sf_z, se_x, se_y, se_z, dxi, dyi, dzi):
    return (diff_term(sf_x, se_x, dxi) + diff_term(sf_y, se_y, dyi)
            + diff_term(sf_z, se_z, dzi))


@functools.partial(jax.jit, static_argnames="compute_dtype")
def advec_u(u, v, w, scal, compute_dtype="float32"):
    """Advection tendency of u, stored in u's dtype. ``scal`` is
    [[dxi, dyi, dzi, 0]]."""
    c = jnp.dtype(compute_dtype)
    dxi, dyi, dzi = (scal[0, i].astype(c) for i in range(3))

    def block(u, v, w):
        uc, vc, wc = (f.astype(c) for f in (u, v, w))
        return advec_terms(_xy_shift(uc, 2), _xy_shift(uc, 1), _z_shift(uc),
                           _xy_shift(vc, 1), _z_shift(wc),
                           dxi, dyi, dzi).astype(u.dtype)

    return _blockwise(block, u, v, w)


@functools.partial(jax.jit, static_argnames="compute_dtype")
def diff_uvw(u, v, w, evisc, scal, compute_dtype="float32"):
    """Variable-viscosity diffusion tendencies of u, v and w."""
    c = jnp.dtype(compute_dtype)
    dxi, dyi, dzi = (scal[0, i].astype(c) for i in range(3))

    def shifts(f):
        f = f.astype(c)
        return _xy_shift(f, 2), _xy_shift(f, 1), _z_shift(f)

    def block(u, v, w, evisc):
        se = shifts(evisc)
        return tuple(diff_field(*shifts(f), *se, dxi, dyi, dzi).astype(f.dtype)
                     for f in (u, v, w))

    return _blockwise(block, u, v, w, evisc)


@jax.jit
def update(u, v, w, ut, dut, dvt, dwt, dt):
    """One explicit Euler step of the velocity fields, in float32."""
    f32 = jnp.float32

    def step(x, t):
        return (x.astype(f32) + dt * t).astype(x.dtype)

    return (step(u, ut.astype(f32) + dut.astype(f32)),
            step(v, dvt.astype(f32)), step(w, dwt.astype(f32)))


@jax.jit
def rel_err(got, want):
    """max |got - want| over max |want|, in float32 (NaN or inf where
    ``got`` is not finite)."""
    g = got.astype(jnp.float32)
    w = want.astype(jnp.float32)
    err = jnp.max(jnp.abs(g - w))
    err = jnp.where(jnp.all(jnp.isfinite(g)), err, jnp.inf)
    return err / jnp.maximum(jnp.max(jnp.abs(w)), 1e-30)
