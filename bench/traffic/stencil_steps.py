"""Generator of the stencil cells' inputs: the fields of one domain, made
on the device from the seed in one jitted call.

Parameters (``bench/traffic/<mix>.json``): ``grid`` (points per axis),
``dtype`` and ``dt``. The window then runs back-to-back time steps.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import harness


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _make(key, n: int, dtype: str):
    ku, kv, kw, ke = jax.random.split(key, 4)
    u, v, w = (jax.random.normal(k, (n, n, n), jnp.float32).astype(dtype)
               for k in (ku, kv, kw))
    evisc = (jax.random.normal(ke, (n, n, n), jnp.float32) ** 2
             + 0.1).astype(dtype)
    return u, v, w, evisc


def fields(params: dict, seed: int, inverse_spacings) -> tuple:
    """(u, v, w, evisc, scal) on the default device; scal is the kernels'
    (1, 4) float32 [[dxi, dyi, dzi, 0]]."""
    u, v, w, evisc = _make(harness.key_from_seed(seed), int(params["grid"]),
                           params["dtype"])
    scal = jnp.asarray([[*map(float, inverse_spacings), 0.0]], jnp.float32)
    return jax.block_until_ready((u, v, w, evisc, scal))
