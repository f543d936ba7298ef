"""Public jit-friendly entry points for all kernels.

Each op routes through a module-level :class:`WisdomKernel` — the runtime
selection + compilation layer (paper §4.5). On TPU the Pallas kernel runs
with the wisdom-selected configuration; on other hosts (or for feature
combinations the Pallas kernel does not support) the ``ref.py`` oracle runs
instead. Model code only ever calls these functions.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import WisdomKernel, resolve_backend
from repro.core.device import current_device

from . import advec_u as _advec_mod
from . import decode_attention as _da_mod
from . import diff_uvw as _diff_mod
from . import flash_attention as _fa_mod
from . import matmul as _mm_mod
from . import mla_attention as _mla_mod
from . import ref

advec_u_kernel = WisdomKernel(_advec_mod.builder)
diff_uvw_kernel = WisdomKernel(_diff_mod.builder)
matmul_kernel = WisdomKernel(_mm_mod.builder)
fa_causal_kernel = WisdomKernel(_fa_mod.causal_builder)
fa_full_kernel = WisdomKernel(_fa_mod.full_builder)
decode_attention_kernel = WisdomKernel(_da_mod.builder)
mla_attention_kernel = WisdomKernel(_mla_mod.builder)

_ALL_KERNELS = (advec_u_kernel, diff_uvw_kernel, matmul_kernel,
                fa_causal_kernel, fa_full_kernel, decode_attention_kernel,
                mla_attention_kernel)


def reload_wisdom() -> None:
    """Invalidate cached wisdom on all ops (after re-tuning)."""
    for k in _ALL_KERNELS:
        k.invalidate()


def pack_scalars(dxi: float, dyi: float, dzi: float):
    return jnp.asarray([[dxi, dyi, dzi, 0.0]], dtype=jnp.float32)


def advec_u(u, v, w, dxi: float, dyi: float, dzi: float):
    """Advection tendency of u (paper kernel 1)."""
    return advec_u_kernel(u, v, w, pack_scalars(dxi, dyi, dzi))


def diff_uvw(u, v, w, evisc, dxi: float, dyi: float, dzi: float):
    """Diffusion tendencies (ut, vt, wt) (paper kernel 2)."""
    return diff_uvw_kernel(u, v, w, evisc, pack_scalars(dxi, dyi, dzi))


def matmul(a, b):
    return matmul_kernel(a, b)


#: Always-on tally of the route every :func:`attention` call took:
#: ``"flash"``, or ``"reference:<reason>"`` for the jnp oracle. A call
#: under an outer ``jax.jit`` counts once per trace, like
#: ``WisdomKernel.tier_counts``.
attention_routes: dict[str, int] = {}


def _attention_route(Sq: int, Sk: int, D: int, window, softcap, scale,
                     kv_offset) -> str:
    if resolve_backend() not in ("pallas", "interpret"):
        return "reference:backend"
    # flash has a TPU (Mosaic) lowering only — on GPU devices the
    # full-featured jnp oracle serves instead (docs/gpu-backend.md)
    if current_device().backend == "gpu":
        return "reference:gpu"
    if window is not None:
        return "reference:window"
    if softcap is not None:
        return "reference:softcap"
    if scale is not None and abs(scale - D ** -0.5) >= 1e-12:
        return "reference:scale"
    if kv_offset != 0:
        return "reference:kv_offset"
    if Sq != Sk:
        return "reference:sq_ne_sk"
    if Sq % 128 or D % 128:
        return "reference:shape"
    return "flash"


def attention(q, k, v, *, causal: bool = True, window: int | None = None,
              softcap: float | None = None, scale: float | None = None,
              kv_offset: int = 0):
    """Multi-head attention, q: (B, Hq, Sq, D), k/v: (B, Hkv, Sk, D).

    Routes to the Pallas flash kernel when the feature set and shapes allow;
    otherwise the full-featured jnp oracle (always the case on CPU hosts).
    Each call's route is tallied in :data:`attention_routes`.
    """
    B, Hq, Sq, D = q.shape
    Sk = k.shape[2]
    route = _attention_route(Sq, Sk, D, window, softcap, scale, kv_offset)
    attention_routes[route] = attention_routes.get(route, 0) + 1
    if route != "flash":
        return ref.attention_ref(q, k, v, causal=causal, window=window,
                                 softcap=softcap, scale=scale,
                                 kv_offset=kv_offset)
    Hkv = k.shape[1]
    qf = q.reshape(B * Hq, Sq, D)
    kf = k.reshape(B * Hkv, Sk, D)
    vf = v.reshape(B * Hkv, Sk, D)
    kernel = fa_causal_kernel if causal else fa_full_kernel
    of = kernel(qf, kf, vf)
    return of.reshape(B, Hq, Sq, D)


#: Always-on tally of the route every :func:`decode_attention` call took:
#: ``"pallas"``, or ``"reference:<reason>"`` for the jnp oracle; once
#: per trace under an outer ``jax.jit``, like :data:`attention_routes`.
decode_attention_routes: dict[str, int] = {}


def _decode_attention_route(S: int) -> str:
    if resolve_backend() not in ("pallas", "interpret"):
        return "reference:backend"
    if current_device().backend == "gpu":
        return "reference:gpu"
    if S % 128:
        return "reference:shape"
    return "pallas"


def decode_attention(q, cache_k, cache_v, k_new, v_new, layer, pos, *,
                     window=0, softcap: float | None = None, start=None):
    """One query token per slot against layer ``layer`` of the KV arena.

    q: (B, Hq, 1, D); cache_k/v: the whole stacked arena
    (L, B, Hkv, D, S), read where it lies; k_new/v_new: (B, Hkv, D), the
    step's own K/V, attended at ``pos`` though the arena does not hold
    them yet (the caller writes them after the step). ``window`` may be
    traced (0 = unlimited); ``start`` (B,) scopes slot b to positions
    [start[b], pos]. Routes to the Pallas kernel where the backend and
    the arena's length allow, else to the jnp oracle
    (:func:`repro.kernels.ref.decode_attention_ref`); each call's route
    is tallied in :data:`decode_attention_routes`.
    """
    route = _decode_attention_route(cache_k.shape[-1])
    decode_attention_routes[route] = decode_attention_routes.get(route, 0) + 1
    ints = jnp.stack([jnp.asarray(x, jnp.int32) for x in (layer, pos, window)])
    cap = jnp.full((1,), softcap or 0.0, jnp.float32)
    if route != "pallas":
        return _da_mod.decode_attention_ref(cache_k, cache_v, q, k_new, v_new,
                                            ints, start, cap)
    if start is None:
        start = jnp.zeros((q.shape[0],), jnp.int32)
    return decode_attention_kernel(cache_k, cache_v, q, k_new, v_new, ints,
                                   start, cap)


#: Always-on tally of the route every :func:`mla_decode_attention` call
#: took, as :data:`decode_attention_routes` does for GQA.
mla_decode_attention_routes: dict[str, int] = {}


def mla_decode_attention(q_c, q_pe, arena, new, layer, pos, *, scale: float,
                         start=None):
    """Absorbed latent attention of one query token per slot against
    layer ``layer`` of the latent arena.

    q_c: (B, H, dv), the nope queries through ``W^UK``; q_pe: (B, H, dr),
    the roped queries; arena: the whole stacked latent arena
    (L, B, S, dv + dr), read where it lies; new: (B, dv + dr), the
    step's own latent row, attended at ``pos`` though the arena does not
    hold it yet. ``start`` (B,) scopes slot b to positions
    [start[b], pos]. Returns (B, H, dv) float32. Routes to the Pallas
    kernel (:mod:`repro.kernels.mla_attention`) where the backend and
    the arena's length allow, else to its jnp oracle; each call's route
    is tallied in :data:`mla_decode_attention_routes`.
    """
    route = _decode_attention_route(arena.shape[2])
    mla_decode_attention_routes[route] = \
        mla_decode_attention_routes.get(route, 0) + 1
    ints = jnp.stack([jnp.asarray(x, jnp.int32) for x in (layer, pos)])
    scale = jnp.full((1,), scale, jnp.float32)
    if route != "pallas":
        return _mla_mod.mla_attention_ref(arena, q_c, q_pe, new, ints, start,
                                          scale)
    if start is None:
        start = jnp.zeros((q_c.shape[0],), jnp.int32)
    return mla_attention_kernel(arena, q_c, q_pe, new, ints, start, scale)


def write_kv_position(k_arena, v_arena, k, v, pos):
    """Write the step's K/V of every layer, k/v (L, B, Hkv, D), into the
    arena (L, B, Hkv, D, S) at ``pos``; returns the new (k_arena,
    v_arena). Takes the route :func:`decode_attention` takes for this
    arena: the in-place Pallas write
    (:func:`repro.kernels.decode_attention.write_position`) where it
    runs the kernel, else ``dynamic_update_slice``."""
    route = _decode_attention_route(k_arena.shape[-1])
    if route == "pallas":
        return _da_mod.write_position(
            k_arena, v_arena, k, v, pos,
            interpret=resolve_backend() == "interpret")
    return tuple(jax.lax.dynamic_update_slice_in_dim(
        arena, new[..., None].astype(arena.dtype), pos, axis=4)
        for arena, new in ((k_arena, k), (v_arena, v)))
