"""Absorbed latent (MLA) decode attention over the latent arena where it
lies, with a tunable ``block_k``.

One query token per slot, every head at once, attends to its slot's live
window of the stacked latent arena ``(L, B, S, W)``: each position's
latent (``dv`` wide, the values) and its roped key (``dr``), zeros to
the row's width ``W``, one key row shared by all heads. The layer index
is a scalar-prefetch argument of the index map, so no per-layer slice
is materialised. The queries come absorbed (``q_c``, the nope query
through ``W^UK``, ``dv`` wide) and roped (``q_pe``), concatenated and
padded to ``W``, so the scores of all heads are one MXU product
``Q @ K^T`` per block and the values another, ``P @ K[:, :dv]``. The
step's own latent row, which the arena does not hold yet, opens the
online softmax; the caller writes it at ``pos`` once every layer has
run. Slot b's live arena keys are ``[start[b], pos)``; the key-block
index map is clamped to that range (``decode_attention._live_blocks``),
so blocks outside it are neither fetched nor computed. Softmax state
and accumulation are float32.

Scalars (data, so one compiled step serves every layer):
``ints = [layer, pos]``, ``start`` (B,), ``scale`` (1,) float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import KernelBuilder, Workload, register
from repro.core.builder import probe_array

from ._lowering import active_backend, dot_precision, lowering_kwargs
from .decode_attention import NEG_INF, _live_blocks


def _kernel(dv: int, bk: int, nk: int, precision, ints_ref, start_ref,
            scale_ref, q_ref, kv_ref, kn_ref, o_ref, m_ref, l_ref, acc_ref):
    b, ik = pl.program_id(0), pl.program_id(1)
    pos = ints_ref[1]
    scale = scale_ref[0, 0]
    lo, first, last = _live_blocks(start_ref[b], pos, 0, bk)

    @pl.when(ik == 0)
    def _init():
        # the step's own token: always inside its slot's window
        kn = kn_ref[...].astype(jnp.float32)                 # (1, W)
        m_ref[...] = jnp.sum(q_ref[...].astype(jnp.float32) * kn, axis=1,
                             keepdims=True) * scale          # (H, 1)
        l_ref[...] = jnp.ones_like(m_ref)
        acc_ref[...] = jnp.broadcast_to(kn[:, :dv], acc_ref.shape)

    kb = first + ik

    @pl.when(kb <= last)
    def _block():
        kv = kv_ref[...]                                     # (bk, W)
        s = lax.dot_general(q_ref[...], kv, (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32) * scale
        k_pos = kb * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        s = jnp.where((k_pos >= lo) & (k_pos < pos), s, NEG_INF)  # (H, bk)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(kv.dtype), kv[:, :dv], (((1,), (0,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...] / l_ref[...]


def _queries(q_c, q_pe, width: int, dtype):
    """(B, H, W): the absorbed and roped queries, zeros to the row's
    width, in the arena's dtype."""
    q = jnp.concatenate([q_c.astype(dtype), q_pe.astype(dtype)], axis=-1)
    return jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[-1])))


def mla_attention_ref(arena, q_c, q_pe, new, ints, start, scale):
    """The kernel's oracle on the kernel's arguments, and the route off
    the chip: the step's latent row written into a copy of the layer's
    slice at ``pos``, then a masked softmax over ``[start[b], pos]``.
    Returns (B, H, dv) float32."""
    layer, pos = ints[0], ints[1]
    dv = q_c.shape[-1]
    cache = lax.dynamic_index_in_dim(arena, layer, 0, keepdims=False)
    cache = lax.dynamic_update_slice_in_dim(
        cache, new[:, None, :].astype(cache.dtype), pos, axis=1)
    dtype = cache.dtype
    q = _queries(q_c, q_pe, cache.shape[-1], dtype)
    s = jnp.einsum("bhe,bke->bhk", q, cache,
                   preferred_element_type=jnp.float32) * scale[0]
    k_pos = jnp.arange(cache.shape[1])
    live = k_pos[None, :] <= pos
    if start is not None:
        live = live & (k_pos[None, :] >= start[:, None])
    s = jnp.where(live[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhk,bkl->bhl", p.astype(dtype), cache[..., :dv],
                      preferred_element_type=jnp.float32)


builder = KernelBuilder("mla_attention", source="repro.kernels.mla_attention")
builder.tune("block_k", (128, 256, 512, 1024, 2048), default=512)


@builder.problem_size
def _problem(arena, q_c, q_pe, *_):
    _, B, S, W = arena.shape
    return (int(B), int(q_c.shape[1]), int(S), int(q_c.shape[-1]),
            int(q_pe.shape[-1]), int(W))


def _block_k(config, problem) -> int:
    """Keys per block: the config's, cut to divide the arena."""
    return math.gcd(config["block_k"], problem[2])


@builder.build
def _build(config, problem, meta, interpret: bool = False):
    B, H, S, dv, dr, width = problem
    bk = _block_k(config, problem)
    if bk % 128:
        raise ValueError(f"keys per block {bk} do not tile seq {S}")
    if active_backend() == "gpu":
        raise NotImplementedError(
            "mla_attention has no GPU lowering; kernels.ops."
            "mla_decode_attention falls back to the reference path on GPU")
    nk = S // bk

    def kv_map(b, ik, ints, start):
        _, first, last = _live_blocks(start[b], ints[1], 0, bk)
        return (ints[0], b, jnp.minimum(first + ik, jnp.maximum(first, last)),
                0)

    def slot_map(b, ik, ints, start):
        return (b, 0, 0)

    call = pl.pallas_call(
        functools.partial(_kernel, dv, bk, nk, dot_precision(meta[0].dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nk),
            in_specs=[pl.BlockSpec((1, 1), lambda *_: (0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((None, H, width), slot_map),
                      pl.BlockSpec((None, None, bk, width), kv_map),
                      pl.BlockSpec((None, 1, width), slot_map)],
            out_specs=pl.BlockSpec((None, H, dv), slot_map),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, dv), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, dv), jnp.float32),
        interpret=interpret, name="mla_attention",
        **lowering_kwargs(dimension_semantics=("parallel", "arbitrary"),
                          interpret=interpret))

    def run(arena, q_c, q_pe, new, ints, start, scale):
        dtype = arena.dtype
        q = _queries(q_c, q_pe, width, dtype)
        return call(ints.astype(jnp.int32), start.astype(jnp.int32),
                    scale.astype(jnp.float32).reshape(1, 1), q, arena,
                    new.astype(dtype).reshape(B, 1, width))

    return run


builder.reference(mla_attention_ref)


@builder.probe
def _probe(problem, dtype):
    B, H, S, dv, dr, width = problem
    rng = np.random.default_rng(0)
    return (probe_array(rng, (1, B, S, width), dtype, 1.0),
            probe_array(rng, (B, H, dv), dtype, 0.05),
            probe_array(rng, (B, H, dr), dtype, 0.05),
            probe_array(rng, (B, width), dtype, 1.0),
            np.array([0, S // 2], np.int32), np.zeros((B,), np.int32),
            np.full((1,), (dv + dr) ** -0.5, np.float32))


@builder.workload
def _workload(config, problem, dtype):
    B, H, S, dv, dr, width = problem
    bk = _block_k(config, problem)
    if bk % 128:
        return Workload(0, 0, 0, 0, valid=False)
    byt = 2 if dtype in ("bfloat16", "float16") else 4
    # whole arena: the live range is data, not part of the scenario
    vmem = 2 * bk * width * byt + H * (width * byt + dv * 4 + 2 * 4)
    return Workload(
        flops=2.0 * B * H * S * (width + dv), hbm_bytes=float(B * S * width
                                                            * byt),
        vmem_bytes=int(vmem), grid=B * (S // bk),
        mxu_tile=(H, bk, width), lane_extent=width, sublane_extent=bk,
        reuse=1.0, notes={"bk": bk})


register(builder)
