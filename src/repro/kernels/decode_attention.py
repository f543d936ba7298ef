"""Decode attention over the KV arena where it lies, with tunable
(block_h, block_k).

One query token per slot attends to its slot's live window of the
stacked arena. The arena is ``(L, B, Hkv, D, S)``, the sequence axis
minor, and the kernel reads it in that stored order, as
``(L, B, Hkv * D, S)`` (the same bytes): the layer index is a
scalar-prefetch argument of the index maps, so no per-layer slice is
ever materialised. The step's own K/V, which the arena does not hold
yet, opens the online softmax; once every layer has run,
:func:`write_position` writes them all at ``pos``, in place. Arena keys live for slot b are
``[max(start[b], pos - window + 1), pos)``; the key-block index map is
clamped to that range, so blocks outside it are neither fetched nor
computed.

A block of ``block_h`` kv heads is one ``(block_h * D, block_k)`` tile
of keys, and both products run on the MXU against it: scores as
``Q @ K`` with ``Q`` block-diagonal (row ``j * block_h + i`` holds query
``j`` of kv head ``i`` in head ``i``'s columns, zeros elsewhere), values
as ``P @ V^T``, whose diagonal blocks are each head's output. The
off-diagonal products are waste the MXU absorbs; a per-head loop of
``(1, D) @ (D, block_k)`` products would reload the MXU's weights for
every head. Softmax state and accumulation are float32.

Scalars (all data, so one compiled step serves every layer, window and
softcap): ``ints = [layer, pos, window]`` (window 0 = unlimited),
``start`` (B,), ``cap`` (1,) float32 (0 = no softcap).
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

from . import ref as _ref
from ._lowering import active_backend, dot_precision, lowering_kwargs

NEG_INF = -1e30


def _live_blocks(start, pos, window, bk: int):
    """First live arena position and the first and last live key blocks
    of one slot (``last < first`` when the window holds no arena key)."""
    lo = jnp.maximum(start, jnp.where(window > 0, pos - window + 1, 0))
    return lo, lo // bk, (pos - 1) // bk


def _kernel(group: int, hb: int, D: int, bk: int, nk: int, precision,
            ints_ref, start_ref, cap_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref,
            o_ref, m_ref, l_ref, acc_ref):
    b, ik = pl.program_id(0), pl.program_id(2)
    pos, window = ints_ref[1], ints_ref[2]
    cap = cap_ref[0, 0]
    scale = D ** -0.5
    lo, first, last = _live_blocks(start_ref[b], pos, window, bk)

    @pl.when(ik == 0)
    def _init():
        # the step's own token: always inside its slot's window
        s = jnp.sum(q_ref[...].astype(jnp.float32)
                    * kn_ref[...].astype(jnp.float32), axis=1,
                    keepdims=True) * scale                   # (R, 1)
        m_ref[...] = _ref.cap_scores(s, cap)
        l_ref[...] = jnp.ones_like(s)
        acc_ref[...] = jnp.broadcast_to(vn_ref[...].astype(jnp.float32),
                                        acc_ref.shape)

    kb = first + ik

    @pl.when(kb <= last)
    def _block():
        s = lax.dot_general(q_ref[...], k_ref[...], (((1,), (0,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32) * scale
        k_pos = kb * bk + lax.broadcasted_iota(jnp.int32, (1, bk), 1)
        live = (k_pos >= lo) & (k_pos < pos)
        s = jnp.where(live, _ref.cap_scores(s, cap), NEG_INF)  # (R, bk)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[...], (((1,), (1,)), ((), ())),
            precision=precision, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _store():
        o = acc_ref[...] / l_ref[...]                     # (R, hb * D)
        row = lax.broadcasted_iota(jnp.int32, o.shape, 0)
        col = lax.broadcasted_iota(jnp.int32, o.shape, 1)
        o = jnp.where(col // D == row % hb, o, 0.0)       # diagonal blocks
        for j in range(group):
            o_ref[pl.ds(j, 1), :] = jnp.sum(
                jnp.where(row // hb == j, o, 0.0), axis=0, keepdims=True)


def decode_attention_ref(k_arena, v_arena, q, k_new, v_new, ints, start,
                         cap):
    """The kernel's oracle on the kernel's arguments, and the route off
    the chip: the step's K/V written into a copy of the layer's slice at
    ``pos``, then :func:`repro.kernels.ref.decode_attention_ref`."""
    layer, pos, window = ints[0], ints[1], ints[2]

    def layer_with_step(arena, new):
        c = lax.dynamic_index_in_dim(arena, layer, 0, keepdims=False)
        return lax.dynamic_update_slice_in_dim(
            c, new[..., None].astype(c.dtype), pos, axis=3)

    return _ref.decode_attention_ref(
        q, layer_with_step(k_arena, k_new), layer_with_step(v_arena, v_new),
        pos, window, cap[0], start=start)


def _write_kernel(width: int, pos_ref, k_ref, v_ref, kn_ref, vn_ref,
                  k_out, v_out):
    at = lax.broadcasted_iota(jnp.int32, k_out.shape, 1) == pos_ref[0] % width
    k_out[...] = jnp.where(at, kn_ref[...].T, k_ref[...])
    v_out[...] = jnp.where(at, vn_ref[...].T, v_ref[...])


def write_position(k_arena, v_arena, k, v, pos, interpret: bool = False):
    """Write one position of every layer into the arena, in place: k/v
    (L, B, Hkv, D) at ``pos``. Each (layer, slot) rewrites the 128
    positions around ``pos`` in its rows, whole tiles, read and written
    once: XLA's ``dynamic_update_slice`` of the one-position column
    takes about three times as long on a v5e. The arena's buffers are the
    outputs' (``input_output_aliases``); under a jit that donates the
    arena nothing is copied."""
    L, B, Hkv, D, S = k_arena.shape
    width, rows = 128, math.gcd(Hkv * D, 2048)

    def slab_map(l, b, r, pos):
        return (l, b, r, pos[0] // width)

    def row_map(l, b, r, pos):
        return (l, b, 0, r)

    slab = pl.BlockSpec((None, None, rows, width), slab_map)
    row = pl.BlockSpec((None, None, 1, rows), row_map)
    flat = jax.ShapeDtypeStruct((L, B, Hkv * D, S), k_arena.dtype)
    k_out, v_out = pl.pallas_call(
        functools.partial(_write_kernel, width),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(L, B, Hkv * D // rows),
            in_specs=[slab, slab, row, row], out_specs=[slab, slab]),
        out_shape=[flat, flat], input_output_aliases={1: 0, 2: 1},
        interpret=interpret, name="kv_write",
        **lowering_kwargs(dimension_semantics=("parallel",) * 3,
                          interpret=interpret))(
        jnp.reshape(pos, (1,)).astype(jnp.int32),
        k_arena.reshape(flat.shape), v_arena.reshape(flat.shape),
        k.astype(k_arena.dtype).reshape(L, B, 1, Hkv * D),
        v.astype(v_arena.dtype).reshape(L, B, 1, Hkv * D))
    return k_out.reshape(k_arena.shape), v_out.reshape(v_arena.shape)


builder = KernelBuilder("decode_attention",
                        source="repro.kernels.decode_attention")
builder.tune("block_h", (1, 2, 4, 8, 16, 32), default=32)
builder.tune("block_k", (128, 256, 512, 1024, 2048), default=256)


@builder.problem_size
def _problem(k_arena, v_arena, q, *_):
    _, B, Hkv, D, S = k_arena.shape
    return (int(B), int(q.shape[1]), int(Hkv), int(D), int(S))


def _blocks(config, problem) -> tuple[int, int]:
    """(heads, keys) per block: the config's, cut to divide the arena."""
    _, _, Hkv, _, S = problem
    return math.gcd(config["block_h"], Hkv), math.gcd(config["block_k"], S)


@builder.build
def _build(config, problem, meta, interpret: bool = False):
    B, Hq, Hkv, D, S = problem
    group = Hq // Hkv
    hb, bk = _blocks(config, problem)
    if bk % 128:
        raise ValueError(f"keys per block {bk} do not tile seq {S}")
    nk, nh, R = S // bk, Hkv // hb, group * hb
    if active_backend() == "gpu":
        raise NotImplementedError(
            "decode_attention has no GPU lowering; kernels.ops."
            "decode_attention falls back to the reference path on GPU")

    def kv_map(b, h, ik, ints, start):
        _, first, last = _live_blocks(start[b], ints[1], ints[2], bk)
        return (ints[0], b, h, jnp.minimum(first + ik,
                                           jnp.maximum(first, last)))

    def head_map(b, h, ik, ints, start):
        return (b, h, 0, 0)

    kv_block = pl.BlockSpec((None, None, hb * D, bk), kv_map)

    def head_block(rows):
        return pl.BlockSpec((None, None, rows, hb * D), head_map)

    call = pl.pallas_call(
        functools.partial(_kernel, group, hb, D, bk, nk,
                          dot_precision(meta[0].dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nh, nk),
            in_specs=[pl.BlockSpec((1, 1), lambda *_: (0, 0),
                                   memory_space=pltpu.SMEM),
                      head_block(R), kv_block, kv_block, head_block(1),
                      head_block(1)],
            out_specs=head_block(group),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, hb * D), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((B, nh, group, hb * D),
                                       jnp.float32),
        interpret=interpret, name="decode_attention",
        **lowering_kwargs(dimension_semantics=("parallel", "parallel",
                                               "arbitrary"),
                          interpret=interpret))

    def run(k_arena, v_arena, q, k_new, v_new, ints, start, cap):
        # Each head block's queries as the block-diagonal Q, and the
        # step's K/V as rows, in the arena's dtype: a few kilobytes.
        dtype, L = k_arena.dtype, k_arena.shape[0]
        qh = q.astype(dtype).reshape(B, nh, hb, group, D)
        q_bd = jnp.einsum("bnigd,ik->bngikd", qh, jnp.eye(hb, dtype=dtype))

        def rows(a):
            return a.astype(dtype).reshape(B, nh, 1, hb * D)

        def arena(a):
            return a.reshape(L, B, Hkv * D, S)

        o = call(ints.astype(jnp.int32), start.astype(jnp.int32),
                 cap.astype(jnp.float32).reshape(1, 1),
                 q_bd.reshape(B, nh, R, hb * D), arena(k_arena),
                 arena(v_arena), rows(k_new), rows(v_new))
        return o.reshape(B, nh, group, hb, D).transpose(0, 1, 3, 2, 4).reshape(
            B, Hq, 1, D).astype(q.dtype)

    return run


builder.reference(decode_attention_ref)


@builder.probe
def _probe(problem, dtype):
    B, Hq, Hkv, D, S = problem
    rng = np.random.default_rng(0)
    scale = 1.0 / (D ** 0.5)
    return (probe_array(rng, (1, B, Hkv, D, S), dtype, scale),
            probe_array(rng, (1, B, Hkv, D, S), dtype, scale),
            probe_array(rng, (B, Hq, 1, D), dtype, scale),
            probe_array(rng, (B, Hkv, D), dtype, scale),
            probe_array(rng, (B, Hkv, D), dtype, scale),
            np.array([0, S // 2, 0], np.int32),
            np.zeros((B,), np.int32), np.zeros((1,), np.float32))


@builder.workload
def _workload(config, problem, dtype):
    B, Hq, Hkv, D, S = problem
    hb, bk = _blocks(config, problem)
    if bk % 128:
        return Workload(0, 0, 0, 0, valid=False)
    byt = 2 if dtype in ("bfloat16", "float16") else 4
    R = Hq // Hkv * hb
    # whole arena: the live range is data, not part of the scenario
    kv_bytes = 2 * B * Hkv * D * S * byt
    vmem = 2 * 2 * hb * D * bk * byt + R * (hb * D + 2) * 4
    return Workload(
        flops=4.0 * B * Hq * D * S, hbm_bytes=float(kv_bytes),
        vmem_bytes=int(vmem), grid=B * (Hkv // hb) * (S // bk),
        mxu_tile=(R, bk, hb * D), lane_extent=bk, sublane_extent=hb * D,
        reuse=1.0, notes={"hb": hb, "bk": bk})


register(builder)
