"""Flash attention (forward) with tunable (block_q, block_k) — the LM-stack
hot-spot that integrates Kernel Launcher into the model framework.

Layout: heads are flattened into the leading axis — q: (B*Hq, S, D),
k/v: (B*Hkv, S, D). GQA is handled *inside* the index map (kv head =
q head // group), so grouped kv is never materialized. Online softmax state
lives in f32 VMEM scratch; the k axis is the innermost, "arbitrary" grid
dimension. Fully-masked causal blocks are skipped with ``pl.when``.

Two builders are registered (causal / full) because causality changes the
problem's workload, not just a value — they tune and store wisdom
independently.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import KernelBuilder, Workload, register
from repro.core.builder import probe_array

from . import ref as _ref
from ._lowering import active_backend, dot_precision, lowering_kwargs

NEG_INF = -1e30


def _fa_kernel(causal: bool, scale: float, nk: int, bq: int, bk: int,
               precision, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def body():
        q = q_ref[0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0].astype(jnp.float32)            # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32) * scale   # (bq, bk)
        if causal:
            q_pos = qb * bq + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bk), 0)
            k_pos = kb * bk + jax.lax.broadcasted_iota(jnp.int32,
                                                       (bq, bk), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_ref[:, :1]                        # (bq, 1)
        l_prev = l_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)                       # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)              # (bq, 1)
        l_new = alpha * l_prev + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    if causal:
        # skip blocks entirely above the diagonal
        pl.when(qb * bq + bq - 1 >= kb * bk)(body)
    else:
        body()

    @pl.when(kb == nk - 1)
    def _store():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _make_builder(causal: bool) -> KernelBuilder:
    name = "flash_attention_causal" if causal else "flash_attention_full"
    b = KernelBuilder(name, source="repro.kernels.flash_attention")
    b.tune("block_q", (128, 256, 512, 1024), default=128)
    b.tune("block_k", (128, 256, 512, 1024), default=128)
    b.tune("dim_semantics", ("arbitrary", "parallel"), default="arbitrary")

    @b.problem_size
    def _problem(q, k, v):
        bh, s, d = q.shape
        return (int(bh), int(k.shape[0]), int(s), int(d))

    @b.build
    def _build(config, problem, meta, interpret: bool = False):
        BH, BHkv, S, D = problem
        group = BH // BHkv
        bq = min(config["block_q"], S)
        bk = min(config["block_k"], S)
        if S % bq or S % bk:
            raise ValueError(f"blocks ({bq},{bk}) do not tile seq {S}")
        gq, gk = S // bq, S // bk
        scale = 1.0 / (D ** 0.5)

        if active_backend() == "gpu":
            # No Triton lowering yet (see docs/gpu-backend.md's lowering
            # matrix); ops.attention never routes here on GPU devices.
            raise NotImplementedError(
                f"{name} has no GPU lowering; use kernels.ops.attention, "
                f"which falls back to the reference path on GPU")
        kwargs = lowering_kwargs(
            dimension_semantics=(config["dim_semantics"],) * 2
            + ("arbitrary",),
            interpret=interpret)

        call = pl.pallas_call(
            functools.partial(_fa_kernel, causal, scale, gk, bq, bk,
                              dot_precision(meta[0].dtype)),
            grid=(BH, gq, gk),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda h, iq, ik, g=group: (h // g, ik, 0)),
                pl.BlockSpec((1, bk, D),
                             lambda h, iq, ik, g=group: (h // g, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, bq, D), lambda h, iq, ik: (h, iq, 0)),
            out_shape=jax.ShapeDtypeStruct((BH, S, D), meta[0].dtype),
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
                pltpu.VMEM((bq, 128), jnp.float32),
            ],
            interpret=interpret, **kwargs)
        return call

    b.reference(_ref.flash_attention_ref_factory(causal))

    @b.probe
    def _probe(problem, dtype):
        BH, BHkv, S, D = problem
        rng = np.random.default_rng(0)
        scale = 1.0 / (D ** 0.5)
        return (probe_array(rng, (BH, S, D), dtype, scale),
                probe_array(rng, (BHkv, S, D), dtype, scale),
                probe_array(rng, (BHkv, S, D), dtype, scale))

    @b.workload
    def _workload(config, problem, dtype, _causal=causal):
        BH, BHkv, S, D = problem
        bq = min(config["block_q"], S)
        bk = min(config["block_k"], S)
        if S % bq or S % bk:
            return Workload(0, 0, 0, 0, valid=False)
        byt = 2 if dtype in ("bfloat16", "float16") else 4
        gq, gk = S // bq, S // bk
        frac = 0.5 + 0.5 / gk if _causal else 1.0   # causal block skipping
        flops = 4.0 * BH * S * S * D * frac
        # q/o once; k/v streamed once per q block
        hbm = (2 * BH * S * D + 2 * BHkv * S * D * gq * frac) * byt
        vmem = ((bq * D + 2 * bk * D) * byt * 2
                + bq * D * 4 + 2 * bq * 128 * 4 + bq * D * byt)
        return Workload(
            flops=flops, hbm_bytes=float(hbm), vmem_bytes=int(vmem),
            grid=int(BH * gq * gk * frac) + 1,
            mxu_tile=(bq, bk, D), lane_extent=D, sublane_extent=bq,
            reuse=1.0, notes={"bq": bq, "bk": bk})

    register(b)
    return b


causal_builder = _make_builder(True)
full_builder = _make_builder(False)
