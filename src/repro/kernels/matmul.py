"""Blocked matmul with a tunable (block_m, block_n, block_k) tiling and grid
order — the canonical MXU kernel, used by the quickstart example and as the
simplest end-to-end demonstration of the Kernel Launcher flow.

Accumulation in an f32 VMEM scratch across the (innermost, "arbitrary") k
axis; the grid-order parameter is the TPU analogue of the paper's unravel
permutation (it changes which operand streams and which stays resident).
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


builder = KernelBuilder("matmul", source="repro.kernels.matmul")
builder.tune("block_m", (64, 128, 256, 512), default=128)
builder.tune("block_n", (64, 128, 256, 512), default=128)
builder.tune("block_k", (128, 256, 512, 1024), default=256)
builder.tune("grid_order", ("mnk", "nmk"), default="mnk")
builder.tune("dim_semantics", ("parallel", "arbitrary"), default="parallel")


@builder.problem_size
def _problem(a, b):
    (m, k), (_, n) = a.shape, b.shape
    return (m, n, k)


def _mm_kernel(nk: int, precision, a_ref, b_ref, o_ref, acc_ref):
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...], precision=precision,
                            preferred_element_type=jnp.float32)

    @pl.when(kb == nk - 1)
    def _store():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _mm_gpu_kernel(a_ref, b_ref, o_ref):
    o_ref[...] = jnp.dot(a_ref[...], b_ref[...],
                         preferred_element_type=jnp.float32
                         ).astype(o_ref.dtype)


def _build_gpu(config, problem, meta, interpret: bool):
    """Triton-shaped lowering: a 2-D (m, n) grid with the full K stripe
    per program — accumulation stays in registers, so no TPU VMEM
    scratch is involved. ``block_k`` survives as the software-pipelining
    depth (``num_stages``) instead of a grid axis."""
    m, n, k = problem
    bm, bn, bk = config["block_m"], config["block_n"], config["block_k"]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn:
        raise ValueError(f"blocks ({bm},{bn}) do not tile {problem}")
    if config["grid_order"] == "mnk":
        grid = (m // bm, n // bn)
        ij = lambda p0, p1: (p0, p1)  # noqa: E731
    else:
        grid = (n // bn, m // bm)
        ij = lambda p0, p1: (p1, p0)  # noqa: E731

    kwargs = lowering_kwargs(
        num_warps=8 if bm * bn >= 256 * 128 else 4,
        num_stages=2 if bk >= 512 else 3,
        interpret=interpret, backend="gpu")
    return pl.pallas_call(
        _mm_gpu_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, k), lambda p0, p1: (ij(p0, p1)[0], 0)),
                  pl.BlockSpec((k, bn), lambda p0, p1: (0, ij(p0, p1)[1]))],
        out_specs=pl.BlockSpec((bm, bn), lambda p0, p1: ij(p0, p1)),
        out_shape=jax.ShapeDtypeStruct((m, n), meta[0].dtype),
        interpret=interpret, **kwargs)


@builder.build
def _build(config, problem, meta, interpret: bool = False):
    backend = active_backend()
    if backend == "gpu":
        return _build_gpu(config, problem, meta, interpret)
    m, n, k = problem
    bm, bn, bk = config["block_m"], config["block_n"], config["block_k"]
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    if m % bm or n % bn or k % bk:
        raise ValueError(f"blocks ({bm},{bn},{bk}) do not tile {problem}")
    gm, gn, gk = m // bm, n // bn, k // bk
    if config["grid_order"] == "mnk":
        grid = (gm, gn, gk)
        ij = lambda p0, p1: (p0, p1)  # noqa: E731
    else:
        grid = (gn, gm, gk)
        ij = lambda p0, p1: (p1, p0)  # noqa: E731

    def a_map(p0, p1, p2):
        i, _ = ij(p0, p1)
        return (i, p2)

    def b_map(p0, p1, p2):
        _, j = ij(p0, p1)
        return (p2, j)

    def o_map(p0, p1, p2):
        i, j = ij(p0, p1)
        return (i, j)

    kwargs = lowering_kwargs(
        dimension_semantics=(config["dim_semantics"],
                             config["dim_semantics"], "arbitrary"),
        interpret=interpret, backend=backend)

    dtype = meta[0].dtype
    scratch = [pltpu.VMEM((bm, bn), jnp.float32)]

    call = pl.pallas_call(
        functools.partial(_mm_kernel, gk, dot_precision(dtype)),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bk), a_map),
                  pl.BlockSpec((bk, bn), b_map)],
        out_specs=pl.BlockSpec((bm, bn), o_map),
        out_shape=jax.ShapeDtypeStruct((m, n), dtype),
        scratch_shapes=scratch,
        interpret=interpret, **kwargs)

    return call


builder.reference(_ref.matmul_ref)


@builder.probe
def _probe(problem, dtype):
    m, n, k = problem
    rng = np.random.default_rng(0)
    return (probe_array(rng, (m, k), dtype),
            probe_array(rng, (k, n), dtype))


@builder.workload
def _workload(config, problem, dtype):
    m, n, k = problem
    bm = min(config["block_m"], m)
    bn = min(config["block_n"], n)
    bk = min(config["block_k"], k)
    if m % bm or n % bn or k % bk:
        return Workload(0, 0, 0, 0, valid=False)
    b = 2 if dtype in ("bfloat16", "float16") else 4
    grid = (m // bm) * (n // bn) * (k // bk)
    # A re-read per n-block, B re-read per m-block, C written once.
    hbm = m * k * b * (n // bn) + k * n * b * (m // bm) + m * n * b
    vmem = (bm * bk + bk * bn) * b * 2 + bm * bn * 4 + bm * bn * b
    return Workload(
        flops=2.0 * m * n * k, hbm_bytes=float(hbm), vmem_bytes=int(vmem),
        grid=grid, mxu_tile=(bm, bn, bk), lane_extent=bn,
        sublane_extent=bm, unroll_ways=1,
        reuse=1.0 if config["grid_order"] == "mnk" else 1.02,
        notes={"bm": bm, "bn": bn, "bk": bk})


register(builder)
