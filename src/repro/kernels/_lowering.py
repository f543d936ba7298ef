"""Backend-aware Pallas lowering: one place that decides compiler params.

Every kernel's ``_build`` needs the same decision: which compiler-param
object (if any) may ride along with ``pl.pallas_call``. The old guard —
"``pltpu`` imported, so pass TPU params" — was wrong on any machine
where the TPU package *imports* but the active device is a GPU or a CPU
host: Mosaic-only kwargs (``dimension_semantics``) would reach a Triton
or interpreter lowering and fail. The decision belongs to the active
:class:`~repro.core.device.DeviceSpec`'s ``backend``, not to what
happens to be importable.

:func:`lowering_kwargs` is that decision:

* backend ``"tpu"``   -> Mosaic ``pltpu.CompilerParams(dimension_semantics)``,
  with ``vmem_limit_bytes`` where the caller passes one (the stencil
  kernels, whose large tiles outgrow Mosaic's 16 MiB scoped default)
* backend ``"gpu"``   -> ``pltriton.CompilerParams(num_warps, num_stages)``
* backend ``"cpu"``   -> no params (the interpreter takes none)
* ``interpret=True``  -> no params, on any backend (the CI story: GPU
  and TPU lowerings both run under the Pallas interpreter on hosts
  without the hardware, and the interpreter rejects backend params)

Kernels still own their *structural* backend choices (scratch memory,
grid shape); this module only centralizes the compiler-param gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas import triton as pltriton

from repro.core.device import current_device

__all__ = ["active_backend", "dot_precision", "lowering_kwargs"]


def dot_precision(dtype) -> jax.lax.Precision | None:
    """MXU precision for a kernel's dots on ``dtype`` inputs.

    Mosaic runs a float32 dot at default precision as one bfloat16 pass
    (about 3e-3 relative error on a v5e against a float32 reference), so
    float32 kernels ask for ``HIGHEST``; other dtypes keep the default.
    """
    return (jax.lax.Precision.HIGHEST
            if jnp.dtype(dtype) == jnp.float32 else None)


def active_backend() -> str:
    """The active device's lowering backend ("tpu" | "gpu" | "cpu")."""
    return current_device().backend


def _gpu_params(num_warps, num_stages):
    kw = {}
    if num_warps is not None:
        kw["num_warps"] = int(num_warps)
    if num_stages is not None:
        kw["num_stages"] = int(num_stages)
    return {"compiler_params": pltriton.CompilerParams(**kw)}


def lowering_kwargs(*, dimension_semantics=(), num_warps=None,
                    num_stages=None, vmem_limit_bytes: int | None = None,
                    interpret: bool = False,
                    backend: str | None = None) -> dict:
    """The ``pl.pallas_call`` kwargs the active backend accepts.

    ``dimension_semantics`` and ``vmem_limit_bytes`` feed the Mosaic
    (TPU) params; ``num_warps`` and ``num_stages`` feed the Triton (GPU)
    params — callers pass both sets and exactly one (or neither) is
    used. Returns ``{}`` under ``interpret`` and on the CPU.

    Example::

        kwargs = lowering_kwargs(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            num_warps=4, num_stages=2, interpret=interpret)
        pl.pallas_call(body, grid=grid, ..., **kwargs)
    """
    if interpret:
        return {}
    b = backend if backend is not None else active_backend()
    if b == "tpu" and dimension_semantics:
        kw = {"dimension_semantics": tuple(dimension_semantics)}
        if vmem_limit_bytes is not None:
            kw["vmem_limit_bytes"] = int(vmem_limit_bytes)
        return {"compiler_params": pltpu.CompilerParams(**kw)}
    if b == "gpu":
        return _gpu_params(num_warps, num_stages)
    return {}
