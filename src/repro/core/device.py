"""Device descriptors.

The paper keys wisdom records by (GPU, architecture) — e.g. ("A100",
"Ampere"). Our analogue is (device *kind*, device *family*). On real TPUs the
kind comes from ``jax.devices()[0].device_kind``; on this CPU-only container
the simulated device pair stands in for the paper's A4000/A100 pair, and the
active kind can be forced with ``KERNEL_LAUNCHER_DEVICE``.

Two device *backends* are modeled (plus the CPU host): the TPU family the
repo grew up on, and a GPU family mirroring the paper's actual hardware
pair (an A100-class and an A4000-class part). ``DeviceSpec.backend``
drives kernel lowering (``repro.kernels._lowering``) — TPU-only Mosaic
compiler params must never reach a Triton lowering and vice versa — and
enters the transfer layer's similarity model (cross-backend predictions
are possible but confidence-penalized).

Unknown hardware is handled *honestly*. :func:`get_device` on a kind it
does not know (a transfer target, a recorded dataset's device) returns a
spec cloned from its backend's baseline and flagged ``estimated=True``;
the transfer model floors similarity for estimated specs and roofline
reports annotate fractions computed against guessed peaks. The *live*
device is different: :func:`current_device` raises
:class:`UnknownDeviceError` when the attached hardware is not in the
table, because every measurement on it would be read against guessed
peaks.

The numeric fields feed the analytical cost model (tuner/costmodel.py).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, replace

import jax

DEVICE_ENV = "KERNEL_LAUNCHER_DEVICE"

#: Device backends a spec can declare; selects the kernel lowering path.
BACKENDS = ("tpu", "gpu", "cpu")


@dataclass(frozen=True)
class DeviceSpec:
    kind: str            # e.g. "tpu-v5e"
    family: str          # e.g. "tpu-v5"
    flops_bf16: float    # peak FLOP/s, bf16 on the MXU / tensor cores
    flops_f32: float     # peak FLOP/s, f32
    hbm_bw: float        # HBM bytes/s
    vmem_bytes: int      # per-core VMEM (TPU) / L2+shared (GPU) capacity
    ici_bw: float        # per-link interconnect bytes/s
    program_overhead: float  # seconds of fixed overhead per grid program
    num_cores: int = 1
    #: Which kernel lowering this device wants ("tpu" | "gpu" | "cpu").
    backend: str = "tpu"
    #: True when the peak numbers are guesses (unknown hardware cloned
    #: from a per-backend baseline), not a measured/spec'd part. Roofline
    #: fractions against an estimated spec are annotated, and the
    #: transfer model floors similarity so estimated pairs never clear
    #: the serving gate.
    estimated: bool = False
    #: Systolic-array / tensor-core tile granule the matmul unit pads
    #: each tile dimension to (128 on the TPU MXU, 16 on Ampere tensor
    #: cores) — feeds the cost model's alignment efficiency.
    matmul_granule: int = 128
    #: Matmul-unit peak over vector-unit peak (TPU VPU sits ~8x below
    #: the MXU; GPU CUDA-core f32 is a much smaller step down).
    vector_ratio: float = 8.0


# Simulated TPU pair (same-vendor, different balance point). v5e numbers
# match the roofline constants in EXPERIMENTS.md; v4 is the
# higher-bandwidth sibling.
TPU_V5E = DeviceSpec(
    kind="tpu-v5e", family="tpu-v5",
    flops_bf16=197e12, flops_f32=98.5e12,
    hbm_bw=819e9, vmem_bytes=16 * 2**20, ici_bw=50e9,
    program_overhead=1.2e-6,
)
TPU_V4 = DeviceSpec(
    kind="tpu-v4", family="tpu-v4",
    flops_bf16=275e12, flops_f32=137.5e12,
    hbm_bw=1228e9, vmem_bytes=32 * 2**20, ici_bw=100e9,
    program_overhead=1.0e-6,
)
# The training-class v5 part and the v6e (Trillium) generation: their
# raw kind strings ("TPU v5", "TPU v5p", "TPU v6 lite") used to fall
# through the generic slugifier into prefix-derived families that
# inherited the wrong peaks.
TPU_V5P = DeviceSpec(
    kind="tpu-v5p", family="tpu-v5p",
    flops_bf16=459e12, flops_f32=229.5e12,
    hbm_bw=2765e9, vmem_bytes=64 * 2**20, ici_bw=200e9,
    program_overhead=1.0e-6,
)
TPU_V6E = DeviceSpec(
    kind="tpu-v6e", family="tpu-v6",
    flops_bf16=918e12, flops_f32=459e12,
    hbm_bw=1640e9, vmem_bytes=64 * 2**20, ici_bw=100e9,
    program_overhead=1.1e-6,
)

# GPU pair mirroring the paper's actual hardware (A100 data-center part,
# A4000 workstation part — same architecture, ~4x apart in throughput).
# vmem_bytes models the L2 cache (the on-chip capacity a Triton tile's
# working set must respect); granule 16 is the Ampere tensor-core tile.
GPU_A100 = DeviceSpec(
    kind="gpu-a100", family="gpu-ampere",
    flops_bf16=312e12, flops_f32=156e12,
    hbm_bw=1555e9, vmem_bytes=40 * 2**20, ici_bw=600e9,
    program_overhead=2.2e-6,
    backend="gpu", matmul_granule=16, vector_ratio=8.0,
)
GPU_A4000 = DeviceSpec(
    kind="gpu-a4000", family="gpu-ampere",
    flops_bf16=76.7e12, flops_f32=38.3e12,
    hbm_bw=448e9, vmem_bytes=4 * 2**20, ici_bw=32e9,
    program_overhead=3.0e-6,
    backend="gpu", matmul_granule=16, vector_ratio=2.0,
)

CPU_HOST = DeviceSpec(
    kind="cpu", family="cpu",
    flops_bf16=5e11, flops_f32=5e11,
    hbm_bw=4e10, vmem_bytes=1 * 2**20, ici_bw=1e9,
    program_overhead=1e-7,
    backend="cpu",
)

DEVICES: dict[str, DeviceSpec] = {
    d.kind: d for d in (TPU_V5E, TPU_V4, TPU_V5P, TPU_V6E,
                        GPU_A100, GPU_A4000, CPU_HOST)
}

#: Per-backend baseline an unknown kind's peaks are cloned from — the
#: closest thing to a guess we can make, and the spec is flagged
#: ``estimated`` so every consumer knows it is one.
_BACKEND_BASELINE: dict[str, DeviceSpec] = {
    "tpu": TPU_V5E, "gpu": GPU_A100, "cpu": CPU_HOST,
}


def infer_backend(kind: str) -> str:
    """Best-effort backend for a device kind string (prefix only)."""
    if kind.startswith("gpu"):
        return "gpu"
    if kind.startswith("cpu"):
        return "cpu"
    return "tpu"


def get_device(kind: str) -> DeviceSpec:
    if kind in DEVICES:
        return DEVICES[kind]
    # Unknown real hardware: clone the backend's baseline peaks but mark
    # the spec estimated — consumers (cost model fractions, transfer
    # similarity) must not treat guessed numbers as ground truth.
    family = "-".join(kind.split("-")[:2]) if "-" in kind else kind
    backend = infer_backend(kind)
    return replace(_BACKEND_BASELINE[backend],
                   kind=kind, family=family, estimated=True)


#: Capability-vector axes, in order (see :func:`capability_vector`).
CAPABILITY_AXES = ("flops_bf16", "flops_f32", "hbm_bw", "vmem_bytes",
                   "program_overhead")


def capability_vector(spec: DeviceSpec) -> tuple[float, ...]:
    """The numeric capabilities that govern cross-device transfer, as a
    plain tuple in ``CAPABILITY_AXES`` order.

    These are the axes along which a tuned configuration's performance
    moves when the hardware changes: compute throughput (both precisions),
    memory bandwidth, on-chip memory capacity (feasibility!), and
    per-program launch overhead. ``repro.transfer.DeviceModel`` works on
    ratios of these vectors, so the absolute units never matter. The
    ``backend`` and ``estimated`` flags are *not* axes — they enter the
    model as a similarity penalty and floor instead (a ratio cannot
    express "different instruction set entirely").
    """
    return (spec.flops_bf16, spec.flops_f32, spec.hbm_bw,
            float(spec.vmem_bytes), spec.program_overhead)


#: Raw ``device_kind`` substring -> canonical kind, checked in order
#: (first match wins, so the "lite"/"e" variants are tested before the
#: bare generation markers — "tpu v5 lite" contains "v5" too).
_TPU_KIND_TABLE: tuple[tuple[str, str], ...] = (
    ("v5e", "tpu-v5e"),
    ("v5 lite", "tpu-v5e"),
    ("v5lite", "tpu-v5e"),
    ("v5p", "tpu-v5p"),
    ("v5", "tpu-v5p"),          # v5p hosts report a bare "TPU v5"
    ("v6e", "tpu-v6e"),
    ("v6 lite", "tpu-v6e"),
    ("v6lite", "tpu-v6e"),
    ("v4", "tpu-v4"),
)

_GPU_KIND_TABLE: tuple[tuple[str, str], ...] = (
    ("a100", "gpu-a100"),
    ("a4000", "gpu-a4000"),
)


def parse_device_kind(raw: str, platform: str = "") -> str:
    """Canonical device kind for a raw JAX ``device_kind`` string.

    ``raw`` is what ``jax.devices()[0].device_kind`` reports (e.g.
    "TPU v5 lite", "TPU v5p", "NVIDIA A100-SXM4-40GB"); ``platform`` is
    the JAX platform name ("tpu" / "gpu" / "cpu") and disambiguates GPU
    strings that never mention their vendor. Unrecognized hardware slugs
    to a prefixed kind ("tpu-…" / "gpu-…") so :func:`get_device` can at
    least pick the right backend baseline for its estimated spec.
    """
    kind = raw.lower()
    if "tpu" in kind or platform == "tpu":
        for marker, canonical in _TPU_KIND_TABLE:
            if marker in kind:
                return canonical
        slug = kind.replace(" ", "-")
        return slug if slug.startswith("tpu") else f"tpu-{slug}"
    if platform == "gpu" or any(v in kind for v in ("nvidia", "amd",
                                                    "rocm", "cuda")):
        for marker, canonical in _GPU_KIND_TABLE:
            if marker in kind:
                return canonical
        slug = kind.replace(" ", "-")
        return slug if slug.startswith("gpu") else f"gpu-{slug}"
    return "cpu"


def current_device_kind() -> str:
    """Active device kind: env override, else the real JAX device."""
    env = os.environ.get(DEVICE_ENV)
    if env:
        return env
    dev = jax.devices()[0]
    return parse_device_kind(dev.device_kind,
                             getattr(dev, "platform", ""))


class UnknownDeviceError(RuntimeError):
    """The attached device's kind is not in :data:`DEVICES`."""


def current_device() -> DeviceSpec:
    """Spec of the active device.

    An explicit ``KERNEL_LAUNCHER_DEVICE`` resolves like
    :func:`get_device`. A live device whose kind is not in the table
    raises :class:`UnknownDeviceError` instead of running on guessed
    peaks.
    """
    kind = current_device_kind()
    spec = get_device(kind)
    if spec.estimated and not os.environ.get(DEVICE_ENV):
        dev = jax.devices()[0]
        raise UnknownDeviceError(
            f"live device {dev.device_kind!r} (platform {dev.platform!r}) "
            f"parses to unknown kind {kind!r}; add it to DEVICES with its "
            f"published peaks, or set {DEVICE_ENV}")
    return spec
