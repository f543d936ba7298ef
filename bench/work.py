"""Required work of each kernel and step, computed from shapes alone.

These counts are the benchmark's yardstick for roofline and peak shares.
They count what the algorithm needs, not what an implementation happens
to move: a stencil reads each input field once and writes each output
once, however its tiles re-read their halos, so the bytes do not depend
on the launch configuration. FLOPs are counted from the reference
formulas in ``bench/reference/stencils.py`` (adds, subtracts and
multiplies; shared sub-expressions once).
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")

#: Field passes (reads + writes of one whole field) per kernel.
STENCIL_PASSES = {
    "advec_u": 4,     # read u, v, w; write ut
    "diff_uvw": 7,    # read u, v, w, evisc; write ut, vt, wt
    "update": 10,     # read u, v, w and four tendencies; write u, v, w
}

#: FLOPs per grid point.
STENCIL_FLOPS = {
    # six fluxes of 11 (face average 2, interpolation 8, product 1),
    # then three differences, three scalings and two sums
    "advec_u": 74,
    # per axis: two face viscosities shared by the three fields (4); per
    # field and axis two differences, two products, one difference and
    # one scaling (7), two sums per field
    "diff_uvw": 3 * 4 + 3 * (3 * 7 + 2),
    # u += dt * (ut + dut): 3; v += dt * dvt: 2; w += dt * dwt: 2
    "update": 7,
}

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2}


class UnknownDeviceError(KeyError):
    """The device kind has no row in the peaks table."""


@dataclass(frozen=True)
class Peaks:
    device_kind: str
    flops_per_s: float       # bf16 matrix peak
    hbm_bytes_per_s: float


def peaks(device_kind: str, path: Path = PEAKS_FILE) -> Peaks:
    """The published peaks of ``device_kind`` (as JAX reports it)."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise UnknownDeviceError(
            f"device kind {device_kind!r} has no row in {path.name}; "
            f"known: {sorted(table)}")
    row = table[device_kind]
    return Peaks(device_kind, float(row["bf16_flops_per_s"]),
                 float(row["hbm_bytes_per_s"]))


@dataclass(frozen=True)
class Work:
    flops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.flops + other.flops, self.bytes + other.bytes)

    def least_s(self, pk: Peaks, flops: bool = True) -> float:
        """Least time the peaks allow: the larger of the FLOP and the byte
        bound (``flops=False`` keeps the byte bound alone)."""
        t_bytes = self.bytes / pk.hbm_bytes_per_s
        return max(self.flops / pk.flops_per_s, t_bytes) if flops \
            else t_bytes


def stencil(kernel: str, grid: int, dtype: str) -> Work:
    """One launch of ``kernel`` (or the field update) on a grid³ domain."""
    points = grid ** 3
    return Work(float(STENCIL_FLOPS[kernel] * points),
                float(STENCIL_PASSES[kernel] * points * DTYPE_BYTES[dtype]))


def stencil_step(grid: int, dtype: str) -> Work:
    """One MicroHH time step: advec_u, diff_uvw and the field update."""
    out = Work(0.0, 0.0)
    for k in STENCIL_PASSES:
        out = out + stencil(k, grid, dtype)
    return out


# -- decoder LM ---------------------------------------------------------------


def _lm_sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    d_head = d // heads
    kv = cfg["num_key_value_heads"]
    return {"d": d, "ff": cfg["intermediate_size"], "L": cfg["num_hidden_layers"],
            "V": cfg["vocab_size"], "d_q": heads * d_head, "d_kv": kv * d_head,
            "kv_heads": kv, "d_head": d_head}


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that one token multiplies through: every layer's
    projections and the output head (the embedding is a lookup)."""
    s = _lm_sizes(cfg)
    per_layer = (s["d"] * s["d_q"] + 2 * s["d"] * s["d_kv"]
                 + s["d_q"] * s["d"] + 3 * s["d"] * s["ff"])
    return s["L"] * per_layer + s["d"] * s["V"]


def lm_params(cfg: dict) -> int:
    """All parameters: matmul weights, the embedding, LayerNorm scale and
    bias (two per layer and a final one), and q/k/v biases if used."""
    s = _lm_sizes(cfg)
    n = lm_matmul_params(cfg) + s["V"] * s["d"]
    n += (2 * s["L"] + 1) * 2 * s["d"]
    if cfg["use_qkv_bias"]:
        n += s["L"] * (s["d_q"] + 2 * s["d_kv"])
    return n


def lm_decode_step(cfg: dict, active: int, live: int) -> Work:
    """One decode step over ``active`` slots that attend to ``live`` cache
    positions between them (each slot its own window, this step's token
    included). FLOPs: 2 per matmul parameter per active slot plus
    QK and PV over the live window. Bytes: every matmul weight once, the
    active rows of the embedding, LayerNorm parameters (float32), q/k/v
    biases where used, each active slot's live K and V, and the new K and
    V written; not the whole arena. Weights and cache are in the
    configuration's ``torch_dtype``."""
    s = _lm_sizes(cfg)
    nb = DTYPE_BYTES[cfg["torch_dtype"]]
    flops = 2.0 * lm_matmul_params(cfg) * active
    flops += 4.0 * s["L"] * s["d_q"] * live
    kv_row = s["L"] * 2 * s["kv_heads"] * s["d_head"] * nb
    biases = s["L"] * (s["d_q"] + 2 * s["d_kv"]) if cfg["use_qkv_bias"] \
        else 0
    nbytes = float((lm_matmul_params(cfg) + active * s["d"] + biases) * nb
                   + (2 * s["L"] + 1) * 2 * s["d"] * 4
                   + (live + active) * kv_row)
    return Work(flops, nbytes)
