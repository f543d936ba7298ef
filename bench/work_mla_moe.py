"""Required work of a DeepSeek-V2 decode step and of its latent attention,
computed from the configuration's shapes alone (``bench/work.py`` holds
the peaks and the decoder of the other serving cell).

A decode step over ``active`` slots whose live windows hold ``live``
positions between them (each slot's own, this step's token included):

* FLOPs: 2 per matmul parameter per active slot through latent attention
  (the absorbed ``W^UK`` and ``W^UV`` are the same parameters), the
  dense layer or the router and shared experts, and the head; the held
  experts' 2 per parameter for the (token, k) pairs they are expected to
  see (``active * top_k * held / n_routed``, routing spread evenly); and
  the latent attention below, in every layer.
* Bytes: every matmul weight once, all held experts' included (in the
  deployment every held expert sees tokens each step), the active rows
  of the embedding, RMSNorm scales and the router (float32), and the
  latent attention's bytes below, in every layer.

One layer's latent attention: every head scores the ``kv_lora + d_rope``
wide key of each live position and sums its first ``kv_lora`` columns
(``2 * H * (kv_lora + d_rope + kv_lora)`` FLOPs a position); it reads each
live key once, the queries of the active slots and writes their
outputs (float32), and the step writes each active slot's new key. Keys
are counted at their width, not at the arena's padded row.
"""

from __future__ import annotations

from bench.work import DTYPE_BYTES, Work


def sizes(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "H": H, "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "n_dense": cfg["first_k_dense_replace"],
            "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"],
            "q_lora": cfg["q_lora_rank"], "kv_lora": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "k": cfg["num_experts_per_tok"],
            "E": cfg["published"]["n_routed_experts"],
            "held": cfg["n_routed_experts"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def attention_params(cfg: dict) -> int:
    """Matmul parameters of one latent attention layer."""
    s = sizes(cfg)
    d, H = s["d"], s["H"]
    return (d * s["q_lora"] + s["q_lora"] * H * (s["dn"] + s["dr"])
            + d * (s["kv_lora"] + s["dr"]) + s["kv_lora"] * H * s["dn"]
            + s["kv_lora"] * H * s["dv"] + H * s["dv"] * d)


def expert_params(cfg: dict) -> int:
    s = sizes(cfg)
    return 3 * s["d"] * s["f"]


def params(cfg: dict) -> dict:
    """Parameter counts by kind; ``float32`` ones are the norms and the
    router, the rest are stored in ``torch_dtype``."""
    s = sizes(cfg)
    d, L, n_moe = s["d"], s["L"], s["L"] - s["n_dense"]
    return {"attention": L * attention_params(cfg),
            "dense": s["n_dense"] * 3 * d * s["ff"],
            "shared": n_moe * 3 * d * s["shared"],
            "experts": n_moe * s["held"] * expert_params(cfg),
            "head": d * s["V"], "embed": s["V"] * d,
            "float32": n_moe * d * s["E"]
            + L * (2 * d + s["q_lora"] + s["kv_lora"]) + d}


def latent_attention(cfg: dict, active: int, live: int) -> Work:
    """One layer's latent attention over ``live`` positions of ``active``
    slots."""
    s = sizes(cfg)
    nb = DTYPE_BYTES[cfg["torch_dtype"]]
    key = s["kv_lora"] + s["dr"]
    flops = 2.0 * s["H"] * (key + s["kv_lora"]) * live
    nbytes = (live + active) * key * nb \
        + active * s["H"] * (key * nb + s["kv_lora"] * 4)
    return Work(flops, float(nbytes))


def decode_step(cfg: dict, active: int, live: int) -> Work:
    """One decode step (see the module docstring)."""
    s = sizes(cfg)
    p = params(cfg)
    nb = DTYPE_BYTES[cfg["torch_dtype"]]
    n_moe = s["L"] - s["n_dense"]
    per_token = (p["attention"] + p["dense"] + p["shared"] + p["head"]
                 + n_moe * s["d"] * s["E"])
    routed = active * s["k"] * s["held"] / s["E"]
    flops = 2.0 * per_token * active \
        + 2.0 * n_moe * routed * expert_params(cfg)
    stored = p["attention"] + p["dense"] + p["shared"] + p["experts"] \
        + p["head"] + active * s["d"]
    nbytes = float(stored * nb + p["float32"] * 4)
    return Work(flops, nbytes) + _times(latent_attention(cfg, active, live),
                                       s["L"])


def _times(w: Work, n: int) -> Work:
    return Work(w.flops * n, w.bytes * n)
