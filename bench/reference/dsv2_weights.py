"""Seeded weights of a DeepSeek-V2 decoder (latent attention, a dense first
layer, then layers of routed and shared experts), made on the device from
one key.

Every tensor has a key of its own: the root key folded with the layer,
then with the tensor's index; an expert's tensors fold in the expert's
global index first. So a layer, or one expert of it, can be made again
alone with exactly the values it has in the whole set, whichever
experts a chip holds. Matrices are stored in the configuration's
``torch_dtype``, RMSNorm scales and the router in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

ATTN_TENSORS = ("ln1_scale", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
                "wk_b", "wv_b", "wo", "ln2_scale")
DENSE_TENSORS = ("w_gate", "w_up", "w_down")
MOE_TENSORS = ("router", "shared_gate", "shared_up", "shared_down")
EXPERT_TENSORS = ("gate", "up", "down")
GLOBAL_TENSORS = ("embed", "head", "final_scale")
#: fold-in index of the global tensors (layers use 0 .. n_layers - 1)
GLOBAL_SLOT = 1 << 20
#: fold-in index of expert e's tensors within its layer: EXPERT_SLOT + e
EXPERT_SLOT = 1 << 16
FLOAT32 = ("router",)


def sizes(cfg: dict) -> dict:
    H = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "f": cfg["moe_intermediate_size"], "H": H,
            "q_lora": cfg["q_lora_rank"], "kv_lora": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"],
            "n_dense": cfg["first_k_dense_replace"],
            "E": cfg["published"]["n_routed_experts"],
            "first": cfg["held_experts"]["first"],
            "held": cfg["n_routed_experts"],
            "shared": cfg["n_shared_experts"] * cfg["moe_intermediate_size"]}


def _shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, H = s["d"], s["H"]
    return {"ln1_scale": (d,), "ln2_scale": (d,),
            "wq_a": (d, s["q_lora"]), "q_norm": (s["q_lora"],),
            "wq_b": (s["q_lora"], H * (s["dn"] + s["dr"])),
            "wkv_a": (d, s["kv_lora"] + s["dr"]),
            "kv_norm": (s["kv_lora"],),
            "wk_b": (s["kv_lora"], H * s["dn"]),
            "wv_b": (s["kv_lora"], H * s["dv"]), "wo": (H * s["dv"], d),
            "w_gate": (d, s["ff"]), "w_up": (d, s["ff"]),
            "w_down": (s["ff"], d), "router": (d, s["E"]),
            "shared_gate": (d, s["shared"]), "shared_up": (d, s["shared"]),
            "shared_down": (s["shared"], d),
            "expert_gate": (d, s["f"]), "expert_up": (d, s["f"]),
            "expert_down": (s["f"], d),
            "embed": (s["V"], d), "head": (d, s["V"]), "final_scale": (d,)}


def _tensor(key, name: str, shape: tuple, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale") or name.endswith("_norm"):
        return 1.0 + 0.1 * z
    if name == "embed":
        return z.astype(dtype)
    out = z * shape[0] ** -0.5
    return out if name in FLOAT32 else out.astype(dtype)


def _make(key, names: tuple, shapes: dict, dtype, prefix: str = "") -> dict:
    return {n: _tensor(jax.random.fold_in(key, i), prefix + n,
                       shapes[prefix + n], dtype)
            for i, n in enumerate(names)}


def frozen(cfg: dict) -> tuple:
    """The sizes and precision of ``cfg`` as a hashable static argument."""
    keys = ("hidden_size", "intermediate_size", "moe_intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "vocab_size", "num_hidden_layers", "first_k_dense_replace",
            "n_routed_experts", "n_shared_experts", "torch_dtype")
    return tuple((k, cfg[k]) for k in keys) + (
        ("published", (("n_routed_experts",
                        cfg["published"]["n_routed_experts"]),)),
        ("held_experts", (("first", cfg["held_experts"]["first"]),)))


def _thaw(cfg_key) -> dict:
    cfg = dict(cfg_key)
    cfg["published"] = dict(cfg["published"])
    cfg["held_experts"] = dict(cfg["held_experts"])
    return cfg


def _layer_tensors(root, l, cfg: dict, moe: bool) -> dict:
    """Layer ``l``'s tensors; with ``moe``, its router, shared experts and
    the held experts stacked as ``expert_<name>`` (held, ...)."""
    s, shapes = sizes(cfg), _shapes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    key = jax.random.fold_in(root, l)
    names = ATTN_TENSORS + (MOE_TENSORS if moe else DENSE_TENSORS)
    out = _make(key, names, shapes, dtype)
    if moe:
        def expert(e):
            return _make(jax.random.fold_in(key, EXPERT_SLOT + e),
                         EXPERT_TENSORS, shapes, dtype, prefix="expert_")
        held = jnp.arange(s["first"], s["first"] + s["held"])
        for n, v in lax.map(expert, held).items():
            out[f"expert_{n}"] = v
    return out


@functools.partial(jax.jit, static_argnames=("cfg_key", "moe"))
def _layer(root, l, cfg_key, moe):
    return _layer_tensors(root, l, _thaw(cfg_key), moe)


def layer(root, l: int, cfg: dict) -> dict:
    """The tensors of layer ``l`` (dense before ``first_k_dense_replace``,
    with experts after)."""
    return _layer(root, l, frozen(cfg), l >= cfg["first_k_dense_replace"])


def globals_(root, cfg: dict) -> dict:
    """Embedding, output head and the final RMSNorm."""
    return _make(jax.random.fold_in(root, GLOBAL_SLOT), GLOBAL_TENSORS,
                 _shapes(cfg), jnp.dtype(cfg["torch_dtype"]))


def stacked_moe_layers(root, cfg: dict) -> dict:
    """The expert layers' tensors, stacked along a leading layer axis (the
    same values ``layer`` gives one by one), made one layer at a time.
    Call under ``jax.jit``."""
    first = cfg["first_k_dense_replace"]
    return lax.map(lambda l: _layer_tensors(root, l, cfg, True),
                   jnp.arange(first, cfg["num_hidden_layers"]))
