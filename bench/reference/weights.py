"""Seeded weights of a decoder LM with LayerNorm and a gated MLP, made on
the device layer by layer from one key.

Every tensor has a key of its own (the root key folded with the layer
and the tensor's index), so layer ``l`` can be made again on its own
with exactly the values it had inside the whole set. Matrices and the
q/k/v biases are stored in the configuration's ``torch_dtype`` (the
served precision), LayerNorm parameters in float32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

LAYER_TENSORS = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                 "ln2_scale", "ln2_bias", "w_gate", "w_up", "w_down")
QKV_BIASES = ("bq", "bk", "bv")
GLOBAL_TENSORS = ("embed", "head", "final_scale", "final_bias")
#: fold-in index of the global tensors (layers use 0 .. n_layers - 1)
GLOBAL_SLOT = 1 << 20


def sizes(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    heads = cfg["num_attention_heads"]
    d_head = d // heads
    return {"d": d, "ff": cfg["intermediate_size"],
            "d_q": heads * d_head,
            "d_kv": cfg["num_key_value_heads"] * d_head,
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def layer_tensors(cfg: dict) -> tuple:
    """Names of one layer's tensors, q/k/v biases last where used."""
    return LAYER_TENSORS + (QKV_BIASES if cfg["use_qkv_bias"] else ())


def _shapes(cfg: dict) -> dict:
    s = sizes(cfg)
    d, ff, dq, dkv = s["d"], s["ff"], s["d_q"], s["d_kv"]
    return {"ln1_scale": (d,), "ln1_bias": (d,), "wq": (d, dq),
            "wk": (d, dkv), "wv": (d, dkv), "wo": (dq, d),
            "bq": (dq,), "bk": (dkv,), "bv": (dkv,),
            "ln2_scale": (d,), "ln2_bias": (d,), "w_gate": (d, ff),
            "w_up": (d, ff), "w_down": (ff, d),
            "embed": (s["V"], d), "head": (d, s["V"]),
            "final_scale": (d,), "final_bias": (d,)}


def _tensor(key, name: str, shape: tuple, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("_scale"):
        return 1.0 + 0.1 * z
    if name.endswith("_bias"):
        return 0.1 * z
    if name in QKV_BIASES:
        return (0.1 * z).astype(dtype)
    if name == "embed":
        return z.astype(dtype)
    return (z * shape[0] ** -0.5).astype(dtype)


def _make(key, names: tuple, shapes: dict, dtype) -> dict:
    return {n: _tensor(jax.random.fold_in(key, i), n, shapes[n], dtype)
            for i, n in enumerate(names)}


def frozen(cfg: dict) -> tuple:
    """The sizes and precision of ``cfg`` as a hashable static argument."""
    keys = ("hidden_size", "intermediate_size", "num_attention_heads",
            "num_key_value_heads", "vocab_size", "num_hidden_layers",
            "use_qkv_bias", "torch_dtype")
    return tuple((k, cfg[k]) for k in keys)


@functools.partial(jax.jit, static_argnames=("cfg_key",))
def _layer(root, l, cfg_key):
    cfg = dict(cfg_key)
    return _make(jax.random.fold_in(root, l), layer_tensors(cfg),
                 _shapes(cfg), jnp.dtype(cfg["torch_dtype"]))


def layer(root, l: int, cfg: dict) -> dict:
    """The tensors of layer ``l``."""
    return _layer(root, l, frozen(cfg))


def globals_(root, cfg: dict) -> dict:
    """Embedding, output head and the final LayerNorm."""
    return _make(jax.random.fold_in(root, GLOBAL_SLOT), GLOBAL_TENSORS,
                 _shapes(cfg), jnp.dtype(cfg["torch_dtype"]))


def stacked_layers(root, cfg: dict) -> dict:
    """Every layer's tensors, stacked along a leading layer axis (the same
    values ``layer`` gives one by one). Call under ``jax.jit``."""
    n = cfg["num_hidden_layers"]
    names, shapes = layer_tensors(cfg), _shapes(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    return jax.vmap(lambda l: _make(jax.random.fold_in(root, l), names,
                                    shapes, dtype))(jnp.arange(n))
