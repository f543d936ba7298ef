"""GQA attention block: train/prefill forward + cached decode step.

Supports per-layer sliding windows *as data* (window scalar array; 0 = full
attention) so heterogeneous stacks (gemma2 alternating, hymba's 3 global
layers) run under one scanned layer body. Softcap per config. The underlying
attention math routes through ``repro.kernels.ops.attention`` (Pallas flash
kernel on TPU, oracle elsewhere), the decode step's through
``repro.kernels.ops.decode_attention`` (a Pallas kernel that reads the KV
arena in place on TPU, oracle elsewhere).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops, ref as kref

from .common import (KeyGen, apply_rope, constrain_batch,
                     dense_init, dt, zeros)
from .config import ArchConfig


def init_attn(keys: KeyGen, cfg: ArchConfig,
              stack: tuple[int, ...] = ()) -> dict:
    dtype = dt(cfg)
    d = cfg.d_model
    p = {
        "wq": dense_init(keys(), (*stack, d, cfg.d_q), dtype),
        "wk": dense_init(keys(), (*stack, d, cfg.d_kv), dtype),
        "wv": dense_init(keys(), (*stack, d, cfg.d_kv), dtype),
        "wo": dense_init(keys(), (*stack, cfg.d_q, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = zeros((*stack, cfg.d_q), dtype)
        p["bk"] = zeros((*stack, cfg.d_kv), dtype)
        p["bv"] = zeros((*stack, cfg.d_kv), dtype)
    return p


def _qkv(cfg: ArchConfig, p: dict, x: jax.Array,
         positions: jax.Array, rope: bool = True):
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,de->bse", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,de->bse", x, p["wv"].astype(x.dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = constrain_batch(q.reshape(B, S, cfg.n_heads, cfg.d_head),
                        head_dim=2)
    k = constrain_batch(k.reshape(B, S, cfg.n_kv_heads, cfg.d_head),
                        head_dim=2)
    v = constrain_batch(v.reshape(B, S, cfg.n_kv_heads, cfg.d_head),
                        head_dim=2)
    if rope and cfg.pos == "rope":
        q = apply_rope(q, positions, cfg.rope_frac, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_frac, cfg.rope_theta)
    # -> (B, H, S, D)
    return (q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3))


def _attn_core(cfg: ArchConfig, p: dict, x: jax.Array, window,
               causal: bool):
    B, S, _ = x.shape
    positions = jnp.arange(S)
    q, k, v = _qkv(cfg, p, x, positions)
    static_window = isinstance(window, int) or window is None
    if static_window:
        win = None if not window else int(window)
        o = ops.attention(q, k, v, causal=causal, window=win,
                          softcap=cfg.attn_softcap)
    else:
        o = _masked_attention(q, k, v, window, causal, cfg.attn_softcap)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.d_q)
    out = constrain_batch(
        jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype)))
    return out, k, v


def attn_forward(cfg: ArchConfig, p: dict, x: jax.Array,
                 window: jax.Array | int | None = None,
                 causal: bool = True) -> jax.Array:
    """Full-sequence attention (training). ``window`` may be a traced
    scalar (0 = full); traced windows always use the masked oracle."""
    return _attn_core(cfg, p, x, window, causal)[0]


def attn_prefill(cfg: ArchConfig, p: dict, x: jax.Array, cache_k, cache_v,
                 window: jax.Array | int | None = None
                 ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Parallel prefill: forward + write K/V for positions [0, S) into
    one layer's cache (B, Hkv, D, max_seq). Returns (out, new_cache_k,
    new_cache_v)."""
    out, k, v = _attn_core(cfg, p, x, window, causal=True)
    cache_k = lax.dynamic_update_slice_in_dim(
        cache_k, k.swapaxes(2, 3).astype(cache_k.dtype), 0, axis=3)
    cache_v = lax.dynamic_update_slice_in_dim(
        cache_v, v.swapaxes(2, 3).astype(cache_v.dtype), 0, axis=3)
    return out, cache_k, cache_v


def _masked_attention(q, k, v, window, causal: bool,
                      softcap: float | None) -> jax.Array:
    """Oracle attention with a *traced* window scalar (0 = full attn);
    dispatches through the blockwise path for long sequences."""
    return kref.attention_ref(q, k, v, causal=causal, window=window,
                              softcap=softcap)


# --------------------------------------------------------------- decode ----

def init_kv_cache(cfg: ArchConfig, n_layers: int, batch: int, max_seq: int,
                  dtype) -> dict:
    """The KV arena: K and V as (n_layers, B, Hkv, d_head, max_seq), the
    sequence axis minor. That is the order the TPU stores it in (a
    d_head of 64 minor would pad to 128 lanes), so decode attention
    reads it in place (docs/serving.md)."""
    shape = (n_layers, batch, cfg.n_kv_heads, cfg.d_head, max_seq)
    return {"k": zeros(shape, dtype), "v": zeros(shape, dtype)}


def attn_decode(cfg: ArchConfig, p: dict, x: jax.Array, cache_k: jax.Array,
                cache_v: jax.Array, layer, pos: jax.Array,
                window: jax.Array | int | None = None,
                start: jax.Array | None = None
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode of layer ``layer``. x: (B, 1, D); cache_k/v: the
    whole stacked arena (L, B, Hkv, D, S), read where it lies; pos:
    scalar — the position of the new token. ``start``, when given, is a
    (B,) vector of per-slot window origins for token-level continuous
    batching: slot b attends only to cache positions in [start[b], pos],
    hiding the previous occupant's stale K/V (always below start[b],
    since the arena cursor only advances). Returns (out, k, v): the new
    token's K/V (B, Hkv, D) in the arena's dtype, for the caller to
    write at ``pos`` (:func:`write_kv`) once every layer has run."""
    B = x.shape[0]
    positions = jnp.full((1,), pos)
    q, k, v = _qkv(cfg, p, x, positions)
    k = k[:, :, 0].astype(cache_k.dtype)
    v = v[:, :, 0].astype(cache_v.dtype)
    win = window if window is not None else 0
    o = ops.decode_attention(q, cache_k, cache_v, k, v, layer, pos,
                             window=win, softcap=cfg.attn_softcap,
                             start=start)
    o = o.transpose(0, 2, 1, 3).reshape(B, 1, cfg.d_q)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
    return out, k, v


def write_kv(kv: dict, k: jax.Array, v: jax.Array, pos) -> dict:
    """Write one position of every layer into the arena: k/v
    (L, B, Hkv, D) at ``pos``. Under a jit that donates the arena this is
    the step's one in-place write."""
    k_arena, v_arena = ops.write_kv_position(kv["k"], kv["v"], k, v, pos)
    return {"k": k_arena, "v": v_arena}


# ----------------------------------------------------------- cross-attn ----

def init_cross_attn(keys: KeyGen, cfg: ArchConfig,
                    stack: tuple[int, ...] = ()) -> dict:
    dtype = dt(cfg)
    d = cfg.d_model
    return {
        "wq": dense_init(keys(), (*stack, d, cfg.d_q), dtype),
        "wk": dense_init(keys(), (*stack, d, cfg.d_kv), dtype),
        "wv": dense_init(keys(), (*stack, d, cfg.d_kv), dtype),
        "wo": dense_init(keys(), (*stack, cfg.d_q, d), dtype),
        "gate": zeros((*stack,), jnp.float32),   # mllama tanh gate
    }


def cross_kv(cfg: ArchConfig, p: dict, memory: jax.Array):
    """Precompute cross-attention K/V from encoder/image memory (B, M, D)."""
    B, M, _ = memory.shape
    k = jnp.einsum("bmd,de->bme", memory, p["wk"].astype(memory.dtype))
    v = jnp.einsum("bmd,de->bme", memory, p["wv"].astype(memory.dtype))
    k = k.reshape(B, M, cfg.n_kv_heads, cfg.d_head).transpose(0, 2, 1, 3)
    v = v.reshape(B, M, cfg.n_kv_heads, cfg.d_head).transpose(0, 2, 1, 3)
    return k, v


def cross_attn_forward(cfg: ArchConfig, p: dict, x: jax.Array,
                       k: jax.Array, v: jax.Array,
                       gated: bool = True) -> jax.Array:
    """x: (B, S, D) queries; k/v: (B, Hkv, M, D) precomputed memory."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,de->bse", x, p["wq"].astype(x.dtype))
    q = q.reshape(B, S, cfg.n_heads, cfg.d_head).transpose(0, 2, 1, 3)
    o = kref.attention_ref(q, k, v, causal=False)
    o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.d_q)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
    if gated:
        out = out * jnp.tanh(p["gate"]).astype(out.dtype)
    return out
