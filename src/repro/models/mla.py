"""Multi-head Latent Attention (DeepSeek-V2) — train forward + *absorbed*
decode.

Train/prefill expands the latent kv to per-head K/V (compute-friendly, remat
under scan). Decode uses the absorbed formulation: queries are projected into
the kv-latent space, so attention runs against the cached (S, kv_lora) latent
plus the shared (S, d_rope) rope key — the cache never expands to per-head
K/V. That is the memory trick that makes the 32k/128-batch decode cell fit.

The latent arena is one ``(L, B, S, latent_width)`` array for every
layer (dense prefix included): each position's normalised latent and its
roped key, side by side (zeros pad the row to whole lane tiles), the
576-wide key every head scores against, whose first ``kv_lora`` columns
are the values. Decode reads it where it lies
(``kernels.ops.mla_decode_attention``); slot b attends to ``[start[b],
pos]`` as in GQA token-level serving (rope is relative and YaRN's
frequencies fixed, so the mask is exact). Each layer returns its new
latent row and :func:`write_latents` writes all of them at ``pos`` once
the step's layers have run. YaRN (``cfg.rope_scaling``) sets the rope
frequencies and multiplies the softmax scale by ``mscale ** 2``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.kernels import ops

from .common import (KeyGen, apply_rope, constrain_batch,
                     dense_init, dt, yarn_mscale)
from .config import ArchConfig


def init_mla(keys: KeyGen, cfg: ArchConfig,
             stack: tuple[int, ...] = ()) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dtype = dt(cfg)
    return {
        "wq_a": dense_init(keys(), (*stack, d, m.q_lora), dtype),
        "q_norm": {"scale": jnp.ones((*stack, m.q_lora), jnp.float32)},
        "wq_b": dense_init(keys(), (*stack, m.q_lora,
                                    h * (m.d_nope + m.d_rope)), dtype),
        "wkv_a": dense_init(keys(), (*stack, d, m.kv_lora + m.d_rope), dtype),
        "kv_norm": {"scale": jnp.ones((*stack, m.kv_lora), jnp.float32)},
        "wk_b": dense_init(keys(), (*stack, m.kv_lora, h * m.d_nope), dtype),
        "wv_b": dense_init(keys(), (*stack, m.kv_lora, h * m.d_v), dtype),
        "wo": dense_init(keys(), (*stack, h * m.d_v, d), dtype),
    }


def _rms(x, scale):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * lax.rsqrt(var + 1e-6) * scale).astype(x.dtype)


def _queries(cfg: ArchConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.n_heads
    cq = _rms(jnp.einsum("bsd,dq->bsq", x, p["wq_a"].astype(x.dtype)),
              p["q_norm"]["scale"])
    q = jnp.einsum("bsq,qe->bse", cq, p["wq_b"].astype(x.dtype))
    q = constrain_batch(q.reshape(B, S, h, m.d_nope + m.d_rope),
                        head_dim=2)
    q_nope, q_pe = q[..., :m.d_nope], q[..., m.d_nope:]
    q_pe = apply_rope(q_pe, positions, 1.0, cfg.rope_theta, cfg.rope_scaling)
    return q_nope, q_pe


def _latents(cfg: ArchConfig, p: dict, x: jax.Array, positions: jax.Array):
    m = cfg.mla
    kv = jnp.einsum("bsd,dq->bsq", x, p["wkv_a"].astype(x.dtype))
    c_kv = _rms(kv[..., :m.kv_lora], p["kv_norm"]["scale"])
    k_pe = apply_rope(kv[..., m.kv_lora:], positions, 1.0, cfg.rope_theta,
                      cfg.rope_scaling)
    return c_kv, k_pe           # (B, S, kv_lora), (B, S, d_rope)


def softmax_scale(cfg: ArchConfig) -> float:
    """``(d_nope + d_rope) ** -0.5``, times YaRN's ``mscale ** 2``."""
    m, y = cfg.mla, cfg.rope_scaling
    scale = (m.d_nope + m.d_rope) ** -0.5
    if y is not None and y.mscale_all_dim:
        scale *= yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def _mla_core(cfg: ArchConfig, p: dict, x: jax.Array):
    from repro.kernels import ref as kref

    m = cfg.mla
    B, S, _ = x.shape
    h = cfg.n_heads
    positions = jnp.arange(S)
    q_nope, q_pe = _queries(cfg, p, x, positions)     # (B,S,h,*)
    c_kv, k_pe = _latents(cfg, p, x, positions)
    k_nope = jnp.einsum("bsq,qe->bse", c_kv, p["wk_b"].astype(x.dtype))
    k_nope = constrain_batch(k_nope.reshape(B, S, h, m.d_nope),
                             head_dim=2)
    v = jnp.einsum("bsq,qe->bse", c_kv, p["wv_b"].astype(x.dtype))
    v = constrain_batch(v.reshape(B, S, h, m.d_v), head_dim=2)

    # Fold (nope ++ rope) into one head dim so the blockwise flash path
    # applies; the shared rope key broadcasts across heads.
    scale = softmax_scale(cfg)
    qq = jnp.concatenate([q_nope, q_pe], axis=-1)     # (B,S,h,dn+dr)
    kk = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe[:, :, None, :],
                                  (B, S, h, m.d_rope))], axis=-1)
    o = kref.attention_ref(qq.transpose(0, 2, 1, 3),
                           kk.transpose(0, 2, 1, 3),
                           v.transpose(0, 2, 1, 3),
                           causal=True, scale=scale)
    o = o.transpose(0, 2, 1, 3).astype(x.dtype).reshape(B, S, h * m.d_v)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
    return out, c_kv, k_pe


def mla_forward(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    """Training: expand latents to per-head K/V."""
    return _mla_core(cfg, p, x)[0]


def mla_prefill(cfg: ArchConfig, p: dict, x: jax.Array
                ) -> tuple[jax.Array, jax.Array]:
    """Parallel prefill: (out, arena rows (B, S, latent_width)) for the
    caller to write at positions [0, S)."""
    out, c_kv, k_pe = _mla_core(cfg, p, x)
    return out, _row(cfg, c_kv, k_pe)


# --------------------------------------------------------------- decode ----

def latent_width(cfg: ArchConfig) -> int:
    """The arena's row: ``kv_lora + d_rope`` rounded up to whole 128-lane
    tiles, zeros past the key. A minor axis the lanes do not tile (576)
    makes the TPU lay the arena out sequence-minor, and every reader and
    the write then relayout a copy of it; the padded row costs no more
    memory than the tiles a 576-wide row occupies anyway."""
    m = cfg.mla
    return -(-(m.kv_lora + m.d_rope) // 128) * 128


def init_mla_cache(cfg: ArchConfig, n_layers: int, batch: int, max_seq: int,
                   dtype) -> dict:
    """The latent arena, ``(n_layers, B, S, latent_width)``."""
    return {"latent": jnp.zeros((n_layers, batch, max_seq,
                                 latent_width(cfg)), dtype)}


def _row(cfg: ArchConfig, *parts) -> jax.Array:
    """Latent, roped key and zeros to the arena's width, on the last axis."""
    row = jnp.concatenate(parts, axis=-1)
    pad = latent_width(cfg) - row.shape[-1]
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, pad)])


def mla_decode(cfg: ArchConfig, p: dict, x: jax.Array, arena: jax.Array,
               layer, pos: jax.Array, start: jax.Array | None = None
               ) -> tuple[jax.Array, jax.Array]:
    """Absorbed one-token decode of layer ``layer``. x: (B, 1, D); arena:
    the whole latent arena (L, B, S, latent_width), read where it lies;
    ``start`` (B,) scopes slot b to positions [start[b], pos]. Returns
    (out, this step's arena row (B, latent_width)) for the caller to
    write at ``pos`` (:func:`write_latents`)."""
    m = cfg.mla
    B = x.shape[0]
    h = cfg.n_heads
    positions = jnp.full((1,), pos)
    q_nope, q_pe = _queries(cfg, p, x, positions)   # (B,1,h,*)
    c_kv, k_pe = _latents(cfg, p, x, positions)     # (B,1,kv_lora/d_rope)
    cd = arena.dtype
    new = _row(cfg, c_kv, k_pe)[:, 0].astype(cd)

    # absorb W^UK into the query: q_c (B, h, kv_lora)
    wk_b = p["wk_b"].astype(cd).reshape(m.kv_lora, h, m.d_nope)
    q_c = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(cd), wk_b,
                     preferred_element_type=jnp.float32)
    o_lat = ops.mla_decode_attention(q_c, q_pe[:, 0], arena, new, layer, pos,
                                     scale=softmax_scale(cfg), start=start)
    # attended in latent space (B, h, kv_lora); expand through W^UV
    wv_b = p["wv_b"].astype(cd).reshape(m.kv_lora, h, m.d_v)
    o = jnp.einsum("bhl,lhd->bhd", o_lat.astype(cd), wv_b,
                   preferred_element_type=jnp.float32)
    o = o.astype(x.dtype).reshape(B, 1, h * m.d_v)
    out = jnp.einsum("bse,ed->bsd", o, p["wo"].astype(x.dtype))
    return out, new


def write_latents(arena: jax.Array, rows: jax.Array, pos) -> jax.Array:
    """Write every layer's arena row, rows (L, B, latent_width), at
    ``pos``. Under a jit that donates the arena this is the step's one
    in-place write."""
    return lax.dynamic_update_slice(
        arena, rows[:, :, None, :].astype(arena.dtype),
        (0, 0, jnp.asarray(pos, jnp.int32), 0))
