"""Shared layer primitives: init, norms, rotary embeddings, MLPs, losses.

Parameters are plain nested dicts of jnp arrays; every init function takes a
PRNG key and returns the dict. Layer-stacked parameters carry a leading
``(L, ...)`` axis and are consumed by ``lax.scan`` in transformer.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from .config import ArchConfig


def dt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


def cdt(cfg: ArchConfig):
    return jnp.dtype(cfg.compute_dtype)


# ------------------------------------------------------------ sharding ----

def _active_mesh():
    try:
        from jax._src import mesh as mesh_lib
        m = mesh_lib.thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:  # pragma: no cover
        return None


def constrain_batch(x: jax.Array, head_dim: int | None = None) -> jax.Array:
    """Pin the leading (batch) dim to the data axes of the active mesh,
    keeping the head axis model-sharded where it divides evenly.

    Head-split reshapes like (B, S, H*Dh) -> (B, S, H, Dh) lose their
    sharding when H*Dh's model-sharding does not align to head boundaries
    (e.g. hymba's 25x64 heads over 16 shards); XLA then silently
    *replicates* the tensor — 16x redundant attention compute/memory.
    Anchoring the batch dim here keeps activations batch-sharded through
    every mixer; for aligned head counts (codeqwen 32, deepseek-v2 128)
    ``head_dim`` keeps tensor parallelism on the heads instead of forcing
    an all-gather. No-op outside a mesh context (tests, single host)."""
    mesh = _active_mesh()
    if mesh is None or x.ndim == 0:
        return x
    from jax.sharding import PartitionSpec as P
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    if not dp:
        return x
    size = 1
    for a in dp:
        size *= mesh.shape[a]
    if size == 1 or x.shape[0] % size:
        return x
    entries: list = [dp] + [None] * (x.ndim - 1)
    if head_dim is not None and "model" in mesh.axis_names \
            and x.shape[head_dim] % mesh.shape["model"] == 0:
        entries[head_dim] = "model"
    return lax.with_sharding_constraint(x, P(*entries))


# ---------------------------------------------------------------- init ----

def dense_init(key, shape, dtype, in_axis: int = -2) -> jax.Array:
    """Variance-scaling (fan-in) normal init; works for stacked (L, ...)
    weights by measuring fan-in on ``in_axis``."""
    fan_in = shape[in_axis]
    std = fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def zeros(shape, dtype) -> jax.Array:
    return jnp.zeros(shape, dtype)


def ones(shape, dtype) -> jax.Array:
    return jnp.ones(shape, dtype)


class KeyGen:
    """Split-on-demand PRNG key stream."""

    def __init__(self, key):
        self._key = key

    def __call__(self):
        self._key, sub = jax.random.split(self._key)
        return sub


# ---------------------------------------------------------------- norms ----

def init_norm(cfg: ArchConfig, d: int) -> dict:
    p = {"scale": ones((d,), jnp.float32)}
    if cfg.norm == "ln":
        p["bias"] = zeros((d,), jnp.float32)
    return p


def apply_norm(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    x32 = x.astype(jnp.float32)
    if cfg.norm == "rms":
        var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
        y = x32 * lax.rsqrt(var + 1e-6) * p["scale"]
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * lax.rsqrt(var + 1e-5) * p["scale"] + p["bias"]
    return y.astype(x.dtype)


# --------------------------------------------------------------- rotary ----

def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_ramp(d_rot: int, theta: float, y) -> tuple[int, int]:
    """The YaRN ramp's (low, high) rope-pair indices: pairs below ``low``
    turn more than ``beta_fast`` times over ``original_max_seq`` and keep
    their frequency, pairs above ``high`` fewer than ``beta_slow`` times
    and are interpolated."""
    def corr(turns):
        return (d_rot * math.log(y.original_max_seq / (turns * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(corr(y.beta_fast)), 0),
            min(math.ceil(corr(y.beta_slow)), d_rot - 1))


def rope_freqs(d_rot: int, theta: float, scaling=None) -> jax.Array:
    """Inverse frequencies of the ``d_rot / 2`` rope pairs; with a
    :class:`~repro.models.config.YarnCfg`, YaRN's blend of the original
    and the ``/ factor`` interpolated ones (DeepseekV2YarnRotaryEmbedding)."""
    extra = 1.0 / (theta ** (jnp.arange(0, d_rot, 2, dtype=jnp.float32)
                             / d_rot))
    if scaling is None:
        return extra
    low, high = yarn_ramp(d_rot, theta, scaling)
    span = (high - low) if high != low else 0.001
    ramp = jnp.clip((jnp.arange(d_rot // 2, dtype=jnp.float32) - low)
                    / span, 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def apply_rope(x: jax.Array, positions: jax.Array, rope_frac: float,
               theta: float, scaling=None) -> jax.Array:
    """x: (..., S, H, D) or (..., S, D); positions: (..., S) or (S,).
    Rotates the first ``rope_frac * D`` dims (partial rotary, stablelm),
    pairs interleaved; ``scaling`` is an optional YaRN description."""
    d = x.shape[-1]
    d_rot = int(d * rope_frac)
    d_rot -= d_rot % 2
    if d_rot == 0:
        return x
    rot, keep = x[..., :d_rot], x[..., d_rot:]
    freqs = rope_freqs(d_rot, theta, scaling)              # (d_rot/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs
    if x.ndim - positions.ndim == 3:                        # head axis present
        angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    r1, r2 = rot[..., ::2], rot[..., 1::2]
    o1 = r1 * cos - r2 * sin
    o2 = r2 * cos + r1 * sin
    rotated = jnp.stack([o1, o2], axis=-1).reshape(rot.shape)
    return jnp.concatenate([rotated.astype(x.dtype), keep], axis=-1)


# ------------------------------------------------------------------ MLP ----

def init_mlp(keys: KeyGen, cfg: ArchConfig, d_in: int, d_ff: int,
             stack: tuple[int, ...] = ()) -> dict:
    dtype = dt(cfg)
    p = {"w_in": dense_init(keys(), (*stack, d_in, d_ff), dtype),
         "w_out": dense_init(keys(), (*stack, d_ff, d_in), dtype)}
    if cfg.gated_mlp:
        p["w_gate"] = dense_init(keys(), (*stack, d_in, d_ff), dtype)
    return p


def _act(cfg: ArchConfig, x):
    return jax.nn.silu(x) if cfg.act == "silu" else jax.nn.gelu(x)


def apply_mlp(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    h = jnp.einsum("...d,df->...f", x, p["w_in"].astype(x.dtype))
    if cfg.gated_mlp:
        g = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
        h = _act(cfg, g) * h
    else:
        h = _act(cfg, h)
    return jnp.einsum("...f,fd->...d", h, p["w_out"].astype(x.dtype))


# ----------------------------------------------------------------- loss ----

def softcap(x: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return x
    return cap * jnp.tanh(x / cap)


def chunked_ce_loss(x: jax.Array, head: jax.Array, labels: jax.Array,
                    mask: jax.Array | None = None,
                    final_softcap: float | None = None,
                    chunk: int = 512,
                    valid_vocab: int | None = None
                    ) -> tuple[jax.Array, jax.Array]:
    """Cross entropy over seq chunks so (B, S, V) never materializes.

    x: (B, S, D) final hidden states; head: (D, V); labels: (B, S).
    ``valid_vocab``: real vocab size — columns beyond it (padding for clean
    TP sharding) are excluded from the logsumexp.
    Returns (sum_nll, sum_weight); caller divides.
    """
    B, S, D = x.shape
    V = head.shape[1]
    chunk = min(chunk, S)
    while S % chunk:
        chunk //= 2
    n_chunks = S // chunk
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    vocab_ok = None
    if valid_vocab is not None and valid_vocab < V:
        vocab_ok = (jnp.arange(V) < valid_vocab)

    xs = (x.reshape(B, n_chunks, chunk, D).swapaxes(0, 1),
          labels.reshape(B, n_chunks, chunk).swapaxes(0, 1),
          mask.reshape(B, n_chunks, chunk).swapaxes(0, 1))

    @jax.checkpoint
    def body(carry, inp):
        # remat: the (B, chunk, V) logits must not be saved for backward —
        # they dominate training memory otherwise.
        nll_sum, w_sum = carry
        xc, yc, mc = inp
        logits = jnp.einsum("bsd,dv->bsv", xc, head.astype(xc.dtype))
        logits = softcap(logits.astype(jnp.float32), final_softcap)
        if vocab_ok is not None:
            logits = jnp.where(vocab_ok, logits, -1e30)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yc[..., None], axis=-1)[..., 0]
        nll = (logz - gold) * mc
        return (nll_sum + nll.sum(), w_sum + mc.sum()), None

    (nll_sum, w_sum), _ = lax.scan(body, (jnp.zeros((), jnp.float32),
                                          jnp.zeros((), jnp.float32)), xs)
    return nll_sum, w_sum
