"""Plain float32 reference of a StableLM-2 style decoder, independent of the
program's ``models/`` package.

Pre-LayerNorm blocks (eps from the config), multi-head attention with
partial rotary embeddings on the first quarter of each head (pairs
interleaved, see the config's ``assumed.rotary_layout``), a SiLU-gated
MLP and an untied output head. Every matrix product runs at
``Precision.HIGHEST``, so float32 is float32 on a TPU. q, k and v take
their biases where the configuration has them. The forward runs
layer by layer, each layer's weights made again from the seed and cast
up to float32 in turn, so it fits beside nothing but its activations.

``fp8`` switches every matrix product's operands to float8 (e4m3) with
one scale per tensor: the control one precision below the served
bfloat16, which the comparison has to fail.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import weights

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0
#: rows of hidden states per call of the final LayerNorm and head
HEAD_BLOCK = 512


def _q(a, fp8: bool):
    if not fp8:
        return a
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec: str, a, b, fp8: bool):
    return jnp.einsum(spec, _q(a, fp8), _q(b, fp8), precision=HI)


def _ln(x, scale, bias, eps):
    mean = x.mean(-1, keepdims=True)
    var = ((x - mean) ** 2).mean(-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _rope(x, rot_dims: int, theta: float):
    """x: (S, H, Dh); rotates pairs (2i, 2i+1) of the first ``rot_dims``."""
    S = x.shape[0]
    inv = 1.0 / theta ** (np.arange(0, rot_dims, 2, dtype=np.float32)
                          / rot_dims)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    r = x[..., :rot_dims]
    a, b = r[..., 0::2], r[..., 1::2]
    ra, rb = a * cos - b * sin, b * cos + a * sin
    rot = jnp.stack([ra, rb], axis=-1).reshape(r.shape)
    return jnp.concatenate([rot, x[..., rot_dims:]], axis=-1)


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _layer_fwd(x, w, cfg_key, fp8):
    """x: (B, S, d) float32 -> (B, S, d), causal over S."""
    cfg = dict(cfg_key)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    H = cfg["num_attention_heads"]
    Hkv = cfg["num_key_value_heads"]
    d = cfg["hidden_size"]
    Dh = d // H
    rot = int(Dh * cfg["partial_rotary_factor"])
    rot -= rot % 2
    eps = cfg["layer_norm_eps"]

    def proj(h, name):
        y = _mm("sd,de->se", h, w[f"w{name}"], fp8)
        return y + w[f"b{name}"] if cfg["use_qkv_bias"] else y

    def attend(xs):                       # one sequence: (S, d)
        S = xs.shape[0]
        h = _ln(xs, w["ln1_scale"], w["ln1_bias"], eps)
        q = proj(h, "q").reshape(S, H, Dh)
        k = proj(h, "k").reshape(S, Hkv, Dh)
        v = proj(h, "v").reshape(S, Hkv, Dh)
        q, k = _rope(q, rot, cfg["rope_theta"]), _rope(k, rot, cfg["rope_theta"])
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        s = _mm("qhd,khd->hqk", q, k, fp8) / np.sqrt(Dh)
        causal = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = _mm("hqk,khd->qhd", p, v, fp8).reshape(S, H * Dh)
        return xs + _mm("se,ed->sd", o, w["wo"], fp8)

    x = jax.lax.map(attend, x)
    h = _ln(x, w["ln2_scale"], w["ln2_bias"], eps)
    g = _mm("bsd,df->bsf", h, w["w_gate"], fp8)
    u = _mm("bsd,df->bsf", h, w["w_up"], fp8)
    return x + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, w["w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_block(h, tok, scale, bias, head, eps, fp8):
    """Final LayerNorm and head on (M, d) rows; returns each row's best
    logit, the logit of ``tok`` and the argmax."""
    h = _ln(h, scale, bias, eps)
    logits = _mm("md,dv->mv", h, head.astype(jnp.float32), fp8)
    best = logits.max(-1)
    picked = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return best, picked, jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits_at(cfg: dict, root, tokens: np.ndarray, rows: np.ndarray,
              targets: np.ndarray, fp8: bool = False):
    """Forward ``tokens`` (B, S) and read the logits at ``rows`` (M, 2)
    pairs of (sequence, position). Returns numpy arrays of the best logit,
    the logit of ``targets`` (M,) and the argmax, one per row."""
    key = weights.frozen(cfg) + tuple(
        (k, cfg[k]) for k in ("partial_rotary_factor", "rope_theta",
                              "layer_norm_eps"))
    g = weights.globals_(root, cfg)
    x = _embed(g["embed"], jnp.asarray(tokens))
    del g["embed"]
    for l in range(cfg["num_hidden_layers"]):
        x = _layer_fwd(x, weights.layer(root, l, cfg), key, fp8)
    h = x[rows[:, 0], rows[:, 1]]
    del x
    M = len(rows)
    block = HEAD_BLOCK
    pad = -M % block
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tg = jnp.asarray(np.pad(targets, (0, pad)).astype(np.int32))
    out = [[], [], []]
    for i in range(0, M + pad, block):
        res = _head_block(h[i:i + block], tg[i:i + block], g["final_scale"],
                          g["final_bias"], g["head"],
                          cfg["layer_norm_eps"], fp8)
        for o, r in zip(out, res):
            o.append(np.asarray(r))
    return tuple(np.concatenate(o)[:M] for o in out)


def served_rows(requests: list[tuple[np.ndarray, list[int]]], pad_to: int):
    """Token batch and the (sequence, position) rows at which each served
    token was produced: the prompt, then every served token but the last,
    padded at the end (causal attention leaves earlier rows alone)."""
    tokens = np.zeros((len(requests), pad_to), np.int32)
    rows, targets = [], []
    for b, (prompt, served) in enumerate(requests):
        seq = np.concatenate([np.asarray(prompt, np.int32),
                              np.asarray(served[:-1], np.int32)])
        if len(seq) > pad_to:
            raise ValueError(f"sequence of {len(seq)} > {pad_to}")
        tokens[b, :len(seq)] = seq
        P = len(prompt)
        for i, t in enumerate(served):
            rows.append((b, P - 1 + i))
            targets.append(t)
    return tokens, np.asarray(rows, np.int32), np.asarray(targets, np.int32)


def widest_gap(cfg: dict, root, requests, pad_to: int) -> tuple[float, int]:
    """The widest gap, over every served token, by which its reference
    logit lies below the reference's best logit at that position; and the
    number of served tokens compared."""
    tokens, rows, targets = served_rows(requests, pad_to)
    best, picked, _ = logits_at(cfg, root, tokens, rows, targets)
    return float(np.max(best - picked)), len(targets)


def control_gap(cfg: dict, root, requests, pad_to: int) -> tuple[float, int]:
    """The control: at the same positions, the token the fp8 forward puts
    first, and the widest gap of its reference logit below the best."""
    tokens, rows, targets = served_rows(requests, pad_to)
    _, _, first = logits_at(cfg, root, tokens, rows, targets, fp8=True)
    best, picked, _ = logits_at(cfg, root, tokens, rows, first)
    return float(np.max(best - picked)), len(targets)
