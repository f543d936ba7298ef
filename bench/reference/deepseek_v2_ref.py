"""Plain float32 reference of a DeepSeek-V2 decoder, independent of the
program's ``models/`` package.

Pre-RMSNorm blocks (eps from the config). Latent attention in its
expanded form, as published: queries through the q LoRA and its RMSNorm,
a normalised kv latent expanded to per-head nope keys and values, and a
roped key shared by the heads; YaRN rope frequencies and cos/sin factor
(``rope_scaling``), pairs interleaved (see the config's
``assumed.rotary_layout``), and the softmax scale
``(nope + rope) ** -0.5 * mscale(mscale_all_dim) ** 2``. The first
``first_k_dense_replace`` layers have a SiLU-gated MLP; the others route
each token over all published experts (float32 softmax scores,
``group_limited_greedy``: the ``topk_group`` groups with the best single
score, then the top ``num_experts_per_tok`` among theirs; gates the
scores times ``routed_scaling_factor``, not renormalised), add the
routed part of the experts this chip holds, weighted by their gates, and
the shared experts. An untied output head. Every matrix product runs at
``Precision.HIGHEST``, so float32 is float32 on a TPU.

Routing is discrete: where two experts (or two groups) score within
rounding of each other, a bfloat16 program may pick either. So the
comparison is teacher-forced on the served experts as it is on the
served tokens: the forward takes, per (token, layer), the experts the
program picked (``forced``), weighted by the reference's own gates, and
reads how far they lie from the reference's own choice
(:func:`route_gap`).

The forward runs layer by layer, each layer's weights made again from the
seed and cast up to float32 in turn, one sequence's attention at a time
and blocked over query rows, so that several sequences of thousands of
tokens fit beside nothing but their activations. ``fp8`` switches every
matrix product's operands to float8 (e4m3) with one scale per tensor:
the control one precision below the served bfloat16, which the
comparison has to fail.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dsv2_weights as weights
from bench.reference.stablelm_ref import HEAD_BLOCK, HI, _mm, _q, served_rows

#: query rows per block of one sequence's attention
QUERY_BLOCK = 128


def _rms(x, scale, eps):
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * scale


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_tables(cfg: dict, length: int) -> tuple[np.ndarray, np.ndarray]:
    """cos and sin, (length, rope_dim / 2), of YaRN's frequencies as
    DeepseekV2YarnRotaryEmbedding computes them, in float64."""
    y, dim, base = cfg["rope_scaling"], cfg["qk_rope_head_dim"], \
        float(cfg["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = extra / y["factor"]

    def corr(turns):
        return (dim * math.log(y["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    m = (yarn_mscale(y["factor"], y["mscale"])
         / yarn_mscale(y["factor"], y["mscale_all_dim"]))
    ang = np.arange(length)[:, None] * inv[None, :]
    return (np.cos(ang) * m).astype(np.float32), \
        (np.sin(ang) * m).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    y = cfg["rope_scaling"]
    m = yarn_mscale(y["factor"], y["mscale_all_dim"])
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _rope(x, cos, sin):
    """x: (S, ..., dr); rotates pairs (2i, 2i+1)."""
    extra = (slice(None),) + (None,) * (x.ndim - 2)
    c, s = cos[extra], sin[extra]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * c - b * s, b * c + a * s], axis=-1).reshape(x.shape)


def _key(cfg: dict) -> tuple:
    y = cfg["rope_scaling"]
    return weights.frozen(cfg) + tuple(
        (k, cfg[k]) for k in ("rms_norm_eps", "num_experts_per_tok", "n_group",
                              "topk_group", "routed_scaling_factor")) + (
        ("scale", softmax_scale(cfg)), ("factor", y["factor"]))


@functools.partial(jax.jit, static_argnames=("cfg_key", "fp8"))
def _attention(x, w, cos, sin, cfg_key, fp8):
    """x: (B, S, d) float32 -> x plus latent attention, causal over S."""
    cfg = weights._thaw(cfg_key)
    c = dict(cfg_key)
    s = weights.sizes(cfg)
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    H, dn, dr, dv = s["H"], s["dn"], s["dr"], s["dv"]
    eps = c["rms_norm_eps"]

    def one(xs):                                  # one sequence: (S, d)
        S = xs.shape[0]
        h = _rms(xs, w["ln1_scale"], eps)
        cq = _rms(_mm("sd,dq->sq", h, w["wq_a"], fp8), w["q_norm"], eps)
        q = _mm("sq,qe->se", cq, w["wq_b"], fp8).reshape(S, H, dn + dr)
        kv = _mm("sd,de->se", h, w["wkv_a"], fp8)
        lat = _rms(kv[:, :s["kv_lora"]], w["kv_norm"], eps)
        k_pe = _rope(kv[:, s["kv_lora"]:], cos, sin)           # (S, dr)
        k_nope = _mm("sl,le->se", lat, w["wk_b"], fp8).reshape(S, H, dn)
        v = _mm("sl,le->se", lat, w["wv_b"], fp8).reshape(S, H, dv)
        q_nope, q_pe = q[..., :dn], _rope(q[..., dn:], cos, sin)
        # the control quantises each operand once, as a whole tensor
        q_nope, q_pe, k_nope, k_pe, v = (_q(a, fp8) for a in
                                         (q_nope, q_pe, k_nope, k_pe, v))
        nb = S // QUERY_BLOCK

        def block(i):
            rows = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
            qn = jax.lax.dynamic_slice_in_dim(q_nope, i * QUERY_BLOCK,
                                              QUERY_BLOCK)
            qp = jax.lax.dynamic_slice_in_dim(q_pe, i * QUERY_BLOCK,
                                              QUERY_BLOCK)
            sc = (jnp.einsum("qhd,khd->hqk", qn, k_nope, precision=HI)
                  + jnp.einsum("qhd,kd->hqk", qp, k_pe, precision=HI)) \
                * c["scale"]
            sc = jnp.where(rows[None, :, None] >= jnp.arange(S)[None, None],
                           sc, -jnp.inf)
            p = _q(jax.nn.softmax(sc, axis=-1), fp8)
            return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)

        o = jax.lax.map(block, jnp.arange(nb)).reshape(S, H * dv)
        return xs + _mm("se,ed->sd", o, w["wo"], fp8)

    return jax.lax.map(one, x)


def route(cfg: dict, logits):
    """(gates, experts) of each token, (N, top_k): group-limited greedy."""
    E, G = logits.shape[-1], cfg["n_group"]
    scores = jax.nn.softmax(logits, axis=-1)
    groups = scores.reshape(-1, G, E // G)
    _, best = jax.lax.top_k(groups.max(-1), cfg["topk_group"])
    kept = jnp.zeros((scores.shape[0], G), bool).at[
        jnp.arange(scores.shape[0])[:, None], best].set(True)
    masked = jnp.where(kept[:, :, None], groups, 0.0).reshape(scores.shape)
    gate, sel = jax.lax.top_k(masked, cfg["num_experts_per_tok"])
    return gate * cfg["routed_scaling_factor"], sel


def route_gap(cfg: dict, logits, sel):
    """How far, in router logits, the experts ``sel`` (N, top_k) of each
    token lie from being a group-limited greedy choice under the
    reference's logits: the wider of

    * the reference's ``topk_group``-th best group score above the lowest
      of ``sel``'s groups (each group scored by its best expert), and
    * the widest inversion within the groups ``sel`` kept (its own,
      filled up to ``topk_group`` with the reference's best others): the
      best expert there outside ``sel`` above the lowest of ``sel``.

    0 exactly where ``sel`` is such a choice (the reference's own, or
    another after a tie); inf for a repeated or unknown expert. Softmax is
    monotone, so a logit gap is a log-score gap."""
    N, E = logits.shape
    G = cfg["n_group"]
    size = E // G
    rows = jnp.arange(N)[:, None]
    gmax = logits.reshape(N, G, size).max(-1)                  # (N, G)
    top_g, _ = jax.lax.top_k(gmax, cfg["topk_group"])
    known = jnp.clip(sel, 0, E - 1)
    lowest = jnp.take_along_axis(logits, known, -1).min(-1)
    lowest_g = jnp.take_along_axis(gmax, known // size, -1).min(-1)
    own_g = jnp.zeros((N, G), bool).at[rows, known // size].set(True)
    order = jnp.argsort(-gmax, axis=-1)
    others = ~jnp.take_along_axis(own_g, order, -1)
    need = cfg["topk_group"] - own_g.sum(-1, keepdims=True)
    fill = others & (jnp.cumsum(others, -1) <= need)
    kept = own_g | jnp.zeros((N, G), bool).at[rows, order].set(fill)
    outside = jnp.repeat(kept, size, axis=-1) \
        & ~jnp.zeros((N, E), bool).at[rows, known].set(True)
    best_out = jnp.where(outside, logits, -jnp.inf).max(-1)
    gap = jnp.maximum(jnp.maximum(best_out - lowest, top_g[:, -1] - lowest_g),
                      0.0)
    s = jnp.sort(sel, -1)
    bad = (jnp.any(s[:, 1:] == s[:, :-1], -1)
           | jnp.any((sel < 0) | (sel >= E), -1))
    return jnp.where(bad, jnp.inf, gap)


def _mlp(h, gate, up, down, fp8):
    return _mm("nf,fd->nd", jax.nn.silu(_mm("nd,df->nf", h, gate, fp8))
               * _mm("nd,df->nf", h, up, fp8), down, fp8)


@functools.partial(jax.jit, static_argnames=("cfg_key", "moe", "fp8"))
def _ffn(x, w, forced, cfg_key, moe, fp8):
    """x: (N, d) float32 -> (x plus the layer's FFN, then, with experts,
    the experts the reference picks (N, k), the experts used (N, k) and
    their route gaps (N,)). Rows of ``forced`` (N, k) that are not -1
    give the experts used there."""
    cfg = dict(cfg_key)
    s = weights.sizes(weights._thaw(cfg_key))
    h = _rms(x, w["ln2_scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    f32 = {k: v.astype(jnp.float32) for k, v in w.items()
           if not k.startswith("expert_")}
    if not moe:
        return x + _mlp(h, f32["w_gate"], f32["w_up"], f32["w_down"],
                        fp8), None
    logits = _mm("nd,de->ne", h, f32["router"], fp8)
    _, own = route(cfg, logits)
    sel = jnp.where(forced[:, :1] >= 0, forced, own)
    gate = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               jnp.clip(sel, 0, logits.shape[-1] - 1), -1) \
        * cfg["routed_scaling_factor"]
    out = _mlp(h, f32["shared_gate"], f32["shared_up"], f32["shared_down"],
               fp8)

    def expert(acc, ew):
        e, g, u, d = ew
        weight = jnp.sum(jnp.where(sel == e, gate, 0.0), axis=-1)  # (N,)
        y = _mlp(h, g.astype(jnp.float32), u.astype(jnp.float32),
                 d.astype(jnp.float32), fp8)
        return acc + weight[:, None] * y, None

    held = s["first"] + jnp.arange(s["held"])
    out, _ = jax.lax.scan(expert, out, (held, w["expert_gate"],
                                        w["expert_up"], w["expert_down"]))
    return x + out, (own, sel, route_gap(cfg, logits, sel))


@functools.partial(jax.jit, static_argnames=("eps", "fp8"))
def _head_block(h, tok, scale, head, eps, fp8):
    """Final RMSNorm and head on (M, d) rows; returns each row's best
    logit, the logit of ``tok`` and the argmax."""
    h = _rms(h, scale, eps)
    logits = _mm("md,dv->mv", h, head.astype(jnp.float32), fp8)
    best = logits.max(-1)
    picked = jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return best, picked, jnp.argmax(logits, -1).astype(jnp.int32)


@jax.jit
def _embed(table, tokens):
    return table[tokens].astype(jnp.float32)


def logits_at(cfg: dict, root, tokens: np.ndarray, rows: np.ndarray,
              targets: np.ndarray, fp8: bool = False,
              forced: list | None = None):
    """Forward ``tokens`` (B, S), S a multiple of ``QUERY_BLOCK``, and read
    the logits at ``rows`` (M, 2) pairs of (sequence, position). ``forced``,
    one (B, S, top_k) array per expert layer, gives the experts used at
    each position (-1: the reference's own). Returns numpy arrays of the
    best logit, the logit of ``targets`` (M,) and the argmax, one per row,
    and per expert layer the experts the reference picks and those used
    (B, S, top_k) and the route gap of those used (B, S)."""
    key = _key(cfg)
    B, S = tokens.shape
    cos, sin = (jnp.asarray(t) for t in rope_tables(cfg, S))
    g = weights.globals_(root, cfg)
    x = _embed(g["embed"], jnp.asarray(tokens))
    del g["embed"]
    n_dense, K = cfg["first_k_dense_replace"], cfg["num_experts_per_tok"]
    experts = []
    for l in range(cfg["num_hidden_layers"]):
        w = weights.layer(root, l, cfg)
        x = _attention(x, {k: w[k] for k in weights.ATTN_TENSORS}, cos, sin,
                       key, fp8)
        f = forced[l - n_dense] if forced is not None and l >= n_dense \
            else np.full((B, S, K), -1, np.int32)
        x, routed = _ffn(x.reshape(B * S, -1), w,
                         jnp.asarray(f, jnp.int32).reshape(B * S, K), key,
                         l >= n_dense, fp8)
        x = x.reshape(B, S, -1)
        del w
        if routed is not None:
            experts.append(tuple(np.asarray(r).reshape(B, S, *r.shape[1:])
                                 for r in routed))
    h = x[rows[:, 0], rows[:, 1]]
    del x
    M = len(rows)
    pad = -M % HEAD_BLOCK
    h = jnp.pad(h, ((0, pad), (0, 0)))
    tg = jnp.asarray(np.pad(targets, (0, pad)).astype(np.int32))
    out = [[], [], []]
    for i in range(0, M + pad, HEAD_BLOCK):
        res = _head_block(h[i:i + HEAD_BLOCK], tg[i:i + HEAD_BLOCK],
                          g["final_scale"], g["head"], cfg["rms_norm_eps"],
                          fp8)
        for o, r in zip(out, res):
            o.append(np.asarray(r))
    return tuple(np.concatenate(o)[:M] for o in out) + (experts,)


#: shortest padded sequence: fewer lengths, so fewer shapes to compile
MIN_PADDED = 1024


def padded_length(prompt, served) -> int:
    """The length a request's sequence (its prompt and all but its last
    served token) is padded to: the next power of two, at least
    ``MIN_PADDED``, so that short ones are not padded to the longest and
    few shapes compile."""
    n = len(prompt) + len(served) - 1
    return max(MIN_PADDED, 1 << (n - 1).bit_length())


def compare(cfg: dict, root, requests, picks: list | None = None,
            control: bool = False) -> dict:
    """The reference's readings of served requests, one request at a time,
    each at its own padded length, teacher-forced on the served tokens
    and experts:

    * ``logit_gap``: the widest gap, over every served token, by which its
      reference logit lies below the reference's best at that position;
    * ``route_gap``: the widest :func:`route_gap`, over every position
      and expert layer, of the experts used there;
    * ``served_tokens`` compared, and ``routing``: how many (position,
      expert layer) choices were compared and how many differ from the
      reference's own.

    ``picks`` holds each request's served experts, (expert layers,
    positions, top_k) over its prompt and all but its last served token;
    without them the reference routes on its own. ``control`` reads the
    float8 control instead: at the same positions, the token and the
    experts the fp8 forward picks."""
    logit = route = 0.0
    n_tokens = compared = differ = 0
    for i, req in enumerate(requests):
        S = padded_length(*req)
        n = len(req[0]) + len(req[1]) - 1
        tokens, rows, targets = served_rows([req], S)
        forced = None
        if control:
            _, _, targets, ex = logits_at(cfg, root, tokens, rows, targets,
                                          fp8=True)
            forced = [np.array(used) for _, used, _ in ex]
            for f in forced:
                f[:, n:] = -1
        elif picks is not None:
            forced = [np.pad(p[:n][None], ((0, 0), (0, S - n), (0, 0)),
                             constant_values=-1) for p in picks[i]]
        best, picked, _, ex = logits_at(cfg, root, tokens, rows, targets,
                                        forced=forced)
        logit = max(logit, float(np.max(best - picked)))
        n_tokens += len(targets)
        for own, used, gap in ex:
            route = max(route, float(np.max(gap[0, :n])))
            compared += n
            differ += int(np.any(np.sort(own[0, :n], -1)
                                 != np.sort(used[0, :n], -1), -1).sum())
    return {"logit_gap": logit, "route_gap": route,
            "served_tokens": n_tokens,
            "routing": {"compared": compared, "differ": differ}}
